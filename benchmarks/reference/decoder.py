"""Plain reference: a pre-norm GQA decoder with rotary positions and a
SwiGLU feed-forward (Mistral-7B / InternLM2 / Llama block), in
straightforward ``jax.numpy``, float32, matmuls at ``highest``.

It imports nothing of the program and takes nothing the program made:
weights come one layer at a time from ``benchmarks.weights`` (the
benchmark's own seeded generator), so a 7B model never exists whole.

``Precision`` models what a configuration STATES, never what a program
happens to do:

* ``act_bits``  — per-token symmetric dynamic quantisation of the input
  of every base matmul (absmax over the contracted dims, as w8a8 is
  defined); ``None`` = none.
* ``kv_bits``   — per (token, kv head) row quantisation of K (after the
  rotation) and V, as an int8 KV cache stores them; ``None`` = none.
* ``weight_bits`` — re-quantise the weights per output channel; ``None``
  = use them as given (int8 data dequantised, or the bf16 values).

Departures from the programs, noted once: the reference quantises K/V
of EVERY position (a serve program attends within a fresh prefill wave
before the rows are stored); it has no cache, no batching, no bursts and
no speculation; attention is the full masked softmax.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights as W

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Precision:
    act_bits: Optional[int] = None
    kv_bits: Optional[int] = None
    weight_bits: Optional[int] = None

    def below(self, stated: "Precision") -> bool:
        """Whether this is strictly lower than ``stated`` somewhere."""
        def lvl(b):
            return 99 if b is None else b
        return any(lvl(a) < lvl(b) for a, b in zip(
            dataclasses.astuple(self), dataclasses.astuple(stated)))


def stated_precision(config: dict) -> Precision:
    """The reference's model of a configuration's stated precision."""
    p = config["precision"]
    bits = {"int8": 8, "int4": 4, "bf16": None, "f32": None}
    return Precision(act_bits=bits[p["activations"]],
                     kv_bits=bits[p.get("kv", "bf16")],
                     weight_bits=None)


def control_precision(config: dict, mild: bool = False) -> Precision:
    """The nearest precision below the stated one, the step that would
    tempt a later PR: int4 where int8 is stated, int8 where bf16 is —
    for the weights, the activations and the cached rows alike.

    ``mild``: lower only what is COMPUTED (activations and cached rows),
    leaving int8 weight data as given — the mildest lowering. A builder
    reads both; the limits are set against the first (the contract's
    control), and PERF.md says what the second shows."""
    p = config["precision"]
    down = {"int8": 4, "bf16": 8, "f32": 8}
    weight_bits = down[p["weights"]]
    if mild and p["weights"] == "int8":
        weight_bits = None
    return Precision(act_bits=down[p["activations"]],
                     kv_bits=down[p.get("kv", "bf16")],
                     weight_bits=weight_bits)


def fake_quant(x, n_tail: int, bits: Optional[int]):
    """Symmetric quantise-dequantise, absmax over the trailing
    ``n_tail`` dims."""
    if bits is None:
        return x
    qmax = float(2 ** (bits - 1) - 1)
    axes = tuple(range(x.ndim - n_tail, x.ndim))
    absmax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale
    # Straight-through: the value is the quantised one, the gradient
    # passes as if it were not (a rounded path has none of its own).
    return x + jax.lax.stop_gradient(q - x)


def _requant_weight(w, n_contract: int, bits: Optional[int]):
    """Per-output-channel re-quantisation (absmax over contracted dims)."""
    if bits is None:
        return w
    qmax = float(2 ** (bits - 1) - 1)
    axes = tuple(range(n_contract))
    absmax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def dense(t, n_contract: int, prec: Precision):
    """A generated tensor as a float32 matrix."""
    if isinstance(t, dict):
        s = t["s"][(None,) * n_contract + (...,)]
        w = t["w"].astype(jnp.float32) * s
    else:
        w = t.astype(jnp.float32)
    return _requant_weight(w, n_contract, prec.weight_bits)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta: float):
    """x: [B, S, H, hd]; rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv       # [S, hd/2]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _stored(kind: str):
    """(weight kind of ``benchmarks.weights``, storage dtype of the
    float leaves). ``int8``: int8 matrices beside a bf16 embedding and
    float32 norms; ``float``: every leaf bf16; ``float32``: every leaf
    float32 (a tree that is trained)."""
    return {"int8": ("int8", jnp.bfloat16, jnp.float32),
            "float": ("float", jnp.bfloat16, jnp.bfloat16),
            "float32": ("float", jnp.float32, jnp.float32)}[kind]


def layer_weights(key, cfg, layer, kind: str, prec: Precision):
    """One layer's float32 matrices, from the seed."""
    wkind, dtype, norm_dtype = _stored(kind)
    shapes = W.block_shapes(cfg)
    out = {name: dense(W.block_tensor(key, cfg, name, layer, wkind, dtype),
                       shapes[name][1], prec) for name in shapes}
    for ln in ("ln1", "ln2"):
        out[ln] = W.norm_scale(key, ln, layer, cfg.d_model).astype(
            norm_dtype).astype(jnp.float32)
    return out


def decoder_layer(x, w, cfg, prec: Precision, lora=None,
                  lora_scale: float = 0.0):
    """x: [B, S, D] float32 -> [B, S, D]."""
    B, S, _ = x.shape
    positions = jnp.arange(S)

    def mm(eq, a, name, n_tail):
        return jnp.einsum(eq, fake_quant(a, n_tail, prec.act_bits),
                          w[name], precision=_HI)

    def delta_in(h, ab):
        u = jnp.einsum("bsd,dr->bsr", h, ab["a"], precision=_HI)
        return lora_scale * jnp.einsum("bsr,rhk->bshk", u, ab["b"],
                                       precision=_HI)

    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    q = mm("bsd,dhk->bshk", h, "wq", 1)
    k = mm("bsd,dhk->bshk", h, "wk", 1)
    v = mm("bsd,dhk->bshk", h, "wv", 1)
    if lora is not None:
        q = q + delta_in(h, lora["wq"])
        k = k + delta_in(h, lora["wk"])
        v = v + delta_in(h, lora["wv"])
    q = rope(q, positions, cfg.rope_theta)
    k = fake_quant(rope(k, positions, cfg.rope_theta), 1, prec.kv_bits)
    v = fake_quant(v, 1, prec.kv_bits)
    rep = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k, precision=_HI)
    s = s * (cfg.head_dim ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqt,bthk->bqhk", p, v, precision=_HI)
    y = mm("bshk,hkd->bsd", o, "wo", 2)
    if lora is not None:
        u = jnp.einsum("bshk,hkr->bsr", o, lora["wo"]["a"], precision=_HI)
        y = y + lora_scale * jnp.einsum("bsr,rd->bsd", u,
                                        lora["wo"]["b"], precision=_HI)
    x = x + y
    h = rms_norm(x, w["ln2"], cfg.norm_eps)
    g = mm("bsd,df->bsf", h, "w_gate", 1)
    u = mm("bsd,df->bsf", h, "w_up", 1)
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, "w_down", 1)


def embed_tokens(key, cfg, tokens, kind: str):
    return W.embedding(key, cfg, _stored(kind)[1]).astype(
        jnp.float32)[tokens]


def final_logits(key, cfg, x, kind: str, prec: Precision):
    """x: [..., D] final-layer output rows -> [..., vocab] logits."""
    wkind, dtype, norm_dtype = _stored(kind)
    fn = W.norm_scale(key, "final_norm", 0, cfg.d_model).astype(
        norm_dtype).astype(jnp.float32)
    h = rms_norm(x, fn, cfg.norm_eps)
    hw = dense(W.head(key, cfg, wkind, dtype), 1, prec)
    return jnp.einsum("...d,dv->...v", fake_quant(h, 1, prec.act_bits),
                      hw, precision=_HI)


# ---------------------------------------------------------------------------
# Drivers: layer by layer, so one layer of weights exists at a time
# ---------------------------------------------------------------------------

class Reference:
    """Jitted per-layer pieces of one (configuration, precision)."""

    def __init__(self, cfg, kind: str, prec: Precision,
                 lora_rank: int = 0, lora_scale: float = 0.0):
        self.cfg, self.kind, self.prec = cfg, kind, prec
        self.lora_rank, self.lora_scale = lora_rank, lora_scale

        def fwd(key, layer, x, lora):
            w = layer_weights(key, cfg, layer, kind, prec)
            return decoder_layer(x, w, cfg, prec, lora, lora_scale)

        def bwd(key, layer, x, lora, g):
            _, vjp = jax.vjp(lambda x_, l_: fwd(key, layer, x_, l_),
                             x, lora)
            return vjp(g)

        def tail_loss(key, x, tokens):
            logits = final_logits(key, cfg, x, kind, prec)[:, :-1]
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
            return -jnp.mean(ll)

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd)
        self._embed = jax.jit(
            lambda key, t: embed_tokens(key, cfg, t, kind))
        self._logits = jax.jit(
            lambda key, x: final_logits(key, cfg, x, kind, prec))
        self._tail = jax.jit(jax.value_and_grad(tail_loss, argnums=1))

    def hidden(self, key, tokens, lora=None, keep: bool = False):
        """tokens [B, S] -> last layer's output (and, with ``keep``,
        every layer's input, for the backward pass)."""
        x = self._embed(key, tokens)
        inputs = []
        for layer in range(self.cfg.n_layers):
            if keep:
                inputs.append(x)
            x = self._fwd(key, np.uint32(layer), x,
                          None if lora is None else _layer_of(lora, layer))
        return (x, inputs) if keep else x

    def logits_at(self, key, tokens, rows, cols):
        """Logits [n, vocab] at the (row, col) positions of ``tokens``."""
        x = self.hidden(key, tokens)
        return self._logits(key, x[rows, cols])

    def loss(self, key, tokens, lora=None):
        """Mean next-token cross-entropy over the batch."""
        return self._tail(key, self.hidden(key, tokens, lora), tokens)[0]

    def loss_and_grads(self, key, tokens, lora):
        """Mean next-token cross-entropy over the batch and its gradient
        with respect to the stacked LoRA tree."""
        x, inputs = self.hidden(key, tokens, lora, keep=True)
        loss, g = self._tail(key, x, tokens)
        grads = []
        for layer in reversed(range(self.cfg.n_layers)):
            g, gl = self._bwd(key, np.uint32(layer), inputs.pop(),
                              _layer_of(lora, layer), g)
            grads.append(gl)
        grads.reverse()
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *grads)
        return loss, stacked


def _layer_of(tree, layer: int):
    return jax.tree.map(lambda a: a[layer], tree)


# ---------------------------------------------------------------------------
# AdamW with global-norm clipping and a linear warm-up, as the training
# configuration states them (written out; no optimizer library)
# ---------------------------------------------------------------------------

def warmup_cosine(step: int, peak: float, warmup: int, total: int,
                  end_ratio: float = 0.1) -> float:
    """Learning rate of update number ``step`` (0-based)."""
    if step < warmup:
        return peak * step / warmup
    decay = max(total, warmup + 1, 1) - warmup
    frac = min(step - warmup, decay) / decay
    cos = 0.5 * (1.0 + np.cos(np.pi * frac))
    end = peak * end_ratio
    return end + (peak - end) * cos


def clip_by_global_norm(grads, max_norm: float):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree.leaves(grads)))
    factor = jnp.where(gn < max_norm, 1.0, max_norm / gn)
    return jax.tree.map(lambda g: g * factor, grads)


def adamw_update(params, grads, mu, nu, step: int, opt: Dict):
    """One update (``step`` 0-based). Returns (params, mu, nu)."""
    b1, b2 = opt["beta1"], opt["beta2"]
    lr = warmup_cosine(step, opt["learning_rate"], opt["warmup_steps"],
                       opt["total_steps"])
    t = step + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def upd(p, m, v):
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + 1e-8)
                         + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu
