"""Plain reference of the ``olmo_hybrid`` family: gated-delta-rule
linear-attention layers with a full softmax-attention layer closing each
period (Olmo-Hybrid-7B), in straightforward ``jax.numpy``, float32,
matmuls at ``highest``.

It imports nothing of the program and takes nothing the program made:
weights come one layer at a time from ``benchmarks.weights_olmo_hybrid``
(the benchmark's own seeded generator), so the 4.1 B parameters of the
cut model never exist whole in float32.

The block (``x`` the residual stream; each sub-layer's OUTPUT is normed,
then added)::

    x = x + RMSNorm(mixer(x));   x = x + RMSNorm(SwiGLU(x))

* linear mixer: ``q~, k~, v~ = W_q x, W_k x, W_v x``; a depthwise causal
  convolution of width ``K`` over time on each (zeros before the first
  token), then SiLU; per head ``q = q~ / |q~| * d_k^-1/2``, ``k = k~ /
  |k~|``; ``beta = 2 sigmoid(w_b . x)``, ``a = exp(-exp(A_log)
  softplus(w_a . x + dt_bias))``; the recurrence TOKEN BY TOKEN (a
  ``lax.scan`` over time, no chunked form) ``S_t = a_t S_{t-1} (I -
  beta_t k_t k_t^T) + beta_t v_t k_t^T``, ``o_t = S_t q_t``; ``y =
  W_o(RMSNorm_{d_v}(o) * SiLU(W_g x))``.
* full mixer: causal softmax attention, RMSNorm over the whole projected
  ``q`` and ``k``, no rotation unless the configuration gives a
  ``rope_theta``; computed in blocks of query rows, so a score tensor of
  8.7 k x 8.7 k rows never exists.
* the feed-forward runs in blocks of rows for the same reason.

Departures from the published description, each ASSUMED (the
configuration's file says so too): the L2 norm is ``x / sqrt(sum x^2 +
1e-6)``; the convolution has no bias; the block's norm placement and the
q / k norms are the OLMo 2 / 3 family's; ``rope_theta`` null means no
rotation.

``Precision`` (``benchmarks.reference.decoder``'s) models what a
configuration STATES: ``act_bits`` quantises the input of every large
matmul per token, ``kv_bits`` the K and V rows a cache would keep,
``weight_bits`` re-quantises every large matrix per output channel,
``state_bits`` 16 rounds the recurrent state to bfloat16 after every
token. The two small projections of the rule (``w_a``, ``w_b``), the
decay, the write strength and the L2 norms stay float32 in every
precision: the configuration states them so.

Departures from the programs, noted once: no cache, no chunks, no
batching, no bursts; a served sequence is one full causal forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights_olmo_hybrid as G
from benchmarks.reference.decoder import (Precision, _requant_weight,
                                          fake_quant, rms_norm, rope)

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
ROW_BLOCK = 2048
L2_EPS = 1e-6
# Matrices that stay float32 in every precision (the rule's own).
_KEPT = ("w_a", "w_b", "conv")


def stated_precision(config: dict) -> Precision:
    p = config["precision"]
    if (p["weights"], p["activations"], p["kv"]) != ("bf16",) * 3:
        raise SystemExit("the olmo_hybrid reference models bf16 serving")
    return Precision()


def control_precision(config: dict) -> Precision:
    """The nearest precision below bf16 everywhere it is stated: int8
    weights per output channel, int8 per-token activations into every
    large matmul, int8 K/V rows."""
    return Precision(act_bits=8, kv_bits=8, weight_bits=8)


def state_control_precision(config: dict) -> Precision:
    """The mechanism's own risk: the recurrent state held in bfloat16
    between tokens, everything else as stated."""
    return Precision(state_bits=16)


def is_linear(d, layer: int) -> bool:
    return layer % (d.lin_per_period + 1) != d.lin_per_period


def layer_weights(key, d, layer, linear: bool, prec: Precision):
    """One layer's float32 tensors from the seed (bf16 values, upcast;
    large matrices re-quantised where the precision says so)."""
    raw = G.layer_tensors(key, d, layer, linear)
    shapes = {**G.mixer_shapes(d, linear), **G.ffn_shapes(d)}
    out = {}
    for name, t in raw.items():
        t = t.astype(jnp.float32)
        if name in shapes and name not in _KEPT:
            t = _requant_weight(t, shapes[name][1], prec.weight_bits)
        out[name] = t
    return out


def _mm(eq, a, w, prec: Precision, n_tail: int = 1):
    return jnp.einsum(eq, fake_quant(a, n_tail, prec.act_bits), w,
                      precision=_HI)


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x, w):
    """x [B, S, C], w [K, C]: ``y_t = sum_j w[j] x_{t - (K - 1) + j}``
    with zeros before the first token."""
    K, S = w.shape[0], x.shape[1]
    xx = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xx[:, j:j + S] * w[j] for j in range(K))


def delta_rule(q, k, v, g, beta, prec: Precision):
    """The recurrence token by token. q, k [B, S, H, d_k], v [B, S, H,
    d_v], g (log decay), beta [B, S, H] -> o [B, S, H, d_v]."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        sk = jnp.einsum("bhvk,bhk->bhv", state, k_t, precision=_HI)
        state = state + (b_t[..., None] * (v_t - sk))[..., :, None] \
            * k_t[..., None, :]
        if prec.state_bits == 16:
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("bhvk,bhk->bhv", state, q_t, precision=_HI)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((B, H, dv, dk), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def linear_mixer(x, w, d, prec: Precision):
    """x: [B, S, D] -> the linear mixer's output [B, S, D]."""
    B, S, _ = x.shape
    h, dk, dv = d.lin_heads, d.lin_k_dim, d.lin_v_dim
    qkv = jnp.concatenate(
        [_mm("bsd,dhk->bshk", x, w[n], prec).reshape(B, S, -1)
         for n in ("wq", "wk", "wv")], axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, w["conv"]))
    q = l2_normalize(qkv[..., :h * dk].reshape(B, S, h, dk)) * dk ** -0.5
    k = l2_normalize(qkv[..., h * dk:2 * h * dk].reshape(B, S, h, dk))
    v = qkv[..., 2 * h * dk:].reshape(B, S, h, dv)
    a = jnp.einsum("bsd,dh->bsh", x, w["w_a"], precision=_HI)
    b = jnp.einsum("bsd,dh->bsh", x, w["w_b"], precision=_HI)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])
    beta = jax.nn.sigmoid(b) * (2.0 if d.allow_neg_eigval else 1.0)
    o = delta_rule(q, k, v, g, beta, prec)
    gate = _mm("bsd,dhv->bshv", x, w["wg"], prec)
    o = rms_norm(o, w["o_norm"], d.norm_eps) * jax.nn.silu(gate)
    return _mm("bshv,hvd->bsd", o, w["wo"], prec, 2)


def full_mixer(x, w, d, prec: Precision):
    """x: [B, S, D] -> the attention block's output [B, S, D]."""
    B, S, _ = x.shape
    nh, nkv, hd = d.n_heads, d.n_kv_heads, d.head_dim
    q = _mm("bsd,dhk->bshk", x, w["wq"], prec)
    k = _mm("bsd,dhk->bshk", x, w["wk"], prec)
    v = _mm("bsd,dhk->bshk", x, w["wv"], prec)
    q = rms_norm(q.reshape(B, S, -1), w["q_norm"],
                 d.norm_eps).reshape(q.shape)
    k = rms_norm(k.reshape(B, S, -1), w["k_norm"],
                 d.norm_eps).reshape(k.shape)
    if d.rope_theta is not None:
        positions = jnp.arange(S)
        q = rope(q, positions, d.rope_theta)
        k = rope(k, positions, d.rope_theta)
    # The rows a cache keeps.
    k = fake_quant(k, 1, prec.kv_bits)
    v = fake_quant(v, 1, prec.kv_bits)
    rep = nh // nkv
    col = jnp.arange(S)

    def block(q0):
        rows = q0 + jnp.arange(QUERY_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK, 1)
        qb = qb.reshape(B, QUERY_BLOCK, nkv, rep, hd)
        s = jnp.einsum("bqgrk,btgk->bgrqt", qb, k, precision=_HI) \
            * hd ** -0.5
        s = jnp.where(col[None, :] <= rows[:, None], s, -jnp.inf)
        o = jnp.einsum("bgrqt,btgk->bqgrk", jax.nn.softmax(s, axis=-1), v,
                       precision=_HI)
        return o.reshape(B, QUERY_BLOCK, nh, hd)

    n_blocks = -(-S // QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n_blocks * QUERY_BLOCK - S), (0, 0), (0, 0)))
    o = jax.lax.map(block, jnp.arange(n_blocks) * QUERY_BLOCK)
    o = jnp.moveaxis(o, 0, 1).reshape(B, n_blocks * QUERY_BLOCK, nh, hd)
    return _mm("bshk,hkd->bsd", o[:, :S], w["wo"], prec, 2)


def swiglu(x, w, prec: Precision):
    """x: [B, S, D] -> [B, S, D], in blocks of rows."""
    B, S, D = x.shape
    rows = x.reshape(B * S, D)
    n_blocks = -(-rows.shape[0] // ROW_BLOCK)
    rows = jnp.pad(rows, ((0, n_blocks * ROW_BLOCK - B * S), (0, 0)))

    def block(h):
        gate = _mm("td,df->tf", h, w["w_gate"], prec)
        up = _mm("td,df->tf", h, w["w_up"], prec)
        return _mm("tf,fd->td", jax.nn.silu(gate) * up, w["w_down"], prec)

    y = jax.lax.map(block, rows.reshape(n_blocks, ROW_BLOCK, D))
    return y.reshape(-1, D)[:B * S].reshape(B, S, D)


def decoder_layer(x, w, d, linear: bool, prec: Precision):
    """x: [B, S, D] float32 -> [B, S, D]."""
    mixed = (linear_mixer if linear else full_mixer)(x, w, d, prec)
    x = x + rms_norm(mixed, w["mixer_norm"], d.norm_eps)
    return x + rms_norm(swiglu(x, w, prec), w["ffn_norm"], d.norm_eps)


def final_logits(key, d, x, prec: Precision):
    """x: [..., D] final-layer output rows -> [..., vocab] logits."""
    fn = G.norm_scale(key, "final_norm", 0, d.d_model).astype(jnp.float32)
    h = rms_norm(x, fn, d.norm_eps)
    hw = _requant_weight(G.head(key, d).astype(jnp.float32), 1,
                         prec.weight_bits)
    return jnp.einsum("...d,dv->...v", fake_quant(h, 1, prec.act_bits), hw,
                      precision=_HI)


class Reference:
    """Jitted per-layer pieces of one (sizes, precision): one layer of
    float32 weights exists at a time."""

    def __init__(self, d, prec: Precision):
        self.d, self.prec = d, prec

        def fwd(key, layer, x, linear):
            w = layer_weights(key, d, layer, linear, prec)
            return decoder_layer(x, w, d, linear, prec)

        self._fwd = jax.jit(fwd, static_argnames=("linear",))
        self._embed = jax.jit(lambda key, t: G.embedding(key, d).astype(
            jnp.float32)[t])
        self._logits = jax.jit(lambda key, x: final_logits(key, d, x, prec))

    def hidden(self, key, tokens):
        """tokens [B, S] -> the last layer's output [B, S, D]."""
        x = self._embed(key, tokens)
        for layer in range(self.d.n_layers):
            x = self._fwd(key, np.uint32(layer), x,
                          linear=is_linear(self.d, layer))
        return x

    def logits_at(self, key, tokens, rows, cols):
        """Logits [n, vocab] at the (row, col) positions of ``tokens``."""
        return self._logits(key, self.hidden(key, tokens)[rows, cols])

    def logits(self, key, tokens):
        """Logits at every position [B, S, vocab] (small sizes only)."""
        return self._logits(key, self.hidden(key, tokens))
