"""Plain reference of the ``afmoe`` family: a decoder whose attention
layers are sliding-window ones with a global layer every few, a sigmoid
gate on the attention's output, norms before and after each sub-layer,
and shared + routed experts after the leading dense layers
(Trinity-Mini), in straightforward ``jax.numpy``, float32, matmuls at
``highest``.

It imports nothing of the program and takes nothing the program made:
weights come one layer at a time from ``benchmarks.weights_afmoe`` (the
benchmark's own seeded generator), so the 4.2 B parameters of the cut
model never exist whole in float32.

The layer (``x`` the residual stream, ``u = N1(x)``)::

    x = x + N2(Attn(N1(x)));   x = x + N4(FFN(N3(x)))

* ``q, k, v, g = W_q u, W_k u, W_v u, W_g u``; ``q`` and ``k`` pass an
  RMSNorm over each head's values; in a ``sliding_attention`` layer both
  are rotated (rotate-half, theta ``rope_theta``) and query ``i`` sees
  keys ``i - window < j <= i`` — a MASK over the whole score matrix: no
  ring, no cache —; in a ``full_attention`` layer nothing is rotated and
  query ``i`` sees every ``j <= i``. Float32 softmax of ``q . k /
  sqrt(head_dim)``, 8 query heads a key head; output ``W_o(o *
  sigmoid(g))``. Computed in blocks of query rows, a sequence at a
  time, so a score tensor of 33 k x 33 k rows never exists.
* dense layers: SwiGLU.
* expert layers: ``s = sigmoid(h W_r)``; the top-k of ``s + b`` is
  chosen (``b`` enters the choice only); weights ``s[chosen] /
  (sum(s[chosen]) + 1e-20) * route_scale``; each expert is applied to
  EVERY row by a plain loop over the experts and its result weighted
  (zero where it was not chosen) — no sort, no groups, no capacity;
  plus the shared expert. In blocks of rows.
* the embedding is scaled by ``sqrt(hidden_size)``; final RMSNorm,
  untied head.

Departures from what the published ``config.json`` spells, each ASSUMED
from the model's public implementation (the configuration's file says
so too) and each a switch of :data:`ASSUMED` that the CPU tests turn
off one at a time: the output ``gate`` and its place before ``W_o``;
``qk_norm`` per head; ``rope_window_only`` (no rotation in the global
layers); ``sandwich_norm`` (the norms AFTER each sub-layer, N2 and N4);
``embed_scale``.

``Precision`` (``benchmarks.reference.decoder``'s, with one field more)
models what a configuration STATES: ``act_bits`` quantises the input of
every matmul per token, ``kv_bits`` the K and V rows a cache would keep
(after norm and rotation), ``weight_bits`` re-quantises every matrix per
output channel; the router's matmul stays float32 in every precision.
``window_all`` is the mechanism's own control: the window layers see
EVERY earlier row, as a cache that never forgot — or a ring read
without its mask — would make them.

Departures from the programs, noted once: no cache, no ring, no chunks,
no batching, no bursts; a served sequence is one full causal forward.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights_afmoe as G
from benchmarks.reference import decoder
from benchmarks.reference.decoder import (_requant_weight, fake_quant,
                                          rms_norm, rope)
# The expert layer is the ``glm_moe`` family's at other numbers, in the
# reference as in the program: its router, SwiGLU and loop over experts.
from benchmarks.reference.glm_moe import expert_ffn, router, swiglu

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
ROW_BLOCK = 4096
LOGIT_BLOCK = 256
ASSUMED = frozenset(("gate", "qk_norm", "rope_window_only",
                     "sandwich_norm", "embed_scale"))


@dataclasses.dataclass(frozen=True)
class Precision(decoder.Precision):
    window_all: bool = False


def stated_precision(config: dict) -> Precision:
    p = config["precision"]
    if (p["weights"], p["activations"], p["kv"]) != ("bf16",) * 3:
        raise SystemExit("the afmoe reference models bf16 serving")
    return Precision()


def control_precision(config: dict) -> Precision:
    """The nearest precision below bf16 everywhere it is stated: int8
    weights per output channel, int8 per-token activations into every
    matmul, int8 K/V rows."""
    return Precision(act_bits=8, kv_bits=8, weight_bits=8)


def window_control_precision(config: dict) -> Precision:
    """The mechanism's own risk: a window layer that sees every row."""
    return Precision(window_all=True)


def layer_weights(key, d, layer, moe: bool, prec: Precision):
    """One layer's float32 tensors from the seed (bf16 values, upcast;
    matrices re-quantised where the precision says so)."""
    raw = G.layer_tensors(key, d, layer, moe)
    shapes = {**G.attn_shapes(d), **G.ffn_shapes(d, moe)}
    out = {}
    for name, t in raw.items():
        t = t.astype(jnp.float32)
        if name in shapes and name != "router":
            nc = shapes[name][1]
            if name.startswith("we_"):       # per expert
                t = jax.vmap(lambda w: _requant_weight(
                    w, nc, prec.weight_bits))(t)
            else:
                t = _requant_weight(t, nc, prec.weight_bits)
        out[name] = t
    return out


def _mm(eq, a, w, prec: Precision, n_tail: int = 1):
    return jnp.einsum(eq, fake_quant(a, n_tail, prec.act_bits), w,
                      precision=_HI)


def attention(u, w, d, window: bool, prec: Precision, assumed=ASSUMED):
    """u: [B, S, D] (already normed) -> the attention's output [B, S, D]."""
    B, S, _ = u.shape
    nh, g, hd = d.n_heads, d.n_kv_heads, d.head_dim
    q = _mm("bsd,dhk->bshk", u, w["wq"], prec)
    k = _mm("bsd,dhk->bshk", u, w["wk"], prec)
    v = _mm("bsd,dhk->bshk", u, w["wv"], prec)
    if "qk_norm" in assumed:
        q = rms_norm(q, w["q_norm"], d.norm_eps)
        k = rms_norm(k, w["k_norm"], d.norm_eps)
    if window or "rope_window_only" not in assumed:
        positions = jnp.arange(S)
        q = rope(q, positions, d.rope_theta)
        k = rope(k, positions, d.rope_theta)
    # The rows a cache keeps.
    k = fake_quant(k, 1, prec.kv_bits)
    v = fake_quant(v, 1, prec.kv_bits)
    rep = nh // g
    col = jnp.arange(S)
    windowed = window and not prec.window_all
    n_blocks = -(-S // QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n_blocks * QUERY_BLOCK - S), (0, 0), (0, 0)))

    def sequence(qkv):
        qs, ks, vs = qkv                         # [S', nh, hd], [S, g, hd]

        def block(q0):
            rows = q0 + jnp.arange(QUERY_BLOCK)
            qb = jax.lax.dynamic_slice_in_dim(qs, q0, QUERY_BLOCK, 0)
            qb = qb.reshape(QUERY_BLOCK, g, rep, hd)
            s = jnp.einsum("qgrk,tgk->grqt", qb, ks, precision=_HI) \
                * hd ** -0.5
            seen = col[None, :] <= rows[:, None]
            if windowed:
                seen = seen & (rows[:, None] - col[None, :] < d.window)
            s = jnp.where(seen, s, -jnp.inf)
            # (A padded query row past S sees keys all the same: j <= i.)
            o = jnp.einsum("grqt,tgk->qgrk", jax.nn.softmax(s, axis=-1), vs,
                           precision=_HI)
            return o.reshape(QUERY_BLOCK, nh, hd)

        o = jax.lax.map(block, jnp.arange(n_blocks) * QUERY_BLOCK)
        return o.reshape(n_blocks * QUERY_BLOCK, nh, hd)[:S]

    o = jax.lax.map(sequence, (q, k, v))
    if "gate" in assumed:
        o = o * jax.nn.sigmoid(_mm("bsd,dhk->bshk", u, w["wg"], prec))
    return _mm("bshk,hkd->bsd", o, w["wo"], prec, 2)


def ffn(h, w, d, moe: bool, prec: Precision):
    """h: [B, S, D] -> [B, S, D], in blocks of rows."""
    B, S, D = h.shape
    rows = h.reshape(B * S, D)
    block_rows = min(ROW_BLOCK, -(-B * S // 8) * 8)
    n_blocks = -(-rows.shape[0] // block_rows)
    rows = jnp.pad(rows, ((0, n_blocks * block_rows - B * S), (0, 0)))

    def block(r):
        if moe:
            return expert_ffn(r, w, d, prec)
        return swiglu(r, w["w_gate"], w["w_up"], w["w_down"], prec)

    y = jax.lax.map(block, rows.reshape(n_blocks, block_rows, D))
    return y.reshape(-1, D)[:B * S].reshape(B, S, D)


def decoder_layer(x, w, d, window: bool, moe: bool, prec: Precision,
                  assumed=ASSUMED):
    """x: [B, S, D] float32 -> [B, S, D]."""
    sandwich = "sandwich_norm" in assumed
    a = attention(rms_norm(x, w["ln1"], d.norm_eps), w, d, window, prec,
                  assumed)
    x = x + (rms_norm(a, w["ln2"], d.norm_eps) if sandwich else a)
    y = ffn(rms_norm(x, w["ln3"], d.norm_eps), w, d, moe, prec)
    return x + (rms_norm(y, w["ln4"], d.norm_eps) if sandwich else y)


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

    def __init__(self, d, prec: Precision, assumed=ASSUMED):
        self.d, self.prec, self.assumed = d, prec, frozenset(assumed)

        def fwd(key, layer, x, window, moe):
            w = layer_weights(key, d, layer, moe, prec)
            return decoder_layer(x, w, d, window, moe, prec, self.assumed)

        def embed(key, t):
            x = G.embedding(key, d).astype(jnp.float32)[t]
            if d.mup_enabled and "embed_scale" in self.assumed:
                x = x * d.d_model ** 0.5
            return x

        self._fwd = jax.jit(fwd, static_argnames=("window", "moe"))
        self._embed = jax.jit(embed)
        self._logits = jax.jit(lambda key, x: final_logits(key, d, x, prec))

    def hidden(self, key, tokens):
        """tokens [B, S] -> the last layer's output [B, S, D]."""
        x = self._embed(key, tokens)
        for layer in range(self.d.n_layers):
            x = self._fwd(key, np.uint32(layer), x,
                          window=self.d.is_window(layer),
                          moe=layer >= self.d.n_dense_layers)
        return x

    def logits_at(self, key, tokens, rows, cols):
        """Logits [n, vocab] at the (row, col) positions of ``tokens``,
        the head applied a block of positions at a time."""
        picked = self.hidden(key, tokens)[np.asarray(rows), np.asarray(cols)]
        out = [np.asarray(self._logits(key, picked[at:at + LOGIT_BLOCK]))
               for at in range(0, picked.shape[0], LOGIT_BLOCK)]
        return np.concatenate(out, axis=0)

    def logits(self, key, tokens):
        """Logits at every position [B, S, vocab] (small sizes only)."""
        return self._logits(key, self.hidden(key, tokens))
