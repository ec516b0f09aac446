"""Plain reference of the ``lfm2_moe`` family: a decoder whose layers are
gated short convolutions with a grouped-query attention layer every few,
routed experts (no shared one) after the leading dense layers and a tied
head (LFM2-8B-A1B), in straightforward ``jax.numpy``, float32, matmuls
at ``highest``.

It imports nothing of the program and takes nothing the program made:
weights come one layer at a time from ``benchmarks.weights_lfm2_moe``
(the benchmark's own seeded generator), so the 4.6 B parameters of the
cut model never exist whole in float32.

The layer (``x`` the residual stream)::

    h = x + Op(N1(x));   y = h + FFN(N2(h))

* conv operator: ``[B, C, x~] = u W_in`` (split in that order), ``z = B
  * x~``, ``c_t = sum_j w_j * z_{t - (K - 1) + j}`` with ``z`` zero
  before the sequence (depthwise, causal, no bias, no activation) —
  written as ``K`` shifted copies of the WHOLE sequence: no tail, no
  cache —, output ``(C * c) W_out``.
* attention: ``q`` as 32 heads, ``k``, ``v`` as 8, of 64; RMSNorm over
  each head's values of ``q`` and of ``k``; rotate-half RoPE over the
  whole head at ``rope_theta``; causal float32 softmax of ``q . k /
  sqrt(head_dim)``, 4 query heads a key head; ``W_o``. In blocks of
  query rows, a sequence at a time.
* dense layers: SwiGLU.
* expert layers: ``s = sigmoid(h W_r)``; the top-k of ``s + b`` is
  chosen (``b`` enters the choice only); weights ``s[chosen] /
  (sum(s[chosen]) + 1e-20) * routed_scaling_factor``; EVERY expert is
  applied to every row by a plain loop over the experts and its result
  weighted (zero where it was not chosen) — no sort, no groups, no
  capacity, no shared expert. In blocks of rows.
* a last RMSNorm, then logits against the embedding.

Departures from what the published ``config.json`` spells, each ASSUMED
from the model's public implementation (the configuration's file says
so too) and each a switch of :data:`ASSUMED` that the CPU tests turn
off one at a time: ``tied_head`` (off: an untied head of its own
stream); ``rope_half`` (rotate-half pairing, dimension ``i`` with ``i +
32``; off: adjacent pairs); ``qk_norm`` per head; ``expert_bias`` (the
seeded +-0.1 selection bias; off: none); ``weight_sum_eps`` (1e-20 in
the weights' sum, the repo's shared expert layer's; off: the public
code's 1e-6 — a departure of one part in a million, noted, which no
tolerance here can see).

``Precision`` (``benchmarks.reference.decoder``'s, with one field more)
models what a configuration STATES: ``act_bits`` quantises the input of
every matmul per token, ``kv_bits`` the K and V rows a cache would keep
(after norm and rotation), ``weight_bits`` re-quantises every matrix per
output channel; the router's matmul and the convolution's taps stay
float32 in every precision. ``tail_zero`` is the mechanism's own
control: the convolution sees ZERO for every earlier token, as a decode
step whose slot lost its tail would.

Departures from the programs, noted once: no cache, no tail, no chunks,
no batching, no bursts; a served sequence is one full causal forward.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights_lfm2_moe as G
from benchmarks.reference import decoder
from benchmarks.reference.decoder import (_requant_weight, fake_quant,
                                          rms_norm)
from benchmarks.reference.glm_moe import swiglu

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
ROW_BLOCK = 4096
LOGIT_BLOCK = 256
ASSUMED = frozenset(("tied_head", "rope_half", "qk_norm", "expert_bias",
                     "weight_sum_eps"))


@dataclasses.dataclass(frozen=True)
class Precision(decoder.Precision):
    tail_zero: bool = False


def stated_precision(config: dict) -> Precision:
    p = config["precision"]
    if (p["weights"], p["activations"], p["kv"]) != ("bf16",) * 3:
        raise SystemExit("the lfm2_moe reference models bf16 serving")
    return Precision()


def control_precision(config: dict) -> Precision:
    """The nearest precision below bf16 everywhere it is stated: int8
    weights per output channel, int8 per-token activations into every
    matmul, int8 K/V rows."""
    return Precision(act_bits=8, kv_bits=8, weight_bits=8)


def tail_control_precision(config: dict) -> Precision:
    """The mechanism's own risk: a convolution without its past."""
    return Precision(tail_zero=True)


def layer_weights(key, d, layer, conv: bool, moe: bool, prec: Precision):
    """One layer's float32 tensors from the seed (bf16 values, upcast;
    matrices re-quantised where the precision says so — not the router,
    not the convolution's taps)."""
    raw = G.layer_tensors(key, d, layer, conv, moe)
    shapes = {**G.op_shapes(d, conv), **G.ffn_shapes(d, moe)}
    out = {}
    for name, t in raw.items():
        t = t.astype(jnp.float32)
        if name in shapes and name not in ("router", "conv"):
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


def rope(x, positions, theta: float, half: bool):
    """x: [B, S, H, hd]. ``half``: dimension ``i`` rotates with ``i + hd
    / 2`` (rotate-half); otherwise with its neighbour (``2i``, ``2i +
    1``)."""
    if half:
        return decoder.rope(x, positions, theta)
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv       # [S, hd/2]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def short_conv(u, w, d, prec: Precision):
    """u: [B, S, D] (already normed) -> the conv operator's output."""
    S, D = u.shape[1], d.d_model
    bcx = _mm("bsd,de->bse", u, w["w_in"], prec)
    b, c, xt = bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]
    z = b * xt
    K = d.conv_kernel
    acc = w["conv"][K - 1] * z
    if not prec.tail_zero:
        for back in range(1, K):             # z_{t - back}, zero before 0
            past = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :S]
            acc = acc + w["conv"][K - 1 - back] * past
    return _mm("bsd,de->bse", c * acc, w["w_out"], prec)


def attention(u, w, d, prec: Precision, assumed=ASSUMED):
    """u: [B, S, D] (already normed) -> the attention's output [B, S, D]."""
    B, S, _ = u.shape
    nh, g, hd = d.n_heads, d.n_kv_heads, d.head_dim
    q = _mm("bsd,dhk->bshk", u, w["wq"], prec)
    k = _mm("bsd,dhk->bshk", u, w["wk"], prec)
    v = _mm("bsd,dhk->bshk", u, w["wv"], prec)
    if "qk_norm" in assumed:
        q = rms_norm(q, w["q_norm"], d.norm_eps)
        k = rms_norm(k, w["k_norm"], d.norm_eps)
    positions = jnp.arange(S)
    q = rope(q, positions, d.rope_theta, "rope_half" in assumed)
    k = rope(k, positions, d.rope_theta, "rope_half" in assumed)
    # The rows a cache keeps.
    k = fake_quant(k, 1, prec.kv_bits)
    v = fake_quant(v, 1, prec.kv_bits)
    rep = nh // g
    col = jnp.arange(S)
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
            s = jnp.where(col[None, :] <= rows[:, None], s, -jnp.inf)
            # (A padded query row past S sees keys all the same: j <= i.)
            o = jnp.einsum("grqt,tgk->qgrk", jax.nn.softmax(s, axis=-1), vs,
                           precision=_HI)
            return o.reshape(QUERY_BLOCK, nh, hd)

        o = jax.lax.map(block, jnp.arange(n_blocks) * QUERY_BLOCK)
        return o.reshape(n_blocks * QUERY_BLOCK, nh, hd)[:S]

    o = jax.lax.map(sequence, (q, k, v))
    return _mm("bshk,hkd->bsd", o, w["wo"], prec, 2)


def router(h, w, d, assumed=ASSUMED):
    """h [T, D] -> (chosen [T, K], their weights [T, K]); float32 in
    every precision."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", h, w["router"],
                                  precision=_HI))
    bias = w["router_bias"] if "expert_bias" in assumed else 0.0
    _, chosen = jax.lax.top_k(s + bias, d.experts_per_tok)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if d.norm_topk_prob:
        eps = 1e-20 if "weight_sum_eps" in assumed else 1e-6
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
    return chosen, picked * d.routed_scaling_factor


def expert_ffn(h, w, d, prec: Precision, assumed=ASSUMED):
    """Routed experts over rows h [T, D], by the definition: every
    expert applied to every row, weighted by the router (zero where it
    was not chosen), summed. No shared expert."""
    chosen, picked = router(h, w, d, assumed)
    T = h.shape[0]
    combine = jnp.zeros((T, d.n_routed_experts), jnp.float32).at[
        jnp.arange(T)[:, None], chosen].set(picked)

    def one(y, e):
        out = swiglu(h, w["we_gate"][e], w["we_up"][e], w["we_down"][e],
                     prec)
        return y + combine[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        jnp.arange(d.n_routed_experts))
    return y


def ffn(h, w, d, moe: bool, prec: Precision, assumed=ASSUMED):
    """h: [B, S, D] -> [B, S, D], in blocks of rows."""
    B, S, D = h.shape
    rows = h.reshape(B * S, D)
    block_rows = min(ROW_BLOCK, -(-B * S // 8) * 8)
    n_blocks = -(-rows.shape[0] // block_rows)
    rows = jnp.pad(rows, ((0, n_blocks * block_rows - B * S), (0, 0)))

    def block(r):
        if moe:
            return expert_ffn(r, w, d, prec, assumed)
        return swiglu(r, w["w_gate"], w["w_up"], w["w_down"], prec)

    y = jax.lax.map(block, rows.reshape(n_blocks, block_rows, D))
    return y.reshape(-1, D)[:B * S].reshape(B, S, D)


def decoder_layer(x, w, d, conv: bool, moe: bool, prec: Precision,
                  assumed=ASSUMED):
    """x: [B, S, D] float32 -> [B, S, D]."""
    u = rms_norm(x, w["ln1"], d.norm_eps)
    x = x + (short_conv(u, w, d, prec) if conv
             else attention(u, w, d, prec, assumed))
    return x + ffn(rms_norm(x, w["ln2"], d.norm_eps), w, d, moe, prec,
                   assumed)


def final_logits(key, d, x, prec: Precision, assumed=ASSUMED):
    """x: [..., D] final-layer output rows -> [..., vocab] logits."""
    fn = G.norm_scale(key, "final_norm", 0, d.d_model).astype(jnp.float32)
    h = rms_norm(x, fn, d.norm_eps)
    if "tied_head" in assumed:
        hw = G.embedding(key, d).astype(jnp.float32).T
    else:
        hw = G.head(key, d).astype(jnp.float32)
    hw = _requant_weight(hw, 1, prec.weight_bits)
    return jnp.einsum("...d,dv->...v", fake_quant(h, 1, prec.act_bits), hw,
                      precision=_HI)


class Reference:
    """Jitted per-layer pieces of one (sizes, precision): one layer of
    float32 weights exists at a time."""

    def __init__(self, d, prec: Precision, assumed=ASSUMED):
        self.d, self.prec, self.assumed = d, prec, frozenset(assumed)

        def fwd(key, layer, x, conv, moe):
            w = layer_weights(key, d, layer, conv, moe, prec)
            return decoder_layer(x, w, d, conv, moe, prec, self.assumed)

        self._fwd = jax.jit(fwd, static_argnames=("conv", "moe"))
        self._embed = jax.jit(
            lambda key, t: G.embedding(key, d).astype(jnp.float32)[t])
        self._logits = jax.jit(
            lambda key, x: final_logits(key, d, x, prec, self.assumed))

    def hidden(self, key, tokens):
        """tokens [B, S] -> the last layer's output [B, S, D]."""
        x = self._embed(key, tokens)
        for layer in range(self.d.n_layers):
            x = self._fwd(key, np.uint32(layer), x,
                          conv=self.d.is_conv(layer),
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
