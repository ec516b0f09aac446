"""Plain reference of the ``glm_moe`` family: a pre-norm decoder with
multi-head LATENT attention (MLA) in every layer, a SwiGLU feed-forward
in the leading dense layers and shared + routed experts in the rest
(``glm4_moe_lite``: GLM-4.7-Flash), in straightforward ``jax.numpy``,
float32, matmuls at ``highest``.

It imports nothing of the program and takes nothing the program made:
weights come one layer at a time from ``benchmarks.weights_glm_moe``
(the benchmark's own seeded generator), so the 4.5 B parameters of the
cut model never exist whole in float32.

The layer, as published (``h`` the residual stream):

* ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` -> heads x (nope + rope);
  ``[c_kv | k_r] = h W_kva``; ``c_kv <- RMSNorm(c_kv)``; ``k_pe =
  RoPE(k_r)`` — ONE key for all heads; ``q_pe = RoPE(q_rope)``;
  ``[k_nope | v] = c_kv W_kvb``; scores ``(q_nope . k_nope + q_pe .
  k_pe) / sqrt(nope + rope)``, causal softmax, ``o = P v`` -> ``W_o``.
  Attention here is NON-absorbed (keys and values are materialised from
  the latent rows) and computed in blocks of query rows, so a score
  tensor of 8.7 k x 8.7 k rows never exists.
* dense layers: SwiGLU.
* expert layers: ``s = sigmoid(h W_r)``; the top-k of ``s + b`` is
  chosen (``b`` enters the choice only); weights ``s[chosen] /
  sum(s[chosen]) * routed_scaling_factor``; each expert is applied to
  EVERY row by a plain loop over the experts and its result weighted
  (zero where it was not chosen) — no sort, no groups, no capacity;
  plus the shared expert.

Assumed (the configuration's file says so too): the rotary pairing is
rotate-half (dimension ``i`` with ``i + rope/2``); all ``rope`` dims are
rotated (``partial_rotary_factor`` 1), no scaling.

``Precision`` (``benchmarks.reference.decoder``'s) models what a
configuration STATES: ``act_bits`` quantises the input of every matmul
per token, ``kv_bits`` the latent row a cache would keep (``c_kv`` after
its norm and ``k_pe`` after the rotation, per token), ``weight_bits``
re-quantises every matrix per output channel. The router's matmul stays
float32 in every precision: the configuration states it so.

Departures from the programs, noted once: no cache, no batching, no
bursts; a served sequence is one full causal forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights_glm_moe as G
from benchmarks.reference.decoder import (Precision, _requant_weight,
                                          fake_quant, rms_norm, rope)

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def stated_precision(config: dict) -> Precision:
    p = config["precision"]
    if (p["weights"], p["activations"], p["kv"]) != ("bf16",) * 3:
        raise SystemExit("the glm_moe reference models bf16 serving")
    return Precision()


def control_precision(config: dict) -> Precision:
    """The nearest precision below bf16 everywhere: int8 weights per
    output channel, int8 per-token activations into every matmul, int8
    latent rows."""
    return Precision(act_bits=8, kv_bits=8, weight_bits=8)


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


def mla(x, w, d, prec: Precision):
    """x: [B, S, D] -> the attention block's output [B, S, D]."""
    B, S, _ = x.shape
    positions = jnp.arange(S)

    def mm(eq, a, name, n_tail=1):
        return jnp.einsum(eq, fake_quant(a, n_tail, prec.act_bits), w[name],
                          precision=_HI)

    h = rms_norm(x, w["ln1"], d.norm_eps)
    c_q = rms_norm(mm("bsd,dr->bsr", h, "wq_a"), w["q_norm"], d.norm_eps)
    q = mm("bsr,rhk->bshk", c_q, "wq_b")
    q_nope, q_pe = q[..., :d.qk_nope], q[..., d.qk_nope:]
    q_pe = rope(q_pe, positions, d.rope_theta)
    kv = mm("bsd,dr->bsr", h, "wkv_a")
    c_kv = rms_norm(kv[..., :d.kv_lora_rank], w["kv_norm"], d.norm_eps)
    k_pe = rope(kv[..., None, d.kv_lora_rank:], positions, d.rope_theta)
    # The row a cache keeps.
    c_kv = fake_quant(c_kv, 1, prec.kv_bits)
    k_pe = fake_quant(k_pe, 1, prec.kv_bits)
    up = mm("bsr,rhk->bshk", c_kv, "wkv_b")
    k_nope, v = up[..., :d.qk_nope], up[..., d.qk_nope:]
    scale = (d.qk_nope + d.qk_rope) ** -0.5
    col = jnp.arange(S)

    def block(q0):
        """Rows q0 .. q0 + QUERY_BLOCK of the (padded) queries."""
        rows = q0 + jnp.arange(QUERY_BLOCK)
        qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, QUERY_BLOCK, 1)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, q0, QUERY_BLOCK, 1)
        s = jnp.einsum("bqhk,bthk->bhqt", qn, k_nope, precision=_HI) \
            + jnp.einsum("bqhk,btk->bhqt", qp, k_pe[:, :, 0], precision=_HI)
        s = jnp.where(col[None, :] <= rows[:, None], s * scale, -jnp.inf)
        return jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, axis=-1), v,
                          precision=_HI)

    # Whole blocks of query rows (the last one padded with zero rows,
    # which attend to every key and are cut off again).
    n_blocks = -(-S // QUERY_BLOCK)
    pad = ((0, 0), (0, n_blocks * QUERY_BLOCK - S), (0, 0), (0, 0))
    q_nope, q_pe = jnp.pad(q_nope, pad), jnp.pad(q_pe, pad)
    o = jax.lax.map(block, jnp.arange(n_blocks) * QUERY_BLOCK)
    o = jnp.moveaxis(o, 0, 1).reshape(B, n_blocks * QUERY_BLOCK,
                                      *o.shape[3:])[:, :S]
    return mm("bshk,hkd->bsd", o, "wo", 2)


def router(h, w, d):
    """h [T, D] -> (chosen [T, K], their weights [T, K]); float32 in
    every precision."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", h, w["router"],
                                  precision=_HI))
    _, chosen = jax.lax.top_k(s + w["router_bias"], d.experts_per_tok)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if d.norm_topk_prob:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, picked * d.routed_scaling_factor


def swiglu(h, gate, up, down, prec: Precision):
    a = fake_quant(h, 1, prec.act_bits)
    g = jnp.einsum("td,df->tf", a, gate, precision=_HI)
    u = jnp.einsum("td,df->tf", a, up, precision=_HI)
    return jnp.einsum("tf,fd->td",
                      fake_quant(jax.nn.silu(g) * u, 1, prec.act_bits),
                      down, precision=_HI)


def expert_ffn(h, w, d, prec: Precision):
    """Shared + routed experts over rows h [T, D]: a loop over the
    experts, each applied to every row and weighted by the router
    (zero where it was not chosen)."""
    chosen, picked = router(h, w, d)
    T = h.shape[0]
    combine = jnp.zeros((T, d.n_routed_experts), jnp.float32).at[
        jnp.arange(T)[:, None], chosen].set(picked)

    def one(y, e):
        out = swiglu(h, w["we_gate"][e], w["we_up"][e], w["we_down"][e],
                     prec)
        return y + combine[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        jnp.arange(d.n_routed_experts))
    return y + swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], prec)


def decoder_layer(x, w, d, moe: bool, prec: Precision):
    """x: [B, S, D] float32 -> [B, S, D]."""
    B, S, D = x.shape
    x = x + mla(x, w, d, prec)
    h = rms_norm(x, w["ln2"], d.norm_eps).reshape(B * S, D)
    if moe:
        y = expert_ffn(h, w, d, prec)
    else:
        y = swiglu(h, w["w_gate"], w["w_up"], w["w_down"], prec)
    return x + y.reshape(B, S, D)


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

        def fwd(key, layer, x, moe):
            w = layer_weights(key, d, layer, moe, prec)
            return decoder_layer(x, w, d, moe, prec)

        self._fwd = jax.jit(fwd, static_argnames=("moe",))
        self._embed = jax.jit(lambda key, t: G.embedding(key, d).astype(
            jnp.float32)[t])
        self._logits = jax.jit(lambda key, x: final_logits(key, d, x, prec))

    def hidden(self, key, tokens):
        """tokens [B, S] -> the last layer's output [B, S, D]."""
        x = self._embed(key, tokens)
        for layer in range(self.d.n_layers):
            x = self._fwd(key, np.uint32(layer), x,
                          moe=layer >= self.d.first_k_dense)
        return x

    def logits_at(self, key, tokens, rows, cols):
        """Logits [n, vocab] at the (row, col) positions of ``tokens``."""
        return self._logits(key, self.hidden(key, tokens)[rows, cols])

    def logits(self, key, tokens):
        """Logits at every position [B, S, vocab] (small sizes only)."""
        return self._logits(key, self.hidden(key, tokens))
