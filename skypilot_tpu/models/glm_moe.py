"""Latent-attention (MLA) decoder with shared + routed experts
(``glm4_moe_lite``: GLM-4.7-Flash), pure functional JAX.

The block, as published (``h`` the residual stream; pre-norm, two
residual adds a layer; untied embedding and head):

* **MLA, every layer.** ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` ->
  heads x (nope + rope); ``[c_kv | k_r] = h W_kva``; ``c_kv <-
  RMSNorm(c_kv)``; ``k_pe = RoPE(k_r)``, ONE rope key shared by all
  heads; ``[k_nope | v] = c_kv W_kvb``; scores ``(q_nope . k_nope +
  q_pe . k_pe) / sqrt(nope + rope)``; ``o = P v`` -> ``W_o``. A cache
  keeps ``(c_kv, k_pe)`` per token and layer — no heads axis.
  *Absorbed* form (the same numbers, no per-row up-projection):
  ``q_lat = q_nope W_kvb[k]^T``, scores ``q_lat . c_kv + q_pe . k_pe``,
  ``o_lat = P c_kv``, ``o = o_lat W_kvb[v]``.
* **The first ``first_k_dense`` layers:** a SwiGLU feed-forward.
* **Every later layer:** ``s = sigmoid(h W_r)`` in float32; the top-k of
  ``s + b`` is chosen (``b`` biases the SELECTION only); weights
  ``s[chosen] / sum(s[chosen]) * routed_scaling_factor``; the weighted
  sum of the chosen experts' SwiGLUs plus one shared SwiGLU. DROPLESS:
  no capacity factor, every token-choice is computed. Two formulations,
  chosen from the static row count (:data:`DENSE_EXPERT_MAX_TOKENS`).
  Few rows (a decode step, which may say which of its rows are LIVE):
  the experts the live rows chose are found (a presence vector, no
  sort) and visited one by one, each read once where it lies in the
  stacked tensor — an expert nobody live chose is not read, so a step
  of one or two live rows streams a tenth of the expert weights
  (:func:`experts_few_rows`). Many rows (a prefill chunk or wave) sort
  the token-choices by expert and multiply by groups
  (:func:`experts_grouped`): on a TPU one Pallas kernel,
  ``ops.grouped_ffn.grouped_swiglu``, that reads each expert of the
  layer once and keeps the gate and up products in VMEM; elsewhere,
  and for rows or widths that are no whole tile, three
  ``lax.ragged_dot``.

The heterogeneous stack is TWO parameter groups, ``dense`` and ``moe``,
each stacked on a leading layer axis and each one ``lax.scan``
(:func:`scan_layers`); a cache's layer axis covers both, dense first.

The multi-token-prediction layer of the checkpoint is no part of the
main model's logits and is not built here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.models import llama
from skypilot_tpu.observability import flight
from skypilot_tpu.ops import attention as attn_ops
from skypilot_tpu.ops import grouped_ffn
from skypilot_tpu.parallel import ring_attention as ra

Params = Dict[str, Any]

# The serve programs of this family (``infer.kvcache.programs_for``).
SERVE_PROGRAMS = "skypilot_tpu.infer.latent"

# Rows at or below which the expert layer takes its few-row form (see
# the module docstring); above it, sort + grouped products.
DENSE_EXPERT_MAX_TOKENS = 64
# The routed experts' matrices, stacked [layers, E, ...] in the tree.
EXPERT_TENSORS = ("we_gate", "we_up", "we_down")


@dataclasses.dataclass(frozen=True)
class GlmMoeConfig:
    """Hyperparameters under the names the repo's other models use;
    :func:`from_published` maps a ``config.json``'s own key names."""

    vocab_size: int = 154_880
    d_model: int = 2048
    n_layers: int = 47
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    d_ff: int = 10_240               # the dense layers' SwiGLU width
    moe_d_ff: int = 1536             # each routed / shared expert's width
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    first_k_dense: int = 1
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 202_752
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16        # activation / compute dtype
    param_dtype: Any = jnp.float32   # storage dtype for parameters

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def latent_row_width(self) -> int:
        """Values a token keeps in the cache per layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def attn_params(self) -> int:
        d, h = self.d_model, self.n_heads
        return (d * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * h * self.qk_head_dim
                + d * self.latent_row_width + self.kv_lora_rank
                + self.kv_lora_rank * h
                * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d + 2 * d)

    def expert_params(self) -> int:
        """One layer's routed experts."""
        return self.n_routed_experts * 3 * self.d_model * self.moe_d_ff

    def num_params(self) -> int:
        d = self.d_model
        dense = self.attn_params() + 3 * d * self.d_ff
        moe = (self.attn_params() + d * self.n_routed_experts
               + self.n_routed_experts + self.expert_params()
               + self.n_shared_experts * 3 * d * self.moe_d_ff)
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return (self.first_k_dense * dense + self.n_moe_layers * moe
                + emb + d)

    def active_params(self) -> int:
        """Parameters one token multiplies with (its chosen experts
        only): what a FLOP count per token is made from."""
        idle = (self.n_routed_experts - self.experts_per_tok) \
            * 3 * self.d_model * self.moe_d_ff
        return self.num_params() - self.n_moe_layers * idle \
            - self.vocab_size * self.d_model


def from_published(config: Dict[str, Any], **overrides) -> GlmMoeConfig:
    """A ``glm4_moe_lite`` ``config.json`` (its own key names) as a
    :class:`GlmMoeConfig`."""
    if int(config.get("n_group", 1)) != 1 \
            or int(config.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    if config.get("rope_scaling"):
        raise ValueError("rope_scaling is not built")
    if float(config.get("partial_rotary_factor", 1)) != 1:
        raise ValueError("partial rotary is not built")
    fields = dict(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        d_ff=int(config["intermediate_size"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        n_routed_experts=int(config["n_routed_experts"]),
        n_shared_experts=int(config["n_shared_experts"]),
        experts_per_tok=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config.get("norm_topk_prob", True)),
        first_k_dense=int(config["first_k_dense_replace"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)))
    fields.update(overrides)
    return GlmMoeConfig(**fields)


CONFIGS: Dict[str, GlmMoeConfig] = {
    # The published model (47 layers, 29.9 B parameters: 60 GB in bf16,
    # more than any single host here holds).
    "glm-4.7-flash": GlmMoeConfig(),
    # Every mechanism at a size the CPU tests run: a leading dense
    # layer, two expert layers, 8 experts top-2 with one shared.
    "glm-moe-tiny": GlmMoeConfig(
        vocab_size=512, d_model=64, n_layers=3, n_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, d_ff=128, moe_d_ff=32,
        n_routed_experts=8, experts_per_tok=2, max_seq_len=512),
}


# ---------------------------------------------------------------------------
# Parameter init + logical sharding axes
# ---------------------------------------------------------------------------

def _attn_shapes(cfg: GlmMoeConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """Per-layer shape and fan-in of the attention matrices."""
    d, h = cfg.d_model, cfg.n_heads
    kv_out = cfg.qk_nope_head_dim + cfg.v_head_dim
    return {"wq_a": ((d, cfg.q_lora_rank), d),
            "wq_b": ((cfg.q_lora_rank, h, cfg.qk_head_dim),
                     cfg.q_lora_rank),
            "wkv_a": ((d, cfg.latent_row_width), d),
            "wkv_b": ((cfg.kv_lora_rank, h, kv_out), cfg.kv_lora_rank),
            "wo": ((h, cfg.v_head_dim, d), h * cfg.v_head_dim)}


def group_shapes(cfg: GlmMoeConfig
                 ) -> Dict[str, Dict[str, Tuple[Tuple[int, ...], int]]]:
    """``{group: {name: (per-layer shape, fan_in)}}`` of every matrix
    (fan_in 0 marks a norm scale, -1 the router's selection bias)."""
    d, ff, f = cfg.d_model, cfg.d_ff, cfg.moe_d_ff
    e, fs = cfg.n_routed_experts, cfg.n_shared_experts * cfg.moe_d_ff
    norms = {"ln1": ((d,), 0), "ln2": ((d,), 0),
             "q_norm": ((cfg.q_lora_rank,), 0),
             "kv_norm": ((cfg.kv_lora_rank,), 0)}
    attn = dict(_attn_shapes(cfg), **norms)
    return {
        "dense": dict(attn, w_gate=((d, ff), d), w_up=((d, ff), d),
                      w_down=((ff, d), ff)),
        "moe": dict(attn, router=((d, e), d), router_bias=((e,), -1),
                    we_gate=((e, d, f), d), we_up=((e, d, f), d),
                    we_down=((e, f, d), f),
                    ws_gate=((d, fs), d), ws_up=((d, fs), d),
                    ws_down=((fs, d), fs))}


def group_layers(cfg: GlmMoeConfig) -> Dict[str, int]:
    return {"dense": cfg.first_k_dense, "moe": cfg.n_moe_layers}


def init_params(rng: jax.Array, cfg: GlmMoeConfig) -> Params:
    """Random parameters; per-layer tensors stacked on axis 0 within
    their group. The selection bias is a trained buffer in a checkpoint;
    here it is drawn at a scale that changes some choices."""
    d, v = cfg.d_model, cfg.vocab_size
    keys = iter(jax.random.split(rng, 64))

    def draw(shape, fan_in):
        if fan_in == 0:
            return jnp.ones(shape, cfg.param_dtype)
        std = 0.05 if fan_in < 0 else fan_in ** -0.5
        return jax.random.normal(next(keys), shape, cfg.param_dtype) * std

    params: Params = {
        "embed": jax.random.normal(next(keys), (v, d),
                                   cfg.param_dtype) * 0.02,
        "final_norm": jnp.ones((d,), cfg.param_dtype)}
    for group, n in group_layers(cfg).items():
        params[group] = {name: draw((n,) + shape, fan_in)
                         for name, (shape, fan_in)
                         in group_shapes(cfg)[group].items()}
    if not cfg.tie_embeddings:
        params["lm_head"] = draw((d, v), d)
    return params


def param_logical_axes(cfg: GlmMoeConfig) -> Params:
    """Logical axis names per parameter (``parallel.sharding`` rules)."""
    attn = {"ln1": ("layer", "embed"), "ln2": ("layer", "embed"),
            "q_norm": ("layer", None), "kv_norm": ("layer", None),
            "wq_a": ("layer", "embed", None),
            "wq_b": ("layer", None, "heads", "head_dim"),
            "wkv_a": ("layer", "embed", None),
            "wkv_b": ("layer", None, "heads", "head_dim"),
            "wo": ("layer", "heads", "head_dim", "embed")}
    axes: Params = {
        "embed": ("vocab", "embed"), "final_norm": ("embed",),
        "dense": dict(attn, w_gate=("layer", "embed", "mlp"),
                      w_up=("layer", "embed", "mlp"),
                      w_down=("layer", "mlp", "embed")),
        "moe": dict(attn, router=("layer", "embed", None),
                    router_bias=("layer", None),
                    we_gate=("layer", "expert", "embed", "mlp"),
                    we_up=("layer", "expert", "embed", "mlp"),
                    we_down=("layer", "expert", "mlp", "embed"),
                    ws_gate=("layer", "embed", "mlp"),
                    ws_up=("layer", "embed", "mlp"),
                    ws_down=("layer", "mlp", "embed"))}
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def rope_tables(cfg: GlmMoeConfig, positions: jax.Array):
    """cos/sin over the rope head dim (all of it is rotated)."""
    hd = cfg.qk_rope_head_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


@jax.named_scope("qkv_proj")
def mla_project(cfg: GlmMoeConfig, layer: Params, x: jax.Array,
                cos: jax.Array, sin: jax.Array):
    """The four MLA projections of rows ``x`` [B, S, D] ->
    ``q_nope`` [B, S, H, nope], ``q_pe`` [B, S, H, rope] (rotated) and
    the row a cache keeps: ``c_kv`` [B, S, R] (normed), ``k_pe``
    [B, S, rope] (rotated, one key for every head)."""
    dt = cfg.dtype
    h = llama.rms_norm(x, layer["ln1"], cfg.norm_eps)
    c_q = llama.rms_norm(
        jnp.einsum("bsd,dr->bsr", h, layer["wq_a"].astype(dt)),
        layer["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, layer["wq_b"].astype(dt))
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_pe = llama.apply_rope(q[..., cfg.qk_nope_head_dim:], cos, sin)
    kv = jnp.einsum("bsd,dr->bsr", h, layer["wkv_a"].astype(dt))
    c_kv = llama.rms_norm(kv[..., :cfg.kv_lora_rank], layer["kv_norm"],
                          cfg.norm_eps)
    k_pe = llama.apply_rope(kv[..., None, cfg.kv_lora_rank:], cos,
                            sin)[..., 0, :]
    return q_nope, q_pe, c_kv, k_pe


def latent_attention(cfg: GlmMoeConfig, wkv_b: jax.Array,
                     q_nope: jax.Array, q_pe: jax.Array, segments,
                     absorbed: bool) -> jax.Array:
    """Attention of query rows over latent rows given in SEGMENTS (a
    resident cache view, a staging buffer, a chunk's own rows): the
    scores of all segments share one softmax, in the order given, and
    no segment is ever concatenated with another at row width.

    q_nope [B, Q, H, nope], q_pe [B, Q, H, rope]; each segment is
    ``(c_kv [B, M, R], k_pe [B, M, rope], mask [B|1, Q, M])``.
    ``absorbed``: fold ``W_kvb`` into the query and the output (no
    per-row up-projection); else materialise keys and values from the
    latent rows. Both give ``o`` [B, Q, H, v] in float32."""
    dt = cfg.dtype
    nope = cfg.qk_nope_head_dim
    scale = cfg.qk_head_dim ** -0.5
    neg = jnp.asarray(-1e30, jnp.float32)
    wkv_b = wkv_b.astype(dt)
    w_k, w_v = wkv_b[..., :nope], wkv_b[..., nope:]
    f32 = dict(preferred_element_type=jnp.float32)
    if absorbed:
        q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, w_k, **f32).astype(dt)
    scores, values = [], []
    for c_kv, k_pe, mask in segments:
        c_kv, k_pe = c_kv.astype(dt), k_pe.astype(dt)
        if absorbed:
            s = jnp.einsum("bqhr,bmr->bhqm", q_lat, c_kv, **f32)
            values.append(c_kv)
        else:
            kv = jnp.einsum("bmr,rhk->bmhk", c_kv, wkv_b)
            s = jnp.einsum("bqhk,bmhk->bhqm", q_nope, kv[..., :nope], **f32)
            values.append(kv[..., nope:])
        s = (s + jnp.einsum("bqhk,bmk->bhqm", q_pe, k_pe, **f32)) * scale
        scores.append(jnp.where(mask[:, None], s, neg))
    p = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1)
    out, at = None, 0
    for val in values:
        m = val.shape[1]
        p_seg = p[..., at:at + m].astype(dt)
        at += m
        # (The latent product keeps the heads axis where the weights
        # have it: the CPU backend has no bf16 x bf16 -> f32 dot for the
        # other order.)
        part = (jnp.einsum("bhqm,bmr->bhqr", p_seg, val, **f32) if absorbed
                else jnp.einsum("bhqm,bmhk->bqhk", p_seg, val, **f32))
        out = part if out is None else out + part
    if absorbed:
        out = jnp.einsum("bhqr,rhv->bqhv", out.astype(dt), w_v, **f32)
    return out


def causal_attention(cfg: GlmMoeConfig, wkv_b: jax.Array, q_nope, q_pe,
                     c_kv, k_pe, mesh=None, heads_axis=None) -> jax.Array:
    """Whole-sequence causal attention with keys and values
    materialised from the latent rows: the shapes are then an ordinary
    head_dim-``qk_head_dim`` attention, so a long bucket takes the same
    flash kernel the GQA models do (``ops.attention``)."""
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, wkv_b.astype(cfg.dtype))
    k_nope, v = kv[..., :cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  k_nope.shape[:3] + k_pe.shape[-1:])],
        axis=-1)
    if v.shape[-1] != q.shape[-1]:
        raise ValueError("causal_attention needs v_head_dim == nope + rope")
    return ra.local_attention(q, k, v, mesh, causal=True, batch_axes=None,
                              heads_axis=heads_axis)


# ---------------------------------------------------------------------------
# Feed-forward: dense SwiGLU, and shared + routed experts
# ---------------------------------------------------------------------------

def _swiglu(h, w_gate, w_up, w_down, dt):
    g = jnp.einsum("...d,df->...f", h, w_gate.astype(dt))
    u = jnp.einsum("...d,df->...f", h, w_up.astype(dt))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u,
                      w_down.astype(dt))


@jax.named_scope("router")
def route(cfg: GlmMoeConfig, h: jax.Array, layer: Params):
    """h [T, D] -> (experts [T, K] int32, weights [T, K] float32). The
    scores are a float32 sigmoid; the bias enters the SELECTION only."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h.astype(jnp.float32),
        layer["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + layer["router_bias"].astype(jnp.float32),
                       cfg.experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


def experts_per_step(cfg) -> int:
    """Routed experts a decode step would read if its rows chose them
    all: expert layers x experts (a serve-program module's answer to
    ``kvcache.programs_for``, beside its count of those it did read)."""
    return cfg.n_moe_layers * cfg.n_routed_experts


def touched_experts(cfg: GlmMoeConfig, idx: jax.Array, live=None):
    """The distinct experts the LIVE rows of ``idx`` [T, K] chose:
    ``(ids [E] int32 — ascending, the first n of them meant, zeros
    after —, n)``. ``live`` [T] bool; absent = every row counts. A
    presence vector over the E experts and its running sum place the
    ids: no sort and no scatter of the T x K choices."""
    E = cfg.n_routed_experts
    experts = jnp.arange(E, dtype=jnp.int32)
    hit = idx[:, :, None] == experts
    if live is not None:
        hit = hit & live[:, None, None]
    chosen = hit.any(axis=(0, 1))                            # [E]
    place = jnp.cumsum(chosen.astype(jnp.int32)) - 1
    ids = jnp.sum(jnp.where(chosen[:, None] & (place[:, None] == experts),
                            experts[:, None], 0), axis=0)
    return ids, jnp.sum(chosen.astype(jnp.int32))


def combine_weights(cfg: GlmMoeConfig, idx, w, live=None) -> jax.Array:
    """Router weights as a float32 ``[E, T]`` table: row ``e`` holds
    each token's weight for expert ``e``, zero where the token did not
    choose it or is not ``live`` (top-k ids of a row are distinct, so
    the sum over K places a weight and adds none)."""
    if live is not None:
        w = jnp.where(live[:, None], w, 0.0)
    experts = jnp.arange(cfg.n_routed_experts, dtype=jnp.int32)
    return jnp.sum(jnp.where(idx[None] == experts[:, None, None],
                             w[None], 0.0), axis=-1)


def experts_visited(cfg: GlmMoeConfig, h, combine, ids, n, layer
                    ) -> jax.Array:
    """Visit experts ``ids[:n]``, one a turn of a loop whose trip count
    is ``n``, and read nothing of any other. A turn takes the expert's
    three matrices where they lie in the stack (at
    ``layer["expert_base"] + id``, as :func:`experts_grouped` addresses
    it), runs the SwiGLU over ALL T rows (bf16 products) and adds it,
    weighted by the expert's combine row, into a float32 [T, D]."""
    dt = cfg.dtype
    base = layer.get("expert_base", 0)

    def turn(t, acc):
        e = ids[t]
        w_gate, w_up, w_down = (
            lax.dynamic_index_in_dim(layer[name], base + e, 0,
                                     keepdims=False).astype(dt)
            for name in EXPERT_TENSORS)
        a = jax.nn.silu(jnp.dot(h, w_gate)) * jnp.dot(h, w_up)
        scale = lax.dynamic_index_in_dim(combine, e, 0, keepdims=False)
        return acc + jnp.dot(
            a, w_down, preferred_element_type=jnp.float32) * scale[:, None]

    return lax.fori_loop(0, n, turn, jnp.zeros(h.shape, jnp.float32))


@jax.named_scope("moe_experts")
def experts_few_rows(cfg: GlmMoeConfig, h, idx, w, layer, live=None):
    """Few rows (a decode step): compute the experts the LIVE rows
    chose, visited one by one (:func:`experts_visited`), and read no
    other. ``live`` [T] bool (absent: every row counts); a row that is
    not live gets a zero routed output. Every live token-choice is
    computed. Returns ``(y [T, D] float32, experts read)``."""
    ids, n = touched_experts(cfg, idx, live)
    combine = combine_weights(cfg, idx, w, live)
    return experts_visited(cfg, h, combine, ids, n, layer), n


@jax.named_scope("moe_experts")
def experts_grouped(cfg: GlmMoeConfig, h, idx, w, layer) -> jax.Array:
    """Sort the T x K token-choices by expert, multiply by groups,
    weight, and sum each token's K results. No capacity: a group is as
    long as its expert was chosen, zero included.

    Inside :func:`scan_layers` the expert matrices arrive as the WHOLE
    stack ``[layers * E, ...]`` with this layer's first group at
    ``layer["expert_base"]``, and are read where they lie — a layer's
    slice of the stack handed to a kernel is first copied (1.2 GB a
    layer, a third of a chunk's time on the v5e).

    The products take one of two forms, chosen at trace time from the
    backend and the shapes (``ops.grouped_ffn.tiles_for``) and noted on
    the program's ``program.compiled`` line. On a TPU, with rows and
    widths that are whole tiles: ONE Pallas kernel
    (``ops.grouped_ffn.grouped_swiglu``) that walks this layer's E
    groups only, reads an expert's three matrices once and none of an
    expert nobody chose, and keeps ``g``, ``u`` and ``silu(g) * u`` in
    VMEM. Elsewhere (the CPU, a row count that is no whole tile): three
    ``lax.ragged_dot`` over the stack's ``layers * E`` groups, all empty
    but this layer's."""
    dt = cfg.dtype
    T, K = idx.shape
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    base = layer.get("expert_base", 0)
    xs = h[order // K]                                   # [T*K, D]
    w_gate, w_up, w_down = (layer[name].astype(dt)
                            for name in EXPERT_TENSORS)
    D, F = xs.shape[1], w_gate.shape[-1]
    tiles = attn_ops._on_tpu() and grouped_ffn.tiles_for(
        T * K, D, F, xs.dtype.itemsize)
    flight.COMPILES.note("expert_ffn", ("grouped_swiglu" if tiles else
                                        "ragged_dot") + f"@{T * K}x{D}x{F}")
    if tiles:
        sizes = jnp.zeros((cfg.n_routed_experts,), jnp.int32).at[flat].add(1)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
        y = grouped_ffn.grouped_swiglu(xs, w_gate, w_up, w_down, offsets,
                                       base, tiles=tiles)
    else:
        sizes = jnp.zeros((w_gate.shape[0],), jnp.int32
                          ).at[base + flat].add(1)
        g = lax.ragged_dot(xs, w_gate, sizes)
        u = lax.ragged_dot(xs, w_up, sizes)
        y = lax.ragged_dot(jax.nn.silu(g) * u, w_down, sizes)
    y = y * w.reshape(-1)[order][:, None].astype(dt)
    return y[jnp.argsort(order)].reshape(T, K, -1).sum(axis=1)


def moe_ffn(cfg: GlmMoeConfig, h: jax.Array, layer: Params, live=None):
    """Routed experts, plus the shared one where the family has it
    (``n_shared_experts`` 0: no ``ws_*`` tensor is read and no shared
    product runs), over rows h [B, S, D] (post-norm).
    ``live`` [B, S] bool (few rows only: a decode step's live slots):
    a row that is not live chooses no expert and gets a zero routed
    output; absent = every row counts. Returns ``(y [B, S, D], routed
    experts read)``: :func:`experts_few_rows`' count, zero for many
    rows (nobody reads it there)."""
    B, S, D = h.shape
    rows = h.reshape(B * S, D)
    idx, w = route(cfg, rows, layer)
    if B * S <= DENSE_EXPERT_MAX_TOKENS:
        y, n = experts_few_rows(cfg, rows, idx, w, layer,
                                None if live is None else live.reshape(-1))
    else:
        if live is not None:
            raise ValueError("a row mask is a few-row (decode) argument")
        y = experts_grouped(cfg, rows, idx, w, layer)
        n = jnp.zeros((), jnp.int32)
    if not cfg.n_shared_experts:
        return y.astype(cfg.dtype).reshape(B, S, D), n
    with jax.named_scope("shared_expert"):
        y = y.astype(cfg.dtype) + _swiglu(
            rows, layer["ws_gate"], layer["ws_up"], layer["ws_down"],
            cfg.dtype)
    return y.reshape(B, S, D), n


@jax.named_scope("out_ffn")
def out_ffn(cfg: GlmMoeConfig, layer: Params, x: jax.Array, o: jax.Array,
            moe: bool, live=None):
    """The back half of a layer: output projection of the attention
    result ``o`` [B, S, H, v], residual, norm, feed-forward, residual.
    Returns ``(x', routed experts read)`` — :func:`moe_ffn`'s count,
    zero in a dense layer."""
    x = x + jnp.einsum("bshk,hkd->bsd", o.astype(cfg.dtype),
                       layer["wo"].astype(cfg.dtype))
    h = llama.rms_norm(x, layer["ln2"], cfg.norm_eps)
    if moe:
        y, n = moe_ffn(cfg, h, layer, live)
        return x + y, n
    return x + _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                       cfg.dtype), jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

def scan_layers(cfg: GlmMoeConfig, params: Params, carry,
                layer_fn: Callable):
    """``layer_fn(carry, layer, i, moe) -> (carry, ys)`` over the dense
    group and then the expert group, one ``lax.scan`` each; ``i`` is the
    layer's index in the whole stack (a cache's layer axis). Returns
    ``(carry, ys stacked over all layers)``."""
    outs = []
    start = 0
    for group, n in group_layers(cfg).items():
        if not n:
            continue
        moe = group == "moe"
        # The routed experts ride the loop WHOLE (an invariant seen as
        # [layers * E, ...]), not sliced a layer a turn: experts_grouped
        # says why.
        whole = {name: w.reshape((-1,) + w.shape[2:])
                 for name, w in params[group].items()
                 if name in EXPERT_TENSORS}
        sliced = {name: w for name, w in params[group].items()
                  if name not in whole}

        def body(c, li, moe=moe, whole=whole, start=start):
            layer, i = li
            if whole:
                layer = dict(layer, **whole, expert_base=(
                    i - start) * cfg.n_routed_experts)
            return layer_fn(c, layer, i, moe)

        carry, ys = lax.scan(
            body, carry,
            (sliced, start + jnp.arange(n, dtype=jnp.int32)))
        outs.append(ys)
        start += n
    if len(outs) == 1:
        return carry, outs[0]
    return carry, jax.tree.map(
        lambda *a: jnp.concatenate(a, axis=0), *outs)


@jax.named_scope("lm_head")
def head_logits(cfg: GlmMoeConfig, params: Params, x: jax.Array) -> jax.Array:
    """Final norm + head over rows x [..., D] -> float32 logits."""
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...d,dv->...v", x,
                      head.astype(cfg.dtype)).astype(jnp.float32)


def forward_hidden(params: Params, tokens: jax.Array, cfg: GlmMoeConfig,
                   mesh=None, heads_axis=None):
    """Token ids [B, S] -> (hidden [B, S, D] before the final norm, the
    latent rows ``{"c_kv": [L, B, S, R], "k_pe": [L, B, S, rope]}``)."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    cos, sin = rope_tables(cfg, jnp.arange(tokens.shape[1]))

    def layer_fn(x, layer, i, moe):
        q_nope, q_pe, c_kv, k_pe = mla_project(cfg, layer, x, cos, sin)
        with jax.named_scope("attn_core"):
            o = causal_attention(cfg, layer["wkv_b"], q_nope, q_pe, c_kv,
                                 k_pe, mesh, heads_axis)
        return out_ffn(cfg, layer, x, o, moe)[0], (c_kv, k_pe)

    x, (c_kv, k_pe) = scan_layers(cfg, params, x, layer_fn)
    return x, {"c_kv": c_kv, "k_pe": k_pe}


def forward(params: Params, tokens: jax.Array, cfg: GlmMoeConfig
            ) -> jax.Array:
    """Token ids [B, S] -> logits [B, S, vocab] float32."""
    x, _ = forward_hidden(params, tokens, cfg)
    return head_logits(cfg, params, x)
