"""Llama-family decoder-only transformer, pure functional JAX.

TPU-first design notes
----------------------
* Params are plain pytrees (nested dicts of ``jnp.ndarray``); per-layer
  weights are *stacked* along a leading ``layer`` axis so the whole decoder
  body runs as one ``lax.scan`` — a single traced layer, compiled once,
  instead of ``n_layers`` unrolled HLO copies.
* Every parameter has *logical axes* (see ``param_logical_axes``); the
  mapping logical-axis -> mesh-axis lives in ``skypilot_tpu.parallel.sharding``
  so the same model code runs single-chip, FSDP, TP, or any combination by
  swapping rules (MaxText-style).
* Compute in bfloat16 (MXU native), params kept in float32 by default;
  activations are sharding-constrained at layer boundaries so XLA inserts
  collectives (all-gather / reduce-scatter over ICI) instead of replicating.
* Attention dispatches through ``skypilot_tpu.ops.attention`` which picks a
  Pallas flash kernel on TPU and a plain XLA einsum path elsewhere.

Reference parity: the reference ships Llama only as *external* workload
recipes (reference: llm/llama-3_1-finetuning/lora.yaml, examples/tpu/v6e/
train-llama3-8b.yaml — PyTorch/XLA + torchtune). Here the model family is
in-tree, which is what makes the in-tree train/serve recipes (§2.11 of
SURVEY.md) possible.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]

# The serve programs of this family (``infer.kvcache.programs_for``).
SERVE_PROGRAMS = "skypilot_tpu.infer.kvcache"


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Model hyperparameters (Llama-3 family proportions)."""

    vocab_size: int = 128_256
    d_model: int = 2048
    n_layers: int = 16
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16       # activation / compute dtype
    param_dtype: Any = jnp.float32  # storage dtype for parameters
    remat: bool = True              # rematerialize each layer in the bwd pass
    # "none" recomputes everything (min HBM); "dots" saves matmul
    # outputs with no batch dims (MXU results kept, elementwise
    # recomputed — the usual best FLOPs/HBM trade on TPU).
    remat_policy: str = "none"
    # Sequence positions per cross-entropy chunk (0 = single pass).
    # Chunking never materializes the full [B, S, vocab] fp32 logits:
    # each chunk's logits are recomputed in the backward (remat), so
    # peak HBM drops by ~B*S*vocab*4 bytes at the cost of one extra
    # lm_head matmul in the backward.
    xent_chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def num_params(self) -> int:
        """Exact parameter count (used for MFU accounting in bench)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        mlp = 3 * d * ff
        norms = 2 * d
        per_layer = attn + mlp + norms
        emb = v * d if self.tie_embeddings else 2 * v * d
        return self.n_layers * per_layer + emb + d


# Pre-baked configs. 8B mirrors Llama-3.1-8B, 1B mirrors Llama-3.2-1B.
CONFIGS: Dict[str, LlamaConfig] = {
    "llama3-8b": LlamaConfig(vocab_size=128_256, d_model=4096, n_layers=32,
                             n_heads=32, n_kv_heads=8, d_ff=14_336),
    # Llama-3.1-70B proportions (multi-slice scale: llm/
    # llama3-70b-multislice.yaml shards it dp x fsdp x tp over v5p).
    "llama3-70b": LlamaConfig(vocab_size=128_256, d_model=8192,
                              n_layers=80, n_heads=64, n_kv_heads=8,
                              d_ff=28_672, max_seq_len=8192),
    # 1B-class config at Llama-3.2-1B proportions, with head_dim 128
    # (16 heads instead of 32): identical parameter count and FLOPs, but
    # the head dim matches the MXU lane width / Mosaic tiling so the
    # Pallas flash kernels engage.
    "llama3-1b": LlamaConfig(vocab_size=128_256, d_model=2048, n_layers=16,
                             n_heads=16, n_kv_heads=8, d_ff=8192),
    "llama3-tiny": LlamaConfig(vocab_size=512, d_model=128, n_layers=2,
                               n_heads=4, n_kv_heads=2, d_ff=256,
                               max_seq_len=256),
    # Fits a single 16 GB v5e chip with AdamW fp32 state: ~420M params.
    "llama3-400m": LlamaConfig(vocab_size=32_768, d_model=1536, n_layers=12,
                               n_heads=12, n_kv_heads=4, d_ff=6144,
                               max_seq_len=4096),
}


# ---------------------------------------------------------------------------
# Parameter init + logical sharding axes
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialize parameters. Per-layer tensors are stacked on axis 0."""
    d, ff, v, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    k = iter(jax.random.split(rng, 16))

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, cfg.param_dtype)
                * (fan_in ** -0.5))

    params: Params = {
        "embed": jax.random.normal(next(k), (v, d), cfg.param_dtype) * 0.02,
        "blocks": {
            "ln1": jnp.ones((L, d), cfg.param_dtype),
            "ln2": jnp.ones((L, d), cfg.param_dtype),
            "wq": norm_init(next(k), (L, d, nh, hd), d),
            "wk": norm_init(next(k), (L, d, nkv, hd), d),
            "wv": norm_init(next(k), (L, d, nkv, hd), d),
            "wo": norm_init(next(k), (L, nh, hd, d), nh * hd),
            "w_gate": norm_init(next(k), (L, d, ff), d),
            "w_up": norm_init(next(k), (L, d, ff), d),
            "w_down": norm_init(next(k), (L, ff, d), ff),
        },
        "final_norm": jnp.ones((d,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(next(k), (d, v), d)
    return params


def param_logical_axes(cfg: LlamaConfig) -> Params:
    """Logical axis names per parameter, same tree structure as params.

    Names are resolved to mesh axes by ``parallel.sharding.logical_to_sharding``.
    """
    axes: Params = {
        "embed": ("vocab", "embed"),
        "blocks": {
            "ln1": ("layer", "embed"),
            "ln2": ("layer", "embed"),
            "wq": ("layer", "embed", "heads", "head_dim"),
            "wk": ("layer", "embed", "kv_heads", "head_dim"),
            "wv": ("layer", "embed", "kv_heads", "head_dim"),
            "wo": ("layer", "heads", "head_dim", "embed"),
            "w_gate": ("layer", "embed", "mlp"),
            "w_up": ("layer", "embed", "mlp"),
            "w_down": ("layer", "mlp", "embed"),
        },
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def remat_policy(cfg: LlamaConfig):
    """Resolve cfg.remat_policy to a jax checkpoint policy."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy != "none":
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; "
            f"expected 'none' or 'dots'")
    return jax.checkpoint_policies.nothing_saveable


@jax.named_scope("norm")
def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(dtype) * scale.astype(dtype)


def rope_frequencies(cfg: LlamaConfig, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embeddings. positions: [B, S] or [S]."""
    hd = cfg.head_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., S, hd/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, H, hd]; cos/sin: [B, S, hd/2] or [S, hd/2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:  # [S, hd/2] -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # [B, S, hd/2]
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _attention(q, k, v, cfg: LlamaConfig, mesh=None, rules=None,
               segment_ids=None):
    """Grouped-query causal attention; dispatches to ops.attention.

    With a mesh whose sequence mesh-axis (per the activation rule table,
    default ``seq -> sp``) is > 1, the sequence dimension is
    context-parallel: ring attention over that ring, circulating the
    unrepeated KV heads (see parallel.ring_attention). Otherwise local
    flash/XLA attention — under a mesh through ``ra.local_attention``,
    which shard_maps the flash kernel over the batch and heads axes.
    Mesh-axis names come from the rules table, never hardcoded here.
    """
    from skypilot_tpu.ops import attention as attn_ops
    if mesh is not None:
        from skypilot_tpu.parallel import ring_attention as ra
        from skypilot_tpu.parallel import sharding as sh
        rules = rules if rules is not None else sh.ACT_RULES
        seq_axis = rules.get("seq")
        heads_axis = rules.get("heads")
        hspec = heads_axis if isinstance(heads_axis, str) else None
        if (isinstance(seq_axis, str)
                and mesh.shape.get(seq_axis, 1) > 1
                and q.shape[1] % mesh.shape[seq_axis] == 0):
            # (seq not divisible by the ring size falls through to local
            # attention — same degrade-to-replicated convention as
            # spec_for.) Packed sequences ride the ring: segment ids
            # circulate with their K/V blocks.
            if rules.get("seq_layout") == "zigzag":
                # forward_hidden already put activations/positions/segs
                # in the zigzag layout (it owns the decision + permute).
                return ra.zigzag_ring_attention(
                    q, k, v, mesh, axis=seq_axis,
                    batch_axes=rules.get("batch"), heads_axis=hspec,
                    segment_ids=segment_ids)
            return ra.ring_attention(
                q, k, v, mesh, causal=True, axis=seq_axis,
                batch_axes=rules.get("batch"), heads_axis=hspec,
                segment_ids=segment_ids)
        return ra.local_attention(
            q, k, v, mesh, causal=True, batch_axes=rules.get("batch"),
            heads_axis=hspec, segment_ids=segment_ids)
    return attn_ops.gqa_attention(q, k, v, causal=True,
                                  segment_ids=segment_ids)


def decoder_layer(cfg: LlamaConfig, x: jax.Array, layer: Params,
                  cos: jax.Array, sin: jax.Array,
                  constrain=lambda x, axes: x, mesh=None,
                  rules=None, segment_ids=None) -> jax.Array:
    """One pre-norm decoder block. x: [B, S, D]."""
    B, S, D = x.shape
    h = rms_norm(x, layer["ln1"], cfg.norm_eps)
    with jax.named_scope("attn"):
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(cfg.dtype))
        k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(cfg.dtype))
        v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(cfg.dtype))
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        q = constrain(q, ("batch", "seq", "heads", "head_dim"))
        k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
        o = _attention(q, k, v, cfg, mesh, rules, segment_ids)
        o = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(cfg.dtype))
        x = x + constrain(o, ("batch", "seq", "embed"))

    h = rms_norm(x, layer["ln2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        g = jnp.einsum("bsd,df->bsf", h,
                       layer["w_gate"].astype(cfg.dtype))
        u = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(cfg.dtype))
        m = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                       layer["w_down"].astype(cfg.dtype))
        return x + constrain(m, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------

def forward_hidden(params: Params, tokens: jax.Array, cfg: LlamaConfig,
                   constrain=None, mesh=None, rules=None,
                   positions=None, segment_ids=None) -> jax.Array:
    """Token ids [B, S] -> final-norm hidden states [B, S, D] (cfg.dtype).

    ``positions`` [B, S] and ``segment_ids`` [B, S] enable packed
    sequences (per-document rope restart + segment attention masking;
    see data.input_pipeline).
    """
    if constrain is None:
        constrain = lambda x, axes: x

    B, S = tokens.shape
    tokens = constrain(tokens, ("batch", "seq"))
    # Lookup-friendly table layout: vocab stays sharded (canonical
    # order), embed replicated — the gather then propagates the token
    # sharding straight to [batch, seq, embed-replicated], which IS the
    # activation layout; no cross-layout transition (and no involuntary
    # full rematerialization from the SPMD partitioner).
    with jax.named_scope("embed"):
        table = constrain(params["embed"].astype(cfg.dtype),
                          ("vocab", "embed"))
        x = table[tokens]
        x = constrain(x, ("batch", "seq", "embed"))
    if positions is None:
        positions = jnp.arange(S)

    # Zigzag sequence layout (load-balanced causal ring, ~2x attention
    # FLOPs saving at large sp): permute embeddings + positions + seg
    # ids ONCE here, run every decoder block in the permuted order
    # (elementwise/matmul ops are order-agnostic; rope follows
    # positions), un-permute once at the end. Decided here so the
    # attention dispatch and the layout always agree.
    from skypilot_tpu.parallel import ring_attention as ra
    (x, positions, segment_ids, layer_rules, use_zigzag,
     n_sp) = ra.apply_zigzag_layout(x, positions, segment_ids, mesh, rules)
    cos, sin = rope_frequencies(cfg, positions)

    def body(carry, layer):
        y = decoder_layer(cfg, carry, layer, cos, sin, constrain, mesh,
                          layer_rules, segment_ids)
        return y, None

    if cfg.remat:
        body = jax.checkpoint(body, policy=remat_policy(cfg))

    x, _ = lax.scan(body, x, params["blocks"])
    if use_zigzag:
        x = ra.zigzag_unpermute(x, n_sp)
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def forward(params: Params, tokens: jax.Array, cfg: LlamaConfig,
            constrain=None, mesh=None, rules=None) -> jax.Array:
    """Token ids [B, S] -> logits [B, S, vocab] (float32).

    ``constrain`` is an optional fn(x, logical_axes) -> x applying
    ``with_sharding_constraint``; identity when running unsharded.
    ``mesh`` (+ optional activation ``rules``) enables the
    context-parallel attention path when the seq mesh-axis is > 1.
    """
    if constrain is None:
        constrain = lambda x, axes: x
    x = forward_hidden(params, tokens, cfg, constrain, mesh, rules)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits.astype(jnp.float32)


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: LlamaConfig,
            constrain=None, mesh=None,
            rules=None) -> tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy. batch: {"tokens": [B, S] int32,
    optionally "mask": [B, S] (1 = predict this position's *next* token)}.

    With ``cfg.xent_chunk`` > 0 the head matmul + softmax run chunked
    over the sequence under remat, so the [B, S, vocab] logits tensor
    never exists in HBM.
    """
    if constrain is None:
        constrain = lambda x, axes: x
    tokens = batch["tokens"]
    h = forward_hidden(params, tokens, cfg, constrain, mesh, rules,
                       positions=batch.get("positions"),
                       segment_ids=batch.get("segment_ids"))
    loss, acc, denom = xent_metrics(params, h, tokens,
                                    packed_loss_mask(batch), cfg,
                                    constrain)
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}


def packed_loss_mask(batch: Dict[str, jax.Array]):
    """Loss mask honoring packed segments: only within-document
    next-token transitions count (the last token of each segment has no
    target). Returns batch["mask"] unchanged when not packed."""
    mask = batch.get("mask")
    seg = batch.get("segment_ids")
    if seg is None:
        return mask
    same_next = (seg[:, :-1] == seg[:, 1:]) & (seg[:, :-1] > 0)
    pad = jnp.zeros((seg.shape[0], 1), bool)
    seg_mask = jnp.concatenate([same_next, pad], axis=1)
    return (seg_mask if mask is None
            else mask * seg_mask.astype(mask.dtype))


@jax.named_scope("xent")
def xent_metrics(params: Params, h: jax.Array, tokens: jax.Array,
                 mask: Optional[jax.Array], cfg: LlamaConfig,
                 constrain=lambda x, axes: x, head: Optional[jax.Array] = None):
    """Shared LM-head + next-token cross-entropy epilogue.

    h: final-norm hidden states [B, S, D]. Returns (loss, acc, denom).
    Honors ``cfg.xent_chunk`` (see LlamaConfig) — used by the llama,
    moe, pipeline, and qlora loss functions alike. ``head`` overrides
    the [D, V] head matrix (qlora passes a dequantized head; the slim
    param tree carries no fp lm_head).
    """
    if head is None:
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
    head = head.astype(cfg.dtype)
    if not cfg.xent_chunk:
        logits = jnp.einsum("bsd,dv->bsv", h, head)
        logits = constrain(logits, ("batch", "seq", "vocab"))
        logits = logits.astype(jnp.float32)[:, :-1]
        targets = tokens[:, 1:]
        logps = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logps, targets[..., None], axis=-1)[..., 0]
        m = (jnp.ones_like(ll) if mask is None
             else mask[:, :-1].astype(ll.dtype))
        denom = jnp.maximum(m.sum(), 1.0)
        loss = -(ll * m).sum() / denom
        acc = ((jnp.argmax(logits, -1) == targets) * m).sum() / denom
        return loss, acc, denom

    B, S = tokens.shape
    # Positions 0..S-2 predict targets 1..S-1. Pad to a chunk multiple
    # with masked-out positions.
    h = h[:, :-1]
    targets = tokens[:, 1:]
    m = (jnp.ones((B, S - 1), jnp.float32) if mask is None
         else mask[:, :-1].astype(jnp.float32))
    c = cfg.xent_chunk
    pad = (-(S - 1)) % c
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        m = jnp.pad(m, ((0, 0), (0, pad)))
    n_chunks = h.shape[1] // c
    h = h.reshape(B, n_chunks, c, -1).swapaxes(0, 1)       # [N,B,c,D]
    targets = targets.reshape(B, n_chunks, c).swapaxes(0, 1)
    m = m.reshape(B, n_chunks, c).swapaxes(0, 1)

    def chunk_body(carry, xs):
        ll_sum, correct = carry
        hc, tc, mc = xs
        logits = jnp.einsum("bcd,dv->bcv", hc, head)
        logits = constrain(logits, ("batch", "seq", "vocab"))
        logits = logits.astype(jnp.float32)
        logps = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logps, tc[..., None], axis=-1)[..., 0]
        ll_sum += (ll * mc).sum()
        correct += ((jnp.argmax(logits, -1) == tc) * mc).sum()
        return (ll_sum, correct), None

    body = jax.checkpoint(chunk_body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (ll_sum, correct), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (h, targets, m))
    denom = jnp.maximum(m.sum(), 1.0)
    return -ll_sum / denom, correct / denom, denom
