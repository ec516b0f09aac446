"""Attention ops: the Pallas flash kernel and the XLA einsum path.

The XLA path is a straightforward einsum softmax attention — XLA already
fuses the mask+softmax chain well on TPU for moderate sequence lengths.
The Pallas flash-attention kernel (``skypilot_tpu.ops.flash_attention``)
is used on TPU backends for longer sequences where materializing the
[B, H, S, S] score tensor would blow HBM.

Which of the two a program gets is decided from its SHAPES at trace
time and counted in ``skytpu_attention_traced_total``; a kernel that
then fails to compile fails the program — nothing here catches it and
retraces the einsum path behind the caller's back.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from skypilot_tpu.observability import metrics
from skypilot_tpu.ops import flash_attention as fa

# Sequence length at which the Pallas kernel wins over plain XLA (the
# score tensor stops fitting comfortably in VMEM-friendly fusion sizes).
_FLASH_MIN_SEQ = 1024

# One series per (implementation, shape) an attention call was TRACED
# with (the Python body runs at trace time only): the record of which
# kernels a process actually compiled. The series' existence is the
# record — a trace under metrics.suppress() (the server's warm-up)
# creates it without counting.
TRACED = metrics.counter(
    "skytpu_attention_traced_total",
    "Attention calls traced, by chosen implementation (flash = Pallas "
    "kernel, xla = einsum over materialized scores) and shape",
    ("impl", "seq", "heads", "head_dim"))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_eligible(seq: int, head_dim: int) -> bool:
    """Whether the flash kernels lower at this shape: Mosaic tiles
    head_dim in 128 lanes, and the sequence must split into blocks
    that are multiples of 128 rows (``fa.fit_block`` then always finds
    a divisor). Below ``_FLASH_MIN_SEQ`` the einsum path is the faster
    one, so short sequences stay there by choice."""
    return (seq >= _FLASH_MIN_SEQ and head_dim % fa.LANES == 0
            and seq % fa.LANES == 0)


def uses_flash(seq: int, head_dim: int, impl: str = "auto") -> bool:
    """The trace-time decision :func:`gqa_attention` makes (env
    override: SKYTPU_ATTN_IMPL). Callers that must know it first ask
    here: a Mosaic kernel cannot be partitioned by the SPMD compiler,
    so under a multi-device mesh the flash call has to sit inside a
    ``shard_map`` (``parallel.ring_attention.local_attention``)."""
    impl = os.environ.get("SKYTPU_ATTN_IMPL", impl)
    return (impl == "flash" or
            (impl == "auto" and _on_tpu()
             and flash_eligible(seq, head_dim)))


def traced_impls() -> list:
    """["flash@2048x16x128", ...] — every (implementation, seq x heads
    x head_dim) this process has traced an attention call with."""
    return sorted(f"{impl}@{seq}x{heads}x{hd}"
                  for (impl, seq, heads, hd), _ in TRACED.children())


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] for grouped-query attention."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  segment_ids: jax.Array | None = None) -> jax.Array:
    """Reference einsum attention. q: [B, S, H, D]; k/v: [B, S, H, D].

    ``segment_ids`` [B, S] (0 = padding): packed-sequence masking —
    a position only attends within its own segment.
    """
    *_, d = q.shape
    scale = d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        scores = jnp.where(mask, scores, -1e30)
    if segment_ids is not None:
        same = (segment_ids[:, None, :, None]
                == segment_ids[:, None, None, :])   # [B, 1, Sq, Sk]
        scores = jnp.where(same, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True, impl: str = "auto",
                  segment_ids: jax.Array | None = None) -> jax.Array:
    """Grouped-query attention. q: [B, S, Hq, D]; k/v: [B, S, Hkv, D].

    impl: "auto" | "flash" | "xla" (env override: SKYTPU_ATTN_IMPL).
    ``segment_ids`` [B, S] (packed sequences) is supported on both
    paths: the Pallas flash kernel masks segments in-block (lane-tiled
    compare), the XLA fallback masks on the materialized scores.
    """
    n_rep = q.shape[2] // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    _, seq, heads, head_dim = q.shape
    use_flash = uses_flash(seq, head_dim, impl)
    TRACED.labels("flash" if use_flash else "xla", str(seq), str(heads),
                  str(head_dim)).inc()
    if use_flash:
        return fa.flash_attention(q, k, v, causal=causal,
                                  segment_ids=segment_ids)
    return xla_attention(q, k, v, causal=causal,
                         segment_ids=segment_ids)
