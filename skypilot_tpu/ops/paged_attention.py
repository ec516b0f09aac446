"""Paged decode-attention as a Pallas TPU kernel.

The PagedAttention idea (vLLM / the JAX TPU serving stack) built in
this repo's Pallas idiom (``ops/flash_attention.py``): decode/verify/
chunk attention over the paged KV block pool WITHOUT materializing the
gathered ``[slots, span, G, hd]`` logical view per layer — the last
copy standing between the serving engine and memory-bandwidth-bound
decode (docs/serving.md §Paged KV cache named it as the residual gap).

Design (per the pallas TPU playbook):

* Grid ``(slots, row_tiles, span_blocks)``; the kernel walks each
  slot's BLOCK TABLE directly via scalar-prefetch index maps
  (``pltpu.PrefetchScalarGridSpec``): the block-table row and the
  per-slot lengths are prefetched to SMEM, and the K/V pool's
  BlockSpec index map reads ``table[slot, j]`` to DMA the j-th
  *logical* block's *physical* rows straight from HBM — no gather, no
  transient. Sentinel entries (logical blocks past the slot's
  allocation) clamp to physical block 0; their compute is skipped.
* One grid cell takes a physical block WHOLE — all ``G`` kv-heads,
  ``[block_len, G, hd]`` — and loops over the heads inside the
  kernel. The pool keeps ``G`` in its second-to-last dimension, and
  Mosaic only lowers a block whose last two dimensions are the
  array's own (or multiples of the (8, 128) tile): a one-head block
  ``(…, 1, hd)`` is refused by the TPU lowering whether the head
  dimension is blocked at 1 or squeezed.
* Query rows are tiled (``ROW_TILE``) so the chunk path's ``C * rep``
  rows per head keep the f32 accumulators inside scoped VMEM; the
  decode and verify paths are one tile.
* The layer index rides the same scalar-prefetch channel, so the one
  kernel serves every layer of the ``lax.scan`` without slicing a
  per-layer pool copy (which would be a bigger transient than the
  gather it replaces).
* The KV sweep is the innermost grid dimension with the online-softmax
  running (max, sum, acc) carried in VMEM scratch across grid steps —
  the FlashAttention-2 accumulation, initialized at block 0 and
  written out at the last block. A block whose start row is past the
  slot's length is skipped whole (the span-rung ladder bounds the
  grid; the length bounds the work).
* int8 KV dequantizes IN KERNEL from the pool's per-(block, head, row)
  scale tensors: K's scale applies to the scores, V's folds into the
  softmax weights — bit-for-bit the factorization the XLA gather path
  uses, so nothing dequantized at cache shape ever exists.

The kernel returns UNNORMALIZED partial-softmax stats ``(acc, m, l)``
rather than finished attention: the caller merges them with the
staged-columns block (the in-burst K/V rows that live outside the big
cache) via the standard two-block online-softmax combine
(``kvcache._merge_attn_parts``). The merged output equals the XLA
gather path's up to summation order — greedy parity (not bit parity)
is the contract, asserted against the gather oracle in
tests/test_paged_attention.py across dtypes, spec modes and span
rungs.

The kernel compiles to Mosaic unless told otherwise: ``INTERPRET``
below is the one switch, and only the CPU test suite
(``tests/conftest.py``) turns it on — the backend name never decides.
Rows-per-head is ``rep = n_heads // n_kv_heads`` on the decode path —
small tiles that Mosaic pads; the chunk path batches ``C * rep`` rows
per head and amortizes properly.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128     # lane-replicated rowwise stats (Mosaic tiling)
NEG_INF = -1e30
# Query rows per grid cell (per kv-head). The f32 accumulators
# [G, rows, hd] + two lane-replicated stats of the same size, double
# buffered as outputs and once more as scratch, must fit the 16 MB of
# scoped VMEM next to the K/V blocks: 128 rows at G=8, hd=128 is
# ~4.5 MB.
ROW_TILE = 128
# Pallas interpret mode for every call that does not pass
# ``interpret=`` itself. False = compile with Mosaic, on any backend
# (a CPU backend then fails loudly instead of quietly interpreting).
# The CPU test suite sets it True in tests/conftest.py.
INTERPRET = False


def _kernel(layer_ref, table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
            block_len: int, span_blocks: int, scale: float,
            quant: bool, n_kv_heads: int):
    if quant:
        ks_ref, vs_ref, acc_ref, m_ref, l_ref, acc_s, m_s, l_s = rest
    else:
        acc_ref, m_ref, l_ref, acc_s, m_s, l_s = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    length = len_ref[b]

    # A block whose first row is past the slot's length holds nothing
    # the mask admits (sentinel table entries always land here: a
    # slot's length never exceeds its allocated rows) — skip the whole
    # block, the causal-pruning idiom of the flash kernel.
    @pl.when(j * block_len < length)
    def _process():
        for g in range(n_kv_heads):
            q = q_ref[g].astype(jnp.float32) * scale      # [R, hd]
            k = k_ref[:, g, :].astype(jnp.float32)        # [bl, hd]
            v = v_ref[:, g, :].astype(jnp.float32)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            if quant:
                s = s * ks_ref[g, :].astype(jnp.float32)[None, :]
            col = j * block_len + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(col < length, s, NEG_INF)
            m_prev = m_s[g, :, :1]                        # [R, 1]
            l_prev = l_s[g, :, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                        # [R, bl]
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * vs_ref[g, :].astype(jnp.float32)[None, :]
            acc_s[g] = acc_s[g] * alpha + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[g] = jnp.broadcast_to(m_new, m_s.shape[1:])
            l_s[g] = jnp.broadcast_to(l_new, l_s.shape[1:])

    @pl.when(j == span_blocks - 1)
    def _emit():
        acc_ref[...] = acc_s[...]
        m_ref[...] = m_s[...]
        l_ref[...] = l_s[...]


def _row_tile(rows: int) -> int:
    """Largest tile <= ROW_TILE that divides ``rows`` and keeps the
    (8, 128) tiling rule (a multiple of 8, or the whole axis)."""
    if rows <= ROW_TILE:
        return rows
    for t in range(ROW_TILE, 7, -8):
        if rows % t == 0:
            return t
    return rows


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    k_scale: Optional[jax.Array],
                    v_scale: Optional[jax.Array],
                    table: jax.Array, lengths: jax.Array,
                    layer: jax.Array, *, span_blocks: int,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Block-table-native online-softmax attention over the paged pool.

    q: ``[B, G, R, hd]`` query rows per (slot, kv-head) — ``R`` is the
    GQA repeat on the decode path, ``C * rep`` on the chunk path.
    k_pool/v_pool: the full pool ``[L, n_blocks, block_len, G, hd]``
    (fp, or int8 with ``k_scale``/``v_scale`` ``[L, n_blocks, G,
    block_len]``). table: ``[B, nb+1]`` int32 per-slot block tables
    (sentinel == n_blocks). lengths: ``[B]`` int32 — the score mask is
    ``col < lengths[b]``, the burst-start validity rule. layer:
    traced int32 scalar selecting the pool's layer via scalar
    prefetch. ``span_blocks`` (static): logical blocks to sweep — the
    span-rung ladder divided by the block length, so the block loop is
    span-bounded exactly like the gather path's table prefix.

    Returns unnormalized stats ``(acc [B,G,R,hd] f32, m [B,G,R] f32,
    l [B,G,R] f32)``: ``acc`` is sum(p * v) with V's dequant scale
    folded in, ``m`` the running row max, ``l`` sum(p). A slot whose
    every block was masked (length 0) reports ``m == -1e30`` and the
    caller's merge annihilates its contribution.
    """
    if interpret is None:
        interpret = INTERPRET
    B, G, R, hd = q.shape
    rt = _row_tile(R)
    n_blocks, bl = k_pool.shape[1], k_pool.shape[2]
    quant = k_scale is not None
    scale = hd ** -0.5

    # Scalar-prefetch operands (SMEM): layer index, block tables,
    # lengths. Index maps read them to route each grid cell's DMA to
    # the right physical block — sentinel (and any overflow) entries
    # clamp to physical block 0: a harmless fetch whose compute the
    # kernel skips (block start >= length).
    layer_arr = jnp.reshape(layer, (1,)).astype(jnp.int32)
    table = table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def phys(tr, b, j):
        t = tr[b, j]
        return jnp.where(t >= n_blocks, 0, t)

    # None = squeezed: the kernel sees [bl, G, hd] / [G, rows, hd].
    kv_spec = pl.BlockSpec(
        (None, None, bl, G, hd),
        lambda b, r, j, lr, tr, ln: (lr[0], phys(tr, b, j), 0, 0, 0))
    in_specs = [
        pl.BlockSpec((None, G, rt, hd),
                     lambda b, r, j, lr, tr, ln: (b, 0, r, 0)),
        kv_spec, kv_spec,
    ]
    args = [q, k_pool, v_pool]
    if quant:
        sc_spec = pl.BlockSpec(
            (None, None, G, bl),
            lambda b, r, j, lr, tr, ln: (lr[0], phys(tr, b, j), 0, 0))
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]

    out_spec = lambda b, r, j, lr, tr, ln: (b, 0, r, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, R // rt, span_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, G, rt, hd), out_spec),
            pl.BlockSpec((None, G, rt, LANES), out_spec),
            pl.BlockSpec((None, G, rt, LANES), out_spec),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, rt, hd), jnp.float32),
            pltpu.VMEM((G, rt, LANES), jnp.float32),
            pltpu.VMEM((G, rt, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, block_len=bl, span_blocks=span_blocks, scale=scale,
        quant=quant, n_kv_heads=G)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, G, R, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, G, R, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, G, R, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="paged_decode",
    )(layer_arr, table, lengths, *args)
    # Stats are lane-replicated (the Mosaic tiling idiom); one lane is
    # the value.
    return acc, m[..., 0], l[..., 0]
