"""Grouped SwiGLU: the routed experts' three products over token-choices
sorted by expert, as ONE Pallas kernel (``grouped_swiglu``).

``xs`` [M, D] holds the M = T x K token-choices of a prefill chunk or
wave, sorted so that expert ``e``'s rows are ``offsets[e] :
offsets[e + 1]``. The expert matrices arrive as the WHOLE stack
``[layers * E, D, F]`` / ``[layers * E, F, D]`` and are read where they
lie, at ``expert_base + e``: nothing is sliced or copied. The result is
``silu(x W_gate) * (x W_up) @ W_down`` a row, [M, D] in ``xs``' dtype —
the operands as given (bf16 in a served model), every sum in float32.

How it walks (after ``jax.experimental.pallas.ops.tpu.megablox``): the
rows are cut into tiles of ``tm``, and the grid's first axis is the
list of VISITS — a (group, row tile) pair for every tile a non-empty
group has rows in, in row order; an expert nobody chose has no visit
and costs no DMA. Group offsets, the visit lists and ``expert_base``
are scalar-prefetched; the index maps send each visit the row tile and
the expert's weight blocks. The second axis cuts F into blocks of
``tf``: a step holds ``W_gate[:, f]``, ``W_up[:, f]`` and ``W_down[f,
:]`` of one expert, forms ``silu(g) * u`` for that slice of F from the
float32 products in VMEM and adds its down product into a float32
[tm, D] accumulator; the last F block casts and stores. ``g`` and ``u``
never reach HBM.

Inside a visit only the group's own rows are multiplied: a loop with a
dynamic trip count takes ``ROW_CHUNK`` rows at a time from the group's
first row (rounded down to the sublane tile) to its last, and a row
mask keeps the neighbours' rows in the tile as they were. At ~32 rows
a group that is one chunk a visit, and the time is the weights' DMA.

An expert is read once a visit, so once — unless its group straddles a
row-tile boundary (at most ``M / tm - 1`` groups do), which is why
``tm`` is as large as VMEM allows. The tiles come from the shapes
(:func:`tiles_for`), which also says when the kernel cannot take a
call (rows, D or F that are no whole tile): the caller then keeps
``lax.ragged_dot``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Rows one product takes: the MXU's height. Fewer rows cost the same
# (a weight tile's load is what a short product waits for), more rows
# than a group has are wasted.
ROW_CHUNK = 128
# A dynamic row offset into a VMEM block must sit on a packed sublane
# tile: 16 rows of bf16 (and a multiple of float32's 8).
ROW_ALIGN = 16
# Candidates, largest first. The row tile bounds how many groups are
# read twice (those that straddle a tile boundary); the F tile is the
# weight block one grid step streams.
_ROW_TILES = (1024, 512, 256, 128)
# What the tiles may take of VMEM (v5e: 128 MiB a core), blocks double
# buffered; the call states its own limit from the same sum.
_VMEM_BUDGET = 40 << 20
# One weight block: large enough that the DMA and not the grid step's
# fixed cost sets the time, small enough to double-buffer three.
_WEIGHT_BLOCK_BYTES = 2 << 20
# Pallas interpret mode for every call that does not pass
# ``interpret=`` itself (``ops.paged_attention.INTERPRET``'s rule: the
# CPU test suite turns it on in tests/conftest.py, the backend's name
# never does).
INTERPRET = False


def _vmem_bytes(tm: int, tf: int, d: int, itemsize: int) -> int:
    """Blocks (double buffered), the accumulator and one chunk's
    float32 temporaries."""
    blocks = 2 * (2 * tm * d + 3 * d * tf) * itemsize
    temps = ROW_CHUNK * (2 * d + 3 * tf) * 4
    return blocks + tm * d * 4 + temps


def tiles_for(m: int, d: int, f: int, itemsize: int = 2
              ) -> Optional[Tuple[int, int]]:
    """``(tm, tf)`` for M rows at widths D and F, or None where the
    kernel cannot take the call: M, D or F is no whole tile (128 rows;
    128 lanes)."""
    if m <= 0 or m % ROW_CHUNK or d % LANES or f % LANES:
        return None
    tf = max((t for t in range(LANES, f + 1, LANES)
              if f % t == 0 and d * t * itemsize <= _WEIGHT_BLOCK_BYTES),
             default=LANES)
    for tm in _ROW_TILES:
        if m % tm == 0 and _vmem_bytes(tm, tf, d, itemsize) <= _VMEM_BUDGET:
            return tm, tf
    return None


def visits(offsets: jax.Array, tm: int, tiles_m: int):
    """The visit lists of groups ``offsets`` [E + 1] over row tiles of
    ``tm``: ``(group [V], tile [V], n)`` with ``V = tiles_m + E - 1``
    (every tile once, and once more for each group boundary inside
    one); the first ``n`` entries are meant. Visits are in row order, a
    tile's visits consecutive; an empty group has none."""
    E = offsets.shape[0] - 1
    lo, hi = offsets[:-1], offsets[1:]
    first = lo // tm
    count = jnp.where(hi > lo, (hi - 1) // tm - first + 1, 0)
    ends = jnp.cumsum(count)
    v = jnp.arange(tiles_m + E - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(ends[None, :] <= v[:, None], axis=1), E - 1
    ).astype(jnp.int32)
    tile = first[group] + v - (ends[group] - count[group])
    return group, jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32), ends[-1]


def _kernel(offs_ref, group_ref, tile_ref, base_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, acc_ref, *, tm: int, n_f: int):
    del base_ref                       # the index maps' argument
    v, j = pl.program_id(0), pl.program_id(1)
    g, row0 = group_ref[v], tile_ref[v] * tm
    # The group's rows inside this tile, tile-relative.
    lo = jnp.maximum(offs_ref[g] - row0, 0)
    hi = jnp.minimum(offs_ref[g + 1] - row0, tm)
    lo_al = lo // ROW_ALIGN * ROW_ALIGN
    rc = ROW_CHUNK

    def chunk(c, carry):
        want = lo_al + c * rc          # the rows this turn is for ...
        at = pl.multiple_of(jnp.minimum(want, tm - rc), ROW_ALIGN)
        rows = pl.ds(at, rc)           # ... inside the tile
        x = x_ref[rows, :]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        act = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        part = jnp.dot(act, wd_ref[...], preferred_element_type=jnp.float32)
        r = at + lax.broadcasted_iota(jnp.int32, part.shape, 0)
        mine = (r >= jnp.maximum(lo, want)) & (r < jnp.minimum(hi, want + rc))
        held = acc_ref[rows, :]
        total = jnp.where(j == 0, part, held + part)
        acc_ref[rows, :] = jnp.where(mine, total, held)

        @pl.when(j == n_f - 1)
        def _store():
            o_ref[rows, :] = jnp.where(mine, total.astype(o_ref.dtype),
                                       o_ref[rows, :])
        return carry

    lax.fori_loop(0, (hi - lo_al + rc - 1) // rc, chunk, 0)


def grouped_swiglu(xs: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   w_down: jax.Array, offsets: jax.Array, expert_base,
                   *, tiles: Optional[Tuple[int, int]] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """xs [M, D] sorted by expert; ``w_gate`` / ``w_up`` [G, D, F] and
    ``w_down`` [G, F, D] the whole stack; ``offsets`` [E + 1] int32
    (``offsets[e]`` the first row of expert ``e`` of THIS layer,
    ``offsets[E] == M``); ``expert_base`` (int32 scalar, may be traced)
    the stack index of this layer's expert 0. ``tiles`` ``(tm, tf)``:
    :func:`tiles_for`'s unless given. Returns [M, D] in ``xs.dtype``."""
    if interpret is None:
        interpret = INTERPRET
    if tiles is None:
        tiles = tiles_for(xs.shape[0], xs.shape[1], w_gate.shape[-1],
                          xs.dtype.itemsize)
    return _call(xs, w_gate, w_up, w_down, offsets.astype(jnp.int32),
                 jnp.asarray(expert_base, jnp.int32), tiles=tiles,
                 interpret=interpret)


# A jitted function of its own: the layers of an unrolled stack then
# share one trace and one lowering of the kernel a program.
@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _call(xs, w_gate, w_up, w_down, offsets, expert_base, *, tiles,
          interpret):
    M, D = xs.shape
    F = w_gate.shape[-1]
    E = offsets.shape[0] - 1
    tm, tf = tiles                     # tm a multiple of ROW_CHUNK
    n_f = F // tf
    group, tile, n = visits(offsets, tm, M // tm)
    base = jnp.reshape(expert_base, (1,))

    rows_at = lambda v, j, offs, gr, ti, b: (ti[v], 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n, n_f),
        in_specs=[
            pl.BlockSpec((tm, D), rows_at),
            pl.BlockSpec((None, D, tf),
                         lambda v, j, offs, gr, ti, b: (b[0] + gr[v], 0, j)),
            pl.BlockSpec((None, D, tf),
                         lambda v, j, offs, gr, ti, b: (b[0] + gr[v], 0, j)),
            pl.BlockSpec((None, tf, D),
                         lambda v, j, offs, gr, ti, b: (b[0] + gr[v], j, 0)),
        ],
        out_specs=pl.BlockSpec((tm, D), rows_at),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)],
    )
    itemsize = xs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, n_f=n_f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, D), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(tm, tf, D, itemsize) + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=6 * M * D * F, transcendentals=M * F,
            bytes_accessed=(3 * E * D * F + 2 * M * D) * itemsize),
        interpret=interpret,
        name="grouped_swiglu",
    )(offsets, group, tile, base, xs, w_gate, w_up, w_down)
