"""Serve programs of the hybrid family (``models/olmo_hybrid.py``): full
softmax-attention layers whose K/V rows live in the paged block pool,
beside linear-attention (gated delta rule) layers that hold a FIXED-size
recurrent state per slot.

The siblings of ``kvcache.py``'s and ``latent.py``'s programs, with the
same signatures, so the engine's jitted entry points call this module
through ``kvcache.programs_for(cfg)``; same block pool, block table,
sentinel column, span ladder and staging discipline for the K/V rows.

Layout: ``k``, ``v`` ``[L_full, blocks, block_len, pool heads, hd]`` in
the compute dtype — the layer axis covers the FULL-attention layers
only, and the heads axis is ``n_kv_heads`` rounded up to a whole tile
(:func:`pool_heads`) — plus, per linear layer and SLOT (not per block: :data:`SLOT_STATE`),
``state`` ``[L_lin, slots, H, d_v, d_k]`` float32 and ``conv`` ``[L_lin,
slots, K - 1, conv_channels]`` (the convolution's last inputs). What
follows from a state that is no row:

* it cannot be shared by block or cut to a prefix, so the family runs
  without the prefix pool, the handoff and copy-on-write
  (:data:`UNSUPPORTED`); a preempted or recovered request re-prefills
  its whole context, which rebuilds the state;
* a slot rented again starts from zero: a wave's ``insert`` overwrites
  the slot's state, and a chunk at ``start == 0`` ignores what the slot
  holds (there is no reset program);
* a chunk at ``start > 0`` CONTINUES the slot's resident state and
  tail, and a padded wave row or a padded last chunk leaves the state
  and the tail of its last REAL token (pad tokens neither decay nor
  write);
* a decode program updates the state of its LIVE slots only, a tile of
  slots a turn (``kvcache._live_tiles``), carried through the ``k``
  steps of a burst: a slot that is mid-prefill, free or the spare keeps
  what it holds.

The full layers' decode attention reads a live slot's resident rows IN
PLACE, a block a turn (:func:`_attend_in_place`: once for the scores,
once for the weighted values), where the other families gather a copy
first: with 30 key/value heads a slot's rows are 16 KB a token and layer,
and copying them cost more than attending them. The read is bounded by
RESIDENCY (``DECODE_READS_BLOCKS_HELD``): a live slot's blocks up to the
rows it holds and a dead slot's not at all, whatever the program's span
rung — one long request sets the rung for every slot of the round, and
a tile is padded to whole slots. A prefill chunk's 512
query rows attend a gathered copy of the one slot's rows
(:func:`_gather_kv`, in pieces of 1 MiB).

Paged layout only; no int8 rows or weights, no adapters, no tensor
parallelism, no speculative verify, no paged-attention kernel
(``engine.refuse_hybrid_options``; ``docs/serving.md`` section Recurrent
state).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.infer import kvcache
from skypilot_tpu.infer import sampling as sampling_mod
from skypilot_tpu.models import olmo_hybrid as oh
from skypilot_tpu.observability import attribution
from skypilot_tpu.ops import gated_delta as gd

Cache = kvcache.Cache

SPARE_COLUMN = None

# This family's answers to the engine (``kvcache.programs_for``).
FAMILY = "hybrid (recurrent state + paged KV)"
SLOT_STATE = ("state", "conv")
DECODE_READS_BLOCKS_HELD = True
_NO_BOUNDARY = ("a shared block holds K/V rows but no recurrent state at "
                "its boundary")
UNSUPPORTED = {
    "prefix_pool": _NO_BOUNDARY,
    "import_prefix": _NO_BOUNDARY,
    "export_prefix": _NO_BOUNDARY,
    "kv_block=0": "the full-attention layers' cache is paged only",
    "kv_int8": "no int8 rows beside a float32 recurrent state",
    "weights_int8": "the linear mixer's matrices have no int8 form",
    "tp": "no recurrent state under a mesh",
    "adapters": "no LoRA targets in the linear mixer",
    "spec_k": "a rejected draft cannot roll a recurrent state back",
    "draft_model": "a rejected draft cannot roll a recurrent state back",
    "kv_kernel": "the paged-attention kernel is not wired to this family",
}


def ring_rows(cfg) -> None:
    """No window layers: no ring (see ``kvcache.programs_for``)."""
    return None


# Rows of a (bf16) tile: the pool's heads axis is second-minor.
_HEAD_TILE = 16


def pool_heads(cfg: oh.OlmoHybridConfig) -> int:
    """Heads a pool row holds: ``n_kv_heads`` rounded up to whole tiles,
    the extra ones zero and never read. With 30 heads second-minor the
    TPU compiler keeps the pool in another layout than the one it is
    handed in (rows second-minor) and copies it whole, there and back,
    in every program; 32 it leaves where they lie."""
    return -(-cfg.n_kv_heads // _HEAD_TILE) * _HEAD_TILE


def init_paged_cache(cfg: oh.OlmoHybridConfig, n_slots: int, n_blocks: int,
                     block_len: int, kv_int8: bool = False) -> Cache:
    """``kvcache.init_paged_cache``'s sibling: the block pool holds the
    full-attention layers' rows; every slot holds a state and a
    convolution tail per linear layer."""
    if kv_int8:
        raise NotImplementedError("the hybrid cache has no int8 rows")
    kv = (cfg.n_full_layers, n_blocks, block_len, pool_heads(cfg),
          cfg.head_dim)
    L = cfg.n_lin_layers
    return {
        "length": jnp.zeros((n_slots,), jnp.int32),
        "last_token": jnp.zeros((n_slots,), jnp.int32),
        "k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
        "state": jnp.zeros((L, n_slots, cfg.lin_heads, cfg.lin_v_dim,
                            cfg.lin_k_dim), jnp.float32),
        "conv": jnp.zeros((L, n_slots, cfg.conv_kernel - 1,
                           cfg.conv_channels), cfg.dtype)}


def token_bytes(cfg: oh.OlmoHybridConfig, cache=None) -> int:
    """Cache bytes a token holds: K and V in the full layers only (the
    pool's heads: :func:`pool_heads`)."""
    return cfg.n_full_layers * 2 * pool_heads(cfg) * cfg.head_dim \
        * jnp.dtype(cfg.dtype).itemsize


def slot_state_bytes(cfg: oh.OlmoHybridConfig) -> int:
    """Bytes ONE slot's recurrent state and tails hold, all layers."""
    state = cfg.lin_heads * cfg.lin_v_dim * cfg.lin_k_dim * 4
    tail = (cfg.conv_kernel - 1) * cfg.conv_channels \
        * jnp.dtype(cfg.dtype).itemsize
    return cfg.n_lin_layers * (state + tail)


def hbm_rows(cache: Cache, params) -> Dict[str, int]:
    """The HBM ledger's rows: the pool as the GQA family's, and what
    the slots hold whatever their length."""
    state = attribution.tensor_bytes([cache[n] for n in SLOT_STATE])
    return {"kv_pool": attribution.tensor_bytes(cache) - state,
            "recurrent_state": state}


def roofline_dims(cfg: oh.OlmoHybridConfig) -> Dict[str, int]:
    """Attention happens in the full layers only."""
    return {"param_count": cfg.num_params(), "n_layers": cfg.n_full_layers,
            "n_heads": cfg.n_heads, "head_dim": cfg.head_dim}


def _need_table(table):
    if table is None:
        raise NotImplementedError(
            "the hybrid cache is paged only (no contiguous layout)")


def _no_extras(qweights, lora, kv_kernel=False):
    if qweights is not None or lora is not None or kv_kernel:
        raise NotImplementedError(
            "the hybrid family serves float weights without adapters or "
            "the paged-attention kernel")


# Indices one scatter of the flush takes (``latent._SCATTER_ROWS``: the
# TPU compiler unrolls a longer scatter row by row).
_SCATTER_ROWS = 512


def _flush_rows(cache: Cache, table, slots, idx, k_rows, v_rows) -> Cache:
    """K/V rows ``[L_full, *I, G, hd]`` land at logical ``(slots, idx)``
    (arrays that broadcast to one shape ``I``) through the block table;
    sentinel / overflow coordinates drop. The pool is written in place,
    a layer and :data:`_SCATTER_ROWS` rows a turn (``latent._append_rows``'
    loop): ``kvcache._write_rows``' one scatter with the layer as a
    window dim makes the compiler re-lay this pool — 4 layers of 30
    heads — with the layer second-minor and copy it whole, twice a
    tensor, in every program."""
    blk, off = kvcache._phys(cache, table, slots, idx)
    blk, off = jnp.broadcast_arrays(blk, off)
    L, n_blocks = cache["k"].shape[:2]
    n = blk.size
    pieces = -(-n // _SCATTER_ROWS)
    width = min(n, _SCATTER_ROWS)
    pad = pieces * width - n
    blk = jnp.pad(blk.reshape(-1), (0, pad), constant_values=n_blocks)
    off = jnp.pad(off.reshape(-1), (0, pad))
    heads = cache["k"].shape[3]
    rows = [jnp.pad(r.reshape((L, n) + r.shape[-2:]),
                    ((0, 0), (0, pad), (0, heads - r.shape[-2]), (0, 0)))
            for r in (k_rows, v_rows)]

    def turn(t, pools):
        layer, at = t // pieces, (t % pieces) * width
        b = lax.dynamic_slice_in_dim(blk, at, width)
        o = lax.dynamic_slice_in_dim(off, at, width)
        return tuple(
            pool.at[layer, b, o].set(lax.dynamic_slice(
                r, (layer, at, 0, 0), (1, width) + r.shape[2:])[0].astype(
                    pool.dtype))
            for pool, r in zip(pools, rows))

    out = dict(cache)
    out["k"], out["v"] = lax.fori_loop(
        0, L * pieces, turn, (cache["k"], cache["v"]))
    return out


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill_batch(params, tokens, true_lens, cfg: oh.OlmoHybridConfig,
                  qweights=None, lora=None, aid=None,
                  mesh=None, heads_axis=None) -> Tuple[Cache, jax.Array]:
    """Causal forward over a WAVE of right-padded prompts [W, S].
    Returns (``{"k", "v": [L_full, W, S, G, hd], "state": [L_lin, W, H,
    d_v, d_k], "conv": [L_lin, W, K - 1, C]}`` — each row's state and
    tail after ITS last real token —, logits at each request's last real
    position [W, vocab] float32)."""
    _no_extras(qweights, lora)
    x, rows = oh.forward_hidden(params, tokens, cfg, true_lens, mesh,
                                heads_axis)
    last = jnp.take_along_axis(
        x, (true_lens - 1)[:, None, None], axis=1)[:, 0]           # [W, D]
    return rows, oh.head_logits(cfg, params, last)


@jax.named_scope("kv_write")
def insert(cache: Cache, prefix: Cache, slot, true_len, first_token,
           table=None) -> Cache:
    """Install one prefilled prompt into a slot: K/V rows [L_full, S, G,
    hd] through its table row, the state and the tail whole (whatever
    the slot's last tenant left is overwritten). The spare slot's
    all-sentinel row drops a dummy wave row's K/V; its state lands in
    the spare's own entry, which nobody reads."""
    _need_table(table)
    out = _flush_rows(cache, table, slot, jnp.arange(prefix["k"].shape[1]),
                      prefix["k"], prefix["v"])
    out["state"] = cache["state"].at[:, slot].set(prefix["state"])
    out["conv"] = cache["conv"].at[:, slot].set(
        prefix["conv"].astype(cache["conv"].dtype))
    out["length"] = cache["length"].at[slot].set(true_len)
    out["last_token"] = cache["last_token"].at[slot].set(first_token)
    return out


# Bytes one window of the K/V gather may hold. The TPU compiler lowers a
# gather of larger windows by first SLICING ITS WHOLE OPERAND — the pool,
# every layer — into halves of the window and writing both out: a block
# of 256 rows x 32 heads x 128 is 2 MiB, and every tile of every full
# layer of every step then copied the 1.6 GB pool, K and V (49 of a
# decode step's 99 ms on the v5e). A window of 1 MiB it gathers as is.
_GATHER_WINDOW_BYTES = 1 << 20


def _span_blocks(cache: Cache, table_rows, span) -> int:
    """Blocks of a slot's table row that cover its first ``span`` logical
    rows (all of them without a span)."""
    if span is None:
        return table_rows.shape[1] - 1
    return -(-span // cache["k"].shape[2])


@jax.named_scope("kv_gather")
def _gather_kv(cfg, cache: Cache, fi, table_rows, span):
    """Full layer ``fi``'s K and V of the slots whose table rows are
    ``table_rows`` [T, nb + 1]: [T, M, n_kv_heads, hd] each, the first
    ``span`` logical rows (all of them without one) —
    ``kvcache._gather_kv_layer``'s read (whole blocks of the table
    prefix straight out of the pool seen flat, then cut to the span),
    with each block fetched in PIECES of at most
    :data:`_GATHER_WINDOW_BYTES` — halves of a block until one fits: the
    pools seen as ``[L * blocks * parts, piece, heads, hd]`` — and cut
    to the heads that are real. (A sentinel id gathers the next layer's
    first block, or clamps: garbage the caller's mask never admits.)"""
    L, n_blocks, bl, heads, hd = cache["k"].shape
    nb = _span_blocks(cache, table_rows, span)
    row_bytes = heads * hd * cache["k"].dtype.itemsize
    piece = bl
    while piece % 2 == 0 and piece * row_bytes > _GATHER_WINDOW_BYTES:
        piece //= 2
    parts = bl // piece
    at = (fi * n_blocks + table_rows[:, :nb])[:, :, None] * parts \
        + jnp.arange(parts)
    flat = [cache[n].reshape(L * n_blocks * parts, piece, heads, hd)
            for n in ("k", "v")]
    at = at.reshape(at.shape[0], nb * parts)
    rows = nb * bl if span is None else span
    return [pool[at].reshape(at.shape[0], nb * bl, heads, hd)[
        :, :rows, :cfg.n_kv_heads] for pool in flat]


def _blocks_held(cache: Cache, rows, span_blocks: int):
    """Blocks that hold a slot's first ``rows`` rows (any shape), at
    most the ``span_blocks`` the program's span covers."""
    return jnp.minimum(-(-rows // cache["k"].shape[2]), span_blocks)


def _attend_in_place(cfg, cache: Cache, fi, table_rows, span, q, held,
                     staged_k, staged_v, staged_mask):
    """Decode attention of one tile of slots WITHOUT a copy of their
    rows: ``q`` [T, 1, n_heads, hd] over each slot's resident rows in
    full layer ``fi``, read out of the pool a block a turn — once for
    the scores, once, after the softmax, for the weighted values — and
    over the staged columns ``staged_k`` / ``staged_v`` [T, k, G, hd]
    that ``staged_mask`` [1, 1, k] admits, under one softmax. ``held``
    [T, 2]: the resident rows of each slot that count (``< held[:, 0]``;
    0 for a dead slot) and the blocks that hold them (:func:`_blocks_held`):
    a slot is read for THAT many turns — the loop over a slot's blocks
    nests in the loop over the tile's slots — whatever the span: a block
    past a slot's rows has masked scores and exact-zero weights (and the
    table's sentinel for an id), so the span only sizes the buffers.
    Equal to gathering the rows and :func:`_attend` up to summation
    order. -> [T, 1, n_heads, hd] float32."""
    T, _, nh, hd = q.shape
    G = cfg.n_kv_heads
    rep, f32 = nh // G, jnp.float32
    L, n_blocks, bl = cache["k"].shape[:3]
    P = _span_blocks(cache, table_rows, span)
    fk, fv = (cache[n].reshape((L * n_blocks,) + cache[n].shape[2:])
              for n in ("k", "v"))
    at = fi * n_blocks + table_rows[:, :P]
    qf = q[:, 0].reshape(T, G, rep, hd).astype(f32) * hd ** -0.5

    def block(pool, t, j):
        return lax.dynamic_index_in_dim(pool, at[t, j], 0, False)[:, :G] \
            .astype(f32)

    def slot_by_slot(turn, carry):
        def slot(t, carry):
            return lax.fori_loop(0, held[t, 1],
                                 lambda j, c: turn(t, j, c), carry)
        return lax.fori_loop(0, T, slot, carry)

    def score(t, j, scores):
        s = jnp.einsum("mgk,grk->mgr", block(fk, t, j), qf[t])
        return lax.dynamic_update_slice(scores, s[None], (t, j * bl, 0, 0))

    scores = slot_by_slot(score, jnp.zeros((T, P * bl, G, rep), f32))
    neg = jnp.asarray(-1e30, f32)
    resident = jnp.arange(P * bl)[None, :] < held[:, :1]
    scores = jnp.where(resident[:, :, None, None], scores, neg)
    staged = jnp.einsum("tgrk,tmgk->tmgr", qf, staged_k.astype(f32))
    staged = jnp.where(staged_mask[0, 0][None, :, None, None], staged, neg)
    w = jax.nn.softmax(jnp.concatenate([scores, staged], axis=1), axis=1)
    w_res, w_st = w[:, :P * bl], w[:, P * bl:]

    def weigh(t, j, acc):
        wp = lax.dynamic_slice(w_res, (t, j * bl, 0, 0), (1, bl, G, rep))[0]
        return acc.at[t].add(
            jnp.einsum("mgr,mgk->grk", wp, block(fv, t, j)))

    o = slot_by_slot(weigh, jnp.zeros((T, G, rep, hd), f32))
    o = o + jnp.einsum("tmgr,tmgk->tgrk", w_st, staged_v.astype(f32))
    return o.reshape(T, 1, nh, hd)


def _attend(cfg, q, segments):
    """Attention of query rows ``q`` [B, Q, n_heads, hd] over K/V given
    in SEGMENTS ``(k [B, M, G, hd], v, mask [B|1, Q, M])`` under one
    softmax, in the order given -> [B, Q, n_heads, hd] float32. The
    dots take operands in the compute dtype and accumulate in float32."""
    B, Q, nh, hd = q.shape
    G, dt = cfg.n_kv_heads, cfg.dtype
    qh = q.reshape(B, Q, G, nh // G, hd).astype(dt)
    neg = jnp.asarray(-1e30, jnp.float32)
    scores = [jnp.where(
        mask[:, :, None, None, :],
        jnp.einsum("bqgrk,bmgk->bqgrm", qh, k.astype(dt),
                   preferred_element_type=jnp.float32) * hd ** -0.5, neg)
        for k, _, mask in segments]
    w = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1)
    out, at = None, 0
    for _, v, _ in segments:
        m = v.shape[1]
        part = jnp.einsum("bqgrm,bmgk->bqgrk",
                          w[..., at:at + m].astype(dt), v.astype(dt),
                          preferred_element_type=jnp.float32)
        at += m
        out = part if out is None else out + part
    return out.reshape(B, Q, nh, hd)


def prefill_chunk(params, cache: Cache, tokens_c, start, n_valid, slot,
                  new_len, rng, cfg: oh.OlmoHybridConfig, sp, *, final: bool,
                  qweights=None, table=None, span=None, kv_kernel=False,
                  lora=None, aid=None):
    """One chunk of an incremental prefill into a slot
    (``kvcache.prefill_chunk``'s contract). A full layer's C query rows
    attend to the slot's resident rows ``< start`` and causally to the
    chunk's own; a linear layer continues the slot's resident state and
    tail when ``start > 0`` and starts from zero at ``start == 0``,
    whatever the slot holds. Tokens at or past ``n_valid`` are padding.
    Returns (cache', rng', first token — 0 unless ``final``)."""
    _need_table(table)
    _no_extras(qweights, lora, kv_kernel)
    C = tokens_c.shape[0]
    M = span if span is not None else kvcache._logical_rows(cache, table)
    kdt = cache["k"].dtype
    x = params["embed"].astype(cfg.dtype)[tokens_c][None]       # [1, C, D]
    rope = oh.rope_tables(cfg, start + jnp.arange(C))
    j = jnp.arange(C)
    intra = ((j[None, :] <= j[:, None]) & (j[None, :] < n_valid))[None]
    resident = jnp.broadcast_to(jnp.arange(M)[None, None, :] < start,
                                (1, C, M))
    slot_table = lax.dynamic_slice_in_dim(table, slot, 1, 0)
    carried = start > 0
    valid = jnp.reshape(n_valid, (1,))

    def lin_fn(x, layer, li):
        state = jnp.where(carried, cache["state"][li, slot], 0.0)[None]
        tail = jnp.where(carried, cache["conv"][li, slot], 0)[None]
        y, state, tail = oh.linear_mixer(cfg, layer, x, state, tail, valid)
        return oh.out_ffn(cfg, layer, x, y), (state[0], tail[0])

    def full_fn(x, layer, fi):
        q, k, v = oh.full_project(cfg, layer, x, rope)
        with jax.named_scope("attn_core"):
            ck, cv = _gather_kv(cfg, cache, fi, slot_table, span)
            o = _attend(cfg, q, [(ck, cv, resident), (k, v, intra)])
        x = oh.out_ffn(cfg, layer, x, oh.full_output(cfg, layer, o))
        return x, (k[0].astype(kdt), v[0].astype(kdt))

    x, (state, conv), rows = oh.scan_periods(cfg, params, x, lin_fn, full_fn)
    if final:
        last = lax.dynamic_index_in_dim(x[0], n_valid - 1, 0,
                                        keepdims=False)
        logits = oh.head_logits(cfg, params, last)
        with jax.named_scope("sample"):
            rng, sub = jax.random.split(rng)
            tok = sampling_mod.sample(logits, sub, sp)
    else:
        tok = jnp.zeros((), jnp.int32)
    # Scatter through the table: a final partial chunk's window may poke
    # past the slot's blocks, and the overflow drops at the sentinel.
    with jax.named_scope("kv_write"):
        out = _flush_rows(cache, table, slot, start + jnp.arange(C), *rows)
        out["state"] = cache["state"].at[:, slot].set(state)
        out["conv"] = cache["conv"].at[:, slot].set(
            conv.astype(cache["conv"].dtype))
        out["length"] = cache["length"].at[slot].set(new_len)
        if final:
            out["last_token"] = cache["last_token"].at[slot].set(tok)
    return out, rng, tok


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

@jax.named_scope("linear_mixer")
def _linear_step(cfg, layer, x, li, state, conv, tiles, live):
    """One token of the linear mixer for every row ``x`` [B, 1, D]: the
    tails of the live rows move on (a where over all rows: a tail is
    69 KB), and the states of the live rows are read, stepped and
    written back a tile of slots a turn. A row that is not live keeps
    its state and tail and gets a zero mixer output. Returns (``y`` [B,
    1, D], state, conv)."""
    B = x.shape[0]
    qkv, g, beta, gate = oh.lin_project(cfg, layer, x)
    tail = lax.dynamic_index_in_dim(conv, li, 0, keepdims=False)
    qkv, moved = gd.causal_conv(qkv, layer["conv"], tail,
                                jnp.ones((B,), jnp.int32))
    conv = lax.dynamic_update_index_in_dim(
        conv, jnp.where(live[:, None, None], moved, tail), li, 0)
    q, k, v = (a[:, 0] for a in oh.lin_heads_of(cfg, qkv))
    g, beta = g[:, 0], beta[:, 0]
    n_tiles, order = tiles[:2]
    at = (0,) * (state.ndim - 2)

    def turn(t, carry):
        state, out = carry
        ids = lax.dynamic_slice_in_dim(order, t * kvcache.TILE, kvcache.TILE)
        # A slot's state is SLICED out of the carried tensor and put
        # back by dynamic_update_slice, slot by slot: its 2.2 MB pass
        # the window a gather may hold (_GATHER_WINDOW_BYTES), and a
        # gather by slot id made the compiler slice the WHOLE tensor
        # (every layer and slot) in halves of d_v before each read.
        held = jnp.concatenate(
            [lax.dynamic_slice(state, (li, ids[j]) + at,
                               (1, 1) + state.shape[2:])[0]
             for j in range(kvcache.TILE)])
        o, stepped = gd.step_rule(q[ids], k[ids], v[ids], g[ids], beta[ids],
                                  held)
        # A tile's tail holds slots that are not live (the order is
        # live-first and tiles are whole): they keep what they hold.
        stepped = jnp.where(live[ids][:, None, None, None], stepped, held)
        for j in range(kvcache.TILE):
            state = lax.dynamic_update_slice(
                state, stepped[j][None, None], (li, ids[j]) + at)
        return state, out.at[ids].set(o)

    with jax.named_scope("delta_rule"):
        state, o = lax.fori_loop(
            0, n_tiles, turn,
            (state, jnp.zeros((B, cfg.lin_heads, cfg.lin_v_dim),
                              jnp.float32)))
    return oh.lin_output(cfg, layer, o[:, None], gate), state, conv


def _staged_steps(params, cache: Cache, cfg: oh.OlmoHybridConfig, table,
                  span, k: int, first_tokens, next_token, live=None):
    """``k`` decode steps for every slot. The K/V pool is a read-only
    invariant (``kvcache.decode_burst_staged``'s formulation: a step's
    rows land in a staging buffer [L_full, B, k, G, hd], attention is
    the resident rows and the staged columns ``<= step`` under one
    softmax, ONE scatter per tensor flushes afterwards); the recurrent
    state and the tails are CARRIED through the steps and updated in
    place for the ``live`` rows only ([B] bool; absent: every row).
    ``next_token(logits, s, last) -> (token fed to step s + 1, what the
    step emits)``. Returns (cache with rows flushed and states advanced
    — length / last_token untouched —, last token [B], emitted [k,
    ...])."""
    _need_table(table)
    B = cache["length"].shape[0]
    M = span if span is not None else kvcache._logical_rows(cache, table)
    G, hd = cfg.n_kv_heads, cfg.head_dim
    kdt = cache["k"].dtype
    pos0 = cache["length"]
    batch_ix = jnp.arange(B)
    n_tiles, order, _, table_rows = kvcache._live_tiles(live, pos0, table)
    if live is None:
        live = jnp.ones((B,), bool)
    # What a full layer reads of the pool is bounded by residency — a
    # live slot's blocks up to the rows it holds, a dead slot's not at
    # all — and the bounds are constants of the program: they ride to
    # each turn where ``_live_tiles`` puts the slots' lengths.
    rows = jnp.where(live, pos0, 0)[order]
    held = jnp.stack([rows, _blocks_held(
        cache, rows, _span_blocks(cache, table, span))], axis=1)
    tiles = (n_tiles, order, held, table_rows)

    def step(carry, s):
        with jax.named_scope("decode_step"):
            last, sk, sv, state, conv = carry
            x = params["embed"].astype(cfg.dtype)[last[:, None]]
            rope = oh.rope_tables(cfg, (pos0 + s)[:, None])
            staged = (jnp.arange(k) <= s)[None, None, :]

            def lin_fn(c, layer, li):
                x, sk, sv, state, conv = c
                y, state, conv = _linear_step(cfg, layer, x, li, state,
                                              conv, tiles, live)
                return (oh.out_ffn(cfg, layer, x, y), sk, sv, state,
                        conv), None

            def full_fn(c, layer, fi):
                x, sk, sv, state, conv = c
                q, kk, v = oh.full_project(cfg, layer, x, rope)
                with jax.named_scope("attn_core"):
                    sk = sk.at[fi, batch_ix, s].set(kk[:, 0].astype(kdt))
                    sv = sv.at[fi, batch_ix, s].set(v[:, 0].astype(kdt))
                    lk = lax.dynamic_index_in_dim(sk, fi, 0, False)
                    lv = lax.dynamic_index_in_dim(sv, fi, 0, False)

                    def attend(ids, held, table_rows):
                        return _attend_in_place(
                            cfg, cache, fi, table_rows, span, q[ids], held,
                            lk[ids], lv[ids], staged)

                    o = kvcache._visit_tiles(
                        tiles, B, attend, (1, cfg.n_heads, hd))
                x = oh.out_ffn(cfg, layer, x, oh.full_output(cfg, layer, o))
                return (x, sk, sv, state, conv), None

            (x, sk, sv, state, conv), _, _ = oh.scan_periods(
                cfg, params, (x, sk, sv, state, conv), lin_fn, full_fn)
            logits = oh.head_logits(cfg, params, x[:, 0])
            last, emitted = next_token(logits, s, last)
        return (last, sk, sv, state, conv), emitted

    stage = jnp.zeros((cfg.n_full_layers, B, k, G, hd), kdt)
    (last, sk, sv, state, conv), emitted = lax.scan(
        step, (first_tokens, stage, stage, cache["state"], cache["conv"]),
        jnp.arange(k))
    with jax.named_scope("kv_write"):
        out = _flush_rows(cache, table, batch_ix[:, None],
                          pos0[:, None] + jnp.arange(k)[None, :], sk, sv)
    out["state"], out["conv"] = state, conv
    return out, last, emitted


def decode_step(params, cache: Cache, cfg: oh.OlmoHybridConfig,
                qweights=None, table=None, span=None,
                lora=None, aid=None, live=None) -> Tuple[Cache, jax.Array]:
    """One token for every slot: (cache' with the pending row written
    and the ``live`` rows' states advanced, logits [slots, vocab]). The
    caller samples and commits (``kvcache.commit_tokens``)."""
    _no_extras(qweights, lora)
    out, _, logits = _staged_steps(
        params, cache, cfg, table, span, 1, cache["last_token"],
        lambda logits, s, last: (last, logits), live=live)
    return out, logits[0]


def decode_burst_staged(params, cache: Cache, rng, active, k: int,
                        cfg: oh.OlmoHybridConfig, sp, qweights=None,
                        table=None, span=None, kv_kernel=False, lora=None,
                        aid=None):
    """``k`` decode steps in one program, the K/V flushed once and the
    ``active`` rows' states carried from step to step
    (``kvcache.decode_burst_staged``'s contract and RNG discipline).
    Returns (cache', rng', toks [k, slots])."""
    _no_extras(qweights, lora, kv_kernel)
    rng, sub = jax.random.split(rng)
    keys = jax.random.split(sub, k)

    def next_token(logits, s, last):
        with jax.named_scope("sample"):
            tok = sampling_mod.sample(logits, keys[s], sp)
        return jnp.where(active, tok, last), tok

    out, last, toks = _staged_steps(
        params, cache, cfg, table, span, k, cache["last_token"], next_token,
        live=active)
    out["length"] = cache["length"] + k * active.astype(jnp.int32)
    out["last_token"] = last
    return out, rng, toks


def verify_draft_staged(*_, **__):
    raise NotImplementedError(
        "the hybrid family has no speculative verify program")
