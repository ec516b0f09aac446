"""Serve programs of the windowed family (``models/afmoe.py``): GLOBAL
attention layers whose K/V rows live in the paged block pool, beside
SLIDING-WINDOW layers that hold, per slot, a RING of the last ``window``
rows and nothing older.

The siblings of ``kvcache.py``'s, ``latent.py``'s and ``hybrid.py``'s
programs, with the same signatures, so the engine's jitted entry points
call this module through ``kvcache.programs_for(cfg)``; same block pool,
block table, sentinel column, span ladder and staging discipline for the
global layers' rows.

Layout: ``k``, ``v`` ``[L_full, blocks, block_len, n_kv_heads * hd]`` —
the layer axis covers the GLOBAL layers only, and a row's heads lie side
by side on the minor axis: with 4 key/value heads, a heads axis of its
own would be padded to a whole tile of 16 sublanes and a token would be
four times its 2048 B a layer, in memory and in every read — plus, per
window layer and SLOT (not per block: :data:`SLOT_STATE`), ``win_k``,
``win_v`` ``[L_win, slots, window, n_kv_heads * hd]``: position ``p`` of
a slot lies at ring row ``p mod window``. What follows from a ring:

* a window layer cannot keep or read more than ``window`` rows: a
  slot's cache is 2048 B a token in the global layers and a FIXED
  ``2 x window x 1024`` B a window layer, whatever its length;
* which ring rows count is decided by POSITIONS, never by what a row
  holds: with ``n`` rows resident, ring row ``r`` holds position ``n - 1
  - ((n - 1 - r) mod window)``, and a query at ``p`` admits it when that
  is ``>= 0`` and ``> p - window``. A slot rented again starts at ``n =
  0`` and sees nothing of its last tenant; there is no reset program;
* a ring is no row of a block, so it cannot be shared by block or cut
  to a prefix: the family runs without the prefix pool, the handoff and
  copy-on-write (:data:`UNSUPPORTED`); a preempted or recovered request
  re-prefills its whole context, which rebuilds its rings;
* a program READS the rings, attends, and THEN writes: a chunk's rows
  see the ``window - 1`` older rows and their own causally before any
  of them lands; a burst's ``k`` steps stage their rows (the window
  slides over the staged columns by position) and flush once, for the
  ``live`` slots only — a slot that is mid-prefill, free or the spare
  keeps its ring; a padded wave row or last chunk writes its real
  tokens only.

Decode attention reads a live slot's rows IN PLACE, a block a turn
(:func:`_attend_in_place`, ``hybrid.py``'s two passes in this layout):
pool blocks up to the rows the slot holds (``DECODE_READS_BLOCKS_HELD``)
in a global layer, ring blocks up to ``min(rows, window)`` in a window
layer. A prefill chunk's global layers walk the slot's resident rows a
tile of blocks a turn under a running softmax (:func:`_attend_resident`:
at 33 k rows a chunk's whole score matrix would be 2 GB), bounded by
residency whatever the span rung; its window layers read the slot's one
ring.

The expert layers are ``models/glm_moe.py``'s; the spare slot's column
of a burst's tokens carries the experts read, as the latent family's.

Paged layout only; no int8 rows or weights, no adapters, no tensor
parallelism, no speculative verify, no paged-attention kernel
(``engine.refuse_options``; ``docs/serving.md`` section Window layers).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.infer import hybrid, kvcache, latent
from skypilot_tpu.infer import sampling as sampling_mod
from skypilot_tpu.models import afmoe
from skypilot_tpu.models import glm_moe
from skypilot_tpu.observability import attribution

Cache = kvcache.Cache

# This family's answers to the engine (``kvcache.programs_for``).
FAMILY = "windowed (sliding-window rings + paged KV)"
SLOT_STATE = ("win_k", "win_v")
DECODE_READS_BLOCKS_HELD = True
# The experts a burst's steps read ride the spare slot's column, under
# the latent family's name and counter.
SPARE_COLUMN = latent.SPARE_COLUMN
_NO_RING = "a shared block holds K/V rows but no window layer's ring"
UNSUPPORTED = {
    "prefix_pool": _NO_RING,
    "import_prefix": _NO_RING,
    "export_prefix": _NO_RING,
    "kv_block=0": "the global layers' cache is paged only",
    "kv_int8": "no int8 rows in a pool row of side-by-side heads or a ring",
    "weights_int8": "the expert and gated-attention matrices have no "
                    "int8 form",
    "tp": "no ring or expert layer under a mesh",
    "adapters": "no LoRA targets in the gated attention",
    "spec_k": "a rejected draft cannot take its rows back out of a ring",
    "draft_model": "a rejected draft cannot take its rows back out of "
                   "a ring",
    "kv_kernel": "the paged-attention kernel reads per-head K/V",
}


def ring_rows(cfg: afmoe.AfmoeConfig) -> Optional[int]:
    """Rows a window layer keeps per slot (the engine's dispatch
    annotations count ``window_rows`` / ``window_keys`` with it)."""
    return cfg.window


# Expert layers x experts (see ``kvcache.programs_for``).
experts_per_step = glm_moe.experts_per_step


# Ring rows one turn of a window layer's in-place read takes: the
# largest divisor of the window at most this (the ring's "block").
_RING_BLOCK = 512


def ring_block(cfg: afmoe.AfmoeConfig) -> int:
    b = min(_RING_BLOCK, cfg.window)
    while cfg.window % b:
        b -= 1
    return b


def init_paged_cache(cfg: afmoe.AfmoeConfig, n_slots: int, n_blocks: int,
                     block_len: int, kv_int8: bool = False) -> Cache:
    """``kvcache.init_paged_cache``'s sibling: the block pool holds the
    global layers' rows; every slot holds a ring per window layer."""
    if kv_int8:
        raise NotImplementedError("the windowed cache has no int8 rows")
    kv = (cfg.n_full_layers, n_blocks, block_len, cfg.kv_width)
    ring = (cfg.n_win_layers, n_slots, cfg.window, cfg.kv_width)
    return {
        "length": jnp.zeros((n_slots,), jnp.int32),
        "last_token": jnp.zeros((n_slots,), jnp.int32),
        "k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
        "win_k": jnp.zeros(ring, cfg.dtype),
        "win_v": jnp.zeros(ring, cfg.dtype)}


def token_bytes(cfg: afmoe.AfmoeConfig, cache=None) -> int:
    """Cache bytes a token holds: K and V in the global layers only."""
    return cfg.n_full_layers * 2 * cfg.kv_width \
        * jnp.dtype(cfg.dtype).itemsize


def slot_state_bytes(cfg: afmoe.AfmoeConfig) -> int:
    """Bytes ONE slot's rings hold, all window layers."""
    return cfg.n_win_layers * 2 * cfg.window * cfg.kv_width \
        * jnp.dtype(cfg.dtype).itemsize


def _expert_tensors(params):
    for group in ("lead", "period", "tail"):
        for layer in params[group]:
            for name in glm_moe.EXPERT_TENSORS:
                if name in layer:
                    yield layer[name]


def hbm_rows(cache: Cache, params) -> Dict[str, int]:
    """The HBM ledger's rows: the pool as the GQA family's, what the
    slots' rings hold whatever their length, and the routed experts (a
    view INSIDE ``weights``, as the latent family's)."""
    ring = attribution.tensor_bytes([cache[n] for n in SLOT_STATE])
    return {"kv_pool": attribution.tensor_bytes(cache) - ring,
            "window_ring": ring,
            "expert_weights": attribution.tensor_bytes(
                list(_expert_tensors(params)))}


def roofline_dims(cfg: afmoe.AfmoeConfig) -> Dict[str, int]:
    """A token multiplies with its chosen experts only; rows that grow
    with the context are attended in the global layers only."""
    return {"param_count": cfg.active_params(),
            "n_layers": cfg.n_full_layers,
            "n_heads": cfg.n_heads, "head_dim": cfg.head_dim}


def _need_table(table):
    if table is None:
        raise NotImplementedError(
            "the windowed cache is paged only (no contiguous layout)")


def _no_extras(qweights, lora, kv_kernel=False):
    if qweights is not None or lora is not None or kv_kernel:
        raise NotImplementedError(
            "the windowed family serves float weights without adapters or "
            "the paged-attention kernel")


# Indices one scatter of a flush takes (``latent._SCATTER_ROWS``: the
# TPU compiler unrolls a longer scatter row by row).
_SCATTER_ROWS = 512


@jax.named_scope("kv_write")
def _scatter_rows(pool_k, pool_v, a, b, k_rows, v_rows):
    """Rows ``[L, *I, width]`` land at ``pool[layer, a, b]`` (``a``, ``b``
    broadcast to one shape ``I``: block and offset of a pool, slot and
    ring row of a ring); a coordinate out of bounds drops its row. Both
    tensors are written in place, a layer and :data:`_SCATTER_ROWS` rows
    a turn (``hybrid._flush_rows`` says why the layer is no window dim
    of one scatter)."""
    a, b = jnp.broadcast_arrays(a, b)
    L, n_a = pool_k.shape[:2]
    n = a.size
    if not L or not n:
        return pool_k, pool_v
    pieces = -(-n // _SCATTER_ROWS)
    width = min(n, _SCATTER_ROWS)
    pad = pieces * width - n
    a = jnp.pad(a.reshape(-1), (0, pad), constant_values=n_a)
    b = jnp.pad(b.reshape(-1), (0, pad))
    rows = [jnp.pad(r.reshape(L, n, -1), ((0, 0), (0, pad), (0, 0)))
            for r in (k_rows, v_rows)]

    def turn(t, pools):
        layer, at = t // pieces, (t % pieces) * width
        ai = lax.dynamic_slice_in_dim(a, at, width)
        bi = lax.dynamic_slice_in_dim(b, at, width)
        return tuple(
            pool.at[layer, ai, bi].set(lax.dynamic_slice(
                r, (layer, at, 0), (1, width, r.shape[2]))[0].astype(
                    pool.dtype))
            for pool, r in zip(pools, rows))

    return lax.fori_loop(0, L * pieces, turn, (pool_k, pool_v))


def _write_pool(cache: Cache, table, slots, idx, k_rows, v_rows) -> Cache:
    """Global-layer rows land at logical ``(slots, idx)`` through the
    block table; sentinel / overflow coordinates drop."""
    blk, off = kvcache._phys(cache, table, slots, idx)
    out = dict(cache)
    out["k"], out["v"] = _scatter_rows(cache["k"], cache["v"], blk, off,
                                       k_rows, v_rows)
    return out


def _write_rings(cache: Cache, slots, positions, keep, k_rows, v_rows
                 ) -> Cache:
    """Window-layer rows of ``positions`` land in ``slots``' rings at
    ``position mod window`` where ``keep`` says so (the others drop)."""
    n_slots, window = cache["win_k"].shape[1:3]
    out = dict(cache)
    out["win_k"], out["win_v"] = _scatter_rows(
        cache["win_k"], cache["win_v"], jnp.where(keep, slots, n_slots),
        positions % window, k_rows, v_rows)
    return out


def _ring_positions(cfg, rows):
    """The position each ring row holds when ``rows`` [...] rows of the
    slot are resident: ``[..., window]``, negative where the ring row
    has never been written for this tenant."""
    last = rows[..., None] - 1
    return last - jnp.mod(last - jnp.arange(cfg.window), cfg.window)


def _layer_split(cfg, stacked):
    """Rows stacked over ALL layers ``[L, ...]`` -> (the global layers',
    the window layers'), each in its cache's layer order."""
    return (stacked[jnp.asarray(cfg.full_layers, jnp.int32)],
            stacked[jnp.asarray(cfg.win_layers, jnp.int32)])


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill_batch(params, tokens, true_lens, cfg: afmoe.AfmoeConfig,
                  qweights=None, lora=None, aid=None,
                  mesh=None, heads_axis=None) -> Tuple[Cache, jax.Array]:
    """Causal forward over a WAVE of right-padded prompts [W, S] (a wave
    row is at most a chunk long: plain masked attention). Returns
    (``{"k", "v": [L_full, W, S, width], "win_k", "win_v": [L_win, W, S,
    width]}``, logits at each request's last real position [W, vocab]
    float32). Padding rows run through the expert layer like any row
    (dropless: they can evict nothing) and are never read."""
    _no_extras(qweights, lora)
    x, rows = afmoe.forward_hidden(params, tokens, cfg)
    last = jnp.take_along_axis(
        x, (true_lens - 1)[:, None, None], axis=1)[:, 0]           # [W, D]
    flat = {n: r.reshape(r.shape[:3] + (-1,)) for n, r in rows.items()}
    (k, wk), (v, wv) = (_layer_split(cfg, flat[n]) for n in ("k", "v"))
    return ({"k": k, "v": v, "win_k": wk, "win_v": wv},
            afmoe.head_logits(cfg, params, last))


def insert(cache: Cache, prefix: Cache, slot, true_len, first_token,
           table=None) -> Cache:
    """Install one prefilled prompt into a slot: the global layers' rows
    [L_full, S, width] through its table row, and of the window layers'
    its last ``window`` REAL rows into the slot's rings (whatever the
    slot's last tenant left there is outside every later window by
    position). The spare slot's all-sentinel row drops a dummy wave
    row's K/V; its ring rows land in the spare's own rings, which nobody
    reads."""
    _need_table(table)
    p = jnp.arange(prefix["k"].shape[1])
    out = _write_pool(cache, table, slot, p, prefix["k"], prefix["v"])
    keep = (p < true_len) & (p >= true_len - cache["win_k"].shape[2])
    out = _write_rings(out, slot, p, keep, prefix["win_k"], prefix["win_v"])
    out["length"] = cache["length"].at[slot].set(true_len)
    out["last_token"] = cache["last_token"].at[slot].set(first_token)
    return out


# Key rows one turn of a chunk's walk over a slot's resident rows takes
# (whole blocks): 512 query rows x 32 heads x 2048 keys are 134 MB of
# float32 scores a turn.
_KEY_TILE_ROWS = 2048


def _attend_resident(cfg, cache: Cache, fi, table_row, start, q, k_new,
                     v_new, intra):
    """A chunk's attention in global layer ``fi``: ``q`` [C, n_heads, hd]
    over the slot's resident rows ``< start`` — read out of the pool
    through ``table_row`` [nb + 1], :data:`_KEY_TILE_ROWS` a turn, under
    a running softmax, for as many turns as hold those rows — and then
    over the chunk's own ``k_new``, ``v_new`` [C, n_kv_heads, hd] under
    ``intra`` [C, C]. -> [C, n_heads, hd] float32."""
    C, nh, hd = q.shape
    G, dt, f32 = cfg.n_kv_heads, cfg.dtype, jnp.float32
    L, n_blocks, bl, width = cache["k"].shape
    per_turn = max(1, _KEY_TILE_ROWS // bl)
    M = per_turn * bl
    nb = table_row.shape[0] - 1
    ids = jnp.pad(table_row[:nb], (0, -nb % per_turn),
                  constant_values=n_blocks)
    fk, fv = (cache[n].reshape(L * n_blocks, bl, width) for n in ("k", "v"))
    qh = (q.reshape(C, G, nh // G, hd).astype(f32) * hd ** -0.5).astype(dt)
    neg = jnp.asarray(-1e30, f32)

    def fold(carry, kt, vt, mask):
        """One more run of keys [M', G, hd] under ``mask`` [C|1, M'] (at
        least one key a row is admitted somewhere before the end)."""
        m, l, acc = carry
        s = jnp.einsum("qgrk,mgk->qgrm", qh, kt.astype(dt),
                       preferred_element_type=f32)
        s = jnp.where(mask[:, None, None, :], s, neg)
        m2 = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask[:, None, None, :], jnp.exp(s - m2[..., None]), 0.0)
        fix = jnp.exp(m - m2)
        acc = acc * fix[..., None] + jnp.einsum(
            "qgrm,mgk->qgrk", p.astype(dt), vt.astype(dt),
            preferred_element_type=f32)
        return m2, l * fix + p.sum(axis=-1), acc

    def turn(j, carry):
        at = fi * n_blocks + lax.dynamic_slice_in_dim(ids, j * per_turn,
                                                      per_turn)
        held = (j * M + jnp.arange(M)) < start
        kt, vt = (jnp.where(held[:, None], f[at].reshape(M, width), 0)
                  .reshape(M, G, hd) for f in (fk, fv))
        return fold(carry, kt, vt, held[None, :])

    shape = (C, G, nh // G)
    carry = lax.fori_loop(
        0, -(-start // M), turn,
        (jnp.full(shape, neg), jnp.zeros(shape, f32),
         jnp.zeros(shape + (hd,), f32)))
    _, l, acc = fold(carry, k_new, v_new, intra)
    return (acc / l[..., None]).reshape(C, nh, hd)


def prefill_chunk(params, cache: Cache, tokens_c, start, n_valid, slot,
                  new_len, rng, cfg: afmoe.AfmoeConfig, sp, *, final: bool,
                  qweights=None, table=None, span=None, kv_kernel=False,
                  lora=None, aid=None):
    """One chunk of an incremental prefill into a slot
    (``kvcache.prefill_chunk``'s contract). A global layer's C query
    rows attend to the slot's resident rows ``< start`` and causally to
    the chunk's own; a window layer's to the ring rows whose POSITIONS
    lie inside each query's window (none at ``start == 0``, whatever
    the slot holds) and to the chunk's own inside it — and only then do
    the chunk's real rows land. Tokens at or past ``n_valid`` are
    padding. Returns (cache', rng', first token — 0 unless ``final``)."""
    _need_table(table)
    _no_extras(qweights, lora, kv_kernel)
    C, W = tokens_c.shape[0], cfg.window
    G, hd = cfg.n_kv_heads, cfg.head_dim
    kdt = cache["k"].dtype
    x = afmoe.embed(cfg, params, tokens_c)[None]                # [1, C, D]
    pos = start + jnp.arange(C)
    rope = afmoe.rope_tables(cfg, pos)
    j = jnp.arange(C)
    intra = (j[None, :] <= j[:, None]) & (j[None, :] < n_valid)
    intra_win = intra & (j[:, None] - j[None, :] < W)
    # The ring, by position: what each row holds with ``start`` rows
    # resident, and which of them each query's window admits.
    held = _ring_positions(cfg, jnp.reshape(start, ()))             # [W]
    in_window = (held[None, :] >= 0) & (held[None, :] > pos[:, None] - W)
    seen = in_window.any(axis=0)
    table_row = lax.dynamic_index_in_dim(table, slot, 0, keepdims=False)

    def layer_fn(x, layer, i, ci, window, moe):
        q, k, v, gate = afmoe.project(cfg, layer, x,
                                      rope if window else None)
        if window:
            with jax.named_scope("window_attn"):
                # A ring row no query admits may hold anything (a last
                # tenant's NaN): it is zeroed before it meets a weight.
                rk, rv = (jnp.where(
                    seen[:, None],
                    lax.dynamic_slice(
                        cache[n], (ci, slot, 0, 0),
                        (1, 1, W, cfg.kv_width))[0, 0], 0
                ).reshape(1, W, G, hd) for n in ("win_k", "win_v"))
                o = hybrid._attend(cfg, q, [(rk, rv, in_window[None]),
                                            (k, v, intra_win[None])])
        else:
            with jax.named_scope("attn_core"):
                o = _attend_resident(cfg, cache, ci, table_row, start, q[0],
                                     k[0], v[0], intra)[None]
        x, _ = afmoe.out_ffn(cfg, layer, x, o, gate, moe)
        return x, (k[0].reshape(C, -1).astype(kdt),
                   v[0].reshape(C, -1).astype(kdt))

    x, (k_all, v_all) = afmoe.scan_layers(cfg, params, x, layer_fn)
    if final:
        last = lax.dynamic_index_in_dim(x[0], n_valid - 1, 0,
                                        keepdims=False)
        logits = afmoe.head_logits(cfg, params, last)
        with jax.named_scope("sample"):
            rng, sub = jax.random.split(rng)
            tok = sampling_mod.sample(logits, sub, sp)
    else:
        tok = jnp.zeros((), jnp.int32)
    (k, wk), (v, wv) = (_layer_split(cfg, a) for a in (k_all, v_all))
    # Scatter through the table: a final partial chunk's rows may poke
    # past the slot's blocks, and the overflow drops at the sentinel.
    out = _write_pool(cache, table, slot, pos, k, v)
    out = _write_rings(out, slot, pos,
                       (j < n_valid) & (j >= n_valid - W), wk, wv)
    out["length"] = cache["length"].at[slot].set(new_len)
    if final:
        out["last_token"] = cache["last_token"].at[slot].set(tok)
    return out, rng, tok


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _attend_in_place(cfg, fk, fv, at, n_held, resident, q, staged_k,
                     staged_v, staged_mask):
    """Decode attention of one tile of slots WITHOUT a copy of their
    rows (``hybrid._attend_in_place``'s two passes over rows whose heads
    lie side by side): ``q`` [T, n_heads, hd] over the blocks ``at`` [T,
    P] of the pool (or ring) seen flat ``fk``, ``fv`` [N, bl, width] —
    slot ``t`` is read for ``n_held[t]`` turns, once for the scores,
    once, after the softmax, for the weighted values — and over the
    staged columns ``staged_k`` / ``staged_v`` [T, k, width] that
    ``staged_mask`` [1, k] admits, under one softmax. ``resident`` [T, P
    * bl] bool says which of the rows read count: a row that does not
    has a masked score, an exact-zero weight and a zeroed value (so what
    it holds, were it NaN, reaches nothing). A key head's columns are a
    lane-aligned slice of a row; scores are kept ``[T, n_heads, rows]``,
    rows minor. -> [T, n_heads, hd] float32."""
    T, nh, hd = q.shape
    G, dt, f32 = cfg.n_kv_heads, cfg.dtype, jnp.float32
    rep = nh // G
    bl, P = fk.shape[1], at.shape[1]
    qg = (q.reshape(T, G, rep, hd).astype(f32) * hd ** -0.5).astype(dt)

    def heads(rows):                        # [M, width] -> G x [M, hd]
        return [rows[:, g * hd:(g + 1) * hd] for g in range(G)]

    def scores_of(rows, qt):                # -> [n_heads, M] float32
        return jnp.concatenate(
            [jnp.einsum("rk,mk->rm", qt[g], kh.astype(dt),
                        preferred_element_type=f32)
             for g, kh in enumerate(heads(rows))], axis=0)

    def values_of(w, rows):                 # [n_heads, M] x -> [nh, hd]
        return jnp.concatenate(
            [jnp.einsum("rm,mk->rk", w[g * rep:(g + 1) * rep].astype(dt),
                        vh.astype(dt), preferred_element_type=f32)
             for g, vh in enumerate(heads(rows))], axis=0)

    def slot_by_slot(turn, carry):
        def slot(t, carry):
            return lax.fori_loop(0, n_held[t],
                                 lambda j, c: turn(t, j, c), carry)
        return lax.fori_loop(0, T, slot, carry)

    def block(pool, t, j):
        return lax.dynamic_index_in_dim(pool, at[t, j], 0, False)

    def score(t, j, scores):
        s = scores_of(block(fk, t, j), qg[t])
        return lax.dynamic_update_slice(scores, s[None], (t, 0, j * bl))

    scores = slot_by_slot(score, jnp.zeros((T, nh, P * bl), f32))
    neg = jnp.asarray(-1e30, f32)
    scores = jnp.where(resident[:, None, :], scores, neg)
    staged = jnp.stack([scores_of(staged_k[t], qg[t]) for t in range(T)])
    staged = jnp.where(staged_mask[:, None, :], staged, neg)
    w = jax.nn.softmax(jnp.concatenate([scores, staged], axis=-1), axis=-1)
    w_res, w_st = w[..., :P * bl], w[..., P * bl:]

    def weigh(t, j, acc):
        wp = lax.dynamic_slice(w_res, (t, 0, j * bl), (1, nh, bl))[0]
        ok = lax.dynamic_slice(resident, (t, j * bl), (1, bl))[0]
        rows = jnp.where(ok[:, None], block(fv, t, j), 0)
        return acc.at[t].add(values_of(wp, rows))

    o = slot_by_slot(weigh, jnp.zeros((T, nh, hd), f32))
    return o + jnp.stack([values_of(w_st[t], staged_v[t])
                          for t in range(T)])


def _staged_steps(params, cache: Cache, cfg: afmoe.AfmoeConfig, table,
                  span, k: int, first_tokens, next_token, live=None):
    """``k`` decode steps for every slot. Pool and rings are read-only
    invariants (``kvcache.decode_burst_staged``'s formulation): a step's
    rows, of EVERY layer, land in a staging buffer [L, B, k, width];
    attention is the resident rows and the staged columns ``<= step``
    under one softmax — in a window layer the resident ring rows and the
    staged columns whose POSITIONS lie inside the step's window, so the
    window slides through the burst —; afterwards ONE flush a cache: the
    global layers' rows into the pool through the table, the window
    layers' into the ``live`` slots' rings ([B] bool; absent: every
    row). ``next_token(logits, s, last) -> (token fed to step s + 1,
    what the step emits)``. Returns (cache with the rows flushed —
    length / last_token untouched —, last token [B], emitted [k, ...],
    routed experts read [k]: a step's sum over its expert layers)."""
    _need_table(table)
    W, width, hd = cfg.window, cfg.kv_width, cfg.head_dim
    if k > W:
        raise ValueError(f"a burst of {k} steps laps a ring of {W} rows")
    B = cache["length"].shape[0]
    kdt = cache["k"].dtype
    pos0 = cache["length"]
    batch_ix = jnp.arange(B)
    n_tiles, order, _, table_rows = kvcache._live_tiles(live, pos0, table)
    if live is None:
        live = jnp.ones((B,), bool)
    # What a layer reads is bounded by residency — a live slot's pool
    # blocks up to the rows it holds, its ring blocks up to min(rows,
    # window), a dead slot's not at all — and the bounds are constants
    # of the program.
    L_full, n_blocks, bl = cache["k"].shape[:3]
    P = hybrid._span_blocks(cache, table, span)
    rows = jnp.where(live, pos0, 0)[order]
    rb = ring_block(cfg)
    per_ring = W // rb
    held = jnp.stack([rows, jnp.minimum(-(-rows // bl), P),
                      -(-jnp.minimum(rows, W) // rb)], axis=1)
    tiles = (n_tiles, order, held, table_rows)
    fk, fv = (cache[n].reshape(L_full * n_blocks, bl, width)
              for n in ("k", "v"))
    rk, rv = (cache[n].reshape(-1, rb, width) for n in ("win_k", "win_v"))
    steps = jnp.arange(k)

    def step(carry, s):
        with jax.named_scope("decode_step"):
            last, sk, sv = carry
            x = afmoe.embed(cfg, params, last[:, None])         # [B, 1, D]
            rope = afmoe.rope_tables(cfg, (pos0 + s)[:, None])
            staged = ((steps <= s) & (s - steps < W))[None, :]

            def layer_fn(c, layer, i, ci, window, moe):
                x, sk, sv = c
                q, kk, v, gate = afmoe.project(cfg, layer, x,
                                               rope if window else None)
                with jax.named_scope("window_attn" if window
                                     else "attn_core"):
                    sk = sk.at[i, batch_ix, s].set(
                        kk.reshape(B, width).astype(kdt))
                    sv = sv.at[i, batch_ix, s].set(
                        v.reshape(B, width).astype(kdt))
                    lk = lax.dynamic_index_in_dim(sk, i, 0, False)
                    lv = lax.dynamic_index_in_dim(sv, i, 0, False)

                    def attend(ids, held, table_rows):
                        if window:
                            at = ((ci * B + ids) * per_ring)[:, None] \
                                + jnp.arange(per_ring)
                            at_pos = _ring_positions(cfg, held[:, 0])
                            resident = (at_pos >= 0) & (
                                at_pos > held[:, :1] + s - W)
                            pools, turns = (rk, rv), held[:, 2]
                        else:
                            at = ci * n_blocks + table_rows[:, :P]
                            resident = jnp.arange(P * bl)[None, :] \
                                < held[:, :1]
                            pools, turns = (fk, fv), held[:, 1]
                        return _attend_in_place(
                            cfg, *pools, at, turns, resident, q[ids, 0],
                            lk[ids], lv[ids], staged)

                    o = kvcache._visit_tiles(tiles, B, attend,
                                             (cfg.n_heads, hd))
                x, read = afmoe.out_ffn(cfg, layer, x, o[:, None], gate,
                                        moe, live[:, None])
                return (x, sk, sv), read

            (x, sk, sv), reads = afmoe.scan_layers(
                cfg, params, (x, sk, sv), layer_fn)
            logits = afmoe.head_logits(cfg, params, x[:, 0])
            last, emitted = next_token(logits, s, last)
        return (last, sk, sv), (emitted, jnp.sum(reads))

    stage = jnp.zeros((cfg.n_layers, B, k, width), kdt)
    (last, sk, sv), (emitted, reads) = lax.scan(
        step, (first_tokens, stage, stage), steps)
    (k_full, k_win), (v_full, v_win) = (_layer_split(cfg, a)
                                        for a in (sk, sv))
    positions = pos0[:, None] + steps[None, :]
    out = _write_pool(cache, table, batch_ix[:, None], positions, k_full,
                      v_full)
    out = _write_rings(out, batch_ix[:, None], positions,
                       live[:, None], k_win, v_win)
    return out, last, emitted, reads


def decode_step(params, cache: Cache, cfg: afmoe.AfmoeConfig,
                qweights=None, table=None, span=None,
                lora=None, aid=None, live=None) -> Tuple[Cache, jax.Array]:
    """One token for every slot: (cache' with the pending row written —
    into the ``live`` rows' rings only —, logits [slots, vocab]). The
    caller samples and commits (``kvcache.commit_tokens``)."""
    _no_extras(qweights, lora)
    out, _, logits, _ = _staged_steps(
        params, cache, cfg, table, span, 1, cache["last_token"],
        lambda logits, s, last: (last, logits), live=live)
    return out, logits[0]


def decode_burst_staged(params, cache: Cache, rng, active, k: int,
                        cfg: afmoe.AfmoeConfig, sp, qweights=None,
                        table=None, span=None, kv_kernel=False, lora=None,
                        aid=None):
    """``k`` decode steps in one program, pool and rings flushed once,
    the ``active`` rows' alone into their rings
    (``kvcache.decode_burst_staged``'s contract and RNG discipline).
    Returns (cache', rng', toks [k, slots]: the last column, the spare
    slot's, holds the step's experts read — :data:`SPARE_COLUMN`)."""
    _no_extras(qweights, lora, kv_kernel)
    rng, sub = jax.random.split(rng)
    keys = jax.random.split(sub, k)

    def next_token(logits, s, last):
        with jax.named_scope("sample"):
            tok = sampling_mod.sample(logits, keys[s], sp)
        return jnp.where(active, tok, last), tok

    out, last, toks, reads = _staged_steps(
        params, cache, cfg, table, span, k, cache["last_token"], next_token,
        live=active)
    out["length"] = cache["length"] + k * active.astype(jnp.int32)
    out["last_token"] = last
    return out, rng, toks.at[:, -1].set(reads.astype(toks.dtype))


def verify_draft_staged(*_, **__):
    raise NotImplementedError(
        "the windowed family has no speculative verify program")
