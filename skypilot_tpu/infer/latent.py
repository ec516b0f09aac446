"""Serve programs of the latent-cache family (``models/glm_moe.py``).

The siblings of ``kvcache.py``'s programs for a model whose cache row
is ``(c_kv, k_pe)`` — one compressed latent row and one shared rope key
per token and layer, NO heads axis — instead of per-head keys and
values. Same signatures, so the engine's jitted entry points
(``_admit_wave``, ``_prefill_chunk``, ``_decode``, ``_decode_burst``)
call either module through ``kvcache.programs_for(cfg)``; same paged
block pool, block table, sentinel column, span ladder and staging
discipline (the big cache is a read-only invariant of every program;
rows land in it by ONE scatter per tensor after the layer loop), so
``BlockAllocator``, ``PrefixIndex``, copy-on-write, lazy growth and
the warm grid move its blocks exactly as they move GQA blocks.

Layout: ``c_kv`` ``[L, blocks, block_len, kv_lora_rank]`` and ``k_pe``
``[L, blocks, block_len, qk_rope_head_dim]`` in the compute dtype; the
layer axis covers the dense layers, then the expert layers.

Decode attends in the ABSORBED form over the gathered latent rows (no
per-row up-projection: a step reads ``R + rope`` values a row, not
``heads x (nope + v)``). A prefill chunk takes the form
:data:`CHUNK_ABSORBED` says; a prefill wave materialises keys and
values (``glm_moe.causal_attention``).

Paged layout only; no int8 rows, no int8 weights, no adapters, no
tensor parallelism, no speculative verify (``engine`` refuses each
with a typed error; ``docs/serving.md`` lists them).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.infer import kvcache
from skypilot_tpu.infer import sampling as sampling_mod
from skypilot_tpu.models import glm_moe as glm
from skypilot_tpu.observability import attribution, metrics

Cache = kvcache.Cache

# Attention form of a prefill chunk (C query rows over a slot's
# resident rows + the chunk's own). At C = 512 the two forms cost about
# the same arithmetic (11.1 M x S absorbed against 9.8 M x S MACs
# materialised); the absorbed form makes no [S, heads, nope + v]
# transient and shares decode's code path.
CHUNK_ABSORBED = True

EXPERTS_READ = metrics.counter(
    "skytpu_experts_read_total",
    "Routed experts whose weights decode steps read: per step and expert "
    "layer, the distinct experts the live rows chose (expert models only)")
# What the hidden spare slot's column (the last) of a decode burst's
# ``toks`` carries instead of that slot's token, which is nobody's: a
# step's routed experts visited, summed over its expert layers, so the
# count reaches the host in the fetch of the tokens. (Field of the
# ``engine.decode.fetch`` annotation, its /metrics counter.) Over a
# burst's k steps, ``experts_read / (k x expert layers x
# n_routed_experts)`` is the share of the expert weights it streamed.
SPARE_COLUMN = ("experts_read", EXPERTS_READ)


def init_paged_cache(cfg: glm.GlmMoeConfig, n_slots: int, n_blocks: int,
                     block_len: int, kv_int8: bool = False) -> Cache:
    """``kvcache.init_paged_cache``'s sibling: the block pool holds
    latent rows. Per-slot ``length`` / ``last_token`` are the same."""
    if kv_int8:
        raise NotImplementedError("the latent cache has no int8 rows")
    L = cfg.n_layers
    return {
        "length": jnp.zeros((n_slots,), jnp.int32),
        "last_token": jnp.zeros((n_slots,), jnp.int32),
        "c_kv": jnp.zeros((L, n_blocks, block_len, cfg.kv_lora_rank),
                          cfg.dtype),
        "k_pe": jnp.zeros((L, n_blocks, block_len, cfg.qk_rope_head_dim),
                          cfg.dtype)}


def token_bytes(cfg: glm.GlmMoeConfig, cache=None) -> int:
    """Cache bytes a token holds, all layers."""
    return cfg.n_layers * cfg.latent_row_width \
        * jnp.dtype(cfg.dtype).itemsize


# This family's answers to the engine (``kvcache.programs_for``).
FAMILY = "latent-cache (MLA)"
UNSUPPORTED = {
    "kv_block=0": "the latent cache is paged only",
    "kv_int8": "latent rows have no int8 form",
    "weights_int8": "the expert and MLA matrices have no int8 form",
    "tp": "no latent cache or expert layer under a mesh",
    "adapters": "no LoRA targets in the MLA projections",
    "spec_k": "no verify program over the latent cache",
    "draft_model": "no verify program over the latent cache",
    "kv_kernel": "the paged-attention kernel reads per-head K/V",
}
SLOT_STATE = ()
DECODE_READS_BLOCKS_HELD = False


def ring_rows(cfg) -> None:
    """No window layers: no ring (see ``kvcache.programs_for``)."""
    return None


# Expert layers x experts (see ``kvcache.programs_for``).
experts_per_step = glm.experts_per_step


def roofline_dims(cfg: glm.GlmMoeConfig) -> dict:
    """A token multiplies with its chosen experts only."""
    return {"param_count": cfg.active_params(), "n_layers": cfg.n_layers,
            "n_heads": cfg.n_heads, "head_dim": cfg.qk_head_dim}


def hbm_rows(cache: Cache, params) -> dict:
    """The HBM ledger's rows: a latent cache and a layer's routed
    experts are rows of their own — what the first holds a token and
    how much of the weights the second is are what sizes such a
    deployment. (``expert_weights`` is a view INSIDE ``weights``, as
    ``kv_used`` is inside its pool.)"""
    return {"latent_kv_pool": attribution.tensor_bytes(cache),
            "expert_weights": attribution.tensor_bytes(
                [params["moe"][n] for n in glm.EXPERT_TENSORS])}


# ---------------------------------------------------------------------------
# Read / append over the tensors a latent cache holds
# ---------------------------------------------------------------------------

@jax.named_scope("kv_gather")
def _gather_rows(cache: Cache, i, table, span=None):
    """Layer ``i``'s latent rows arranged per table row: ``c_kv``
    [B, M, R] and ``k_pe`` [B, M, rope], the first ``span`` logical rows
    (whole blocks of the table prefix are gathered, then cut to the
    span — ``kvcache._gather_kv_layer``'s semantics). The blocks are
    gathered straight out of the pool seen as ``[L * blocks, block_len,
    width]``: slicing layer ``i`` out first copies the layer's whole
    pool, every layer of every step."""
    nb = table.shape[1] - 1                  # sentinel column: no rows
    out = []
    for name in ("c_kv", "k_pe"):
        pool = cache[name]
        L, n_blocks, bl = pool.shape[:3]
        if span is not None:
            nb = -(-span // bl)
        # (A sentinel id gathers the next layer's first block, or clamps:
        # garbage the caller's mask never admits, as before.)
        ids = i * n_blocks + table[:, :nb]
        rows = pool.reshape((L * n_blocks,) + pool.shape[2:])[ids]
        rows = rows.reshape(table.shape[0], nb * bl, -1)
        out.append(rows if span is None else rows[:, :span])
    return out


# Indices one scatter of the flush takes. The TPU compiler emits a
# scatter of more than ~1000 rows as straight-line code, row by row
# (3696 rows: half a million instruction bundles and 30-40 s of compile
# time a program; 528: a loop and 3 s), so the flush is a LOOP over
# (layer, piece) and each turn scatters one piece.
_SCATTER_ROWS = 512


@jax.named_scope("kv_write")
def _append_rows(cache: Cache, blk, off, c_kv, k_pe) -> Cache:
    """Rows ``[L, *blk.shape, width]`` land at the physical ``(blk,
    off)`` coordinates ``kvcache._phys`` gave; sentinel / overflow
    coordinates drop (scatter out of bounds). The pool is written in
    place, a layer and :data:`_SCATTER_ROWS` rows a turn: with the
    layer a WINDOW dim of one 4-D scatter (``.at[:, blk, off]``) the
    compiler transposes the whole donated pool into another layout and
    back — two copies of 2 GB in every program."""
    L, n_blocks = cache["c_kv"].shape[:2]
    n = blk.size
    pieces = -(-n // _SCATTER_ROWS)
    width = min(n, _SCATTER_ROWS)
    pad = pieces * width - n
    blk = jnp.pad(blk.reshape(-1), (0, pad), constant_values=n_blocks)
    off = jnp.pad(off.reshape(-1), (0, pad))
    rows = [jnp.pad(r.reshape(L, n, -1), ((0, 0), (0, pad), (0, 0)))
            for r in (c_kv, k_pe)]

    def turn(t, pools):
        layer, at = t // pieces, (t % pieces) * width
        b = lax.dynamic_slice_in_dim(blk, at, width)
        o = lax.dynamic_slice_in_dim(off, at, width)
        return tuple(
            pool.at[layer, b, o].set(lax.dynamic_slice(
                r, (layer, at, 0), (1, width, r.shape[2]))[0].astype(
                    pool.dtype))
            for pool, r in zip(pools, rows))

    out = dict(cache)
    out["c_kv"], out["k_pe"] = lax.fori_loop(
        0, L * pieces, turn, (cache["c_kv"], cache["k_pe"]))
    return out


def _need_table(table):
    if table is None:
        raise NotImplementedError(
            "the latent cache is paged only (no contiguous layout)")


def _no_extras(qweights, lora):
    if qweights is not None or lora is not None:
        raise NotImplementedError(
            "the latent-cache family serves float weights without adapters")


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill_batch(params, tokens, true_lens, cfg: glm.GlmMoeConfig,
                  qweights=None, lora=None, aid=None,
                  mesh=None, heads_axis=None) -> Tuple[Cache, jax.Array]:
    """Causal forward over a WAVE of right-padded prompts [W, S].
    Returns (``{"c_kv": [L, W, S, R], "k_pe": [L, W, S, rope]}``, logits
    at each request's last real position [W, vocab] float32). Padding
    rows run through the expert layer like any row (dropless: they can
    evict nothing) and are never read."""
    _no_extras(qweights, lora)
    x, rows = glm.forward_hidden(params, tokens, cfg, mesh, heads_axis)
    last = jnp.take_along_axis(
        x, (true_lens - 1)[:, None, None], axis=1)[:, 0]           # [W, D]
    return rows, glm.head_logits(cfg, params, last)


def insert(cache: Cache, prefix: Cache, slot, true_len, first_token,
           table=None) -> Cache:
    """Install one prefilled prompt (rows [L, S, width]) into a slot
    through its table row; the spare slot's all-sentinel row drops a
    dummy wave row's writes."""
    _need_table(table)
    S = prefix["c_kv"].shape[1]
    blk, off = kvcache._phys(cache, table, slot, jnp.arange(S))
    out = _append_rows(cache, blk, off, prefix["c_kv"], prefix["k_pe"])
    out["length"] = cache["length"].at[slot].set(true_len)
    out["last_token"] = cache["last_token"].at[slot].set(first_token)
    return out


def prefill_chunk(params, cache: Cache, tokens_c, start, n_valid, slot,
                  new_len, rng, cfg: glm.GlmMoeConfig, sp, *, final: bool,
                  qweights=None, table=None, span=None, kv_kernel=False,
                  lora=None, aid=None):
    """One chunk of an incremental prefill into a slot
    (``kvcache.prefill_chunk``'s contract): C query rows attend to the
    slot's resident rows ``< start`` (a reused prefix and earlier
    chunks, read from the first ``span`` logical rows) and causally to
    the chunk's own. Returns (cache', rng', first token — 0 unless
    ``final``)."""
    _need_table(table)
    _no_extras(qweights, lora)
    C = tokens_c.shape[0]
    M = span if span is not None else kvcache._logical_rows(cache, table)
    x = params["embed"].astype(cfg.dtype)[tokens_c][None]       # [1, C, D]
    cos, sin = glm.rope_tables(cfg, start + jnp.arange(C))
    j = jnp.arange(C)
    intra = ((j[None, :] <= j[:, None]) & (j[None, :] < n_valid))[None]
    resident = jnp.broadcast_to(jnp.arange(M)[None, None, :] < start,
                                (1, C, M))
    slot_table = lax.dynamic_slice_in_dim(table, slot, 1, 0)

    def layer_fn(x, layer, i, moe):
        q_nope, q_pe, c_kv, k_pe = glm.mla_project(cfg, layer, x, cos, sin)
        with jax.named_scope("attn_core"):
            rc, rp = _gather_rows(cache, i, slot_table, span)
            o = glm.latent_attention(
                cfg, layer["wkv_b"], q_nope, q_pe,
                [(rc, rp, resident), (c_kv, k_pe, intra)], CHUNK_ABSORBED)
        return glm.out_ffn(cfg, layer, x, o, moe)[0], (c_kv[0], k_pe[0])

    x, (c_l, p_l) = glm.scan_layers(cfg, params, x, layer_fn)
    if final:
        last = lax.dynamic_index_in_dim(x[0], n_valid - 1, 0,
                                        keepdims=False)
        logits = glm.head_logits(cfg, params, last)
        with jax.named_scope("sample"):
            rng, sub = jax.random.split(rng)
            tok = sampling_mod.sample(logits, sub, sp)
    else:
        tok = jnp.zeros((), jnp.int32)
    # Scatter (not dynamic_update_slice): a final partial chunk's
    # window may poke past the slot's blocks, and the overflow maps to
    # the sentinel block, where the write drops.
    blk, off = kvcache._phys(cache, table, slot, start + jnp.arange(C))
    out = _append_rows(cache, blk, off, c_l, p_l)
    out["length"] = cache["length"].at[slot].set(new_len)
    if final:
        out["last_token"] = cache["last_token"].at[slot].set(tok)
    return out, rng, tok


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _staged_steps(params, cache: Cache, cfg: glm.GlmMoeConfig, table, span,
                  k: int, first_tokens, next_token, live=None):
    """``k`` decode steps for every slot with the big cache a read-only
    invariant (``kvcache.decode_burst_staged``'s formulation): a step's
    latent rows land in a staging buffer [L, B, k, width]; attention is
    the resident rows (``< length`` at the start, a constant mask) and
    the staged columns ``<= step`` under one softmax; ONE scatter per
    tensor flushes all ``k`` rows afterwards. ``next_token(logits, s,
    last) -> (token fed to step s + 1, what the step emits)``. ``live``
    [B] bool: the rows whose tokens anyone keeps — the expert layers
    read only the experts THEY chose, and resident rows are gathered
    and attended for THEM alone, ``kvcache.TILE`` slots a turn
    (``kvcache._live_tiles``; absent: every row counts). Returns
    (cache with the rows flushed — bookkeeping untouched —, last token
    [B], emitted [k, ...], routed experts read [k]: a step's sum over
    its expert layers)."""
    _need_table(table)
    B = cache["length"].shape[0]
    M = span if span is not None else kvcache._logical_rows(cache, table)
    L = cfg.n_layers
    dt = cache["c_kv"].dtype
    pos0 = cache["length"]
    batch_ix = jnp.arange(B)
    tiles = kvcache._live_tiles(live, pos0, table)
    live = None if live is None else live[:, None]

    def step(carry, s):
        with jax.named_scope("decode_step"):
            last, sc, sp_ = carry
            x = params["embed"].astype(cfg.dtype)[last[:, None]]
            cos, sin = glm.rope_tables(cfg, (pos0 + s)[:, None])
            staged = (jnp.arange(k) <= s)[None, None, :]

            def layer_fn(c2, layer, i, moe):
                x, sc, sp_ = c2
                q_nope, q_pe, c_kv, k_pe = glm.mla_project(
                    cfg, layer, x, cos, sin)
                with jax.named_scope("attn_core"):
                    sc = sc.at[i, batch_ix, s].set(c_kv[:, 0].astype(dt))
                    sp_ = sp_.at[i, batch_ix, s].set(k_pe[:, 0].astype(dt))
                    lc = lax.dynamic_index_in_dim(sc, i, 0, False)
                    lp = lax.dynamic_index_in_dim(sp_, i, 0, False)

                    def attend(ids, pos, table_rows):
                        rc, rp = _gather_rows(cache, i, table_rows, span)
                        resident = kvcache._resident_mask(pos, M)
                        return glm.latent_attention(
                            cfg, layer["wkv_b"], q_nope[ids], q_pe[ids],
                            [(rc, rp, resident[:, None, :]),
                             (lc[ids], lp[ids], staged)], True)

                    o = kvcache._visit_tiles(
                        tiles, B, attend, (1, cfg.n_heads, cfg.v_head_dim))
                x, read = glm.out_ffn(cfg, layer, x, o, moe, live)
                return (x, sc, sp_), read

            (x, sc, sp_), reads = glm.scan_layers(
                cfg, params, (x, sc, sp_), layer_fn)
            logits = glm.head_logits(cfg, params, x[:, 0])
            last, emitted = next_token(logits, s, last)
        return (last, sc, sp_), (emitted, jnp.sum(reads))

    init = (first_tokens,
            jnp.zeros((L, B, k, cfg.kv_lora_rank), dt),
            jnp.zeros((L, B, k, cfg.qk_rope_head_dim), dt))
    (last, sc, sp_), (emitted, reads) = lax.scan(step, init, jnp.arange(k))
    blk, off = kvcache._phys(cache, table, batch_ix[:, None],
                             pos0[:, None] + jnp.arange(k)[None, :])
    return _append_rows(cache, blk, off, sc, sp_), last, emitted, reads


def decode_step(params, cache: Cache, cfg: glm.GlmMoeConfig,
                qweights=None, table=None, span=None,
                lora=None, aid=None) -> Tuple[Cache, jax.Array]:
    """One token for every slot: (cache' with the pending row written,
    logits [slots, vocab]). The caller samples and commits
    (``kvcache.commit_tokens``)."""
    _no_extras(qweights, lora)
    out, _, logits, _ = _staged_steps(
        params, cache, cfg, table, span, 1, cache["last_token"],
        lambda logits, s, last: (last, logits))
    return out, logits[0]


def decode_burst_staged(params, cache: Cache, rng, active, k: int,
                        cfg: glm.GlmMoeConfig, sp, qweights=None,
                        table=None, span=None, kv_kernel=False, lora=None,
                        aid=None):
    """``k`` decode steps in one program, the cache flushed once
    (``kvcache.decode_burst_staged``'s contract and RNG discipline).
    Only the ``active`` rows' expert choices are read; a dead row's
    token is discarded here and its cache rows drop at the sentinel
    block, as before. Returns (cache', rng', toks [k, slots]: the last
    column, the spare slot's, holds the step's experts read —
    :data:`SPARE_COLUMN`)."""
    _no_extras(qweights, lora)
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
        "the latent-cache family has no speculative verify program")
