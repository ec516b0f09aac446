"""Serve programs of the short-convolution family
(``models/lfm2_moe.py``): grouped-query attention layers whose K/V rows
live in the paged block pool, beside gated short-convolution layers
that hold, per slot, the last ``K - 1`` inputs of their convolution —
two rows of ``d_model`` at ``K = 3`` — and nothing per token.

The siblings of ``kvcache.py``'s, ``latent.py``'s, ``hybrid.py``'s and
``windowed.py``'s programs, with the same signatures, so the engine's
jitted entry points call this module through
``kvcache.programs_for(cfg)``; same block pool, block table, sentinel
column, span ladder and staging discipline for the attention layers'
rows. A module of its own rather than a second kind of state in
``hybrid.py``: that module's pool has a heads axis and its layer is the
delta-rule mixer's; what this family shares with the others it IMPORTS —
the flat pool's write, walk and in-place read are ``windowed.py``'s, the
live tiles ``kvcache.py``'s, the expert layer ``models/glm_moe.py``'s and
its count the latent family's.

Layout: ``k``, ``v`` ``[L_full, blocks, block_len, n_kv_heads * hd]`` —
the layer axis covers the ATTENTION layers only, and a row's 8 heads of
64 lie side by side on the minor axis, 512 values: laid ``[..., 8, 64]``
the minor dim would pad to 128 lanes, and a heads axis rounded up to a
tile of 16 (``hybrid.pool_heads``) would hold 8 heads of zeros — either
way twice the bytes in memory and in every read — plus, per conv layer
and SLOT (not per block: :data:`SLOT_STATE`), ``conv`` ``[L_conv, slots,
K - 1, d_model]`` in the compute dtype. What follows from a tail that
is no row:

* it cannot be shared by block or cut to a prefix, so the family runs
  without the prefix pool, the handoff and copy-on-write
  (:data:`UNSUPPORTED`); a preempted or recovered request re-prefills
  its whole context, which rebuilds its tails;
* a slot rented again starts from zero: a wave's ``insert`` overwrites
  the slot's tails, and a chunk at ``start == 0`` ignores what the slot
  holds (there is no reset program);
* a chunk at ``start > 0`` CONTINUES the slot's resident tails, and a
  padded wave row or a padded last chunk leaves the tail of its last
  REAL token;
* a decode program moves the tails of its LIVE slots only, carried
  through the ``k`` steps of a burst: a slot that is mid-prefill, free
  or the spare keeps what it holds.

Decode attention reads a live slot's rows IN PLACE, a block a turn, up
to the rows the slot holds (``windowed._attend_in_place``;
``DECODE_READS_BLOCKS_HELD``); a prefill chunk walks the slot's resident
rows under a running softmax (``windowed._attend_resident``). The spare
slot's column of a burst's tokens carries the experts read, as the
latent family's.

Paged layout only; no int8 rows or weights, no adapters, no tensor
parallelism, no speculative verify, no paged-attention kernel
(``engine.refuse_options``; ``docs/serving.md`` section Convolution
tails).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.infer import hybrid, kvcache, latent, windowed
from skypilot_tpu.infer import sampling as sampling_mod
from skypilot_tpu.models import glm_moe
from skypilot_tpu.models import lfm2_moe as lfm
from skypilot_tpu.observability import attribution

Cache = kvcache.Cache

# This family's answers to the engine (``kvcache.programs_for``).
FAMILY = "short-convolution (conv tails + paged KV)"
SLOT_STATE = ("conv",)
DECODE_READS_BLOCKS_HELD = True
# The experts a burst's steps read ride the spare slot's column, under
# the latent family's name and counter.
SPARE_COLUMN = latent.SPARE_COLUMN
_NO_TAIL = "a shared block holds K/V rows but no conv layer's tail"
_NO_ROLLBACK = "a rejected draft cannot take its inputs back out of a tail"
UNSUPPORTED = {
    "prefix_pool": _NO_TAIL,
    "import_prefix": _NO_TAIL,
    "export_prefix": _NO_TAIL,
    "kv_block=0": "the attention layers' cache is paged only",
    "kv_int8": "no int8 rows in a pool row of side-by-side heads",
    "weights_int8": "the expert and conv-operator matrices have no int8 "
                    "form",
    "tp": "no conv tail or expert layer under a mesh",
    "adapters": "no LoRA targets in the conv operator",
    "spec_k": _NO_ROLLBACK,
    "draft_model": _NO_ROLLBACK,
    "kv_kernel": "the paged-attention kernel reads per-head K/V",
}


def ring_rows(cfg) -> None:
    """No window layers: no ring (see ``kvcache.programs_for``)."""
    return None


# Expert layers x experts (see ``kvcache.programs_for``).
experts_per_step = glm_moe.experts_per_step


def init_paged_cache(cfg: lfm.Lfm2MoeConfig, n_slots: int, n_blocks: int,
                     block_len: int, kv_int8: bool = False) -> Cache:
    """``kvcache.init_paged_cache``'s sibling: the block pool holds the
    attention layers' rows; every slot holds a tail per conv layer."""
    if kv_int8:
        raise NotImplementedError("the short-conv cache has no int8 rows")
    kv = (cfg.n_full_layers, n_blocks, block_len, cfg.kv_width)
    return {
        "length": jnp.zeros((n_slots,), jnp.int32),
        "last_token": jnp.zeros((n_slots,), jnp.int32),
        "k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
        "conv": jnp.zeros((cfg.n_conv_layers, n_slots, cfg.conv_kernel - 1,
                           cfg.d_model), cfg.dtype)}


def token_bytes(cfg: lfm.Lfm2MoeConfig, cache=None) -> int:
    """Cache bytes a token holds: K and V in the attention layers only."""
    return cfg.n_full_layers * 2 * cfg.kv_width \
        * jnp.dtype(cfg.dtype).itemsize


def slot_state_bytes(cfg: lfm.Lfm2MoeConfig) -> int:
    """Bytes ONE slot's tails hold, all conv layers."""
    return cfg.n_conv_layers * (cfg.conv_kernel - 1) * cfg.d_model \
        * jnp.dtype(cfg.dtype).itemsize


def hbm_rows(cache: Cache, params) -> Dict[str, int]:
    """The HBM ledger's rows: the pool as the GQA family's, what the
    slots' tails hold whatever their length, and the routed experts (a
    view INSIDE ``weights``, as the latent family's)."""
    tails = attribution.tensor_bytes([cache[n] for n in SLOT_STATE])
    experts = [layer[name] for layer in params["layers"]
               for name in glm_moe.EXPERT_TENSORS if name in layer]
    return {"kv_pool": attribution.tensor_bytes(cache) - tails,
            "conv_tail": tails,
            "expert_weights": attribution.tensor_bytes(experts)}


def roofline_dims(cfg: lfm.Lfm2MoeConfig) -> Dict[str, int]:
    """A token multiplies with its chosen experts only; rows that grow
    with the context are attended in the attention layers only."""
    return {"param_count": cfg.active_params(),
            "n_layers": cfg.n_full_layers,
            "n_heads": cfg.n_heads, "head_dim": cfg.head_dim}


def _need_table(table):
    if table is None:
        raise NotImplementedError(
            "the short-conv cache is paged only (no contiguous layout)")


def _no_extras(qweights, lora, kv_kernel=False):
    if qweights is not None or lora is not None or kv_kernel:
        raise NotImplementedError(
            "the short-conv family serves float weights without adapters "
            "or the paged-attention kernel")


def _flat(rows):
    """K or V rows ``[..., n_kv_heads, hd]`` as the pool lays them:
    ``[..., n_kv_heads * hd]``."""
    return rows.reshape(rows.shape[:-2] + (-1,))


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill_batch(params, tokens, true_lens, cfg: lfm.Lfm2MoeConfig,
                  qweights=None, lora=None, aid=None,
                  mesh=None, heads_axis=None) -> Tuple[Cache, jax.Array]:
    """Causal forward over a WAVE of right-padded prompts [W, S] (a wave
    row is at most a chunk long: plain masked attention). Returns
    (``{"k", "v": [L_full, W, S, width], "conv": [L_conv, W, K - 1, D]}``
    — each row's tail after ITS last real token —, logits at each
    request's last real position [W, vocab] float32). Padding rows run
    through the expert layer like any row (dropless: they can evict
    nothing) and are never read."""
    _no_extras(qweights, lora)
    x, rows = lfm.forward_hidden(params, tokens, cfg, true_lens)
    last = jnp.take_along_axis(
        x, (true_lens - 1)[:, None, None], axis=1)[:, 0]           # [W, D]
    return ({"k": _flat(rows["k"]), "v": _flat(rows["v"]),
             "conv": rows["conv"]}, lfm.head_logits(cfg, params, last))


def insert(cache: Cache, prefix: Cache, slot, true_len, first_token,
           table=None) -> Cache:
    """Install one prefilled prompt into a slot: the attention layers'
    rows [L_full, S, width] through its table row, the tails whole
    (whatever the slot's last tenant left is overwritten). The spare
    slot's all-sentinel row drops a dummy wave row's K/V; its tails land
    in the spare's own entry, which nobody reads."""
    _need_table(table)
    out = windowed._write_pool(cache, table, slot,
                               jnp.arange(prefix["k"].shape[1]),
                               prefix["k"], prefix["v"])
    with jax.named_scope("kv_write"):
        out["conv"] = cache["conv"].at[:, slot].set(
            prefix["conv"].astype(cache["conv"].dtype))
        out["length"] = cache["length"].at[slot].set(true_len)
        out["last_token"] = cache["last_token"].at[slot].set(first_token)
    return out


def prefill_chunk(params, cache: Cache, tokens_c, start, n_valid, slot,
                  new_len, rng, cfg: lfm.Lfm2MoeConfig, sp, *, final: bool,
                  qweights=None, table=None, span=None, kv_kernel=False,
                  lora=None, aid=None):
    """One chunk of an incremental prefill into a slot
    (``kvcache.prefill_chunk``'s contract). An attention layer's C query
    rows attend to the slot's resident rows ``< start`` and causally to
    the chunk's own; a conv layer continues the slot's resident tail
    when ``start > 0`` and starts from zero at ``start == 0``, whatever
    the slot holds. Tokens at or past ``n_valid`` are padding. Returns
    (cache', rng', first token — 0 unless ``final``)."""
    _need_table(table)
    _no_extras(qweights, lora, kv_kernel)
    C = tokens_c.shape[0]
    kdt = cache["k"].dtype
    x = lfm.embed(cfg, params, tokens_c)[None]                  # [1, C, D]
    pos = start + jnp.arange(C)
    rope = lfm.rope_tables(cfg, pos)
    j = jnp.arange(C)
    intra = (j[None, :] <= j[:, None]) & (j[None, :] < n_valid)
    table_row = lax.dynamic_index_in_dim(table, slot, 0, keepdims=False)
    carried = start > 0
    valid = jnp.reshape(n_valid, (1,))

    def conv_fn(x, layer, ci, moe):
        tail = jnp.where(carried, cache["conv"][ci, slot], 0)[None]
        y, tail = lfm.short_conv(cfg, layer, x, tail, valid)
        return lfm.out_ffn(cfg, layer, x, y, moe)[0], tail[0]

    def full_fn(x, layer, fi, moe):
        q, k, v = lfm.attn_project(cfg, layer, x, rope)
        with jax.named_scope("attn_core"):
            o = windowed._attend_resident(cfg, cache, fi, table_row, start,
                                          q[0], k[0], v[0], intra)[None]
        x, _ = lfm.out_ffn(cfg, layer, x, lfm.attn_output(cfg, layer, o),
                           moe)
        return x, (_flat(k[0]).astype(kdt), _flat(v[0]).astype(kdt))

    x, tails, (k, v) = lfm.walk_layers(cfg, params, x, conv_fn, full_fn)
    if final:
        last = lax.dynamic_index_in_dim(x[0], n_valid - 1, 0,
                                        keepdims=False)
        logits = lfm.head_logits(cfg, params, last)
        with jax.named_scope("sample"):
            rng, sub = jax.random.split(rng)
            tok = sampling_mod.sample(logits, sub, sp)
    else:
        tok = jnp.zeros((), jnp.int32)
    # Scatter through the table: a final partial chunk's rows may poke
    # past the slot's blocks, and the overflow drops at the sentinel.
    out = windowed._write_pool(cache, table, slot, pos, k, v)
    with jax.named_scope("kv_write"):
        out["conv"] = cache["conv"].at[:, slot].set(
            tails.astype(cache["conv"].dtype))
        out["length"] = cache["length"].at[slot].set(new_len)
        if final:
            out["last_token"] = cache["last_token"].at[slot].set(tok)
    return out, rng, tok


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _staged_steps(params, cache: Cache, cfg: lfm.Lfm2MoeConfig, table,
                  span, k: int, first_tokens, next_token, live=None):
    """``k`` decode steps for every slot. The K/V pool is a read-only
    invariant (``kvcache.decode_burst_staged``'s formulation: a step's
    rows land in a staging buffer [L_full, B, k, width], attention is the
    resident rows and the staged columns ``<= step`` under one softmax,
    ONE flush afterwards); the conv tails are CARRIED through the steps
    and moved on for the ``live`` rows only ([B] bool; absent: every
    row). ``next_token(logits, s, last) -> (token fed to step s + 1,
    what the step emits)``. Returns (cache with rows flushed and tails
    advanced — length / last_token untouched —, last token [B], emitted
    [k, ...], routed experts read [k]: a step's sum over its expert
    layers)."""
    _need_table(table)
    B = cache["length"].shape[0]
    width, hd = cfg.kv_width, cfg.head_dim
    kdt = cache["k"].dtype
    pos0 = cache["length"]
    batch_ix = jnp.arange(B)
    n_tiles, order, _, table_rows = kvcache._live_tiles(live, pos0, table)
    if live is None:
        live = jnp.ones((B,), bool)
    # What an attention layer reads of the pool is bounded by residency —
    # a live slot's blocks up to the rows it holds, a dead slot's not at
    # all — and the bounds are constants of the program.
    L_full, n_blocks, bl = cache["k"].shape[:3]
    P = hybrid._span_blocks(cache, table, span)
    rows = jnp.where(live, pos0, 0)[order]
    held = jnp.stack([rows, hybrid._blocks_held(cache, rows, P)], axis=1)
    tiles = (n_tiles, order, held, table_rows)
    fk, fv = (cache[n].reshape(L_full * n_blocks, bl, width)
              for n in ("k", "v"))
    steps = jnp.arange(k)
    one = jnp.ones((B,), jnp.int32)

    def step(carry, s):
        with jax.named_scope("decode_step"):
            last, sk, sv, conv = carry
            x = lfm.embed(cfg, params, last[:, None])           # [B, 1, D]
            rope = lfm.rope_tables(cfg, (pos0 + s)[:, None])
            staged = (steps <= s)[None, :]

            def conv_fn(c, layer, ci, moe):
                x, sk, sv, conv = c
                y, moved = lfm.short_conv(cfg, layer, x, conv[ci], one)
                conv = conv.at[ci].set(
                    jnp.where(live[:, None, None], moved, conv[ci]))
                x, read = lfm.out_ffn(cfg, layer, x, y, moe, live[:, None])
                return (x, sk, sv, conv), read

            def full_fn(c, layer, fi, moe):
                x, sk, sv, conv = c
                q, kk, v = lfm.attn_project(cfg, layer, x, rope)
                with jax.named_scope("attn_core"):
                    sk = sk.at[fi, batch_ix, s].set(
                        kk.reshape(B, width).astype(kdt))
                    sv = sv.at[fi, batch_ix, s].set(
                        v.reshape(B, width).astype(kdt))

                    def attend(ids, held, table_rows):
                        return windowed._attend_in_place(
                            cfg, fk, fv, fi * n_blocks + table_rows[:, :P],
                            held[:, 1],
                            jnp.arange(P * bl)[None, :] < held[:, :1],
                            q[ids, 0], sk[fi][ids], sv[fi][ids], staged)

                    o = kvcache._visit_tiles(tiles, B, attend,
                                             (cfg.n_heads, hd))
                x, read = lfm.out_ffn(
                    cfg, layer, x, lfm.attn_output(cfg, layer, o[:, None]),
                    moe, live[:, None])
                return (x, sk, sv, conv), read

            (x, sk, sv, conv), r_conv, r_full = lfm.walk_layers(
                cfg, params, (x, sk, sv, conv), conv_fn, full_fn)
            reads = sum(jnp.sum(r) for r in (r_conv, r_full)
                        if r is not None)
            logits = lfm.head_logits(cfg, params, x[:, 0])
            last, emitted = next_token(logits, s, last)
        return (last, sk, sv, conv), (emitted, reads)

    stage = jnp.zeros((L_full, B, k, width), kdt)
    (last, sk, sv, conv), (emitted, reads) = lax.scan(
        step, (first_tokens, stage, stage, cache["conv"]), steps)
    out = windowed._write_pool(cache, table, batch_ix[:, None],
                               pos0[:, None] + steps[None, :], sk, sv)
    out["conv"] = conv
    return out, last, emitted, reads


def decode_step(params, cache: Cache, cfg: lfm.Lfm2MoeConfig,
                qweights=None, table=None, span=None,
                lora=None, aid=None, live=None) -> Tuple[Cache, jax.Array]:
    """One token for every slot: (cache' with the pending row written
    and the ``live`` rows' tails moved on, logits [slots, vocab]). The
    caller samples and commits (``kvcache.commit_tokens``)."""
    _no_extras(qweights, lora)
    out, _, logits, _ = _staged_steps(
        params, cache, cfg, table, span, 1, cache["last_token"],
        lambda logits, s, last: (last, logits), live=live)
    return out, logits[0]


def decode_burst_staged(params, cache: Cache, rng, active, k: int,
                        cfg: lfm.Lfm2MoeConfig, sp, qweights=None,
                        table=None, span=None, kv_kernel=False, lora=None,
                        aid=None):
    """``k`` decode steps in one program, the K/V flushed once and the
    ``active`` rows' tails carried from step to step
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
        "the short-conv family has no speculative verify program")
