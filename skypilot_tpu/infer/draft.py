"""Model-backed speculative drafter: a small model riding the engine's
own program machinery (docs/serving.md §Speculative decoding).

PR 8's n-gram drafter is pure host work but only pays on repetitive
text. :class:`DraftEngine` serves a REAL draft model (a llama3-400m-
class config in production; a truncated-layer draft of the target is
the zero-training starting point — :func:`truncated_draft`) and plugs
into the engine's ``spec_decode_burst`` as a *batched* drafter: K
greedy tokens per active slot per round from ONE device dispatch
(``kvcache.decode_burst_staged`` on the draft config — the identical
staged-burst program the main engine runs, at the draft model's size).

Design rules (the PAPER.md contract, restated for two models):

* **Static shapes, bounded programs.** The drafter compiles the same
  bounded grid the main engine does: one staged rollout program per
  (k, span-rung), one chunked ingest program per span rung, one
  batched sync program. Its own :class:`~skypilot_tpu.observability.
  flight.CompileWatch` guards the surface — ``warm_programs`` +
  ``declare_warmup_complete`` make a mid-traffic draft-model compile
  the same typed alarm a main-engine compile is.
* **Paged KV in lockstep.** The drafter owns a paged block-pool cache
  (same ``kvcache`` layout, block table + sentinel column). Slot ``s``
  of the drafter mirrors slot ``s`` of the main engine; its rows
  advance as the drafter rolls out and ROLL BACK exactly as the
  verifier's do — a length non-advance (``kvcache.sync_slots``), never
  a row copy or block move. Rows are content-tracked host-side
  (``_SlotState.toks``: the token backing each resident row), so after
  a verify commits ``n_commit`` tokens the longest valid row prefix is
  found by comparison and everything past it is dead by bookkeeping.
* **Correctness never depends on the draft.** The verifier is
  greedy-exact and unchanged; a bad draft only wastes verify
  positions. The drafter therefore keeps NO invariant the engine
  could violate: any state mismatch resolves to rollback + re-ingest.

The async pipeline (engine ``spec_pipeline``): while the main model's
verify dispatch is in flight, the engine calls :meth:`rollout` to run
the NEXT round's draft program against the drafter's committed-so-far
state — the drafter speculates on its own speculation (it assumes the
current draft fully accepts and predicts the verifier's bonus token as
its own next greedy token). The rollout's tokens are fetched LAZILY at
the next round's :meth:`draft_batch`, which validates them against
what the verifier actually committed: a full match serves the next
draft with zero new device work; a mispredicted round is discarded
host-side (rollback = length non-advance, free under paged blocks).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.infer import kvcache, sampling
from skypilot_tpu.models import llama
from skypilot_tpu.observability import attribution as attribution_lib
from skypilot_tpu.observability import flight as flight_lib
from skypilot_tpu.observability import metrics


@dataclasses.dataclass
class _SlotState:
    """Host mirror of one draft slot's device state. ``toks[i]`` is
    the token whose K/V occupies row ``i`` (committed AND speculative
    rollout rows — validity is decided by comparison against the
    verifier's committed context, never trusted); ``last`` is the
    pending token the next rollout step consumes (device
    ``last_token``); ``confirmed`` bounds how far the committed
    context has already been matched, so a steady-state sync compares
    O(new tokens), not O(context)."""
    toks: List[int]
    last: Optional[int]
    confirmed: int = 0


class DraftEngine:
    """A small model + paged KV cache + the three draft programs.

    Not a request scheduler: the MAIN engine owns admission, slots and
    retirement, and drives this through three calls —
    :meth:`draft_batch` (K draft tokens per slot, syncing the draft KV
    to the verifier's committed state first), :meth:`rollout` (the
    async predraft while a verify is in flight), and :meth:`release`
    (slot retired/preempted: blocks free, state drops). Single-thread
    contract: all calls come from the engine loop thread, exactly like
    the engine's own block management.
    """

    def __init__(self, params: llama.Params, cfg: llama.LlamaConfig,
                 n_slots: int, max_len: int, kv_int8: bool = False,
                 qweights=None, kv_block: Optional[int] = None,
                 kv_blocks: Optional[int] = None, span_buckets=None,
                 ingest_chunk: Optional[int] = None, seed: int = 1):
        from skypilot_tpu.infer.engine import _span_ladder
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.qweights = qweights
        # Paged block-pool layout, the engine's exact idiom: block
        # length clamped to a divisor of max_len, host-authoritative
        # table with a dirty-tracked device copy, sentinel last
        # column. The pool defaults to one full-length allocation per
        # slot (+ spare) — the draft model's KV is small, and a
        # drafter must never become the admission limiter.
        if kv_block is None:
            kv_block = int(os.environ.get("SKYTPU_DRAFT_KV_BLOCK",
                                          "256") or 0)
        self.paged = kv_block > 0
        if self.paged:
            b = min(kv_block, max_len)
            while max_len % b:
                b -= 1
            self.kv_block = b
            nb = max_len // b
            self.blocks_per_slot = nb
            self.n_kv_blocks = (kv_blocks if kv_blocks and kv_blocks > 0
                                else (n_slots + 1) * nb)
            self.allocator = kvcache.BlockAllocator(self.n_kv_blocks)
            self.block_table = np.full(
                (n_slots + 1, nb + 1), self.n_kv_blocks, np.int32)
            self._table_dev = None
            self._table_dirty = True
            self.cache = kvcache.init_paged_cache(
                cfg, n_slots + 1, self.n_kv_blocks, self.kv_block,
                kv_int8=kv_int8)
        else:
            self.kv_block = None
            self.blocks_per_slot = 0
            self.n_kv_blocks = 0
            self.allocator = None
            self.block_table = None
            self._table_dev = None
            self._table_dirty = False
            self.cache = kvcache.init_cache(cfg, n_slots + 1, max_len,
                                            kv_int8=kv_int8)
        self.span_ladder = _span_ladder(span_buckets, max_len)
        # One compiled ingest program per span rung: ``ingest_chunk``
        # is its static width (catch-up rows land in chunks of this).
        self.ingest_chunk = min(int(ingest_chunk or 256), max_len)
        self.rng = jax.random.key(seed)
        self._state: Dict[int, _SlotState] = {}
        # The one deferred rollout (async predraft): (device toks,
        # slots, k). At most one outstanding — the engine runs one
        # verify round at a time.
        self._pending_roll: Optional[
            Tuple[jax.Array, List[int], int]] = None
        # Introspection counters (tests + bench structure asserts).
        self.rollouts = 0            # rollout programs dispatched
        self.ingest_chunks = 0       # catch-up chunk programs
        self.rollbacks = 0           # speculative rows discarded
        self.reuse_hits = 0          # rounds served from a predraft
        self.decode_programs: set = set()
        self.compile_watch = flight_lib.CompileWatch()
        # Device-time calibration for the DRAFT model's programs: the
        # engine's "draft" flight records look their dev_ms_est up in
        # THIS calibrator (draft program identity is drafter-scoped,
        # exactly like its compile watch).
        self.devtime = attribution_lib.DeviceTimeCalibrator()
        self.compile_watch.calibrator = self.devtime

        sp = sampling.SamplingParams()     # drafting is argmax-only

        # The draft rollout: k greedy steps with on-device token
        # feedback — kvcache.decode_burst_staged on the DRAFT config,
        # the literal program the main engine bursts with. RNG rides
        # the signature (greedy sampling ignores it) so the program
        # shape matches the engine's; the drafter's stream is its own.
        @functools.partial(jax.jit, donate_argnums=(1, 2),
                           static_argnames=("k", "span"))
        def _rollout(params, cache, rng, active, table=None, *, k,
                     span=None, qweights=None):
            return kvcache.decode_burst_staged(
                params, cache, rng, active, k, cfg, sp,
                qweights=qweights, table=table, span=span)

        # Catch-up ingest: one chunk of committed tokens into a draft
        # slot — kvcache.prefill_chunk with ``final=False`` (no
        # sampling, no RNG split), stamping the running row count.
        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnames=("final", "span"))
        def _ingest(params, cache, tokens_c, start, n_valid, slot,
                    new_len, rng, table=None, *, final=False,
                    span=None, qweights=None):
            return kvcache.prefill_chunk(
                params, cache, tokens_c, start, n_valid, slot,
                new_len, rng, cfg, sp, final=final, qweights=qweights,
                table=table, span=span)

        # Lockstep/rollback: batched (length, last_token) sync — a
        # mispredicted rollout's rows die by this bookkeeping write
        # alone (kvcache.sync_slots).
        @functools.partial(jax.jit, donate_argnums=(0,))
        def _sync(cache, active, lengths, tokens):
            return kvcache.sync_slots(cache, active, lengths, tokens)

        watch = self.compile_watch.wrap
        self._rollout_fn = watch("draft_rollout", _rollout,
                                 ("k", "span"))
        self._ingest_fn = watch("draft_ingest", _ingest,
                                ("final", "span"))
        self._sync_fn = watch("draft_sync", _sync)

    # -- paged table (the engine's dirty-tracked device copy idiom) --------

    def table_device(self):
        if not self.paged:
            return None
        if self._table_dirty or self._table_dev is None:
            self._table_dev = jnp.asarray(self.block_table)
            self._table_dirty = False
        return self._table_dev

    @property
    def blocks_used(self) -> int:
        return self.allocator.used if self.paged else 0

    # -- span buckets ------------------------------------------------------

    def _span_for(self, rows: int) -> int:
        for s in self.span_ladder:
            if rows <= s:
                return s
        return self.span_ladder[-1]

    def _span_arg(self, span: int) -> Optional[int]:
        return None if span >= self.max_len else span

    # -- slot lifecycle ----------------------------------------------------

    def claimed(self, slot: int) -> bool:
        return slot in self._state

    def _acquire(self, slot: int) -> Optional[_SlotState]:
        """Fresh state + a full-length block allocation for a slot the
        engine started drafting on. Returns None when the draft pool
        is dry (custom-undersized pool): the slot simply gets an empty
        draft — the drafter degrades, it never stalls admission."""
        if self.paged:
            if self.allocator.available < self.blocks_per_slot:
                return None
            row = self.block_table[slot]
            row[:] = self.n_kv_blocks
            blocks = [self.allocator.alloc()
                      for _ in range(self.blocks_per_slot)]
            row[:len(blocks)] = blocks
            self._table_dirty = True
        st = _SlotState(toks=[], last=None, confirmed=0)
        self._state[slot] = st
        return st

    def release(self, slot: int) -> None:
        """Slot retired/preempted on the main engine: free its draft
        blocks and drop state. Rows a released slot leaves behind are
        dead by construction — its table row goes all-sentinel (other
        slots' rollout garbage writes for it drop) and a re-acquire
        starts from zero rows, re-ingesting everything it will read."""
        st = self._state.pop(slot, None)
        if st is None:
            return
        if self.paged:
            row = self.block_table[slot]
            for b in row[row < self.n_kv_blocks].tolist():
                self.allocator.decref(b)
            row[:] = self.n_kv_blocks
            self._table_dirty = True

    def reset(self) -> None:
        """Engine reset: drop all state (counts may be mid-failure
        inconsistent — wholesale, like the engine's allocator reset)."""
        self._state.clear()
        self._pending_roll = None
        if self.paged:
            self.allocator.reset()
            self.block_table[:] = self.n_kv_blocks
            self._table_dirty = True
        self.cache["length"] = jnp.zeros_like(self.cache["length"])

    def hbm_bytes(self) -> int:
        """Device-resident bytes the drafter holds (draft weights +
        its KV pool) — the engine's HBM ledger publishes this as the
        ``draft_pool`` component. Metadata reads only (nbytes), never
        a device fetch."""
        return (attribution_lib.tensor_bytes(self.params)
                + attribution_lib.tensor_bytes(self.qweights)
                + attribution_lib.tensor_bytes(self.cache))

    # -- drafting ----------------------------------------------------------

    def draft_batch(self, ctxs: Dict[int, Sequence[int]],
                    k: int) -> Dict[int, List[int]]:
        """Up to ``k`` draft tokens per slot, syncing each slot's
        draft KV to the verifier's committed context first.

        ``ctxs``: slot -> the request's committed context (prompt +
        committed tokens). Lockstep sync per slot: the longest row
        prefix backed by committed tokens stays (an accepted round's
        rows — and a matching predraft's — are valid by content);
        everything past it is discarded by a batched length/pending
        rollback; missing rows ingest through the chunk program. When
        a deferred predraft (:meth:`rollout`) matched what the
        verifier committed, the round is served with ZERO new device
        work — the async pipeline's win.
        """
        self._apply_pending()
        k = max(k, 1)
        preds: Dict[int, List[int]] = {}
        fix: Dict[int, Tuple[int, int]] = {}
        ctx_by_slot: Dict[int, List[int]] = {}
        need_roll: List[int] = []
        for slot, ctx in ctxs.items():
            # The caller hands a fresh per-round list (engine._ctx);
            # no defensive copy — the sync path is per slot per round
            # and an O(context) copy here is pure waste (the PR 11
            # _ctx_len lesson).
            if not isinstance(ctx, list):
                ctx = list(ctx)
            if not ctx:
                preds[slot] = []
                continue
            ctx_by_slot[slot] = ctx
            st = self._state.get(slot)
            if st is None:
                st = self._acquire(slot)
                if st is None:          # draft pool dry: degrade
                    preds[slot] = []
                    continue
            p = self._sync_slot(slot, st, ctx, fix)
            preds[slot] = p
            if len(p) >= k:
                self.reuse_hits += 1
            elif len(st.toks) + k <= self.max_len:
                need_roll.append(slot)
        if fix:
            self._dispatch_sync(fix)
        if need_roll:
            toks = self._dispatch_rollout(need_roll, k)
            # The draft path's completion fetch: the next verify
            # window needs these token VALUES host-side.
            arr = np.asarray(toks)
            self._apply_rollout(arr, need_roll, k)
            for slot in need_roll:
                st = self._state[slot]
                M = len(ctx_by_slot[slot])
                # Predictions beyond the context: O(k), never a full
                # toks+[last] concat (O(rows)) per round.
                preds[slot] = st.toks[M:] + [st.last]
        return {s: p[:k] for s, p in preds.items()}

    def rollout(self, slots: Sequence[int], k: int) -> bool:
        """Async predraft: dispatch one ``k``-step rollout for the
        given slots WITHOUT fetching (the engine calls this while its
        verify dispatch is in flight; the tokens are fetched — and
        validated against what the verify actually committed — at the
        next :meth:`draft_batch`). Slots without state or row headroom
        are skipped. Returns whether anything dispatched."""
        self._apply_pending()
        live = [s for s in slots
                if s in self._state
                and self._state[s].last is not None
                and len(self._state[s].toks) + k <= self.max_len]
        if not live or k <= 0:
            return False
        toks = self._dispatch_rollout(live, k)
        self._pending_roll = (toks, live, k)
        return True

    # -- internals ---------------------------------------------------------

    def _apply_pending(self) -> None:
        if self._pending_roll is None:
            return
        toks, slots, k = self._pending_roll
        self._pending_roll = None
        # Deferred fetch: the device finished this while the verify
        # round's fetch + commit bookkeeping ran.
        arr = np.asarray(toks)
        self._apply_rollout(arr, slots, k)

    def _apply_rollout(self, arr: np.ndarray, slots: Sequence[int],
                       k: int) -> None:
        for slot in slots:
            st = self._state.get(slot)
            if st is None:           # released mid-flight: rows dead
                continue
            p = [int(arr[j, slot]) for j in range(k)]
            st.toks.append(st.last)
            st.toks.extend(p[:-1])
            st.last = p[-1]

    def _sync_slot(self, slot: int, st: _SlotState, ctx: List[int],
                   fix: Dict[int, Tuple[int, int]]) -> List[int]:
        """Sync one slot to the committed context; returns the
        still-valid predictions beyond it ([] after a rollback)."""
        M = len(ctx)
        n = len(st.toks)
        have = n + (1 if st.last is not None else 0)
        if have >= M:
            # Compare WITHOUT materializing toks+[last] (O(rows) per
            # slot per round): seq[i] is toks[i] below n, last at n.
            i = st.confirmed
            while i < M and (st.toks[i] if i < n
                             else st.last) == ctx[i]:
                i += 1
            if i == M:
                # Full match: rows 0..M-2 are committed-backed, the
                # tail is the drafter's own consistent chain — its
                # outputs beyond the context are the live predictions
                # (O(k), the spare tail).
                st.confirmed = M - 1
                preds = st.toks[M:]
                if st.last is not None and n >= M:
                    # ``last`` sits at chain index n: a prediction
                    # only when it lies BEYOND the context (n >= M) —
                    # at n == M-1 it IS the committed pending token.
                    preds = preds + [st.last]
                return preds
        # Mismatch (or a fresh/short slot): roll back to the longest
        # committed-backed row prefix — a pure bookkeeping write, the
        # rows themselves never move (kvcache.sync_slots docstring).
        v = st.confirmed
        limit = min(len(st.toks), M - 1)
        while v < limit and st.toks[v] == ctx[v]:
            v += 1
        if v < len(st.toks):
            self.rollbacks += len(st.toks) - v
            del st.toks[v:]
        st.last = None
        if v < M - 1:
            self._ingest(slot, ctx, v, M - 1)
            st.toks.extend(ctx[v:M - 1])
        st.last = ctx[M - 1]
        st.confirmed = M - 1
        fix[slot] = (M - 1, ctx[M - 1])
        return []

    def _ingest(self, slot: int, ctx: List[int], start: int,
                upto: int) -> None:
        """Rows [start, upto) for tokens ctx[start:upto], in chunks of
        the static ingest width (one compiled program per span rung)."""
        C = self.ingest_chunk
        pos = start
        while pos < upto:
            n = min(C, upto - pos)
            chunk = np.zeros((C,), np.int32)
            chunk[:n] = ctx[pos:pos + n]
            sarg = self._span_arg(self._span_for(pos))
            self.decode_programs.add(("ingest", False, sarg))
            self.cache, self.rng, _ = self._ingest_fn(
                self.params, self.cache, jnp.asarray(chunk),
                jnp.asarray(pos, jnp.int32),
                jnp.asarray(n, jnp.int32),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(pos + n, jnp.int32), self.rng,
                self.table_device(), final=False, span=sarg,
                qweights=self.qweights)
            self.ingest_chunks += 1
            pos += n

    def _dispatch_sync(self, fix: Dict[int, Tuple[int, int]]) -> None:
        active = np.zeros((self.n_slots + 1,), bool)
        lengths = np.zeros((self.n_slots + 1,), np.int32)
        tokens = np.zeros((self.n_slots + 1,), np.int32)
        for slot, (ln, tok) in fix.items():
            active[slot] = True
            lengths[slot] = ln
            tokens[slot] = tok
        self.cache = self._sync_fn(
            self.cache, jnp.asarray(active), jnp.asarray(lengths),
            jnp.asarray(tokens))

    def _dispatch_rollout(self, slots: Sequence[int],
                          k: int) -> jax.Array:
        active = np.zeros((self.n_slots + 1,), bool)
        rows_max = 1
        for s in slots:
            active[s] = True
            rows_max = max(rows_max, len(self._state[s].toks))
        sarg = self._span_arg(self._span_for(rows_max))
        self.decode_programs.add(("rollout", k, sarg))
        self.cache, self.rng, toks = self._rollout_fn(
            self.params, self.cache, self.rng, jnp.asarray(active),
            self.table_device(), k=k, span=sarg,
            qweights=self.qweights)
        self.rollouts += 1
        return toks

    # -- warmup ------------------------------------------------------------

    def warm_programs(self, k: int) -> int:
        """Pre-compile the drafter's reachable grid against the spare
        slot (its table row is all-sentinel, writes drop) — same
        contract as the engine's sweep: run under metrics.suppress,
        scrub lengths after, republish compile metrics from the watch
        registry. Covers rollouts at k AND k+1 (the pipelined predraft
        width) per span rung, the ingest program per rung, and the
        sync program. Returns programs compiled."""
        before = self.compile_watch.count
        pre_keys = set(self.compile_watch.summary())
        k = max(int(k), 1)
        spare = self.n_slots
        active = np.zeros((self.n_slots + 1,), bool)
        active[spare] = True
        active_dev = jnp.asarray(active)
        with flight_lib.STARTUP.phase("warm_grid.draft"), \
                metrics.suppress():
            for span in self.span_ladder:
                sarg = self._span_arg(span)
                for kk in sorted({k, k + 1}):
                    self.cache, self.rng, _ = self._rollout_fn(
                        self.params, self.cache, self.rng, active_dev,
                        self.table_device(), k=kk, span=sarg,
                        qweights=self.qweights)
                chunk = jnp.zeros((self.ingest_chunk,), jnp.int32)
                self.cache, self.rng, _ = self._ingest_fn(
                    self.params, self.cache, chunk,
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(1, jnp.int32),
                    jnp.asarray(spare, jnp.int32),
                    jnp.asarray(0, jnp.int32), self.rng,
                    self.table_device(), final=False, span=sarg,
                    qweights=self.qweights)
            zeros = jnp.zeros((self.n_slots + 1,), jnp.int32)
            self.cache = self._sync_fn(
                self.cache, jnp.zeros((self.n_slots + 1,), bool),
                zeros, zeros)
            self.cache["length"] = jnp.zeros_like(self.cache["length"])
        self.compile_watch.drain_new()
        self.compile_watch.republish(pre_keys)
        return self.compile_watch.count - before

    def declare_warmup_complete(self) -> None:
        self.compile_watch.declare_warm()

    def stats(self) -> Dict[str, int]:
        return {
            "rollouts": self.rollouts,
            "ingest_chunks": self.ingest_chunks,
            "rollbacks": self.rollbacks,
            "reuse_hits": self.reuse_hits,
            "slots": len(self._state),
            "blocks_used": self.blocks_used,
            "pending": 1 if self._pending_roll is not None else 0,
        }


# ---------------------------------------------------------------------------
# Draft-model construction helpers.

def truncated_draft(params: llama.Params, cfg: llama.LlamaConfig,
                    n_layers: int) -> Tuple[llama.Params,
                                            llama.LlamaConfig]:
    """The zero-training draft model: the target's first ``n_layers``
    decoder blocks + its embedding/norm/head, sliced from the stacked
    per-layer tensors (no copies beyond the slice). Residual-stream
    models degrade gracefully under layer truncation, so this is the
    standard no-checkpoint starting point; a self-distilled draft
    (train/qlora on the target's outputs) slots into the same seam."""
    n_layers = max(1, min(int(n_layers), cfg.n_layers))
    dcfg = dataclasses.replace(cfg, n_layers=n_layers)
    blocks = {name: w[:n_layers] for name, w in params["blocks"].items()}
    return dict(params, blocks=blocks), dcfg


def truncated_qweights(qweights, n_layers: int):
    """The int8 tree of a ``self:N`` draft: the target's first
    ``n_layers`` quantized blocks + its quantized head."""
    return {"head": qweights["head"],
            "blocks": {name: {k: a[:n_layers] for k, a in qw.items()}
                       for name, qw in qweights["blocks"].items()}}


def self_distilled_pair(params: llama.Params, cfg: llama.LlamaConfig,
                        draft_layers: int):
    """(target_params, draft_params, draft_cfg) at the distillation
    ENDPOINT: the target's residual blocks past ``draft_layers`` get
    zeroed output projections (wo, w_down), so they pass the residual
    stream through unchanged and the truncated-layer draft agrees with
    the target exactly — the regime a finished self-distillation run
    converges toward. The bench and tests use it to exercise the
    draft/verify machinery at high acceptance without a training run;
    the zeroed layers still pay their full matmul cost, so the
    TARGET's decode cost is unchanged and the comparison stays honest.
    """
    draft_layers = max(1, min(int(draft_layers), cfg.n_layers))
    blocks = dict(params["blocks"])
    blocks["wo"] = blocks["wo"].at[draft_layers:].set(0)
    blocks["w_down"] = blocks["w_down"].at[draft_layers:].set(0)
    target = dict(params, blocks=blocks)
    draft, dcfg = truncated_draft(target, cfg, draft_layers)
    return target, draft, dcfg


def draft_engine_from_env(params: llama.Params, cfg: llama.LlamaConfig,
                          n_slots: int, max_len: int,
                          spec: Optional[str] = None,
                          kv_int8: bool = False,
                          seed: int = 1,
                          qweights=None) -> Optional[DraftEngine]:
    """Build the serving drafter from ``--draft-model`` /
    ``SKYTPU_DRAFT_MODEL``:

    * ``self:N`` — truncated-layer draft sharing the target's first N
      blocks (zero extra weights, zero extra checkpoints); a w8a8
      target passes its ``qweights`` and the draft runs w8a8 too;
    * a ``llama.CONFIGS`` name (e.g. ``llama3-400m``) — a separate
      draft config, randomly initialized (the repo's serving scaffold
      initializes the target the same way; a distilled checkpoint
      loads over it);
    * unset/empty — no model drafter (n-gram stays the default).
    """
    spec = (spec if spec is not None
            else os.environ.get("SKYTPU_DRAFT_MODEL", "")).strip()
    if not spec:
        return None
    dqweights = None
    if spec.startswith("self:"):
        n = int(spec.split(":", 1)[1])
        dparams, dcfg = truncated_draft(params, cfg, n)
        if qweights is not None:
            dqweights = truncated_qweights(qweights, dcfg.n_layers)
    elif spec in llama.CONFIGS:
        dcfg = llama.CONFIGS[spec]
        if dcfg.vocab_size != cfg.vocab_size:
            dcfg = dataclasses.replace(dcfg,
                                       vocab_size=cfg.vocab_size)
        dparams = llama.init_params(jax.random.key(seed), dcfg)
    else:
        raise ValueError(
            f"SKYTPU_DRAFT_MODEL={spec!r}: expected 'self:N' or one "
            f"of {sorted(llama.CONFIGS)}")
    return DraftEngine(dparams, dcfg, n_slots=n_slots,
                       max_len=max_len, kv_int8=kv_int8, seed=seed,
                       qweights=dqweights)
