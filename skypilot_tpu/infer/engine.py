"""Continuous-batching inference engine (JetStream-equivalent).

Slot-based serving: a fixed pool of decode slots advances one token per
``step()`` for every active request, while new requests prefill into free
slots between steps. All device programs are compiled once per prompt
bucket — admission/eviction is host-side bookkeeping only; no shape ever
changes on device.

TTFT = one bucketed prefill (+ queue wait); steady-state throughput =
slots x decode rate. The orchestration mirrors JetStream's
prefill-insert-generate loop, which is what the reference benchmarks on
TPU (reference: examples/tpu/v6e/README.md §Serve — 11.42 req/s,
1829 ms median TTFT on v6e; BASELINE.md).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import hashlib
import os
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu import chaos
from skypilot_tpu.infer import adapters as adapters_lib
from skypilot_tpu.infer import kvcache, sampling
from skypilot_tpu.infer import qos as qos_lib
from skypilot_tpu.models import llama, registry
from skypilot_tpu.observability import attribution as attribution_lib
from skypilot_tpu.observability import flight as flight_lib
from skypilot_tpu.observability import forensics as forensics_lib
from skypilot_tpu.observability import metrics, tracing
from skypilot_tpu.utils import timeline

# Live serving metrics (docs/observability.md). Span names match the
# histogram names exactly, so a Perfetto trace and a /metrics scrape
# describe the same instrumentation points.
PREFILL_SECONDS = metrics.histogram(
    "skytpu_prefill_seconds",
    "Admission-wave prefill latency, dispatch to first-token fetch, "
    "by prompt bucket", labelnames=("bucket",))
PREFILL_REQUESTS = metrics.counter(
    "skytpu_prefill_requests_total",
    "Requests prefilled, by prompt bucket", labelnames=("bucket",))
PREFILL_WAVES = metrics.counter(
    "skytpu_prefill_waves_total",
    "Admission waves prefilled, by prompt bucket and the row rung the "
    "wave was padded to", labelnames=("bucket", "rows"))
DECODE_STEP_SECONDS = metrics.histogram(
    "skytpu_decode_step_seconds",
    "Decode device-call latency, dispatch to token fetch (one call "
    "decodes a burst of k tokens per active slot)")
DECODE_TOKENS = metrics.counter(
    "skytpu_decode_tokens_total",
    "Output tokens committed to requests by decode")
TTFT_SECONDS = metrics.histogram(
    "skytpu_ttft_seconds",
    "Per-request time to first token (submit/enqueue to first token)",
    buckets=metrics.latency_buckets())
TPOT_SECONDS = metrics.histogram(
    "skytpu_tpot_seconds",
    "Per-request mean time per output token after the first",
    buckets=metrics.latency_buckets())
SLOTS_ACTIVE = metrics.gauge(
    "skytpu_slots_active", "Decode slots currently serving a request")
SLOTS_TOTAL = metrics.gauge(
    "skytpu_slots_total", "Configured decode slot pool size")
ENGINE_WAITING = metrics.gauge(
    "skytpu_engine_waiting",
    "Requests accepted by the engine but not yet prefilled")
REQUESTS_FINISHED = metrics.counter(
    "skytpu_requests_finished_total", "Requests fully generated")
PREFIX_HITS = metrics.counter(
    "skytpu_prefix_cache_hits_total",
    "Admissions that reused a resident prompt-prefix's KV rows "
    "(suffix-only prefill)")
PREFIX_MISSES = metrics.counter(
    "skytpu_prefix_cache_misses_total",
    "Admissions eligible for prefix reuse (pool enabled, prompt longer "
    "than one chunk) that found no resident prefix")
PREFIX_EVICTIONS = metrics.counter(
    "skytpu_prefix_cache_evictions_total",
    "Prefix-pool rows evicted (LRU) to admit a new prefix")
PREFIX_HIT_RATIO = metrics.gauge(
    "skytpu_prefix_cache_hit_ratio",
    "Lifetime fraction of prefix-eligible admissions that reused a "
    "resident prefix (hits / (hits + misses); 0 until the first "
    "eligible admission) — a gauge so fleet aggregation keeps the "
    "per-replica spread affinity routing is supposed to close")
PREFILL_CHUNKS = metrics.counter(
    "skytpu_prefill_chunks_total",
    "Chunked-prefill device calls (one fixed-size chunk each, "
    "interleaved with decode bursts), counted when the chunk is "
    "landed: awaited=1 the prompt's final chunk, whose token the host "
    "fetched; awaited=0 a chunk dispatched and not waited for",
    labelnames=("awaited",))
DECODE_STALL_SECONDS = metrics.histogram(
    "skytpu_decode_stall_seconds",
    "Time active decode slots waited on a prefill device call (one "
    "chunk or one admission wave) — the interference chunked prefill "
    "bounds",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0, 2.5, 5.0))
WINDOW_ROWS = metrics.counter(
    "skytpu_window_rows_read_total",
    "Ring rows the window layers of decode programs had to read: per "
    "dispatched program, the sum over its live slots of min(rows held, "
    "window) (models with sliding-window layers only)")
WINDOW_KEYS = metrics.counter(
    "skytpu_window_keys_scored_total",
    "Key rows the window layers of prefill programs had to score: per "
    "dispatched chunk or wave, the sum over its real tokens of "
    "min(position + 1, window) (models with sliding-window layers only)")
KV_BLOCKS_TOTAL = metrics.gauge(
    "skytpu_kv_blocks_total",
    "Paged KV cache: physical blocks in the pool (0 when the engine "
    "runs the contiguous layout)")
KV_TOKEN_BYTES = metrics.gauge(
    "skytpu_kv_cache_bytes_per_token",
    "Cache bytes one token holds over all layers, from the cache's own "
    "tensors: 2 x layers x kv_heads x head_dim (+ scales) for per-head "
    "K/V, layers x (kv_lora_rank + rope dim) for a latent (MLA) cache, "
    "the full-attention layers only for a hybrid cache, the global "
    "layers only for a windowed one, the attention layers only for a "
    "short-convolution one")
KV_BLOCKS_USED = metrics.gauge(
    "skytpu_kv_blocks_used",
    "Paged KV cache: blocks currently referenced by decode slots "
    "and/or resident prefix-cache entries")
KV_COW_COPIES = metrics.counter(
    "skytpu_kv_cow_copies_total",
    "Paged KV cache copy-on-write block copies (partial shared blocks "
    "duplicated on prefix store/hit before a writer touches them)")
SPEC_DRAFTED = metrics.counter(
    "skytpu_spec_drafted_total",
    "Speculative-decode draft tokens proposed (n-gram/prompt-lookup) "
    "and scored by a verify burst")
SPEC_ACCEPTED = metrics.counter(
    "skytpu_spec_accepted_total",
    "Speculative-decode draft tokens accepted (matched the model's "
    "greedy argmax and were committed)")
SPEC_ROLLBACKS = metrics.counter(
    "skytpu_spec_rollbacks_total",
    "Speculative-decode draft tokens not committed — rejected by "
    "verification, or discarded when the request retired mid-run "
    "(their KV rows sit past the committed length and are never read)")
SPEC_ACCEPT_RATE = metrics.gauge(
    "skytpu_spec_acceptance_rate",
    "Speculative-decode lifetime acceptance rate "
    "(accepted / drafted; 0 until the first draft)")
SPEC_DRAFT_TOKENS = metrics.counter(
    "skytpu_spec_draft_tokens_total",
    "Speculative-decode draft tokens proposed, by drafter kind: "
    "'model' = the draft-model engine (infer/draft.py), 'ngram' = "
    "the host prompt-lookup drafter (also the demotion fallback) — "
    "the fallback ladder model -> ngram -> off is observable per "
    "window", labelnames=("drafter",))
SPEC_VERIFY_WALL = metrics.counter(
    "skytpu_spec_verify_wall_seconds_total",
    "Host wall seconds spent per verify round, dispatch to fetch — "
    "the window the async draft pipeline overlaps draft work into")
SPEC_OVERLAP_WALL = metrics.counter(
    "skytpu_spec_overlap_wall_seconds_total",
    "Host wall seconds spent dispatching the NEXT round's draft "
    "rollout while the current verify was in flight (the pipelined "
    "predraft); overlap ratio = this over "
    "skytpu_spec_verify_wall_seconds_total")
DECODE_ATTN_ROWS = metrics.histogram(
    "skytpu_decode_attn_rows",
    "Span bucket (logical KV rows gathered per slot) actually "
    "dispatched for a decode/verify burst — decode attention "
    "bandwidth tracks this, not max_len (the full-view fallback)",
    buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384,
             32768))
KV_LAZY_GROWS = metrics.counter(
    "skytpu_kv_lazy_grows_total",
    "Paged KV blocks allocated by lazy per-burst growth "
    "(SKYTPU_KV_LAZY=1: admission reserves prompt + one burst of "
    "rows; the rest allocates at burst dispatch)")
DECODE_ATTN_PATH = metrics.counter(
    "skytpu_decode_attn_bursts_total",
    "Decode-family bursts (decode, verify, single-step) by big-cache "
    "attention read path: 'kernel' = the Pallas paged-attention "
    "kernel (SKYTPU_KV_KERNEL=1), 'gather' = the XLA logical-view "
    "gather (the parity oracle and contiguous/fallback path) — the "
    "kernel rollout is observable per burst",
    labelnames=("path",))
QOS_KV_QUOTA_STALLS = metrics.counter(
    "skytpu_qos_kv_quota_stalls_total",
    "Admissions stalled because the request's tenant is at its "
    "per-tenant KV-block quota (qos tenant spec max_kv_blocks) — a "
    "typed wait for the tenant's own retirements, never a 503; other "
    "tenants keep admitting",
    labelnames=("tenant",))
QOS_KV_BLOCKS = metrics.gauge(
    "skytpu_qos_kv_blocks_used",
    "Paged KV blocks currently charged to each tenant (table "
    "references, shared prefix blocks charged to every referencing "
    "tenant) — the quantity max_kv_blocks caps",
    labelnames=("tenant",))
ENGINE_RECOVERIES = metrics.counter(
    "skytpu_engine_recoveries_total",
    "Engine crash recoveries: a device dispatch seam raised, the "
    "engine reset (allocator/table/index wiped) and every in-flight "
    "request was re-admitted through the preemption resume path, "
    "by the seam that failed", labelnames=("seam",))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    submit_s: float = 0.0
    first_token_s: Optional[float] = None
    done: bool = False
    eos_id: Optional[int] = None
    # Identity of this request's trace span ("engine.request", recorded
    # at retirement): queue-wait/prefill/decode child spans parent to
    # it. parent_id links it into an external trace (the HTTP caller's
    # traceparent) when one rode in with the request.
    span_ctx: Optional[tracing.SpanContext] = None
    parent_id: Optional[str] = None
    # Prefix-cache / chunked-prefill stats (surfaced in the server's
    # response trailer and the prefill span's attrs).
    cached_len: int = 0
    n_chunks: int = 0
    prefill_begin_s: float = 0.0
    # Seconds from submit to the dispatch of this request's first
    # prefill program (wave or first chunk) — what the queue cost it;
    # the dispatch / fetch annotations report it beside the TTFT.
    queue_s: float = 0.0
    # Speculative-decode stats (surfaced next to the cache stats in
    # the response trailer) + per-request drafter state. ``spec_off``
    # flips when this request's acceptance collapses — it keeps riding
    # verify bursts with an empty draft (or plain bursts when every
    # active request collapsed), never paying wasted verify compute.
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_off: bool = False
    drafter: Optional[Any] = None
    # Drafter kind this request is currently riding ("model" when the
    # engine has a DraftEngine, else "ngram"; "off" once collapsed).
    # The acceptance-collapse fallback DEMOTES down the ladder
    # model -> ngram -> off, with a fresh acceptance window per rung
    # (spec_mode_drafted/accepted reset on demotion — the lifetime
    # spec_drafted/accepted keep feeding the trailer).
    spec_mode: Optional[str] = None
    spec_mode_drafted: int = 0
    spec_mode_accepted: int = 0
    # Multi-tenant QoS (docs/serving.md §Multi-tenant QoS): tenant
    # feeds the fair scheduler and flight attribution; priority picks
    # the lane (higher preempts lower); ``preemptions`` counts how
    # often this request was evicted mid-decode and resumed (surfaced
    # in the response trailer); ``resumed_len`` is the KV rows the
    # LAST resume reused warm from the prefix cache (0 = cold resume).
    tenant: str = qos_lib.DEFAULT_TENANT
    priority: int = 0
    preemptions: int = 0
    resumed_len: int = 0
    # Engine crash recoveries this request survived: each one is an
    # involuntary preemption — the request was re-admitted through the
    # same prompt+committed-tokens resume path eviction uses, so the
    # greedy output stays bit-identical (surfaced in the trailer).
    recoveries: int = 0
    # Per-tenant KV-block quota: True while this request sits queued
    # because its tenant is at max_kv_blocks — the typed stall event
    # and counter fire once per episode, not once per admission pass.
    kv_quota_stalled: bool = False
    # Multi-LoRA adapter catalog (docs/serving.md §Adapter catalog):
    # ``adapter`` names the fine-tune this request generates under
    # (None = the base model); ``adapter_slot`` is the device pool
    # slot serving it (0 = the all-zeros base adapter), assigned at
    # claim; ``adapter_pinned`` tracks the catalog's in-flight
    # refcount so release happens exactly once per acquire; ``error``
    # carries a typed failure body (adapter load failure) the server
    # returns instead of generated tokens — a failed adapter load
    # must NEVER silently fall through to the base model's weights.
    adapter: Optional[str] = None
    adapter_slot: int = 0
    adapter_pinned: bool = False
    error: Optional[Dict[str, Any]] = None
    # Request forensics (observability/forensics.py): admission-stall
    # episode accounting. ``stall_ms`` accumulates closed episodes by
    # cause (pool_dry / kv_quota / adapter_pin); an OPEN episode is
    # (stall_cause, stall_begin_s) and closes — idempotently — at the
    # successful claim. The retirement record carries the totals, and
    # the ledger's queue-wait gaps consume them into named stall
    # phases.
    stall_ms: Dict[str, float] = dataclasses.field(default_factory=dict)
    stall_cause: Optional[str] = None
    stall_begin_s: float = 0.0


@dataclasses.dataclass
class BurstHandle:
    """A dispatched-but-unfetched decode burst (see
    :meth:`InferenceEngine.dispatch_decode_burst`): ONE device program
    over every slot that had headroom, at the span rung covering the
    longest of them."""
    # [k, slots+1], still on device; the spare slot's column (the last)
    # carries the family's own count where it has one (``SPARE_COLUMN``).
    toks: jax.Array
    slots: List[int]                  # the slots the program decoded for
    k: int
    slot_req: Dict[int, "Request"]    # slot->request snapshot at dispatch
    # Span opened at dispatch, closed when the tokens are fetched —
    # double-records into skytpu_decode_step_seconds.
    span: Optional[timeline.Event] = None
    # The program's static span argument (None = full view): the
    # flight record written at completion carries the program identity.
    span_arg: Optional[int] = None
    # Compile-watch program key — the completion record's dev_ms_est
    # looks the calibrated device-time EWMA up by this identity.
    key: Optional[str] = None
    # Wall clock when the dispatch returned: the completion record
    # splits its host wall into dispatch vs fetch at this stamp.
    dispatch_done_s: Optional[float] = None
    # Burst sequence number: the dispatch and the fetch annotations of
    # one burst carry it, so a trace reader pairs them exactly.
    seq: int = 0
    # Blocks the burst's slots held at dispatch, where the family's
    # program reads by residency (``_family_notes``): the completion
    # record carries it beside ``tiles``.
    kv_blocks: Optional[int] = None
    # Ring rows the burst's slots held at dispatch (a family with
    # window layers; ``_family_notes``).
    window_rows: Optional[int] = None


class PromptTooLongError(ValueError):
    """Prompt exceeds the engine's largest prompt bucket. A client
    error, not an engine failure: the server maps it to HTTP 400 with a
    typed body (``typed_error``) instead of a 500."""

    def __init__(self, prompt_len: int, max_prompt_len: int):
        super().__init__(
            f"prompt length {prompt_len} exceeds max bucket "
            f"{max_prompt_len}")
        self.prompt_len = prompt_len
        self.max_prompt_len = max_prompt_len
        self.typed_error = {
            "type": "prompt_too_long",
            "message": str(self),
            "prompt_len": prompt_len,
            "max_prompt_len": max_prompt_len,
        }


class KvQuotaUnsatisfiableError(ValueError):
    """The request's own worst-case KV-block need exceeds its tenant's
    ``max_kv_blocks`` quota, so no amount of the tenant's retirements
    could ever admit it — stalling would hang the client forever. A
    client error (HTTP 400, typed body), never a stall or a 500."""

    def __init__(self, tenant: str, need: int, quota: int):
        super().__init__(
            f"request needs {need} KV blocks but tenant "
            f"{tenant!r} is capped at max_kv_blocks={quota}")
        self.typed_error = {
            "type": "kv_quota_unsatisfiable",
            "message": str(self),
            "tenant": tenant,
            "need_blocks": need,
            "max_kv_blocks": quota,
        }


class EngineDispatchError(RuntimeError):
    """A device dispatch seam (admission wave, prefill chunk, decode
    burst, spec verify) raised. The engine's host bookkeeping may
    disagree with device state, so the only safe move is a full
    ``reset()`` — but every in-flight request is recoverable through
    the preemption resume path (``recover()``): a crash is just an
    involuntary preemption. ``recoverable`` is the duck-typed flag the
    server loop keys recovery on."""

    recoverable = True

    def __init__(self, seam: str, cause: BaseException):
        super().__init__(f"engine dispatch failed at {seam}: {cause}")
        self.seam = seam
        self.cause = cause
        self.typed_error = {
            "type": "engine_dispatch_failed",
            "message": str(self),
            "seam": seam,
        }


class WeightsDoNotFitError(ValueError):
    """The serving weights alone are bigger than a device's memory: a
    start-up error that names the bytes, raised BEFORE anything is
    allocated — not an allocator crash minutes into the init. An
    operator error (pick --weights-int8, or more chips with --tp)."""

    def __init__(self, what: str, need_bytes: int, limit_bytes: int):
        super().__init__(
            f"{what} need {need_bytes:,} bytes per device but the "
            f"device holds {limit_bytes:,} (memory_stats bytes_limit); "
            f"serve int8 weights (--weights-int8) or shard over more "
            f"chips (--tp)")
        self.typed_error = {
            "type": "weights_do_not_fit",
            "message": str(self),
            "need_bytes": need_bytes,
            "limit_bytes": limit_bytes,
        }


class UnsupportedOptionError(ValueError):
    """An engine option the configuration's model family does not
    serve (yet): a typed start-up refusal naming the option, raised
    before anything is allocated — never a silent fallback to a path
    that computes something else."""

    def __init__(self, option: str, family: str, why: str):
        super().__init__(
            f"{option} is not supported for the {family} family: {why}")
        self.typed_error = {
            "type": "unsupported_option",
            "message": str(self),
            "option": option,
            "family": family,
        }


def window_keys(start: int, n: int, window: int) -> int:
    """Key rows a window layer must score for the ``n`` tokens at
    positions ``start .. start + n - 1``: the sum of ``min(p + 1,
    window)`` (a token sees itself and what its window still holds)."""
    end = start + n
    ramp = min(end, window)
    keys = (ramp * (ramp + 1) - start * (start + 1)) // 2 \
        if start < ramp else 0
    return keys + window * max(0, end - max(start, window))


def refuse_options(progs, **given) -> None:
    """Refuse, by name and before anything is allocated, each option of
    ``given`` that is on and that the family of serve programs ``progs``
    (``kvcache.programs_for``) cannot serve: the family's module lists
    them with their reasons (``UNSUPPORTED``)."""
    for option, on in given.items():
        if on and option in progs.UNSUPPORTED:
            raise UnsupportedOptionError(option, progs.FAMILY,
                                         progs.UNSUPPORTED[option])


def refuse_hybrid_options(**given) -> None:
    """:func:`refuse_options` for the hybrid family (``infer/hybrid.py``),
    which serves float weights from paged K/V beside a per-slot
    recurrent state on one device, with no shared blocks: a non-zero
    prefix pool, the handoff and everything the latent family refuses
    too are refused by name (docs/serving.md §Recurrent state lists
    them)."""
    from skypilot_tpu.infer import hybrid
    refuse_options(hybrid, **given)


class KvPoolWedgedError(RuntimeError):
    """The paged KV pool is exhausted and nothing can make progress:
    every block is held by an active slot (lazy growth has no victim
    to evict). Admission sizing should make this unreachable — hitting
    it means the pool is undersized for the configured slot count, an
    operator error, not a transient."""

    def __init__(self, detail: str):
        super().__init__(detail)
        self.typed_error = {
            "type": "kv_pool_wedged",
            "message": detail,
        }


@contextlib.contextmanager
def _dispatch_boundary(seam: str, point: bool = True):
    """Typed failure boundary around one device dispatch seam.

    Chaos point ``engine.dispatch`` fires inside the try so an injected
    fault takes the same wrap path a real device error would
    (``point=False``: the wait for a program whose dispatch already was
    a point). Typed client errors (prompt too long, unsatisfiable
    quota) pass through unwrapped — they are the caller's fault, not a
    crash — as do already-wrapped dispatch errors from a nested seam."""
    try:
        if point:
            chaos.point("engine.dispatch", seam=seam)
        yield
    except (EngineDispatchError, PromptTooLongError,
            KvQuotaUnsatisfiableError):
        raise
    except Exception as e:
        raise EngineDispatchError(seam, e) from e


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise PromptTooLongError(n, buckets[-1])


def _span_ladder(buckets, max_len: int) -> Tuple[int, ...]:
    """The span-bucket ladder: ascending rungs, largest always
    ``max_len`` (the full view — also the only rung when bucketing is
    disabled). ``buckets``: None -> the default power-of-two ladder
    (max_len/8, /4, /2, max_len — same idiom as the prefill prompt
    buckets); an explicit iterable -> its positive rungs clamped
    below max_len; empty/0 -> disabled. Rungs need no block
    alignment: the paged gather covers whole blocks and slices to
    the span. Every decode/verify/chunk program compiles once per
    rung it is dispatched at, so the ladder size bounds the compile
    count."""
    if buckets is None:
        ladder = [max_len // d for d in (8, 4, 2)]
    elif isinstance(buckets, int):
        ladder = [buckets] if buckets > 0 else []    # 0 = disabled
    else:
        ladder = [int(b) for b in buckets if int(b) > 0]
    rungs = {s for s in ladder if 0 < s < max_len}
    rungs.add(max_len)
    return tuple(sorted(rungs))


class PrefixIndex:
    """Host-side index over resident prompt prefixes.

    Hash granularity is the prefill chunk: a prompt's prefix is
    cacheable at every multiple of ``block`` tokens, keyed by a
    blake2b-128 digest of the token bytes (content-addressed — a
    Python ``hash`` collision would silently serve the wrong prefix).
    ``salt`` prefixes every digest: the engine feeds the request's
    ADAPTER identity through it, because stored K/V rows carry the
    fine-tune's wk/wv deltas — without the salt, two adapters sharing
    a prompt prefix would share cached K/V computed under whichever
    stored first (silently serving the wrong model).
    One ENTRY holds one stored prefix; every chunk-multiple key of
    that prefix points at the entry, so a shorter shared prefix hits
    it too. Eviction is LRU over entries (a hit or a store bumps the
    entry); evicting drops all of its keys.

    An entry's *payload* is storage-layout specific: the contiguous
    engine stores a pool ROW id (int, allocated via ``acquire_row``);
    the paged engine stores a TUPLE of ref-counted block ids
    (``insert_entry`` — the caller owns the ref-count bookkeeping and
    decrefs whatever ``evict_lru``/``clear``/``insert_entry`` report
    as evicted). ``rows`` caps resident entries either way.
    """

    def __init__(self, rows: int, block: int):
        self.rows = rows
        self.block = block
        self.clear()

    def clear(self) -> None:
        self._tick = 0
        self._keys: Dict[bytes, Tuple[Any, int]] = {}  # -> (payload, n)
        self._ent_keys: Dict[Any, set] = {}
        self._ent_used: Dict[Any, int] = {}            # payload -> LRU

    def _digest(self, prompt: List[int], n: int,
                salt: bytes = b"") -> bytes:
        return hashlib.blake2b(
            salt + np.asarray(prompt[:n], np.int64).tobytes(),
            digest_size=16).digest()

    def eligible(self, prompt: List[int]) -> bool:
        # The shortest cacheable prefix is one block, and at least one
        # suffix token must remain to produce the first-token logits.
        return len(prompt) > self.block

    def payloads(self) -> List[Any]:
        return list(self._ent_used)

    def lookup(self, prompt: List[int],
               salt: bytes = b"") -> Optional[Tuple[Any, int]]:
        """Longest resident chunk-aligned proper prefix of ``prompt``
        (under ``salt`` — the adapter-identity namespace); returns
        (payload, cached_len) and bumps the entry's LRU stamp."""
        for k in range((len(prompt) - 1) // self.block, 0, -1):
            ent = self._keys.get(
                self._digest(prompt, k * self.block, salt))
            if ent is not None:
                self._tick += 1
                self._ent_used[ent[0]] = self._tick
                return ent
        return None

    def _drop(self, payload) -> None:
        for key in self._ent_keys.pop(payload, ()):
            del self._keys[key]
        self._ent_used.pop(payload, None)

    def evict_lru(self) -> Optional[Any]:
        """Drop the least-recently-used entry; returns its payload (the
        caller releases the storage) or None when empty."""
        if not self._ent_used:
            return None
        payload = min(self._ent_used, key=self._ent_used.get)
        self._drop(payload)
        return payload

    def payloads_lru(self) -> List[Any]:
        """Resident payloads, least-recently-used first."""
        return sorted(self._ent_used, key=self._ent_used.get)

    def evict_entry(self, payload) -> None:
        """Drop one specific entry (the caller releases its storage)."""
        self._drop(payload)

    def acquire_row(self) -> Tuple[int, bool]:
        """Contiguous-pool payloads: a free row in [0, rows), or the
        LRU row evicted (its keys dropped). Returns (row, evicted)."""
        evicted = False
        free = [r for r in range(self.rows) if r not in self._ent_used]
        if free:
            row = free[0]
        else:
            row = min(self._ent_used, key=self._ent_used.get)
            self._drop(row)
            evicted = True
        self._tick += 1
        self._ent_used[row] = self._tick
        return row, evicted

    def insert_entry(self, prompt: List[int], n_tokens: int,
                     payload, salt: bytes = b"") -> List[Any]:
        """Paged payloads: admit a new entry, evicting LRU entries past
        the ``rows`` cap. Returns the evicted payloads (caller decrefs
        their blocks)."""
        evicted: List[Any] = []
        while len(self._ent_used) >= self.rows:
            p = self.evict_lru()
            if p is None:
                break
            evicted.append(p)
        self._tick += 1
        self._ent_used[payload] = self._tick
        self.register(prompt, n_tokens, payload, salt)
        return evicted

    def register(self, prompt: List[int], n_tokens: int,
                 payload, salt: bytes = b"") -> None:
        """Point every not-yet-resident chunk multiple <= n_tokens at
        ``payload`` (shorter multiples already resident keep their
        entry — both copies hold identical bytes)."""
        for k in range(1, n_tokens // self.block + 1):
            d = self._digest(prompt, k * self.block, salt)
            if d not in self._keys:
                self._keys[d] = (payload, k * self.block)
                self._ent_keys.setdefault(payload, set()).add(d)


class NGramDrafter:
    """Prompt-lookup speculative drafter (host-side, zero device work).

    The request's context (prompt + committed tokens) is indexed by
    trailing n-gram: ``_index`` maps each n-gram to the START of its
    most recent occurrence that already has a continuation. Drafting
    looks up the context's last n tokens and proposes the up-to-k
    tokens that followed that earlier occurrence — the prompt-lookup
    heuristic: repeated spans (shared boilerplate, quoted input, a
    generation that has entered a cycle) verify at near-full
    acceptance, and a miss costs nothing (empty draft).

    No second model, no trained weights: correctness never depends on
    draft quality because verification is greedy-exact — a bad draft
    only wastes the verify burst's spare positions.
    """

    def __init__(self, tokens: List[int], n: int = 2):
        self.n = max(int(n), 1)
        self.tokens: List[int] = []
        self._index: Dict[Tuple[int, ...], int] = {}
        self.extend(tokens)

    def extend(self, toks) -> None:
        """Append committed tokens, indexing each n-gram the moment it
        gains a continuation (the trailing n-gram itself is never
        indexed — it has nothing after it to draft)."""
        for t in toks:
            self.tokens.append(int(t))
            j = len(self.tokens) - self.n - 1
            if j >= 0:
                self._index[tuple(self.tokens[j:j + self.n])] = j

    def catch_up(self, prompt: List[int], generated: List[int]) -> None:
        """Sync with the request after tokens committed through any
        path (verify bursts, a plain-decode fallback burst, the
        admission first token)."""
        missing = len(prompt) + len(generated) - len(self.tokens)
        if missing > 0:
            self.extend(generated[len(generated) - missing:])

    def draft(self, k: int) -> List[int]:
        """Up to ``k`` proposed continuation tokens ([] on a miss or a
        context shorter than one n-gram — degenerate prompts draft
        nothing rather than guessing).

        Self-extending: when the matched continuation runs into the
        end of the context (the most recent occurrence is near the
        tail — ALWAYS the case once generation enters a cycle), the
        lookup continues from the draft's own tail n-gram, which by
        construction re-matches an earlier occurrence. A tight loop
        therefore drafts the full K instead of the 1-2 tokens left
        after the nearest match."""
        if k <= 0 or len(self.tokens) < self.n:
            return []
        out: List[int] = []
        # Only the trailing n tokens ever feed the key — keep the
        # lookup O(n + k), not O(context): drafting runs per slot per
        # burst on the verify hot path.
        tail = self.tokens[-self.n:]
        while len(out) < k:
            key = tuple((tail + out)[-self.n:])
            j = self._index.get(key)
            if j is None:
                break
            take = self.tokens[j + self.n:j + self.n + k - len(out)]
            if not take:
                break
            out.extend(take)
        return out


@dataclasses.dataclass
class _ChunkState:
    """A request mid-chunked-prefill: slot claimed, rows [0, pos)
    resident (reused prefix and/or completed chunks), first token not
    yet produced (or, on a preemption resume, the NEXT token not yet
    produced). ``ctx`` is the admission-time context snapshot — the
    prompt for a fresh request, prompt + committed tokens for a
    preempted request resuming."""
    req: Request
    pos: int            # next row offset to prefill
    total: int          # len(ctx)
    ctx: Optional[List[int]] = None


@dataclasses.dataclass
class _ChunkHandle:
    """A dispatched chunk program whose bookkeeping has not run yet
    (see :meth:`InferenceEngine.prefill_chunk_step`). A non-final
    chunk's token is garbage nobody reads: the handle keeps it only as
    the thing to wait on when the chunk is landed."""
    tok: jax.Array                    # still on device
    req: Request
    final: bool
    begin_s: float                    # wall clock before the dispatch
    dispatch_done_s: float            # ... and when it returned
    key: Optional[str]                # compile-watch program key
    span_arg: Optional[int]           # the program's static span
    decode_active: bool               # rows were decoding at dispatch
    # Decode bursts dispatched before this chunk: a burst with a higher
    # ``seq`` runs behind it, so that burst's landing proves it done.
    burst_seq: int
    window_keys: Optional[int] = None


class InferenceEngine:
    """Single-model continuous-batching engine.

    Parameters live wherever the caller put them (replicated or
    TP-sharded under a mesh); the engine only compiles and schedules.
    """

    def __init__(self, params: llama.Params, cfg: llama.LlamaConfig,
                 n_slots: int = 8, max_len: int = 1024,
                 prompt_buckets: Tuple[int, ...] = (128, 512, 1024),
                 sampling_params: sampling.SamplingParams = sampling.SamplingParams(),
                 eos_id: Optional[int] = None, seed: int = 0,
                 kv_int8: bool = False, weights_int8: bool = False,
                 qweights=None, max_wave: Optional[int] = None,
                 pad_waves: bool = False, mesh=None, shard_rules=None,
                 prefill_chunk: Optional[int] = None,
                 prefix_pool: Optional[int] = None,
                 kv_block: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 spec_drafter: Optional[Callable] = None,
                 draft_engine: Optional[Any] = None,
                 spec_pipeline: Optional[bool] = None,
                 span_buckets=None, kv_lazy: Optional[bool] = None,
                 kv_kernel: Optional[bool] = None,
                 flight_recorder: Optional[
                     flight_lib.FlightRecorder] = None,
                 qos: Optional[qos_lib.FairScheduler] = None,
                 adapters: Optional[
                     adapters_lib.AdapterCatalog] = None,
                 forensics: Optional[bool] = None,
                 exemplar_store: Optional[
                     forensics_lib.ExemplarStore] = None):
        self.params = params
        # Multi-tenant QoS: a FairScheduler reorders ``waiting`` into
        # priority lanes + DRR interleave before each admission pass
        # and arms priority preemption-by-eviction (preempt_slot).
        # None (the default) is the zero-cost single-tenant path —
        # admission order stays pure FIFO and nothing ever preempts.
        self.qos = qos
        self.cfg = cfg
        # The serve programs of the config's family: kvcache's own
        # (GQA rows), infer/latent.py's (MLA latent rows) or
        # infer/hybrid.py's (K/V rows beside a per-slot recurrent
        # state). Every jitted entry point below calls through this,
        # everything that moves blocks without reading rows is shared,
        # and what differs by family is a question the module answers
        # (kvcache.programs_for lists them) — first of all which of
        # these options it cannot serve.
        self._progs = progs = kvcache.programs_for(cfg)
        # Rows a window layer keeps per slot (None: the family has no
        # such layer, and says nothing of them in its annotations).
        self._ring_rows = progs.ring_rows(cfg)
        # Expert layers x experts; a family without an expert layer
        # says nothing of them.
        per_step = getattr(progs, "experts_per_step", None)
        self._experts_per_step = per_step(cfg) if per_step else None
        refuse_options(progs, **{
            "kv_block=0": kv_block == 0 or (
                kv_block is None and os.environ.get(
                    "SKYTPU_KV_BLOCK", "256") in ("", "0")),
            "kv_int8": kv_int8,
            "weights_int8": weights_int8 or qweights is not None,
            "tp": mesh is not None,
            "adapters": adapters is not None,
            "spec_k": (spec_k if spec_k is not None else int(
                os.environ.get("SKYTPU_SPEC_K", "0") or 0)) > 0,
            "draft_model": draft_engine is not None,
            "kv_kernel": bool(kv_kernel) or (
                kv_kernel is None
                and os.environ.get("SKYTPU_KV_KERNEL", "") == "1"),
            "prefix_pool": (prefix_pool if prefix_pool is not None else int(
                os.environ.get("SKYTPU_PREFIX_POOL", "0") or 0)) > 0})
        self.n_slots = n_slots
        self.max_len = max_len
        self.buckets = tuple(b for b in prompt_buckets if b <= max_len)
        # Chunked prefill: prompts longer than ``prefill_chunk`` are
        # prefilled in fixed-size chunks interleaved with decode bursts
        # (one compiled chunk program for every bucket and offset)
        # instead of one per-bucket O(S^2) monolith that stalls every
        # decode slot for the whole prompt. 0 disables. Budget knob:
        # SKYTPU_PREFILL_CHUNK (ctor arg wins).
        if prefill_chunk is None:
            prefill_chunk = int(
                os.environ.get("SKYTPU_PREFILL_CHUNK", "512") or 0)
        self.prefill_chunk = (prefill_chunk
                              if prefill_chunk and prefill_chunk > 0
                              else None)
        # Prefix KV reuse: up to ``prefix_pool`` resident prompt
        # prefixes at chunk granularity; a request whose prompt shares
        # a resident prefix prefills only the suffix. Paged engines
        # store a prefix as ref-counted shared blocks (near-zero cost);
        # the contiguous layout reserves ``prefix_pool`` pool rows in a
        # separate tensor and copies rows on store/hit. Requires
        # chunking (the suffix runs through the chunk program). Budget
        # knob: SKYTPU_PREFIX_POOL. 0 disables.
        if prefix_pool is None:
            prefix_pool = int(
                os.environ.get("SKYTPU_PREFIX_POOL", "0") or 0)
        self.prefix_pool = (max(prefix_pool, 0)
                            if self.prefill_chunk else 0)
        # Admission wave cap: a burst of N requests prefills as
        # ceil(N/max_wave) device calls instead of one. Each wave's
        # first tokens can then stream out (step_burst's on_wave hook)
        # while later waves are still prefilling — early requests'
        # TTFT stops paying for the whole burst's prefill.
        # <= 0 means uncapped (a 0 cap would otherwise spin _admit
        # forever on empty waves).
        self.max_wave = max_wave if max_wave and max_wave > 0 else None
        # pad_waves: every admission wave is padded (dummy rows ->
        # spare slot) to the smallest rung of the fixed row ladder
        # {1, max_wave} that holds it, so TWO compiled programs per
        # bucket serve every wave and warm_programs can warm them all:
        # no mid-traffic XLA compile can ever stall a request (a fresh
        # (bucket, rows) pair otherwise compiles on first sight — tens
        # of seconds on an 8B model). A lone arrival prefills one row;
        # a wave of 2..max_wave-1 pays dummy prefill compute. Unpadded
        # engines pad to the next power of two of the wave's size and
        # compile each pair on first sight.
        self.pad_waves = bool(pad_waves and self.max_wave)
        if self.pad_waves:
            self.wave_rungs = tuple(sorted({1, self.max_wave}))
        else:
            cap = self.max_wave or n_slots
            self.wave_rungs = tuple(
                1 << i for i in range((cap - 1).bit_length() + 1))
        self.sampling_params = sampling_params
        self.eos_id = eos_id
        # Speculative decoding: a host-side drafter proposes up to K
        # tokens per slot per burst and ONE compiled verify program
        # scores the K+1 window positions in a single forward pass —
        # K is static, so no new retrace surface. Greedy-exact: spec
        # is forced off under temperature sampling (verification is
        # only output-preserving for argmax, and the RNG stream must
        # stay untouched). Budget knob: SKYTPU_SPEC_K (ctor arg wins;
        # 0 = off — the library default; the server defaults to 4).
        # Clamped to [0, 16]: each K compiles its own program and
        # acceptance past a handful of tokens is workload fantasy.
        if spec_k is None:
            spec_k = int(os.environ.get("SKYTPU_SPEC_K", "0") or 0)
        spec_k = max(0, min(int(spec_k), 16))
        if sampling_params.temperature > 0.0:
            spec_k = 0
        self.spec_k = spec_k
        # Pluggable drafter factory (request -> drafter with the
        # NGramDrafter protocol: catch_up/draft). The per-request seam
        # PR 8 built; default is prompt-lookup. It is ALSO the
        # demotion target: a request whose model-draft acceptance
        # collapses falls back to this factory's drafter.
        self._spec_drafter_factory = (
            spec_drafter
            if spec_drafter is not None
            else (lambda req: NGramDrafter(req.prompt)))
        # Model-backed batched drafter (infer/draft.py DraftEngine):
        # when present, requests start in "model" mode — K tokens per
        # slot per round from the draft model's own staged-burst
        # program, its paged KV advanced/rolled-back in lockstep with
        # the verifier's commits. The n-gram factory above stays the
        # zero-cost fallback rung.
        self.draft_engine = draft_engine
        # Async draft/verify pipeline: while a verify dispatch is in
        # flight, the drafter runs the NEXT round's rollout (its
        # prediction of the bonus token + the following K drafts) and
        # the fetch reconciles — a matched predraft serves the next
        # round with zero new draft work, a miss is discarded
        # host-side (drafter rollback = length non-advance). Only
        # meaningful with a model drafter (n-gram drafting is pure
        # host work with nothing to overlap). Knob:
        # SKYTPU_SPEC_PIPELINE (default on; ctor arg wins).
        if spec_pipeline is None:
            spec_pipeline = (
                os.environ.get("SKYTPU_SPEC_PIPELINE", "1") != "0")
        self.spec_pipeline = bool(spec_pipeline) \
            and draft_engine is not None
        # Per-request acceptance-collapse fallback: once a request has
        # drafted >= spec_min_drafted tokens at an acceptance rate
        # below spec_min_rate IN ITS CURRENT MODE, it demotes down the
        # drafter ladder (model -> ngram -> off) — verify compute
        # stops being wasted on a workload the current drafter can't
        # predict, and the burst degrades to plain decode when every
        # active request has collapsed.
        self.spec_min_drafted = 16
        self.spec_min_rate = 0.2
        self._spec_drafted_total = 0
        self._spec_accepted_total = 0
        # Paged KV cache: the default storage layout. Fixed-size blocks
        # from one shared pool + a per-slot block table decouple slot
        # count from worst-case length — a slot's HBM rent is its
        # ACTUAL rows (rounded up to a block), not max_len, so slot
        # count grows ~max_len/need x at the same HBM. Knobs:
        # SKYTPU_KV_BLOCK (block length, default 256; 0 = contiguous
        # layout) and SKYTPU_KV_BLOCKS (pool size in blocks, default
        # the contiguous-equivalent HBM: (slots+1) * max_len / block).
        if kv_block is None:
            kv_block = int(os.environ.get("SKYTPU_KV_BLOCK", "256")
                           or 0)
        self.paged = kv_block > 0
        if self.paged:
            # Largest divisor of max_len <= the requested block: the
            # block axis must tile max_len exactly for the logical->
            # physical row map to stay a static reshape.
            b = min(kv_block, max_len)
            while max_len % b:
                b -= 1
            self.kv_block = b
            nb = max_len // b
            if kv_blocks is None:
                kv_blocks = int(
                    os.environ.get("SKYTPU_KV_BLOCKS", "0") or 0)
            self.n_kv_blocks = kv_blocks if kv_blocks > 0 \
                else (n_slots + 1) * nb
            if self.n_kv_blocks < nb:
                raise ValueError(
                    f"kv_blocks={self.n_kv_blocks} cannot hold one "
                    f"max_len request ({nb} blocks of {b})")
            self.blocks_per_slot = nb
            self.allocator = kvcache.BlockAllocator(self.n_kv_blocks)
            # Per-slot block table (+ spare). One extra column pinned
            # to the sentinel (== n_kv_blocks): logical rows past the
            # slot's allocation scatter out of bounds and drop. Host
            # numpy is authoritative; a cached device copy rides into
            # every program (_table_device).
            self.block_table = np.full(
                (n_slots + 1, nb + 1), self.n_kv_blocks, np.int32)
            self._table_dev = None
            self._table_dirty = True
        else:
            self.kv_block = None
            self.n_kv_blocks = 0
            self.blocks_per_slot = 0
            self.allocator = None
            self.block_table = None
            self._table_dev = None
            self._table_dirty = False
        # Span-bucketed decode attention: decode/verify/chunk programs
        # compile per SPAN BUCKET (a power-of-two ladder whose largest
        # rung is max_len — the full view) and gather only the first
        # span logical rows, so decode KV bandwidth tracks the ACTIVE
        # span of the round, not the engine's worst-case length. The
        # ladder is the entire new retrace surface; selection is
        # host-side: a decode round is ONE program at the rung that
        # covers its longest live slot (a program runs every batch
        # row whatever it holds and its time does not fall as its
        # span grows, so one program at the widest span present is
        # never slower than one per span present). Knob:
        # SKYTPU_SPAN_BUCKETS (ctor arg wins) — a comma-separated
        # explicit ladder, or 0 to disable (full view only).
        if span_buckets is None:
            env = os.environ.get("SKYTPU_SPAN_BUCKETS", "").strip()
            if env:
                span_buckets = [int(t) for t in
                                env.replace(",", " ").split()]
        self.span_ladder = _span_ladder(span_buckets, max_len)
        # Pallas paged-attention kernel (SKYTPU_KV_KERNEL=1 /
        # --kv-kernel, ctor arg wins): decode/verify/chunk big-cache
        # reads walk each slot's block table in-kernel instead of
        # materializing the gathered logical view per layer. Paged
        # layouts only — a contiguous engine falls back to the gather
        # path (typed event, not an error) which also remains the
        # greedy-parity oracle and is selectable at runtime by leaving
        # the flag off. The flag is a STATIC jit argument on every
        # kernel-capable entry point, so it is part of compile-watch
        # program identity and can never be a retrace surface (it is
        # engine-constant).
        if kv_kernel is None:
            kv_kernel = os.environ.get("SKYTPU_KV_KERNEL", "") == "1"
        self.kv_kernel = bool(kv_kernel) and self.paged
        if kv_kernel and not self.paged:
            tracing.add_event(
                "engine.kv_kernel_fallback",
                {"reason": "contiguous_layout"}, echo=True)
        # Decode-side program keys actually dispatched ((kind, width,
        # span) tuples; span None = the full view): the retrace-
        # discipline tests assert this stays bounded by the ladder —
        # never one program per observed length.
        self.decode_programs: set = set()
        # Flight recorder: one record per device burst (wave/chunk/
        # decode/verify), program identity + group composition + host
        # timing, zero device fetches. Injectable so tests/bench can
        # observe an isolated window; None/disabled is a no-op guard.
        self.flight = (flight_recorder if flight_recorder is not None
                       else flight_lib.RECORDER)
        # Compile watch: program registry over the jit entry points
        # below — first-dispatch compile cost, and the mid-traffic
        # unexpected-compile alarm once warmup is declared complete.
        self.compile_watch = flight_lib.CompileWatch()
        # Device-time calibration: every Nth hit dispatch of a program
        # key (SKYTPU_DEVTIME_EVERY; 0 = off) is timed synchronously
        # through the calibrator's bracket, maintaining a per-program
        # EWMA of pure device seconds — the dev_ms_est column flight
        # records carry next to host wall.
        self.devtime = attribution_lib.DeviceTimeCalibrator()
        self.compile_watch.calibrator = self.devtime
        # Per-burst attribution accumulators for the flight record
        # (loop-thread only): COW copies / prefix evictions / lazy
        # grows since the previous record.
        self._fl_cow = 0
        self._fl_evictions = 0
        self._fl_lazy_grows = 0
        # Lifetime prefix-cache hit/miss tallies (loop-thread only)
        # backing the skytpu_prefix_cache_hit_ratio gauge — a gauge,
        # not two counters, so the fleet aggregator can show the
        # per-replica min/max spread that makes affinity skew visible
        # (counters are summed across instances; gauges keep theirs).
        self._prefix_hit_n = 0
        self._prefix_miss_n = 0
        # Request forensics (observability/forensics.py): one
        # retirement record per request (the ledger's anchor) plus
        # streaming P2 tail detection on TTFT/TPOT that pins crossing
        # requests' full evidence into the exemplar store. A plain
        # runtime-flippable flag, exactly like the recorder's — the
        # off path is bit-identical and the bench gates the on path
        # at <= 1.01x.
        if forensics is None:
            forensics = forensics_lib.forensics_enabled()
        self.forensics = bool(forensics)
        self.tail = forensics_lib.TailDetector()
        self.exemplars = (exemplar_store if exemplar_store is not None
                          else forensics_lib.EXEMPLARS)
        # Lazy per-burst block growth (paged only): admission reserves
        # the prompt plus ONE burst of rows instead of the full
        # max_new_tokens worst case; the rest allocates at burst
        # dispatch through the same dry-pool evict/stall path
        # admission uses. Eager stays the default: lazy trades the
        # no-mid-flight-fault guarantee for tighter reservations (a
        # slot the pool cannot grow sits a burst out until
        # retirements free blocks). Knob: SKYTPU_KV_LAZY=1.
        if kv_lazy is None:
            kv_lazy = os.environ.get("SKYTPU_KV_LAZY", "") == "1"
        self.kv_lazy = bool(kv_lazy) and self.paged
        self._lazy_headroom = max(16, self.spec_k + 1)
        # One hidden spare slot (index n_slots): batched admission pads
        # its wave with dummy prefills targeting the spare, so one
        # compiled program serves every wave size. (Paged: the spare's
        # table row stays all-sentinel — dummy writes drop, zero block
        # cost.)
        if self.paged:
            build_cache = lambda: progs.init_paged_cache(
                cfg, n_slots + 1, self.n_kv_blocks, self.kv_block,
                kv_int8=kv_int8)
        else:
            build_cache = lambda: kvcache.init_cache(
                cfg, n_slots + 1, max_len, kv_int8=kv_int8)
        # Contiguous layout only: the separate prefix-pool tensor.
        # Paged engines need no pool — a stored prefix is just shared
        # ref-counted blocks mapped into the new slot's table.
        build_pool = (
            (lambda: kvcache.init_prefix_pool(
                cfg, self.prefix_pool, max_len, kv_int8=kv_int8))
            if self.prefix_pool and not self.paged else None)
        # Tensor-parallel serving: the cache (and pool) are created
        # ALREADY sharded over the mesh's tp axis — each device
        # allocates only its kv-heads' share. Built whole and resharded
        # afterwards, a 32-slot 8B cache would first have to fit the
        # one chip it is being split to relieve.
        self.mesh = mesh
        heads_axis = None
        if mesh is not None:
            from skypilot_tpu.parallel import sharding as sh
            rules = shard_rules or sh.INFER_TP_RULES
            self._shard_rules = rules
            heads_axis = rules.get("heads")
            self.cache = sh.init_sharded(
                build_cache, kvcache.cache_logical_axes, mesh, rules)
            self.pool = (sh.init_sharded(
                build_pool, kvcache.pool_logical_axes, mesh, rules)
                if build_pool else None)
        else:
            self.cache = build_cache()
            self.pool = build_pool() if build_pool else None
        self._prefix_index = (PrefixIndex(self.prefix_pool,
                                          self.prefill_chunk)
                              if self.prefix_pool else None)
        KV_BLOCKS_TOTAL.set(self.n_kv_blocks)
        # w8a8 serving: int8 weights for BOTH prefill and decode, so no
        # fp copy of the seven block matrices (or the head) is kept —
        # the memory halving that fits an 8B-class model on a 16 GB
        # chip. ``qweights`` may be passed pre-built (with a slim
        # params tree: embed + norms only). Not wired for MoE experts.
        self.qweights = qweights
        if weights_int8 and qweights is None:
            if hasattr(cfg, "n_experts"):
                raise NotImplementedError(
                    "weights_int8 is not supported for MoE configs yet")
            self.qweights = jax.jit(lambda prm: {
                "blocks": kvcache.quantize_block_weights(prm),
                "head": kvcache.quantize_head(prm, cfg),
            })(params)
        if self.qweights is not None:
            self.params = params = kvcache.slim_params(params)
        # Tensor-parallel serving: shard params/qweights over the
        # mesh's tp axis (Megatron head/mlp/vocab split; the KV cache
        # above shards its kv_heads dim, so each device holds its
        # heads' KV). A no-op for weights that were built sharded
        # (random_serving_weights). The jitted prefill/decode programs
        # need NO changes — XLA SPMD partitions them from the input
        # shardings, inserting the all-reduces where wo/w_down contract
        # (verified token-exact vs a single-device engine in
        # tests/test_infer_tp.py). Multi-chip 70B-class serving is this
        # + enough chips.
        if mesh is not None:
            self.params = params = sh.shard_tree_subset(
                params, llama.param_logical_axes(cfg), mesh, rules)
            if self.qweights is not None:
                self.qweights = sh.shard_tree_subset(
                    self.qweights, kvcache.qweight_logical_axes(cfg),
                    mesh, rules)
        self.rng = jax.random.key(seed)

        # Multi-LoRA adapter catalog (docs/serving.md §Adapter
        # catalog): a device-resident stacked (A, B) pool + host LRU
        # hot-load/evict. Per-slot adapter ids live in a host numpy
        # array with a dirty-tracked device copy — EXACTLY the block-
        # table idiom — and ride every program as data, so adapter
        # count/identity never enters program identity (the compile
        # watch is the guard). None (the default) is the zero-cost
        # adapterless path: every program traces exactly as before.
        self.adapters = adapters
        if adapters is not None:
            self.adapter_ids = np.zeros((n_slots + 1,), np.int32)
            self._aid_dev = None
            self._aid_dirty = True
        else:
            self.adapter_ids = None
            self._aid_dev = None
            self._aid_dirty = False

        # HBM ledger + roofline model (observability/attribution.py):
        # analytical byte accounting of every device-resident tensor
        # family this engine owns, refreshed from host bookkeeping at
        # every _update_gauges, and the per-record FLOPs/bytes cost
        # model behind the MFU / bandwidth-utilization columns. KV
        # bytes-per-token is computed from the ACTUAL cache dtypes
        # (int8 KV counts its fp32 scales).
        self._kv_token_bytes = progs.token_bytes(cfg, self.cache)
        KV_TOKEN_BYTES.set(self._kv_token_bytes)
        self._kv_block_bytes = (self._kv_token_bytes * self.kv_block
                                if self.paged else 0)
        weight_bytes = (attribution_lib.tensor_bytes(self.params)
                        + attribution_lib.tensor_bytes(self.qweights))
        self.hbm_ledger = attribution_lib.HbmLedger()
        self._weight_bytes = weight_bytes
        self.roofline = attribution_lib.Roofline(
            weight_bytes=weight_bytes,
            kv_token_bytes=self._kv_token_bytes, d_model=cfg.d_model,
            max_len=max_len, chunk_tokens=self.prefill_chunk,
            **progs.roofline_dims(cfg))
        # The draft model's rollouts attribute at ITS scale, not the
        # verifier's — a second roofline on the draft config.
        self._draft_roofline = None
        if draft_engine is not None:
            dcfg = draft_engine.cfg
            d_itemsize = draft_engine.cache["k"].dtype.itemsize
            d_kvt = 2 * dcfg.n_layers * dcfg.n_kv_heads \
                * dcfg.head_dim * d_itemsize \
                + (2 * dcfg.n_layers * dcfg.n_kv_heads * 4
                   if "k_scale" in draft_engine.cache else 0)
            self._draft_roofline = attribution_lib.Roofline(
                param_count=dcfg.num_params(),
                weight_bytes=(
                    attribution_lib.tensor_bytes(draft_engine.params)
                    + attribution_lib.tensor_bytes(
                        draft_engine.qweights)),
                kv_token_bytes=d_kvt, d_model=dcfg.d_model,
                n_layers=dcfg.n_layers, n_heads=dcfg.n_heads,
                head_dim=dcfg.head_dim, max_len=draft_engine.max_len)
        peak_flops, peak_bw = attribution_lib.device_peaks()
        attribution_lib.ROOFLINE_PEAK_FLOPS.set(peak_flops)
        attribution_lib.ROOFLINE_PEAK_BW.set(peak_bw)
        # Per-tenant KV-block quotas (qos tenant spec max_kv_blocks):
        # blocks a slot's table references are charged to its tenant
        # at claim/growth and refunded when the slot's blocks free.
        # Shared prefix blocks charge EVERY referencing tenant — a
        # reference holds the block live, so each referencing tenant
        # pays. Host bookkeeping only (loop thread).
        self._slot_kv_charge: Dict[int, Tuple[str, int]] = {}
        self._tenant_kv: Dict[str, int] = {}
        self.free_slots = list(range(n_slots))
        self.slot_req: Dict[int, Request] = {}
        self.waiting: Deque[Request] = collections.deque()
        self.chunking: Deque[_ChunkState] = collections.deque()
        # Non-final chunks of the head chunker that were dispatched and
        # not awaited, oldest first: at most one when the next chunk is
        # dispatched, none once a final chunk has been fetched.
        self._queued_chunks: Deque[_ChunkHandle] = collections.deque()
        self.finished: List[Request] = []
        # Requests a crashed admission pass was holding in locals
        # (crash safety; see _rescue_admit_limbo).
        self._admit_limbo: List[Request] = []
        self._next_rid = 0
        # Tokens dispatched to the device but not yet committed
        # host-side (one outstanding async burst at a time is the
        # expected pattern; the count caps the next burst).
        self._inflight_tokens = 0
        self._burst_seq = 0      # decode bursts dispatched (annotations)
        # Static ledger components once; the dynamic ones (kv_used,
        # prefix_pinned) refresh with the slot gauges, so the ledger
        # init must follow the slot bookkeeping above. The runtime
        # cross-check fills bytes_in_use / the true bytes_limit where
        # the backend reports memory_stats (CPU: typed fallback event,
        # analytical-only).
        self._init_hbm_ledger()
        SLOTS_TOTAL.set(n_slots)
        self._update_gauges()

        sp = self.sampling_params

        # The cache is donated everywhere: the engine reassigns
        # self.cache from the output every call, so XLA updates the
        # [L, slots, max_len, G, hd] buffers in place, never copying.

        # RNG lives on device and every program splits it INTERNALLY,
        # returning the successor key: a host-side jax.random.split per
        # call would be an extra eagerly-dispatched device program on
        # the hot path (per decode burst / admission wave).

        # Batched admission: ONE batched prefill for the whole wave (the
        # W requests share every weight read; matmuls run at W x S
        # rows), then a scan of per-request cache inserts (cheap
        # scatters). Dummy rows target the spare slot; its length
        # bookkeeping is zeroed HERE (last row of the length vector)
        # rather than by a follow-up eager scatter per wave.
        @functools.partial(jax.jit, donate_argnums=(1, 5),
                           static_argnames=("bucket",))
        def _admit_wave(params, cache, tokens_b, true_lens, slots, rng,
                        table=None, lora=None, aid=None, *, bucket,
                        qweights=None):
            del bucket
            from jax import lax as _lax
            rng, sub = jax.random.split(rng)
            prefix, logits = progs.prefill_batch(
                params, tokens_b, true_lens, cfg, qweights=qweights,
                lora=lora, aid=aid, mesh=mesh, heads_axis=heads_axis)
            with jax.named_scope("sample"):
                first = sampling.sample(logits, sub, sp)      # [W]

            def ins(c, w):
                rows = {name: _lax.dynamic_index_in_dim(
                    t, w, 1, keepdims=False) for name, t in prefix.items()}
                c = progs.insert(c, rows, slots[w], true_lens[w],
                                 first[w], table=table)
                return c, None

            cache, _ = _lax.scan(ins, cache,
                                 jnp.arange(tokens_b.shape[0]))
            cache["length"] = cache["length"].at[-1].set(0)  # spare
            return cache, rng, first

        @functools.partial(jax.jit, donate_argnums=(1, 2),
                           static_argnames=("span",))
        def _decode(params, cache, rng, active, table=None,
                    lora=None, aid=None, qweights=None, *, span=None):
            rng, sub = jax.random.split(rng)
            # A per-slot state moves on for the rows that keep their
            # token only; rows in a pool need no such care (a dead
            # slot's pending row lands past its length).
            own = {"live": active} if progs.SLOT_STATE else {}
            cache, logits = progs.decode_step(params, cache, cfg,
                                                qweights=qweights,
                                                table=table, span=span,
                                                lora=lora, aid=aid, **own)
            with jax.named_scope("sample"):
                toks = sampling.sample(logits, sub, sp)
            cache = kvcache.commit_tokens(cache, toks, active)
            return cache, rng, toks

        # Burst decode: k steps in one device program -> one host round
        # trip per k tokens. Crucial when dispatch cost rivals the
        # per-token compute (small models). The
        # program is the STAGED formulation — in-burst rows accumulate
        # in a small staging buffer and flush to the cache once per
        # burst, keeping the big cache a loop invariant (see
        # kvcache.decode_burst_staged; ~25% faster than a scan of
        # per-step cache updates on an 8B model).
        @functools.partial(jax.jit, donate_argnums=(1, 2),
                           static_argnames=("k", "span", "kernel"))
        def _decode_burst(params, cache, rng, active, table=None,
                          lora=None, aid=None, *, k,
                          qweights=None, span=None, kernel=False):
            return progs.decode_burst_staged(
                params, cache, rng, active, k, cfg, sp,
                qweights=qweights, table=table, span=span,
                kv_kernel=kernel, lora=lora, aid=aid)

        # Speculative verify: the decode_burst_staged formulation with
        # the sampled-token feedback replaced by the host's draft
        # window and greedy argmax outputs + on-device acceptance. No
        # RNG argument at all — the greedy stream stays untouched, so
        # spec-on and spec-off runs consume identical RNG.
        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnames=("k", "span", "kernel"))
        def _verify(params, cache, draft, n_draft, active, table=None,
                    lora=None, aid=None, *, k, qweights=None,
                    span=None, kernel=False):
            return progs.verify_draft_staged(
                params, cache, draft, n_draft, active, k, cfg,
                qweights=qweights, table=table, span=span,
                kv_kernel=kernel, lora=lora, aid=aid)

        # Chunked-prefill programs: ONE chunk program (two traces: the
        # ``final`` variant samples the first token and splits the RNG)
        # serves every bucket and every suffix offset; the claim/copy
        # programs are trivial gathers/scatters.
        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnames=("final", "span", "kernel"))
        def _prefill_chunk(params, cache, tokens_c, start, n_valid,
                           slot, new_len, rng, table=None, lora=None,
                           aid=None, *, final,
                           qweights=None, span=None, kernel=False):
            return progs.prefill_chunk(
                params, cache, tokens_c, start, n_valid, slot, new_len,
                rng, cfg, sp, final=final, qweights=qweights,
                table=table, span=span, kv_kernel=kernel, lora=lora,
                aid=aid)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _claim(cache, slot, claim_len):
            return kvcache.claim_slot(cache, slot, claim_len)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _pool_load(cache, pool, row, slot, claim_len):
            return kvcache.pool_load(cache, pool, row, slot, claim_len)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _pool_store(pool, cache, slot, row):
            return kvcache.pool_store(pool, cache, slot, row)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _copy_block(cache, src, dst):
            return kvcache.copy_block(cache, src, dst)

        # Cross-replica KV handoff (docs/serving.md §Disaggregated
        # serving): gather a stored prefix's physical blocks to host,
        # scatter them into a receiving replica's pool. The index
        # vector is FIXED-width (blocks_per_slot, sentinel-padded), so
        # each direction is one compiled program for the engine's
        # lifetime — a handoff can never hit a mid-traffic compile.
        @jax.jit
        def _export_blocks(cache, idx):
            return kvcache.export_blocks(cache, idx)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _import_blocks(cache, idx, vals):
            return kvcache.import_blocks(cache, idx, vals)

        # Adapter hot-load: scatter one fine-tune's stacked (A, B)
        # weights into a pool slot (pool donated — the install is in
        # place). Weight shapes are pool constants, so ONE program
        # serves every adapter for the engine's lifetime; it rides the
        # compile watch and the warm grid like every other entry point,
        # which is what makes mid-traffic hot-loads compile-free.
        @functools.partial(jax.jit, donate_argnums=(0,))
        def _adapter_install(pool, slot, weights):
            return adapters_lib.pool_install(pool, slot, weights)

        # Every jit entry point rides the compile watch: a program key
        # is (entry point, static args) — plus the wave's ROW COUNT,
        # which is shape-derived identity jit recompiles on even under
        # an unchanged static key. First dispatch records the compile
        # wall; post-warmup new keys raise the unexpected-compile
        # alarm. The wrappers are transparent pass-throughs (donation
        # and async dispatch semantics unchanged).
        watch = self.compile_watch.wrap
        self._admit_wave_fn = watch(
            "admit_wave", _admit_wave, ("bucket",),
            key_fn=lambda a, kw: (("rows", a[2].shape[0]),))
        self._decode_fn = watch("decode1", _decode, ("span",))
        self._decode_burst_fn = watch("decode_burst", _decode_burst,
                                      ("k", "span", "kernel"))
        self._verify_fn = watch("verify", _verify,
                                ("k", "span", "kernel"))
        self._prefill_chunk_fn = watch("prefill_chunk", _prefill_chunk,
                                       ("final", "span", "kernel"))
        self._claim_fn = watch("claim", _claim)
        self._pool_load_fn = watch("pool_load", _pool_load)
        self._pool_store_fn = watch("pool_store", _pool_store)
        self._copy_block_fn = watch("copy_block", _copy_block)
        self._export_blocks_fn = watch("export_blocks", _export_blocks)
        self._import_blocks_fn = watch("import_blocks", _import_blocks)
        self._adapter_install_fn = watch("adapter_load",
                                         _adapter_install)
        if self.adapters is not None:
            self.adapters.bind_loader(
                lambda pool, slot, weights: self._adapter_install_fn(
                    pool, jnp.asarray(slot, jnp.int32), weights))

    # -- admission ---------------------------------------------------------

    # -- sharded init ------------------------------------------------------
    @staticmethod
    def sharded_init(cfg, mesh, rules=None, seed: int = 0):
        """Initialize params DIRECTLY onto the mesh (jit with
        out_shardings): each device materializes only its own weight
        shards, so a model bigger than one chip's HBM can be built at
        all — init-then-shard would OOM device 0 before the engine's
        device_put ever ran. Pass the result + the same mesh to
        InferenceEngine (its device_put then no-ops)."""
        from skypilot_tpu.parallel import sharding as sh
        return sh.init_sharded(
            lambda: registry.model_for(cfg).init_params(
                jax.random.key(seed), cfg),
            lambda _: registry.model_for(cfg).param_logical_axes(cfg),
            mesh,
            rules or sh.INFER_TP_RULES)

    def add_request(self, prompt: List[int],
                    max_new_tokens: int = 128,
                    trace_ctx: Optional[tracing.SpanContext] = None,
                    tenant: str = qos_lib.DEFAULT_TENANT,
                    priority: int = 0,
                    adapter: Optional[str] = None,
                    committed: Optional[List[int]] = None) -> int:
        _bucket(len(prompt), self.buckets)   # validate length up front
        self.check_kv_quota(tenant, len(prompt), max_new_tokens)
        self.check_adapter(adapter)          # unknown name -> typed 404
        req = Request(rid=self._next_rid, prompt=list(prompt),
                      max_new_tokens=max_new_tokens, submit_s=time.time(),
                      eos_id=self.eos_id, tenant=tenant,
                      priority=priority, adapter=adapter)
        if committed:
            # Disaggregated handoff: tokens another replica already
            # committed (and streamed) ride in pre-seeded, so this
            # request admits through the SAME prompt+committed resume
            # path preemption and crash recovery use — the suffix it
            # decodes is bit-identical to finishing on the origin
            # replica, and max_new_tokens keeps its original meaning
            # (the budget counts the committed tokens).
            req.tokens = [int(t) for t in committed]
        # Per-request span identity, minted at submit so child spans
        # recorded before retirement can already parent to it. The
        # parent comes from the caller's explicit context (the HTTP
        # handler's traceparent — admission runs on the loop thread,
        # which has no ambient context) or the ambient one.
        parent = trace_ctx if trace_ctx is not None else tracing.current()
        req.span_ctx = tracing.SpanContext(
            parent.trace_id if parent else tracing.new_trace_id(),
            tracing.new_span_id())
        req.parent_id = parent.span_id if parent else None
        self._next_rid += 1
        self.waiting.append(req)
        ENGINE_WAITING.set(len(self.waiting))
        return req.rid

    def _update_gauges(self) -> None:
        SLOTS_ACTIVE.set(len(self.slot_req))
        ENGINE_WAITING.set(len(self.waiting))
        seen = self._prefix_hit_n + self._prefix_miss_n
        if seen:
            PREFIX_HIT_RATIO.set(self._prefix_hit_n / seen)
        if self.paged:
            KV_BLOCKS_USED.set(self.allocator.used)
        self._refresh_hbm_ledger()

    # -- HBM ledger --------------------------------------------------------

    def _init_hbm_ledger(self) -> None:
        """Static ledger components: resident capacity each tensor
        family holds for the engine's lifetime (array nbytes are
        metadata reads — no device fetch). The workspace entry is the
        per-program activation ESTIMATE for the widest admission wave
        (rows x bucket x (ff + 2d) fp32 plus the wave logits), the one
        family with no host-authoritative array to read."""
        led = self.hbm_ledger
        # The gauge is the process's: rows an earlier engine of another
        # family left there (a test worker's) are not this engine's.
        led.zero_published_rows()
        led.set_bytes("weights", self._weight_bytes)
        # What the cache holds is the family's to name: ``kv_pool``,
        # or ``latent_kv_pool`` (+ the ``expert_weights`` view inside
        # ``weights``), or ``kv_pool`` beside ``recurrent_state``.
        for row, n in self._progs.hbm_rows(self.cache,
                                           self.params).items():
            led.set_bytes(row, n)
        led.set_bytes("prefix_pool",
                      attribution_lib.tensor_bytes(self.pool))
        led.set_bytes("draft_pool",
                      self.draft_engine.hbm_bytes()
                      if self.draft_engine is not None else 0)
        led.set_bytes("adapter_pool",
                      attribution_lib.tensor_bytes(self.adapters.pool)
                      if self.adapters is not None else 0)
        rows = (self.max_wave if self.pad_waves else self.n_slots) + 1
        widest = max(self.buckets) if self.buckets else self.max_len
        cfg = self.cfg
        workspace = rows * widest * (cfg.d_ff + 2 * cfg.d_model) * 4 \
            + rows * cfg.vocab_size * 4
        led.set_bytes("workspace", workspace)
        stats = led.cross_check()
        if stats is None or "bytes_limit" not in stats:
            # No backend truth: the alarmable limit comes from the
            # operator (env) or defaults to the analytical total plus
            # slack — headroom stays a meaningful ratio either way.
            env = os.environ.get("SKYTPU_HBM_LIMIT_BYTES", "")
            try:
                limit = int(env) if env else 0
            except ValueError:
                limit = 0
            led.set_limit(limit if limit > 0
                          else int(led.total() * 1.25))
        self._refresh_hbm_ledger()

    def _refresh_hbm_ledger(self) -> None:
        """Dynamic (occupancy) components, recomputed from the SAME
        host bookkeeping the engine admits against — allocator block
        counts and prefix payloads — so a ledger leak IS a structure
        leak. Occupancy views overlap the capacity components
        (kv_used is resident inside kv_pool); the headroom SLO rule
        sums capacity components only."""
        led = self.hbm_ledger
        if self.paged:
            led.set_bytes("kv_used",
                          self.allocator.used * self._kv_block_bytes)
            pinned = 0
            if self._prefix_index is not None:
                for payload in self._prefix_index.payloads():
                    if isinstance(payload, (list, tuple)):
                        pinned += len(payload) * self._kv_block_bytes
            led.set_bytes("prefix_pinned", pinned)
        else:
            led.set_bytes("kv_used",
                          len(self.slot_req) * self.max_len
                          * self._kv_token_bytes)
            led.set_bytes(
                "prefix_pinned",
                (len(self._prefix_index.payloads()) * self.max_len
                 * self._kv_token_bytes)
                if self._prefix_index is not None else 0)

    # -- flight recorder + compile watch -----------------------------------

    def _tiles(self, burst: str, n_live: int) -> int:
        """Turns a layer of this decode program takes to read and attend
        its live rows, ``kvcache.TILE`` slots a turn: host arithmetic
        over what the dispatch already knows (the device counts the
        same from its ``active`` mask). ``decode1`` has no mask and
        visits every row; the paged kernel visits none by tiles."""
        if burst == "decode1":
            n_live = self.n_slots + 1
        elif self.kv_kernel:
            return 0
        return -(-n_live // kvcache.TILE)

    def _family_notes(self, slots) -> Dict[str, int]:
        """What the family's module adds to a decode dispatch annotation
        (host arithmetic over what the round already holds):
        ``state_rows`` — the slots whose per-slot state the program
        updates (a family without one says nothing) — and ``kv_blocks``
        — the blocks that hold the round's slots' rows at its start,
        which a program whose K/V read is bounded by residency visits a
        layer (``tiles * TILE * ceil(span / kv_block)`` is what the rung
        alone would make it read; a family whose read is not bounded
        says nothing) — and ``window_rows`` — the ring rows a window
        layer reads for them, ``min(rows, window)`` a slot (a family
        without window layers says nothing)."""
        notes = {}
        if self._progs.SLOT_STATE:
            notes["state_rows"] = len(slots)
        if self._progs.DECODE_READS_BLOCKS_HELD:
            notes["kv_blocks"] = sum(
                -(-self._slot_rows(self.slot_req[s]) // self.kv_block)
                for s in slots)
        if self._ring_rows:
            notes["window_rows"] = sum(
                min(self._slot_rows(self.slot_req[s]), self._ring_rows)
                for s in slots)
            WINDOW_ROWS.inc(notes["window_rows"])
        return notes

    def _window_notes(self, runs) -> Dict[str, int]:
        """What a prefill dispatch annotation says of the window layers:
        ``window_keys``, the key rows one of them must score for the
        program's real tokens — ``runs``: (first position, tokens) a
        request. A family without window layers says nothing."""
        if not self._ring_rows:
            return {}
        keys = sum(window_keys(start, n, self._ring_rows)
                   for start, n in runs)
        WINDOW_KEYS.inc(keys)
        return {"window_keys": keys}

    def _record_flight(self, burst: str, begin_s: float, end_s: float,
                       program: Dict[str, Any], slots, reqs,
                       toks: int, stall: bool = False,
                       drafted: int = 0, accepted: int = 0,
                       drafter: Optional[str] = None,
                       overlap_ms: float = 0.0,
                       dispatch_s: Optional[float] = None,
                       dev_keys: Optional[List[Optional[str]]] = None,
                       calibrator: Optional[
                           attribution_lib.DeviceTimeCalibrator]
                       = None,
                       kv_blocks: Optional[int] = None,
                       window_rows: Optional[int] = None,
                       window_keys: Optional[int] = None,
                       queued: Optional[int] = None) -> None:
        """Append one burst record to the flight recorder. HOST
        bookkeeping only — every value here already lives on the host
        (request lists, ints, floats); a device fetch on this path
        would stall the dispatch pipeline the recorder exists to
        observe. COW/eviction/lazy-grow attribution: whatever
        accumulated since the previous record rides this one (claims
        run just before the wave/chunk record they belong to; lazy
        growth happens inside the burst being recorded; a queued
        chunk's record is written at its landing, so what its claim
        accumulated may ride the burst record that lands before it)."""
        cow, self._fl_cow = self._fl_cow, 0
        evs, self._fl_evictions = self._fl_evictions, 0
        lazy, self._fl_lazy_grows = self._fl_lazy_grows, 0
        compiled = self.compile_watch.drain_new()
        # Big-cache read path this burst rode: the kernel covers the
        # burst/verify/chunk programs; decode1 (the classic single-
        # step fallback) stays on the gather even with the flag on.
        attn = None
        if burst in ("decode", "verify", "chunk", "decode1"):
            attn = ("kernel" if self.kv_kernel and burst != "decode1"
                    else "gather")
            if burst != "chunk":
                DECODE_ATTN_PATH.labels(path=attn).inc()
        fl = self.flight
        if fl is None or not fl.enabled:
            return
        program = dict(program)
        program["layout"] = "paged" if self.paged else "contig"
        if attn is not None:
            program["attn"] = attn
        extra: Dict[str, Any] = {}
        if burst in ("decode", "verify", "decode1"):
            extra["tiles"] = self._tiles(burst, len(slots))
            if kv_blocks is not None:
                extra["kv_blocks"] = kv_blocks
            if window_rows is not None:
                extra["window_rows"] = window_rows
        if window_keys is not None:
            extra["window_keys"] = window_keys
        if queued is not None:
            # A chunk record: 1 = dispatched and landed later (its end
            # is when the host saw it done), 0 = the awaited final one.
            extra["queued"] = queued
        if stall:
            extra["stall"] = True
        if drafted:
            extra["drafted"] = drafted
            extra["accepted"] = accepted
        if drafter:
            # Which drafter kind fed this burst (verify bursts:
            # model|ngram|mixed group composition; "draft" records:
            # the pipelined predraft dispatch itself).
            extra["drafter"] = drafter
        if overlap_ms:
            # Host wall the round spent dispatching next-round draft
            # work INSIDE the verify's dispatch->fetch window — the
            # pipeline-overlap attribution skytpu flight/--perfetto
            # render as overlapping spans.
            extra["overlap_ms"] = overlap_ms
        if cow:
            extra["cow"] = cow
        if evs:
            extra["evictions"] = evs
        if lazy:
            extra["lazy_grows"] = lazy
        if compiled:
            extra["compiled"] = compiled
        # Device-truth attribution (observability/attribution.py).
        # dur_s stays the dispatch->fetch host wall for render/test
        # compat; the split names where it went (enqueueing vs
        # waiting), and dev_ms_est is the calibrated EWMA of pure
        # device time for the program(s) this record dispatched.
        dur_ms = max(end_s - begin_s, 0.0) * 1e3
        if dispatch_s is not None:
            disp_ms = min(max((dispatch_s - begin_s) * 1e3, 0.0),
                          dur_ms)
            extra["dispatch_wall_ms"] = round(disp_ms, 4)
            extra["fetch_wall_ms"] = round(dur_ms - disp_ms, 4)
        cal = calibrator if calibrator is not None else self.devtime
        if dev_keys:
            ests = [cal.estimate(k) for k in dev_keys]
            ests = [e for e in ests if e is not None]
            if ests:
                dev_ms = sum(ests) * 1e3
                extra["dev_ms_est"] = round(dev_ms, 4)
                attribution_lib.DEVICE_SECONDS.inc(dev_ms / 1e3)
        rl = (self._draft_roofline if burst == "draft"
              else self.roofline)
        if rl is not None:
            flops, hbm = rl.record_cost(burst, program,
                                        len(slots), toks)
            if flops:
                extra["flops"] = flops
                extra["hbm_bytes"] = hbm
                attribution_lib.DEVICE_FLOPS.inc(flops)
                attribution_lib.DEVICE_HBM_MOVED.inc(hbm)
        if self.adapters is not None and reqs:
            # Per-burst adapter composition (host dict over the
            # request list): `skytpu flight` and the bench read which
            # fine-tunes shared each dispatch straight off records.
            ads: Dict[str, int] = {}
            for r in reqs:
                if r.adapter:
                    ads[r.adapter] = ads.get(r.adapter, 0) + 1
            if ads:
                extra["adapters"] = ads
        if self.qos is not None and reqs:
            # Per-burst tenant/priority composition (host dict builds
            # over the request list): the chaos fairness scenario and
            # `skytpu flight` read group make-up straight off records.
            tenants: Dict[str, int] = {}
            for r in reqs:
                tenants[r.tenant] = tenants.get(r.tenant, 0) + 1
            extra["tenants"] = tenants
            if any(r.priority for r in reqs):
                prios: Dict[str, int] = {}
                for r in reqs:
                    key = str(r.priority)
                    prios[key] = prios.get(key, 0) + 1
                extra["priorities"] = prios
        fl.record(
            burst, ts_s=begin_s, dur_s=max(end_s - begin_s, 0.0),
            program=program, slots=list(slots),
            rids=[r.rid for r in reqs],
            traces=[r.span_ctx.trace_id for r in reqs
                    if r.span_ctx is not None],
            toks=toks, **extra)

    def declare_warmup_complete(self) -> None:
        """Arm the compile watch: every program the live workload can
        reach is believed compiled, so any later compile is the
        mid-traffic stall the static-shape design forbids — a typed
        ``engine.unexpected_compile`` event plus
        ``skytpu_unexpected_compiles_total`` (the SLO watchdog's
        ``unexpected-compiles`` rule alarms on it)."""
        self.compile_watch.declare_warm()
        if self.draft_engine is not None:
            # The drafter's programs are part of this replica's live
            # surface: a mid-traffic draft-model compile stalls the
            # spec path exactly like a main-engine one.
            self.draft_engine.declare_warmup_complete()

    def warm_programs(self, max_burst: int = 8) -> int:
        """Pre-compile the engine's reachable program grid so no XLA
        compile can stall live traffic (call once at startup, then
        :meth:`declare_warmup_complete`).

        Every (kind, static-args) variant dispatches once against the
        hidden SPARE slot, whose writes are garbage by construction
        (paged: the spare's table row is all-sentinel so writes drop;
        contiguous: they land in the spare's own dead rows), and the
        length bookkeeping is zeroed afterwards. Greedy output is
        unaffected — argmax sampling ignores the RNG stream this
        consumes. Runs under ``metrics.suppress`` so the compile-
        dominated sweep stays out of the serving histograms, then
        republishes the sweep's compile metrics (skytpu_compile_
        seconds / skytpu_programs_compiled_total) from the watch
        registry — "programs compiled on this replica" must stay
        truthful on warm-grid fleets. Returns the number of programs
        compiled."""
        before = self.compile_watch.count
        pre_keys = set(self.compile_watch.summary())
        spare = self.n_slots
        active = np.zeros((self.n_slots + 1,), bool)
        active[spare] = True
        active_dev = jnp.asarray(active)
        spans = [self._span_arg(s) for s in self.span_ladder]
        lora_kw = self._lora_args()
        # One start-up phase a program family (a child of the server's
        # ``startup.warm_grid``); each closes outside the suppression
        # its sweep runs under, so its gauge is kept.
        family = flight_lib.STARTUP.phase
        with family("warm_grid.decode"), metrics.suppress():
            for sarg in spans:
                self.cache, self.rng, _ = self._decode_fn(
                    self.params, self.cache, self.rng, active_dev,
                    self.table_device(), qweights=self.qweights,
                    span=sarg, **lora_kw)
                k = 1
                while k <= max_burst:
                    self.cache, self.rng, _ = self._decode_burst_fn(
                        self.params, self.cache, self.rng, active_dev,
                        self.table_device(), k=k,
                        qweights=self.qweights, span=sarg,
                        kernel=self.kv_kernel, **lora_kw)
                    k *= 2
                if self.spec_k:
                    draft = jnp.zeros((self.n_slots + 1, self.spec_k),
                                      jnp.int32)
                    n_draft = jnp.zeros((self.n_slots + 1,), jnp.int32)
                    self.cache, _, _ = self._verify_fn(
                        self.params, self.cache, draft, n_draft,
                        active_dev, self.table_device(), k=self.spec_k,
                        qweights=self.qweights, span=sarg,
                        kernel=self.kv_kernel, **lora_kw)
        with family("warm_grid.chunk"), metrics.suppress():
            if self.prefill_chunk:
                chunk = jnp.zeros((self.prefill_chunk,), jnp.int32)
                for sarg in spans:
                    for final in (False, True):
                        self.cache, self.rng, _ = \
                            self._prefill_chunk_fn(
                                self.params, self.cache, chunk,
                                jnp.asarray(0, jnp.int32),
                                jnp.asarray(1, jnp.int32),
                                jnp.asarray(spare, jnp.int32),
                                jnp.asarray(self.max_len, jnp.int32),
                                self.rng, self.table_device(),
                                final=final, qweights=self.qweights,
                                span=sarg, kernel=self.kv_kernel,
                                **lora_kw)
        with family("warm_grid.wave"), metrics.suppress():
            # Admission waves: every (bucket, rung) pair a wave can
            # be padded to — warm the whole ladder, or declaring
            # warmup complete would false-page on the first wave that
            # lands on a cold rung.
            for bucket in self.buckets:
                if self.prefill_chunk and bucket > self.prefill_chunk \
                        and min(self.buckets) <= self.prefill_chunk:
                    # Unreachable: a context longer than the chunk
                    # never rides a bucketed wave (_use_chunked), so a
                    # bucket wider than the chunk admits nothing — and
                    # at a long --max-len its program is the largest
                    # the engine could build.
                    continue
                for rows in self.wave_rungs:
                    tokens_b = np.ones((rows, bucket), np.int32)
                    true_lens = np.ones((rows,), np.int32)
                    slot_ids = np.full((rows,), spare, np.int32)
                    wave_lora = {}
                    if self.adapters is not None:
                        wave_lora = {
                            "lora": self.adapters.pool,
                            "aid": jnp.zeros((rows,), jnp.int32)}
                    self.cache, self.rng, _ = self._admit_wave_fn(
                        self.params, self.cache, jnp.asarray(tokens_b),
                        jnp.asarray(true_lens),
                        jnp.asarray(slot_ids), self.rng,
                        self.table_device(), bucket=bucket,
                        qweights=self.qweights, **wave_lora)
        with family("warm_grid.small"), metrics.suppress():
            # The admission path's small gather/scatter programs.
            claim_len = jnp.asarray(self.max_len, jnp.int32)
            self.cache = self._claim_fn(
                self.cache, jnp.asarray(spare, jnp.int32), claim_len)
            if self.pool is not None:
                self.cache = self._pool_load_fn(
                    self.cache, self.pool, jnp.asarray(0, jnp.int32),
                    jnp.asarray(spare, jnp.int32), claim_len)
                self.pool = self._pool_store_fn(
                    self.pool, self.cache,
                    jnp.asarray(spare, jnp.int32),
                    jnp.asarray(0, jnp.int32))
            if self.paged:
                self.cache = self._copy_block_fn(
                    self.cache, jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32))
                # Handoff export/import: warm against an all-sentinel
                # index — the gather clamps (garbage nobody reads), the
                # scatter drops every write (out of bounds), so the
                # sweep leaves the pool untouched.
                ids = jnp.full((self.blocks_per_slot,),
                               self.n_kv_blocks, jnp.int32)
                vals = self._export_blocks_fn(self.cache, ids)
                self.cache = self._import_blocks_fn(self.cache, ids,
                                                    vals)
            if self.adapters is not None:
                # Warm the hot-load program by installing the all-zero
                # weights into the base slot (values unchanged): a
                # demand load mid-traffic must dispatch, not compile.
                self.adapters.pool = self._adapter_install_fn(
                    self.adapters.pool, jnp.asarray(0, jnp.int32),
                    self.adapters.zero_weights())
            # Scrub: zero the length bookkeeping — the sweep's data
            # rows are dead without a length exposing them.
            self.cache["length"] = jnp.zeros_like(self.cache["length"])
            # The grid is warm when its first executions have run, not
            # when the last of them is queued.
            jax.block_until_ready((self.cache, self.pool))
        self.compile_watch.drain_new()   # not any burst's to claim
        # Republish the sweep's compile metrics OUTSIDE suppress: the
        # wrapper's increments were discarded inside it, but "programs
        # compiled on this replica" must mirror the watch registry —
        # or a warm-grid fleet would read `compiles 0` on skytpu top.
        self.compile_watch.republish(pre_keys)
        n = self.compile_watch.count - before
        if self.spec_k and self.draft_engine is not None:
            # The drafter's grid (rollouts at K and K+1 per span rung,
            # ingest, sync) is reachable the moment the first request
            # drafts — warm it with the engine's, or a live replica's
            # first spec round pays a draft-model compile.
            n += self.draft_engine.warm_programs(self.spec_k)
        return n

    # -- paged block management --------------------------------------------

    @property
    def blocks_used(self) -> int:
        """Physical blocks currently referenced (0 when contiguous)."""
        return self.allocator.used if self.paged else 0

    def table_device(self):
        """The block table as a device array (None when contiguous).
        Cached between calls — claims/retires mark it dirty — so a
        steady decode stream pays no per-burst host->device copy."""
        if not self.paged:
            return None
        if self._table_dirty or self._table_dev is None:
            self._table_dev = jnp.asarray(self.block_table)
            self._table_dirty = False
        return self._table_dev

    # -- adapter catalog ---------------------------------------------------

    def aid_device(self):
        """The per-slot adapter-id vector as a device array (None when
        no catalog). Cached between calls — claims/retires mark it
        dirty — so a steady decode stream pays no per-burst
        host->device copy (the block-table idiom)."""
        if self.adapters is None:
            return None
        if self._aid_dirty or self._aid_dev is None:
            self._aid_dev = jnp.asarray(self.adapter_ids)
            self._aid_dirty = False
        return self._aid_dev

    def _lora_args(self) -> Dict[str, Any]:
        """kwargs routing the adapter pool + per-slot ids into a
        decode-family dispatch ({} on the adapterless path — the
        programs then trace exactly as before)."""
        if self.adapters is None:
            return {}
        return {"lora": self.adapters.pool, "aid": self.aid_device()}

    def check_adapter(self, name: Optional[str]) -> None:
        """Submit-time guard (server handler threads, the _bucket
        idiom): an unknown fine-tune is a clean typed 404 before the
        request ever rides the inbox. An engine with NO catalog knows
        no adapters at all."""
        if name is None:
            return
        if self.adapters is None:
            raise adapters_lib.UnknownAdapterError(name, [])
        self.adapters.check(name)

    def _acquire_adapter(self, req: Request) -> str:
        """Pin the request's fine-tune into the device pool at claim
        time. Returns "ok" (adapter_slot assigned, pin counted),
        "stall" (every pool slot pinned by in-flight requests — the
        caller re-queues and retries once a retirement unpins), or
        "failed" (checkpoint load failed after retries / unknown name:
        the request has been FAILED TYPED and consumed — it must never
        silently fall through to the base model's weights)."""
        if self.adapters is None or req.adapter is None:
            req.adapter_slot = 0
            return "ok"
        try:
            slot = self.adapters.acquire(req.adapter)
        except (adapters_lib.AdapterLoadError,
                adapters_lib.UnknownAdapterError) as e:
            self._fail_request(req, e)
            return "failed"
        if slot is None:
            return "stall"
        req.adapter_slot = slot
        req.adapter_pinned = slot > 0
        return "ok"

    def _release_adapter(self, req: Request) -> None:
        """Drop the request's in-flight adapter pin (exactly once per
        acquire: retirement, preemption, or an abandoned claim)."""
        if req.adapter_pinned and self.adapters is not None:
            self.adapters.release(req.adapter_slot)
            req.adapter_pinned = False

    def _set_slot_adapter(self, slot: int, pool_slot: int) -> None:
        if self.adapters is None:
            return
        if self.adapter_ids[slot] != pool_slot:
            self.adapter_ids[slot] = pool_slot
            self._aid_dirty = True

    def _prefix_salt(self, req: Request) -> bytes:
        """The request's prefix-cache key namespace. Stored K/V rows
        carry the fine-tune's wk/wv deltas, so cached prefixes are
        ADAPTER-SPECIFIC: without the salt, two adapters sharing a
        prompt prefix would hit cached K/V computed under whichever
        stored first — silently serving the wrong model. Keyed by the
        adapter's CONTENT digest (warm prefixes survive evict/reload
        and alias names); base-model requests keep the empty salt
        (the pre-adapter key space, bit-compatible)."""
        if self.adapters is None or not req.adapter_slot:
            return b""
        return self.adapters.slot_content(req.adapter_slot)

    def _fail_request(self, req: Request, exc: Exception) -> None:
        """Retire a request with a typed error instead of tokens (the
        adapter-load failure path). The server returns the body with
        the error's HTTP status; the engine never substitutes base-
        model output for a named fine-tune."""
        req.error = getattr(exc, "typed_error", None) or {
            "type": "error", "message": str(exc)}
        if getattr(exc, "http_status", None):
            req.error = dict(req.error,
                             http_status=exc.http_status)
        req.done = True
        self.finished.append(req)

    def _need_blocks(self, req: Request,
                     ctx_len: Optional[int] = None) -> int:
        """Blocks to reserve at admission. Eager (default): the
        worst case — prompt plus the full token budget, capped by
        max_len — so decode can never run out of backing mid-flight;
        the pool, not a mid-decode fault path, is the admission
        limiter. (The formula is already total-shaped, so a preempted
        request resuming with committed tokens reserves the identical
        worst case.) Lazy (SKYTPU_KV_LAZY=1): just the admission
        context plus one burst of headroom; the rest allocates per
        burst in :meth:`_ensure_headroom` through the same dry-pool
        evict/stall path."""
        need = min(len(req.prompt) + req.max_new_tokens, self.max_len)
        if self.kv_lazy:
            base = ctx_len if ctx_len is not None else len(req.prompt)
            need = min(base + self._lazy_headroom, need)
        return -(-need // self.kv_block)

    def _ensure_headroom(self, slot: int, req: Request,
                         need_rows: int) -> bool:
        """Lazy mode: grow the slot's block allocation to back
        ``need_rows`` cache rows before a burst writes them (eager
        engines reserved the worst case at admission and always pass).
        Growth rides admission's dry-pool path — LRU prefix entries
        evict first, and a pool that stays dry returns False: the
        slot sits this burst out and retries after retirements free
        blocks."""
        if not self.kv_lazy:
            return True
        cap = min(len(req.prompt) + req.max_new_tokens, self.max_len)
        need_rows = min(need_rows, cap)
        row = self.block_table[slot]
        have = len(row[row < self.n_kv_blocks])
        grow = -(-need_rows // self.kv_block) - have
        if grow <= 0:
            return True
        blocks = self._alloc_blocks(grow)
        if blocks is None:
            return False
        row[have:have + len(blocks)] = blocks
        self._table_dirty = True
        self._sync_kv_charge(slot, req.tenant)
        KV_LAZY_GROWS.inc(len(blocks))
        self._fl_lazy_grows += len(blocks)
        return True

    # -- span buckets ------------------------------------------------------

    def _span_for(self, rows: int) -> int:
        """Smallest ladder rung covering ``rows`` cache rows (the full
        view for anything past the ladder — callers' row counts are
        already capped by max_len)."""
        for s in self.span_ladder:
            if rows <= s:
                return s
        return self.span_ladder[-1]

    def _span_arg(self, span: int) -> Optional[int]:
        """The static ``span`` argument for a dispatch: None selects
        the unsliced full-view program — the identical trace the
        pre-span engine compiled, so a disabled ladder costs
        nothing."""
        return None if span >= self.max_len else span

    def _slot_rows(self, req: Request) -> int:
        """Cache rows the slot holds at the next burst's start as the
        DEVICE will see it: host-committed tokens plus every token
        still in flight (dispatched bursts commit on device before
        the next program runs)."""
        return (len(req.prompt) + len(req.tokens)
                + self._inflight_tokens)

    def _round_slots(self, width: int
                     ) -> Tuple[int, List[int], int]:
        """The decode round's ONE program: every active slot the pool
        can back, at the ladder rung covering the longest of them.
        ``width``: rows the round will write per slot — lazy growth
        must back them; a slot the pool cannot grow is left out and
        retries once retirements free blocks. Returns (span, slots,
        promoted): ``promoted`` counts the slots whose own rung lies
        below the program's span — what riding one program costs them
        in rows read (their extra rows carry exact-zero softmax
        weight). ``slots`` empty: nothing can run this round."""
        rungs: Dict[int, int] = {}
        for slot, req in self.slot_req.items():
            rows = self._slot_rows(req)
            if self._ensure_headroom(slot, req, rows + width):
                rungs[slot] = self._span_for(rows)
        span = max(rungs.values(), default=0)
        return (span, list(rungs),
                sum(1 for r in rungs.values() if r < span))

    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """n fresh blocks, evicting LRU prefix-cache entries on a dry
        pool (their blocks free unless still shared with live slots).
        None when the pool stays too dry — the caller leaves the
        request queued; retirements free blocks and admission retries
        next pass."""
        chaos.point("kv.alloc", need=n)
        alloc = self.allocator
        idx = self._prefix_index
        while alloc.available < n and idx is not None:
            # Evict the LRU entry that would actually FREE blocks.
            # Entries whose blocks are all still shared with live
            # slots (or pinned by the claim in progress) free nothing
            # — dropping them would wipe the warm cache for zero
            # capacity, turning one transient dry-pool moment into a
            # fleet-wide cold-prefill regression.
            victim = None
            for p in idx.payloads_lru():
                if any(alloc.ref(b) == 1 for b in p):
                    victim = p
                    break
            if victim is None:
                break
            idx.evict_entry(victim)
            PREFIX_EVICTIONS.inc()
            self._fl_evictions += 1
            for b in victim:
                alloc.decref(b)
        if alloc.available < n:
            return None
        return [alloc.alloc() for _ in range(n)]

    # -- per-tenant KV-block quotas (qos max_kv_blocks) --------------------

    def _kv_quota(self, tenant: str) -> int:
        """The tenant's ``max_kv_blocks`` quota (0 = unlimited):
        paged engines with a QoS config only."""
        if not self.paged or self.qos is None:
            return 0
        return max(self.qos.cfg.tenant(tenant).max_kv_blocks, 0)

    def check_kv_quota(self, tenant: str, prompt_len: int,
                       max_new_tokens: int) -> None:
        """Submit-time guard: a request whose OWN worst-case block
        need exceeds its tenant's ``max_kv_blocks`` quota can never
        admit (the need formula is total-shaped and never shrinks), so
        stalling it would hang the client forever — raise the typed
        error instead. Reads only engine constants, so the server's
        handler threads call it eagerly (the ``_bucket`` idiom: a
        clean 400 before the request ever rides the inbox — an
        exception on the loop thread could reach no client)."""
        quota = self._kv_quota(tenant)
        if not quota:
            return
        need = min(prompt_len + max_new_tokens, self.max_len)
        if self.kv_lazy:
            need = min(prompt_len + self._lazy_headroom, need)
        need = -(-need // self.kv_block)
        if need > quota:
            raise KvQuotaUnsatisfiableError(tenant, need, quota)

    def _kv_quota_blocked(self, req: Request) -> bool:
        """Admission-time per-tenant KV-block quota check: True holds
        THIS request back (typed ``qos.kv_quota_stall`` event +
        counter, once per episode) while other tenants keep admitting
        — a hot tenant can no longer hog the paged pool via long
        contexts even while rate-limited. The quota gates ADMISSION
        only: in-flight lazy growth is never blocked, so an admitted
        request always runs to completion (growth is still charged,
        which holds the tenant's NEXT admission)."""
        quota = self._kv_quota(req.tenant)
        if not quota:
            return False
        need = self._need_blocks(req, self._ctx_len(req))
        used = self._tenant_kv.get(req.tenant, 0)
        if used + need <= quota:
            req.kv_quota_stalled = False
            if req.stall_cause == "kv_quota":
                self._end_stall(req)
            return False
        self._mark_stall(req, "kv_quota")
        if not req.kv_quota_stalled:
            req.kv_quota_stalled = True
            QOS_KV_QUOTA_STALLS.labels(
                tenant=qos_lib.tenant_label(req.tenant,
                                            self.qos.cfg)).inc()
            tracing.add_event(
                "qos.kv_quota_stall",
                {"tenant": req.tenant, "rid": req.rid,
                 "used_blocks": used, "need_blocks": need,
                 "max_kv_blocks": quota})
        return True

    def _set_tenant_kv(self, tenant: str, n: int) -> None:
        # Entries pop at zero: tenant names are client-supplied, so a
        # scanner minting one name per request must not grow the dict
        # for the engine's lifetime.
        if n > 0:
            self._tenant_kv[tenant] = n
        else:
            self._tenant_kv.pop(tenant, None)
        # The gauge is absolute and its label CAP collapses overflow
        # tenants into "other" — publish the label's SUM, not this
        # tenant's count, or collapsed tenants would overwrite each
        # other (a counter tolerates collapse; a .set() gauge only
        # does summed).
        cfg = self.qos.cfg if self.qos is not None else None
        label = qos_lib.tenant_label(tenant, cfg)
        total = sum(v for t, v in self._tenant_kv.items()
                    if qos_lib.tenant_label(t, cfg) == label)
        QOS_KV_BLOCKS.labels(tenant=label).set(total)

    def _sync_kv_charge(self, slot: int,
                        tenant: Optional[str] = None) -> None:
        """Re-point the tenant KV-block accounting at the slot's
        CURRENT table occupancy (called at claim, growth and free):
        the charge is the number of blocks the slot's table
        references, so shared prefix blocks charge every referencing
        tenant and the refund at :meth:`_free_slot_blocks` is exact by
        construction — no leak path exists that does not also leak the
        table row itself."""
        if not self.paged:
            return
        old_tenant, old_n = self._slot_kv_charge.get(slot, (None, 0))
        tenant = tenant if tenant is not None else old_tenant
        row = self.block_table[slot]
        have = len(row[row < self.n_kv_blocks])
        if old_tenant is not None and old_n:
            self._set_tenant_kv(
                old_tenant, self._tenant_kv.get(old_tenant, 0) - old_n)
        if tenant is not None and have:
            self._slot_kv_charge[slot] = (tenant, have)
            self._set_tenant_kv(
                tenant, self._tenant_kv.get(tenant, 0) + have)
        else:
            self._slot_kv_charge.pop(slot, None)

    def _wave_claim(self, req: Request
                    ) -> Tuple[str, Optional[int]]:
        """Claim a slot (+ its KV blocks when paged, + the adapter
        pool pin when the request names a fine-tune) for a wave-path
        request. Returns (status, slot): ("ok", slot); ("dry", None)
        — the block pool is too dry, the caller re-queues and stalls
        admission globally; ("held", None) — every adapter-pool slot
        is pinned by in-flight requests, the caller steps THIS request
        aside (the quota-held idiom — a per-resource limit must not
        head-of-line-block base-model traffic); ("failed", None) —
        the adapter failed to load and the request has been FAILED
        TYPED and consumed."""
        st = self._acquire_adapter(req)
        if st == "failed":
            return "failed", None
        if st == "stall":
            self._mark_stall(req, "adapter_pin")
            return "held", None
        if not self.paged:
            slot = self.free_slots.pop(0)
            self._set_slot_adapter(slot, req.adapter_slot)
            self._end_stall(req)
            return "ok", slot
        blocks = self._alloc_blocks(
            self._need_blocks(req, self._ctx_len(req)))
        if blocks is None:
            # The adapter pin must not leak across the re-queue: the
            # next pass re-acquires (resident slots are warm hits).
            self._release_adapter(req)
            self._mark_stall(req, "pool_dry")
            return "dry", None
        slot = self.free_slots.pop(0)
        row = self.block_table[slot]
        row[:] = self.n_kv_blocks
        row[:len(blocks)] = blocks
        self._table_dirty = True
        self._sync_kv_charge(slot, req.tenant)
        self._set_slot_adapter(slot, req.adapter_slot)
        self._end_stall(req)
        return "ok", slot

    def _free_slot_blocks(self, slot: int) -> None:
        """Release a slot's block references and clear its table row to
        the sentinel: bursts dispatched after the retirement drop their
        garbage writes for the dead slot. A burst already in flight
        rode the OLD device table and still writes the old blocks —
        safely: device programs execute in dispatch order, so a re-
        allocated block's every readable row is overwritten by its new
        owner's (later-dispatched) prefill/decode writes before the
        owner's length ever exposes it."""
        if not self.paged:
            return
        row = self.block_table[slot]
        for b in row[row < self.n_kv_blocks].tolist():
            self.allocator.decref(b)
        row[:] = self.n_kv_blocks
        self._table_dirty = True
        self._sync_kv_charge(slot)      # refund the tenant's charge

    # -- QoS: re-queue, fair scheduling, preemption-by-eviction ------------

    def _mark_stall(self, req: Request, cause: str) -> None:
        """Open (or re-assert) an admission-stall episode on ``req``.
        Idempotent per cause while the episode stays open — one
        episode spans every admission pass that re-hits the same
        blocker; a cause CHANGE closes the old episode into
        ``stall_ms`` and opens the new one. Host floats/dicts only,
        on paths that only run when admission is already blocked."""
        now = time.time()
        if req.stall_cause is not None:
            if req.stall_cause == cause:
                return
            req.stall_ms[req.stall_cause] = (
                req.stall_ms.get(req.stall_cause, 0.0)
                + (now - req.stall_begin_s) * 1e3)
        req.stall_cause = cause
        req.stall_begin_s = now

    def _end_stall(self, req: Request) -> None:
        """Close an open stall episode (successful claim, quota
        unblock, retirement). No-op when none is open."""
        if req.stall_cause is None:
            return
        req.stall_ms[req.stall_cause] = (
            req.stall_ms.get(req.stall_cause, 0.0)
            + max(time.time() - req.stall_begin_s, 0.0) * 1e3)
        req.stall_cause = None
        req.stall_begin_s = 0.0

    def _requeue(self, req: Request) -> None:
        """THE re-queue path: every request going back to the queue
        head (dry-pool admission stall, chunk-claim stall, preemption
        eviction) passes through here, so the queue-depth gauge
        updates with the deque in one place and ``skytpu_engine_
        waiting`` can never go stale on a re-queue branch."""
        self.waiting.appendleft(req)
        ENGINE_WAITING.set(len(self.waiting))

    def _ctx(self, req: Request) -> List[int]:
        """A queued request's admission context: its prompt, extended
        by committed tokens when it was preempted mid-decode — the
        resume prefills (or prefix-cache-reuses) the full committed
        sequence and the final chunk's sample IS the next token the
        unpreempted run would have decoded (greedy-exact)."""
        if not req.tokens:
            return req.prompt
        return req.prompt + req.tokens

    def _ctx_len(self, req: Request) -> int:
        """``len(self._ctx(req))`` without materializing the concat —
        the admission loop asks for queued requests' context lengths
        every pass, and a long preempted conversation stuck behind a
        dry pool must not re-build a multi-KB list each time."""
        return len(req.prompt) + len(req.tokens)

    def _resumable(self, ctx_len: int) -> bool:
        """Whether a context of this length could be re-admitted after
        eviction THROUGH THE CHUNK PATH — the only resume the
        bit-identical parity matrix covers. A wave re-admission would
        re-sample the victim's next token from the wave program's
        logits where an unpreempted run used the decode program's;
        rather than extend the parity surface across programs, a slot
        whose context still fits a wave simply isn't preempted yet
        (one more burst makes it eligible)."""
        if ctx_len >= self.max_len:
            return False
        return (self.prefill_chunk is not None
                and ctx_len > self.prefill_chunk)

    def preempt_slot(self, slot: int) -> bool:
        """Preemption-by-eviction of one decode slot — the priority
        lanes' primitive (ROADMAP items 1/4, shared by items 3/5).

        The victim's committed KV rows [0, prompt+tokens-1) are
        exactly the bytes prefill/decode wrote; the chunk-aligned
        prefix retires into the prefix cache as ref-counted shared
        blocks (paged: increfs only — the dying slot never writes
        again, so even a trailing partial block is shared without the
        COW copy a live donor would need). The request re-queues with
        its tokens intact and resumes through the ORDINARY prefix-hit
        admission path over its extended context, re-prefilling only
        the sub-chunk tail; greedy output is bit-identical to an
        unpreempted run (tests/test_qos.py asserts it across
        {fp32, int8} x {spec-on, spec-off}).

        Refuses while a dispatched burst is un-fetched (its completion
        would commit tokens into a request already back in the queue)
        and for contexts the engine could not re-admit. Host-side
        bookkeeping only — a block-table edit, never a device copy.
        """
        req = self.slot_req.get(slot)
        if req is None or self._inflight_tokens:
            return False
        ctx = req.prompt + req.tokens
        if not self._resumable(len(ctx)):
            return False
        retired_rows = 0
        if (self.paged and self._prefix_index is not None
                and req.n_chunks):
            # Committed rows stop one short of the context: the last
            # token's KV row is written by the burst that decodes its
            # successor, which never ran. Only a CHUNK-admitted
            # victim's rows may enter the shared cache — the cache
            # promises chunk-origin bytes to every later sharer
            # (_store_prefix's parity rule), and a wave-admitted
            # victim's prompt rows came from the wave program. Such a
            # victim still evicts; it just resumes cold.
            self._store_prefix(ctx, slot, len(ctx) - 1,
                               donor_live=False,
                               salt=self._prefix_salt(req))
            # The flight record reports what the RESUME will read
            # warm: the cached rows covering the victim's context
            # after the store (admission may have stored the prompt's
            # prefix already — still warm; a dry-pool or sub-chunk
            # skip with no prior entry — cold, 0). Never the raw
            # context length.
            covered = self._prefix_index.lookup(
                ctx, self._prefix_salt(req))
            if covered is not None:
                retired_rows = covered[1]
        self.slot_req.pop(slot, None)
        self.free_slots.append(slot)
        self._free_slot_blocks(slot)
        self._set_slot_adapter(slot, 0)
        if self.draft_engine is not None:
            self.draft_engine.release(slot)
        self._release_adapter(req)
        req.slot = None
        req.preemptions += 1
        qos_lib.QOS_PREEMPTIONS.labels(
            tenant=qos_lib.tenant_label(
                req.tenant,
                self.qos.cfg if self.qos is not None else None)).inc()
        fl = self.flight
        if fl is not None and fl.enabled:
            fl.record(
                "preempt", ts_s=time.time(), dur_s=0.0,
                program={"layout": "paged" if self.paged else "contig"},
                slots=[slot], rids=[req.rid], toks=0,
                tenants={req.tenant: 1}, priority=req.priority,
                retired_rows=retired_rows)
        self._requeue(req)
        self._update_gauges()
        return True

    def _preempt_for_waiting(self) -> bool:
        """Give the priority lanes teeth: for each queued request that
        outranks a running one and cannot get a free slot, evict the
        lowest-priority active slot (ties: the youngest — least sunk
        decode work). Runs before admission claims slots; the evicted
        victims re-queue behind the high-priority lane on the next
        reorder. Returns whether anything was evicted."""
        if self._inflight_tokens or not self.slot_req:
            return False
        evicted_any = False
        avail = len(self.free_slots)
        for w in list(self.waiting)[:self.n_slots]:
            if avail > 0:
                avail -= 1          # a free slot already covers it
                continue
            # Outranked residents, best victim first (lowest priority,
            # then youngest = least sunk decode). preempt_slot can
            # refuse a candidate (un-resumable context) — fall through
            # to the next one rather than strand an evictable victim
            # in another slot behind the refusal.
            candidates = sorted(
                (r.priority, -r.rid, slot)
                for slot, r in self.slot_req.items()
                if r.priority < w.priority)
            for _, _, slot in candidates:
                if self.preempt_slot(slot):
                    evicted_any = True
                    break
            else:
                break               # nothing outranked (or evictable)
        return evicted_any

    def _admit(self, on_wave=None) -> None:
        """Admission pass behind the ``admit`` dispatch boundary: a
        device error anywhere in wave dispatch/completion or a chunk
        claim's block allocation surfaces as a recoverable
        :class:`EngineDispatchError` (typed client errors pass
        through). Exception-safe: requests the pass had popped off
        ``waiting`` but not yet landed in ``chunking``/``slot_req``
        (mid-claim, mid-wave, quota-held) go back to the queue head
        BEFORE the error crosses the boundary — otherwise
        :meth:`recover`'s snapshot cannot see them and a crash would
        silently drop in-flight requests."""
        self._admit_limbo = []
        try:
            with _dispatch_boundary("admit"):
                self._admit_impl(on_wave)
        except EngineDispatchError:
            self._rescue_admit_limbo()
            raise

    def _rescue_admit_limbo(self) -> None:
        """Re-queue every request the crashed admission pass was
        holding in locals. Membership by rid (Request __eq__ is
        field-wise): anything already reachable from ``waiting``,
        ``chunking``, ``slot_req``, or ``finished`` stays put — limbo
        restore must never duplicate a request."""
        reachable = {r.rid for r in self.waiting}
        reachable.update(st.req.rid for st in self.chunking)
        reachable.update(r.rid for r in self.slot_req.values())
        reachable.update(r.rid for r in self.finished)
        lost = [r for r in self._admit_limbo
                if r.rid not in reachable]
        self._admit_limbo = []
        for r in reversed(lost):     # earliest pop back at the head
            self.waiting.appendleft(r)
        ENGINE_WAITING.set(len(self.waiting))

    def _admit_impl(self, on_wave=None) -> None:
        with timeline.phase("engine.admit.plan",
                            n=len(self.waiting)) as plan:
            self._admit_pass(on_wave, plan)

    def _admit_pass(self, on_wave, plan: timeline.Phase) -> None:
        # Waves are grouped by prompt bucket (prefill is O(S^2): one
        # long prompt must not drag every co-admitted short prompt up
        # to its bucket) and capped at max_wave, then padded to the
        # smallest rung of ``wave_rungs`` that holds them (dummy rows
        # -> spare slot) so each (bucket, rows) pair compiles exactly
        # once. ``on_wave`` fires as each wave's first tokens LAND
        # (fetch order = device order) — the server streams them while
        # later, already dispatched waves are still prefilling;
        # requests on_wave drains into ``waiting`` join the next
        # outer-loop pass.
        #
        # PIPELINED: all waves' device programs are dispatched first
        # (JAX dispatch is async; the programs chain on the donated
        # cache and execute back-to-back), THEN each wave's first
        # tokens are fetched in order. Fetching inside the build loop
        # would serialize a full host round trip per wave, a fixed
        # TTFT cost for every wave after the first.
        if self.qos is not None and self.waiting:
            # WFQ + priority lanes: reorder the deque (DRR across
            # per-tenant subqueues, high priority first), then evict
            # outranked decode slots for queued high-priority work.
            # Both are host bookkeeping; wave building below is
            # unchanged and span selection downstream never sees
            # tenants.
            self.qos.reorder(self.waiting)
            if self._preempt_for_waiting() and self.waiting:
                # Evicted victims re-queued at the head; put them back
                # behind the lanes that outrank them. Back-to-back
                # reorders are otherwise idempotent — the DRR rotation
                # advances only when a request actually LEAVES the
                # queue, never per call, so a pass that admits nothing
                # cannot shift which tenant owns the front.
                self.qos.reorder(self.waiting)
        stalled = False
        # Requests held by a PER-REQUEST resource limit this pass —
        # their tenant's KV-block quota, or a fully-pinned adapter
        # pool: such limits must not stall the whole queue the way
        # the (global) dry-block-pool stall does. Held requests step
        # aside, everyone behind them gets their shot, and they
        # re-queue at the head for the next pass (a retirement
        # unblocks them: it frees the tenant's blocks / unpins an
        # adapter slot).
        quota_held: List[Request] = []
        limbo = self._admit_limbo

        def pop_waiting() -> Request:
            # Every admission pop is limbo-tracked until the request
            # lands somewhere recover() can see (crash safety; see
            # _rescue_admit_limbo).
            req = self.waiting.popleft()
            limbo.append(req)
            return req

        while self.waiting and self.free_slots and not stalled:
            dispatched = []
            while self.waiting and self.free_slots and not stalled:
                if self._kv_quota_blocked(self.waiting[0]):
                    quota_held.append(pop_waiting())
                    continue
                # Chunk-path requests (prompt longer than the chunk —
                # which also covers every possible prefix-cache hit)
                # claim a slot and join the chunk queue; they never
                # ride a bucketed wave. "stall" means the paged block
                # pool is dry: the request went back to the queue head
                # and admission stops until retirements free blocks
                # (the pool, not the slot count, is then the admission
                # limiter); "held" means its fine-tune's pool is fully
                # pinned — it steps aside and everyone behind it keeps
                # admitting.
                if self._use_chunked(self.waiting[0]):
                    req = pop_waiting()
                    cst = self._claim_chunked(req)
                    if cst == "stall":
                        stalled = True
                    elif cst == "held":
                        quota_held.append(req)
                    continue
                bucket = _bucket(self._ctx_len(self.waiting[0]),
                                 self.buckets)
                wave: List[Request] = []
                slots: List[int] = []
                rest: List[Request] = []
                while self.waiting and self.free_slots and \
                        not stalled and \
                        (self.max_wave is None
                         or len(wave) < self.max_wave):
                    req = pop_waiting()
                    if self._kv_quota_blocked(req):
                        quota_held.append(req)
                    elif self._use_chunked(req):
                        cst = self._claim_chunked(req)
                        if cst == "stall":
                            stalled = True
                        elif cst == "held":
                            quota_held.append(req)
                    elif _bucket(self._ctx_len(req),
                                 self.buckets) == bucket:
                        st, slot = self._wave_claim(req)
                        if st == "ok":
                            wave.append(req)
                            slots.append(slot)
                        elif st == "held":
                            # Adapter pool fully pinned: step aside —
                            # base-model and resident-adapter traffic
                            # behind it keeps admitting.
                            quota_held.append(req)
                        elif st == "dry":    # block pool dry
                            self._requeue(req)
                            stalled = True
                        # "failed": consumed (failed typed)
                    else:
                        rest.append(req)
                self.waiting.extendleft(reversed(rest))
                if wave:
                    dispatched.append(
                        (wave, slots, bucket) + self._dispatch_wave(
                            wave, slots, bucket))
            for wave, slots, bucket, first_dev, span, stall, disp_s, \
                    dev_key in dispatched:
                self._complete_wave(wave, slots, first_dev, span,
                                    bucket, stall, dispatch_s=disp_s,
                                    dev_key=dev_key)
                if on_wave is not None:
                    on_wave()
            # on_wave may have drained fresh arrivals into ``waiting``
            # — the outer loop admits them while slots remain.
        if quota_held:
            self.waiting.extendleft(reversed(quota_held))
            ENGINE_WAITING.set(len(self.waiting))
        plan.set(held=len(quota_held), stalled=1 if stalled else 0)

    def _use_chunked(self, req: Request) -> bool:
        return (self.prefill_chunk is not None
                and self._ctx_len(req) > self.prefill_chunk)

    def _claim_chunked(self, req: Request) -> str:
        """Claim a slot for an incremental prefill: look up the prefix
        cache, reuse a hit's rows (suffix-only prefill), and queue the
        remaining chunks. The claim stamps the slot's cache length to
        max_len so interleaved decode bursts' garbage writes for this
        (inactive) slot land out of bounds and are dropped — they must
        never corrupt rows a finished chunk already wrote.

        Paged: a hit maps the stored prefix's ref-counted blocks into
        the slot's table — NO row copies. A partially-filled shared
        block (block_len not dividing the cached length) is copied on
        write first (`skytpu_kv_cow_copies_total`): this slot's suffix
        prefill writes into it at offset cached%block. Contiguous: the
        hit copies the pool row on-device as before. Returns "ok"
        (claimed), "failed" (adapter load failed — the request was
        consumed, failed typed), "held" (adapter pool fully pinned —
        the caller steps this request aside, everyone behind it keeps
        admitting), or "stall" (paged block pool dry — the request was
        re-queued at the head and admission pauses).
        """
        st = self._acquire_adapter(req)
        if st == "failed":
            return "failed"  # consumed (failed typed); keep admitting
        if st == "stall":
            self._mark_stall(req, "adapter_pin")
            return "held"    # adapter pool pinned: step aside
        ctx = self._ctx(req)
        idx = self._prefix_index
        hit = (idx.lookup(ctx, self._prefix_salt(req))
               if idx is not None else None)
        payload = cached = None
        n_shared = partial = 0
        shared: List[int] = []
        new_blocks: Optional[List[int]] = None
        if self.paged:
            if hit is not None:
                payload, cached = hit
                n_shared, partial = divmod(cached, self.kv_block)
                # PIN the shared blocks BEFORE any dry-pool eviction:
                # _alloc_blocks may evict the hit's own entry, and an
                # unpinned payload block could be freed and handed
                # straight back as a fresh block — one physical block
                # aliased at two table positions, silently corrupting
                # the cached prefix the request is about to read.
                shared = list(payload[:n_shared])
                for b in shared:
                    self.allocator.incref(b)
            # Lazy reservations can be SMALLER than the shared prefix
            # rounds to; never ask for a negative count.
            new_blocks = self._alloc_blocks(
                max(self._need_blocks(req, len(ctx)) - n_shared, 0))
            if new_blocks is None:
                for b in shared:          # unpin; retry next pass
                    self.allocator.decref(b)
                self._release_adapter(req)
                self._mark_stall(req, "pool_dry")
                self._requeue(req)
                return "stall"
        slot = self.free_slots.pop(0)
        self._set_slot_adapter(slot, req.adapter_slot)
        req.slot = slot
        self._end_stall(req)
        req.prefill_begin_s = time.time()
        tracing.record_span(
            "engine.queue_wait", req.submit_s, req.prefill_begin_s,
            parent=req.span_ctx, attrs={"rid": req.rid})
        claim_len = jnp.asarray(self.max_len, jnp.int32)
        reused = 0
        if self.paged:
            row = self.block_table[slot]
            row[:] = self.n_kv_blocks
            if hit is not None:
                reused = cached
                PREFIX_HITS.inc()
                self._prefix_hit_n += 1
                row[:n_shared] = shared   # pinned above
                if partial:
                    # COW the partial shared block BEFORE the suffix
                    # prefill writes into it (its owner keeps ref > 1,
                    # so nothing else may scatter there).
                    self.cache = self._copy_block_fn(
                        self.cache,
                        jnp.asarray(payload[n_shared], jnp.int32),
                        jnp.asarray(new_blocks[0], jnp.int32))
                    KV_COW_COPIES.inc()
                    self._fl_cow += 1
            elif idx is not None and idx.eligible(ctx):
                PREFIX_MISSES.inc()
                self._prefix_miss_n += 1
            row[n_shared:n_shared + len(new_blocks)] = new_blocks
            self._table_dirty = True
            self._sync_kv_charge(slot, req.tenant)
            self.cache = self._claim_fn(
                self.cache, jnp.asarray(slot, jnp.int32), claim_len)
        elif hit is not None:
            payload, cached = hit
            reused = cached
            PREFIX_HITS.inc()
            self._prefix_hit_n += 1
            self.cache = self._pool_load_fn(
                self.cache, self.pool, jnp.asarray(payload, jnp.int32),
                jnp.asarray(slot, jnp.int32), claim_len)
        else:
            if idx is not None and idx.eligible(ctx):
                PREFIX_MISSES.inc()
                self._prefix_miss_n += 1
            self.cache = self._claim_fn(
                self.cache, jnp.asarray(slot, jnp.int32), claim_len)
        if req.tokens:
            # Preemption resume: the trailer's cached_len keeps the
            # ORIGINAL admission's prompt-prefix story; warm-resume
            # reuse is its own stat.
            req.resumed_len = reused
        else:
            req.cached_len = reused
        self.chunking.append(_ChunkState(req=req, pos=reused,
                                         total=len(ctx), ctx=ctx))
        # The request left ``waiting``; without this the queue-depth
        # gauge overreports by one per claim for the whole (possibly
        # multi-second) chunked prefill.
        self._update_gauges()
        return "ok"

    def prefill_chunk_step(self) -> bool:
        """Dispatch ONE chunk of the head chunked prefill. Only a
        prompt's FINAL chunk is awaited: its token is the request's
        first. A non-final chunk's token is garbage nobody reads, so
        the chunk is dispatched, kept as a handle and LANDED later —
        its bookkeeping (``skytpu_prefill_chunks_total``,
        ``req.n_chunks``, ``skytpu_decode_stall_seconds`` when slots
        were decoding, the ``chunk`` flight record) runs when a decode
        burst dispatched behind it is fetched, or at the next chunk
        but one, whichever comes first. At most one chunk is unlanded
        when the next is dispatched (one running, one queued), with or
        without rows decoding, so nothing dispatched later waits behind
        more than two chunk programs. The scheduler still alternates
        chunk -> decode burst; the device runs them in dispatch order.
        Returns True if a chunk was dispatched. Dispatch and landing
        run behind the ``chunk`` dispatch boundary: a device failure in
        a chunk, queued or awaited, surfaces as a recoverable
        :class:`EngineDispatchError`."""
        if not self.chunking:
            return False
        with _dispatch_boundary("chunk"):
            return self._prefill_chunk_impl()

    def _land_chunks(self, before_seq: Optional[int] = None,
                     keep: int = 0) -> None:
        """Run the bookkeeping of queued chunks, oldest first: all but
        the newest ``keep``, or only those a burst of ``before_seq``
        was dispatched behind. Each is waited for on its own output, so
        its end is its own where the device had not passed it yet and
        costs nothing where a later program has already landed."""
        q = self._queued_chunks
        while len(q) > keep and (before_seq is None
                                 or q[0].burst_seq < before_seq):
            handle = q.popleft()
            with _dispatch_boundary("chunk", point=False), \
                    timeline.phase("engine.chunk.land"):
                handle.tok.block_until_ready()
            self._chunk_landed(handle)

    def _chunk_landed(self, handle: _ChunkHandle) -> None:
        """Host bookkeeping of one finished chunk program."""
        end_s = time.time()
        req = handle.req
        PREFILL_CHUNKS.labels(awaited="1" if handle.final else "0").inc()
        req.n_chunks += 1
        if handle.decode_active:
            DECODE_STALL_SECONDS.observe(end_s - handle.begin_s)
        self._record_flight(
            "chunk", begin_s=handle.begin_s, end_s=end_s,
            program={"span": handle.span_arg, "final": handle.final},
            slots=[req.slot], reqs=[req], toks=1 if handle.final else 0,
            stall=handle.decode_active,
            dispatch_s=handle.dispatch_done_s, dev_keys=[handle.key],
            window_keys=handle.window_keys,
            queued=0 if handle.final else 1)

    def _prefill_chunk_impl(self) -> bool:
        st = self.chunking[0]
        req = st.req
        ctx = st.ctx if st.ctx is not None else req.prompt
        C = self.prefill_chunk
        start = st.pos
        n_valid = min(C, st.total - start)
        final = start + n_valid >= st.total
        chunk = np.zeros((C,), np.int32)
        chunk[:n_valid] = ctx[start:start + n_valid]
        new_len = st.total if final else self.max_len
        # The big-cache dot reads only rows below this chunk's offset:
        # the span bucket covering ``start`` suffices, and because the
        # span is a pure function of the offset, warm (suffix-only)
        # and cold runs of the same chunk pick the same program —
        # the cached-vs-cold parity guarantee extends to spans.
        attn_span = self._span_arg(self._span_for(start))
        self.decode_programs.add(("chunk", final, attn_span))
        # One running, one queued: the chunk before the last is landed
        # (waited for, where no burst's landing has proved it done)
        # before this one joins the device's queue.
        self._land_chunks(keep=1)
        t0 = time.time()
        fresh = req.first_token_s is None    # not a preemption resume
        counts = {"chunk_tokens": n_valid, "padded_tokens": C,
                  "final": 1 if final else 0,
                  "queued": 0 if final else 1}
        if self._progs.SLOT_STATE:
            # Whether the chunk continues a state resident in the slot.
            counts["carried"] = 1 if start > 0 else 0
        if fresh and req.n_chunks == 0 and not self._queued_chunks:
            # The request's first chunk: none landed, none queued.
            req.queue_s = max(t0 - req.submit_s, 0.0)
            counts["queue_ms"] = round(req.queue_s * 1e3, 3)
        window = self._window_notes([(start, n_valid)])
        with timeline.phase("engine.chunk.dispatch", **counts, **window):
            self.cache, self.rng, tok_dev = self._prefill_chunk_fn(
                self.params, self.cache, jnp.asarray(chunk),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(n_valid, jnp.int32),
                jnp.asarray(req.slot, jnp.int32),
                jnp.asarray(new_len, jnp.int32), self.rng,
                self.table_device(), final=final,
                qweights=self.qweights, span=attn_span,
                kernel=self.kv_kernel, **self._lora_args())
        handle = _ChunkHandle(
            tok=tok_dev, req=req, final=final, begin_s=t0,
            dispatch_done_s=time.time(),
            key=self.compile_watch.last_key, span_arg=attn_span,
            decode_active=bool(self.slot_req),
            burst_seq=self._burst_seq,
            window_keys=window.get("window_keys"))
        st.pos += n_valid
        if not final:
            self._queued_chunks.append(handle)
            return True
        # The chunks before it first, each at its own end; then THE
        # fetch of a chunked prefill: the final chunk's token, which is
        # the request's first.
        self._land_chunks()
        with timeline.phase("engine.chunk.fetch", final=1) as ph:
            tok = int(tok_dev)           # host sync
            if fresh:
                # The request's first token lands here: its queue wait
                # and its TTFT on one event, as on a wave's fetch.
                ph.set(queue_ms=round(req.queue_s * 1e3, 3),
                       ttft_ms=round(max(time.time() - req.submit_s,
                                         0.0) * 1e3, 3))
        self._chunk_landed(handle)
        self.chunking.popleft()
        now = time.time()
        tracing.record_span(
            "engine.prefill", req.prefill_begin_s, now,
            parent=req.span_ctx,
            attrs={"rid": req.rid, "bucket": "chunked",
                   "cached_len": req.cached_len,
                   "chunks": req.n_chunks})
        req.tokens.append(tok)
        if req.first_token_s is None:
            # A preemption resume already served its first token —
            # TTFT is a once-per-request truth.
            req.first_token_s = now
            TTFT_SECONDS.observe(max(now - req.submit_s, 0.0))
        PREFILL_SECONDS.labels(bucket="chunked").observe(
            max(now - req.prefill_begin_s, 0.0))
        PREFILL_REQUESTS.labels(bucket="chunked").inc()
        self.slot_req[req.slot] = req
        self._store_prefix(ctx, req.slot, len(ctx),
                           salt=self._prefix_salt(req))
        if self._req_finished(req, tok):
            self._retire(req)
        self._update_gauges()
        return True

    def _store_prefix(self, ctx: List[int], slot: Optional[int],
                      rows: int, donor_live: bool = True,
                      salt: bytes = b"") -> int:
        """Install ``ctx``'s chunk-aligned prefix (over the slot's
        first ``rows`` resident rows) into the prefix cache unless it
        is already resident. Returns the number of rows actually
        installed — 0 on every skip path (no index, sub-chunk prefix,
        already covered, dry pool, contiguous dead donor) — so a
        caller can tell a real install from a no-op. Only chunk-path sequences are stored:
        their rows came from the chunk program, so a later cached run
        replays bit-identical state (the parity guarantee) — and a
        preempted slot's rows are the literal bytes decode committed,
        which is exactly what its resume must read back.

        Paged: storing is (mostly) FREE — the slot's full blocks over
        the prefix are increfed and recorded as the entry's payload, no
        row copies. A trailing partial block is copied-on-share while
        the donor LIVES (it keeps writing into its own copy past the
        prefix; `skytpu_kv_cow_copies_total`); a dying donor
        (preemption-by-eviction) shares the partial block by incref
        alone — no writer remains, so eviction stays a pure table
        edit. Contiguous: the slot's rows copy into a pool row as
        before (live donors only; a contiguous eviction resumes
        cold)."""
        idx = self._prefix_index
        if idx is None or slot is None:
            return 0
        n = (rows // idx.block) * idx.block
        if n < idx.block:
            return 0
        covered = idx.lookup(ctx, salt)
        if covered is not None and covered[1] >= n:
            return 0
        if self.paged:
            n_full, partial = divmod(n, self.kv_block)
            nb = n_full + (1 if partial else 0)
            blocks = self.block_table[slot, :nb].tolist()
            if partial and donor_live:
                cow = self._alloc_blocks(1)
                if cow is None:      # pool dry: skip storing
                    return 0
                self.cache = self._copy_block_fn(
                    self.cache,
                    jnp.asarray(blocks[n_full], jnp.int32),
                    jnp.asarray(cow[0], jnp.int32))
                KV_COW_COPIES.inc()
                self._fl_cow += 1
                blocks[n_full] = cow[0]
            for b in blocks[:n_full]:
                self.allocator.incref(b)
            if partial and not donor_live:
                self.allocator.incref(blocks[n_full])
            for payload in idx.insert_entry(ctx, n, tuple(blocks),
                                            salt):
                PREFIX_EVICTIONS.inc()
                self._fl_evictions += 1
                for b in payload:
                    self.allocator.decref(b)
            self._update_gauges()
            return n
        if not donor_live:
            return 0
        row, evicted = idx.acquire_row()
        if evicted:
            PREFIX_EVICTIONS.inc()
            self._fl_evictions += 1
        self.pool = self._pool_store_fn(
            self.pool, self.cache, jnp.asarray(slot, jnp.int32),
            jnp.asarray(row, jnp.int32))
        idx.register(ctx, n, row, salt)
        return n

    def clear_prefix_cache(self) -> None:
        """Drop every resident prefix. Paged: the entries' block refs
        are released (blocks still mapped into live slots stay until
        those retire). Contiguous: host index only — the pool rows
        become unreachable. Benchmarks use this to measure a cold pass
        against a warm one on the same engine."""
        idx = self._prefix_index
        if idx is None:
            return
        if self.paged:
            for payload in idx.payloads():
                for b in payload:
                    self.allocator.decref(b)
        idx.clear()
        self._update_gauges()

    # -- cross-replica KV handoff (disaggregated serving) ------------------

    def handoff_eligible(self, prompt: List[int],
                         max_new_tokens: int) -> bool:
        """Whether a request prefilled HERE can hand its KV off to
        another replica: paged layout + prefix cache on, and the
        resumed context (prompt + the one committed token) must take
        the chunk-path resume on the receiving tier — the same
        ``_resumable`` conditions preemption requires, because a
        handoff IS a preemption with a network hop. Single-token
        budgets stay single-tier: there is nothing left to decode."""
        return (self.paged
                and self._prefix_index is not None
                and self._prefix_index.eligible(prompt)
                and max_new_tokens > 1
                and self._resumable(len(prompt) + 1))

    def export_prefix_for(self, req: Request) -> Optional[Dict[str, Any]]:
        """Host-side snapshot of the retired request's stored prefix —
        block contents + lengths — for transfer to a decode-tier
        replica. The chunk path stored the prefix at final-chunk
        completion (:meth:`_store_prefix`), so this is a PrefixIndex
        lookup plus ONE fixed-shape device gather; the entry's blocks
        stay ref-counted LRU residents here (nothing to leak — a
        handoff leaves the donor exactly as warm as any cached serve).
        Returns None when no chunk-aligned prefix is resident (the
        caller falls back to single-tier)."""
        refuse_options(self._progs, export_prefix=True)
        idx = self._prefix_index
        if not self.paged or idx is None:
            return None
        ctx = self._ctx(req)
        salt = self._prefix_salt(req)
        hit = idx.lookup(ctx, salt)
        if hit is None:
            return None
        payload, cached = hit
        nb = len(payload)
        ids = np.full((self.blocks_per_slot,), self.n_kv_blocks,
                      np.int32)
        ids[:nb] = payload
        vals = self._export_blocks_fn(self.cache, jnp.asarray(ids))
        tensors = {}
        for name, v in vals.items():
            arr = np.ascontiguousarray(np.asarray(v)[:, :nb])
            tensors[name] = arr
        # The salt rides the export: an adapter-scoped prefix must be
        # re-inserted on the decode tier under the SAME content digest
        # its claim-time lookup will use (the fleet shares one catalog,
        # so the decode replica's hot-load reproduces the digest).
        return {"cached_len": cached, "kv_block": self.kv_block,
                "n_blocks": nb, "salt": salt, "tensors": tensors}

    def import_prefix(self, ctx: List[int], export: Dict[str, Any],
                      salt: bytes = b"") -> int:
        """Install another replica's exported prefix into this
        engine's pool + PrefixIndex so the handed-off request resumes
        through the ordinary prefix-hit suffix prefill. Returns the
        cached rows now resident for ``ctx`` (0 = nothing imported —
        layout/geometry mismatch or a dry pool; the caller's request
        still runs correctly, just cold). Loop-thread only: allocates
        blocks and swaps the donated cache."""
        refuse_options(self._progs, import_prefix=True)
        idx = self._prefix_index
        if not self.paged or idx is None:
            return 0
        if export.get("kv_block") != self.kv_block:
            return 0            # geometry mismatch: resume cold
        cached = int(export["cached_len"])
        nb = int(export["n_blocks"])
        tensors = export["tensors"]
        for name in ("k", "v"):
            want = self.cache[name]
            have = tensors.get(name)
            # The wire widens sub-fp32 float planes to float32 (exact;
            # the scatter casts back), so a float32 payload matches a
            # bfloat16 pool; int8-vs-float is a REAL quant-config
            # mismatch and resumes cold.
            ok_dtype = (str(have.dtype) == str(want.dtype)
                        if have is not None else False) or (
                have is not None
                and str(have.dtype) == "float32"
                and jnp.issubdtype(want.dtype, jnp.floating))
            if (have is None or have.shape[0] != want.shape[0]
                    or have.shape[2:] != want.shape[2:]
                    or not ok_dtype):
                return 0        # model/dtype mismatch: resume cold
        if ("k_scale" in self.cache) != ("k_scale" in tensors):
            return 0
        covered = idx.lookup(ctx, salt)
        if covered is not None and covered[1] >= cached:
            return covered[1]   # already at least as warm
        blocks = self._alloc_blocks(nb)
        if blocks is None:
            return 0            # pool dry: resume cold
        ids = np.full((self.blocks_per_slot,), self.n_kv_blocks,
                      np.int32)
        ids[:nb] = blocks
        pad = self.blocks_per_slot - nb
        vals = {}
        for name, arr in tensors.items():
            if pad:
                arr = np.concatenate(
                    [arr, np.zeros((arr.shape[0], pad) + arr.shape[2:],
                                   arr.dtype)], axis=1)
            vals[name] = jnp.asarray(arr)
        self.cache = self._import_blocks_fn(
            self.cache, jnp.asarray(ids), vals)
        for payload in idx.insert_entry(ctx, cached, tuple(blocks),
                                        salt):
            PREFIX_EVICTIONS.inc()
            self._fl_evictions += 1
            for b in payload:
                self.allocator.decref(b)
        self._update_gauges()
        return cached

    def _dispatch_wave(self, wave: List["Request"], slots: List[int],
                       bucket: int
                       ) -> Tuple[jax.Array, timeline.Event, bool,
                                  float, Optional[str]]:
        """Enqueue one wave's prefill+insert program; returns the
        (device) first-token array without forcing a host sync, the
        open prefill span (closed at completion — the span covers
        dispatch THROUGH first-token fetch, the latency a request
        actually experiences), and whether decode slots were active at
        dispatch (the wave then also counts as decode stall)."""
        span = timeline.Event(
            "skytpu_prefill_seconds",
            histogram=PREFILL_SECONDS.labels(bucket=str(bucket)))
        span.begin()
        for req in wave:
            # Queue wait ends where the prefill dispatch begins.
            tracing.record_span(
                "engine.queue_wait", req.submit_s, span.begin_s,
                parent=req.span_ctx, attrs={"rid": req.rid})
            if req.first_token_s is None:      # not a preemption resume
                req.queue_s = max(span.begin_s - req.submit_s, 0.0)
        n = next(r for r in self.wave_rungs if r >= len(wave))
        queued = [req.queue_s * 1e3 for req in wave
                  if req.first_token_s is None]
        with timeline.phase(
                "engine.wave.dispatch", rows=len(wave), padded_rows=n,
                bucket=bucket,
                prompt_tokens=sum(self._ctx_len(r) for r in wave),
                queue_ms_sum=round(sum(queued), 3),
                queue_ms_max=round(max(queued, default=0.0), 3),
                **self._window_notes(
                    [(0, self._ctx_len(r)) for r in wave])):
            return self._launch_wave(wave, slots, bucket, n, span)

    def _launch_wave(self, wave: List["Request"], slots: List[int],
                     bucket: int, n: int, span: timeline.Event
                     ) -> Tuple[jax.Array, timeline.Event, bool,
                                float, Optional[str]]:
        """Build one wave's padded host arrays and enqueue its
        program (the body of :meth:`_dispatch_wave`'s annotation)."""
        tokens_b = np.zeros((n, bucket), np.int32)
        true_lens = np.ones((n,), np.int32)
        slot_ids = np.full((n,), self.n_slots, np.int32)  # spare
        for i, (req, slot) in enumerate(zip(wave, slots)):
            ctx = self._ctx(req)
            tokens_b[i, :len(ctx)] = ctx
            true_lens[i] = len(ctx)
            slot_ids[i] = slot
        decode_active = bool(self.slot_req)
        wave_lora = {}
        if self.adapters is not None:
            # Per-wave-row adapter ids (dummy rows ride the all-zeros
            # base slot): the wave's rows each gather their own
            # fine-tune — mixed-adapter admission is one dispatch.
            aid_w = np.zeros((n,), np.int32)
            for i, req in enumerate(wave):
                aid_w[i] = req.adapter_slot
            wave_lora = {"lora": self.adapters.pool,
                         "aid": jnp.asarray(aid_w)}
        self.cache, self.rng, first = self._admit_wave_fn(
            self.params, self.cache, jnp.asarray(tokens_b),
            jnp.asarray(true_lens), jnp.asarray(slot_ids), self.rng,
            self.table_device(), bucket=bucket, qweights=self.qweights,
            **wave_lora)
        return (first, span, decode_active, time.time(),
                self.compile_watch.last_key)

    def _complete_wave(self, wave: List["Request"], slots: List[int],
                       first_dev: jax.Array, span: timeline.Event,
                       bucket: int, decode_active: bool = False,
                       dispatch_s: Optional[float] = None,
                       dev_key: Optional[str] = None) -> None:
        fresh = [r for r in wave if r.first_token_s is None]
        with timeline.phase("engine.wave.fetch", rows=len(wave),
                            first_tokens=len(fresh)) as ph:
            first = np.asarray(first_dev)      # host sync for THIS wave
            span.end()
            now = time.time()
            # Queue wait and TTFT of the SAME requests (resumes carry
            # neither), so a reader can take their ratio per fetch.
            ph.set(queue_ms_sum=round(
                       sum(r.queue_s for r in fresh) * 1e3, 3),
                   ttft_ms_sum=round(sum(
                       max(now - r.submit_s, 0.0) for r in fresh) * 1e3,
                       3))
        if decode_active:
            DECODE_STALL_SECONDS.observe(max(now - span.begin_s, 0.0))
        PREFILL_WAVES.labels(bucket=str(bucket),
                             rows=str(first.shape[0])).inc()
        self._record_flight(
            "wave", begin_s=span.begin_s, end_s=now,
            program={"bucket": bucket, "rows": first.shape[0]},
            slots=slots, reqs=wave, toks=len(wave),
            stall=decode_active, dispatch_s=dispatch_s,
            dev_keys=[dev_key])
        for req in wave:
            # The latency the request experienced: dispatch through
            # first-token fetch (same window as the histogram span).
            tracing.record_span(
                "engine.prefill", span.begin_s, now,
                parent=req.span_ctx,
                attrs={"rid": req.rid, "bucket": bucket,
                       "cached_len": 0, "chunks": 0})
        for i, (req, slot) in enumerate(zip(wave, slots)):
            tok = int(first[i])
            req.slot = slot
            req.tokens.append(tok)
            if req.first_token_s is None:      # not a preemption resume
                req.first_token_s = now
                TTFT_SECONDS.observe(max(now - req.submit_s, 0.0))
            PREFILL_REQUESTS.labels(bucket=str(bucket)).inc()
            self.slot_req[slot] = req
            if self._req_finished(req, tok):
                self._retire(req)
        self._update_gauges()


    # -- stepping ----------------------------------------------------------

    def _req_finished(self, req: Request, tok: int) -> bool:
        if req.eos_id is not None and tok == req.eos_id:
            return True
        if len(req.tokens) >= req.max_new_tokens:
            return True
        return len(req.prompt) + len(req.tokens) >= self.max_len

    def _retire(self, req: Request) -> None:
        with timeline.phase("engine.retire",
                            prompt_tokens=len(req.prompt),
                            tokens=len(req.tokens)):
            self._retire_impl(req)

    def _retire_impl(self, req: Request) -> None:
        # No cache-length scrub: ``insert`` stamps the slot's length on
        # reuse, decode's commit mask skips non-active slots, and a
        # dead slot's attention output is never read — an eager
        # per-retirement scatter here was pure hygiene at one device
        # dispatch per finished request (reset() still zeroes all).
        req.done = True
        self.finished.append(req)
        REQUESTS_FINISHED.inc()
        now = time.time()
        decoded = req.first_token_s is not None and len(req.tokens) > 1
        if req.span_ctx is not None:
            if decoded:
                # ONE decode span per request (first token ->
                # retirement): a span per slot per burst floods the
                # flight-recorder ring at high occupancy — 64 slots at
                # ~100 bursts/s would leave only seconds of history.
                # Device-call timing stays on the
                # skytpu_decode_step_seconds histogram/timeline span.
                tracing.record_span(
                    "engine.decode", req.first_token_s, now,
                    parent=req.span_ctx,
                    attrs={"rid": req.rid,
                           "tokens": len(req.tokens) - 1})
            tracing.record_span(
                "engine.request", req.submit_s, now,
                ctx=req.span_ctx, parent_id=req.parent_id,
                attrs={"rid": req.rid, "prompt_len": len(req.prompt),
                       "n_tokens": len(req.tokens)})
        if decoded:
            TPOT_SECONDS.observe(
                max(now - req.first_token_s, 0.0)
                / (len(req.tokens) - 1))
        if self.forensics:
            # Request forensics: ONE retirement record anchors the
            # critical-path ledger (submit/first-token/end stamps +
            # closed stall episodes — `skytpu why` reassembles the
            # request's bursts around it), then the streaming tail
            # detector decides whether this request's evidence is
            # worth pinning past ring rollover. Host bookkeeping
            # only, once per request, off the burst path.
            self._end_stall(req)
            fl = self.flight
            if fl is not None and fl.enabled:
                fl.record(
                    "retire", ts_s=now, dur_s=0.0,
                    program={"layout":
                             "paged" if self.paged else "contig"},
                    slots=[req.slot] if req.slot is not None else [],
                    rids=[req.rid],
                    traces=[req.span_ctx.trace_id]
                    if req.span_ctx is not None else [],
                    toks=0, submit_s=req.submit_s,
                    first_token_s=req.first_token_s, end_s=now,
                    prompt_len=len(req.prompt),
                    n_toks=len(req.tokens),
                    cached_len=req.cached_len,
                    resumed_len=req.resumed_len,
                    n_chunks=req.n_chunks,
                    spec_drafted=req.spec_drafted,
                    spec_accepted=req.spec_accepted,
                    preemptions=req.preemptions,
                    stalls={k: round(v, 4)
                            for k, v in req.stall_ms.items()},
                    tenants={req.tenant: 1}, adapter=req.adapter)
            self._observe_tail(req, now)
        if req.slot is not None:
            self.slot_req.pop(req.slot, None)
            self.free_slots.append(req.slot)
            self._free_slot_blocks(req.slot)
            self._set_slot_adapter(req.slot, 0)
            if self.draft_engine is not None:
                # Drafter lifecycle rides the slot's: the mirrored
                # draft slot frees its blocks with the main slot (a
                # reused slot's next occupant re-ingests from zero).
                self.draft_engine.release(req.slot)
            req.slot = None
        self._release_adapter(req)
        SLOTS_ACTIVE.set(len(self.slot_req))
        if self.paged:
            KV_BLOCKS_USED.set(self.allocator.used)

    def _observe_tail(self, req: Request, now: float) -> None:
        """Streaming tail detection at retirement: fold this request's
        TTFT/TPOT into the P2 estimators (O(1) host floats), and when
        it crosses the configured quantile pin its FULL evidence —
        retirement record, every flight record it rode, its assembled
        ledger — into the exemplar store. The ring scan happens only
        on a crossing (~1 in 10^3 at the default p99.9), never on the
        ordinary retire path."""
        if metrics.suppressed():      # warmup must not skew the tail
            return
        hits = []
        if req.first_token_s is not None:
            ttft_ms = max(req.first_token_s - req.submit_s, 0.0) * 1e3
            crossed, thr = self.tail.observe("ttft", ttft_ms)
            if crossed:
                hits.append(("ttft", ttft_ms, thr))
            if len(req.tokens) > 1:
                tpot_ms = (max(now - req.first_token_s, 0.0) * 1e3
                           / (len(req.tokens) - 1))
                crossed, thr = self.tail.observe("tpot", tpot_ms)
                if crossed:
                    hits.append(("tpot", tpot_ms, thr))
        if not hits:
            return
        fl = self.flight
        recs: List[Dict[str, Any]] = []
        retire = None
        if fl is not None and fl.enabled:
            for r in fl.tail():
                if req.rid in (r.get("rids") or ()):
                    recs.append(r)
                    if r.get("burst") == "retire":
                        retire = r
        ledger = (forensics_lib.build_ledger(retire, recs)
                  if retire is not None else None)
        for metric, value, thr in hits:
            forensics_lib.TAIL_EXEMPLARS_PINNED.labels(
                metric=metric).inc()
            self.exemplars.pin({
                "rid": req.rid, "metric": metric,
                "value_ms": round(value, 4),
                "threshold_ms": (round(thr, 4)
                                 if thr is not None else None),
                "ts_s": now,
                "trace_id": (req.span_ctx.trace_id
                             if req.span_ctx is not None else None),
                "tenant": req.tenant, "adapter": req.adapter,
                "retire": retire, "records": recs, "ledger": ledger})

    def step(self) -> Dict[int, int]:
        """Admit waiting requests (draining any chunked prefills to
        completion — single-step callers want classic semantics),
        decode one token per active slot.

        Returns {rid: token} emitted this step.
        """
        self._admit()
        while self.chunking:
            self.prefill_chunk_step()
        return self.step_decode_once()

    def admit(self, on_wave=None) -> None:
        """Prefill+insert every admissible waiting request (public
        wrapper: the server calls this separately from decode so it can
        size decode bursts AFTER admission — full bursts only when the
        slots are full and admission is impossible anyway)."""
        self._admit(on_wave)

    def reset(self) -> None:
        """Drop every queued and in-flight request and zero the slot
        state. After an engine failure the server must not re-drive
        poisoned slots — stale waiting/slot_req would re-raise the same
        error for every future request (advisor r3)."""
        self.waiting.clear()
        self.chunking.clear()
        # Queued chunks are dropped unlanded: their rows die with the
        # slots wiped below, and a chunker re-admits from the start.
        self._queued_chunks.clear()
        self.finished.clear()
        self.slot_req.clear()
        self.free_slots = list(range(self.n_slots))
        self._inflight_tokens = 0
        self.cache["length"] = jnp.zeros_like(self.cache["length"])
        # A mid-copy/mid-chunk failure may have left pool rows (or
        # block refcounts) in an unknown state; drop the index rather
        # than serve them.
        if self.paged:
            # The index entries' refs die with the wholesale pool
            # reset below — clear WITHOUT per-block decrefs (a failure
            # mid-claim may have left counts inconsistent; decref
            # could double-free).
            if self._prefix_index is not None:
                self._prefix_index.clear()
            self.allocator.reset()
            self.block_table[:] = self.n_kv_blocks
            self._table_dirty = True
            self._slot_kv_charge.clear()
            for t in list(self._tenant_kv):
                self._set_tenant_kv(t, 0)
        else:
            self.clear_prefix_cache()
        if self.adapters is not None:
            # A failure mid-hot-load may have left pins inconsistent;
            # drop all residency (pool arrays stay — nothing maps to
            # them until re-acquired).
            self.adapters.reset()
            self.adapter_ids[:] = 0
            self._aid_dirty = True
        if self.draft_engine is not None:
            # Drafter state mirrors the slots just wiped; a failure
            # mid-rollout may have left its counts inconsistent too.
            self.draft_engine.reset()
        self._update_gauges()

    def recover(self, exc: Optional[BaseException] = None) -> int:
        """Crash recovery: full :meth:`reset` (device/host bookkeeping
        may disagree after a failed dispatch — nothing narrower is
        safe), then re-admit every request that was queued or in
        flight through the preemption resume path. A crash is an
        involuntary preemption of EVERY resident at once: each victim
        re-queues with its prompt + committed tokens, re-prefills that
        context via the ordinary (now-cold) chunk admission path, and
        its greedy continuation is bit-identical to an uncrashed run
        (same guard rail as :meth:`preempt_slot` — contexts that still
        fit a wave re-admit through the wave program, which the parity
        matrix does not cover).

        Returns the number of requests re-queued. Requests already
        retired with output stay finished; the server keeps streaming
        the SAME Request objects, so open streams continue gapless.
        """
        # Snapshot before the wipe: residents (decode slots), chunkers
        # (mid-chunked-prefill — disjoint from residents until the
        # final chunk; reset() drops their queued chunk handles, and
        # they re-prefill from the start), and the untouched queue.
        # Order within each class is deterministic (rid = arrival
        # order) so a recovered engine admits in the same order every
        # time.
        residents = sorted(self.slot_req.values(), key=lambda r: r.rid)
        chunkers = [st.req for st in self.chunking]
        chunker_rids = {r.rid for r in chunkers}
        queued = list(self.waiting)
        finished = list(self.finished)
        self.reset()
        self.finished.extend(finished)   # retired output survives
        seam = getattr(exc, "seam", None) or "unknown"
        now = time.time()
        victims: List[Request] = []
        seen = set()
        for req in residents + chunkers + queued:
            if req.done or req.rid in seen:
                continue
            seen.add(req.rid)
            victims.append(req)
        for req in victims:
            in_flight = (req.slot is not None
                         or req.rid in chunker_rids)
            # reset() wiped the tables/pins wholesale — scrub the
            # per-request mirrors WITHOUT the release paths (a decref
            # or unpin now would double-free against the wiped state).
            req.slot = None
            req.adapter_pinned = False
            req.adapter_slot = 0
            if in_flight:
                req.recoveries += 1
                # The re-prefill wait is a named stall episode: the
                # ledger's queue-ish gaps consume it into the
                # ``stall_recover`` phase, closed by the next claim.
                self._mark_stall(req, "recover")
            self._requeue(req)
        self.waiting.reverse()           # _requeue prepends; restore order
        ENGINE_RECOVERIES.labels(seam=seam).inc()
        fl = self.flight
        if fl is not None and fl.enabled:
            fl.record(
                "recover", ts_s=now, dur_s=0.0,
                program={"layout": "paged" if self.paged else "contig",
                         "seam": seam},
                slots=[], rids=[r.rid for r in victims],
                toks=0, n_victims=len(victims))
        self._update_gauges()
        return len(victims)

    def step_burst(self, max_burst: int = 8,
                   on_wave=None) -> Dict[int, List[int]]:
        """Admit, dispatch ONE prefill chunk if any are queued (chunk
        -> decode-burst alternation: long prompts prefill without
        stalling decode for their whole length), then decode up to
        ``max_burst`` tokens per slot in one device call; the burst's
        fetch lands the chunk that ran before it. Tokens past a
        request's EOS/limit are discarded host-side (their cache rows
        die with the slot). Returns {rid: [tokens...]} emitted this call.
        ``on_wave`` fires after each admission wave (streaming flush
        hook)."""
        self._admit(on_wave)
        if self.chunking:
            self.prefill_chunk_step()
        return self.decode_burst(max_burst)

    def decode_burst(self, max_burst: int = 8, why: str = ""
                     ) -> Dict[int, List[int]]:
        """Decode up to ``max_burst`` tokens per active slot in one
        device call — NO admission (callers that interleave admission
        and decode use :meth:`admit` + this).

        With speculation enabled (``spec_k > 0``) a verify burst
        REPLACES the plain decode burst: one device call scores K
        drafted tokens + the correction position per slot and commits
        the accepted run. Falls back to a plain burst only for the
        rounds where NO active slot drafted (all missed, collapsed,
        or out of row headroom — a tight slot alone just rides the
        verify burst with an empty draft)."""
        if self.spec_k:
            out = self.spec_decode_burst(why)
            if out is not None:
                return out
        handle = self.dispatch_decode_burst(max_burst, why)
        if handle is None:
            return {}
        return self.complete_decode_burst(handle)

    def _spec_mode(self, req: Request) -> str:
        """Resolve (and advance) this request's drafter rung. Requests
        start at "model" when the engine has a DraftEngine, else
        "ngram" (the factory seam — custom test drafters ride it too).
        Acceptance collapse in the CURRENT mode (>= spec_min_drafted
        drafted below spec_min_rate accepted since the last demotion)
        demotes one rung: model -> ngram (fresh window, fresh factory
        drafter, draft-engine slot released) -> off."""
        if req.spec_off:
            return "off"
        if req.spec_mode is None:
            req.spec_mode = ("model" if self.draft_engine is not None
                             else "ngram")
        if req.spec_mode == "model" and self.draft_engine is None:
            # The drafter was detached mid-flight (tests/bench toggle
            # routing between passes): fall to the factory rung with a
            # fresh window rather than dereference a gone engine.
            req.spec_mode = "ngram"
            req.spec_mode_drafted = 0
            req.spec_mode_accepted = 0
        if (req.spec_mode_drafted >= self.spec_min_drafted
                and req.spec_mode_accepted
                < self.spec_min_rate * req.spec_mode_drafted):
            if req.spec_mode == "model":
                req.spec_mode = "ngram"
                req.spec_mode_drafted = 0
                req.spec_mode_accepted = 0
                req.drafter = None       # factory rebuilds on demand
                if self.draft_engine is not None \
                        and req.slot is not None:
                    self.draft_engine.release(req.slot)
            else:
                req.spec_mode = "off"
                req.spec_off = True
        return req.spec_mode

    def _draft_for(self, req: Request) -> List[int]:
        """This request's draft through the per-request factory seam
        (n-gram by default; the demotion rung below the model
        drafter). Host-only: builds the drafter lazily and syncs it
        with tokens committed through any path."""
        if req.drafter is None:
            req.drafter = self._spec_drafter_factory(req)
            if req.drafter is None:          # factory opted this one out
                req.spec_off = True
                req.spec_mode = "off"
                return []
        req.drafter.catch_up(req.prompt, req.tokens)
        return req.drafter.draft(self.spec_k)

    def spec_decode_burst(self, why: str = ""
                          ) -> Optional[Dict[int, List[int]]]:
        """One draft-and-verify burst for every active slot: the host
        drafter proposes up to K tokens per slot, ONE compiled verify
        program scores the K+1 window positions, and the accepted run
        (+ the correction token) commits — up to K+1 tokens per slot
        per device call instead of 1.

        The verify FETCH is synchronous (the next round's window needs
        these tokens), but with a model drafter and ``spec_pipeline``
        the round is internally overlapped: the NEXT round's draft
        rollout dispatches while the verify program is in flight (the
        device chews on it behind the verify; the host fetches it
        lazily next round), so neither model waits on the other — the
        overlap PR 8's spec engines forfeited by skipping the async
        double-buffer. A mispredicted predraft is discarded host-side
        at the next ``draft_batch`` (drafter rollback = length
        non-advance, free under paged blocks).

        Returns None when the spec path can't run this round and the
        caller should fall back to a plain decode burst: no active
        slot produced a draft (all missed, collapsed, or out of row
        headroom — a K+1-wide verify would then be strictly worse
        than a plain burst).
        """
        K = self.spec_k
        if not self.slot_req or K <= 0:
            return None
        with _dispatch_boundary("verify"):
            return self._spec_decode_burst_impl(why)

    def _spec_decode_burst_impl(self, why: str = ""
                                ) -> Optional[Dict[int, List[int]]]:
        K = self.spec_k
        draft = np.zeros((self.n_slots + 1, K), np.int32)
        n_draft = np.zeros((self.n_slots + 1,), np.int32)
        dlen: Dict[int, int] = {}
        model_reqs: Dict[int, Request] = {}
        for slot, req in self.slot_req.items():
            # A slot within K+1 rows of max_len drafts NOTHING instead
            # of disabling speculation engine-wide: its single
            # correction row (at length <= max_len-1, guaranteed for
            # any active request) is in bounds, its spare window rows
            # past max_len drop via the same OOB-scatter net every
            # dead-slot write rides, and every other slot keeps its
            # draft. (Budget needs no check: an active request always
            # has >= 1 token remaining — every commit path retires at
            # the cap via _req_finished.)
            if len(req.prompt) + len(req.tokens) + K + 1 > self.max_len:
                continue
            mode = self._spec_mode(req)
            if mode == "off":
                continue
            if mode == "model":
                # Model-mode slots draft BATCHED below: one draft-
                # model dispatch covers every such slot (the whole
                # point of a DraftEngine over per-request drafters).
                model_reqs[slot] = req
                continue
            d = self._draft_for(req)
            if d:
                n_draft[slot] = len(d)
                draft[slot, :len(d)] = d
                dlen[slot] = len(d)
        if model_reqs:
            batch = self.draft_engine.draft_batch(
                {s: self._ctx(r) for s, r in model_reqs.items()}, K)
            for slot, d in batch.items():
                if d:
                    n_draft[slot] = len(d)
                    draft[slot, :len(d)] = d
                    dlen[slot] = len(d)
        if not dlen:
            return None
        # One verify program for the round, exactly as the plain
        # burst: every backable slot, at the longest one's rung.
        attn_span, slots, promoted = self._round_slots(K + 1)
        drafted = sum(dlen.get(s, 0) for s in slots)
        if not drafted:
            # Every drafting slot was kept out (lazy dry pool): a
            # K+1-wide verify for the rest would be strictly worse
            # than the plain burst the caller falls back to.
            return None
        span = timeline.Event("skytpu_decode_step_seconds",
                              histogram=DECODE_STEP_SECONDS)
        span.begin()
        self._burst_seq += 1
        active = np.zeros((self.n_slots + 1,), bool)
        active[slots] = True
        sarg = self._span_arg(attn_span)
        self.decode_programs.add(("verify", K, sarg))
        DECODE_ATTN_ROWS.observe(attn_span)
        # A verify program computes K + 1 window positions a row.
        with timeline.phase(
                "engine.decode.dispatch", seq=self._burst_seq,
                k=K + 1, slots=len(slots), rows=self.n_slots + 1,
                tiles=self._tiles("verify", len(slots)),
                span=attn_span, promoted=promoted, why=why,
                waiting=len(self.waiting)):
            self.cache, toks_dev, commit_dev = self._verify_fn(
                self.params, self.cache, jnp.asarray(draft),
                jnp.asarray(n_draft), jnp.asarray(active),
                self.table_device(), k=K, qweights=self.qweights,
                span=sarg, kernel=self.kv_kernel,
                **self._lora_args())
        verify_key = self.compile_watch.last_key
        dispatch_done_s = time.time()   # verify program enqueued
        # Pipelined predraft: with the verify program now in
        # flight, roll the draft model forward K+1 steps for the
        # model-drafting slots — its prediction of the verifier's
        # bonus/correction token plus the NEXT round's K drafts. The
        # dispatch is async (the device runs it behind the verify;
        # the tokens fetch lazily at the next draft_batch, which
        # validates them against what the verify actually committed),
        # so the draft model's work overlaps the verify wall instead
        # of serializing after the fetch.
        overlap_s = 0.0
        pre_slots = [s for s in dlen if s in model_reqs]
        if self.spec_pipeline and pre_slots:
            t_d0 = time.time()
            if self.draft_engine.rollout(pre_slots, K + 1):
                t_d1 = time.time()
                overlap_s = t_d1 - t_d0
                SPEC_OVERLAP_WALL.inc(overlap_s)
                self._record_flight(
                    "draft", begin_s=t_d0, end_s=t_d1,
                    program={"k": K + 1, "span": None},
                    slots=pre_slots,
                    reqs=[model_reqs[s] for s in pre_slots], toks=0,
                    drafter="model",
                    dev_keys=[self.draft_engine.compile_watch.last_key],
                    calibrator=getattr(self.draft_engine, "devtime",
                                       None) or self.devtime)
        # THE completion fetch: the verify tokens are this round's
        # output (the next round's window input), so this is the one
        # deliberate sync of the spec path — same role as
        # complete_decode_burst's.
        n_done0 = len(self.finished)
        self._land_chunks()      # all dispatched before this verify
        with timeline.phase(
                "engine.decode.fetch", seq=self._burst_seq, k=K + 1,
                parts=1, waiting=len(self.waiting)) as fetch_ph:
            toks = np.asarray(toks_dev)          # [B, K+1]
            n_commit = np.asarray(commit_dev)    # [B]
            span.end()
            end_s = time.time()
            SPEC_VERIFY_WALL.inc(max(end_s - span.begin_s, 0.0))
            out: Dict[int, List[int]] = {}
            n_emitted = accepted = 0
            model_drafted = ngram_drafted = 0
            live_reqs: List[Request] = []
            for slot in slots:
                req = self.slot_req.get(slot)
                if req is None or req.done:
                    continue
                nd = dlen.get(slot, 0)
                nc = int(n_commit[slot])
                emitted: List[int] = []
                for i in range(nc):
                    tok = int(toks[slot, i])
                    emitted.append(tok)
                    req.tokens.append(tok)
                    if self._req_finished(req, tok):
                        self._retire(req)
                        break
                # Accepted = matched draft tokens the request actually
                # emitted: the first nc-1 outputs are the matched run,
                # the nc-th the correction/bonus — an early EOS/budget
                # retire discards the tail, and counting the full run
                # would inflate the trailer stats and the acceptance
                # gauge on EOS-heavy workloads.
                acc = min(len(emitted), nc - 1)
                req.spec_drafted += nd
                req.spec_accepted += acc
                req.spec_mode_drafted += nd
                req.spec_mode_accepted += acc
                if slot in model_reqs:
                    model_drafted += nd
                else:
                    ngram_drafted += nd
                accepted += acc
                out[req.rid] = emitted
                n_emitted += len(emitted)
                live_reqs.append(req)
            self._record_flight(
                "verify", begin_s=span.begin_s, end_s=end_s,
                program={"k": K, "span": sarg},
                slots=slots, reqs=live_reqs, toks=n_emitted,
                drafted=model_drafted + ngram_drafted,
                accepted=accepted,
                drafter=("mixed" if model_drafted and ngram_drafted
                         else "model" if model_drafted
                         else "ngram" if ngram_drafted else None),
                overlap_ms=round(overlap_s * 1e3, 3),
                dispatch_s=dispatch_done_s, dev_keys=[verify_key])
            fetch_ph.set(tokens=n_emitted,
                         retired=len(self.finished) - n_done0)
        if model_drafted:
            SPEC_DRAFT_TOKENS.labels(drafter="model").inc(model_drafted)
        if ngram_drafted:
            SPEC_DRAFT_TOKENS.labels(drafter="ngram").inc(ngram_drafted)
        SPEC_DRAFTED.inc(drafted)
        if accepted:
            SPEC_ACCEPTED.inc(accepted)
        if drafted > accepted:
            SPEC_ROLLBACKS.inc(drafted - accepted)
        self._spec_drafted_total += drafted
        self._spec_accepted_total += accepted
        SPEC_ACCEPT_RATE.set(self._spec_accepted_total
                             / self._spec_drafted_total)
        if n_emitted:
            DECODE_TOKENS.inc(n_emitted)
        return out

    def dispatch_decode_burst(self, max_burst: int = 8, why: str = ""
                              ) -> Optional["BurstHandle"]:
        """Enqueue one decode-burst program WITHOUT fetching its tokens;
        pass the handle to :meth:`complete_decode_burst` later.

        This is the TPU-idle killer for streaming servers: dispatch
        burst k+1, THEN fetch/stream burst k's tokens — the device
        chews on k+1 (programs chain on the donated cache) while the
        host does JSON framing, socket writes and LB hops for k. The
        burst cap accounts for tokens still in flight, and slots whose
        request retires at k's completion simply waste rows in k+1
        (their tokens are discarded; OOB cache writes clamp into the
        dead slot's own rows).

        Returns ``None`` when there is nothing to decode — no active
        slot, or every active request's remaining budget is already
        covered by in-flight tokens.
        """
        if not self.slot_req:
            return None
        with _dispatch_boundary("decode"):
            return self._dispatch_decode_burst_impl(max_burst, why)

    def _dispatch_decode_burst_impl(self, max_burst: int, why: str = ""
                                    ) -> Optional["BurstHandle"]:
        # Cap the burst so no active slot's cache can overflow (counting
        # dispatched-but-uncommitted tokens), then round down to a power
        # of two: each distinct k compiles its own program, so the
        # k-space must stay tiny. (Tokens a request doesn't need are
        # discarded host-side — cheaper than a recompile.)
        k = max_burst
        need = 0
        for req in self.slot_req.values():
            rows = (len(req.prompt) + len(req.tokens)
                    + self._inflight_tokens)
            k = min(k, self.max_len - rows)
            need = max(need, req.max_new_tokens - len(req.tokens)
                       - self._inflight_tokens)
        if k < 1 or need < 1:
            return None
        k = 1 << (k.bit_length() - 1)
        # ONE program for the round: every slot the pool backs (lazy
        # mode grows each slot's blocks here; unbackable slots sit the
        # round out), at the rung covering the longest of them.
        attn_span, slots, promoted = self._round_slots(k)
        if not slots:
            return None            # lazy: pool dry — retry next round
        ev = timeline.Event("skytpu_decode_step_seconds",
                            histogram=DECODE_STEP_SECONDS)
        ev.begin()
        self._burst_seq += 1
        active = np.zeros((self.n_slots + 1,), bool)
        active[slots] = True
        sarg = self._span_arg(attn_span)
        self.decode_programs.add(("burst", k, sarg))
        DECODE_ATTN_ROWS.observe(attn_span)
        # The program's k steps run at ``rows`` batch rows of which
        # ``slots`` are live, ``promoted`` of them above their own rung;
        # a layer reads and attends the live ones in ``tiles`` turns.
        notes = self._family_notes(slots)
        if self._experts_per_step:
            # What the burst would read of the routed experts if its
            # rows chose them all; the fetch says what it did read.
            notes["experts_held"] = k * self._experts_per_step
        with timeline.phase(
                "engine.decode.dispatch", seq=self._burst_seq, k=k,
                slots=len(slots), rows=self.n_slots + 1,
                tiles=self._tiles("decode", len(slots)),
                span=attn_span, promoted=promoted, why=why,
                waiting=len(self.waiting), **notes):
            self.cache, self.rng, toks = self._decode_burst_fn(
                self.params, self.cache, self.rng,
                jnp.asarray(active), self.table_device(), k=k,
                qweights=self.qweights, span=sarg,
                kernel=self.kv_kernel, **self._lora_args())
        self._inflight_tokens += k
        return BurstHandle(toks=toks, slots=slots, k=k,
                           slot_req=dict(self.slot_req), span=ev,
                           span_arg=sarg,
                           key=self.compile_watch.last_key,
                           dispatch_done_s=time.time(),
                           seq=self._burst_seq,
                           kv_blocks=notes.get("kv_blocks"),
                           window_rows=notes.get("window_rows"))

    def complete_decode_burst(self, handle: "BurstHandle"
                              ) -> Dict[int, List[int]]:
        """Fetch a dispatched burst's tokens (host sync) and do the
        bookkeeping: append/retire per request, using the slot->request
        snapshot taken at dispatch. Requests retired by an earlier
        completion are skipped (their surplus tokens are discarded);
        slots a lazy dry pool kept out of the burst are not in the
        handle and emit nothing this round."""
        with _dispatch_boundary("decode"):
            return self._complete_decode_burst_impl(handle)

    def _complete_decode_burst_impl(self, handle: "BurstHandle"
                                    ) -> Dict[int, List[int]]:
        # A chunk dispatched before this burst ran before it.
        self._land_chunks(before_seq=handle.seq)
        with timeline.phase("engine.decode.fetch", seq=handle.seq,
                            k=handle.k, parts=1,
                            waiting=len(self.waiting)) as ph:
            toks = np.asarray(handle.toks)       # [k, slots+1]
            if handle.span is not None:
                handle.span.end()
            before = len(self.finished)
            with timeline.phase("engine.decode.commit"):
                out, n_emitted = self._commit_burst(handle, toks)
            counts = {}
            if self._progs.SPARE_COLUMN is not None:
                # The family's own count rides the spare slot's column.
                name, counter = self._progs.SPARE_COLUMN
                counts[name] = int(toks[:, self.n_slots].sum())
                counter.inc(counts[name])
            if self._experts_per_step:
                counts["experts_held"] = handle.k * self._experts_per_step
            ph.set(tokens=n_emitted,
                   retired=len(self.finished) - before, **counts)
        if n_emitted:
            DECODE_TOKENS.inc(n_emitted)
        return out

    def _commit_burst(self, handle: "BurstHandle", toks: np.ndarray
                      ) -> Tuple[Dict[int, List[int]], int]:
        """Host bookkeeping of a fetched burst: append / retire per
        request and write the flight record. Returns ({rid: tokens},
        tokens emitted and kept)."""
        end_s = time.time()
        begin_s = (handle.span.begin_s if handle.span is not None
                   else end_s)
        self._inflight_tokens -= handle.k
        out: Dict[int, List[int]] = {}
        n_emitted = 0
        live_reqs: List[Request] = []
        for slot in handle.slots:
            req = handle.slot_req.get(slot)
            if req is None or req.done:
                continue
            emitted = []
            for i in range(handle.k):
                tok = int(toks[i, slot])
                emitted.append(tok)
                req.tokens.append(tok)
                if self._req_finished(req, tok):
                    self._retire(req)
                    break
            out[req.rid] = emitted
            n_emitted += len(emitted)
            live_reqs.append(req)
        self._record_flight(
            "decode", begin_s=begin_s, end_s=end_s,
            program={"k": handle.k, "span": handle.span_arg},
            slots=handle.slots, reqs=live_reqs, toks=n_emitted,
            dispatch_s=handle.dispatch_done_s, dev_keys=[handle.key],
            kv_blocks=handle.kv_blocks, window_rows=handle.window_rows)
        return out, n_emitted

    def step_decode_once(self) -> Dict[int, int]:
        """One single-token decode for all active slots (no admission)
        — the classic-semantics fallback, at the same one span a burst
        round takes."""
        if not self.slot_req:
            return {}
        attn_span, slots, promoted = self._round_slots(1)
        if not slots:
            # Lazy mode only (eager slots always have headroom): the
            # sync single-step path has no outstanding burst whose
            # completion could free blocks, so an all-slots-unbackable
            # round is a genuine wedge — raise like run_to_completion,
            # never spin silently.
            raise KvPoolWedgedError(
                "KV block pool exhausted: lazy growth cannot back any "
                "active slot — size SKYTPU_KV_BLOCKS for the live "
                "working set or disable SKYTPU_KV_LAZY")
        active = np.zeros((self.n_slots + 1,), bool)
        active[slots] = True
        sarg = self._span_arg(attn_span)
        self.decode_programs.add(("decode1", 1, sarg))
        ev = timeline.Event("skytpu_decode_step_seconds",
                            histogram=DECODE_STEP_SECONDS)
        ev.begin()
        self._burst_seq += 1
        notes = self._family_notes(slots)
        with timeline.phase(
                "engine.decode.dispatch", seq=self._burst_seq, k=1,
                slots=len(slots), rows=self.n_slots + 1,
                tiles=self._tiles("decode1", len(slots)),
                span=attn_span, promoted=promoted, why="step",
                waiting=len(self.waiting), **notes):
            self.cache, self.rng, toks = self._decode_fn(
                self.params, self.cache, self.rng, jnp.asarray(active),
                self.table_device(), qweights=self.qweights, span=sarg,
                **self._lora_args())
        t_disp = time.time()
        step_key = self.compile_watch.last_key
        n_done0 = len(self.finished)
        self._land_chunks()      # all dispatched before this step
        with timeline.phase(
                "engine.decode.fetch", seq=self._burst_seq, k=1,
                parts=1, waiting=len(self.waiting)) as fetch_ph:
            toks = np.asarray(toks)
            ev.end()
            out: Dict[int, int] = {}
            step_reqs = [self.slot_req[s] for s in slots]
            for slot, req in zip(slots, step_reqs):
                tok = int(toks[slot])
                req.tokens.append(tok)
                out[req.rid] = tok
                if self._req_finished(req, tok):
                    self._retire(req)
            fetch_ph.set(tokens=len(out),
                         retired=len(self.finished) - n_done0)
        DECODE_TOKENS.inc(len(out))
        self._record_flight(
            "decode1", begin_s=ev.begin_s, end_s=time.time(),
            program={"k": 1, "span": sarg},
            slots=slots, reqs=step_reqs, toks=len(out),
            dispatch_s=t_disp, dev_keys=[step_key],
            kv_blocks=notes.get("kv_blocks"),
            window_rows=notes.get("window_rows"))
        return out

    def run_to_completion(self, max_burst: int = 8) -> List[Request]:
        """Drain all waiting + active requests; returns finished list.

        Lazy mode can genuinely wedge: every active slot needs blocks
        the pool cannot grow and nothing is left to retire. Eager
        admission makes that impossible by construction; here the
        stall is detected and raised instead of spinning forever."""
        stalled = 0
        while self.waiting or self.chunking or self.slot_req:
            had_chunks = bool(self.chunking)
            before = len(self.finished)
            out = self.step_burst(max_burst)
            progress = (bool(out) or had_chunks
                        or len(self.finished) > before)
            stalled = 0 if progress else stalled + 1
            if self.kv_lazy and self.slot_req and stalled > 2:
                raise KvPoolWedgedError(
                    "KV block pool exhausted: lazy growth cannot back "
                    "any active slot and nothing can retire — size "
                    "SKYTPU_KV_BLOCKS for the live working set or "
                    "disable SKYTPU_KV_LAZY")
        return self.finished

    # -- convenience -------------------------------------------------------

    def generate(self, prompts: List[List[int]],
                 max_new_tokens: int = 128) -> List[List[int]]:
        ids = [self.add_request(p, max_new_tokens) for p in prompts]
        self.run_to_completion()
        by_rid = {r.rid: r for r in self.finished}
        return [by_rid[i].tokens for i in ids]


def random_serving_weights(cfg: llama.LlamaConfig, *,
                           weights_int8: bool = False, mesh=None,
                           rules=None, seed: int = 0):
    """``(params, qweights)``: random serving weights built ON the
    device(s) at the size they are served — the one builder behind
    ``infer.server`` and ``bench_serve``.

    * ``weights_int8``: int8 block weights + head and a slim float
      tree (embedding + norms), never the float tree they would
      quantize from (:func:`kvcache.random_quantized_params`) — how
      llama3-8b (32 GB in float32) starts on a 16 GB chip.
    * otherwise the float tree in the COMPUTE dtype (``cfg.dtype``):
      every serve program casts a weight to it at use, so storing it
      that way is bit-identical and half the bytes of ``param_dtype``.
    * ``mesh``: each device materializes only its own shards
      (:func:`sharding.init_sharded`).

    The per-device bytes are checked against the device's
    ``bytes_limit`` first (where the backend reports one):
    :class:`WeightsDoNotFitError` names both numbers."""
    from skypilot_tpu.parallel import sharding as sh
    model = registry.model_for(cfg)
    refuse_options(kvcache.programs_for(cfg), weights_int8=weights_int8,
                   tp=mesh is not None)
    if weights_int8:
        def build():
            params, qweights = kvcache.random_quantized_params(cfg, seed)
            return {"params": params, "qweights": qweights}
        axes = {"params": llama.param_logical_axes(cfg),
                "qweights": kvcache.qweight_logical_axes(cfg)}
    else:
        def build():
            params = model.init_params(jax.random.key(seed), cfg)
            return {"params": jax.tree.map(
                lambda w: w.astype(cfg.dtype), params)}
        axes = {"params": model.param_logical_axes(cfg)}
    abstract = jax.eval_shape(build)
    rules = rules or sh.INFER_TP_RULES
    leaves = jax.tree.leaves(abstract)
    shapes = [a.shape for a in leaves]
    if mesh is not None:
        shardings = jax.tree.leaves(
            sh.subset_shardings(abstract, axes, mesh, rules))
        shapes = [s.shard_shape(shape)
                  for s, shape in zip(shardings, shapes)]
    need = sum(int(np.prod(shape)) * a.dtype.itemsize
               for a, shape in zip(leaves, shapes))
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if limit and need > limit:
        raise WeightsDoNotFitError(
            f"{'int8' if weights_int8 else jnp.dtype(cfg.dtype).name} "
            f"serving weights ({cfg.num_params():,} parameters)",
            need, int(limit))
    if mesh is not None:
        out = sh.init_sharded(build, lambda _: axes, mesh, rules)
    elif weights_int8:
        # Leaf by leaf, eagerly: one jitted program could schedule
        # several multi-GB random draws (and their temporaries) at
        # once next to ~8 GB of finished weights.
        out = build()
    else:
        # Jitted so each float32 draw fuses into its cast and the
        # param_dtype tree never exists whole.
        out = jax.jit(build)()
    return out["params"], out.get("qweights")
