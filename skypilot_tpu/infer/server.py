"""HTTP model server: continuous-batching engine behind a stdlib server.

This is what a SkyServe replica runs (see llm/serve-llama.yaml): the
load balancer probes ``/health`` and proxies ``/generate``; the engine
thread batches concurrent requests into shared decode bursts.

Endpoints:
  GET  /health              -> 200 {"status": "ok", "device": {...}}
                               once warm (the device JAX opened)
  GET  /metrics             -> Prometheus text exposition of the
                               process registry (engine TTFT/TPOT
                               histograms, slot occupancy, queue depth,
                               HTTP latencies; docs/observability.md)
  POST /generate            {"tokens": [...], "max_new_tokens": N}
                            -> {"tokens": [...], "ttft_ms": ..., ...}
  POST /generate + "stream": true
                            -> Transfer-Encoding: chunked, one JSON
                               line per emission ({"tokens": [...]}),
                               closing line {"done": true, "ttft_ms":.}
                               Tokens stream AS DECODED — TTFT is one
                               prefill away, not one full generation.

Reference parity: the reference's serving recipes wrap external engines
(reference: llm/vllm/serve.yaml, JetStream in examples/tpu/v6e) — this
is the in-tree TPU-native equivalent; streaming mirrors what the
JetStream benchmark measures (examples/tpu/v6e/README.md TTFT).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer
from socketserver import ThreadingMixIn
from typing import Dict, Optional

from skypilot_tpu import chaos
from skypilot_tpu.infer import qos as qos_lib
from skypilot_tpu.observability import flight as flight_lib
from skypilot_tpu.observability import health as health_lib
from skypilot_tpu.observability import attribution, metrics, tracing
from skypilot_tpu.utils import compile_cache, timeline

HTTP_SECONDS = metrics.histogram(
    "skytpu_http_request_seconds",
    "Model-server HTTP request latency (streaming requests span the "
    "full generation)", labelnames=("route",),
    buckets=metrics.latency_buckets())
HTTP_REQUESTS = metrics.counter(
    "skytpu_http_requests_total",
    "Model-server HTTP requests by route and status code",
    labelnames=("route", "code"))
INBOX_DEPTH = metrics.gauge(
    "skytpu_server_inbox_depth",
    "Requests accepted by handler threads, not yet drained into the "
    "engine (queue depth ahead of admission)")
PENDING_REQUESTS = metrics.gauge(
    "skytpu_server_pending_requests",
    "Requests in flight in the serving loop (drained, not finished)")
BURST_FLUSHES = metrics.counter(
    "skytpu_server_burst_flushes_total",
    "Async decode bursts landed (fetched + streamed) by the loop")
WAVE_FLUSH_SECONDS = metrics.histogram(
    "skytpu_server_wave_flush_seconds",
    "Post-admission-wave flush (stream first tokens + re-drain inbox)")
SERVER_DRAINING = metrics.gauge(
    "skytpu_server_draining",
    "1 while this replica is draining (POST /drain received: new "
    "admissions get a typed 503, in-flight requests finish, /healthz "
    "reports 'draining' so the LB and controller stop routing here)")


class _Pending:
    def __init__(self, req=None):
        self.event = threading.Event()
        self.result: Optional[Dict] = None
        self.enqueued_s = time.time()
        self.stream = False
        # Streaming: the engine loop pushes token batches as decoded
        # ({"tokens": [...]}); a {"done"/"error"} dict terminates.
        self.req = req            # engine Request (tokens grow in place)
        self.cursor = 0           # tokens already pushed to the stream
        self.chunks: queue.Queue = queue.Queue()
        # Disaggregated prefill tier: when set, the finished-request
        # pass attaches the retired request's stored-prefix export
        # (block contents + lengths) to the result for the /prefill
        # response — the payload the LB hands to a decode replica.
        self.export_prefix = False


class ModelServer:
    """Engine + request queue + batching loop.

    Ownership model: the step loop thread is the ONLY thread that
    touches the engine. Handler threads drop (tokens, pending) into an
    inbox under a tiny lock and wait on their pending's event/queue.
    (An earlier design guarded the engine with one big lock; the
    busy loop re-acquired it back-to-back and barge-starved admissions
    on a single core — concurrent TTFTs collapsed to full-batch wall.)
    """

    def __init__(self, engine, max_burst: int = 8,
                 open_burst: int = 4, coalesce_s: float = 0.012,
                 qos: Optional[qos_lib.AdmissionController] = None):
        self.engine = engine
        self.max_burst = max_burst
        # Multi-tenant QoS admission (docs/serving.md §Multi-tenant
        # QoS): handler threads run the token-bucket + overload check
        # BEFORE a request ever touches the inbox; None (the default)
        # is the zero-cost path.
        self.qos = qos
        # Admission coalescing: when the inbox yields less than a full
        # wave but a request arrived within the last ``coalesce_s``,
        # wait a beat (in 2 ms slices, re-draining) before dispatching.
        # Burst arrivals land over several ms — on a single-core host
        # the handler threads need the GIL the loop thread is holding —
        # and an eager dispatch sends the first arrival as a wave of
        # its own: measured 7 waves instead of 6 for a 24-request
        # burst at wave 4, one more read of an 8B model's weights per
        # run. The sleep slices also yield the GIL, which is exactly
        # what lets the stragglers enqueue.
        self.coalesce_s = coalesce_s
        # Burst size while a slot is free: a late HTTP arrival waits at
        # most the short burst in flight and the one queued behind it
        # before its prefill, instead of two max_burst decodes
        # (JetStream's prefill-over-generate priority; r3 driver bench
        # showed 5x TTFT variance from arrivals stranded behind full
        # bursts). Full bursts run only when every slot is busy —
        # admission is impossible then. The length turns on nothing but
        # slot state: it used to go long after a wall-clock second
        # without arrivals, to amortise a dispatch that the async pair
        # below now hides (the sync path, speculation, pays one fetch
        # a short burst), and an arrival after such a spell — most
        # arrivals of a lightly loaded replica — then waited out up to
        # two long bursts (535 ms each at a 16.6 ms step), by a phase
        # that a few milliseconds decided.
        self.open_burst = min(open_burst, max_burst)
        # Monotonic: an NTP step must not stretch or cut a coalescing
        # wait.
        self._last_arrival = 0.0     # guarded-by: _inbox_lock
        # Double-buffered decode (engines exposing the async pair):
        # burst k+1 is dispatched BEFORE burst k's tokens are fetched
        # and streamed, so the TPU decodes k+1 while this thread does
        # k's JSON framing + socket writes + LB hop. Fake/simple
        # engines without the pair fall back to sync decode_burst.
        # Speculative engines (spec_k > 0) also run the sync path:
        # verify FETCHES can't double-buffer — the next round's window
        # depends on the tokens this one commits — and decode_burst
        # itself routes to the verify program there. The overlap spec
        # mode used to forfeit now lives INSIDE the round: with a
        # model drafter + spec_pipeline, the next round's draft
        # rollout dispatches while the verify is in flight
        # (engine.spec_decode_burst), so the draft model's work rides
        # the verify wall instead of serializing after it.
        self._burst = None
        self._async_decode = (hasattr(engine, "dispatch_decode_burst")
                              and not getattr(engine, "spec_k", 0))
        # The real engine's decode entry points take ``why`` (the burst
        # policy's reason, for its dispatch annotation); simple doubles
        # exposing only decode_burst(max_burst) keep working.
        self._takes_why = hasattr(engine, "dispatch_decode_burst")
        # Component health detail behind GET /healthz: "" while
        # serving; a reason string while warming or after a failed
        # engine reset (the two _ready-unset states a probe must tell
        # apart — one recovers by waiting, one needs replacement).
        self.health_reason = "warming"
        self._inbox_lock = threading.Lock()
        self._inbox: list = []        # guarded-by: _inbox_lock
        self._pending: Dict[int, _Pending] = {}   # loop-thread only
        self._ready = threading.Event()
        self._stop = threading.Event()
        # Graceful drain (docs/robustness.md §Replica loss & rolling
        # update): once draining, new admissions get a typed 503 and
        # in-flight requests run to completion; past the deadline the
        # replica self-reports DEGRADED so `skytpu status --health`
        # exits 2. Flags are written by handler threads and read
        # everywhere — benign un-locked reads, same as queue_depth().
        self._draining = False
        self._drain_deadline_s = 0.0
        # Engine crash-recovery storm guard: recover at most
        # ``_storm_limit`` times per ``_storm_window_s`` rolling
        # window, then fall back to fail-all + reset (a device that
        # keeps crashing needs replacement, not an infinite
        # recover/crash loop that never fails a request visibly).
        self._storm_limit = int(os.environ.get(
            "SKYTPU_RECOVERY_STORM_LIMIT", "3"))
        self._storm_window_s = float(os.environ.get(
            "SKYTPU_RECOVERY_STORM_WINDOW_S", "30"))
        self._recover_times: list = []    # loop-thread only
        # Off-thread event-log heartbeat: engine spans become durable
        # (visible to a separate-process `skytpu trace`) within ~5s of
        # recording, and the O(ring) flush serialization never runs on
        # the serving loop between decode waves. The flight recorder
        # gets the same durability heartbeat (visible to a separate-
        # process `skytpu flight --local`).
        tracing.ensure_flush_thread()
        flight_lib.ensure_flush_thread()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def queue_depth(self) -> int:
        """Inbox + in-flight requests — the overload-shed input.
        Benign racy len() reads from handler threads: a threshold
        check needs no exactness, and taking the loop's locks here
        would serialize admission behind decode."""
        return len(self._inbox) + len(self._pending)

    # -- graceful drain ----------------------------------------------------

    def start_drain(self, grace_s: float = 30.0) -> Dict:
        """Enter (or re-poll) the draining state: idempotent — the
        first call stamps the deadline, repeats just report progress,
        so the controller polls `POST /drain` until ``drained``."""
        if not self._draining:
            self._draining = True
            self._drain_deadline_s = time.time() + max(grace_s, 0.0)
            SERVER_DRAINING.set(1)
            tracing.add_event(
                "server.draining",
                {"in_flight": self.queue_depth(),
                 "grace_s": grace_s}, echo=True)
        return self.drain_status()

    def draining(self) -> bool:
        return self._draining

    def drain_status(self) -> Dict:
        depth = self.queue_depth()
        return {
            "draining": self._draining,
            "in_flight": depth,
            "drained": self._draining and depth == 0,
            "deadline_s": round(self._drain_deadline_s, 3),
        }

    def _add(self, tokens, max_new_tokens: int,
             stream: bool = False, trace_ctx=None,
             tenant: str = qos_lib.DEFAULT_TENANT,
             priority: int = 0,
             adapter: Optional[str] = None,
             export_prefix: bool = False,
             handoff: Optional[Dict] = None) -> _Pending:
        from skypilot_tpu.infer import engine as eng
        # Validate eagerly (oversized prompt / unsatisfiable KV quota /
        # unknown adapter -> clean 400/404) without touching the
        # engine's mutable state from this thread — an exception
        # raised later on the loop thread could reach no client.
        eng._bucket(len(tokens), self.engine.buckets)
        check = getattr(self.engine, "check_kv_quota", None)
        if check is not None:
            check(tenant, len(tokens), max_new_tokens)
        if adapter is not None:
            check_ad = getattr(self.engine, "check_adapter", None)
            if check_ad is not None:
                check_ad(adapter)
        p = _Pending()
        p.stream = stream
        p.export_prefix = export_prefix
        with self._inbox_lock:
            # The caller's trace context rides the inbox tuple: the
            # loop thread (which has no ambient context) hands it to
            # add_request so the engine's per-request spans join the
            # HTTP caller's trace.
            self._inbox.append((list(tokens), max_new_tokens, p,
                                trace_ctx, tenant, priority, adapter,
                                handoff))
            self._last_arrival = time.monotonic()
            INBOX_DEPTH.set(len(self._inbox))
        return p

    def submit(self, tokens, max_new_tokens: int, trace_ctx=None,
               tenant: str = qos_lib.DEFAULT_TENANT,
               priority: int = 0, adapter: Optional[str] = None,
               export_prefix: bool = False,
               handoff: Optional[Dict] = None) -> Dict:
        p = self._add(tokens, max_new_tokens, trace_ctx=trace_ctx,
                      tenant=tenant, priority=priority, adapter=adapter,
                      export_prefix=export_prefix, handoff=handoff)
        t0 = time.time()
        p.event.wait()
        out = dict(p.result or {})
        out["total_ms"] = round((time.time() - t0) * 1e3, 2)
        return out

    def submit_stream(self, tokens, max_new_tokens: int, trace_ctx=None,
                      tenant: str = qos_lib.DEFAULT_TENANT,
                      priority: int = 0, adapter: Optional[str] = None,
                      handoff: Optional[Dict] = None):
        """Iterator of chunk dicts: {"tokens": [...]} as decoded, then
        one {"done": true, "ttft_ms": ...} (or {"error": ...}).

        Admission validation happens EAGERLY (before any bytes are
        written), so an oversized prompt — or an unknown adapter
        name — raises here as a clean 400/404, not mid-stream after a
        200 went out.
        """
        p = self._add(tokens, max_new_tokens, stream=True,
                      trace_ctx=trace_ctx, tenant=tenant,
                      priority=priority, adapter=adapter,
                      handoff=handoff)

        def gen():
            while True:
                chunk = p.chunks.get()
                yield chunk
                if "done" in chunk or "error" in chunk:
                    return

        return gen()

    def _loop(self) -> None:
        # Warm the compile path before /health flips: the load balancer
        # must not route traffic into a cold XLA compile. The warmup
        # runs the fully instrumented path and the compile dominates
        # it — observed, that one sample would skew the serving
        # histograms' (TTFT/prefill/decode-step) sums and means for the
        # life of the process, so it records nothing (the trainer skips
        # its compile step for the same reason).
        try:
            with metrics.suppress():
                self.engine.generate([[1]], max_new_tokens=2)
            self.engine.finished.clear()
        except Exception as e:  # noqa: BLE001
            tracing.add_event("server.warmup_failed",
                              {"error": str(e)}, echo=True)
        self.health_reason = ""
        self._ready.set()
        while not self._stop.is_set():
            try:
                busy = self._step()
            except Exception as e:  # noqa: BLE001 — fail the in-flight
                # requests loudly; never let the serving thread die
                # while /health reports ok.
                self._burst = None   # poisoned in-flight burst, if any
                # Crash RECOVERY first (docs/robustness.md): a typed
                # recoverable dispatch failure resets the engine and
                # re-queues every in-flight request through the
                # preemption resume path — the _pending entries (and
                # their Request objects) survive, so open streams
                # continue gapless and greedy output stays
                # bit-identical. The storm guard keeps a persistently
                # dying device from recover-looping forever.
                if self._try_recover(e):
                    continue
                # Unrecoverable (or storming): fail the in-flight
                # requests. The engine's waiting/slot_req still hold
                # the poisoned requests — left in place, every
                # subsequent step would re-drive them and fail all
                # future traffic with the same error (advisor r3).
                # Reset the slot state; if even that fails the device
                # is gone: flip /health to 503 so the LB stops routing
                # here. Health flips BEFORE the pending events fire: a
                # client reacting to its failed request must not race
                # a still-green /health.
                try:
                    self.engine.reset()
                except Exception as e2:  # noqa: BLE001
                    tracing.add_event("server.engine_reset_failed",
                                      {"error": str(e2)}, echo=True)
                    self.health_reason = "engine reset failed"
                    self._ready.clear()
                for p in self._pending.values():
                    p.result = {"error": f"engine failure: {e}"}
                    if p.stream:
                        p.chunks.put({"error": p.result["error"]})
                    p.event.set()
                self._pending.clear()
                # The gauge tracks _pending; left stale it would report
                # the pre-failure in-flight count for the whole outage
                # window — exactly when an operator reads it.
                PENDING_REQUESTS.set(0)
                busy = False
            if not busy:
                # One phase per idle STRETCH (capped, so that a trace
                # started mid-stretch soon sees one), not per 2 ms poll:
                # an idle server must not flood a trace or the Chrome
                # buffer with ticks.
                with timeline.phase("server.idle"):
                    until = time.monotonic() + 0.25
                    while True:
                        time.sleep(0.002)
                        if (self._has_work() or self._stop.is_set()
                                or time.monotonic() >= until):
                            break

    def _try_recover(self, e: BaseException) -> bool:
        """Attempt engine crash recovery for a typed recoverable
        dispatch failure. Loop-thread only. Returns True when the
        engine reset and re-queued its in-flight requests (the step
        loop just continues); False routes to the fail-all path."""
        if not (getattr(e, "recoverable", False)
                and hasattr(self.engine, "recover")):
            return False
        now = time.monotonic()
        self._recover_times = [
            t for t in self._recover_times
            if now - t < self._storm_window_s]
        if len(self._recover_times) >= self._storm_limit:
            tracing.add_event(
                "server.recovery_storm",
                {"recoveries": len(self._recover_times),
                 "window_s": self._storm_window_s,
                 "error": str(e)}, echo=True)
            return False
        self._recover_times.append(now)
        try:
            n = self.engine.recover(e)
        except Exception as e2:  # noqa: BLE001 — reset itself failed;
            # the fail-all path will retry it and flip health.
            tracing.add_event("server.engine_recover_failed",
                              {"error": str(e2)}, echo=True)
            return False
        tracing.add_event(
            "server.engine_recovered",
            {"seam": getattr(e, "seam", None), "victims": n,
             "error": str(e)}, echo=True)
        return True

    def _drain_inbox(self) -> None:
        with self._inbox_lock:
            new, self._inbox = self._inbox, []
            INBOX_DEPTH.set(0)
        if new:
            with timeline.phase("server.inbox", n=len(new)):
                self._enqueue(new)
            PENDING_REQUESTS.set(len(self._pending))

    def _enqueue(self, new: list) -> None:
        """Hand drained inbox entries to the engine (loop thread)."""
        for tokens, max_new, p, trace_ctx, tenant, priority, adapter, \
                handoff in new:
            # Optional kwargs only when they carry signal: simple
            # engine doubles (and older engines) without the kwargs
            # keep working.
            kwargs = {}
            if trace_ctx is not None:
                kwargs["trace_ctx"] = trace_ctx
            if tenant != qos_lib.DEFAULT_TENANT:
                kwargs["tenant"] = tenant
            if priority:
                kwargs["priority"] = priority
            if adapter is not None:
                kwargs["adapter"] = adapter
            if handoff is not None:
                # Disaggregated decode tier: install the prefill
                # tier's exported KV blocks into this engine's prefix
                # cache (loop thread — the only engine toucher), then
                # admit prompt + committed through the ordinary
                # preemption-resume path. A failed/skipped import
                # (dry pool, geometry mismatch) is a COLD resume, not
                # an error: the output is bit-identical either way.
                committed = list(handoff.get("committed") or [])
                export = handoff.get("export")
                imp = getattr(self.engine, "import_prefix", None)
                if export is not None and imp is not None:
                    try:
                        imp(list(tokens) + committed, export,
                            salt=export.get("salt", b""))
                    except Exception as e:  # noqa: BLE001 — cold
                        # resume; the request must still run.
                        tracing.add_event(
                            "server.handoff_import_failed",
                            {"error": str(e)}, echo=True)
                kwargs["committed"] = committed
            rid = self.engine.add_request(tokens, max_new, **kwargs)
            # add_request appends to engine.waiting; keep the Request so
            # emitted tokens can be diffed without a rid->req search.
            p.req = self.engine.waiting[-1]
            assert p.req.rid == rid
            # TTFT counts from when the handler enqueued the request,
            # not when the loop got around to admitting it.
            p.req.submit_s = p.enqueued_s
            self._pending[rid] = p

    def _flush_streams(self) -> None:
        """Push newly decoded tokens to every pending stream. Works for
        admission-time first tokens and burst tokens alike — it diffs
        req.tokens against the cursor. Blocking requests skip the chunk
        queue entirely (nobody drains it)."""
        with timeline.phase("server.streams",
                            n=len(self._pending)) as ph:
            pushed = 0
            for p in self._pending.values():
                if p.req is None or not p.stream:
                    continue
                new = p.req.tokens[p.cursor:]
                if new:
                    p.cursor += len(new)
                    p.chunks.put({"tokens": list(new)})
                    pushed += len(new)
            ph.set(tokens=pushed)

    @timeline.event(name="skytpu_server_wave_flush_seconds",
                    histogram=WAVE_FLUSH_SECONDS)
    def _on_wave(self) -> None:
        # After each admission wave: stream its first tokens, then pull
        # any requests that arrived DURING the wave's prefill into this
        # same admission pass (engine._admit keeps looping while
        # waiting+free slots exist) — they'd otherwise sit through a
        # decode burst first.
        self._flush_streams()
        self._drain_inbox()

    def _complete_burst(self) -> None:
        """Land the outstanding async burst: fetch its tokens (host
        sync), run retire bookkeeping, stream what it decoded."""
        if self._burst is not None:
            handle, self._burst = self._burst, None
            self.engine.complete_decode_burst(handle)
            BURST_FLUSHES.inc()
            self._flush_streams()

    def _has_work(self) -> bool:
        """Anything for :meth:`_step` to do? (The racy inbox read is
        benign, as in :meth:`queue_depth`: a miss costs one 2 ms poll.)"""
        eng = self.engine
        return bool(self._inbox or eng.waiting or eng.slot_req
                    or getattr(eng, "chunking", None)
                    or self._burst is not None)

    def _step(self) -> bool:
        self._drain_inbox()
        eng = self.engine
        chunking = getattr(eng, "chunking", None)
        if not self._has_work():
            return False
        # Coalesce a filling wave: more arrivals are in flight when the
        # last one is only milliseconds old. Never waits when the wave
        # is already full, slots are exhausted, or the last arrival is
        # older than coalesce_s — and the wait is bounded by one
        # coalesce_s total.
        if eng.waiting and eng.free_slots:
            target = min(getattr(eng, "max_wave", None)
                         or len(eng.free_slots),
                         len(eng.free_slots))
            deadline = time.monotonic() + self.coalesce_s
            with timeline.phase("server.coalesce",
                                waiting=len(eng.waiting)):
                while (len(eng.waiting) < target
                       and time.monotonic() < deadline
                       and time.monotonic() - self._last_arrival
                           < self.coalesce_s):
                    time.sleep(0.002)
                    self._drain_inbox()
        # Admission has strict priority over decode — but it needs
        # accurate slot state, so the outstanding burst lands first
        # (retirements there may free the very slots admission wants).
        if eng.waiting:
            self._complete_burst()
            admit = bool(eng.free_slots)
            if (not admit and eng.slot_req
                    and getattr(eng, "qos", None) is not None):
                # Saturated replica: admission is the only path into
                # the engine's priority-preemption pass, so it must
                # still run when a queued request outranks a resident —
                # otherwise the priority lanes are dead exactly when
                # every slot is held, the one situation they exist for.
                floor = min(r.priority for r in eng.slot_req.values())
                admit = any(w.priority > floor for w in eng.waiting)
            if eng.waiting and admit:
                eng.admit(on_wave=self._on_wave)
                self._flush_streams()
        if chunking:
            # Interference scheduler: dispatch ONE prefill chunk, then
            # fall through to dispatch the next decode burst and only
            # THEN land the outstanding one — chunk -> decode
            # alternation, so a long prompt's prefill never stalls
            # decode slots for more than one chunk, in the dispatch-
            # then-land order the loop runs when nothing chunks: the
            # device holds a chunk and a burst queued while this thread
            # streams. The engine awaits only a prompt's final chunk
            # (its token is the request's first); the burst behind a
            # non-final chunk lands it.
            eng.prefill_chunk_step()
            self._flush_streams()   # final chunk emits a first token
        if eng.slot_req:
            # While a chunked prefill is in flight, bursts stay short
            # regardless of slot pressure: the alternation granularity
            # IS the chunked-prefill TTFT bound. ``chunking`` is the
            # engine's live deque — its truthiness reflects claims made
            # by the admit call above.
            full = not eng.free_slots and not chunking
            k = self.max_burst if full else self.open_burst
            why = {}
            if self._takes_why:
                # Why this burst has the length it has: it rides the
                # engine's dispatch annotation.
                why["why"] = ("chunking" if chunking
                              else "full" if full else "open")
            if self._async_decode:
                # Dispatch the NEXT burst before fetching the previous
                # one: the device decodes while this thread streams.
                nxt = eng.dispatch_decode_burst(max_burst=k, **why)
                self._complete_burst()
                self._burst = nxt
            else:
                eng.decode_burst(max_burst=k, **why)
                self._flush_streams()
        else:
            self._complete_burst()
        if self.engine.finished:
            with timeline.phase("server.results",
                                n=len(self.engine.finished)):
                self._deliver_finished()
            PENDING_REQUESTS.set(len(self._pending))
        self.engine.finished.clear()
        return True

    def _deliver_finished(self) -> None:
        """Hand every finished request's result to its waiter."""
        for req in self.engine.finished:
            p = self._pending.pop(req.rid, None)
            if p is None:
                continue
            err = getattr(req, "error", None)
            if err is not None:
                # Typed per-request failure (adapter load failed): the
                # body rides verbatim with the error's HTTP status —
                # the engine never substituted base-model output.
                err = dict(err)
                status = err.pop("http_status", 500)
                p.result = {"error": err, "http_status": status}
                if p.stream:
                    p.chunks.put({"error": err})
                p.event.set()
                continue
            ttft = ((req.first_token_s - req.submit_s) * 1e3
                    if req.first_token_s is not None else None)
            ttft = round(ttft, 2) if ttft is not None else None
            cached = getattr(req, "cached_len", 0)
            p.result = {
                "tokens": req.tokens,
                "ttft_ms": ttft,
                # Per-request prefix-cache stats (the response
                # trailer): how much prefill this request skipped.
                "cache_hit": bool(cached),
                "cached_tokens": cached,
                "prefill_chunks": getattr(req, "n_chunks", 0),
                # Speculative-decode stats: how much of the decode this
                # request's drafts covered (accepted / drafted), and
                # which drafter rung served it last (model|ngram|off —
                # the acceptance-collapse ladder's resting place).
                "spec_drafted": getattr(req, "spec_drafted", 0),
                "spec_accepted": getattr(req, "spec_accepted", 0),
                "drafter": getattr(req, "spec_mode", None) or "off",
                # QoS: how often this request was preempted-by-
                # eviction and resumed (0 on the single-tenant path).
                "preemptions": getattr(req, "preemptions", 0),
                # Fault tolerance: engine crash recoveries this
                # request rode through (re-admitted via the same
                # resume path, output bit-identical).
                "recoveries": getattr(req, "recoveries", 0),
                # Adapter catalog: which fine-tune generated this
                # (None = the base model).
                "model": getattr(req, "adapter", None),
            }
            if p.export_prefix:
                # Disaggregated prefill tier: snapshot the stored
                # prefix's blocks for the /prefill response. Runs on
                # the loop thread (one fixed-shape gather + host
                # fetch); the entry stays a ref-counted LRU resident
                # here, so a lost handoff leaks nothing. None when no
                # prefix is resident (evicted under pool pressure
                # between store and retire) — the LB falls back to
                # single-tier.
                exp_fn = getattr(self.engine, "export_prefix_for",
                                 None)
                p.result["export"] = (exp_fn(req)
                                      if exp_fn is not None else None)
            if p.stream:
                p.chunks.put({"done": True, "ttft_ms": ttft,
                              "n_tokens": len(req.tokens),
                              "cache_hit": bool(cached),
                              "cached_tokens": cached,
                              "spec_drafted":
                                  getattr(req, "spec_drafted", 0),
                              "spec_accepted":
                                  getattr(req, "spec_accepted", 0),
                              "drafter":
                                  getattr(req, "spec_mode", None)
                                  or "off",
                              "preemptions":
                                  getattr(req, "preemptions", 0),
                              "recoveries":
                                  getattr(req, "recoveries", 0)})
            p.event.set()

    def shutdown(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


class _Threading(ThreadingMixIn, HTTPServer):
    daemon_threads = True
    # A burst of concurrent clients (the LB fan-in) overflows the
    # default listen backlog of 5 -> connection resets under load.
    request_queue_size = 128


_KNOWN_ROUTES = frozenset({"/health", "/healthz", "/metrics",
                           "/generate", "/prefill", "/handoff",
                           "/drain", "/debug/flight",
                           "/debug/forensics"})


def encode_export(export: Dict) -> Dict:
    """JSON-safe wire form of an engine prefix export (the /prefill
    response body's ``export`` field): block tensors as base64 raw
    bytes + shape/dtype, the adapter salt as base64. bfloat16 scale
    planes widen to float32 on the wire (exact, and the receiver's
    scatter casts back), so every wire dtype is plain numpy."""
    import base64

    import numpy as np
    tensors = {}
    for name, arr in export["tensors"].items():
        arr = np.ascontiguousarray(arr)
        if str(arr.dtype) == "bfloat16":
            arr = arr.astype(np.float32)
        tensors[name] = {
            "shape": list(arr.shape), "dtype": str(arr.dtype),
            "data": base64.b64encode(arr.tobytes()).decode()}
    return {"cached_len": int(export["cached_len"]),
            "kv_block": int(export["kv_block"]),
            "n_blocks": int(export["n_blocks"]),
            "salt": base64.b64encode(export.get("salt")
                                     or b"").decode(),
            "tensors": tensors}


def decode_export(wire: Dict) -> Dict:
    """Inverse of :func:`encode_export` — the dict
    ``InferenceEngine.import_prefix`` consumes. Raises ValueError /
    KeyError / TypeError on malformed wire payloads (the /handoff
    handler maps those to a 400)."""
    import base64

    import numpy as np
    tensors = {}
    for name, spec in wire["tensors"].items():
        arr = np.frombuffer(
            base64.b64decode(spec["data"]),
            dtype=np.dtype(str(spec["dtype"]))).reshape(
                [int(d) for d in spec["shape"]])
        tensors[str(name)] = arr
    return {"cached_len": int(wire["cached_len"]),
            "kv_block": int(wire["kv_block"]),
            "n_blocks": int(wire["n_blocks"]),
            "salt": base64.b64decode(wire.get("salt") or ""),
            "tensors": tensors}


def make_handler(model: ModelServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _observe(self, code: int) -> None:
            route = self.path.split("?", 1)[0]
            if route not in _KNOWN_ROUTES:
                # Label children are never evicted; arbitrary scanner
                # paths must not mint unbounded series.
                route = "other"
            HTTP_REQUESTS.labels(route=route, code=str(code)).inc()
            t0 = getattr(self, "_t0", None)
            if t0 is not None:
                HTTP_SECONDS.labels(route=route).observe(
                    time.monotonic() - t0)
                self._t0 = None

        def _json(self, code, obj, headers=None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)
            self._observe(code)

        def do_GET(self):
            self._t0 = time.monotonic()
            if self.path == "/health":
                if model._draining:
                    # 503 stops the LB/controller routing here — the
                    # point of the drain; in-flight work continues.
                    return self._json(503, {"status": "draining"},
                                      headers={"Retry-After": "1"})
                if model._ready.is_set():
                    return self._json(
                        200, {"status": "ok",
                              "device": attribution.device_report()})
                return self._json(503, {"status": "warming"})
            if self.path == "/healthz":
                # The fleet health model's shape: always 200 (the
                # probe succeeded), status carries the verdict.
                if model._draining:
                    depth = model.queue_depth()
                    past = (depth > 0
                            and time.time() > model._drain_deadline_s)
                    health_lib.write_healthz(
                        self,
                        health_lib.DEGRADED if past
                        else health_lib.DRAINING,
                        reason=(f"draining past deadline "
                                f"({depth} in flight)" if past
                                else f"draining ({depth} in flight)"))
                    return self._observe(200)
                ready = model._ready.is_set()
                health_lib.write_healthz(
                    self,
                    health_lib.HEALTHY if ready else health_lib.DEGRADED,
                    reason=model.health_reason)
                return self._observe(200)
            if self.path == "/metrics":
                metrics.write_exposition(self)
                return self._observe(200)
            if self.path.split("?", 1)[0] == "/debug/flight":
                # Burst-level introspection: the engine's in-process
                # flight ring + compile-watch registry (no flush
                # needed — this reads live state). ?n= caps the
                # record tail (default 128).
                n = 128
                since = None
                if "?" in self.path:
                    from urllib.parse import parse_qs
                    qs = parse_qs(self.path.split("?", 1)[1])
                    try:
                        n = max(int(qs.get("n", ["128"])[0]), 1)
                    except ValueError:
                        pass
                    try:
                        if "since" in qs:
                            since = int(qs["since"][0])
                    except ValueError:
                        pass
                eng = model.engine
                fl = getattr(eng, "flight", None)
                watch = getattr(eng, "compile_watch", None)
                # Device-truth attribution (PR 16): the calibrated
                # per-program device-time EWMAs and the HBM ledger
                # ride the same live-state read — skytpu flight
                # renders host-vs-device and headroom without a
                # second endpoint.
                devtime = getattr(eng, "devtime", None)
                ledger = getattr(eng, "hbm_ledger", None)
                # ?since=<seq> is the incremental cursor: only records
                # the recorder stamped AFTER that sequence number come
                # back (``skytpu flight --follow`` tails the ring by
                # re-sending the returned "seq" instead of refetching
                # 8192 records per poll).
                if fl is None:
                    records: list = []
                elif since is not None:
                    records = fl.since(since)
                else:
                    records = fl.tail(n)
                return self._json(200, {
                    "records": records,
                    "seq": fl.seq() if fl is not None else 0,
                    "enabled": bool(fl is not None and fl.enabled),
                    "programs": (watch.summary()
                                 if watch is not None else {}),
                    "warm": bool(watch is not None and watch.warm),
                    "unexpected": (watch.unexpected
                                   if watch is not None else []),
                    "devtime": (devtime.summary()
                                if devtime is not None else {}),
                    "hbm": (ledger.snapshot()
                            if ledger is not None else {}),
                })
            if self.path.split("?", 1)[0] == "/debug/forensics":
                # Request forensics: bare — the engine's streaming
                # tail estimates + pinned-exemplar summaries;
                # ?rid=<id> — that request's critical-path ledger
                # assembled from the live flight ring (falling back
                # to a pinned exemplar once the ring rolled over),
                # what `skytpu why <rid>` renders.
                rid = None
                if "?" in self.path:
                    from urllib.parse import parse_qs
                    qs = parse_qs(self.path.split("?", 1)[1])
                    try:
                        if "rid" in qs:
                            rid = int(qs["rid"][0])
                    except ValueError:
                        return self._json(400, {"error": "bad rid"})
                eng = model.engine
                fl = getattr(eng, "flight", None)
                tail = getattr(eng, "tail", None)
                store = getattr(eng, "exemplars", None)
                if rid is None:
                    return self._json(200, {
                        "enabled": bool(getattr(eng, "forensics",
                                                False)),
                        "tail": (tail.snapshot()
                                 if tail is not None else {}),
                        "exemplars": (store.list()
                                      if store is not None else []),
                    })
                from skypilot_tpu.observability import (
                    forensics as forensics_lib)
                recs = fl.tail() if fl is not None else []
                ledger = forensics_lib.ledger_from_records(rid, recs)
                records = forensics_lib.records_for(rid, recs)
                exemplar = (store.get(rid)
                            if store is not None else None)
                if ledger is None and exemplar is not None:
                    # Ring rolled over; the pinned evidence is the
                    # whole point of the exemplar store.
                    ledger = exemplar.get("ledger")
                    records = exemplar.get("records") or []
                if ledger is None:
                    return self._json(404, {
                        "error": f"no retired request {rid} in the "
                                 f"flight ring or exemplar store"})
                return self._json(200, {
                    "rid": rid, "ledger": ledger,
                    "records": records,
                    "exemplar": exemplar is not None,
                })
            return self._json(404, {"error": "not found"})

        def _stream(self, chunks):
            """Chunked NDJSON: tokens flow as the engine decodes them."""
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def write_chunk(data: bytes) -> None:
                # ONE write per chunk: the handler's wfile is unbuffered
                # (http.server wbufsize=0), so separate size/data/CRLF
                # writes would be three syscalls — and three chances for
                # the kernel to emit small segments — per streamed token
                # batch.
                self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))

            code = 200
            try:
                for chunk in chunks:
                    # Chaos: a replica.kill fault here drops the
                    # connection mid-stream with NO terminal chunk —
                    # to the LB this replica just got SIGKILLed, which
                    # is exactly what the mid-stream failover path
                    # must recover from.
                    chaos.point("replica.kill", route="/generate")
                    write_chunk(json.dumps(chunk).encode() + b"\n")
            except chaos.ChaosError:
                code = 500
                self.close_connection = True
                return
            except ConnectionError:
                # Client went away mid-stream (broken pipe OR a reset —
                # flaky LBs produce both): count it as 499 (client
                # closed request), not a success.
                code = 499
                return
            finally:
                self._observe(code)
            try:
                self.wfile.write(b"0\r\n\r\n")
            except ConnectionError:
                pass

        def do_POST(self):
            self._t0 = time.monotonic()
            # Chunked request bodies have no Content-Length; reading
            # them is unimplemented, and NOT reading them would leave
            # unread bytes on a keep-alive socket — the next request
            # on the connection would parse the stale body as its
            # request line. 411 + close is the honest answer.
            if "chunked" in (self.headers.get("Transfer-Encoding")
                             or "").lower():
                self.close_connection = True
                return self._json(411, {"error": {
                    "type": "length_required",
                    "message": "chunked request bodies are not "
                               "supported; send Content-Length"}})
            if self.path == "/drain":
                length = int(self.headers.get("Content-Length") or 0)
                try:
                    body = json.loads(self.rfile.read(length)
                                      or b"{}")
                    grace = float(body.get("grace_s", 30.0))
                except (ValueError, TypeError, AttributeError):
                    return self._json(
                        400, {"error": "bad drain request"})
                return self._json(200, model.start_drain(grace))
            if self.path not in ("/generate", "/prefill", "/handoff"):
                return self._json(404, {"error": "not found"})
            if model._draining:
                # Typed drain shed: the LB treats the 503 as a
                # connection-level failure and retries the request on
                # a surviving replica; direct clients back off per
                # Retry-After. Consume the body first — an unread
                # body on a keep-alive socket corrupts the NEXT
                # request on the connection.
                self.rfile.read(
                    int(self.headers.get("Content-Length") or 0))
                return self._json(
                    503,
                    {"error": {
                        "type": "draining",
                        "message": "replica is draining; retry "
                                   "against another replica"}},
                    headers={"Retry-After": "1"})
            length = int(self.headers.get("Content-Length") or 0)
            try:
                body = json.loads(self.rfile.read(length) or b"{}")
                tokens = [int(t) for t in body["tokens"]]
                max_new = int(body.get("max_new_tokens", 64))
                stream = bool(body.get("stream", False))
                # Adapter catalog: the fine-tune this request targets.
                # HEADER FIRST, body ``model`` (the SDK path) as the
                # fallback — the LB resolves in exactly this order
                # (it never parses the body when the header is
                # present), and the two tiers must agree or a request
                # carrying both would route/validate under one
                # adapter and be served under another. None/"" = the
                # base model.
                from skypilot_tpu.infer import adapters as ad_lib
                model_name = (self.headers.get(ad_lib.MODEL_HEADER)
                              or body.get("model"))
                # `or None` AFTER the strip: a whitespace-only header
                # must read as the base model at BOTH tiers (the LB
                # normalizes the same way) — not 404 here while the
                # LB routed it as base.
                model_name = (str(model_name).strip()[:128] or None
                              if model_name else None)
            except (ValueError, TypeError, KeyError) as e:
                return self._json(400, {"error": f"bad request: {e}"})
            trace_ctx = tracing.parse_traceparent(
                self.headers.get("traceparent"))
            # Multi-tenant QoS: identity from header/body, then the
            # token-bucket + overload check BEFORE any engine state is
            # touched. A shed is a typed client signal (429
            # rate_limited / 503 overloaded with Retry-After), never
            # a 500 — the LB runs the same check one hop earlier.
            tenant, priority = qos_lib.request_identity(
                self.headers, body,
                cfg=model.qos.cfg if model.qos is not None else None)
            if model.qos is not None:
                try:
                    model.qos.admit(tenant, depth=model.queue_depth())
                except qos_lib.ShedError as e:
                    return self._json(
                        e.http_status, {"error": e.typed_error},
                        headers={"Retry-After": e.retry_after_header()})
            # Client errors carry a typed body when the engine minted
            # one (PromptTooLongError.typed_error — a prompt past the
            # largest bucket is the caller's fault, never a 500; an
            # unknown adapter name rides its 404 the same way).
            def _bad_request(e):
                return self._json(
                    getattr(e, "http_status", 400),
                    {"error": getattr(e, "typed_error", None) or str(e)})

            if self.path == "/prefill":
                # Disaggregated prefill tier (docs/serving.md
                # §Disaggregated serving): run chunked admission to
                # completion (ONE committed token), export the stored
                # prefix's blocks, and return both — the LB hands them
                # to a decode replica. Blocking JSON only; the decode
                # tier owns streaming. An ineligible request (or a
                # prefix evicted under pool pressure before export) is
                # a typed 409 the LB answers by falling back to
                # ordinary single-tier routing — never an error the
                # client sees.
                elig = getattr(model.engine, "handoff_eligible", None)
                if elig is None or not elig(tokens, max_new):
                    return self._json(409, {"error": {
                        "type": "handoff_ineligible",
                        "message": "request cannot hand off (prompt "
                                   "shorter than one prefill chunk, "
                                   "single-token budget, or prefix "
                                   "cache off); route single-tier"}})
                try:
                    out = model.submit(tokens, 1, trace_ctx=trace_ctx,
                                       tenant=tenant,
                                       priority=priority,
                                       adapter=model_name,
                                       export_prefix=True)
                except ValueError as e:
                    return _bad_request(e)
                if "error" in out:
                    return self._json(out.pop("http_status", 500), out)
                export = out.pop("export", None)
                if export is None:
                    return self._json(409, {"error": {
                        "type": "handoff_ineligible",
                        "message": "prefix evicted before export "
                                   "(pool pressure); route "
                                   "single-tier"}})
                out["committed"] = out.pop("tokens")
                out["export"] = encode_export(export)
                return self._json(200, out)

            if self.path == "/handoff":
                # Disaggregated decode tier: import the prefill tier's
                # exported blocks, then resume prompt + committed
                # through the ordinary prefix-resume path — a
                # preemption with a network hop. The committed tokens
                # stream immediately (cursor starts at 0), so the
                # client's TTFT is the prefill tier's.
                try:
                    committed = [int(t) for t in
                                 body.get("committed") or []]
                    export = (decode_export(body["export"])
                              if body.get("export") else None)
                except (ValueError, TypeError, KeyError) as e:
                    return self._json(
                        400, {"error": f"bad handoff: {e}"})
                handoff = {"committed": committed, "export": export}
                if stream:
                    try:
                        chunks = model.submit_stream(
                            tokens, max_new, trace_ctx=trace_ctx,
                            tenant=tenant, priority=priority,
                            adapter=model_name, handoff=handoff)
                    except ValueError as e:
                        return _bad_request(e)
                    return self._stream(chunks)
                try:
                    out = model.submit(tokens, max_new,
                                       trace_ctx=trace_ctx,
                                       tenant=tenant,
                                       priority=priority,
                                       adapter=model_name,
                                       handoff=handoff)
                except ValueError as e:
                    return _bad_request(e)
                if "error" in out:
                    return self._json(out.pop("http_status", 500), out)
                return self._json(200, out)

            if stream:
                try:
                    chunks = model.submit_stream(tokens, max_new,
                                                 trace_ctx=trace_ctx,
                                                 tenant=tenant,
                                                 priority=priority,
                                                 adapter=model_name)
                except ValueError as e:  # oversized prompt, 404 etc.
                    return _bad_request(e)
                return self._stream(chunks)
            try:
                out = model.submit(tokens, max_new, trace_ctx=trace_ctx,
                                   tenant=tenant, priority=priority,
                                   adapter=model_name)
            except ValueError as e:      # oversized prompt, 404 etc.
                return _bad_request(e)
            if "error" in out:
                return self._json(out.pop("http_status", 500), out)
            return self._json(200, out)

        def log_message(self, *a):
            pass

    return Handler


def serve(engine, host: str = "0.0.0.0", port: int = 8080,
          max_burst: int = 8, open_burst: int = 4,
          coalesce_s: float = 0.012,
          qos: Optional[qos_lib.AdmissionController] = None):
    model = ModelServer(engine, max_burst=max_burst,
                        open_burst=open_burst,
                        coalesce_s=coalesce_s, qos=qos)
    httpd = _Threading((host, port), make_handler(model))
    return model, httpd


def _main() -> None:
    # Start-up goes on the record phase by phase (flight.STARTUP): the
    # first phase opened stamps ``before_main`` — process start to here —
    # and installs the compile ledger; ``server.listening`` carries the
    # whole account (docs/observability.md §Start-up).
    startup = flight_lib.STARTUP
    compile_cache.configure()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="llama3-400m")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV cache: half the HBM per token")
    ap.add_argument("--weights-int8", action="store_true",
                    help="w8a8 decode: int8 weights + activations")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-burst", type=int, default=8,
                    help="decode tokens per device call (streaming "
                         "granularity vs dispatch amortization)")
    ap.add_argument("--open-burst", type=int, default=4,
                    help="decode burst while free slots remain — keeps "
                         "late arrivals from waiting out a full burst "
                         "before their prefill")
    ap.add_argument("--admit-wave", type=int, default=8,
                    help="admission wave cap: early waves' first "
                         "tokens stream while later waves prefill "
                         "(0 = uncapped)")
    ap.add_argument("--coalesce", type=float, default=0.012,
                    help="seconds to wait for a filling admission wave "
                         "when the newest arrival is fresher than this "
                         "(prevents 1-row padded waves on bursts)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: prompts longer than this "
                         "prefill in fixed chunks interleaved with "
                         "decode bursts (0 disables; default env "
                         "SKYTPU_PREFILL_CHUNK or 512)")
    ap.add_argument("--prefix-pool", type=int, default=None,
                    help="prefix KV cache: resident prompt prefixes "
                         "for suffix-only prefill on shared system "
                         "prompts (paged: ref-counted shared blocks; "
                         "contiguous: reserved pool rows; 0 disables; "
                         "default env SKYTPU_PREFIX_POOL or 8)")
    ap.add_argument("--kv-block", type=int, default=None,
                    help="paged KV cache block length: slots rent "
                         "blocks for rows they actually use instead "
                         "of a contiguous max-len row, so slot count "
                         "is bounded by tokens, not worst-case length "
                         "(0 = contiguous layout; default env "
                         "SKYTPU_KV_BLOCK or 256)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged KV pool size in blocks (default env "
                         "SKYTPU_KV_BLOCKS, or the contiguous-"
                         "equivalent HBM: (slots+1)*max_len/block)")
    ap.add_argument("--span-buckets", default=None,
                    help="span-bucketed decode attention: comma-"
                         "separated ladder of KV-row spans (each "
                         "decode/verify/chunk program compiles per "
                         "rung and reads only that many rows, so "
                         "decode bandwidth tracks the active span, "
                         "not --max-len). "
                         "Default: max_len/8,/4,/2 ladder "
                         "(env SKYTPU_SPAN_BUCKETS); 0 disables "
                         "(full-view reads only)")
    ap.add_argument("--kv-kernel", action="store_true",
                    default=None,
                    help="Pallas paged decode-attention kernel: "
                         "decode/verify/chunk big-cache reads walk "
                         "each slot's block table in-kernel instead "
                         "of materializing the gathered logical view "
                         "per layer (paged layouts only; contiguous "
                         "falls back to the gather, which also stays "
                         "the greedy-parity oracle). Default env "
                         "SKYTPU_KV_KERNEL=1")
    ap.add_argument("--kv-lazy", action="store_true",
                    default=None,
                    help="lazy paged-KV growth: admission reserves "
                         "prompt + one burst of blocks instead of "
                         "the full max_new_tokens worst case; the "
                         "rest allocates at burst dispatch (dry pool "
                         "= the slot sits a burst out). Default env "
                         "SKYTPU_KV_LAZY; eager reservation is the "
                         "default")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative decoding: draft up to K tokens "
                         "per slot per burst and verify them in one "
                         "device call — up to K+1 committed tokens "
                         "per decode dispatch, greedy output "
                         "bit-preserved (0 disables; forced off under "
                         "--temperature > 0; default env SKYTPU_SPEC_K "
                         "or 4)")
    ap.add_argument("--draft-model", default=None,
                    help="model-backed speculative drafter: 'self:N' "
                         "(truncated-layer draft sharing the target's "
                         "first N blocks — zero extra weights) or a "
                         "llama config name (e.g. llama3-400m; a "
                         "distilled checkpoint's config). The draft "
                         "model runs the engine's own staged-burst "
                         "program on its own paged KV, advanced/"
                         "rolled-back in lockstep with the verifier; "
                         "unset = the n-gram drafter only (env "
                         "SKYTPU_DRAFT_MODEL)")
    ap.add_argument("--spec-pipeline", type=int, default=None,
                    help="async draft/verify pipeline (model drafter "
                         "only): 1 = dispatch the next round's draft "
                         "rollout while the verify is in flight, "
                         "reconciling on fetch; 0 = synchronous "
                         "draft-then-verify (default env "
                         "SKYTPU_SPEC_PIPELINE or 1)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard weights + KV "
                         "cache over the first N local devices "
                         "(Megatron head/mlp/vocab split — serves "
                         "models bigger than one chip's HBM)")
    ap.add_argument("--adapters", default=None,
                    help="multi-LoRA adapter catalog: JSON object of "
                         "{name: checkpoint path} (adapters.save_"
                         "adapter .npz files). Requests pick a "
                         "fine-tune via the body's 'model' field or "
                         "the x-skytpu-model header; unknown names "
                         "get a typed 404. Default env "
                         "SKYTPU_ADAPTERS (how the serve controller "
                         "hands a replica its catalog)")
    ap.add_argument("--adapter-slots", type=int, default=None,
                    help="device adapter-pool capacity (fine-tunes "
                         "resident at once; LRU hot-load/evict past "
                         "it; default env SKYTPU_ADAPTER_SLOTS or 8)")
    ap.add_argument("--adapter-rank", type=int, default=None,
                    help="adapter-pool LoRA rank (lower-rank "
                         "checkpoints zero-pad; default env "
                         "SKYTPU_ADAPTER_RANK or 16)")
    ap.add_argument("--warm-grid", action="store_true",
                    default=os.environ.get("SKYTPU_WARM_GRID") == "1",
                    help="pre-compile the engine's whole program grid "
                         "at startup and arm the compile watch: any "
                         "later XLA compile is a mid-traffic stall "
                         "and raises the typed "
                         "engine.unexpected_compile alarm + "
                         "skytpu_unexpected_compiles_total (env "
                         "SKYTPU_WARM_GRID=1). Off by default: "
                         "startup pays the full compile sweep")
    ap.add_argument("--profiler-port", type=int, default=0,
                    help="start the JAX profiler's server on this port so "
                         "a device trace (with the loop's and the "
                         "engine's phase annotations on the same "
                         "clock) can be captured from the running "
                         "server; 0 = off (docs/observability.md "
                         "§Device trace)")
    args = ap.parse_args()

    # Long-lived serving daemon: sever any inherited trace root. A
    # server launched as a task inherits SKYTPU_TRACEPARENT from the
    # launch request's rpc chain — without this, every headerless
    # /generate for the life of the server would attach its engine
    # spans to that ONE launch trace (the same spawn-time-root
    # misattribution the skylet avoids via the persisted arm context).
    # Requests that carry their own traceparent are unaffected.
    os.environ.pop(tracing.ENV_VAR, None)
    tracing.set_process_name("model-server")

    with startup.phase("imports"):
        import jax

        from skypilot_tpu.infer import adapters as ad_lib
        from skypilot_tpu.infer import draft as draft_lib
        from skypilot_tpu.infer import engine as eng, kvcache, sampling
        from skypilot_tpu.models import registry
    with startup.phase("backend"):
        devices = jax.devices()    # the backend starts here
    if args.profiler_port:
        jax.profiler.start_server(args.profiler_port)

    try:
        cfg = registry.get_config(args.config)
    except KeyError as e:
        raise SystemExit(e.args[0])
    # A family without a verify program serves with speculation off
    # unless the operator asks for it (and is then refused by name,
    # like every option the family does not serve: main()).
    progs = kvcache.programs_for(cfg)
    can_verify = "spec_k" not in progs.UNSUPPORTED
    # Before either is built from a config object of another family.
    eng.refuse_options(
        progs,
        adapters=bool(args.adapters or os.environ.get(
            "SKYTPU_ADAPTERS", "").strip()),
        draft_model=bool(args.draft_model or os.environ.get(
            "SKYTPU_DRAFT_MODEL", "").strip()))
    mesh = None
    if args.tp > 1:
        import numpy as np
        from jax.sharding import Mesh
        if len(devices) < args.tp:
            raise SystemExit(f"--tp {args.tp} needs {args.tp} devices, "
                             f"found {len(devices)}")
        mesh = Mesh(np.array(devices[:args.tp]), ("tp",))
    # Weights are built on the device(s) at the size they are served:
    # int8 without the float tree they would quantize from, float in
    # the compute dtype, sharded at init under --tp. A tree that
    # cannot fit is a typed start-up error naming the bytes.
    with startup.phase("weights"):
        params, qweights = jax.block_until_ready(
            eng.random_serving_weights(
                cfg, weights_int8=args.weights_int8, mesh=mesh))
    # "--span-buckets 0" disables bucketing; a comma list is an
    # explicit ladder; unset falls through to the engine default /
    # SKYTPU_SPAN_BUCKETS.
    span_buckets = None
    if args.span_buckets is not None:
        rungs = [int(t) for t in
                 args.span_buckets.replace(",", " ").split()]
        span_buckets = [r for r in rungs if r > 0] or 0
    with startup.phase("engine_init"):     # cache, pools, tables
        # Multi-LoRA adapter catalog (docs/serving.md §Adapter catalog):
        # a JSON {name: checkpoint path} names the replica's fine-tunes;
        # loading to device is on demand (the first request naming one
        # pays the hot-load). None = the zero-cost adapterless engine.
        catalog = ad_lib.catalog_from_env(cfg, adapters_json=args.adapters,
                                          slots=args.adapter_slots,
                                          rank=args.adapter_rank)
        # Model-backed drafter (docs/serving.md §Speculative decoding): a
        # 'self:N' draft shares the target's first N blocks (float or
        # int8) by reference. None = the n-gram drafter stays the only
        # rung.
        draft_engine = draft_lib.draft_engine_from_env(
            params, cfg, n_slots=args.slots, max_len=args.max_len,
            spec=args.draft_model, kv_int8=args.kv_int8,
            qweights=qweights)
        engine = eng.InferenceEngine(
            params, cfg, n_slots=args.slots, max_len=args.max_len,
            mesh=mesh,
            prompt_buckets=(128, min(512, args.max_len),
                            args.max_len),
            sampling_params=sampling.SamplingParams(
                temperature=args.temperature),
            kv_int8=args.kv_int8, qweights=qweights,
            max_wave=args.admit_wave,
            prefill_chunk=args.prefill_chunk,
            kv_block=args.kv_block, kv_blocks=args.kv_blocks,
            span_buckets=span_buckets, kv_lazy=args.kv_lazy,
            kv_kernel=args.kv_kernel,
            # Serving default: prefix reuse ON (repeated system prompts are
            # the common serving workload); the engine-level default stays
            # 0 so library users opt in. A family whose blocks cannot be
            # shared defaults to 0 and refuses more, by name.
            prefix_pool=(args.prefix_pool
                         if args.prefix_pool is not None
                         else int(os.environ.get(
                             "SKYTPU_PREFIX_POOL",
                             "0" if "prefix_pool" in progs.UNSUPPORTED
                             else "8") or 0)),
            # Serving default: speculation ON at K=4 (greedy serving is the
            # common case and a missed draft costs one empty verify slot);
            # the engine-level default stays 0 so library users opt in.
            spec_k=(args.spec_k
                    if args.spec_k is not None
                    else int(os.environ.get(
                        "SKYTPU_SPEC_K", "4" if can_verify else "0") or 0)),
            draft_engine=draft_engine,
            spec_pipeline=(bool(args.spec_pipeline)
                           if args.spec_pipeline is not None else None),
            # Two compiled prefill programs per bucket (1 row and
            # --admit-wave rows), both warmed: no wave size can hit a
            # mid-traffic XLA compile on a live replica.
            pad_waves=True,
            # Multi-tenant QoS (SKYTPU_QOS=1): WFQ + priority lanes in the
            # engine's waiting deque. All host-side — tenant count never
            # enters program identity (the compile watch is the gate).
            qos=qos_lib.scheduler_from_env(),
            adapters=catalog)
        jax.block_until_ready((engine.cache, engine.pool))
    if args.warm_grid:
        # Compile the whole program grid BEFORE /health can flip, then
        # arm the compile watch: from here on, a new program compiling
        # under live traffic is an alarm, not tens of silent seconds
        # of TPOT (docs/observability.md §Flight recorder).
        with startup.phase("warm_grid"):
            engine.warm_programs(max_burst=args.max_burst)
            engine.declare_warmup_complete()
    # Startup leaves some hundreds of thousands of live objects behind
    # (JAX itself, every traced program). A full collection walks them
    # all: ~0.1 s with the loop thread stopped, and WHERE it lands is an
    # accident of allocation counts — on the v5e one such pause, falling
    # between two admission waves with the device idle, moved the chat
    # cell's TTFT p95 by 13 % (PERF.md §6, PR 25). Park them in the
    # permanent generation: later collections see only what serving
    # allocates.
    with startup.phase("gc_freeze"):
        gc.collect()
        gc.freeze()
    with startup.phase("listen"):
        model, httpd = serve(engine, port=args.port,
                             max_burst=args.max_burst,
                             open_burst=args.open_burst,
                             coalesce_s=args.coalesce,
                             qos=qos_lib.admission_from_env("server"))
    watches = [engine.compile_watch]
    if draft_engine is not None:
        watches.append(draft_engine.compile_watch)
    tracing.add_event("server.listening",
                      {"port": args.port,
                       "device": attribution.device_report(),
                       "startup": startup.report(watches)},
                      echo=True)
    try:
        httpd.serve_forever()
    finally:
        model.shutdown()


def main() -> None:
    """The server's entry point (``python -m skypilot_tpu.infer.server``,
    and what the benchmark's serve child calls). An operator error that
    carries a ``typed_error`` (weights that cannot fit, an option the
    configuration's family does not serve) ends the process with its
    message and the typed event ``server.<type>``, not a traceback."""
    try:
        _main()
    except ValueError as e:
        typed = getattr(e, "typed_error", None)
        if typed is None:
            raise
        tracing.add_event(f"server.{typed['type']}", typed, echo=True)
        raise SystemExit(str(e))


if __name__ == "__main__":
    main()
