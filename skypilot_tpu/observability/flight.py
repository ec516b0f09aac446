"""Engine flight recorder + compile watch: burst-level serving
introspection and the runtime retrace guard.

Two complementary pieces close the gap between the static retrace lint
(``skytpu lint`` promises the compiled-program surface is bounded) and
what actually happens on a live replica:

* :class:`FlightRecorder` — a bounded, lock-disciplined ring of
  per-burst records. Every device dispatch the serving engine makes
  (admission wave, prefill chunk, decode burst, speculative verify,
  single-step decode) appends ONE host-side record: which compiled
  program ran (span rung, bucket, draft K, KV layout), which slots and
  requests rode it, how long the host waited dispatch-to-fetch, how
  many tokens committed, and what block-management events (COW copies,
  prefix evictions, lazy grows) it caused. Recording is a dict append
  under a lock — ZERO device fetches — so when TPOT spikes in
  production the last thousands of bursts answer "which program ran
  this burst and did anything compile?" without re-running anything.

* :class:`CompileWatch` — a program registry keyed on
  ``(entry point, static args)`` wrapped around every jit entry point
  the engine dispatches. First dispatch of a new key records the
  trace+compile wall time (``skytpu_compile_seconds{program}``,
  ``skytpu_programs_compiled_total``); after the engine declares
  warmup complete, any NEW key is the silent mid-traffic XLA compile
  the whole static-shape design exists to prevent — it emits a typed
  ``engine.unexpected_compile`` event (``echo=True``) and increments
  ``skytpu_unexpected_compiles_total``, which the SLO watchdog alarms
  on (the ``unexpected-compiles`` default rule).

* :class:`CompileLedger` (:data:`COMPILES`) — what each compile cost,
  stage by stage, as ``jax.monitoring`` reports it from inside JAX:
  tracing, jaxpr -> MLIR lowering, XLA compile, persistent-cache
  load. The watch's first-dispatch wall lumps all four with the first
  execution; the ledger is what splits it. :class:`Startup`
  (:data:`STARTUP`) keeps the process's start-up phases by the host
  clock (start-up lies before any profiler trace) and assembles the
  ``startup`` record ``server.listening`` / ``train.ready`` carry.

Records flush to per-process JSONL files (``flight-<proc>-<pid>-<ms>
.jsonl``) in the tracing events dir via the same atomic
tempfile+``os.replace`` idiom, so ``skytpu flight --local`` and
``skytpu trace <req>`` (burst records carry member requests' trace
ids) assemble them cross-process. Same design constraints as
``tracing.py``: stdlib + host-only on the record path, cheap when
idle, safe under concurrency, and a disabled recorder
(``SKYTPU_FLIGHT=0`` or ``recorder.enabled = False``) is a no-op
guard — the hot path pays one attribute check, exactly like
``metrics.suppress``.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
import threading
import time
import types
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from skypilot_tpu.observability import _ringflush, metrics, tracing
from skypilot_tpu.utils import timeline

COMPILE_SECONDS = metrics.histogram(
    "skytpu_compile_seconds",
    "First-dispatch wall time per engine program identity: tracing, "
    "lowering, XLA compile (or, from a warm persistent cache, the "
    "load) and the first execution's dispatch — jit compiles "
    "synchronously at first call, so this is what a request stalled "
    "behind that dispatch experienced "
    "(skytpu_compile_stage_seconds_total splits it)",
    labelnames=("program",),
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             60.0, 120.0))
PROGRAMS_COMPILED = metrics.counter(
    "skytpu_programs_compiled_total",
    "Engine programs compiled (distinct (entry point, static args) "
    "keys first-dispatched through the compile watch)")
UNEXPECTED_COMPILES = metrics.counter(
    "skytpu_unexpected_compiles_total",
    "Engine programs compiled AFTER warmup was declared complete — "
    "each one is a mid-traffic XLA compile stalling live requests; "
    "the retrace-safety invariant says this stays 0")

COMPILE_STAGE_SECONDS = metrics.counter(
    "skytpu_compile_stage_seconds_total",
    "Seconds this process spent compiling, by stage as jax.monitoring "
    "reports them: trace (Python -> jaxpr), lower (jaxpr -> MLIR), "
    "compile (XLA, less any cache lookup) and load (an executable "
    "read back from the persistent cache); no second counted twice",
    labelnames=("stage",))
COMPILE_CACHE_HITS = metrics.counter(
    "skytpu_compile_cache_hits_total",
    "Compiles served from the persistent compilation cache")
COMPILE_CACHE_MISSES = metrics.counter(
    "skytpu_compile_cache_misses_total",
    "Compiles that missed the persistent compilation cache and wrote "
    "their entry (a compile too quick to be kept counts as neither)")
STARTUP_SECONDS = metrics.gauge(
    "skytpu_startup_seconds",
    "Seconds this process spent in each start-up phase (before_main, "
    "imports, backend, weights, engine_init, warm_grid and its "
    "program families, gc_freeze, listen; a trainer: state, "
    "first_step), by the host clock",
    labelnames=("phase",))

# Ring bound: at a production burst cadence (~100 bursts/s across
# groups) 8192 records is over a minute of history, and one flush
# serializes at most this many lines.
_MAX_RECORDS = 8192

_FILE_PREFIX = "flight-"


def enabled() -> bool:
    """The flight recorder is on unless explicitly disabled
    (``SKYTPU_FLIGHT=0``)."""
    return os.environ.get("SKYTPU_FLIGHT", "1") != "0"


class FlightRecorder:
    """Bounded ring of per-burst flight records.

    One recorder per process is the normal shape (:data:`RECORDER`);
    engines take an injectable instance so tests and the bench can
    observe an isolated window. ``enabled`` is a plain attribute the
    owner may flip at runtime — a disabled recorder's :meth:`record`
    returns before touching the lock (the recorder-off no-op guard).
    """

    def __init__(self, capacity: int = _MAX_RECORDS,
                 file_prefix: str = _FILE_PREFIX):
        self.enabled = enabled()
        self.capacity = capacity
        self._ring = _ringflush.Ring(
            capacity,
            lambda: (f"{file_prefix}{tracing.process_name()}"
                     f"-{os.getpid()}-{int(time.time() * 1000)}.jsonl"),
            tracing.events_dir, seq_field="seq",
            thread_name="flight-flush")

    # -- recording (the hot path) ------------------------------------------

    def record(self, burst: str, **fields: Any) -> None:
        """Append one burst record. Host-side values ONLY — the engine
        hands in host bookkeeping (lists, ints, floats), never device
        arrays; fetching one here would stall the dispatch pipeline
        the recorder exists to observe. Honors :func:`metrics.suppress`
        (warmup work must not pollute the ring either)."""
        if not self.enabled or metrics.suppressed():
            return
        rec: Dict[str, Any] = {
            "kind": "flight", "burst": burst, "pid": os.getpid(),
            "proc": tracing.process_name(),
        }
        rec.update(fields)
        self._ring.append(rec)

    # -- introspection -----------------------------------------------------

    def seq(self) -> int:
        return self._ring.seq()

    def tail(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """Snapshot of the newest ``n`` records (all when None),
        oldest first."""
        recs = self._ring.snapshot()
        return recs[-n:] if n else recs

    def since(self, seq: int) -> List[Dict[str, Any]]:
        """Records appended after sequence number ``seq`` that are
        still in the ring (tests/bench window over the shared ring)."""
        return [r for r in self._ring.snapshot() if r["seq"] > seq]

    # -- flushing (the shared _ringflush atomic-replace idiom) -------------

    def flush(self) -> None:
        """Atomically rewrite this process's flight log with the whole
        ring. Serialization happens OUTSIDE the ring lock so recorder
        callers (the engine loop) never block on an O(ring) dumps."""
        self._ring.flush()

    def flush_periodic(self, min_new_records: int = 256) -> None:
        self._ring.flush_periodic(min_new_records=min_new_records)

    def ensure_flush_thread(self, interval_s: float = 5.0) -> None:
        """Start (once) a daemon thread flushing this recorder
        periodically — durability off the owner's hot loop."""
        self._ring.ensure_flush_thread(interval_s, min_new_records=256)

    def _reset_for_tests(self) -> None:
        self._ring.reset_for_tests()


RECORDER = FlightRecorder()


def ensure_flush_thread(interval_s: float = 5.0) -> None:
    """Start (once) a daemon thread flushing :data:`RECORDER`
    periodically — the model server's durability heartbeat, off the
    serving loop (same rationale as tracing.ensure_flush_thread)."""
    RECORDER.ensure_flush_thread(interval_s)


# ---------------------------------------------------------------------------
# Compile ledger: what jax.monitoring says each compile cost.

STAGES = ("trace", "lower", "compile", "load")
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
# Bounds: a process compiles a few dozen functions; a retrace storm
# must not grow the ledger (or one thread's interval stack) for ever.
_MAX_COMPILES = 4096
_MAX_OPEN_INTERVALS = 64


def _zero_totals() -> Dict[str, float]:
    return {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "load_s": 0.0, "cache_hits": 0, "cache_misses": 0}


class _ThreadCompiles(threading.local):
    """One thread's view of the compile in flight (JAX calls the
    listeners on the thread that compiles)."""

    def __init__(self):
        # (start, seconds) of the events seen, newest last: a new event
        # that began before them CONTAINS them (JAX times a function's
        # tracing round the tracing of every jitted function it calls).
        self.intervals: List[Tuple[float, float]] = []
        # Tracing and lowering since this thread's last compile.
        self.open = {"trace_s": 0.0, "lower_s": 0.0}
        # What the code being traced said of itself (CompileLedger.note).
        self.notes: Dict[str, set] = {}
        self.load_s = 0.0
        self.cache_hit: Optional[bool] = None
        self.totals = _zero_totals()


class CompileLedger:
    """Every compile of the process, split by stage, from the duration
    events JAX records itself (``jax.monitoring``): ``trace_s`` (Python
    to jaxpr), ``lower_s`` (jaxpr to MLIR), ``compile_s`` (XLA) and
    ``load_s`` (the executable read back from the persistent cache).

    No second is counted twice. JAX's events nest — a function's tracing
    spans the tracing of each jitted function it calls, and
    ``backend_compile_duration`` spans the cache lookup — so an event is
    credited its duration LESS the events it contains, and on a cache
    hit ``compile_s`` is the backend event less the retrieval. The
    stages of a first dispatch therefore sum to at most its wall.

    The listeners fire only when something compiles, never on a
    dispatch. A finished compile (the backend event closes it) is one
    record and one echoed ``program.compiled`` event; the ``/metrics``
    counters follow through :meth:`publish`, which a caller that
    compiled under ``metrics.suppress`` repeats once outside it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False                   # guarded-by: _lock
        self._records: List[Dict[str, Any]] = []  # guarded-by: _lock
        self._totals = _zero_totals()             # guarded-by: _lock
        self._published = _zero_totals()          # guarded-by: _lock
        self._n = 0                               # guarded-by: _lock
        self._thread = _ThreadCompiles()

    def install(self) -> bool:
        """Register the listeners, once a process. Whatever builds a
        program calls this (a compile watch, a step builder, the first
        start-up phase); a process that has not loaded JAX has nothing
        to listen to."""
        with self._lock:
            if self._installed:
                return True
            if "jax" not in sys.modules:
                return False
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                self._on_duration)
            monitoring.register_event_listener(self._on_event)
            self._installed = True
            return True

    def note(self, key: str, value: str) -> None:
        """Code that chooses between forms AT TRACE TIME says which it
        took (``expert_ffn``: ``models.glm_moe.experts_grouped``): the
        compile this thread finishes next carries ``key`` with every
        distinct value noted since the last, on its record and its
        ``program.compiled`` line."""
        self._thread.notes.setdefault(key, set()).add(value)

    # -- the listeners (the compiling thread) ------------------------------

    def _own_seconds(self, th: _ThreadCompiles, seconds: float) -> float:
        """``seconds`` less the events this one contains."""
        start = time.monotonic() - seconds
        inner = 0.0
        stack = th.intervals
        while stack and stack[-1][0] >= start - 1e-6:
            inner += stack.pop()[1]
        if len(stack) >= _MAX_OPEN_INTERVALS:
            del stack[:_MAX_OPEN_INTERVALS // 2]
        stack.append((start, seconds))
        return max(seconds - inner, 0.0)

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self._thread.cache_hit = True
        elif event == _CACHE_MISS:
            self._thread.cache_hit = False

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        th = self._thread
        if event == _CACHE_RETRIEVAL:
            th.load_s = seconds
            return
        stage = _STAGE_OF.get(event)
        if stage is None:
            return
        own = self._own_seconds(th, seconds)
        if stage != "compile":
            # Tracing and lowering: the compile that follows claims them.
            th.open[stage + "_s"] += own
            return
        load = min(th.load_s, own) if th.cache_hit else 0.0
        rec = {"fun_name": str(kw.get("fun_name", "?")), **th.open,
               "compile_s": own - load, "load_s": load,
               "cache_hit": th.cache_hit,
               **{k: ",".join(sorted(v)) for k, v in th.notes.items()}}
        th.open = {"trace_s": 0.0, "lower_s": 0.0}
        th.notes = {}
        th.load_s, th.cache_hit = 0.0, None
        gained = {s + "_s": rec[s + "_s"] for s in STAGES}
        if rec["cache_hit"] is not None:
            gained["cache_hits" if rec["cache_hit"]
                   else "cache_misses"] = 1
        with self._lock:
            self._n += 1
            if len(self._records) < _MAX_COMPILES:
                self._records.append(rec)
            for key, value in gained.items():
                th.totals[key] += value
                self._totals[key] += value
        self.publish()
        tracing.add_event("program.compiled", _rounded(rec), echo=True)

    # -- reading -----------------------------------------------------------

    def thread_totals(self) -> Dict[str, float]:
        """The stage seconds and cache counts of the compiles this
        thread has finished (the compile watch takes them before and
        after a first dispatch)."""
        return dict(self._thread.totals)

    def totals(self) -> Dict[str, float]:
        """The process's stage seconds of FINISHED compiles, the cache
        hits and misses, and ``functions``: how many compiled."""
        with self._lock:
            return dict(self._totals, functions=self._n)

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for r in self._records]

    def publish(self) -> None:
        """Bring the ``/metrics`` counters up to the ledger's totals.
        Under ``metrics.suppress`` nothing moves and nothing is marked
        published: the caller's republish outside it carries the sum
        (``engine.warm_programs``)."""
        if metrics.suppressed():
            return
        with self._lock:
            due = {k: self._totals[k] - self._published[k]
                   for k in self._totals}
            self._published = dict(self._totals)
        for stage in STAGES:
            if due[stage + "_s"] > 0:
                COMPILE_STAGE_SECONDS.labels(stage=stage).inc(
                    due[stage + "_s"])
        if due["cache_hits"]:
            COMPILE_CACHE_HITS.inc(due["cache_hits"])
        if due["cache_misses"]:
            COMPILE_CACHE_MISSES.inc(due["cache_misses"])


def _rounded(rec: Dict[str, Any]) -> Dict[str, Any]:
    return {k: round(v, 4) if isinstance(v, float) else v
            for k, v in rec.items()}


COMPILES = CompileLedger()


# ---------------------------------------------------------------------------
# Start-up: the phases of a process's start, by the host clock.

def process_start_s() -> Optional[float]:
    """Wall-clock time this process started, from ``/proc/self/stat``
    (start time in clock ticks since boot) against ``/proc/uptime``;
    None where the kernel keeps no such record."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # The command (field 2) may hold spaces; the rest follow
            # its closing parenthesis. starttime is field 22.
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        with open("/proc/uptime", "rb") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    if not 0.0 <= age < 30 * 24 * 3600.0:
        return None
    return time.time() - age


class Startup:
    """The start-up record of a process: each phase's seconds, by the
    host clock (start-up lies before any profiler trace could run).

    A phase is a ``timeline.Event`` whose duration the metrics bridge
    hands back here: a ``skytpu_startup_seconds{phase}`` gauge and, on
    closing, one echoed ``startup.phase {phase, s}`` line — so a start
    that dies half way, or a process that never announces itself (a
    caller that runs the builders under a loop of its own), still
    leaves its record. A dotted name (``warm_grid.decode``) is a child:
    it is reported, and left out of the sum. Opening the first phase
    stamps ``before_main`` — process start to that moment: the
    interpreter, the imports and whatever the caller did first; every
    phase opened makes sure the compile ledger is listening."""

    def __init__(self):
        self._lock = threading.Lock()
        self._phases: Dict[str, float] = {}      # guarded-by: _lock
        self._start_s = process_start_s()
        self._opened = False                     # guarded-by: _lock

    def phase(self, name: str) -> timeline.Event:
        """``with STARTUP.phase("weights"): ...``"""
        with self._lock:
            first, self._opened = not self._opened, True
        if first and self._start_s is not None:
            self.close("before_main",
                       max(time.time() - self._start_s, 0.0))
        COMPILES.install()     # as soon as a phase finds JAX loaded
        # timeline's metrics bridge observes the duration into close().
        sink = types.SimpleNamespace(
            observe=functools.partial(self.close, name))
        return timeline.Event("startup." + name, histogram=sink)

    def close(self, name: str, seconds: float) -> None:
        with self._lock:
            total = self._phases[name] = \
                self._phases.get(name, 0.0) + seconds
        STARTUP_SECONDS.labels(phase=name).set(total)
        tracing.add_event("startup.phase",
                          {"phase": name, "s": round(seconds, 4)},
                          echo=True)

    def phases(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._phases)

    def report(self, watches: Sequence["CompileWatch"] = ()
               ) -> Dict[str, Any]:
        """The ``startup`` field of ``server.listening`` /
        ``train.ready``: ``total_s`` from process start to now, the
        phases, what no phase claims, the compile ledger's totals with
        the five programs that cost most (the watches' keys; without a
        watch, the ledger's functions), and the device's memory."""
        now = time.time()
        phases = self.phases()
        top = sum(s for name, s in phases.items() if "." not in name)
        total = now - self._start_s if self._start_s is not None else top
        programs = [dict(split, program=key) for w in watches
                    for key, split in w.splits().items()]
        if not programs:
            programs = [{("program" if k == "fun_name" else k): v
                         for k, v in r.items()}
                        for r in COMPILES.records()]
        programs.sort(key=lambda r: -sum(
            r.get(stage + "_s", 0.0) for stage in STAGES))
        totals = COMPILES.totals()
        totals.pop("functions")
        return {
            "total_s": round(total, 4),
            "phases": {k: round(v, 4) for k, v in phases.items()},
            "unattributed_s": round(total - top, 4),
            "compile": dict(_rounded(totals), programs=len(programs),
                            slowest=[_rounded(r) for r in programs[:5]]),
            "memory": device_memory()}


def device_memory() -> Dict[str, int]:
    """The fullest local device's memory as the backend reports it
    (a backend that keeps no statistics reports nothing)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return {}
    keys = ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
            "bytes_limit")
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {k: max(int(s.get(k, 0)) for s in stats)
            for k in keys if any(k in s for s in stats)}


STARTUP = Startup()


# ---------------------------------------------------------------------------
# Compile watch.

class CompileWatch:
    """Program registry over the engine's jit entry points.

    :meth:`wrap` returns a transparent wrapper that derives a program
    KEY from the call's static arguments (plus an optional ``key_fn``
    for shape-derived identity, e.g. the admission wave's row count —
    jit recompiles on new shapes even under an unchanged static key).
    A key's first dispatch is where jit traces and compiles
    SYNCHRONOUSLY, so that call's wall time is the compile cost a
    stalled request experienced; it lands in
    ``skytpu_compile_seconds{program}``, and :meth:`splits` says how
    much of it was tracing, lowering, XLA compile, cache load and
    (the remainder) the first execution's dispatch — the compile
    ledger's totals on this thread before and after the call. After
    :meth:`declare_warm`,
    a new key is a mid-traffic compile: typed
    ``engine.unexpected_compile`` event + counter.

    One watch per engine: program identity is engine-scoped (two
    engines in one process legitimately compile the same key twice).
    ``event_name`` is the typed event a post-warm compile emits — the
    serving engines keep the default ``engine.unexpected_compile``;
    the trainer's own watch emits ``train.unexpected_compile`` so the
    two alarm surfaces stay distinguishable in the event log.
    """

    def __init__(self, event_name: str = "engine.unexpected_compile"):
        self.event_name = event_name
        self._lock = threading.Lock()
        self._programs: Dict[str, float] = {}    # guarded-by: _lock
        self._splits: Dict[str, Dict[str, Any]] = {}  # guarded-by: _lock
        self._unexpected: List[str] = []         # guarded-by: _lock
        self._new: List[str] = []                # guarded-by: _lock
        self._warm = False                       # guarded-by: _lock
        # Device-time calibration (attribution.DeviceTimeCalibrator,
        # attached by the engine): every Nth HIT dispatch of a key is
        # routed through the calibrator's timed bracket, maintaining
        # the per-program device-seconds EWMA. None = no calibration.
        self.calibrator = None
        # Key of the most recent dispatch through any wrapper. Loop-
        # thread discipline (the engine reads it right after the
        # dispatch it made), so a plain attribute suffices.
        self.last_key: Optional[str] = None
        COMPILES.install()

    def wrap(self, name: str, fn: Callable,
             static_argnames: Sequence[str] = (),
             key_fn: Optional[Callable[[tuple, dict],
                                       Sequence[Tuple[str, Any]]]]
             = None) -> Callable:
        def wrapped(*args, **kwargs):
            parts = [f"{a}={kwargs[a]}" for a in static_argnames
                     if a in kwargs]
            if key_fn is not None:
                parts.extend(f"{k}={v}" for k, v in key_fn(args, kwargs))
            key = name + (f"[{' '.join(parts)}]" if parts else "")
            self.last_key = key
            with self._lock:
                hit = key in self._programs
            if hit:
                # Sampled device-time calibration rides the HIT path
                # only: the first dispatch is the compile, whose wall
                # would poison a pure-execution EWMA.
                cal = self.calibrator
                if cal is not None and cal.tick(key):
                    return cal.timed_call(key, fn, *args, **kwargs)
                return fn(*args, **kwargs)
            before = COMPILES.thread_totals()
            t0 = time.monotonic()
            out = fn(*args, **kwargs)
            dt = time.monotonic() - t0
            split = _split_since(before, COMPILES.thread_totals(), dt)
            with self._lock:
                if key in self._programs:    # racing first dispatches
                    return out
                self._programs[key] = dt
                self._splits[key] = split
                self._new.append(key)
                warm = self._warm
                if warm:
                    self._unexpected.append(key)
            COMPILE_SECONDS.labels(program=key).observe(dt)
            PROGRAMS_COMPILED.inc()
            if warm:
                UNEXPECTED_COMPILES.inc()
                tracing.add_event(
                    self.event_name,
                    {"program": key, "compile_s": round(dt, 4)},
                    echo=True)
            return out
        # The jitted function itself, for callers that lower/compile
        # ahead of time (tests/test_chip_compile.py).
        wrapped.__wrapped__ = fn
        return wrapped

    # -- warmup state ------------------------------------------------------

    def declare_warm(self) -> None:
        """The owner believes every program the live workload can
        reach is compiled; from here on a new key is an alarm."""
        with self._lock:
            self._warm = True

    @property
    def warm(self) -> bool:
        with self._lock:
            return self._warm

    # -- introspection -----------------------------------------------------

    @property
    def count(self) -> int:
        with self._lock:
            return len(self._programs)

    @property
    def unexpected(self) -> List[str]:
        with self._lock:
            return list(self._unexpected)

    def drain_new(self) -> List[str]:
        """Keys compiled since the last drain — the engine attaches
        them to the flight record of the burst that paid for them."""
        with self._lock:
            new, self._new = self._new, []
        return new

    def summary(self) -> Dict[str, float]:
        """``{program key: first-dispatch wall seconds}``."""
        with self._lock:
            return dict(self._programs)

    def republish(self, pre_keys) -> None:
        """After a sweep under ``metrics.suppress`` (a warm grid), whose
        increments were discarded: publish the compile metrics of every
        key not in ``pre_keys`` from the registry, and with them the
        ledger's stage seconds and cache counts — so ``/metrics`` and
        the start-up record agree on a warm-grid replica."""
        for key, wall in self.summary().items():
            if key not in pre_keys:
                COMPILE_SECONDS.labels(program=key).observe(wall)
                PROGRAMS_COMPILED.inc()
        COMPILES.publish()

    def splits(self) -> Dict[str, Dict[str, Any]]:
        """``{program key: {trace_s, lower_s, compile_s, load_s,
        execute_s, cache_hit}}``: each first dispatch's wall by stage
        (they sum to :meth:`summary`'s seconds)."""
        with self._lock:
            return {k: dict(v) for k, v in self._splits.items()}

    def total_compile_s(self) -> float:
        with self._lock:
            return sum(self._programs.values())


def _split_since(before: Dict[str, float], after: Dict[str, float],
                 wall: float) -> Dict[str, Any]:
    """A first dispatch's wall by stage: what the ledger gained on this
    thread across the call, ``execute_s`` the remainder, ``cache_hit``
    true when all it compiled came from the cache, false when any of
    it missed (None: nothing the cache keeps)."""
    split: Dict[str, Any] = {
        stage + "_s": after[stage + "_s"] - before[stage + "_s"]
        for stage in STAGES}
    split["execute_s"] = max(wall - sum(split.values()), 0.0)
    hits = after["cache_hits"] - before["cache_hits"]
    misses = after["cache_misses"] - before["cache_misses"]
    split["cache_hit"] = not misses if hits or misses else None
    return split


# ---------------------------------------------------------------------------
# Loading + rendering (skytpu flight, /debug/flight consumers).

def load_records(dirs: Optional[List[str]] = None,
                 n: Optional[int] = None) -> List[Dict[str, Any]]:
    """Flight records from every flushed per-process log under the
    event-log search dirs, oldest first by (ts, seq). Corrupt lines
    (crash mid-line predates the atomic flush; foreign files) are
    skipped, never fatal."""
    from skypilot_tpu.observability import trace_view
    records: List[Dict[str, Any]] = []
    for d in (dirs if dirs is not None else trace_view.search_dirs()):
        for path in sorted(glob.glob(
                os.path.join(d, _FILE_PREFIX + "*.jsonl"))):
            try:
                with open(path, encoding="utf-8") as f:
                    for line in f:
                        try:
                            rec = json.loads(line)
                        except ValueError:
                            continue
                        if (isinstance(rec, dict)
                                and rec.get("kind") == "flight"):
                            records.append(rec)
            except OSError:
                continue
    records.sort(key=lambda r: (r.get("ts_s", 0.0), r.get("seq", 0)))
    return records[-n:] if n else records


def program_label(rec: Dict[str, Any]) -> str:
    """Compact program-identity string for one record, e.g.
    ``decode[k=8 span=256 paged]``."""
    prog = rec.get("program") or {}
    parts = [f"{k}={prog[k]}" for k in sorted(prog) if k != "layout"]
    layout = prog.get("layout")
    if layout:
        parts.append(str(layout))
    inner = " ".join(parts)
    return f"{rec.get('burst', '?')}[{inner}]" if inner \
        else str(rec.get("burst", "?"))


def summarize(records: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Per-program rollup over a record set: count, tokens committed,
    mean/max host dispatch-to-fetch wall, spec drafted/accepted."""
    out: Dict[str, Dict[str, Any]] = {}
    for r in records:
        agg = out.setdefault(program_label(r), {
            "count": 0, "toks": 0, "total_s": 0.0, "max_s": 0.0,
            "drafted": 0, "accepted": 0, "compiled": 0,
            "dev_s": 0.0, "dev_samples": 0, "flops": 0,
            "hbm_bytes": 0})
        dur = max(float(r.get("dur_s", 0.0)), 0.0)
        agg["count"] += 1
        agg["toks"] += int(r.get("toks", 0))
        agg["total_s"] += dur
        agg["max_s"] = max(agg["max_s"], dur)
        agg["drafted"] += int(r.get("drafted", 0))
        agg["accepted"] += int(r.get("accepted", 0))
        agg["compiled"] += len(r.get("compiled", ()))
        # Device-truth attribution (calibrated estimates + analytical
        # roofline inputs); records predating the attribution layer
        # simply contribute nothing.
        if r.get("dev_ms_est") is not None:
            agg["dev_s"] += float(r["dev_ms_est"]) / 1e3
            agg["dev_samples"] += 1
        agg["flops"] += int(r.get("flops", 0))
        agg["hbm_bytes"] += int(r.get("hbm_bytes", 0))
    for agg in out.values():
        agg["mean_ms"] = round(agg["total_s"] / agg["count"] * 1e3, 3)
        agg["max_s"] = round(agg["max_s"], 6)
        agg["total_s"] = round(agg["total_s"], 6)
        agg["dev_ms"] = (round(agg["dev_s"] / agg["dev_samples"] * 1e3,
                               3)
                         if agg["dev_samples"] else None)
        agg["dev_s"] = round(agg["dev_s"], 6)
    return out


def render_table(records: List[Dict[str, Any]],
                 programs: Optional[Dict[str, float]] = None,
                 last: int = 32) -> str:
    """Human view: the last-N bursts table plus the per-program
    summary (and, when a compile-watch summary is supplied, each
    program's first-dispatch compile cost)."""
    if not records:
        return "no flight records (recorder off, or nothing flushed yet)"
    lines: List[str] = []
    shown = records[-last:]
    t0 = shown[0].get("ts_s", 0.0)
    lines.append(f"last {len(shown)} of {len(records)} bursts:")
    fmt = "{:>9}  {:<34} {:>5} {:>5} {:>9} {:>8}  {}"
    lines.append(fmt.format("T+MS", "PROGRAM", "SLOTS", "TOKS",
                            "HOST-MS", "DEV-MS", "FLAGS"))
    for r in shown:
        flags = []
        if r.get("stall"):
            flags.append("stall")
        if r.get("drafted"):
            flags.append(f"spec {r.get('accepted', 0)}"
                         f"/{r.get('drafted', 0)}")
        # Drafter attribution (PR 14): which drafter kind fed a verify
        # burst (model|ngram|mixed — "draft" records ARE the pipelined
        # predraft dispatch), and how much host wall the round spent
        # dispatching next-round draft work inside the verify's
        # dispatch->fetch window — draft and verify render as
        # OVERLAPPING spans under --perfetto, not a serial chain.
        if r.get("drafter"):
            flags.append(f"drafter={r['drafter']}")
        if r.get("overlap_ms"):
            flags.append(f"overlap={r['overlap_ms']:.2f}ms")
        for k in ("cow", "evictions", "lazy_grows"):
            if r.get(k):
                flags.append(f"{k}={r[k]}")
        # QoS attribution (engine bursts carry tenant composition when
        # a fair scheduler is installed; "preempt" records carry the
        # victim's lane): only non-default make-ups earn a flag.
        tenants = r.get("tenants") or {}
        if tenants and (len(tenants) > 1
                        or next(iter(tenants)) != "default"):
            flags.append("tenants=" + ",".join(
                f"{t}:{n}" for t, n in sorted(tenants.items())))
        # Adapter-catalog composition: which fine-tunes shared this
        # dispatch (base-model members carry no entry).
        ads = r.get("adapters") or {}
        if ads:
            flags.append("adapters=" + ",".join(
                f"{a}:{n}" for a, n in sorted(ads.items())))
        if r.get("burst") == "preempt":
            flags.append(f"prio={r.get('priority', 0)} "
                         f"retired={r.get('retired_rows', 0)}")
        if r.get("compiled"):
            flags.append(f"COMPILED={len(r['compiled'])}")
        dev = r.get("dev_ms_est")
        lines.append(fmt.format(
            f"+{(r.get('ts_s', t0) - t0) * 1e3:.1f}",
            program_label(r)[:34],
            len(r.get("slots", ())), r.get("toks", 0),
            f"{float(r.get('dur_s', 0.0)) * 1e3:.2f}",
            f"{float(dev):.2f}" if dev is not None else "-",
            " ".join(flags)))
    lines.append("")
    lines.append("per-program summary:")
    fmt2 = "{:<40} {:>6} {:>8} {:>9} {:>8} {:>9}  {}"
    lines.append(fmt2.format("PROGRAM", "BURSTS", "TOKS", "MEAN-MS",
                             "DEV-MS", "MAX-MS", "SPEC"))
    for label, agg in sorted(summarize(records).items()):
        spec = (f"{agg['accepted']}/{agg['drafted']}"
                if agg["drafted"] else "-")
        lines.append(fmt2.format(
            label[:40], agg["count"], agg["toks"], agg["mean_ms"],
            agg["dev_ms"] if agg["dev_ms"] is not None else "-",
            round(agg["max_s"] * 1e3, 3), spec))
    if programs:
        lines.append("")
        lines.append("compiled programs (first-dispatch wall):")
        for key in sorted(programs):
            lines.append(f"  {key:<44} {programs[key] * 1e3:9.1f}ms")
    return "\n".join(lines)


def as_spans(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Flight records reshaped as span records so
    ``trace_view.to_perfetto`` renders them as duration tracks
    (``skytpu flight --perfetto``)."""
    spans = []
    for r in records:
        ts = float(r.get("ts_s", 0.0))
        attrs = {k: r[k] for k in ("toks", "drafted", "accepted",
                                   "stall", "rids", "tenants",
                                   "adapters", "priority",
                                   "retired_rows", "drafter",
                                   "overlap_ms", "dev_ms_est",
                                   "dispatch_wall_ms",
                                   "fetch_wall_ms", "flops",
                                   "hbm_bytes")
                 if r.get(k)}
        attrs["slots"] = len(r.get("slots", ()))
        spans.append({
            "kind": "span", "name": program_label(r),
            "start_s": ts, "end_s": ts + float(r.get("dur_s", 0.0)),
            "pid": r.get("pid", 0), "tid": r.get("pid", 0),
            "proc": r.get("proc", "?"), "attrs": attrs,
        })
    return spans
