"""SLO watchdog: declarative rules over federated fleet snapshots.

A small rule engine in the Google SRE-workbook style: each rule is
evaluated over TWO windows (a short one for responsiveness, a long one
for confidence) and alerts only when BOTH breach — one slow request or
a single scrape blip must not page. Breaches and recoveries are emitted
as typed events (``slo.breach`` / ``slo.recovered``, ``echo=True``) so
they land in the structured event log AND the daemon's stderr, exactly
like the skylet's autostop events.

Rule kinds (all windowed deltas clamp counter resets to zero — see
``aggregate.delta``):

  * ``histogram_quantile`` — e.g. TTFT p95 over the window > threshold
    seconds;
  * ``ratio`` — numerator/denominator counter increase, e.g. HTTP 5xx
    ratio (label filters select the numerator; prefix matches support
    ``code=~"5"``-style classes via ``label_prefix``);
  * ``rate`` — counter increase per second, e.g. rpc transport-failure
    rate;
  * ``heartbeat_staleness`` — now minus a unix-timestamp gauge
    (instantaneous: both windows see the same truth);
  * ``train_step_regression`` — mean step time over the window vs the
    fleet's trailing-median gauge (the trainer exports
    ``skytpu_train_step_median_seconds``), thresholded as a ratio;
  * ``component_dead`` — any component the health model reports dead
    (instantaneous).

Rules are declarative data: the defaults below, overridable by a JSON
file at ``<home>/slo_rules.json`` (a list of rule dicts with the same
field names).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from skypilot_tpu.observability import (aggregate, forensics, health,
                                        metrics, tracing)

SLO_BREACHES = metrics.counter(
    "skytpu_slo_breaches_total",
    "SLO watchdog breach events emitted, by rule", labelnames=("rule",))
SLO_ACTIVE = metrics.gauge(
    "skytpu_slo_alert_active",
    "1 while a rule's alert is firing (multi-window burn rate: both "
    "windows breached)", labelnames=("rule",))
SLO_EVALUATIONS = metrics.counter(
    "skytpu_slo_evaluations_total", "SLO watchdog evaluation passes")

RULES_FILENAME = "slo_rules.json"


@dataclasses.dataclass
class SloRule:
    """One declarative objective. ``threshold`` semantics depend on
    ``kind`` (seconds, ratio 0..1, events/s, staleness seconds, or a
    regression factor)."""

    name: str
    kind: str
    threshold: float
    metric: str = ""
    quantile: float = 0.95
    # Numerator label filters for `ratio` (exact and prefix matches).
    labels: Dict[str, str] = dataclasses.field(default_factory=dict)
    label_prefix: Dict[str, str] = dataclasses.field(default_factory=dict)
    # Series dropped from BOTH sides of a ratio (and from rate/ratio
    # numerators): label -> excluded values. The default 5xx rule
    # excludes monitoring routes — the watchdog's own /metrics scrapes
    # and /healthz probes would otherwise pad the denominator with
    # steady 200s and dilute the error ratio of low-traffic services.
    exclude_labels: Dict[str, List[str]] = dataclasses.field(
        default_factory=dict)
    denominator: str = ""            # ratio: defaults to same metric
    baseline_metric: str = ""        # train_step_regression
    short_window_s: float = 60.0
    long_window_s: float = 300.0
    # Ratio/rate rules ignore windows with fewer events than this: a
    # single failed request out of one request is a 100% error ratio
    # and exactly the page the burn-rate design exists to avoid.
    min_events: float = 1.0

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SloRule":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown SLO rule fields {sorted(unknown)}")
        return cls(**d)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


DEFAULT_RULES: List[SloRule] = [
    SloRule("ttft-p95", "histogram_quantile", threshold=10.0,
            metric="skytpu_ttft_seconds", quantile=0.95),
    SloRule("http-5xx-ratio", "ratio", threshold=0.05,
            metric="skytpu_http_requests_total",
            label_prefix={"code": "5"}, min_events=5.0,
            exclude_labels={"route": ["/metrics", "/healthz",
                                      "/health"]}),
    SloRule("rpc-transport-failures", "rate", threshold=0.2,
            metric="skytpu_rpc_failures_total",
            labels={"kind": "transport"}),
    SloRule("skylet-heartbeat", "heartbeat_staleness", threshold=120.0,
            metric="skytpu_skylet_last_tick_timestamp_seconds"),
    # The runtime retrace guard: an engine that compiled ANY program
    # after declaring warmup complete is stalling live requests on XLA
    # (tens of seconds on an 8B model) — threshold 0 means one
    # unexpected compile in both windows pages.
    SloRule("unexpected-compiles", "rate", threshold=0.0,
            metric="skytpu_unexpected_compiles_total"),
    # Sustained QoS load-shedding: sheds are the fleet protecting
    # itself (a hot tenant over its bucket, or an overloaded queue) —
    # working as designed in a burst, but a shed rate held across both
    # windows means capacity or quota is mis-sized and real traffic is
    # bouncing. The burn-rate autoscaler usually reacts first; this
    # rule pages when it can't (max_replicas hit, scaling frozen).
    SloRule("qos-shed-rate", "rate", threshold=1.0,
            metric="skytpu_qos_shed_total", min_events=5.0),
    SloRule("train-step-regression", "train_step_regression",
            threshold=1.5, metric="skytpu_train_step_seconds",
            baseline_metric="skytpu_train_step_median_seconds",
            min_events=3.0),
    # The training goodput floor, expressed as its complement: badput
    # (unproductive wall, named buckets) over attributed elapsed wall.
    # Breaching 0.5 in both windows means the run spent the majority
    # of the last minutes NOT in productive compute — an input-bound
    # pipeline, a checkpoint stall storm, or restart thrash — with the
    # named-bucket counters saying which. warmup_compile is excluded
    # from the numerator (cold-start compile is expected badput, not a
    # page), and min_events keeps windows with almost no attributed
    # wall (idle or just-started processes) from paging.
    SloRule("train-goodput-floor", "ratio", threshold=0.5,
            metric="skytpu_train_unproductive_seconds_total",
            denominator="skytpu_train_wall_seconds_total",
            exclude_labels={"bucket": ["warmup_compile"]},
            min_events=30.0),
    SloRule("component-alive", "component_dead", threshold=0.0),
    # Analytical HBM pressure from the engine's ledger: capacity
    # components (weights, pools, workspace) summed against the
    # published limit. Views (kv_used, prefix_pinned; expert_weights,
    # the routed experts' part of weights) are excluded — they live
    # INSIDE a pool or the weights and would double-count. Pages
    # before the allocator does, while there is
    # still headroom to act (evict prefixes, shrink max_batch).
    SloRule("hbm-headroom", "hbm_headroom", threshold=0.92,
            metric="skytpu_hbm_bytes",
            baseline_metric="skytpu_hbm_limit_bytes",
            exclude_labels={"component": ["kv_used", "prefix_pinned",
                                          "expert_weights"]}),
]


def load_rules(path: Optional[str] = None) -> List[SloRule]:
    """Rules from ``<home>/slo_rules.json`` when present, else the
    defaults. A broken file falls back loudly (typed event) rather
    than silently disabling the watchdog."""
    if path is None:
        from skypilot_tpu.utils import paths
        path = os.path.join(paths.home(), RULES_FILENAME)
    if not os.path.exists(path):
        return list(DEFAULT_RULES)
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
        return [SloRule.from_dict(d) for d in raw]
    except (OSError, ValueError, TypeError) as e:
        tracing.add_event("slo.rules_invalid",
                          attrs={"path": path,
                                 "error_type": type(e).__name__,
                                 "message": str(e)[:500]},
                          echo=True)
        return list(DEFAULT_RULES)


# ---------------------------------------------------------------------------
# Evaluation.

Snapshot = Tuple[float, Dict[str, dict], List[Dict[str, Any]]]
#          (ts,    families,             components)


def _excluded_fn(rule: SloRule) -> Callable[[Dict[str, str]], bool]:
    def excluded(labels: Dict[str, str]) -> bool:
        return any(labels.get(k) in vals
                   for k, vals in rule.exclude_labels.items())
    return excluded


def _match_fn(rule: SloRule) -> Callable[[Dict[str, str]], bool]:
    excluded = _excluded_fn(rule)

    def ok(labels: Dict[str, str]) -> bool:
        if excluded(labels):
            return False
        for k, v in rule.labels.items():
            if labels.get(k) != v:
                return False
        for k, pfx in rule.label_prefix.items():
            if not str(labels.get(k, "")).startswith(pfx):
                return False
        return True
    return ok


def _window_start(history: List[Snapshot], now: float,
                  window_s: float) -> Optional[Snapshot]:
    """The newest snapshot at least ``window_s`` old (the window's
    left edge); None when history doesn't reach back that far."""
    best = None
    for snap in history:
        if now - snap[0] >= window_s:
            best = snap
        else:
            break
    return best


def _eval_window(rule: SloRule, start: Optional[Snapshot],
                 end: Snapshot) -> Optional[float]:
    """The rule's measured value over one window; None = not enough
    data (never a breach)."""
    ts, families, components = end
    prev = start[1] if start is not None else None
    span = ts - start[0] if start is not None else None
    if rule.kind == "component_dead":
        return float(sum(1 for c in components
                         if c["status"] == health.DEAD))
    if rule.kind == "heartbeat_staleness":
        # Worst instance, not freshest: agg="max" would let one fresh
        # skylet mask every wedged sibling forever.
        last = aggregate.sample_value(families, rule.metric, agg="min")
        if not last:
            return None
        return ts - last
    if rule.kind == "histogram_quantile":
        if prev is None:
            return None
        count = aggregate.delta(prev, families, rule.metric,
                                sample_name=f"{rule.metric}_count")
        if count is None or count < rule.min_events:
            return None
        return aggregate.histogram_quantile(prev, families, rule.metric,
                                            rule.quantile)
    if rule.kind == "ratio":
        if prev is None:
            return None
        num = aggregate.filtered_delta(prev, families, rule.metric,
                              _match_fn(rule))
        denom_metric = rule.denominator or rule.metric
        excluded = _excluded_fn(rule)
        denom = aggregate.filtered_delta(prev, families, denom_metric,
                                lambda labels: not excluded(labels))
        if num is None or not denom or denom < rule.min_events:
            return None
        return num / denom
    if rule.kind == "rate":
        if prev is None or not span:
            return None
        inc = aggregate.filtered_delta(prev, families, rule.metric,
                              _match_fn(rule))
        if inc is None:
            return None
        return inc / span
    if rule.kind == "train_step_regression":
        if prev is None:
            return None
        n = aggregate.delta(prev, families, rule.metric,
                            sample_name=f"{rule.metric}_count")
        s = aggregate.delta(prev, families, rule.metric,
                            sample_name=f"{rule.metric}_sum")
        baseline = aggregate.sample_value(families, rule.baseline_metric,
                                          agg="max")
        if not n or n < rule.min_events or s is None or not baseline:
            return None
        return (s / n) / baseline
    if rule.kind == "hbm_headroom":
        # Instantaneous gauge ratio: ledger components over the limit.
        # sample_value has no exclusion filter, so walk the family by
        # hand — exclude_labels drops the occupancy views that overlap
        # the capacity components. Each serving instance has its OWN
        # HBM: federated gauges arrive instance-labeled (never summed),
        # so group by instance and page on the worst ratio — summing
        # across replicas would breach on fleet size, not memory.
        fam = families.get(rule.metric)
        lim_fam = families.get(rule.baseline_metric)
        if fam is None or lim_fam is None:
            return None
        excluded = _excluded_fn(rule)
        inst_l = aggregate.INSTANCE_LABEL
        used: Dict[str, float] = {}
        for labels, value in fam["samples"]:
            if "__name__" in labels or excluded(labels):
                continue
            inst = labels.get(inst_l, "")
            used[inst] = used.get(inst, 0.0) + value
        limits: Dict[str, float] = {}
        for labels, value in lim_fam["samples"]:
            if "__name__" in labels:
                continue
            inst = labels.get(inst_l, "")
            limits[inst] = max(limits.get(inst, 0.0), value)
        fallback = max(limits.values(), default=0.0)
        ratios = [u / (limits.get(inst) or fallback)
                  for inst, u in used.items()
                  if limits.get(inst) or fallback]
        return max(ratios) if ratios else None
    return None


_INSTANT_KINDS = ("component_dead", "heartbeat_staleness",
                  "hbm_headroom")


def evaluate_rule(rule: SloRule, history: List[Snapshot]
                  ) -> Tuple[bool, Optional[float], Optional[float]]:
    """Multi-window verdict: ``(breached, short_value, long_value)``.
    Instantaneous kinds read the latest snapshot only; windowed kinds
    breach when BOTH windows exceed the threshold."""
    if not history:
        return False, None, None
    end = history[-1]
    now = end[0]
    if rule.kind in _INSTANT_KINDS:
        v = _eval_window(rule, None, end)
        return (v is not None and v > rule.threshold), v, v
    short = _eval_window(
        rule, _window_start(history, now, rule.short_window_s), end)
    long_ = _eval_window(
        rule, _window_start(history, now, rule.long_window_s), end)
    breached = (short is not None and short > rule.threshold
                and long_ is not None and long_ > rule.threshold)
    return breached, short, long_


class Watchdog:
    """Periodically snapshots the fleet, evaluates rules, and emits
    ``slo.breach``/``slo.recovered`` typed events on transitions.

    ``snapshot_fn`` returns ``(families, components)``; the default
    federates over :func:`aggregate.discover_endpoints` and runs the
    health model — the API server installs its own that includes its
    in-process registry.
    """

    def __init__(self, rules: Optional[List[SloRule]] = None,
                 interval_s: float = 15.0,
                 snapshot_fn: Optional[Callable[
                     [], Tuple[Dict[str, dict],
                               List[Dict[str, Any]]]]] = None,
                 history_s: float = 900.0):
        self.rules = list(rules) if rules is not None else load_rules()
        self.interval_s = interval_s
        self._snapshot_fn = snapshot_fn or self._default_snapshot
        self._history_s = history_s
        self._history: List[Snapshot] = []            # guarded-by: _lock
        self._active: Dict[str, Dict[str, Any]] = {}  # guarded-by: _lock
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def _default_snapshot():
        snap = aggregate.federate(aggregate.discover_endpoints())
        return snap.families, health.fleet_health()

    # -- evaluation --------------------------------------------------------
    def observe(self, families: Dict[str, dict],
                components: List[Dict[str, Any]],
                ts: Optional[float] = None) -> List[Dict[str, Any]]:
        """Feed one snapshot and evaluate every rule. Returns the
        transition events emitted this pass (tests drive this
        directly; the thread loop calls it with fresh federation)."""
        ts = time.time() if ts is None else ts
        snap: Snapshot = (ts, families, components)
        transitions: List[Dict[str, Any]] = []
        with self._lock:
            self._history.append(snap)
            cutoff = ts - self._history_s
            while len(self._history) > 2 and self._history[0][0] < cutoff:
                self._history.pop(0)
            history = list(self._history)
        SLO_EVALUATIONS.inc()
        for rule in self.rules:
            breached, short, long_ = evaluate_rule(rule, history)
            attrs = {"rule": rule.name, "kind": rule.kind,
                     "threshold": rule.threshold,
                     "short_window_value": short,
                     "long_window_value": long_}
            if rule.kind == "component_dead":
                attrs["dead_components"] = [
                    f"{c['component']}/{c['instance']}"
                    for c in components
                    if c["status"] == health.DEAD][:10]
            with self._lock:
                was_active = rule.name in self._active
                if breached and not was_active:
                    self._active[rule.name] = {
                        "rule": rule.name, "since": ts, "attrs": attrs}
                elif not breached and was_active:
                    del self._active[rule.name]
            if breached and not was_active:
                SLO_BREACHES.labels(rule=rule.name).inc()
                SLO_ACTIVE.labels(rule=rule.name).set(1)
                # Incident snapshot (observability/forensics.py): the
                # breach TRANSITION is the one moment the evidence —
                # flight-ring tail, recent events, metrics, pinned
                # tail exemplars — is still in memory; capture it to a
                # GC'd bundle and link the dir from the breach event
                # (`skytpu incidents show <name>` reads it back).
                # Contained: a full disk must not kill the watchdog.
                try:
                    inc = forensics.capture_incident(
                        rule.name, attrs,
                        health={"components": components})
                except Exception:  # noqa: BLE001
                    inc = None
                if inc:
                    attrs["incident"] = os.path.basename(inc)
                tracing.add_event("slo.breach", attrs=attrs, echo=True)
                transitions.append({"event": "slo.breach", **attrs})
            elif not breached and was_active:
                SLO_ACTIVE.labels(rule=rule.name).set(0)
                tracing.add_event("slo.recovered", attrs=attrs,
                                  echo=True)
                transitions.append({"event": "slo.recovered", **attrs})
        return transitions

    def tick(self) -> List[Dict[str, Any]]:
        """One full pass: snapshot + evaluate. Snapshot failures are
        contained (a watchdog that dies of a scrape error watches
        nothing)."""
        try:
            families, components = self._snapshot_fn()
        except Exception as e:  # noqa: BLE001
            tracing.add_event("slo.snapshot_failed",
                              attrs={"error_type": type(e).__name__,
                                     "message": str(e)[:500]},
                              echo=True)
            return []
        return self.observe(families, components)

    def active_alerts(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(v) for v in self._active.values()]

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="slo-watchdog", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.tick()
            try:
                tracing.flush_periodic(min_new_records=64,
                                       max_age_s=self.interval_s * 2)
            except OSError:
                pass
