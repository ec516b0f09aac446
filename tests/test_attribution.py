"""Device-truth attribution (ISSUE 16): the sampled device-time
calibrator, the analytical HBM ledger (+ the memory_stats fallback and
the leak audit), the roofline cost model, the bubble analyzer, the
hbm-headroom SLO rule, and the `skytpu top` / `skytpu flight` wiring.
"""

import json

import jax
import numpy as np
import pytest

from skypilot_tpu.infer import engine as eng
from skypilot_tpu.models import llama
from skypilot_tpu.observability import attribution
from skypilot_tpu.observability import flight as fl
from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.observability import slo, tracing


def _counter_total(snap, name):
    if name not in snap:
        return 0.0
    return sum(s.get("value", s.get("count", 0))
               for s in snap[name]["samples"])


def _gauge_value(name, **labels):
    snap = metrics_lib.REGISTRY.snapshot()
    if name not in snap:
        return None
    for s in snap[name]["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return None


# ---------------------------------------------------------------------------
# (a) The device-time calibrator.

def test_devtime_every_env(monkeypatch):
    monkeypatch.delenv("SKYTPU_DEVTIME_EVERY", raising=False)
    assert attribution.devtime_every() == 64
    monkeypatch.setenv("SKYTPU_DEVTIME_EVERY", "8")
    assert attribution.devtime_every() == 8
    monkeypatch.setenv("SKYTPU_DEVTIME_EVERY", "0")
    assert attribution.devtime_every() == 0
    monkeypatch.setenv("SKYTPU_DEVTIME_EVERY", "nonsense")
    assert attribution.devtime_every() == 64


def test_tick_cadence_first_dispatch_then_every_nth():
    cal = attribution.DeviceTimeCalibrator(every=4)
    got = [cal.tick("prog[a]") for _ in range(9)]
    # The first post-compile dispatch seeds the EWMA, then every 4th.
    assert got == [True, False, False, False,
                   True, False, False, False, True]
    # Keys count independently.
    assert cal.tick("prog[b]") is True


def test_tick_off_and_suppressed():
    cal = attribution.DeviceTimeCalibrator(every=0)
    assert not any(cal.tick("p") for _ in range(8))
    cal2 = attribution.DeviceTimeCalibrator(every=1)
    with metrics_lib.suppress():
        # Warmup sweeps never sample: a bracket would serialize the
        # sweep and poison the EWMA with compile-adjacent timings.
        assert cal2.tick("p") is False
    assert cal2.tick("p") is True


def test_ewma_update_estimate_and_metrics():
    before = metrics_lib.REGISTRY.snapshot()
    cal = attribution.DeviceTimeCalibrator(every=1, alpha=0.25)
    cal.update("prog[x]", 0.100)
    assert cal.estimate("prog[x]") == pytest.approx(0.100)
    cal.update("prog[x]", 0.200)
    # EWMA: prev + alpha * (x - prev).
    assert cal.estimate("prog[x]") == pytest.approx(0.125)
    assert cal.estimate("prog[never]") is None
    assert cal.estimate(None) is None
    after = metrics_lib.REGISTRY.snapshot()
    assert _counter_total(after, "skytpu_devtime_calibrations_total") \
        - _counter_total(before, "skytpu_devtime_calibrations_total") \
        == 2
    assert _gauge_value("skytpu_devtime_ewma_ms", program="prog[x]") \
        == pytest.approx(125.0)
    summ = cal.summary()
    assert summ["prog[x]"]["dev_ms"] == pytest.approx(125.0)
    assert summ["prog[x]"]["age_s"] >= 0


def test_timed_call_brackets_and_returns():
    cal = attribution.DeviceTimeCalibrator(every=1)
    out = cal.timed_call("prog[y]", lambda a, b: a + b,
                         np.ones(4), np.ones(4))
    np.testing.assert_array_equal(out, np.full(4, 2.0))
    assert cal.estimate("prog[y]") is not None
    assert cal.samples == 1


def test_compile_watch_calibrator_rides_hit_path_only():
    watch = fl.CompileWatch()
    cal = attribution.DeviceTimeCalibrator(every=1)
    watch.calibrator = cal
    wrapped = watch.wrap("prog", lambda x, k=0: np.asarray([x * k]),
                         ("k",))
    wrapped(2, k=3)            # first dispatch = compile, never timed
    assert cal.samples == 0
    assert watch.last_key == "prog[k=3]"
    wrapped(2, k=3)            # hit path: every=1 -> bracketed
    assert cal.samples == 1
    assert cal.estimate("prog[k=3]") is not None


# ---------------------------------------------------------------------------
# (b) The HBM ledger.

def test_ledger_set_snapshot_total_clear():
    led = attribution.HbmLedger()
    led.set_bytes("weights", 1000)
    led.set_bytes("kv_pool", 500)
    led.set_bytes("kv_used", -3)      # clamped, never negative
    assert led.snapshot() == {"weights": 1000, "kv_pool": 500,
                              "kv_used": 0}
    assert led.total() == 1500
    assert _gauge_value("skytpu_hbm_bytes", component="weights") == 1000
    led.clear()
    assert led.snapshot() == {} and led.total() == 0
    assert _gauge_value("skytpu_hbm_bytes", component="weights") == 0


def test_memstats_unavailable_typed_event_once():
    led = attribution.HbmLedger()

    class _NoStats:
        platform = "cpu"

    def _events():
        return [r for r in tracing.buffered_records()
                if r.get("name") == "attribution.memstats_unavailable"]

    n0 = len(_events())
    assert led.cross_check(device=_NoStats()) is None
    assert len(_events()) == n0 + 1
    # Once per ledger — never a per-refresh event storm.
    assert led.cross_check(device=_NoStats()) is None
    assert len(_events()) == n0 + 1


def test_memstats_cross_check_publishes():
    led = attribution.HbmLedger()

    class _Dev:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_in_use": 123456, "bytes_limit": 1000000}

    out = led.cross_check(device=_Dev())
    assert out == {"bytes_in_use": 123456, "bytes_limit": 1000000}
    assert _gauge_value("skytpu_hbm_device_bytes_in_use") == 123456
    assert _gauge_value("skytpu_hbm_limit_bytes") == 1000000


# ---------------------------------------------------------------------------
# (c) The roofline cost model.

def _roofline():
    return attribution.Roofline(
        param_count=1000, weight_bytes=2000, kv_token_bytes=16,
        d_model=8, n_layers=2, n_heads=2, head_dim=4, max_len=128,
        chunk_tokens=8)


def test_roofline_decode_burst():
    # k x rows tokens, k weight passes. attn = 4*L*nh*hd = 64 / token
    # / span row.
    flops, moved = _roofline().record_cost(
        "decode", {"k": 2, "span": 32}, 3, 6)
    assert flops == 2 * 1000 * 6 + 64 * 32 * 6
    assert moved == 2 * 2000 + 2 * 3 * 32 * 16 + 6 * 16


def test_roofline_wave_chunk_verify():
    rl = _roofline()
    flops, moved = rl.record_cost("wave", {"rows": 2, "bucket": 16},
                                  2, 2)
    # Causal prefill: rows*bucket tokens at mean span bucket/2.
    assert flops == 2 * 1000 * 32 + 64 * 8 * 32
    assert moved == 2000 + 2 * 8 * 16 + 32 * 16
    flops, moved = rl.record_cost("chunk", {"span": 64}, 1, 0)
    assert flops == 2 * 1000 * 8 + 64 * 64 * 8
    assert moved == 2000 + 64 * 16 + 8 * 16
    flops, moved = rl.record_cost("verify", {"k": 2, "span": 32}, 2, 4)
    assert flops == 2 * 1000 * 6 + 64 * 32 * 6
    assert moved == 2000 + 2 * 32 * 16 + 6 * 16


def test_roofline_unknown_burst_costs_nothing():
    assert _roofline().record_cost("preempt", {}, 1, 0) == (0, 0)


def test_device_peaks_env_override(monkeypatch):
    monkeypatch.setenv("SKYTPU_PEAK_TFLOPS", "918")
    monkeypatch.setenv("SKYTPU_PEAK_GBPS", "1638")
    f, b = attribution.device_peaks()
    assert f == pytest.approx(918e12)
    assert b == pytest.approx(1638e9)


# ---------------------------------------------------------------------------
# Bubble analysis.

def _rec(ts, dur, burst, **kw):
    r = {"kind": "flight", "ts_s": ts, "dur_s": dur, "burst": burst,
         "program": {}, "toks": 0}
    r.update(kw)
    return r


def _synthetic_window():
    return [
        _rec(0.000, 0.010, "wave"),
        _rec(0.015, 0.008, "chunk"),                     # 5ms admission
        _rec(0.026, 0.010, "decode", dev_ms_est=6.0),    # 3ms overhead
        _rec(0.040, 0.010, "verify"),                    # 4ms drafter
        _rec(0.052, 0.010, "decode", priorities={"1": 2}),  # 2ms qos
    ]


def test_analyze_bubbles_attributes_named_causes():
    rep = attribution.analyze_bubbles(_synthetic_window())
    assert rep["n_records"] == 5
    assert set(rep["by_cause"]) <= set(attribution.BUBBLE_CAUSES)
    assert rep["by_cause"]["admission"] == pytest.approx(5.0, abs=1e-6)
    assert rep["by_cause"]["drafter_sync"] == pytest.approx(4.0,
                                                            abs=1e-6)
    assert rep["by_cause"]["qos_reorder"] == pytest.approx(2.0,
                                                           abs=1e-6)
    # Inter-record gap (3ms) + within-record slack (dur 10 - dev 6).
    assert rep["by_cause"]["dispatch_overhead"] == \
        pytest.approx(7.0, abs=1e-6)
    assert rep["device_idle_ms"] == pytest.approx(18.0, abs=1e-6)
    assert rep["device_busy_ms"] == pytest.approx(44.0, abs=1e-6)
    # The acceptance bar: >= 90% of idle attributed to a named cause.
    assert rep["coverage"] >= 0.9
    assert rep["window_ms"] == pytest.approx(62.0, abs=1e-6)


def test_analyze_bubbles_residue_lowers_coverage():
    recs = [_rec(0.0, 0.010, "flush"),
            _rec(0.020, 0.010, "decode")]   # unnameable 10ms gap
    rep = attribution.analyze_bubbles(recs)
    assert rep["by_cause"] == {"host_other": pytest.approx(10.0)}
    assert rep["coverage"] == 0.0


def test_analyze_bubbles_empty_and_single():
    assert attribution.analyze_bubbles([])["coverage"] == 1.0
    rep = attribution.analyze_bubbles([_rec(0.0, 0.01, "decode")])
    assert rep["n_records"] == 1 and rep["bubbles"] == []


def test_idle_spans_are_perfetto_ready():
    spans = attribution.idle_spans(_synthetic_window())
    assert spans and all(s["kind"] == "span" for s in spans)
    names = {s["name"] for s in spans}
    assert "bubble:admission" in names
    assert all(s["end_s"] > s["start_s"] for s in spans)


def test_render_bubbles_report():
    out = attribution.render_bubbles(
        attribution.analyze_bubbles(_synthetic_window()))
    assert "idle by cause" in out
    assert "admission" in out and "largest bubbles" in out


# ---------------------------------------------------------------------------
# The hbm-headroom SLO rule.

def _hbm_rule():
    return next(r for r in slo.DEFAULT_RULES if r.name == "hbm-headroom")


def _hbm_fams(capacity_frac, occupancy_frac=0.3, limit=1000.0):
    return {
        "skytpu_hbm_bytes": {"type": "gauge", "samples": [
            ({"component": "weights"}, limit * capacity_frac * 0.6),
            ({"component": "kv_pool"}, limit * capacity_frac * 0.4),
            ({"component": "kv_used"}, limit * occupancy_frac),
            ({"component": "prefix_pinned"}, limit * occupancy_frac)]},
        "skytpu_hbm_limit_bytes": {"type": "gauge",
                                   "samples": [({}, limit)]},
    }


def test_hbm_headroom_rule_is_default_and_instant():
    rule = _hbm_rule()
    assert rule.kind in slo._INSTANT_KINDS
    # the views: occupancy inside a pool, the experts inside weights
    assert rule.exclude_labels == {"component": [
        "kv_used", "prefix_pinned", "expert_weights"]}


def test_hbm_headroom_excludes_occupancy_views():
    rule = _hbm_rule()
    # Capacity 85% + occupancy views that would naively push the sum
    # past 1.0: the rule must read 0.85 (kv_used lives INSIDE kv_pool
    # — summing both double-counts), so no breach at threshold 0.92.
    wd = slo.Watchdog(rules=[rule])
    assert wd.observe(_hbm_fams(0.85), []) == []
    v = slo._eval_window(rule, None,
                         (0.0, _hbm_fams(0.85), []))
    assert v == pytest.approx(0.85)


def test_hbm_headroom_breaches_and_recovers():
    wd = slo.Watchdog(rules=[_hbm_rule()])
    ev = wd.observe(_hbm_fams(0.95), [])
    assert [e["event"] for e in ev] == ["slo.breach"]
    ev = wd.observe(_hbm_fams(0.5), [])
    assert [e["event"] for e in ev] == ["slo.recovered"]


def test_hbm_headroom_no_limit_no_verdict():
    rule = _hbm_rule()
    fams = _hbm_fams(0.99)
    del fams["skytpu_hbm_limit_bytes"]
    assert slo._eval_window(rule, None, (0.0, fams, [])) is None
    assert slo._eval_window(rule, None, (0.0, {}, [])) is None


# ---------------------------------------------------------------------------
# Engine integration: the ledger leak audit + attribution wiring.

def _tiny_engine(**overrides):
    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    kw = dict(n_slots=4, max_len=128, prompt_buckets=(16, 64),
              prefill_chunk=8, prefix_pool=4, spec_k=0, kv_block=16,
              max_wave=4, pad_waves=True)
    kw.update(overrides)
    return eng.InferenceEngine(params, cfg, **kw)


def _prompts():
    rng = np.random.default_rng(7)
    return ([rng.integers(1, 40, 6).tolist() for _ in range(2)]
            + [rng.integers(1, 40, 20).tolist() for _ in range(2)])


def test_engine_ledger_leak_audit():
    """Admit -> retire -> clear must return every component gauge to
    its post-build baseline: the ledger mirrors the engine's own
    bookkeeping, so a residue here IS a KV/prefix leak."""
    e = _tiny_engine()
    base = e.hbm_ledger.snapshot()
    assert base["weights"] > 0 and base["kv_pool"] > 0
    assert base["workspace"] > 0
    assert base["kv_used"] == 0 and base["prefix_pinned"] == 0
    e.generate(_prompts(), max_new_tokens=6)
    e._refresh_hbm_ledger()
    mid = e.hbm_ledger.snapshot()
    # Capacity components are static for the engine's lifetime.
    for c in ("weights", "kv_pool", "prefix_pool", "draft_pool",
              "adapter_pool", "workspace"):
        assert mid[c] == base[c], c
    # The run left prefixes resident (that's the cache working) —
    # visible as pinned occupancy, not as capacity drift.
    assert mid["prefix_pinned"] > 0
    e.clear_prefix_cache()
    e._refresh_hbm_ledger()
    end = e.hbm_ledger.snapshot()
    assert end == base
    # And the published gauges agree with the snapshot.
    for comp, val in end.items():
        assert _gauge_value("skytpu_hbm_bytes", component=comp) == val


def test_engine_publishes_roofline_peaks_and_limit():
    e = _tiny_engine()
    assert _gauge_value("skytpu_roofline_peak_flops") > 0
    assert _gauge_value("skytpu_roofline_peak_hbm_bytes_per_s") > 0
    # No env override: the limit defaults to 1.25x the build-time
    # ledger total, so headroom starts at 80%.
    lim = _gauge_value("skytpu_hbm_limit_bytes")
    assert lim >= e.hbm_ledger.total()


def test_engine_devtime_calibrates_during_serving(monkeypatch):
    monkeypatch.setenv("SKYTPU_DEVTIME_EVERY", "1")
    e = _tiny_engine(flight_recorder=fl.FlightRecorder())
    seq0 = e.flight.seq()
    e.generate(_prompts(), max_new_tokens=6)
    assert e.devtime.samples > 0
    window = e.flight.since(seq0)
    assert any("dev_ms_est" in r for r in window)
    assert e.devtime.summary()


def test_engine_devtime_off_is_bit_identical(monkeypatch):
    monkeypatch.setenv("SKYTPU_DEVTIME_EVERY", "0")
    e = _tiny_engine()
    out_off = e.generate(_prompts(), max_new_tokens=6)
    assert e.devtime.samples == 0
    monkeypatch.setenv("SKYTPU_DEVTIME_EVERY", "1")
    e2 = _tiny_engine()
    out_on = e2.generate(_prompts(), max_new_tokens=6)
    assert e2.devtime.samples > 0
    assert [list(r) for r in out_off] == [list(r) for r in out_on]


# ---------------------------------------------------------------------------
# CLI wiring: `skytpu top` columns and `skytpu flight --bubbles`.

def test_top_serve_line_mfu_bw_columns():
    from skypilot_tpu.client import cli as cli_mod

    def fams(flops, hbm):
        return {
            "skytpu_http_requests_total": {
                "type": "counter",
                "samples": [({"route": "/generate", "code": "200"},
                             10.0)]},
            "skytpu_device_flops_total": {
                "type": "counter", "samples": [({}, float(flops))]},
            "skytpu_device_hbm_moved_bytes_total": {
                "type": "counter", "samples": [({}, float(hbm))]},
            "skytpu_roofline_peak_flops": {
                "type": "gauge", "samples": [({}, 0.5e12)]},
            "skytpu_roofline_peak_hbm_bytes_per_s": {
                "type": "gauge", "samples": [({}, 50e9)]},
        }

    payload = {"components": [], "alerts": []}
    now = 1000.0
    frame = cli_mod._render_top_frame(
        fams(0, 0), now - 10.0,
        fams(0.35 * 0.5e12 * 10, 0.6 * 50e9 * 10), now, payload)
    serve = next(l for l in frame.splitlines()
                 if l.startswith("serve"))
    assert "mfu 35.0%" in serve
    assert "bw 60.0%" in serve
    # First frame (no prev): the columns are absent, never a lie.
    frame1 = cli_mod._render_top_frame(None, None, fams(1, 1), now,
                                       payload)
    serve1 = next(l for l in frame1.splitlines()
                  if l.startswith("serve"))
    assert "mfu" not in serve1


@pytest.fixture
def fresh_events(tmp_path, monkeypatch):
    monkeypatch.setenv(tracing.EVENTS_DIR_ENV_VAR, str(tmp_path))
    monkeypatch.delenv(tracing.ENV_VAR, raising=False)
    tracing._reset_for_tests()
    yield str(tmp_path)
    tracing._reset_for_tests()


def test_flight_cli_bubbles_and_idle_spans(fresh_events, tmp_path,
                                           monkeypatch):
    from click.testing import CliRunner

    from skypilot_tpu.client import cli as cli_mod

    monkeypatch.setenv("SKYTPU_DEVTIME_EVERY", "1")
    e = _tiny_engine(flight_recorder=fl.FlightRecorder())
    e.generate(_prompts(), max_new_tokens=5)
    e.flight.flush()
    runner = CliRunner()
    res = runner.invoke(cli_mod.cli, ["flight", "--local", "--bubbles"])
    assert res.exit_code == 0, res.output
    assert "idle by cause" in res.output
    assert "% of idle attributed" in res.output
    pf_path = str(tmp_path / "flight.json")
    res2 = runner.invoke(
        cli_mod.cli,
        ["flight", "--local", "--perfetto", pf_path])
    assert res2.exit_code == 0, res2.output
    with open(pf_path, encoding="utf-8") as f:
        pf = json.load(f)
    assert any(ev.get("name", "").startswith("bubble:")
               for ev in pf["traceEvents"])
