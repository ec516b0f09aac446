"""Timeline tracing, usage telemetry, callbacks, benchmark subsystem."""

import json
import os
import time

import pytest
from click.testing import CliRunner

import skypilot_tpu.callbacks as sky_callback
from skypilot_tpu.usage import usage_lib
from skypilot_tpu.utils import timeline


def test_timeline_disabled_is_noop(tmp_path, monkeypatch):
    monkeypatch.delenv(timeline.ENV_VAR, raising=False)
    # (An earlier test file on this worker may have traced on purpose.)
    timeline._events.clear()

    @timeline.event
    def f():
        return 42

    assert f() == 42
    assert not timeline._events


def test_timeline_records_and_saves(tmp_path, monkeypatch):
    out = tmp_path / "trace.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(out))
    timeline._events.clear()

    @timeline.event(name="my-op")
    def f():
        time.sleep(0.01)
        return 1

    f()
    with timeline.Event("manual", message="hello"):
        pass
    timeline.save_now()
    data = json.loads(out.read_text())
    names = [e["name"] for e in data["traceEvents"]]
    assert "my-op" in names and "manual" in names
    evt = next(e for e in data["traceEvents"] if e["name"] == "my-op")
    assert evt["ph"] == "X" and evt["dur"] >= 10_000  # >= 10ms in us


def test_filelock_event(tmp_path, monkeypatch):
    monkeypatch.setenv(timeline.ENV_VAR, str(tmp_path / "t.json"))
    with timeline.FileLockEvent(str(tmp_path / "x.lock")):
        pass
    assert any("filelock.acquire" in e["name"] for e in timeline._events)


def test_usage_sink_local(tmp_path, monkeypatch):
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path))
    monkeypatch.delenv(usage_lib.DISABLE_ENV, raising=False)
    monkeypatch.delenv(usage_lib.ENDPOINT_ENV, raising=False)
    with usage_lib.entrypoint_context("launch", cloud="gcp") as msg:
        msg.set("num_nodes", 4)
    rec = json.loads((tmp_path / "usage" / "usage.jsonl")
                     .read_text().strip().splitlines()[-1])
    assert rec["kind"] == "launch"
    assert rec["num_nodes"] == 4 and rec["cloud"] == "gcp"
    assert rec["exception"] is None and rec["schema_version"] == 1


def test_usage_opt_out(tmp_path, monkeypatch):
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path))
    monkeypatch.setenv(usage_lib.DISABLE_ENV, "1")
    with usage_lib.entrypoint_context("launch"):
        pass
    assert not (tmp_path / "usage").exists()


def test_usage_records_exception(tmp_path, monkeypatch):
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path))
    monkeypatch.delenv(usage_lib.DISABLE_ENV, raising=False)
    with pytest.raises(ValueError):
        with usage_lib.entrypoint_context("down"):
            raise ValueError("x")
    rec = json.loads((tmp_path / "usage" / "usage.jsonl")
                     .read_text().strip().splitlines()[-1])
    assert rec["exception"] == "ValueError"


def test_callbacks_summary(tmp_path, monkeypatch):
    monkeypatch.setenv("SKYTPU_CALLBACK_LOG_DIR", str(tmp_path))
    sky_callback.init(total_steps=10, warmup_steps=1)
    for _ in range(3):
        with sky_callback.step():
            time.sleep(0.005)
    s = sky_callback.summary()
    assert s["steps"] == 3
    assert s["avg_step_s"] >= 0.004     # warmup step excluded
    assert s["eta_s"] is not None
    sky_callback.write_summary()
    on_disk = json.loads((tmp_path / sky_callback.SUMMARY_FILE).read_text())
    assert on_disk["steps"] == 3


def test_benchmark_state_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path))
    from skypilot_tpu.benchmark import benchmark_state as bs
    bs.add_benchmark("b1", "{}")
    bs.add_result("b1", "c0", "local:tpu-v5e-8", 1.2)
    bs.finish_result("b1", "c0", 600.0, metrics={"steps": 5})
    bs.set_benchmark_status("b1", "FINISHED")
    assert bs.list_benchmarks()[0]["status"] == "FINISHED"
    (row,) = bs.get_results("b1")
    assert row["duration_s"] == 600.0 and row["metrics"]["steps"] == 5
    bs.delete_benchmark("b1")
    assert bs.get_results("b1") == []


def test_benchmark_launch_local(tmp_path, monkeypatch):
    """End-to-end bench over the local fake cloud, two candidates."""
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path))
    from skypilot_tpu.benchmark import benchmark_utils
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.task import Task
    task = Task(run="echo bench-ok", name="b")
    task.set_resources(Resources.from_yaml_config(
        {"cloud": "local", "accelerators": "tpu-v5e-8"}))
    results = benchmark_utils.launch_benchmark(
        "bench-e2e", task, [{}, {"accelerators": "tpu-v5e-8"}])
    assert all(r["status"] == "FINISHED" for r in results)
    rows = benchmark_utils.summarize("bench-e2e")
    assert len(rows) == 2
    assert all(r["cost"] >= 0 for r in rows)


# -- metrics integration (observability PR) ---------------------------------

def _hist_count(hist):
    return sum(sum(child.hist_state()[0]) for _, child in hist.children())


def _counter_total(counter):
    return sum(child.value for _, child in counter.children())


def test_engine_records_ttft_and_slot_occupancy():
    import jax

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama

    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    ttft0 = _hist_count(eng.TTFT_SECONDS)
    prefill0 = _counter_total(eng.PREFILL_REQUESTS)
    decode0 = eng.DECODE_TOKENS._require_default().value
    finished0 = eng.REQUESTS_FINISHED._require_default().value

    e = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                            prompt_buckets=(16,))
    assert eng.SLOTS_TOTAL._require_default().value == 2
    e.add_request([3, 17, 42], max_new_tokens=48)
    e.add_request([5, 9], max_new_tokens=48)
    e.step()                      # prefill both -> slots occupied
    assert eng.SLOTS_ACTIVE._require_default().value == 2
    assert _hist_count(eng.TTFT_SECONDS) == ttft0 + 2
    assert _counter_total(eng.PREFILL_REQUESTS) == prefill0 + 2
    # Per-request TTFT was observed from submit time, so every sample
    # is positive and the histogram sum moved.
    while e.slot_req or e.waiting:
        e.step()
    assert eng.SLOTS_ACTIVE._require_default().value == 0
    assert eng.REQUESTS_FINISHED._require_default().value == finished0 + 2
    assert eng.DECODE_TOKENS._require_default().value > decode0
    assert _hist_count(eng.DECODE_STEP_SECONDS) > 0
    assert _hist_count(eng.TPOT_SECONDS) >= 2


def test_engine_prefill_bucket_labels():
    # (The wave-size histogram went with ISSUE 25: a wave's real and
    # padded rows are arguments of its ``engine.wave.dispatch``
    # annotation — tests/test_trace_annotations.py.)
    import jax

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama

    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(1), cfg)
    e = eng.InferenceEngine(params, cfg, n_slots=4, max_len=64,
                            prompt_buckets=(8, 16))
    e.generate([[1, 2, 3], [4, 5]], max_new_tokens=2)
    # Prefill latency histograms are labeled by prompt bucket.
    labels = {v for v, _ in eng.PREFILL_SECONDS.children()}
    assert ("8",) in labels


def test_timeline_save_is_atomic_and_repeatable(tmp_path, monkeypatch):
    out = tmp_path / "trace.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(out))
    timeline._events.clear()
    with timeline.Event("one"):
        pass
    timeline.save_now()
    first = json.loads(out.read_text())
    with timeline.Event("two"):
        pass
    timeline.save_now()
    timeline.save_now()           # repeat is safe, full buffer each time
    data = json.loads(out.read_text())
    names = [e["name"] for e in data["traceEvents"]]
    assert "one" in names and "two" in names
    assert len(data["traceEvents"]) >= len(first["traceEvents"])
    # No stranded temp files from the atomic replace.
    leftovers = [p for p in os.listdir(tmp_path)
                 if p != "trace.json" and p.startswith("trace.json")]
    assert leftovers == []


def test_timeline_real_thread_ids_and_names(tmp_path, monkeypatch):
    import threading

    monkeypatch.setenv(timeline.ENV_VAR, str(tmp_path / "t.json"))
    timeline._events.clear()
    timeline._named_tids.clear()

    def record():
        with timeline.Event("in-thread"):
            pass

    t = threading.Thread(target=record, name="worker-thread")
    t.start()
    t.join()
    with timeline.Event("in-main"):
        pass
    spans = {e["name"]: e for e in timeline._events if e["ph"] == "X"}
    # Real (unfolded) idents: the two threads get distinct tids.
    assert spans["in-thread"]["tid"] != spans["in-main"]["tid"]
    meta = [e for e in timeline._events
            if e["ph"] == "M" and e["name"] == "thread_name"]
    by_tid = {e["tid"]: e["args"]["name"] for e in meta}
    assert by_tid[spans["in-thread"]["tid"]] == "worker-thread"
    assert spans["in-main"]["tid"] in by_tid


def test_timeline_thread_name_not_inherited_on_ident_reuse(
        tmp_path, monkeypatch):
    """CPython reuses thread idents; a recycled ident must re-emit name
    metadata instead of inheriting the dead thread's track name."""
    import threading

    monkeypatch.setenv(timeline.ENV_VAR, str(tmp_path / "t.json"))
    timeline._events.clear()
    timeline._named_tids.clear()
    cur = threading.current_thread()
    old = cur.name
    try:
        cur.name = "incarnation-1"   # same ident, two names = reuse
        with timeline.Event("a"):
            pass
        cur.name = "incarnation-2"
        with timeline.Event("b"):
            pass
    finally:
        cur.name = old
    meta = [e for e in timeline._events
            if e["ph"] == "M" and e["name"] == "thread_name"]
    assert [e["args"]["name"] for e in meta] == \
        ["incarnation-1", "incarnation-2"]
    timeline._events.clear()
    timeline._named_tids.clear()


def test_timeline_trim_drops_stale_thread_metadata(tmp_path, monkeypatch):
    """Under thread churn, name metadata of threads whose spans aged out
    of the capped buffer must not accumulate without bound."""
    import threading

    monkeypatch.setenv(timeline.ENV_VAR, str(tmp_path / "t.json"))
    timeline._events.clear()
    timeline._named_tids.clear()
    monkeypatch.setattr(timeline, "_MAX_EVENTS", 40)

    def record():
        with timeline.Event("churn"):
            pass

    for i in range(120):
        t = threading.Thread(target=record, name=f"w{i}")
        t.start()
        t.join()
    assert len(timeline._events) <= 2 * 40
    meta_tids = {e["tid"] for e in timeline._events if e["ph"] == "M"}
    span_tids = {e["tid"] for e in timeline._events if e["ph"] != "M"}
    assert meta_tids <= span_tids     # no orphaned thread names
    timeline._events.clear()
    timeline._named_tids.clear()


def test_timeline_flush_skips_clean_buffer(tmp_path, monkeypatch):
    """A daemon flushing every tick must not re-serialize an unchanged
    buffer: after a flush with no new events, the file is untouched."""
    out = tmp_path / "t.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(out))
    timeline._events.clear()
    timeline._named_tids.clear()
    with timeline.Event("tick-span"):
        pass
    timeline.save_now()
    sentinel = '{"traceEvents": [], "sentinel": true}'
    out.write_text(sentinel)
    timeline.save_now()                    # clean buffer -> no rewrite
    assert out.read_text() == sentinel
    with timeline.Event("tick-span-2"):    # dirty again -> rewrites
        pass
    timeline.save_now()
    names = [e["name"] for e in
             json.loads(out.read_text())["traceEvents"]]
    assert "tick-span-2" in names
    timeline._events.clear()
    timeline._named_tids.clear()


def test_job_queue_state_gauges(tmp_path):
    from skypilot_tpu.runtime import job_queue

    db = str(tmp_path / "jobs.db")
    jid = job_queue.add_job(db, "j", "echo hi")
    t_before = job_queue.JOB_TRANSITIONS.labels(status="RUNNING").value
    job_queue.set_status(db, jid, job_queue.JobStatus.RUNNING)
    counts = job_queue.update_state_gauges(db)
    assert counts["RUNNING"] == 1
    assert job_queue.JOBS_BY_STATE.labels(status="RUNNING").value == 1
    # Every status gets a (possibly zero) sample so scrapes see
    # transitions back to zero.
    assert set(counts) == {s.value for s in job_queue.JobStatus}
    assert counts["PENDING"] == 0
    assert (job_queue.JOB_TRANSITIONS.labels(status="RUNNING").value
            == t_before + 1)
    # An unreadable DB must never take a daemon tick down.
    bad = job_queue.update_state_gauges(str(tmp_path / "no" / "x.db"))
    assert set(bad) == {s.value for s in job_queue.JobStatus}
    # A no-op UPDATE (unknown job) records no transition.
    t_ghost = job_queue.JOB_TRANSITIONS.labels(status="FAILED").value
    job_queue.set_status(db, 999, job_queue.JobStatus.FAILED)
    assert (job_queue.JOB_TRANSITIONS.labels(status="FAILED").value
            == t_ghost)


def test_managed_jobs_terminal_counter(tmp_path, monkeypatch):
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path))
    from skypilot_tpu.jobs import state as jobs_state

    c = jobs_state.MANAGED_TERMINAL.labels(status="SUCCEEDED")
    before = c.value
    jid = jobs_state.add("m", {"run": "true"}, "FAILOVER")
    jobs_state.set_status(jid, jobs_state.ManagedJobStatus.SUCCEEDED)
    assert c.value == before + 1
    # First-wins: a late terminal write does not apply, so no count.
    cancelled = jobs_state.MANAGED_TERMINAL.labels(status="CANCELLED")
    cancelled_before = cancelled.value
    jobs_state.set_status(jid, jobs_state.ManagedJobStatus.CANCELLED)
    assert cancelled.value == cancelled_before
    assert c.value == before + 1


def test_skylet_tick_heartbeat_and_trace_flush(tmp_path, monkeypatch):
    from skypilot_tpu.runtime import job_queue, skylet

    out = tmp_path / "skylet-trace.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(out))
    timeline._events.clear()
    with timeline.Event("skylet-span"):
        pass
    # Age out the throttle: the tick's flush is periodic, not per-event.
    monkeypatch.setattr(timeline, "_last_flush_s", 0.0)
    db = str(tmp_path / "jobs.db")
    job_queue.add_job(db, "j", "echo hi")
    ticks0 = skylet.SKYLET_TICKS._require_default().value
    t0 = time.time()
    skylet.observe_tick(db)
    assert skylet.SKYLET_TICKS._require_default().value == ticks0 + 1
    hb = skylet.SKYLET_HEARTBEAT._require_default().value
    assert t0 <= hb <= time.time()
    assert job_queue.JOBS_BY_STATE.labels(status="PENDING").value >= 1
    # The tick flushed the trace buffer atomically.
    names = [e["name"] for e in
             json.loads(out.read_text())["traceEvents"]]
    assert "skylet-span" in names
    skylet.observe_tick(db)       # idempotent: daemons tick forever
    # An unwritable trace path must not take the tick down either.
    with timeline.Event("skylet-span-2"):
        pass                      # dirty buffer: the flush is attempted
    monkeypatch.setattr(timeline, "_last_flush_s", 0.0)
    blocked = tmp_path / "blocked"
    blocked.write_text("")        # a FILE where a directory is needed
    monkeypatch.setenv(timeline.ENV_VAR, str(blocked / "nested.json"))
    skylet.observe_tick(db)


def test_save_periodic_throttles_full_buffer_rewrites(tmp_path,
                                                      monkeypatch):
    """Per-tick daemon flushes re-serialize the whole buffer; the
    throttled entry point skips until enough news or enough age."""
    out = tmp_path / "t.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(out))
    timeline._events.clear()
    timeline._named_tids.clear()
    with timeline.Event("first"):
        pass
    timeline.save_now()           # flush: _last_flush_s is now fresh
    with timeline.Event("second"):
        pass
    timeline.save_periodic(min_new_events=100, max_age_s=60.0)
    names = [e["name"] for e in
             json.loads(out.read_text())["traceEvents"]]
    assert "second" not in names  # few events + fresh flush: skipped
    timeline.save_periodic(min_new_events=1, max_age_s=60.0)
    names = [e["name"] for e in
             json.loads(out.read_text())["traceEvents"]]
    assert "second" in names      # enough pending events: flushed
    with timeline.Event("third"):
        pass
    monkeypatch.setattr(timeline, "_last_flush_s", 0.0)
    timeline.save_periodic(min_new_events=100, max_age_s=60.0)
    names = [e["name"] for e in
             json.loads(out.read_text())["traceEvents"]]
    assert "third" in names       # stale last flush: age triggers
    timeline._events.clear()
    timeline._named_tids.clear()
