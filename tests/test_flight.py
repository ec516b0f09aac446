"""Flight recorder + compile watch: burst records, ring discipline,
the unexpected-compile alarm, metrics<->record consistency, and the
CLI/trace surfaces (docs/observability.md §Flight recorder)."""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import kvcache
from skypilot_tpu.models import llama
from skypilot_tpu.observability import flight as fl
from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.observability import trace_view, tracing


# ---------------------------------------------------------------------------
# Recorder core.

def test_ring_bounded():
    rec = fl.FlightRecorder(capacity=16)
    for i in range(100):
        rec.record("decode", toks=i)
    recs = rec.tail()
    assert len(recs) == 16
    # Oldest dropped, newest kept, seq monotone.
    assert [r["toks"] for r in recs] == list(range(84, 100))
    assert rec.seq() == 100


def test_concurrent_records_thread_safe():
    rec = fl.FlightRecorder(capacity=10_000)
    n_threads, per = 8, 200

    def worker(t):
        for i in range(per):
            rec.record("decode", t=t, i=i)

    ts = [threading.Thread(target=worker, args=(t,))
          for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    recs = rec.tail()
    assert len(recs) == n_threads * per
    # Every record intact and uniquely sequenced.
    assert len({r["seq"] for r in recs}) == n_threads * per


def test_suppress_honored():
    rec = fl.FlightRecorder()
    with metrics_lib.suppress():
        rec.record("decode", toks=1)
    assert rec.tail() == []
    rec.record("decode", toks=1)
    assert len(rec.tail()) == 1


def test_disabled_recorder_is_noop():
    rec = fl.FlightRecorder()
    rec.enabled = False
    rec.record("decode", toks=1)
    assert rec.tail() == [] and rec.seq() == 0
    rec.enabled = True
    rec.record("decode", toks=1)
    assert rec.seq() == 1


def test_env_disable(monkeypatch):
    monkeypatch.setenv("SKYTPU_FLIGHT", "0")
    assert fl.FlightRecorder().enabled is False
    monkeypatch.delenv("SKYTPU_FLIGHT")
    assert fl.FlightRecorder().enabled is True


def test_flush_load_roundtrip_and_corrupt_skip(tmp_path, monkeypatch):
    monkeypatch.setenv(tracing.EVENTS_DIR_ENV_VAR, str(tmp_path))
    rec = fl.FlightRecorder()
    rec.record("decode", ts_s=2.0, toks=3,
               program={"k": 8, "span": 64, "layout": "paged"})
    rec.record("chunk", ts_s=1.0, toks=1,
               program={"final": True, "layout": "paged"})
    rec.flush()
    files = [n for n in os.listdir(tmp_path) if n.startswith("flight-")]
    assert len(files) == 1
    # A torn/corrupt line and a foreign file must be skipped quietly.
    with open(tmp_path / files[0], "a", encoding="utf-8") as f:
        f.write("{not json\n")
    (tmp_path / "flight-foreign-1-2.jsonl").write_text("junk\n{}\n")
    loaded = fl.load_records(dirs=[str(tmp_path)])
    assert [r["burst"] for r in loaded] == ["chunk", "decode"]  # ts order
    # Idempotent flush: nothing new -> no rewrite needed.
    rec.flush()
    assert len([n for n in os.listdir(tmp_path)
                if n.startswith("flight-")]) == 2


# ---------------------------------------------------------------------------
# Compile watch.

def test_compile_watch_keys_costs_and_unexpected():
    watch = fl.CompileWatch()
    calls = []
    wrapped = watch.wrap("prog", lambda *a, **kw: calls.append(kw),
                         ("k", "span"))
    before = metrics_lib.REGISTRY.snapshot()
    wrapped(1, k=8, span=64)
    wrapped(1, k=8, span=64)          # cached key: no new program
    wrapped(1, k=4, span=64)
    assert watch.count == 2
    assert set(watch.summary()) == {"prog[k=8 span=64]",
                                    "prog[k=4 span=64]"}
    assert watch.drain_new() == ["prog[k=8 span=64]",
                                 "prog[k=4 span=64]"]
    assert watch.drain_new() == []
    assert not watch.unexpected and not watch.warm
    after = metrics_lib.REGISTRY.snapshot()

    def delta(name, key="value"):
        def total(snap):
            return sum(s[key] for s in snap[name]["samples"]) \
                if name in snap else 0
        return total(after) - total(before)

    assert delta("skytpu_programs_compiled_total") == 2
    assert delta("skytpu_unexpected_compiles_total") == 0
    # Post-warm compiles alarm: counter + typed echo event.
    watch.declare_warm()
    wrapped(1, k=2, span=None)
    assert watch.unexpected == ["prog[k=2 span=None]"]
    snap3 = metrics_lib.REGISTRY.snapshot()
    assert (sum(s["value"] for s in
                snap3["skytpu_unexpected_compiles_total"]["samples"])
            - sum(s["value"] for s in
                  after["skytpu_unexpected_compiles_total"]["samples"])
            ) == 1
    events = [r for r in tracing.buffered_records()
              if r.get("name") == "engine.unexpected_compile"]
    assert events and events[-1]["attrs"]["program"] == \
        "prog[k=2 span=None]"


def test_compile_watch_key_fn_shape_identity():
    watch = fl.CompileWatch()
    wrapped = watch.wrap("wave", lambda *a, **kw: None, ("bucket",),
                         key_fn=lambda a, kw: (("rows", len(a[0])),))
    wrapped([1, 2], bucket=128)
    wrapped([1, 2, 3], bucket=128)    # same statics, new shape
    assert set(watch.summary()) == {"wave[bucket=128 rows=2]",
                                    "wave[bucket=128 rows=3]"}


# ---------------------------------------------------------------------------
# Engine integration: one tiny engine, the full mixed workload.

def _mk_engine(**overrides):
    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    kw = dict(n_slots=4, max_len=128, prompt_buckets=(16, 64),
              prefill_chunk=8, prefix_pool=4, spec_k=2, kv_block=16,
              max_wave=4, pad_waves=True,
              flight_recorder=fl.FlightRecorder())
    kw.update(overrides)
    return eng.InferenceEngine(params, cfg, **kw)


def _mixed_prompts(n_short=2, n_long=2):
    rng = np.random.default_rng(7)
    shorts = [rng.integers(1, 40, 6).tolist() for _ in range(n_short)]
    longs = [rng.integers(1, 40, 20).tolist() for _ in range(n_long)]
    return shorts + longs


@pytest.fixture(scope="module")
def flown_engine():
    """One engine driven through the mixed workload (waves + chunked
    admission + spec verify + decode bursts), plus the counter
    snapshots around the run — shared by the coverage and consistency
    tests (compile cost paid once)."""
    e = _mk_engine()
    before = metrics_lib.REGISTRY.snapshot()
    seq0 = e.flight.seq()
    prompts = _mixed_prompts()
    ids = [e.add_request(p, max_new_tokens=10) for p in prompts]
    e.run_to_completion(max_burst=4)
    finished = {r.rid: r for r in e.finished}
    after = metrics_lib.REGISTRY.snapshot()
    window = e.flight.since(seq0)
    return e, window, before, after, ids, finished


def _counter_delta(before, after, name):
    def total(snap):
        if name not in snap:
            return 0.0
        return sum(s.get("value", s.get("count", 0))
                   for s in snap[name]["samples"])
    return total(after) - total(before)


def _hist_count_delta(before, after, name):
    def total(snap):
        if name not in snap:
            return 0
        return sum(s["count"] for s in snap[name]["samples"])
    return total(after) - total(before)


def test_every_burst_has_a_record_with_matching_identity(flown_engine):
    e, window, _, _, ids, finished = flown_engine
    kinds = {r["burst"] for r in window}
    assert {"wave", "chunk"} <= kinds
    assert kinds & {"decode", "verify"}
    # Program identity on decode-side records == what the engine
    # actually selected (both directions).
    rec_dv = {(r["program"]["k"], r["program"]["span"])
              for r in window if r["burst"] in ("decode", "verify")}
    eng_dv = {(k, s) for kind, k, s in e.decode_programs
              if kind in ("burst", "verify")}
    assert rec_dv == eng_dv
    # Every decode-side record says how many turns of live tiles a
    # layer took: host arithmetic over its slots.
    for r in window:
        if r["burst"] in ("decode", "verify"):
            assert r["tiles"] == e._tiles(r["burst"], len(r["slots"])) \
                == -(-len(r["slots"]) // kvcache.TILE)
        else:
            assert "tiles" not in r or r["burst"] == "decode1"
    assert e._tiles("decode", 0) == 0
    assert e._tiles("verify", 2 * kvcache.TILE + 1) == 3
    assert e._tiles("decode1", 1) == -(-(e.n_slots + 1) // kvcache.TILE)
    # Layout stamped on every record; host timing sane.
    assert all(r["program"]["layout"] == "paged" for r in window)
    assert all(r["dur_s"] >= 0 and r["ts_s"] > 0 for r in window)
    # Group composition: every record's rids/traces are the member
    # requests', and every finished request appears in some record.
    for r in window:
        assert len(r["rids"]) == len(r["traces"]) <= len(r["slots"]) \
            or r["burst"] in ("wave", "chunk")
        for rid in r["rids"]:
            assert rid in finished
            assert finished[rid].span_ctx.trace_id in r["traces"]
    seen_rids = {rid for r in window for rid in r["rids"]}
    assert set(ids) <= seen_rids
    # The first dispatches compiled: some record carries the compile
    # attribution.
    assert any(r.get("compiled") for r in window)


def test_counter_deltas_match_record_sums(flown_engine):
    """The metrics-consistency gate (ISSUE 10 satellite): over a mixed
    chunk+verify+wave workload, every serving counter's delta equals
    the sum over flight-recorder records — double-counting on any
    path would split them apart."""
    _, window, before, after, _, _ = flown_engine
    chunks = sum(1 for r in window if r["burst"] == "chunk")
    assert _counter_delta(before, after,
                          "skytpu_prefill_chunks_total") == chunks
    decode_toks = sum(r["toks"] for r in window
                      if r["burst"] in ("decode", "verify", "decode1"))
    assert _counter_delta(before, after,
                          "skytpu_decode_tokens_total") == decode_toks
    drafted = sum(r.get("drafted", 0) for r in window)
    accepted = sum(r.get("accepted", 0) for r in window)
    assert _counter_delta(before, after,
                          "skytpu_spec_drafted_total") == drafted
    assert _counter_delta(before, after,
                          "skytpu_spec_accepted_total") == accepted
    assert _counter_delta(
        before, after, "skytpu_spec_rollbacks_total") == \
        drafted - accepted
    # Prefill completions: one wave row or final chunk per request.
    waves_toks = sum(r["toks"] for r in window if r["burst"] == "wave")
    finals = sum(1 for r in window
                 if r["burst"] == "chunk" and r["program"]["final"])
    assert _counter_delta(before, after,
                          "skytpu_prefill_requests_total") == \
        waves_toks + finals
    # Decode-stall observations == records flagged as interference.
    stalls = sum(1 for r in window if r.get("stall"))
    assert _hist_count_delta(before, after,
                             "skytpu_decode_stall_seconds") == stalls
    # Device-truth attribution (ISSUE 16): the roofline counters are
    # incremented on the SAME path that stamps the record fields — a
    # record with a cost and no counter inc (or vice versa) splits
    # these. flops are stamped on every costed burst, so the workload
    # must have produced some.
    flops = sum(r.get("flops", 0) for r in window)
    hbm = sum(r.get("hbm_bytes", 0) for r in window)
    assert flops > 0 and hbm > 0
    assert _counter_delta(before, after,
                          "skytpu_device_flops_total") == flops
    assert _counter_delta(before, after,
                          "skytpu_device_hbm_moved_bytes_total") == hbm
    # dev_ms_est is rounded on the record; the counter takes the raw
    # value — equal to rounding noise.
    dev_s = sum(r.get("dev_ms_est", 0.0) for r in window) / 1e3
    assert _counter_delta(before, after,
                          "skytpu_device_seconds_total") == \
        pytest.approx(dev_s, abs=1e-6)
    # The host-wall split sums back to dur_s exactly wherever present.
    for r in window:
        if "dispatch_wall_ms" in r:
            assert r["dispatch_wall_ms"] >= 0
            assert r["fetch_wall_ms"] >= 0
            assert r["dispatch_wall_ms"] + r["fetch_wall_ms"] == \
                pytest.approx(r["dur_s"] * 1e3, abs=1e-3)
    assert any("dispatch_wall_ms" in r for r in window)


def test_ledger_sums_to_wall(flown_engine):
    """The ledger-sums gate (ISSUE 17): every retired request of the
    mixed workload gets a forensics ledger whose phases sum to the
    measured submit->retire wall (exact partition to rounding), with
    >=90% of the wall in NAMED phases — an unsorted ring or a
    double-counted overlap breaks the sum, a classification hole
    breaks the coverage."""
    from skypilot_tpu.observability import forensics

    _, window, _, _, ids, finished = flown_engine
    retires = [r for r in window if r["burst"] == "retire"]
    assert {r["rids"][0] for r in retires} == set(ids)
    for rid in ids:
        led = forensics.ledger_from_records(rid, window)
        assert led is not None
        total = sum(p["ms"] for p in led["phases"])
        assert total == pytest.approx(led["wall_ms"], abs=0.05), \
            f"rid {rid}: phases sum {total} != wall {led['wall_ms']}"
        assert led["named_ms"] >= 0.90 * led["wall_ms"], \
            f"rid {rid}: named {led['named_ms']} < 90% of " \
            f"{led['wall_ms']}"
        assert led["named_ms"] + led["other_ms"] == \
            pytest.approx(led["wall_ms"], abs=0.05)
        # The retire record mirrors the request's own stamps.
        req = finished[rid]
        assert led["wall_ms"] > 0
        assert led["detail"]["n_toks"] == len(req.tokens)
        # Renders without crashing, names the request.
        assert f"request {rid}" in forensics.render_ledger(led)


def test_chunk_verify_interleave_consistency():
    """The ISSUE-named audit path: chunked prefills interleaving with
    LIVE speculative verify bursts (small vocab => the drafter
    actually drafts). Counter deltas must equal flight-record sums —
    a double count on either side of the interleave splits them."""
    import dataclasses
    cfg = dataclasses.replace(llama.CONFIGS["llama3-tiny"],
                              vocab_size=12)
    params = llama.init_params(jax.random.key(0), cfg)
    e = eng.InferenceEngine(
        params, cfg, n_slots=4, max_len=128, prompt_buckets=(16, 64),
        prefill_chunk=8, prefix_pool=4, spec_k=3, kv_block=16,
        max_wave=4, pad_waves=True,
        flight_recorder=fl.FlightRecorder())
    rng = np.random.default_rng(1)
    before = metrics_lib.REGISTRY.snapshot()
    seq0 = e.flight.seq()
    # Stagger: shorts decode (spec kicks in on the cycling small-vocab
    # output), THEN longs arrive so their chunks interleave with live
    # verify bursts.
    for _ in range(2):
        e.add_request(rng.integers(1, 12, 6).tolist(),
                      max_new_tokens=40)
    e.admit()
    for _ in range(3):
        e.decode_burst(4)
    for _ in range(2):
        e.add_request(rng.integers(1, 12, 30).tolist(),
                      max_new_tokens=40)
    e.run_to_completion(max_burst=4)
    after = metrics_lib.REGISTRY.snapshot()
    window = e.flight.since(seq0)
    # The scenario actually interleaved: chunks AND drafting verifies.
    assert sum(1 for r in window if r["burst"] == "chunk") > 0
    assert sum(1 for r in window if r.get("drafted")) > 0
    drafted = sum(r.get("drafted", 0) for r in window)
    accepted = sum(r.get("accepted", 0) for r in window)
    assert drafted > 0 and 0 < accepted <= drafted
    assert _counter_delta(before, after,
                          "skytpu_spec_drafted_total") == drafted
    assert _counter_delta(before, after,
                          "skytpu_spec_accepted_total") == accepted
    assert _counter_delta(before, after,
                          "skytpu_spec_rollbacks_total") == \
        drafted - accepted
    assert _counter_delta(before, after,
                          "skytpu_prefill_chunks_total") == \
        sum(1 for r in window if r["burst"] == "chunk")
    assert _counter_delta(before, after,
                          "skytpu_decode_tokens_total") == \
        sum(r["toks"] for r in window
            if r["burst"] in ("decode", "verify", "decode1"))
    assert _hist_count_delta(before, after,
                             "skytpu_decode_stall_seconds") == \
        sum(1 for r in window if r.get("stall"))


@pytest.mark.parametrize("live", [False, True], ids=["alone", "live_rows"])
def test_a_queued_chunks_record_is_written_at_its_landing(live):
    """A non-final chunk's record appears when the chunk is landed —
    by the burst dispatched behind it, or before the next chunk but one
    — closes no earlier than its dispatch returned, says ``queued`` and,
    where rows were decoding, ``stall``; the counter's ``awaited``
    label and ``n_chunks`` count every chunk once."""
    e = _mk_engine(spec_k=0, prefix_pool=0)
    if live:
        e.generate([[1, 2, 3]], max_new_tokens=1)    # programs warm
        e.add_request([4, 5, 6], max_new_tokens=40)
        e.admit()
        assert e.slot_req
    before = metrics_lib.REGISTRY.snapshot()
    seq0 = e.flight.seq()
    e.add_request(list(range(1, 30)), max_new_tokens=3)   # 4 chunks
    e.admit()
    long_req = e.chunking[0].req

    def chunk_records():
        return [r for r in e.flight.since(seq0) if r["burst"] == "chunk"]

    assert e.prefill_chunk_step()
    assert len(e._queued_chunks) == 1 and not chunk_records()
    assert long_req.n_chunks == 0
    if live:
        # The burst behind the chunk lands it.
        handle = e.dispatch_decode_burst(max_burst=2)
        e.complete_decode_burst(handle)
        assert not e._queued_chunks and len(chunk_records()) == 1
    assert e.prefill_chunk_step()
    assert e.prefill_chunk_step()
    # One running, one queued: the third dispatch first landed the
    # first chunk, where no burst's landing had.
    assert len(e._queued_chunks) == 2 and len(chunk_records()) == 1
    assert e.prefill_chunk_step()                 # the final chunk
    assert not e._queued_chunks and not e.chunking
    recs = chunk_records()
    assert [r["queued"] for r in recs] == [1, 1, 1, 0]
    assert [r["program"]["final"] for r in recs] == [False] * 3 + [True]
    assert [bool(r.get("stall")) for r in recs] == [live] * 4
    for r in recs:
        assert 0 <= r["dispatch_wall_ms"] <= r["dur_s"] * 1e3 + 1e-6
        assert r["fetch_wall_ms"] >= 0
    assert long_req.n_chunks == 4 and len(long_req.tokens) == 1
    after = metrics_lib.REGISTRY.snapshot()

    def awaited(snap, value):
        return sum(s["value"]
                   for s in snap["skytpu_prefill_chunks_total"]["samples"]
                   if s["labels"].get("awaited") == value)

    assert awaited(after, "0") - awaited(before, "0") == 3
    assert awaited(after, "1") - awaited(before, "1") == 1
    stalls = _hist_count_delta(before, after,
                               "skytpu_decode_stall_seconds")
    assert stalls == (4 if live else 0)
    e.run_to_completion()
    assert e.blocks_used == 0


def test_reset_mid_flight_ring_survives():
    e = _mk_engine()
    rec = e.flight
    # Long prompt -> chunked claim; run ONE chunk then reset with the
    # prefill mid-flight.
    e.generate([[1, 2, 3]], max_new_tokens=2)    # history in the ring
    e.add_request(list(range(1, 21)), max_new_tokens=4)
    e.admit()
    assert e.chunking
    e.prefill_chunk_step()
    n = rec.seq()
    assert n >= 1
    # The non-final chunk was dispatched and not awaited: no record of
    # it yet, and the reset drops its handle unlanded.
    assert len(e._queued_chunks) == 1
    e.reset()
    assert not e._queued_chunks
    # Ring survives the reset (history is the point), bounded, and
    # the engine serves cleanly afterwards with records flowing.
    assert rec.seq() == n
    out = e.generate([[1, 2, 3]], max_new_tokens=3)
    assert len(out[0]) == 3
    assert rec.seq() > n
    assert len(rec.tail()) <= rec.capacity
    # No block leak across the reset + rerun.
    assert e.blocks_used == 0


def test_recorder_off_engine_still_serves():
    e = _mk_engine()
    e.flight.enabled = False
    out = e.generate(_mixed_prompts(1, 1), max_new_tokens=5)
    assert all(len(o) == 5 for o in out)
    assert e.flight.tail() == []


def test_warm_programs_then_zero_unexpected():
    e = _mk_engine()
    n = e.warm_programs(max_burst=8)   # generate() bursts at k<=8
    assert n > 0
    e.declare_warmup_complete()
    e.generate(_mixed_prompts(), max_new_tokens=10)
    assert e.compile_watch.unexpected == []
    # And warming is idempotent: a second sweep compiles nothing.
    assert e.warm_programs(max_burst=8) == 0


def test_unwarmed_engine_alarms_after_declare():
    e = _mk_engine()
    e.declare_warmup_complete()           # lie: nothing compiled yet
    e.generate([[1, 2, 3]], max_new_tokens=3)
    assert e.compile_watch.unexpected     # the alarm fired
    snap = metrics_lib.REGISTRY.snapshot()
    assert sum(s["value"] for s in
               snap["skytpu_unexpected_compiles_total"]["samples"]) > 0
    # Every unexpected key rode some burst record's compile
    # attribution or the pre-burst drain — the typed event always
    # lands.
    names = [r.get("name") for r in tracing.buffered_records()]
    assert "engine.unexpected_compile" in names


# ---------------------------------------------------------------------------
# Trace link + CLI surfaces.

@pytest.fixture()
def fresh_events(tmp_path, monkeypatch):
    monkeypatch.setenv(tracing.EVENTS_DIR_ENV_VAR, str(tmp_path))
    monkeypatch.delenv(tracing.ENV_VAR, raising=False)
    tracing._reset_for_tests()
    yield str(tmp_path)
    tracing._reset_for_tests()


def test_trace_shows_bursts_ridden(fresh_events):
    e = _mk_engine()
    rid = e.add_request(list(range(1, 21)), max_new_tokens=6)
    e.run_to_completion(max_burst=4)
    req = next(r for r in e.finished if r.rid == rid)
    trace_id = req.span_ctx.trace_id
    tracing.flush()
    e.flight.flush()
    records = trace_view.load_trace(trace_id, dirs=[fresh_events])
    flights = [r for r in records if r.get("kind") == "flight"]
    assert flights, "flight records must join the request's trace"
    assert all(trace_id in r["traces"] for r in flights)
    rendered = trace_view.render(records, trace_id)
    assert "bursts ridden" in rendered
    assert "engine.request" in rendered
    # Perfetto export carries the bursts as duration events.
    pf = trace_view.to_perfetto(records)
    assert any(ev.get("ph") == "X" and "chunk[" in ev.get("name", "")
               for ev in pf["traceEvents"])


def test_flight_cli_local_and_perfetto(fresh_events, tmp_path):
    from click.testing import CliRunner

    from skypilot_tpu.client import cli as cli_mod

    e = _mk_engine()
    e.generate(_mixed_prompts(1, 1), max_new_tokens=5)
    e.flight.flush()
    runner = CliRunner()
    res = runner.invoke(cli_mod.cli, ["flight", "--local"])
    assert res.exit_code == 0, res.output
    assert "per-program summary" in res.output
    assert "decode[" in res.output or "wave[" in res.output
    pf_path = str(tmp_path / "flight.json")
    res2 = runner.invoke(cli_mod.cli,
                         ["flight", "--local", "--perfetto", pf_path])
    assert res2.exit_code == 0, res2.output
    with open(pf_path, encoding="utf-8") as f:
        pf = json.load(f)
    assert pf["traceEvents"]


def test_flight_cli_empty_dir(fresh_events):
    from click.testing import CliRunner

    from skypilot_tpu.client import cli as cli_mod

    res = CliRunner().invoke(cli_mod.cli, ["flight", "--local"])
    assert res.exit_code == 0
    assert "no flight records" in res.output


def test_render_table_flags_compiles():
    recs = [{"kind": "flight", "burst": "decode", "ts_s": 1.0,
             "dur_s": 0.01, "toks": 8, "slots": [0, 1],
             "program": {"k": 8, "span": 64, "layout": "paged"},
             "compiled": ["decode_burst[k=8 span=64]"]},
            {"kind": "flight", "burst": "verify", "ts_s": 1.1,
             "dur_s": 0.02, "toks": 5, "slots": [0],
             "program": {"k": 4, "span": 64, "layout": "paged"},
             "drafted": 4, "accepted": 3}]
    out = fl.render_table(recs, {"decode_burst[k=8 span=64]": 1.25})
    assert "COMPILED=1" in out
    assert "spec 3/4" in out
    assert "decode_burst[k=8 span=64]" in out and "1250.0ms" in out


def test_summarize_rollup():
    recs = [{"burst": "decode", "ts_s": 1.0, "dur_s": 0.01, "toks": 4,
             "program": {"k": 8, "span": 64, "layout": "paged"}},
            {"burst": "decode", "ts_s": 1.1, "dur_s": 0.03, "toks": 6,
             "program": {"k": 8, "span": 64, "layout": "paged"}}]
    agg = fl.summarize(recs)
    (label,) = agg
    assert label == "decode[k=8 span=64 paged]"
    assert agg[label]["count"] == 2 and agg[label]["toks"] == 10
    assert agg[label]["mean_ms"] == 20.0


# ---------------------------------------------------------------------------
# SLO wiring.

def test_unexpected_compiles_slo_rule_registered():
    from skypilot_tpu.observability import slo
    (rule,) = [r for r in slo.DEFAULT_RULES
               if r.name == "unexpected-compiles"]
    assert rule.kind == "rate" and rule.threshold == 0.0
    assert rule.metric == "skytpu_unexpected_compiles_total"


def test_unexpected_compiles_rule_breaches_on_one_compile():
    from skypilot_tpu.observability import slo
    (rule,) = [r for r in slo.DEFAULT_RULES
               if r.name == "unexpected-compiles"]

    def fams(v):
        return {"skytpu_unexpected_compiles_total": {
            "type": "counter", "samples": [({}, v)]}}

    t0 = time.time()
    history = [(t0 - 400, fams(0), []), (t0 - 90, fams(0), []),
               (t0, fams(1), [])]
    breached, short, long_ = slo.evaluate_rule(rule, history)
    assert breached and short > 0 and long_ > 0
    quiet = [(t0 - 400, fams(1), []), (t0 - 90, fams(1), []),
             (t0, fams(1), [])]
    assert not slo.evaluate_rule(rule, quiet)[0]


# ---------------------------------------------------------------------------
# Bench wiring (CI-sized smoke — structure asserted, wall-clock never).

def test_flight_smoke_bench_wiring():
    from skypilot_tpu.infer import bench_serve
    r = bench_serve.run_flight_smoke()
    assert r["unexpected_compiles"] == 0
    assert r["coverage_ok"] and r["parity_ok"]
    assert r["n_records"] > 0
    for layout in ("paged", "contig"):
        det = r["layouts"][layout]
        assert det["unexpected_compiles"] == 0
        assert det["n_chunk_records"] > 0 and det["n_wave_records"] > 0
