"""The second trace reduction (``benchmarks/spans.py``) and the readers
on top of it: the arithmetic on plain tuples, the reduction of a small
trace recorded on a chip (``data/make_spans_fixture.py``), and a trace
without annotations or scopes, on which every new reader reads nothing.
"""

import json
import math
import os
import re
import shutil

import pytest

from benchmarks import manifest, spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "data", "spans_fixture.xplane.pb")
EXPECTED = os.path.join(HERE, "data", "spans_fixture.expected.json")
NEW_METRICS = (
    "ttft_queue_share", "prefill_useful_token_share",
    "decode_device_ms_per_step", "decode_useful_token_share",
    "decode_kv_gather_share", "flash_fwd_device_ms", "flash_dkv_device_ms",
    "flash_dq_device_ms", "base_matmul_share")


def disp(seq, start, k=4, slots=3, rows=8, why="open"):
    return (spans.DISPATCH, start, start + 0.001, 0,
            {"seq": seq, "k": k, "slots": slots, "rows": rows, "why": why})


def fetch(seq, end, parts=1, tokens=9, k=4):
    return (spans.FETCH, end - 0.002, end, 0,
            {"seq": seq, "k": k, "parts": parts, "tokens": tokens,
             "retired": 0})


# ---------------------------------------------------------------------------
# Plain tuples
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path,want", [
    ("jit(prog)/decode_step/kv_gather/jit(_take)/gather:", "kv_gather"),
    ("jit(step)/transpose(jvp(attn))/base_matmul/dot_general:",
     "base_matmul"),
    ("jit(step)/jvp(attn)/checkpoint/flash_fwd/pallas_call:", "attn"),
    ("jit(step)/transpose(jvp(mlp))/mul:", "mlp"),
    ("jit(prog)/while/body/closed_call/decode_step/iota:", "decode_step"),
    ("jit(prog)/bd,df->bf/dot_general:", ""),
    ("", ""),
])
def test_innermost_scope(path, want):
    assert spans.innermost_scope(path) == want


@pytest.mark.parametrize("instr,path,want", [
    ("flash_fwd.3", "jit(step)/jvp(attn)/flash_fwd/pallas_call:",
     "flash_fwd"),
    # under rematerialisation XLA names the call after the checkpoint;
    # the pallas_call's own name is still on its scope path
    ("checkpoint.25",
     "jit(step)/transpose(jvp(attn))/checkpoint/flash_fwd/pallas_call:",
     "flash_fwd"),
    ("custom-call.7", "", "custom-call"),
])
def test_kernel_name(instr, path, want):
    assert spans.kernel_name(instr, path) == want


def test_sum_args_multiplies_and_skips_events_without_the_argument():
    evs = [("e", 0, 1, 0, {"a": 2, "b": 3}), ("e", 1, 2, 0, {"a": 5}),
           ("e", 2, 3, 0, {"a": 1, "b": 1.5})]
    assert spans.sum_args(evs, "a") == 8
    assert spans.sum_args(evs, "a", "b") == 7.5
    assert spans.sum_args(evs, "c") == 0


def test_pairing_drops_the_bursts_cut_by_either_edge():
    """Burst 1 was dispatched before the trace began (its fetch and its
    launch are there, its dispatch is not); burst 4's fetch fell after
    the trace's end. Bursts 2 and 3 count, each with its own launch.
    The device's clock runs 0.3 s behind here: launches are paired by
    when the HOST enqueued them, not by when the device says they ran."""
    anns = [fetch(1, 1.00), disp(2, 0.40), disp(3, 1.01), fetch(2, 2.00),
            disp(4, 2.01), fetch(3, 3.00)]
    launches = [(-0.20, 0.69, 0.05), (0.69, 1.69, 0.41),
                (1.69, 2.69, 1.02), (2.69, 3.20, 2.02)]
    got = spans.pair_decode(anns, launches)
    assert [b["seq"] for b in got] == [2, 3]
    assert [b["launches"] for b in got] == [[(0.69, 1.69)], [(1.69, 2.69)]]


def test_pairing_takes_a_burst_of_two_programs_whole_or_not_at_all():
    anns = [disp(7, 0.10, slots=5), disp(7, 0.11, slots=2),
            fetch(7, 1.00, parts=2), disp(8, 0.50), disp(8, 0.51),
            fetch(8, 2.00, parts=2)]
    got = spans.pair_decode(anns, [(0.2, 0.6, 0.101), (0.6, 0.99, 0.111),
                                   (1.0, 1.9, 0.501)])
    assert [b["seq"] for b in got] == [7]       # burst 8 lost a launch
    assert len(got[0]["dispatches"]) == 2


def test_pairing_passes_over_a_launch_from_before_its_first_dispatch():
    """Without the runtime's enqueue event the launch's own start stands
    in: one that began before the first dispatch is nobody's."""
    anns = [disp(5, 0.50), fetch(5, 2.00)]
    assert spans.pair_decode(anns, [(0.1, 0.9, None)]) == []
    got = spans.pair_decode(anns, [(0.1, 0.9, None), (0.9, 1.8, None)])
    assert [b["launches"] for b in got] == [[(0.9, 1.8)]]


def _ops():
    return [
        ("fusion.1", 0.10, 0.20, "fusion",
         "jit(_decode_burst)/while/body/decode_step/kv_gather/gather:"),
        ("fusion.2", 0.20, 0.25, "fusion",
         "jit(_decode_burst)/while/body/decode_step/attn_core/dot:"),
        ("argmax.1", 0.25, 0.26, "fusion",
         "jit(_decode_burst)/while/body/decode_step/sample/argmax:"),
        ("fusion.1", 0.30, 0.40, "fusion",
         "jit(_decode_burst)/while/body/decode_step/kv_gather/gather:"),
        ("argmax.1", 0.45, 0.46, "fusion",
         "jit(_decode_burst)/while/body/decode_step/sample/argmax:"),
        ("while.3", 0.10, 0.50, "while", "jit(_decode_burst)/while:"),
        ("copy.9", 0.60, 0.70, "copy", "jit(_decode_burst)/kv_write/x:"),
        ("flash_fwd.1", 1.00, 1.10, spans.MOSAIC,
         "jit(step)/jvp(attn)/flash_fwd/pallas_call:"),
        ("checkpoint.2", 1.10, 1.25, spans.MOSAIC,
         "jit(step)/transpose(jvp(attn))/checkpoint/flash_fwd/pallas_call:"),
        ("fusion.8", 1.30, 1.50, "fusion",
         "jit(step)/transpose(jvp(attn))/base_matmul/dot_general:"),
    ]


def test_ops_group_by_module_innermost_scope_and_kernel():
    mods = [("jit__decode_burst", 0.05, 0.55, None),
            ("jit_step", 0.95, 1.60, None)]
    got = spans.group_ops(_ops(), mods)
    burst, step = got["jit__decode_burst"], got["jit_step"]
    assert burst["n"] == 1 and burst["s"] == pytest.approx(0.5)
    assert burst["scopes"]["kv_gather"] == pytest.approx(0.2)
    assert burst["scopes"]["attn_core"] == pytest.approx(0.05)
    # the while op spans its body and the copy lies outside every
    # recorded module event: neither is counted
    assert burst["ops_s"] == pytest.approx(0.27)
    assert "kv_write" not in burst["scopes"]
    assert step["kernels"] == {"flash_fwd": pytest.approx(0.25)}
    assert step["scopes"]["base_matmul"] == pytest.approx(0.2)
    assert step["scopes"]["attn"] == pytest.approx(0.25)


def test_steps_are_counted_on_the_device_by_the_head_and_sample_scopes():
    assert spans.steps_on_device(_ops(), [(0.05, 0.55)]) == 2
    assert spans.steps_on_device(_ops(), [(0.95, 1.60)]) == 0
    # the head's op runs once a step too; an op seen more often (the
    # scan over layers nests in the step) does not raise the count
    head = [("fusion.316", t, t + 0.001, "fusion",
             "jit(_decode_burst)/while/body/decode_step/lm_head/dot:")
            for t in (0.27, 0.47)]
    assert spans.steps_on_device(_ops() + head, [(0.05, 0.55)]) == 2


def test_reduce_events_counts_steps_and_tokens_over_the_same_launches():
    device = [{"ops": _ops(), "modules": [
        ("jit__decode_burst", 0.05, 0.55, 0.02),
        ("jit__decode_burst", 0.56, 0.9, 0.31),
        ("jit_step", 0.95, 1.60, 0.94)]}]
    anns = [disp(1, 0.01, k=2), fetch(1, 0.56, tokens=5, k=2),
            disp(2, 0.30, k=4)]                # burst 2: never fetched
    red = spans.reduce_events(device, anns)
    assert red["device_clock_lag_s"] == pytest.approx(-0.01)
    d = red["decode"]
    assert (d["bursts"], d["launches"], d["launches_seen"]) == (1, 1, 2)
    assert d["steps"] == 2 and d["tokens"] == 5
    assert d["row_steps"] == 16 and d["live_row_steps"] == 6
    assert d["device_s"] == pytest.approx(0.5)
    assert d["steps_on_device"] == 2
    assert d["by_why"] == {"open": {"launches": 1, "steps": 2}}
    assert d["by_program"] == {"k=2 span=None": {
        "launches": 1, "steps": 2, "device_s": pytest.approx(0.5),
        "slots": 3}}
    assert red["phases"][spans.DISPATCH]["n"] == 2
    assert red["host_lines"] == 1 and red["platform"] == "tpu"


# ---------------------------------------------------------------------------
# The recorded trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return spans.reduce_xplane(FIXTURE), json.load(open(EXPECTED))


def test_recorded_trace_pairs_exactly_the_whole_bursts(recorded):
    red, want = recorded
    d = red["decode"]
    assert red["platform"] == "tpu" == want["platform"]
    assert d["seqs"] == want["seqs"]
    assert d["launches"] == want["launches"]
    assert d["steps"] == want["steps"] == d["steps_on_device"]
    assert d["tokens"] == want["tokens"]
    assert d["row_steps"] == want["row_steps"]
    # the launch of the burst dispatched before the trace began, and of
    # the one fetched after it ended, were seen and left out
    assert d["launches_seen"] > d["launches"]
    assert 0 < d["device_s"] <= red["modules"]["jit__decode_burst"]["s"]
    assert set(d["by_why"]) == {"open", "full"}
    assert red["host_lines"] == 1
    # on this trace the device's clock ran ~1.5 ms behind the host's
    assert 0 < red["device_clock_lag_s"] < 0.01


def test_recorded_trace_names_scopes_and_kernels(recorded):
    red, want = recorded
    step = red["modules"]["jit_step"]
    assert step["n"] == want["train_steps"]
    assert set(step["kernels"]) == {"flash_fwd", "flash_bwd_dkv",
                                    "flash_bwd_dq"}
    # the forward kernel runs twice a step, the others once
    assert step["kernels"]["flash_fwd"] > step["kernels"]["flash_bwd_dq"]
    assert step["scopes"]["base_matmul"] > 0
    burst = red["modules"]["jit__decode_burst"]
    assert {"kv_gather", "attn_core", "sample", "kv_write"} \
        <= set(burst["scopes"])
    assert sum(burst["scopes"].values()) <= burst["ops_s"] <= burst["s"]
    assert red["phases"]["train.step"]["n"] == want["train_steps"]
    steps = [a for a in red["annotations"] if a[0] == "train.step"]
    assert [a[4]["step_num"] for a in steps] == [0, 1, 2]


def _facts_ctx(tmp_path, trace_file):
    return ({"trace": {"file": str(trace_file), "platform": "tpu"}},
            {"out_dir": str(tmp_path), "bench_dir": manifest.BENCH_DIR})


def _read(name, facts, ctx):
    spec = manifest.load_metric(name)
    reader = manifest.load_module("readers", spec["reader"])
    return reader.read(facts, ctx, **(spec.get("args") or {}))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_reads_the_recorded_trace(tmp_path, name):
    facts, ctx = _facts_ctx(tmp_path, FIXTURE)
    value = _read(name, facts, ctx)
    assert value is not None and math.isfinite(value) and value > 0
    if manifest.load_metric(name)["unit"] == "%":
        assert value <= 100.0
    assert os.path.isfile(tmp_path / "spans_reduced.json")   # kept


def test_new_metrics_against_the_recorded_numbers(tmp_path, recorded):
    red, want = recorded
    facts, ctx = _facts_ctx(tmp_path, FIXTURE)
    assert _read("ttft_queue_share", facts, ctx) == pytest.approx(
        100 * (30 + 10) / (120 + 80))
    assert _read("prefill_useful_token_share", facts, ctx) == \
        pytest.approx(100 * (200 + 100) / (4 * 128 + 256))
    assert _read("decode_useful_token_share", facts, ctx) == \
        pytest.approx(100 * want["tokens"] / want["row_steps"])
    assert _read("decode_device_ms_per_step", facts, ctx) == \
        pytest.approx(red["decode"]["device_s"] * 1e3 / want["steps"])
    step = red["modules"]["jit_step"]
    assert _read("flash_fwd_device_ms", facts, ctx) == pytest.approx(
        step["kernels"]["flash_fwd"] * 1e3 / want["train_steps"])
    assert _read("base_matmul_share", facts, ctx) == pytest.approx(
        100 * step["scopes"]["base_matmul"] / step["s"])


# ---------------------------------------------------------------------------
# A program without annotations, scopes or kernel names
# ---------------------------------------------------------------------------

def _unnamed_reduction(tmp_path, platform="tpu"):
    """What the parent commit of ISSUE 25 traces to: modules and ops,
    no annotation, no scope, Mosaic calls named by XLA."""
    ops = [("fusion.1", 0.1, 0.2, "fusion", ""),
           ("custom-call.4", 1.0, 1.1, spans.MOSAIC, ""),
           ("checkpoint.25", 1.1, 1.2, spans.MOSAIC, "")]
    device = [{"ops": ops, "modules": [
        ("jit__decode_burst", 0.05, 0.5, None),
        ("jit_step", 0.9, 1.5, None)]}]
    red = spans.reduce_events(device if platform == "tpu" else [], [])
    trace_file = tmp_path / "t.xplane.pb"
    trace_file.write_bytes(b"")
    with open(tmp_path / "spans_reduced.json", "w") as f:
        json.dump(red, f)
    return _facts_ctx(tmp_path, trace_file)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_without_annotations_or_scopes_every_new_reader_reads_nothing(
        tmp_path, name):
    facts, ctx = _unnamed_reduction(tmp_path)
    assert _read(name, facts, ctx) is None


@pytest.mark.parametrize("name", ["ttft_queue_share", "base_matmul_share"])
def test_a_cpu_trace_or_no_trace_gives_no_device_metric(tmp_path, name):
    facts, ctx = _unnamed_reduction(tmp_path, platform="cpu")
    assert _read(name, facts, ctx) is None
    assert _read(name, {}, ctx) is None
    assert _read(name, {"trace": {"file": str(tmp_path / "gone")}},
                 ctx) is None


def test_the_reduction_is_made_once_per_run_directory(tmp_path):
    copy = tmp_path / "trace.xplane.pb"
    shutil.copy(FIXTURE, copy)
    facts, ctx = _facts_ctx(tmp_path, copy)
    first = spans.load(facts, ctx)
    kept = tmp_path / "spans_reduced.json"
    stamp = os.path.getmtime(kept)
    assert spans.load(facts, ctx) == first
    assert os.path.getmtime(kept) == stamp


# ---------------------------------------------------------------------------
# The manifest and the program agree with this file
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,source,moves", [
    ("ttft_queue_share", "program_span", "ttft_p95_ms"),
    ("prefill_useful_token_share", "program_counter", "ttft_p95_ms"),
    ("decode_device_ms_per_step", "device_trace", "tpot_p90_ms"),
    ("decode_useful_token_share", "program_counter", "tpot_p90_ms"),
    ("decode_kv_gather_share", "device_trace", "tpot_p90_ms"),
    ("flash_fwd_device_ms", "device_trace", "train_tokens_per_s"),
    ("flash_dkv_device_ms", "device_trace", "train_tokens_per_s"),
    ("flash_dq_device_ms", "device_trace", "train_tokens_per_s"),
    ("base_matmul_share", "device_trace", "train_tokens_per_s"),
    # the chat cell's first-token readings, split off in PR 37: the cell
    # reports no TTFT end to end, so they move what it does report
    ("ttft_queue_share.chat", "program_span", "tpot_p90_ms"),
    ("prefill_useful_token_share.chat", "program_counter", "tpot_p90_ms"),
    ("prefill_device_ms_per_ktok.chat", "device_trace", "tpot_p90_ms"),
    ("ttft_p95_ms.chat", "host_clock", "tpot_p90_ms"),
])
def test_manifest_entry_of_each_new_metric(name, source, moves):
    spec = manifest.load_manifest()
    entry = [m for m in spec["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    entry = entry[0]
    assert entry["source"] == source and entry["moves"] == moves
    # whichever cells list it (later PRs add theirs), each reports the
    # end-to-end metric it moves
    reports = {m["name"]: m.get("workloads") for m in spec["end_to_end"]}
    assert entry["workloads"]
    assert set(entry["workloads"]) <= set(reports[moves])
    on_file = manifest.load_metric(name)
    for key in ("layer", "unit", "moves"):
        assert on_file[key] == entry[key]
    # appended: the six metrics PR 24 brought are still the first six
    assert spec["per_layer"].index(entry) >= 6
    manifest.validate(spec)


def test_scopes_and_kernel_names_are_the_programs_own():
    """Every scope this file groups by is entered somewhere in the
    program, and every ``pallas_call`` there has a ``name=``."""
    text = ""
    calls = 0
    for sub in ("infer/kvcache.py", "infer/engine.py", "models/llama.py",
                "train/qlora.py", "train/trainer.py",
                "ops/flash_attention.py", "ops/paged_attention.py"):
        with open(os.path.join(ROOT, "skypilot_tpu", sub)) as f:
            src = f.read()
        text += src
        for m in re.finditer(r"pl\.pallas_call\(", src):
            calls += 1
            end = src.index("\n    )(", m.start())
            assert "name=" in src[m.start():end], sub
    assert calls == 4
    entered = set(re.findall(r'named_scope\("([a-z_]+)"\)', text))
    assert entered == set(spans.SCOPES)
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq",
                   "paged_decode"):
        assert f'name="{kernel}"' in text


# ---------------------------------------------------------------------------
# A quantity split between cells that report different end-to-end metrics
# ---------------------------------------------------------------------------

def _split_metrics():
    spec = manifest.load_manifest()
    names = {m["name"] for m in spec["per_layer"]}
    return sorted(n for n in names
                  if "." in n and n.rsplit(".", 1)[0] in names)


@pytest.mark.parametrize("name", _split_metrics())
def test_a_split_metric_reads_what_its_original_reads(name):
    """``<metric>.<cell>`` is the same reading for cells that report
    another end-to-end metric: layer, unit, reader and arguments are its
    original's, only ``moves`` and the cells differ; no cell lists both."""
    spec = manifest.load_manifest()
    base = name.rsplit(".", 1)[0]
    a, b = manifest.load_metric(base), manifest.load_metric(name)
    for key in ("layer", "unit", "reader", "args"):
        assert a.get(key) == b.get(key), key
    assert a["moves"] != b["moves"]
    ea, eb = ([m for m in spec["per_layer"] if m["name"] == n][0]
              for n in (base, name))
    for key in ("layer", "unit", "better", "source"):
        assert ea[key] == eb[key], key
    assert eb["moves"] == b["moves"] and ea["moves"] == a["moves"]
    assert not set(ea["workloads"]) & set(eb["workloads"])


def test_the_chat_cell_keeps_its_prefill_readings():
    assert _split_metrics() == [
        "prefill_device_ms_per_ktok.chat",
        "prefill_useful_token_share.chat", "ttft_queue_share.chat"]
    spec = manifest.load_manifest()
    chat = {m["name"] for m in manifest.cell_metrics(
        spec, "mistral-7b-w8a8.chat-steady", "per_layer")}
    assert set(_split_metrics()) | {"ttft_p95_ms.chat"} <= chat
    assert not {n.rsplit(".", 1)[0] for n in _split_metrics()} & chat


def test_a_client_value_is_the_runs_own_and_only_from_the_chip(tmp_path):
    """``ttft_p95_ms.chat``: the first-token tail the generator measured
    over the traced run's whole window; nothing off the chip, and nothing
    where the runner gave no such value."""
    facts, ctx = _facts_ctx(tmp_path, FIXTURE)
    assert _read("ttft_p95_ms.chat", facts, ctx) is None
    facts["client"] = {"ttft_p95_ms": 431.5, "tpot_p90_ms": 31.8}
    assert _read("ttft_p95_ms.chat", facts, ctx) == 431.5
    facts["trace"]["platform"] = "cpu"
    assert _read("ttft_p95_ms.chat", facts, ctx) is None
    assert _read("ttft_p95_ms.chat", {"client": {"ttft_p95_ms": 1.0}},
                 ctx) is None
