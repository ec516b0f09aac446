"""The benchmark's own arithmetic: percentiles, spreads, required
operations and bytes against hand-worked numbers, the roofline floor,
and the trace reduction on plain tuples."""

import json
import os

import pytest

from benchmarks import flops, manifest, peaks, run as bench_run, stats, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _dims(name):
    config = manifest.load_config(name)
    return manifest.load_family(config).dims(config)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8),
                                    (100, 5.0), (25, 2.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,want", [(9, None), (100, 90), (200, 95),
                                    (1000, 99), (150, 90)])
def test_supported_tail_needs_ten_samples_beyond(n, want):
    assert stats.supported_tail(n) == want


def test_iqr_share_is_the_contracts_spread():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    import statistics
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))


def test_two_sides_of_one_commit_by_hand():
    """``tools/aa_runs.summarise``: each side's median and spread, the
    spread without the run farthest from the median (one far-off run
    does no harm there, as in the driver's rule), and how far apart the
    medians lie."""
    from benchmarks.tools import aa_runs
    sides = {0: [100.0, 101.0, 102.0, 103.0, 104.0, 150.0],
             1: [110.0, 111.0, 112.0, 113.0, 114.0, 115.0]}
    rows = [{"side": s, "values": {"m": v, "absent": None}}
            for s, vs in sides.items() for v in vs]
    got, absent = aa_runs.summarise(rows, ["m", "absent"])
    assert absent["sides"] == {}
    a, b = got["sides"][0], got["sides"][1]
    assert a["median"] == 102.5 and b["median"] == 112.5
    assert a["spread"] == pytest.approx(stats.iqr_share(sides[0]))
    assert a["spread_trimmed"] == pytest.approx(
        stats.iqr_share(sides[0][:5])) and a["spread_trimmed"] < a["spread"]
    assert got["medians_apart"] == pytest.approx(10 / 102.5)


def test_mean_gap_spans_bursts():
    # four tokens in two bursts: the mean gap is (t_last - t_first) / 3
    assert stats.mean_gap_ms([1.0, 1.0, 1.06, 1.06]) == pytest.approx(20.0)
    assert stats.mean_gap_ms([1.0]) is None


@pytest.mark.parametrize("name,params", [
    ("mistral-7b-w8a8", 7_248_023_552),
    ("mistral-7b-qlora", 7_248_023_552),
    ("internlm2-1.8b-bf16", 1_889_110_016),
    ("internlm2-1.8b-f32adamw", 1_889_110_016)])
def test_parameter_counts_match_the_published_models(name, params):
    cfg = manifest.load_config(name)
    assert _dims(name).num_params() == params == cfg["parameters"]


@pytest.mark.parametrize("name,by_hand,want", [
    # wq + wo: 2 * 4096 * 4096; wk + wv: 2 * 4096 * 1024; FFN 3 * 4096 * 14336
    ("mistral-7b-w8a8",
     2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336, 218_103_808),
    ("internlm2-1.8b-bf16",
     2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192, 62_914_560),
    ("internlm2-1.8b-f32adamw",
     2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192, 62_914_560)])
def test_block_matmul_params_by_hand(name, by_hand, want):
    assert flops.block_matmul_params(_dims(name)) == by_hand == want


@pytest.mark.parametrize("name", ["mistral-7b-qlora"])
def test_lora_params_by_hand(name):
    d = _dims(name)
    # rank 16 on wq (4096+4096), wk and wv (4096+1024 each), wo (4096+4096)
    per_layer = 16 * ((4096 + 4096) * 2 + (4096 + 1024) * 2)
    assert flops.lora_params(d, 16) == 32 * per_layer == 13_631_488


@pytest.mark.parametrize("name", ["mistral-7b-qlora"])
def test_attention_flops_are_causal(name):
    d = _dims(name)
    fwd = flops.attention_flops_per_sequence(d, 2048, backward=False)
    # two matmuls, 2 FLOPs a multiply-add, half the 2048 x 2048 square,
    # 32 heads of 128
    assert fwd == 2 * 2 * (2048 * 2048 // 2) * 32 * 128 == 34_359_738_368
    assert flops.attention_flops_per_sequence(d, 2048, True) == 2 * fwd


@pytest.mark.parametrize("name", ["mistral-7b-qlora"])
def test_qlora_flops_count_a_frozen_base(name):
    d = _dims(name)
    base = 32 * 218_103_808 + 4096 * 32768
    attn = 32 * 3 * 34_359_738_368 / 2048
    want = 4 * base + 6 * 13_631_488 + attn
    got = flops.qlora_train_flops_per_token(d, 2048, {"lora_rank": 16})
    assert got == pytest.approx(want)
    # a frozen base needs 4 FLOPs a weight, full training 6
    full = flops.full_train_flops_per_token(d, 2048)
    assert full - got == pytest.approx(2 * base - 6 * 13_631_488)
    assert 2.9e10 < got < 3.1e10


@pytest.mark.parametrize("name", ["mistral-7b-qlora"])
def test_flash_step_work_and_its_floor(name):
    d = _dims(name)
    work = flops.flash_attention_step_work(d, batch=4, seq=2048)
    assert work["flops"] == 4 * 32 * 3 * 34_359_738_368
    q_el, kv_el = 2048 * 32 * 128, 2048 * 8 * 128
    assert work["bytes"] == 4 * 32 * 2 * (6 * q_el + 6 * kv_el)
    floor = flops.least_seconds(work, "TPU v5 lite")
    assert floor["bound"] == "compute"
    assert floor["seconds"] == pytest.approx(work["flops"] / 197e12)
    assert floor["memory_s"] == pytest.approx(work["bytes"] / 819e9)


@pytest.mark.parametrize("name", ["internlm2-1.8b-f32adamw"])
def test_full_training_flops_by_hand(name):
    """6 FLOPs a matmul weight (blocks and head) plus causal attention,
    forward and backward, of 16 heads of 128 over 24 layers."""
    d = _dims(name)
    base = 24 * 62_914_560 + 2048 * 92544
    one = 2 * (2048 * 2048 // 2) * 16 * 128
    attn = 24 * 6 * one / 2048
    assert flops.full_train_flops_per_token(d, 2048) == pytest.approx(
        6 * base + attn)
    # the whole step's attention work is shared among a cell's chips
    work = flops.flash_attention_step_work(d, batch=8, seq=2048)
    assert work["flops"] == 8 * 24 * 6 * one


def test_unknown_device_has_no_peaks():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# -- trace arithmetic ----------------------------------------------------

def test_busy_is_a_union_not_a_sum():
    assert trace.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.union_seconds([]) == 0


def test_gaps_cover_what_the_ops_do_not():
    gaps = trace.gaps_of([(1, 2), (1.5, 3), (4, 5)], 0, 6)
    assert gaps == [(0, 1), (3, 4), (5, 6)]


def test_nested_annotations_flatten_to_the_innermost():
    segs = trace.flatten_spans([("server._step", 0.0, 10.0),
                                ("engine.step", 2.0, 6.0),
                                ("server._flush_streams", 7.0, 8.0)])
    assert segs == [(0.0, 2.0, "server._step"), (2.0, 6.0, "engine.step"),
                    (6.0, 7.0, "server._step"),
                    (7.0, 8.0, "server._flush_streams"),
                    (8.0, 10.0, "server._step")]
    assert trace.attribute_gap((2.5, 5.0), segs) == {"engine.step": 2.5}
    assert trace.attribute_gap((5.5, 7.25), segs) == {
        "engine.step": 0.5, "server._step": 1.0,
        "server._flush_streams": 0.25}
    assert trace.attribute_gap((11.0, 12.0), segs) == {"unattributed": 1.0}
    assert trace.attribute_gap((9.5, 12.0), segs) == {
        "server._step": 0.5, "unattributed": 2.0}


def test_reduce_events_by_hand():
    plane = {"ops": [("fusion.1", 0.0, 1.0, ""), ("fusion.1", 1.0, 1.5, ""),
                     ("custom-call.2", 3.0, 4.0, "")],
             "modules": [("jit__decode_burst(123)", 0.0, 1.5, ""),
                         ("jit__admit_wave(7)", 3.0, 4.0, "")]}
    red = trace.reduce_events([plane], [("server._step", 1.4, 3.1)],
                              (0.0, 5.0), "tpu")
    assert red["busy_s"] == pytest.approx(2.5)
    assert red["window_s"] == 5.0
    assert red["modules"]["jit__decode_burst"] == {"s": 1.5, "n": 1}
    assert red["ops"][0] == ["fusion.1", 1.5]
    # idle 1.5-3.0 (1.5 s of it under server._step) and 4.0-5.0
    assert red["idle_gaps"] == [["server._step", pytest.approx(1.5)],
                                ["unattributed", pytest.approx(1.0)]]
    assert red["kinds"] == {}


def test_op_kind_is_the_opcode_or_the_custom_call_target():
    name, kind = trace.op_name(
        '%checkpoint.25 = (bf16[2,32]{1,0:T(8,128)(2,1)}, bf16[2]{0}) '
        'custom-call(bf16[2,32]{1,0} %p), custom_call_target='
        '"tpu_custom_call"')
    assert (name, kind) == ("checkpoint.25", "tpu_custom_call")
    # an operand named custom-call does not make a fusion a kernel
    assert trace.op_name(
        '%fusion.818 = bf16[2,4]{1,0:T(8,128)(2,1)} fusion(bf16[2,4]{1,0}'
        ' %custom-call.7, bf16[4]{0} %x), kind=kOutput') == (
            "fusion.818", "fusion")
    assert trace.op_name('%c.4 = bf16[3]{0} custom-call(), '
                         'custom_call_target="AllocateBuffer"')[1] == \
        "AllocateBuffer"
    assert trace.op_name("jit__decode") == ("jit__decode", "")


def test_a_fusion_that_calls_a_collective_is_that_collective():
    """How the chip's compiler writes a reduce-scatter (taken from the
    four-chip step compiled for a described v5e 2x2)."""
    assert trace.op_name(
        '%fusion.381 = bf16[1024,46272]{0,1:T(8,128)(2,1)} fusion('
        '%convolution_bitcast_fusion.6), kind=kCustom, '
        'calls=%all-reduce-scatter.clone.clone, metadata={}') == (
            "fusion.381", "all-reduce-scatter")
    assert trace.op_name(
        '%all-gather.310 = bf16[2048,46272]{0,1} all-gather('
        '%get-tuple-element.2263), channel_id=65, dimensions={0}') == (
            "all-gather.310", "all-gather")
    # an asynchronous gather is a fusion known by its instruction's name
    assert trace.op_name(
        '%async-collective-start = (bf16[1024,4,128]{0,2,1}, s32[2]{0}) '
        'fusion(%dynamic-slice_bitcast_fusion.29), kind=kCustom, '
        'calls=%fused_computation.369') == ("async-collective-start",
                                            "fusion")


def test_collective_share_by_hand():
    """Two chips' mean: 0.2 s of a 2.0 s step in gathers, scatters and
    reductions by kind, 0.1 s in asynchronous starts and dones by name."""
    spec = manifest.load_metric("collective_share")
    reader = manifest.load_module("readers", spec["reader"])
    facts = {"trace": {
        "platform": "tpu",
        "modules": {"jit_step": {"s": 2.0, "n": 4}, "jit_other": {"s": 9.0}},
        "kinds": {"all-gather": 0.05, "all-reduce-scatter": 0.1,
                  "all-reduce": 0.03, "collective-permute-done": 0.02,
                  "fusion": 1.0, "tpu_custom_call": 0.3},
        "ops": [["fusion.1", 1.0], ["async-collective-done.3", 0.06],
                ["async-collective-start.3", 0.04], ["all-gather.9", 0.05]]}}
    assert reader.read(facts, {}, **spec["args"]) == pytest.approx(15.0)
    # a one-chip step has no collective: nothing to read, not a zero
    facts["trace"]["kinds"] = {"fusion": 1.0}
    facts["trace"]["ops"] = [["fusion.1", 1.0]]
    assert reader.read(facts, {}, **spec["args"]) is None
    facts["trace"]["platform"] = "cpu"
    assert reader.read(facts, {}, **spec["args"]) is None


@pytest.mark.parametrize("name", ["internlm2-1.8b.pretrain-4chip"])
def test_a_four_chip_cells_work_is_shared_among_its_chips(name):
    """``flash_attn_roofline`` and ``train_mfu`` on four chips: the
    trace's seconds are a mean over the chips, the step's work is the
    whole step's, and the count is the configuration's own."""
    cell = manifest.load_workload(name)
    config = manifest.load_config(cell["config"])
    ctx = {"cell": cell, "config": config, "bench_dir": manifest.BENCH_DIR,
           "mix": manifest.load_traffic(cell)}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    dims = manifest.load_family(config).dims(config)
    work = flops.flash_attention_step_work(dims, 8, 2048)
    floor = flops.least_seconds(work, device["kind"])["seconds"]
    facts = {"device": device, "traced": {"steps": 4},
             "trace": {"platform": "tpu",
                       "kinds": {"tpu_custom_call": 4 * floor}},
             "train_tokens_per_s": 30000.0}
    spec = manifest.load_manifest()
    got = bench_run.read_metrics(
        ctx, {"facts": facts}, [m for m in spec["per_layer"] if m["name"]
                                in ("flash_attn_roofline", "train_mfu")],
        manifest.BENCH_DIR)
    # a step took each chip the WHOLE step's floor, four times its share's
    assert got["flash_attn_roofline"]["value"] == pytest.approx(25.0)
    per_token = flops.full_train_flops_per_token(dims, 2048)
    assert got["train_mfu"]["value"] == pytest.approx(
        100 * per_token * 30000.0 / (4 * 197e12))
    assert 30 < got["train_mfu"]["value"] < 60


def test_recorded_tpu_trace_reduces():
    """A small trace recorded on a v5e (four launches of a jitted
    ``_decode_burst``, four of ``_admit_wave``, with the benchmark's
    annotations round them), checked in beside this file."""
    path = os.path.join(HERE, "data", "v5e_small.xplane.pb")
    if not os.path.isfile(path):
        pytest.skip("no recorded trace checked in")
    red = trace.reduce_xplane(path)
    want = json.load(open(os.path.join(HERE, "data", "v5e_small.json")))
    assert red["platform"] == "tpu" and red["devices"] == 1
    assert red["modules"]["jit__decode_burst"]["n"] == 4
    assert red["modules"]["jit__admit_wave"]["n"] == 4
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    for name, m in want["modules"].items():
        assert red["modules"][name]["s"] == pytest.approx(m["s"], rel=1e-9)
    labels = {g[0] for g in red["idle_gaps"]}
    assert labels & {"bench.loss_fetch", "bench.step_dispatch",
                     "server._step", "unattributed"}
    # per-module device time cannot exceed the busy union's window
    assert sum(m["s"] for m in red["modules"].values()) <= red["window_s"]
