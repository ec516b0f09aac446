"""The benchmark's own arithmetic: percentiles, spreads, required
operations and bytes against hand-worked numbers, the roofline floor,
and the trace reduction on plain tuples."""

import json
import os

import pytest

from benchmarks import flops, manifest, peaks, stats, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _dims(name):
    return manifest.model_dims(manifest.load_config(name))


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


def test_mean_gap_spans_bursts():
    # four tokens in two bursts: the mean gap is (t_last - t_first) / 3
    assert stats.mean_gap_ms([1.0, 1.0, 1.06, 1.06]) == pytest.approx(20.0)
    assert stats.mean_gap_ms([1.0]) is None


@pytest.mark.parametrize("name,params", [
    ("mistral-7b-w8a8", 7_248_023_552),
    ("mistral-7b-qlora", 7_248_023_552),
    ("internlm2-1.8b-bf16", 1_889_110_016)])
def test_parameter_counts_match_the_published_models(name, params):
    cfg = manifest.load_config(name)
    assert _dims(name).num_params() == params == cfg["parameters"]


def test_block_matmul_params_by_hand():
    d = _dims("mistral-7b-w8a8")
    # wq + wo: 2 * 4096 * 4096; wk + wv: 2 * 4096 * 1024; FFN 3 * 4096 * 14336
    assert flops.block_matmul_params(d) == (
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) == 218_103_808
    i = _dims("internlm2-1.8b-bf16")
    assert flops.block_matmul_params(i) == (
        2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192) == 62_914_560


def test_lora_params_by_hand():
    d = _dims("mistral-7b-qlora")
    # rank 16 on wq (4096+4096), wk and wv (4096+1024 each), wo (4096+4096)
    per_layer = 16 * ((4096 + 4096) * 2 + (4096 + 1024) * 2)
    assert flops.lora_params(d, 16) == 32 * per_layer == 13_631_488


def test_attention_flops_are_causal():
    d = _dims("mistral-7b-qlora")
    fwd = flops.attention_flops_per_sequence(d, 2048, backward=False)
    # two matmuls, 2 FLOPs a multiply-add, half the 2048 x 2048 square,
    # 32 heads of 128
    assert fwd == 2 * 2 * (2048 * 2048 // 2) * 32 * 128 == 34_359_738_368
    assert flops.attention_flops_per_sequence(d, 2048, True) == 2 * fwd


def test_qlora_flops_count_a_frozen_base():
    d = _dims("mistral-7b-qlora")
    base = 32 * 218_103_808 + 4096 * 32768
    attn = 32 * 3 * 34_359_738_368 / 2048
    want = 4 * base + 6 * 13_631_488 + attn
    got = flops.qlora_train_flops_per_token(d, 2048, {"lora_rank": 16})
    assert got == pytest.approx(want)
    # a frozen base needs 4 FLOPs a weight, full training 6
    full = flops.full_train_flops_per_token(d, 2048)
    assert full - got == pytest.approx(2 * base - 6 * 13_631_488)
    assert 2.9e10 < got < 3.1e10


def test_flash_step_work_and_its_floor():
    d = _dims("mistral-7b-qlora")
    work = flops.flash_attention_step_work(d, batch=4, seq=2048)
    assert work["flops"] == 4 * 32 * 3 * 34_359_738_368
    q_el, kv_el = 2048 * 32 * 128, 2048 * 8 * 128
    assert work["bytes"] == 4 * 32 * 2 * (6 * q_el + 6 * kv_el)
    floor = flops.least_seconds(work, "TPU v5 lite")
    assert floor["bound"] == "compute"
    assert floor["seconds"] == pytest.approx(work["flops"] / 197e12)
    assert floor["memory_s"] == pytest.approx(work["bytes"] / 819e9)


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
