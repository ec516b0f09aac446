"""The ``olmo_hybrid`` family's configuration, cell, metrics, reader,
work counts and seeded weights: they validate through the manifest as it
is, the cell rehearses end to end on the CPU with ``correct`` true and no
device metric, and the weights are pinned by hash."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, spans, weights
from benchmarks import weights_olmo_hybrid as G
from benchmarks.run import merge
from test_run_serve import bench, last_line

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "olmo-hybrid-7b-bf16"
CELL = "olmo-hybrid-7b-bf16.longprompt-steady"
TYPES = ["linear_attention"] * 3 + ["full_attention"]

# The catalog row's ``config`` (model-configs guide,
# architectures.jsonl, "Olmo-Hybrid-7B"), every key.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": TYPES * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
JOINED = {
    "prefill_device_ms_per_ktok", "decode_device_ms_per_ktok",
    "ttft_queue_share", "prefill_useful_token_share",
    "decode_device_ms_per_step", "decode_useful_token_share",
    "decode_kv_gather_share", "decode_attn_core_share"}
NEW_METRICS = {"prefill_delta_rule_share", "decode_state_update_share",
               "decode_state_rw_roofline", "prefill_delta_rule_roofline"}
LAYER = "Linear-attention layer (models/olmo_hybrid.py, ops/gated_delta.py)"


@pytest.fixture(scope="module")
def spec():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(CONFIG)


def test_the_manifest_with_the_new_cell_is_valid(spec):
    manifest.validate(spec)
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "longprompt-steady"
    assert cell["config"] == CONFIG
    e2e = {m["name"] for m in manifest.cell_metrics(spec, CELL,
                                                    "end_to_end")}
    assert e2e == {"ttft_p95_ms", "tpot_p90_ms", "setup_s"}
    layers = {m["name"] for m in manifest.cell_metrics(spec, CELL,
                                                       "per_layer")}
    assert layers == JOINED | NEW_METRICS
    # one configuration, one cell, four per-layer metrics, each appended
    assert [c["name"] for c in spec["configs"]].index(CONFIG) == 4
    assert [w["name"] for w in spec["workloads"]].index(CELL) == 4
    new = [m for m in spec["per_layer"] if m["name"] in NEW_METRICS]
    assert len(new) == 4
    for m in new:
        assert m["workloads"] == [CELL] and m["layer"] == LAYER
        assert m["unit"] == "%" and m["source"] == "device_trace"
    # ... and the cell's name appended to the lists it joins, last
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in JOINED | {"ttft_p95_ms", "tpot_p90_ms"}:
            assert m["workloads"][-1] == CELL, m["name"]
    # the cells that were there report what they reported
    glm = {m["name"] for m in manifest.cell_metrics(
        spec, "glm-4.7-flash-bf16.longprompt-steady", "per_layer")}
    assert not glm & NEW_METRICS


def test_metric_files_read_the_scopes_the_program_names():
    shares = {"prefill_delta_rule_share": "ttft_p95_ms",
              "decode_state_update_share": "tpot_p90_ms",
              "decode_state_rw_roofline": "tpot_p90_ms",
              "prefill_delta_rule_roofline": "ttft_p95_ms"}
    for name, moves in shares.items():
        m = manifest.load_metric(name)
        assert m["layer"] == LAYER and m["moves"] == moves
        args = m["args"]
        assert args["scope"] == "delta_rule"
        assert {"linear_mixer", "delta_rule", "attn_core", "kv_gather",
                "out_ffn", "lm_head"} <= set(args["scopes"])
        prefill = name.startswith("prefill")
        assert args["modules"] == (["_admit_wave", "_prefill_chunk"]
                                   if prefill else ["_decode", "_verify"])
        manifest.load_module("readers", m["reader"])
        if "work" in args:
            assert callable(manifest.load_function(args["work"]))
    assert manifest.load_metric("decode_state_rw_roofline")["args"]["work"] \
        == "delta_work.decode_state_work"
    assert manifest.load_metric("prefill_delta_rule_roofline")["reader"] \
        == "delta_rule_roofline"
    # the program does name them
    from skypilot_tpu.infer import hybrid
    from skypilot_tpu.models import olmo_hybrid
    from skypilot_tpu.ops import gated_delta
    import inspect
    assert 'named_scope("delta_rule")' in inspect.getsource(gated_delta)
    assert 'named_scope("delta_rule")' in inspect.getsource(hybrid)
    assert 'named_scope("linear_mixer")' in inspect.getsource(olmo_hybrid)


def test_configuration_keeps_every_published_key(config, spec):
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    for key, value in PUBLISHED.items():
        assert key in config, key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 16
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    assert {"norm_placement", "qk_norm", "rope", "linear_mixer",
            "A_log_dt_bias", "weights", "layer_types",
            "kv_pool_heads"} <= set(config["assumed"])
    assert "null" in config["assumed"]["rope"]
    assert "two pipeline stages" in config["deployment"]
    dims = manifest.load_family(config).dims(config)
    assert (dims.n_layers, dims.lin_per_period, dims.n_lin_layers,
            dims.n_full_layers, dims.vocab_size) == (16, 3, 12, 4, 100352)
    assert dims.rope_theta is None
    assert dims.num_params() == config["parameters"] == 4_100_788_944
    whole = manifest.load_family(config).dims(
        dict(config, num_hidden_layers=32))
    assert whole.num_params() == 7_430_870_688
    b = config["bytes"]
    assert b["weights_bf16"] == 2 * dims.num_params()
    assert b["weights_bf16_published_32_layers"] == 2 * whole.num_params()
    assert b["linear_layer"] == 2 * dims.lin_layer_params()
    assert b["full_layer"] == 2 * dims.full_layer_params()
    assert b["recurrent_state_per_slot_per_linear_layer"] \
        == 30 * 192 * 96 * 4 == 2_211_840
    assert b["conv_tail_per_slot_per_linear_layer"] == 3 * 11520 * 2
    assert b["kv_per_token_4_full_layers_30_heads"] == 61_440
    flags = config["program"]["flags"]
    blocks = int(flags[flags.index("--kv-blocks") + 1])
    assert flags == ["--slots", "32", "--max-len", "8704", "--max-burst",
                     "32", "--open-burst", "4", "--admit-wave", "4",
                     "--spec-k", "0", "--warm-grid", "--kv-blocks",
                     str(blocks), "--prefix-pool", "0"]
    assert b[f"kv_pool_{blocks}_blocks_x_256_rows"] \
        == blocks * 256 * b["kv_per_token_as_pooled_32_heads"]
    # what a deployment would hold: weights + state + pool between 70
    # and 90 % of a 17.18 GB chip before transients
    resident = b["weights_bf16"] + b["recurrent_state_33_slots_x_12_layers"] \
        + b[f"kv_pool_{blocks}_blocks_x_256_rows"]
    assert 0.70 < resident / 17.18e9 < 0.90
    assert config["precision"]["weights"] == "bf16"
    assert "float32 recurrent state" in config["precision"]["stated"]


def test_the_cell_is_the_issues(config):
    cell = manifest.load_workload(CELL)
    mix = manifest.load_traffic(cell)
    base = manifest.load_traffic(dict(cell, traffic_overrides={}))
    assert {k: v for k, v in mix.items() if k != "rate_rps"} == base
    assert mix["shape_seed"] == 20260928 and mix["lead_in_s"] == 10
    assert mix["prompt_tokens"]["median"] == 2048
    assert cell["end_to_end"] == ["ttft_p95_ms", "tpot_p90_ms", "setup_s"]
    assert set(cell["correct"]["limits"]) == {"served_logit_gap_max",
                                              "served_logit_gap_mean"}
    assert "control" in cell["correct"]["limits_from"]
    gen = manifest.load_module("traffic", mix["generator"])
    plan = gen.generate(mix, 2 ** 31 + 5, 40.0, 100352,
                        config["program"]["max_len"])
    assert all(len(r["prompt"]) + r["max_new"] <= 8704
               and max(r["prompt"]) < 100352 for r in plan["requests"])


def _sha(a):
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(a)).tobytes()).hexdigest()[:16]


_PINNED = {
    "embed": "8325f9f5d90c0731", "lm_head": "80405be8486dd704",
    "lin.wq": "1d96f647e4dc52b5", "lin.conv": "0a598d68da849315",
    "lin.A_log": "6317658ea6462cee", "lin.dt_bias": "f5a6b187876ad11a",
    "lin.wo": "979b92d99a6c8862", "full.wk": "0e41818bcab77d6b",
    "full.q_norm": "02b690c33ba6fecb", "full.w_down": "50f34e261f984633",
    "wv's stream, layer 5, (3840, 2, 192)": "fcc578f2394dbdc0",
    "wq's stream, layer 7, (64, 30, 128)": "3780ff89060ae1f0"}


def test_seeded_weights_are_pinned(config):
    """At the rehearsal size the whole tree, and two tensors at the
    published widths' fan-in (a hash is of the element's index in ITS
    shape), as the serve child and the reference reach them."""
    family = manifest.load_family(config)
    tiny = family.dims(merge(config, config["rehearse"]))
    seed = 2 ** 32 + 12345                      # more than 32 bits
    p = G.build_serving(seed, tiny)
    got = {"embed": _sha(p["embed"]), "lm_head": _sha(p["lm_head"])}
    for name in ("wq", "conv", "A_log", "dt_bias", "wo"):
        got[f"lin.{name}"] = _sha(jnp.stack([g[name] for g in p["lin"]]))
    for name in ("wk", "q_norm", "w_down"):
        got[f"full.{name}"] = _sha(p["full"][name])
    key = jnp.asarray(weights.seed_key(seed))
    full = family.dims(config)
    got["wv's stream, layer 5, (3840, 2, 192)"] = _sha(jax.jit(lambda k: G.matrix(
        k, "wv", np.uint32(5), (3840, 2, 192), 1))(key))
    got["wq's stream, layer 7, (64, 30, 128)"] = _sha(jax.jit(lambda k: G.matrix(
        k, "wq", np.uint32(7), (64, 30, 128), 1))(key))
    assert G.mixer_shapes(full, True)["wv"] == ((3840, 30, 192), 1)
    assert got == _PINNED
    # another seed, other weights; a layer alone = its place in the stack
    q = G.build_serving(seed - 2 ** 32, tiny)
    assert (np.asarray(q["lin"][0]["wq"])
            != np.asarray(p["lin"][0]["wq"])).any()
    one = G.layer_tensors(key, tiny, np.uint32(6), True)  # place 2, period 1
    assert (np.asarray(one["w_down"])
            == np.asarray(p["lin"][2]["w_down"][1])).all()
    a = np.exp(np.asarray(jnp.stack([g["A_log"] for g in p["lin"]]),
                          np.float32))
    assert G.A_RANGE[0] * 0.99 <= a.min() and a.max() <= G.A_RANGE[1] * 1.01
    dt = np.log1p(np.exp(np.asarray(
        jnp.stack([g["dt_bias"] for g in p["lin"]]), np.float32)))
    assert G.DT_RANGE[0] * 0.9 <= dt.min() and dt.max() <= G.DT_RANGE[1] * 1.1


def test_work_counts(config):
    """``delta_work``'s arithmetic, by hand: per token and linear layer
    7 H d_k d_v = 3.87 MFLOP and 34 560 B of q, k, v, o; a state is
    2 211 840 B."""
    dims = manifest.load_family(config).dims(config)
    per_token = manifest.load_function("delta_work.rule_flops_per_token")
    assert per_token(dims) == 7 * 30 * 96 * 192 == 3_870_720
    assert manifest.load_function("delta_work.state_bytes")(dims) \
        == 2_211_840
    assert manifest.load_function("delta_work.operand_bytes_per_token")(
        dims) == 34_560
    decode = manifest.load_function("delta_work.decode_state_work")
    work = decode(dims, 10.0)
    assert work["bytes"] == 12 * 10 * (2 * 2_211_840 + 34_560)
    assert work["flops"] == 12 * 10 * 3_870_720
    assert decode(dims, 0)["bytes"] == 0
    # bound by bytes: 5.4e-6 s of HBM against 2e-8 s of FLOPs a row-layer
    from benchmarks import flops
    assert flops.least_seconds(work, "TPU v5 lite")["bound"] == "memory"
    prefill = manifest.load_function("delta_work.prefill_rule_work")
    work = prefill(dims, 512.0, 2.0)
    assert work["flops"] == 12 * 512 * 3_870_720
    assert work["bytes"] == 12 * (512 * 34_560 + 2 * 2_211_840)
    assert flops.least_seconds(work, "TPU v5 lite")["bound"] == "memory"


def test_readers_read_nothing_where_there_is_nothing_to_read(tmp_path,
                                                             config):
    """On a trace of a program without the scope and the annotations
    (the fixture: a Llama engine before this family) both readers give
    ``None`` and do not raise; so they do without a trace."""
    path = os.path.join(HERE, "data", "spans_fixture.xplane.pb")
    facts = {"trace": {"file": path}, "device": {"kind": "TPU v5 lite"}}
    ctx = {"out_dir": str(tmp_path), "config": config,
           "bench_dir": manifest.BENCH_DIR}
    for name in NEW_METRICS:
        m = manifest.load_metric(name)
        reader = manifest.load_module("readers", m["reader"])
        assert reader.read(facts, ctx, **m["args"]) is None, name
        assert reader.read({"trace": {}}, ctx, **m["args"]) is None, name
    # the scope it CAN find in the fixture, it reads as scoped_ops does
    m = manifest.load_metric("prefill_delta_rule_roofline")
    reader = manifest.load_module("readers", m["reader"])
    args = dict(m["args"], scope="attn_core", modules=["_decode_burst"])
    red = spans.reduce_xplane(path)
    tokens = spans.sum_args(spans.annotations_named(
        red, "engine.chunk.dispatch"), "chunk_tokens") + spans.sum_args(
        spans.annotations_named(red, "engine.wave.dispatch"),
        "prompt_tokens")
    got = reader.read(facts, ctx, **args)
    # (a synthetic trace: the number means nothing, the path is what runs)
    assert tokens > 0 and got is not None and got > 0


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("traced_olmo")
    rc, lines, err = bench(["--workload", CELL, "--seed", str(2 ** 31 + 31),
                            "--seconds", "6", "--trace", "1", "--rehearse",
                            "--out", str(out)])
    assert rc == 0, err[-2000:]
    return last_line(lines), lines, out


def test_traced_rehearsal_is_correct_and_prints_no_device_metric(traced):
    obj, lines, out = traced
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] >= 3
    assert obj["metrics"] == {} and "breakdown" not in obj
    assert obj["device"]["platform"] == "cpu"
    (reh,) = [l for l in lines if l.startswith("REHEARSAL_TRACE ")]
    for program in ("jit__decode_burst", "jit__prefill_chunk",
                    "jit__admit_wave"):
        assert program in reh
    checks = {c["name"]: c for c in
              (json.loads(l[6:]) for l in lines if l.startswith("CHECK "))}
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["served_logit_gap_max"]["ok"] \
        and checks["served_logit_gap_mean"]["ok"]
    # both prefill paths ran, chunks carried a resident state, and the
    # bursts say whose state they updated
    results = json.load(open(out / "results.json"))
    red = spans.reduce_xplane(results["facts"]["trace"]["file"])
    assert red["phases"]["engine.wave.dispatch"]["n"] > 0
    chunks = spans.annotations_named(red, "engine.chunk.dispatch")
    assert chunks and spans.sum_args(chunks, "carried") > 0
    assert any(a[4].get("carried") == 0 for a in chunks)
    bursts = spans.annotations_named(red, "engine.decode.dispatch")
    assert bursts and all(a[4]["state_rows"] == a[4]["slots"]
                          for a in bursts)


def test_untraced_rehearsal_reports_the_end_to_end_metrics(tmp_path):
    rc, lines, err = bench(["--workload", CELL, "--seed", str(2 ** 32 + 3),
                            "--seconds", "5", "--trace", "0", "--rehearse",
                            "--control", "--out", str(tmp_path / "out")])
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["correct"] is True and obj["failed"] == 0
    assert set(obj["metrics"]) == {"ttft_p95_ms", "tpot_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in obj["metrics"].values())
    (ref,) = [json.loads(l[10:]) for l in lines
              if l.startswith("REFERENCE ")]
    # both controls were computed, each in a precision below the stated
    assert "act_bits=8" in ref["control_precision"]
    assert "state_bits=16" in ref["control_state_precision"]
    assert ref["control_gap_mean"] > ref["served_gap_mean"]
