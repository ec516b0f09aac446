"""The ``lfm2_moe`` family's configuration, cell, metrics, work counts and
seeded weights: they validate through the manifest as it is, the
configuration's arithmetic is ISSUE 44's recomputed, the cell is the
chat mix unedited at the stated share of its knee, and the new metric
files read what the program names. Entries are found BY NAME, never by
position or by count: a later PR appends its own."""

import hashlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops, manifest, moe_work, weights
from benchmarks import weights_lfm2_moe as G
from benchmarks.readers import annotation_ratio
from benchmarks.run import merge

CONFIG = "lfm2-8b-a1b-bf16"
CELL = "lfm2-8b-a1b-bf16.chat-steady"
CHAT = "mistral-7b-w8a8.chat-steady"
C, F = "conv", "full_attention"

# The catalog row's ``config`` (model-configs guide,
# architectures.jsonl, "LFM2-8B-A1B"), every key.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [C, C, F, C, C, C, F, C, C, C, F, C, C, C, F, C, C, C, F,
                    C, C, F, C, C],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
JOINED = {
    "decode_device_ms_per_step", "decode_device_ms_per_ktok",
    "decode_useful_token_share", "decode_attn_core_share",
    "decode_expert_ffn_share", "decode_expert_read_roofline",
    "setup_pre_program_s", "setup_weights_state_s", "setup_warm_grid_s",
    "setup_lowering_s", "setup_compile_or_load_s", "setup_cache_misses",
    "ttft_p95_ms.chat", "prefill_device_ms_per_ktok.chat",
    "ttft_queue_share.chat", "prefill_useful_token_share.chat"}
NEW_METRICS = {"decode_short_conv_share", "decode_experts_touched_share"}
LAYER = "Short-convolution layers (models/lfm2_moe.py, infer/shortconv.py)"
EXPERT_LAYER = "Expert layer (models/glm_moe.py)"


@pytest.fixture(scope="module")
def spec():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(CONFIG)


@pytest.fixture(scope="module")
def dims(config):
    return manifest.load_family(config).dims(config)


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_the_manifest_with_the_new_cell_is_valid(spec):
    manifest.validate(spec)
    cell = _named(spec["workloads"], CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "chat-steady", CONFIG)
    e2e = {m["name"] for m in manifest.cell_metrics(spec, CELL,
                                                    "end_to_end")}
    assert e2e == {"tpot_p90_ms", "setup_s"}
    layers = {m["name"] for m in manifest.cell_metrics(spec, CELL,
                                                       "per_layer")}
    assert layers == JOINED | NEW_METRICS
    assert _named(spec["configs"], CONFIG)["reduced"] == [
        "num_hidden_layers", "num_dense_layers"]
    for name in NEW_METRICS:
        m = _named(spec["per_layer"], name)
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "tpot_p90_ms"
    share = _named(spec["per_layer"], "decode_short_conv_share")
    assert share["layer"] == LAYER and share["source"] == "device_trace"
    assert share["better"] == "lower"
    # ISSUE 44's decode_short_conv_roofline is NOT there: the compiler
    # fetches the operator's weights into VMEM asynchronously, under its
    # neighbours' ops, so the scope's seconds leave the transfer out and
    # a share of the HBM peak over them read 249 % (PERF.md section 6)
    assert not [m for m in spec["per_layer"]
                if m["name"] == "decode_short_conv_roofline"]
    touched = _named(spec["per_layer"], "decode_experts_touched_share")
    assert touched["layer"] == EXPERT_LAYER == _named(
        spec["per_layer"], "decode_expert_read_roofline")["layer"]
    assert touched["source"] == "program_counter"
    # the cell's name is appended to the lists it joins, and to no other
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in JOINED | NEW_METRICS | {"tpot_p90_ms"}:
            assert m["workloads"][-1] == CELL or CELL in m["workloads"]
        elif "workloads" in m:
            assert CELL not in m["workloads"], m["name"]
    # every reading of the cell moves a metric the cell reports
    for m in manifest.cell_metrics(spec, CELL, "per_layer"):
        assert m["moves"] in ("tpot_p90_ms", "setup_s"), m["name"]
    # the cells that were there report none of the new metrics, and the
    # benchmark still has one four-chip cell
    for other in spec["workloads"]:
        if other["name"] != CELL:
            theirs = {m["name"] for m in manifest.cell_metrics(
                spec, other["name"], "per_layer")}
            assert not theirs & NEW_METRICS
    assert sum(1 for w in spec["workloads"] if w["chips"] == 4) == 1
    # the other chat cell's first-token readings are this cell's too
    for name in ("ttft_p95_ms.chat", "ttft_queue_share.chat"):
        assert {CHAT, CELL} <= set(_named(spec["per_layer"],
                                          name)["workloads"])


def test_configuration_keeps_every_published_key(config, spec, dims):
    entry = _named(spec["configs"], CONFIG)
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers"]
    assert config["published"] == {"num_hidden_layers": 24,
                                   "num_dense_layers": 2}
    for key, value in PUBLISHED.items():
        assert key in config, key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_dense_layers"]) \
        == (13, 1)
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert config["family"] == "lfm2_moe"
    # every point the reference can switch off is stated as assumed
    from benchmarks.reference import lfm2_moe as ref
    assert ref.ASSUMED <= set(config["assumed"])
    assert {"weights", "num_hidden_layers", "num_dense_layers",
            "kv_row_layout", "conv_operator"} <= set(config["assumed"])
    assert config["tie_word_embeddings"] is True
    assert "two pipeline stages of whole layers" in config["deployment"]
    # the layer list is kept whole; its first 13 run: c | c F c c c F c c
    # c F c c
    assert dims.layer_types == tuple(PUBLISHED["layer_types"][:13])
    assert (dims.n_layers, dims.n_dense_layers, dims.n_conv_layers,
            dims.n_full_layers, dims.n_moe_layers) == (13, 1, 10, 3, 12)
    assert (dims.n_routed_experts, dims.experts_per_tok, dims.head_dim,
            dims.vocab_size) == (32, 4, 64, 65536)


def test_the_configurations_arithmetic_is_the_issues(config, dims):
    """Parameters, bytes a token, tail bytes: ISSUE 44's Motivation,
    recomputed from the sizes."""
    assert dims.conv_params() == 16_783_360
    assert dims.attn_params() == 10_485_888
    assert dims.dense_ffn_params() == 44_040_192
    assert dims.expert_ffn_params() == 352_387_104
    assert dims.expert_params() == 3 * 2048 * 1792
    assert dims.num_params() == config["parameters"] == 4_606_249_728 \
        == (10 * 16_783_360 + 3 * 10_485_888 + 13 * 4_096 + 44_040_192
            + 12 * 352_387_104 + 134_219_776)
    whole = manifest.load_family(config).dims(
        dict(config, **config["published"]))
    assert whole.num_params() == 8_339_930_560 \
        == config["parameters_published_24_layers"]
    assert config["parameters_published_untied"] == 8_474_148_288
    b = config["bytes"]
    assert b["weights_bf16"] == 2 * dims.num_params() == 9_212_499_456
    assert b["weights_bf16_published_24_layers"] == 2 * whole.num_params()
    assert b["weights_bf16_published_24_layers"] > 16e9   # no chip holds it
    assert b["one_routed_expert"] == 2 * dims.expert_params() == 22_020_096
    assert b["conv_operator_per_layer"] == 2 * dims.conv_params()
    assert b["kv_per_token_3_attention_layers"] == dims.kv_token_bytes \
        == 6144
    assert b["kv_per_token_published_6_attention_layers"] \
        == whole.kv_token_bytes == 12288
    assert b["conv_tail_per_slot_per_conv_layer"] == dims.tail_bytes == 8192
    assert b["conv_tails_33_slots_x_10_layers"] == 33 * 10 * 8192 \
        == 2_703_360
    assert b["kv_pool_165_blocks_x_256_rows"] == 33 * 1280 * 6144
    flags = config["program"]["flags"]
    # ISSUE 44's flags, no more and no fewer: the span ladder is the
    # engine's default, as a user of the chat recipe gets it
    assert flags == ["--slots", "32", "--max-len", "1280",
                     "--max-burst", "32", "--open-burst", "4",
                     "--admit-wave", "4", "--spec-k", "0",
                     "--warm-grid", "--prefix-pool", "0"]
    assert "--weights-int8" not in flags and "--kv-int8" not in flags
    # what a deployment would hold: over half the chip is weights before
    # any cache, and nearly none of it is cache — the family's nature
    assert 0.5 < b["weights_bf16"] / 16.9e9 < 0.6
    cache = b["kv_pool_165_blocks_x_256_rows"] \
        + b["conv_tails_33_slots_x_10_layers"]
    assert cache < 0.03 * b["weights_bf16"]
    assert config["precision"]["weights"] == "bf16"
    assert "float32 router" in config["precision"]["stated"]
    # the program counts the same, from its own config object
    cfg = manifest.load_family(config).register(dict(config,
                                                     name="lfm2-count"))
    assert cfg.num_params() == dims.num_params()
    from skypilot_tpu.infer import shortconv
    assert shortconv.token_bytes(cfg) == dims.kv_token_bytes
    assert shortconv.slot_state_bytes(cfg) == 10 * dims.tail_bytes


def test_the_cell_is_the_issues(config):
    """The EXISTING chat mix, unedited but for the rate; the chat
    recipe's flags in bf16; tpot_p90_ms and setup_s end to end."""
    cell = manifest.load_workload(CELL)
    mix = manifest.load_traffic(cell)
    base = manifest.load_traffic(dict(cell, traffic_overrides={}))
    assert {k: v for k, v in mix.items() if k != "rate_rps"} == base
    assert set(cell["traffic_overrides"]) == {"rate_rps"}
    assert mix["shape_seed"] == 20260927 and mix["lead_in_s"] == 10
    assert mix["arrivals"] == {"process": "poisson"}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.9, "min": 32, "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 96,
                                    "sigma": 0.7, "min": 8, "max": 256}
    assert mix["shared_prefix"]["share"] == 0
    knee = cell["knee"]
    assert knee["share"] in (0.8, 0.6)           # ISSUE 44's one fallback
    assert mix["rate_rps"] \
        == int(knee["rate_rps"] * knee["share"] * 10 + 1e-9) / 10
    assert "knee_sweep" in knee["swept"]
    assert cell["end_to_end"] == ["tpot_p90_ms", "setup_s"]
    assert "ttft_p95_ms" in cell["ttft_note"]
    assert set(cell["correct"]["limits"]) == {"served_logit_gap_max",
                                              "served_logit_gap_mean"}
    for word in ("control", "tail", "int8"):
        assert word in cell["correct"]["limits_from"], word
    gen = manifest.load_module("traffic", mix["generator"])
    plan = gen.generate(mix, 2 ** 31 + 5, 40.0, 65536,
                        config["program"]["max_len"])
    lens = [len(r["prompt"]) for r in plan["requests"]]
    assert all(n + r["max_new"] <= 1280 and max(r["prompt"]) < 65536
               for n, r in zip(lens, plan["requests"]))
    # both prefill paths run: prompts either side of the 512-token chunk
    assert 0.1 < sum(n > 512 for n in lens) / len(lens) < 0.5
    # enough requests for the 90th percentile to be a supported tail
    judged = round(mix["rate_rps"] * 40)
    assert judged >= 100


def test_metric_files_read_what_the_program_names():
    m = manifest.load_metric("decode_short_conv_share")
    assert m["layer"] == LAYER and m["moves"] == "tpot_p90_ms"
    assert m["reader"] == "scoped_ops" and "work" not in m["args"]
    args = m["args"]
    assert args["scope"] == "short_conv"
    assert args["modules"] == ["_decode", "_verify"]
    assert {"short_conv", "attn_core", "qkv_proj", "out_ffn",
            "moe_experts", "router", "lm_head"} <= set(args["scopes"])
    manifest.load_module("readers", m["reader"])
    touched = manifest.load_metric("decode_experts_touched_share")
    assert touched["reader"] == "annotation_ratio"
    assert touched["layer"] == EXPERT_LAYER
    assert touched["args"] == {
        "numerator": [["engine.decode.fetch", "experts_read"]],
        "denominator": [["engine.decode.fetch", "experts_held"]],
        "counted_decode": True}
    # the program does name them
    from skypilot_tpu.infer import engine, shortconv
    from skypilot_tpu.models import lfm2_moe
    src = inspect.getsource(lfm2_moe)
    for scope in ("short_conv", "qkv_proj", "out_ffn"):
        assert f'named_scope("{scope}")' in src
    assert '"attn_core"' in inspect.getsource(shortconv)
    assert '"experts_held"' in inspect.getsource(engine)
    assert shortconv.SPARE_COLUMN[0] == "experts_read"


def test_the_touched_share_on_hand_made_annotations(monkeypatch):
    """``annotation_ratio`` over hand-made fetch annotations: Σ
    experts_read ÷ Σ experts_held in percent; a program that says no
    ``experts_held`` (the parent) gives nothing and does not raise."""
    spec = manifest.load_metric("decode_experts_touched_share")["args"]
    fetches = [{"experts_read": 1200, "experts_held": 1536, "k": 4},
               {"experts_read": 300, "experts_held": 384, "k": 1}]

    def named(red, name, counted):
        assert name == "engine.decode.fetch" and counted is True
        return red

    monkeypatch.setattr(annotation_ratio.spans, "load",
                        lambda facts, ctx: ctx["red"])
    monkeypatch.setattr(annotation_ratio.spans, "annotations_named", named)
    monkeypatch.setattr(
        annotation_ratio.spans, "sum_args",
        lambda events, *args: sum(
            np.prod([e[a] for a in args]) for e in events
            if all(a in e for a in args)))
    got = annotation_ratio.read({}, {"red": fetches}, **spec)
    assert got == pytest.approx(100.0 * 1500 / 1920)
    parent = [{"experts_read": 1200, "k": 4}]
    assert annotation_ratio.read({}, {"red": parent}, **spec) is None


def test_work_counts(dims):
    """``moe_work``'s arithmetic with this family's dims."""
    # the expert read: 12 layers, 32 experts of 22.0 MB, top-4
    touched = moe_work.expected_experts_touched(dims, 20.0)
    assert touched == pytest.approx(32 * (1 - (28 / 32) ** 20))
    assert 29 < touched < 30
    e = moe_work.decode_expert_read_work(dims, 20.0)
    assert e["bytes"] == pytest.approx(12 * touched * 22_020_096)
    assert 7.7e9 < e["bytes"] < 7.9e9            # ISSUE 44's 7.8 GB a step
    floor = flops.least_seconds(e, "TPU v5 lite")
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(e["bytes"] / 819e9)


def _sha(a):
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(a)).tobytes()).hexdigest()[:16]


def test_seeded_weights_are_a_function_of_seed_tensor_layer_and_index(
        config):
    """At the rehearsal size: the tree has the program's layout, no
    ``ws_*`` tensor and no head; a layer alone is its place in the
    stack; another seed gives other weights; a seed past 32 bits works;
    the bias stays inside its range."""
    family = manifest.load_family(config)
    tiny = family.dims(merge(config, config["rehearse"]))
    seed = 2 ** 32 + 12345                      # more than 32 bits
    p = G.build_serving(seed, tiny)
    assert set(p) == {"embed", "final_norm", "layers"}
    assert len(p["layers"]) == tiny.n_layers == 8
    names = set().union(*(set(layer) for layer in p["layers"]))
    assert not [n for n in names if n.startswith("ws_")]
    for i, layer in enumerate(p["layers"]):
        assert ("w_in" in layer) == tiny.is_conv(i) == ("wq" not in layer)
        assert ("w_gate" in layer) == (i < tiny.n_dense_layers) \
            == ("we_gate" not in layer)
    key = jnp.asarray(weights.seed_key(seed))
    one = G.layer_tensors(key, tiny, np.uint32(3), tiny.is_conv(3), True)
    for name, t in one.items():
        assert (np.asarray(t) == np.asarray(p["layers"][3][name])).all()
    again = G.build_serving(seed, tiny)
    assert _sha(again["layers"][5]["we_up"]) == _sha(p["layers"][5]["we_up"])
    other = G.build_serving(seed - 2 ** 32, tiny)
    assert _sha(other["embed"]) != _sha(p["embed"])
    bias = np.asarray(jnp.stack([layer["router_bias"] for layer
                                 in p["layers"][1:]]), np.float32)
    assert 0.05 < np.abs(bias).max() <= G.BIAS_RANGE
    taps = np.asarray(p["layers"][0]["conv"], np.float32)
    assert taps.shape == (3, tiny.d_model) and abs(taps.std() - 3 ** -0.5) \
        < 0.1
    full = family.dims(config)
    assert G.op_shapes(full, True)["w_in"] == ((2048, 6144), 1)
    assert G.ffn_shapes(full, True)["we_gate"] == ((32, 2048, 1792), 1)
    assert jax.tree.leaves(p)[0].dtype == jnp.bfloat16
