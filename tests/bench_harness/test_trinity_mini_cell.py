"""The ``afmoe`` family's configuration, cell, traffic mix, metrics,
reader, work counts and seeded weights: they validate through the
manifest as it is, the cell rehearses end to end on the CPU with
``correct`` true, BOTH controls refused and no device metric, and the
weights are pinned by hash. Entries are found BY NAME, never by
position: a later PR appends its own."""

import hashlib
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops, manifest, spans, weights
from benchmarks import weights_afmoe as G
from benchmarks.run import merge
from test_run_serve import bench, last_line

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "trinity-mini-bf16"
CELL = "trinity-mini-bf16.longctx-steady"
S, F = "sliding_attention", "full_attention"

# The catalog row's ``config`` (model-configs guide,
# architectures.jsonl, "Trinity-Mini"), every key.
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [S, S, S, F] * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
JOINED = {
    "decode_device_ms_per_ktok",
    "decode_device_ms_per_step", "decode_useful_token_share",
    "decode_kv_gather_share", "decode_attn_core_share",
    "decode_expert_ffn_share",
    "decode_expert_read_roofline", "setup_pre_program_s",
    "setup_weights_state_s", "setup_warm_grid_s", "setup_lowering_s",
    "setup_compile_or_load_s", "setup_cache_misses"}
NEW_METRICS = {"prefill_window_attn_share", "decode_window_attn_share",
               "decode_window_read_roofline",
               "prefill_window_attn_roofline"}
LAYER = "Window layers (models/afmoe.py, infer/windowed.py)"
# The driver's 2 x 6 runs spread the cell's first-token p95 over half its
# bound, so the cell took ISSUE 42's fallback (the chat cell's of PR 37):
# it is not on ``ttft_p95_ms``'s list, and the prefill readings it would
# have joined carry names of their own that move ``tpot_p90_ms``.
OWN_NAMES = {"prefill_device_ms_per_ktok.longctx":
                 "prefill_device_ms_per_ktok",
             "ttft_queue_share.longctx": "ttft_queue_share",
             "prefill_useful_token_share.longctx":
                 "prefill_useful_token_share",
             "prefill_expert_ffn_share.longctx": "prefill_expert_ffn_share",
             "ttft_p95_ms.longctx": "ttft_p95_ms.chat"}


@pytest.fixture(scope="module")
def spec():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(CONFIG)


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_the_manifest_with_the_new_cell_is_valid(spec):
    manifest.validate(spec)
    cell = _named(spec["workloads"], CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longctx-steady"
    assert cell["config"] == CONFIG
    e2e = {m["name"] for m in manifest.cell_metrics(spec, CELL,
                                                    "end_to_end")}
    assert e2e == {"tpot_p90_ms", "setup_s"}
    layers = {m["name"] for m in manifest.cell_metrics(spec, CELL,
                                                       "per_layer")}
    assert layers == JOINED | NEW_METRICS | set(OWN_NAMES)
    # one configuration, one cell, four per-layer metrics, found by name
    assert _named(spec["configs"], CONFIG)["reduced"] == [
        "num_hidden_layers", "num_dense_layers"]
    for name in NEW_METRICS:
        m = _named(spec["per_layer"], name)
        assert m["workloads"] == [CELL] and m["layer"] == LAYER
        assert m["unit"] == "%" and m["source"] == "device_trace"
        assert m["better"] == ("higher" if "roofline" in name else "lower")
    # ... and the cell's name is on the lists it joins; every per-layer
    # metric has a list
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in JOINED | {"tpot_p90_ms"}:
            assert CELL in m["workloads"], m["name"]
        elif m["name"] in set(OWN_NAMES.values()) | {"ttft_p95_ms"}:
            assert CELL not in m["workloads"], m["name"]
    # every reading of the cell moves a metric the cell reports
    for m in manifest.cell_metrics(spec, CELL, "per_layer"):
        assert m["moves"] in ("tpot_p90_ms", "setup_s"), m["name"]
    for own, accepted in OWN_NAMES.items():
        mine, theirs = (_named(spec["per_layer"], n) for n in (own, accepted))
        assert mine["workloads"] == [CELL] and mine["moves"] == "tpot_p90_ms"
        assert {k: mine[k] for k in ("unit", "better", "source", "layer")} \
            == {k: theirs[k] for k in ("unit", "better", "source", "layer")}
    assert all("workloads" in m for m in spec["per_layer"])
    # the cells that were there report what they reported
    for other in ("glm-4.7-flash-bf16.longprompt-steady",
                  "olmo-hybrid-7b-bf16.longprompt-steady",
                  "mistral-7b-w8a8.chat-steady"):
        theirs = {m["name"] for m in manifest.cell_metrics(
            spec, other, "per_layer")}
        assert not theirs & (NEW_METRICS | set(OWN_NAMES))
    assert sum(1 for w in spec["workloads"] if w["chips"] == 4) == 1


def test_metric_files_read_the_scopes_the_program_names():
    moves = {"prefill_window_attn_share": "tpot_p90_ms",
             "decode_window_attn_share": "tpot_p90_ms",
             "decode_window_read_roofline": "tpot_p90_ms",
             "prefill_window_attn_roofline": "tpot_p90_ms"}
    for name, moved in moves.items():
        m = manifest.load_metric(name)
        assert m["layer"] == LAYER and m["moves"] == moved
        args = m["args"]
        assert args["scope"] == "window_attn"
        assert {"window_attn", "attn_core", "qkv_proj", "out_ffn",
                "moe_experts", "router", "lm_head"} <= set(args["scopes"])
        prefill = name.startswith("prefill")
        assert args["modules"] == (["_admit_wave", "_prefill_chunk"]
                                   if prefill else ["_decode", "_verify"])
        manifest.load_module("readers", m["reader"])
        if "roofline" in name:
            assert m["reader"] == "window_attn_roofline"
            assert args["phase"] == ("prefill" if prefill else "decode")
            assert callable(manifest.load_function(args["work"]))
        else:
            assert m["reader"] == "scoped_ops" and "work" not in args
    # the cell's own names read what the accepted names read
    for own, accepted in OWN_NAMES.items():
        mine, theirs = manifest.load_metric(own), manifest.load_metric(
            accepted)
        assert mine["moves"] == "tpot_p90_ms"
        assert {k: v for k, v in mine.items() if k not in ("moves", "name")} \
            == {k: v for k, v in theirs.items()
                if k not in ("moves", "name")}
    # the program does name them, and keeps the two kinds disjoint
    from skypilot_tpu.infer import windowed
    from skypilot_tpu.models import afmoe
    for module in (windowed, afmoe):
        src = inspect.getsource(module)
        assert '"window_attn"' in src and '"attn_core"' in src
    assert 'named_scope("qkv_proj")' in inspect.getsource(afmoe)
    assert 'named_scope("out_ffn")' in inspect.getsource(afmoe)


def test_configuration_keeps_every_published_key(config, spec):
    entry = _named(spec["configs"], CONFIG)
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "num_dense_layers": 2}
    for key, value in PUBLISHED.items():
        assert key in config, key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_dense_layers"]) \
        == (5, 1)
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    assert config["family"] == "afmoe"
    # every point the reference can switch off is stated as assumed
    from benchmarks.reference import afmoe as ref
    assert ref.ASSUMED <= set(config["assumed"])
    assert {"expert_bias", "weights", "num_hidden_layers",
            "num_dense_layers", "kv_row_layout"} <= set(config["assumed"])
    assert "modeling_afmoe.py" in \
        config["assumed"]["source_of_the_points_below"]
    assert "pipeline of whole layers" in config["deployment"]
    dims = manifest.load_family(config).dims(config)
    assert (dims.n_layers, dims.n_dense_layers, dims.n_win_layers,
            dims.n_full_layers, dims.n_moe_layers) == (5, 1, 4, 1, 4)
    assert (dims.n_routed_experts, dims.experts_per_tok, dims.window,
            dims.vocab_size) == (128, 8, 2048, 200192)
    assert dims.num_params() == config["parameters"] == 4_241_534_720
    whole = manifest.load_family(config).dims(
        dict(config, **config["published"]))
    assert whole.num_params() == 26_123_974_400 \
        == config["parameters_published_32_layers"]
    b = config["bytes"]
    assert b["weights_bf16"] == 2 * dims.num_params()
    assert b["weights_bf16_published_32_layers"] == 2 * whole.num_params()
    assert b["attention_per_layer"] == 2 * 27_263_232
    assert b["dense_layer"] == 2 * dims.dense_layer_params() \
        == 2 * 65_020_160
    assert b["expert_layer"] == 2 * dims.expert_layer_params() \
        == 2 * 839_131_520
    assert b["embedding_head_and_final_norm"] == 2 * 819_988_480
    assert b["kv_per_token_per_layer"] == dims.kv_row_bytes == 2048
    assert b["kv_per_token_published_8_global_layers"] == 8 * 2048
    assert b["window_ring_per_slot_per_window_layer"] == 2048 * 2048
    flags = config["program"]["flags"]
    blocks = int(flags[flags.index("--kv-blocks") + 1])
    block = int(flags[flags.index("--kv-block") + 1])
    assert flags[:14] == ["--slots", "32", "--max-len", "33280",
                          "--max-burst", "32", "--open-burst", "4",
                          "--admit-wave", "4", "--spec-k", "0",
                          "--warm-grid", "--kv-block"]
    assert flags[-2:] == ["--prefix-pool", "0"]
    assert 33280 % block == 0 and blocks == 33 * 33280 // block
    assert b[f"kv_pool_{blocks}_blocks_x_{block}_rows"] \
        == blocks * block * 2048
    # what a deployment would hold: weights + rings + pool between 65 and
    # 90 % of a 16.9 GB chip before transients; kept whole in all five
    # layers the same rows would not fit beside the weights
    resident = b["weights_bf16"] + b["window_rings_33_slots_x_4_layers"] \
        + b[f"kv_pool_{blocks}_blocks_x_{block}_rows"]
    assert 0.65 < resident / 16.9e9 < 0.90
    assert b["weights_bf16"] \
        + b["cache_if_all_5_layers_kept_33_x_33280_rows"] > 16.9e9
    assert config["precision"]["weights"] == "bf16"
    assert "float32 router" in config["precision"]["stated"]
    assert config["rehearse"]["sliding_window"] == 32


def test_the_cell_is_the_issues(config):
    cell = manifest.load_workload(CELL)
    mix = manifest.load_traffic(cell)
    base = manifest.load_traffic(dict(cell, traffic_overrides={}))
    assert {k: v for k, v in mix.items() if k != "rate_rps"} == base
    assert mix["shape_seed"] == 20261002 and mix["lead_in_s"] == 10
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                    "sigma": 0.8, "min": 1024, "max": 32768}
    assert mix["output_tokens"]["median"] == 128
    assert mix["shared_prefix"]["share"] == 0
    knee = cell["knee"]
    assert knee["share"] == 0.8
    assert mix["rate_rps"] == int(knee["rate_rps"] * 0.8 * 10 + 1e-9) / 10
    assert cell["end_to_end"] == ["tpot_p90_ms", "setup_s"]
    assert set(cell["correct"]["limits"]) == {"served_logit_gap_max",
                                              "served_logit_gap_mean"}
    assert "control" in cell["correct"]["limits_from"]
    assert "window" in cell["correct"]["limits_from"]
    gen = manifest.load_module("traffic", mix["generator"])
    plan = gen.generate(mix, 2 ** 31 + 5, 40.0, 200192,
                        config["program"]["max_len"])
    lens = [len(r["prompt"]) for r in plan["requests"]]
    assert all(n + r["max_new"] <= 33280 and max(r["prompt"]) < 200192
               for n, r in zip(lens, plan["requests"]))
    # nearly every prompt passes the window; some reach four windows
    assert sum(n > 2048 for n in lens) >= 0.85 * len(lens)
    assert max(lens) > 4 * 2048
    # the rehearsal's prompts pass ITS window too
    tiny = merge(cell, cell["rehearse"])
    assert tiny["traffic_overrides"]["prompt_tokens"]["median"] \
        > config["rehearse"]["sliding_window"]


def _sha(a):
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(a)).tobytes()).hexdigest()[:16]


_PINNED = {
    "embed": "63493d900e63e1d9", "lm_head": "338db4b366f6855e",
    "lead.wq": "f5c13a8fedde693d", "lead.w_down": "3c0385a10ed8389c",
    "period.wg": "262ff0eb16c8f255", "period.q_norm": "8a6ca23089bebe2d",
    "period.router_bias": "600245d9ccfec914",
    "period.we_up": "bd0716494730a9a8",
    "wk's stream, layer 3, (2048, 4, 128)": "4dd6dad9018e6bc9",
    "we_gate's stream, layer 2, (2, 2048, 1024)": "2298201c2b61834b"}


def _pins(config):
    family = manifest.load_family(config)
    tiny = family.dims(merge(config, config["rehearse"]))
    seed = 2 ** 32 + 12345                      # more than 32 bits
    p = G.build_serving(seed, tiny)
    got = {"embed": _sha(p["embed"]), "lm_head": _sha(p["lm_head"])}
    for name in ("wq", "w_down"):
        got[f"lead.{name}"] = _sha(p["lead"][0][name])
    for name in ("wg", "q_norm", "router_bias", "we_up"):
        got[f"period.{name}"] = _sha(
            jnp.stack([g[name] for g in p["period"]]))
    key = jnp.asarray(weights.seed_key(seed))
    got["wk's stream, layer 3, (2048, 4, 128)"] = _sha(jax.jit(
        lambda k: G.matrix(k, "wk", np.uint32(3), (2048, 4, 128), 1))(key))
    got["we_gate's stream, layer 2, (2, 2048, 1024)"] = _sha(jax.jit(
        lambda k: G.matrix(k, "we_gate", np.uint32(2), (2, 2048, 1024),
                           1))(key))
    return got, p, tiny, key, seed


def test_seeded_weights_are_pinned(config):
    """At the rehearsal size the whole tree, and two tensors at the
    published widths' fan-in (a hash is of the element's index in ITS
    shape), as the serve child and the reference reach them."""
    got, p, tiny, key, seed = _pins(config)
    full = manifest.load_family(config).dims(config)
    assert G.attn_shapes(full)["wk"] == ((2048, 4, 128), 1)
    assert G.ffn_shapes(full, True)["we_gate"] == ((128, 2048, 1024), 1)
    assert got == _PINNED
    # another seed, other weights; a layer alone = its place in the stack
    q = G.build_serving(seed - 2 ** 32, tiny)
    assert (np.asarray(q["lead"][0]["wq"])
            != np.asarray(p["lead"][0]["wq"])).any()
    assert tiny.plan() == ((0,), 4, 1, ())
    one = G.layer_tensors(key, tiny, np.uint32(3), True)  # place 2
    assert (np.asarray(one["ws_down"])
            == np.asarray(p["period"][2]["ws_down"][0])).all()
    bias = np.asarray(jnp.stack([g["router_bias"] for g in p["period"]]),
                      np.float32)
    assert np.abs(bias).max() <= G.BIAS_RANGE and np.abs(bias).max() > 0.05


def test_work_counts(config):
    """``window_work``'s arithmetic, by hand, and ``moe_work``'s with
    this family's dims: a key row is 4 x 32 x 128 = 16 384 operations a
    query and 2048 B a read; 128 experts of 12.6 MB, top-8."""
    dims = manifest.load_family(config).dims(config)
    per_key = manifest.load_function("window_work.attn_flops_per_key")
    assert per_key(dims) == 4 * 32 * 128 == 16_384
    decode = manifest.load_function("window_work.decode_ring_read_work")
    work = decode(dims, 10 * 2048.0)         # ten slots past the window
    assert work["bytes"] == 4 * 10 * 2048 * 2048
    assert work["flops"] == 4 * 10 * 2048 * 16_384
    assert decode(dims, 0)["bytes"] == 0
    assert flops.least_seconds(work, "TPU v5 lite")["bound"] == "memory"
    prefill = manifest.load_function("window_work.prefill_window_attn_work")
    keys = 512 * 2048.0                      # a chunk far past the window
    work = prefill(dims, keys, 512.0)
    assert work["flops"] == 4 * keys * 16_384
    assert work["bytes"] == 4 * 512 * (2 * 32 * 128 * 2 + 2048)
    assert flops.least_seconds(work, "TPU v5 lite")["bound"] == "compute"
    # the engine's count for those tokens is the same sum
    from skypilot_tpu.infer import engine as eng
    assert eng.window_keys(8192, 512, 2048) == keys
    assert eng.window_keys(0, 512, 2048) == 512 * 513 // 2
    # the shared expert layer's counts at 128 / top-8
    touched = manifest.load_function("moe_work.expected_experts_touched")
    assert round(touched(dims, 2), 1) == 15.5
    assert round(touched(dims, 33), 1) == 112.8
    read = manifest.load_function("moe_work.decode_expert_read_work")
    work = read(dims, 33.0)
    assert dims.expert_params() == 6_291_456
    assert work["bytes"] == 4 * touched(dims, 33) * 6_291_456 * 2
    assert flops.least_seconds(work, "TPU v5 lite")["bound"] == "memory"


def test_readers_read_nothing_where_there_is_nothing_to_read(tmp_path,
                                                             config):
    """On a trace of a program without the scope and the annotations
    (the fixture: a Llama engine before this family) every new reading
    gives ``None`` and does not raise; so it does without a trace, and
    for a family whose dims have no window."""
    path = os.path.join(HERE, "data", "spans_fixture.xplane.pb")
    facts = {"trace": {"file": path}, "device": {"kind": "TPU v5 lite"}}
    ctx = {"out_dir": str(tmp_path), "config": config,
           "bench_dir": manifest.BENCH_DIR}
    for name in NEW_METRICS:
        m = manifest.load_metric(name)
        reader = manifest.load_module("readers", m["reader"])
        assert reader.read(facts, ctx, **m["args"]) is None, name
        assert reader.read({"trace": {}}, ctx, **m["args"]) is None, name
    m = manifest.load_metric("prefill_window_attn_roofline")
    reader = manifest.load_module("readers", m["reader"])
    # a scope the fixture HAS, but no ``window_keys`` on its annotations
    args = dict(m["args"], scope="attn_core", modules=["_decode_burst"])
    assert reader.read(facts, ctx, **args) is None
    other = dict(ctx, config=manifest.load_config("olmo-hybrid-7b-bf16"))
    assert reader.read(facts, other, **args) is None


def test_the_reader_divides_required_work_by_the_scopes_seconds(
        tmp_path, config, monkeypatch):
    """The reader's arithmetic on a hand-made reduction: decode — two
    bursts of k = 4 and 8 steps with 3000 and 6000 ring rows, 6 ms under
    the scope; prefill — 1.5 M key rows over 1024 tokens, 10 ms."""
    reader = manifest.load_module("readers", "window_attn_roofline")
    dims = manifest.load_family(config).dims(config)
    scoped = {"platform": "tpu",
              "modules": {"jit__prefill_chunk": {
                  "s": 0.05, "scopes": {"window_attn": 0.010}}},
              "decode": {"scopes_s": {"window_attn": 0.006}, "steps": 12,
                         "device_s": 0.05, "live_row_steps": 40}}
    notes = [("engine.decode.dispatch", 0.0, 0.001, 0,
              {"seq": 1, "k": 4, "window_rows": 3000}),
             ("engine.decode.dispatch", 0.1, 0.001, 0,
              {"seq": 2, "k": 8, "window_rows": 6000}),
             ("engine.decode.dispatch", 0.2, 0.001, 0,
              {"seq": 3, "k": 8, "window_rows": 9999}),   # not counted
             ("engine.chunk.dispatch", 0.3, 0.001, 0,
              {"chunk_tokens": 512, "window_keys": 1_000_000}),
             ("engine.wave.dispatch", 0.4, 0.001, 0,
              {"prompt_tokens": 512, "window_keys": 500_000})]
    red = {"platform": "tpu", "annotations": notes, "modules": {},
           "decode": {"seqs": [1, 2]}}
    monkeypatch.setattr(reader.scoped_ops, "_load", lambda *a, **k: scoped)
    monkeypatch.setattr(reader.spans, "load", lambda *a, **k: red)
    monkeypatch.setattr(reader.scoped_ops, "_device_kind",
                        lambda *a: "TPU v5 lite")
    ctx = {"out_dir": str(tmp_path), "config": config,
           "bench_dir": manifest.BENCH_DIR}
    m = manifest.load_metric("decode_window_read_roofline")
    got = reader.read({}, ctx, **m["args"])
    rows = (4 * 3000 + 8 * 6000) / 12
    want = 100 * (4 * rows * 2048 / 819e9) / (0.006 / 12)
    assert got == pytest.approx(want, rel=1e-9)
    m = manifest.load_metric("prefill_window_attn_roofline")
    got = reader.read({}, ctx, **m["args"])
    want = 100 * (4 * 1_500_000 * 16_384 / 197e12) / 0.010
    assert got == pytest.approx(want, rel=1e-9) and got < 100
    assert dims.n_win_layers == 4


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("traced_trinity")
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
    # both prefill paths ran, and every program says what its window
    # layers had to read or score
    results = json.load(open(out / "results.json"))
    red = spans.reduce_xplane(results["facts"]["trace"]["file"])
    waves = spans.annotations_named(red, "engine.wave.dispatch")
    chunks = spans.annotations_named(red, "engine.chunk.dispatch")
    bursts = spans.annotations_named(red, "engine.decode.dispatch")
    assert waves and chunks and bursts
    assert all(a[4]["window_keys"] > 0 for a in waves + chunks)
    # a chunk past the window scores exactly W keys a token
    assert any(a[4]["window_keys"] == 32 * a[4]["chunk_tokens"]
               for a in chunks)
    assert all(0 < a[4]["window_rows"] <= 32 * a[4]["slots"]
               and a[4]["state_rows"] == a[4]["slots"]
               and a[4]["kv_blocks"] > 0 for a in bursts)


def test_untraced_rehearsal_computes_both_controls(tmp_path):
    """Both controls are computed, in the precisions the family names;
    the window control (window layers that see every row) is refused by
    BOTH limits of the rehearsal's comparison. The int8 control is
    refused at the chip's sizes, by the cell's own mean limit (its
    ``limits_from`` gives the readings): at width 64 one router near-tie
    flips at bf16 as readily as at int8, and the rehearsal's limits,
    which every seed's sound run must pass, cannot tell the two apart."""
    rc, lines, err = bench(["--workload", CELL, "--seed", str(2 ** 32 + 3),
                            "--seconds", "8", "--trace", "0", "--rehearse",
                            "--control", "--out", str(tmp_path / "out")])
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["correct"] is True and obj["failed"] == 0
    assert set(obj["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in obj["metrics"].values())
    (ref,) = [json.loads(l[10:]) for l in lines
              if l.startswith("REFERENCE ")]
    assert "act_bits=8" in ref["control_precision"]
    assert "window_all=True" in ref["control_window_precision"]
    assert "window_all=False" in ref["precision"]
    cell = manifest.load_workload(CELL)
    limits = cell["rehearse"]["correct"]["limits"]
    assert ref["served_gap_mean"] < limits["served_logit_gap_mean"] \
        < ref["control_window_gap_mean"]
    assert ref["served_gap_max"] < limits["served_logit_gap_max"] \
        < ref["control_window_gap_max"]
    assert ref["control_window_not_argmax"] > ref["positions"] // 2
    assert ref["control_gap_mean"] > 0
    # the cell's own limits are tighter than the rehearsal's, and the
    # mean one is what refuses the int8 control there
    real = cell["correct"]["limits"]
    assert real["served_logit_gap_mean"] < limits["served_logit_gap_mean"]
    assert "int8" in cell["correct"]["limits_from"]
