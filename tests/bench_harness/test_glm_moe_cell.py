"""The ``glm_moe`` family's configuration, cell, traffic mix, metrics,
reader, work counts and seeded weights: they validate through the
manifest as it is, the cell rehearses end to end on the CPU with
``correct`` true and no device metric, and the weights are pinned by
hash."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, spans, weights
from benchmarks import weights_glm_moe as G
from benchmarks.run import merge
from test_run_serve import bench, last_line

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "glm-4.7-flash-bf16"
CELL = "glm-4.7-flash-bf16.longprompt-steady"

# The catalog row's ``config`` (model-configs guide,
# architectures.jsonl, "GLM-4.7-Flash"), every key.
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "vocab_size": 154880}
SERVE_METRICS = {
    "prefill_device_ms_per_ktok", "decode_device_ms_per_ktok",
    "ttft_queue_share", "prefill_useful_token_share",
    "decode_device_ms_per_step", "decode_useful_token_share",
    "decode_kv_gather_share"}
NEW_METRICS = {"decode_attn_core_share", "decode_expert_ffn_share",
               "prefill_expert_ffn_share", "decode_expert_read_roofline"}


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
    e2e = {m["name"] for m in manifest.cell_metrics(spec, CELL,
                                                    "end_to_end")}
    assert e2e == {"ttft_p95_ms", "tpot_p90_ms", "setup_s"}
    layers = {m["name"] for m in manifest.cell_metrics(spec, CELL,
                                                       "per_layer")}
    assert layers == SERVE_METRICS | NEW_METRICS
    chat = {m["name"] for m in manifest.cell_metrics(
        spec, "mistral-7b-w8a8.chat-steady", "per_layer")}
    # since PR 37 the chat cell reports no first token end to end: its
    # first-token readings carry its own names (PERF.md section 2)
    first_token = {"prefill_device_ms_per_ktok", "ttft_queue_share",
                   "prefill_useful_token_share"}
    assert chat == (SERVE_METRICS - first_token) | {
        "decode_attn_core_share", "ttft_p95_ms.chat"} | {
        n + ".chat" for n in first_token}
    # new entries stand last in their lists (PR 37's four after them)
    assert spec["configs"][-1]["name"] == CONFIG
    assert spec["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in spec["per_layer"][-8:-4]} == NEW_METRICS


def test_configuration_keeps_every_published_key(config, spec):
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert config["published"] == {"num_hidden_layers": 47,
                                   "num_nextn_predict_layers": 1}
    for key, value in PUBLISHED.items():
        assert key in config, key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 7
    assert config["num_nextn_predict_layers"] == 0
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    assert {"rotary_pairing", "e_score_correction_bias", "weights",
            "num_nextn_predict_layers"} <= set(config["assumed"])
    dims = manifest.load_family(config).dims(config)
    assert (dims.n_layers, dims.first_k_dense, dims.n_routed_experts,
            dims.experts_per_tok, dims.vocab_size) == (7, 1, 64, 4, 154880)
    assert dims.num_params() == config["parameters"] == 4_530_936_960
    assert config["bytes"]["weights_bf16"] == 2 * dims.num_params()
    assert config["bytes"]["latent_cache_per_token"] \
        == 7 * (dims.kv_lora_rank + dims.qk_rope) * 2 == 8064
    flags = config["program"]["flags"]
    assert flags == ["--slots", "32", "--max-len", "8704", "--max-burst",
                     "32", "--open-burst", "4", "--admit-wave", "4",
                     "--spec-k", "0", "--warm-grid"]


def test_traffic_is_the_issues(config):
    cell = manifest.load_workload(CELL)
    mix = manifest.load_traffic(cell)
    assert mix["generator"] == "requests"
    assert mix["arrivals"] == {"process": "poisson"}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.9, "min": 256, "max": 8192}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.7, "min": 16, "max": 512}
    assert mix["shared_prefix"]["share"] == 0 and mix["lead_in_s"] == 10
    assert "shape_seed" in mix and mix["rate_rps"] > 0
    gen = manifest.load_module("traffic", mix["generator"])
    plan = gen.generate(mix, 2 ** 31 + 5, 40.0, 154880,
                        config["program"]["max_len"])
    lens = np.array([len(r["prompt"]) for r in plan["requests"]])
    assert lens.min() >= 256 and lens.max() <= 8192
    assert all(len(r["prompt"]) + r["max_new"] <= 8704
               for r in plan["requests"])
    # the same schedule for another seed, other token ids
    again = gen.generate(mix, 7, 40.0, 154880, 8704)
    assert [len(r["prompt"]) for r in again["requests"]] == list(lens)
    assert again["requests"][0]["prompt"] != plan["requests"][0]["prompt"]
    assert set(cell["correct"]["limits"]) == {"served_logit_gap_max",
                                              "served_logit_gap_mean"}


def _sha(a):
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(a)).tobytes()).hexdigest()[:16]


_PINNED = {
    "embed": "3fcf4db96fc04ddc", "lm_head": "1c70096f49e44818",
    "dense.ln1": "f2d1fbccce4f8a9e", "dense.w_down": "ad32f2e8eb91b192",
    "moe.wkv_b": "fe81df1aca10deb2", "moe.router_bias": "60bb32f2c41fa102",
    "moe.we_gate": "d5eabc76c29b73c7", "moe.ws_down": "9209b0e88a6ff122",
    "full.we_up[3][:8 experts]": "a0feab426af2a84b",
    "full.wkv_b[0]": "dedb170d45e02933"}


def test_seeded_weights_are_pinned(config):
    """At the rehearsal size the whole tree, and two full-size tensors,
    as the serve child and the reference reach them."""
    family = manifest.load_family(config)
    tiny = family.dims(merge(config, config["rehearse"]))
    seed = 2 ** 32 + 12345                      # more than 32 bits
    p = G.build_serving(seed, tiny)
    got = {"embed": _sha(p["embed"]), "lm_head": _sha(p["lm_head"]),
           "dense.ln1": _sha(p["dense"]["ln1"]),
           "dense.w_down": _sha(p["dense"]["w_down"]),
           "moe.wkv_b": _sha(p["moe"]["wkv_b"]),
           "moe.router_bias": _sha(p["moe"]["router_bias"]),
           "moe.we_gate": _sha(p["moe"]["we_gate"]),
           "moe.ws_down": _sha(p["moe"]["ws_down"])}
    key = jnp.asarray(weights.seed_key(seed))
    full = family.dims(config)
    got["full.we_up[3][:8 experts]"] = _sha(jax.jit(lambda k: G.matrix(
        k, "we_up", np.uint32(3), (8, 2048, 1536), 1))(key))
    got["full.wkv_b[0]"] = _sha(jax.jit(lambda k: G.matrix(
        k, "wkv_b", np.uint32(0), *G.attn_shapes(full)["wkv_b"]))(key))
    assert got == _PINNED
    # another seed, other weights; a layer alone = its slice of the stack
    q = G.build_serving(seed - 2 ** 32, tiny)
    assert (np.asarray(q["moe"]["we_gate"])
            != np.asarray(p["moe"]["we_gate"])).any()
    one = G.layer_tensors(key, tiny, np.uint32(2), True)
    assert (np.asarray(one["we_down"])
            == np.asarray(p["moe"]["we_down"][1])).all()
    bias = np.asarray(p["moe"]["router_bias"], np.float32)
    assert 0.03 < np.abs(bias).max() <= G.BIAS_RANGE


def test_work_counts(config):
    dims = manifest.load_family(config).dims(config)
    fn = manifest.load_function("moe_work.decode_expert_read_work")
    touched = manifest.load_function("moe_work.expected_experts_touched")
    assert touched(dims, 0) == 0
    assert touched(dims, 32) / 64 == pytest.approx(0.873, abs=0.002)
    assert touched(dims, 10_000) == pytest.approx(64)
    work = fn(dims, 33.0)
    per_expert = 3 * 2048 * 1536
    assert work["bytes"] == pytest.approx(
        6 * touched(dims, 33) * per_expert * 2)
    assert 1.0e9 < work["bytes"] / 6 < 1.21e9     # a layer: under all 64
    assert work["flops"] == 6 * 33 * 4 * 2 * per_expert


def test_scoped_reader_groups_as_the_cached_reduction_does(tmp_path):
    """With ``spans.SCOPES`` as its list the new reader's grouping is
    the cached reduction's; with a scope the fixture lacks it reads
    nothing; its decode sums are ``pair_decode``'s."""
    reader = manifest.load_module("readers", "scoped_ops")
    path = os.path.join(HERE, "data", "spans_fixture.xplane.pb")
    want = spans.reduce_xplane(path)
    facts = {"trace": {"file": path}, "device": {"kind": "TPU v5 lite"}}
    ctx = {"out_dir": str(tmp_path)}
    red = reader._load(facts, ctx, list(spans.SCOPES))
    assert red is not None
    assert {n: g["scopes"] for n, g in red["modules"].items()} \
        == {n: g["scopes"] for n, g in want["modules"].items()}
    assert red["decode"]["steps"] == want["decode"]["steps"] > 0
    assert red["decode"]["device_s"] == pytest.approx(
        want["decode"]["device_s"])
    assert red["decode"]["live_row_steps"] \
        == want["decode"]["live_row_steps"]
    scopes = list(spans.SCOPES) + ["router", "moe_experts", "shared_expert"]
    share = reader.read(facts, ctx, ["_decode"], "kv_gather", scopes)
    groups = spans.module_groups(want, ["_decode"])
    assert share == pytest.approx(
        100 * sum(g["scopes"].get("kv_gather", 0) for g in groups)
        / sum(g["s"] for g in groups))
    assert reader.read(facts, ctx, ["_decode"], "moe_experts", scopes) \
        is None
    assert reader.read(facts, ctx, ["_decode"], "moe_experts", scopes,
                       work="moe_work.decode_expert_read_work") is None
    assert reader.read({"trace": {}}, ctx, ["_decode"], "kv_gather",
                       scopes) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("traced_glm")
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
    assert "busy_s" not in obj["device"]
    (reh,) = [l for l in lines if l.startswith("REHEARSAL_TRACE ")]
    for program in ("jit__decode_burst", "jit__prefill_chunk",
                    "jit__admit_wave"):
        assert program in reh
    checks = {c["name"]: c for c in
              (json.loads(l[6:]) for l in lines if l.startswith("CHECK "))}
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["served_logit_gap_max"]["ok"] \
        and checks["served_logit_gap_mean"]["ok"]
    # both prefill paths ran: prompts of 8-200 tokens either side of the
    # rehearsal's 32-token chunk
    results = json.load(open(out / "results.json"))
    red = spans.reduce_xplane(results["facts"]["trace"]["file"])
    assert red["phases"]["engine.chunk.dispatch"]["n"] > 0
    assert red["phases"]["engine.wave.dispatch"]["n"] > 0


def test_untraced_rehearsal_reports_the_end_to_end_metrics(tmp_path):
    rc, lines, err = bench(["--workload", CELL, "--seed", str(2 ** 32 + 3),
                            "--seconds", "5", "--trace", "0", "--rehearse",
                            "--out", str(tmp_path / "out")])
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["correct"] is True and obj["failed"] == 0
    assert set(obj["metrics"]) == {"ttft_p95_ms", "tpot_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in obj["metrics"].values())
