"""``BENCHMARK.json`` against the contract's limits, and the promise
that a later PR adds a cell, a configuration, a traffic mix, a generator
or a per-layer metric with new files only."""

import copy
import json
import os
import shutil

import pytest

from benchmarks import manifest, run as bench_run


@pytest.fixture(scope="module")
def spec():
    return manifest.load_manifest()


def test_the_committed_manifest_is_valid(spec):
    manifest.validate(spec)
    assert spec["command"][0] == "python3"
    assert all(w["chips"] == 1 for w in spec["workloads"])
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


def test_every_cell_reports_setup_one_more_and_a_layer(spec):
    for w in spec["workloads"]:
        e2e = [m["name"] for m in
               manifest.cell_metrics(spec, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.cell_metrics(spec, w["name"], "per_layer")
        cell = manifest.load_workload(w["name"])
        assert sorted(cell["end_to_end"]) == sorted(e2e)


def test_no_width_differs_from_the_source(spec):
    published = {
        "mistral-7b-w8a8": (4096, 14336, 32, 32, 8, 128, 32768),
        "mistral-7b-qlora": (4096, 14336, 32, 32, 8, 128, 32768),
        "internlm2-1.8b-bf16": (2048, 8192, 24, 16, 8, 128, 92544)}
    # speculation off is the one departure from the recipe, and is said
    reduced = {"mistral-7b-w8a8": ["spec_k"], "mistral-7b-qlora": []}
    for c in spec["configs"]:
        cfg = manifest.load_config(c["name"])
        assert c["reduced"] == cfg["reduced"] == reduced[c["name"]]
        assert all(k in cfg and k in cfg["assumed"] for k in c["reduced"])
        assert not any(manifest.is_width_key(k) for k in c["reduced"])
    for name in published:
        d = manifest.model_dims(manifest.load_config(name))
        assert (d.d_model, d.d_ff, d.n_layers, d.n_heads, d.n_kv_heads,
                d.head_dim, d.vocab_size) == published[name]
        assert d.rope_theta == 1e6 and d.norm_eps == 1e-5
        assert not d.tie_embeddings


def _broken(spec, edit):
    bad = copy.deepcopy(spec)
    edit(bad)
    return bad


@pytest.mark.parametrize("edit", [
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["end_to_end"][0].update(unit="µs"),
    lambda m: m["end_to_end"][0].update(name="ttft p95"),
    lambda m: m["end_to_end"][0].update(bound=0.5),
    lambda m: m["end_to_end"][0].update(why="because"),
    lambda m: m["end_to_end"][0].update(source="program_span"),
    lambda m: m["per_layer"][0].update(moves="nothing"),
    lambda m: m["per_layer"][0].update(workloads=["no.such.cell"]),
    lambda m: m["per_layer"][0].update(
        workloads=["mistral-7b-qlora.sft-2k"]),      # does not report tpot
    lambda m: m["workloads"][0].update(chips=2),
    lambda m: m["workloads"][0].update(why="x" * 201),
    lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")),
    lambda m: m["configs"][0].update(reduced=["hidden_size"]),
    lambda m: m["configs"][0].update(reduced=["kv_lora_rank"]),
    lambda m: m["configs"][0].update(file="README.md"),
    lambda m: m.update(run_seconds=60),
    lambda m: m.update(command=["python3", "/root/x.py"]),
    lambda m: m.update(extra=1),
    lambda m: m["end_to_end"].pop(),                  # no setup_s
], ids=lambda f: "")
def test_a_breach_of_the_contract_is_refused(spec, edit):
    with pytest.raises(manifest.ManifestError):
        manifest.validate(_broken(spec, edit))


def test_adding_needs_new_files_only(tmp_path, spec):
    """A cell, a configuration, a traffic mix with its own generator and
    a per-layer metric with its own reader, in a directory of their own:
    the harness finds each by name and edits nothing."""
    bench = tmp_path / "benchmarks"
    for kind in ("workloads", "configs", "traffic", "metrics", "readers",
                 "runners"):
        (bench / kind).mkdir(parents=True)
    cfg = manifest.load_config("internlm2-1.8b-bf16")
    cfg["name"] = "newmodel-1b"
    cfg["num_hidden_layers"] = 12
    (bench / "configs" / "newmodel-1b.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "bursty.json").write_text(json.dumps(
        {"generator": "replay", "loop": "open", "lengths": [3, 5, 7]}))
    (bench / "traffic" / "replay.py").write_text(
        "def generate(mix, seed, seconds, vocab_size, max_len=0):\n"
        "    return {'loop': 'open', 'requests': [\n"
        "        {'prompt': [1] * n, 'max_new': 2, 'due_s': float(i),\n"
        "         'phase': 'window'} for i, n in enumerate(mix['lengths'])]}\n")
    (bench / "workloads" / "newmodel-1b.bursty.json").write_text(json.dumps(
        {"config": "newmodel-1b", "traffic": "bursty", "runner": "fake",
         "chips": 1, "traffic_overrides": {"lengths": [2, 4]},
         "end_to_end": ["tpot_p90_ms", "setup_s"], "why": "a test"}))
    (bench / "runners" / "fake.py").write_text(
        "def run(ctx):\n    return {'device': {'platform': 'tpu'}}\n")
    (bench / "metrics" / "queue_wait_ms.json").write_text(json.dumps(
        {"layer": "Engine scheduler (infer/engine.py)", "unit": "ms",
         "moves": "tpot_p90_ms", "reader": "constant",
         "args": {"value": 4.5}}))
    (bench / "metrics" / "absent_ms.json").write_text(json.dumps(
        {"layer": "Engine scheduler (infer/engine.py)", "unit": "ms",
         "moves": "tpot_p90_ms", "reader": "constant",
         "args": {"value": None}}))
    (bench / "readers" / "constant.py").write_text(
        "def read(facts, ctx, value):\n    return value\n")
    bdir = str(bench)

    cell = manifest.load_workload("newmodel-1b.bursty", bdir)
    assert cell["name"] == "newmodel-1b.bursty"
    assert manifest.model_dims(
        manifest.load_config(cell["config"], bdir)).n_layers == 12
    mix = manifest.load_traffic(cell, bdir)
    assert mix["lengths"] == [2, 4]                  # the cell's override
    gen = manifest.load_module("traffic", mix["generator"], bdir)
    assert [len(r["prompt"]) for r in
            gen.generate(mix, 1, 10, 100)["requests"]] == [2, 4]
    assert manifest.load_module("runners", "fake", bdir).run({})

    grown = copy.deepcopy(spec)
    shutil.copy(bench / "configs" / "newmodel-1b.json",
                tmp_path / "newmodel-1b.json")
    grown["configs"].append(
        {"name": "newmodel-1b", "source": "https://example.org/new",
         "file": "benchmarks/configs/newmodel-1b.json",
         "reduced": ["num_hidden_layers"], "why": "a test"})
    grown["workloads"].append(
        {"name": "newmodel-1b.bursty", "config": "newmodel-1b",
         "traffic": "bursty", "chips": 1, "why": "a test"})
    next(m for m in grown["end_to_end"] if m["name"] == "tpot_p90_ms")[
        "workloads"].append("newmodel-1b.bursty")
    entries = [{"name": n, "unit": "ms", "better": "lower",
                "source": "program_span",
                "layer": "Engine scheduler (infer/engine.py)",
                "moves": "tpot_p90_ms",
                "workloads": ["newmodel-1b.bursty"]}
               for n in ("queue_wait_ms", "absent_ms")]
    grown["per_layer"].extend(entries)
    assert [m["name"] for m in manifest.cell_metrics(
        grown, "newmodel-1b.bursty", "per_layer")] == [
            "queue_wait_ms", "absent_ms"]
    # a reader that finds nothing returns nothing and is left out
    got = bench_run.read_metrics({}, {"facts": {}}, entries, bdir)
    assert got == {"queue_wait_ms": {"value": 4.5, "unit": "ms"}}


def test_a_function_that_counts_work_is_found_by_name(tmp_path):
    """A metric's file names its FLOP or byte count as
    ``<module>.<function>``; a later PR's count is a new file beside
    ``flops.py`` and no reader changes."""
    fn = manifest.load_function("flops.flash_attention_step_work")
    dims = manifest.model_dims(manifest.load_config("mistral-7b-qlora"))
    assert fn(dims, 2, 2048)["flops"] > 0
    for name in ("train_mfu", "flash_attn_roofline"):
        args = manifest.load_metric(name)["args"]
        ref = args.get("flops_function") or args["work"]
        assert callable(manifest.load_function(ref))
    (tmp_path / "flops_moe.py").write_text(
        "def routed_flops_per_token(dims, seq, program):\n"
        "    return 2.0 * dims.d_model * program['experts_per_token']\n")
    got = manifest.load_function("flops_moe.routed_flops_per_token",
                                 str(tmp_path))
    assert got(dims, 2048, {"experts_per_token": 2}) == 4.0 * 4096
    for bad in ("flops.no_such_function", "no_such_file.f",
                "../flops.least_seconds", "readers.train_mfu.read"):
        with pytest.raises(manifest.ManifestError):
            manifest.load_function(bad)


def test_a_name_cannot_leave_the_directory():
    with pytest.raises(manifest.ManifestError):
        manifest.load_workload("../BENCHMARK")
    with pytest.raises(manifest.ManifestError):
        manifest.load_module("readers", "no_such_reader")
