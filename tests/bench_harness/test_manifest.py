"""``BENCHMARK.json`` against the contract's limits, and the promise
that a later PR adds a cell, a configuration of ANOTHER model family
(with its reference, weights and work count), a traffic mix, a generator
or a per-layer metric with new files only. No test here names an entry
of today's manifest: each reads the files."""

import copy
import hashlib
import json
import os
import shutil
import textwrap

import pytest

from benchmarks import manifest, run as bench_run
from test_run_serve import ROOT, bench, last_line


@pytest.fixture(scope="module")
def spec():
    return manifest.load_manifest()


def test_the_committed_manifest_is_valid(spec):
    manifest.validate(spec)
    assert spec["command"][0] == "python3"
    cells = spec["workloads"]
    assert all(w["chips"] in (1, 4) for w in cells)
    # four chips cost four times: a quarter of the cells at most, and
    # one always may
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
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
        assert cell["chips"] == w["chips"]


def test_no_width_differs_from_the_source(spec):
    """Every configuration against its OWN file: what it says it reduced,
    assumed and published, and the widths the file states."""
    assert spec["configs"]
    for c in spec["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert c["reduced"] == cfg["reduced"]
        published = cfg.get("published", {})
        for key in c["reduced"]:
            assert key in cfg and key in cfg["assumed"] and key in published
            assert not manifest.is_width_key(key)
        manifest.check_reduced(c, cfg)
        widths = {k: v for k, v in cfg.items()
                  if manifest.is_width_key(k) and isinstance(v, (int, float))
                  and not isinstance(v, bool)}
        assert widths, c["name"]
        # a width that the file also gives as published is that value
        assert all(published.get(k, v) == v for k, v in widths.items())
        # and the family's arithmetic (reference, work counts, the
        # program's config) takes every one of them as the file states it
        dims = manifest.load_family(cfg).dims(cfg)
        assert set(widths.values()) <= set(vars(dims).values()), c["name"]
        assert dims.vocab_size == cfg["vocab_size"]


def _broken(spec, edit):
    bad = copy.deepcopy(spec)
    edit(bad)
    return bad


def _not_reporting(m):
    """Point the first per-layer metric at a cell that does not report
    the end-to-end metric it moves."""
    entry = m["per_layer"][0]
    reports = next(e for e in m["end_to_end"]
                   if e["name"] == entry["moves"])["workloads"]
    other = next(w["name"] for w in m["workloads"]
                 if w["name"] not in reports)
    entry.update(workloads=[other])


def _all_on_four_chips(m):
    for w in m["workloads"]:
        w.update(chips=4)


@pytest.mark.parametrize("edit", [
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["end_to_end"][0].update(unit="µs"),
    lambda m: m["end_to_end"][0].update(name="ttft p95"),
    lambda m: m["end_to_end"][0].update(bound=0.5),
    lambda m: m["end_to_end"][0].update(why="because"),
    lambda m: m["end_to_end"][0].update(source="program_span"),
    lambda m: m["per_layer"][0].update(moves="nothing"),
    lambda m: m["per_layer"][0].update(workloads=["no.such.cell"]),
    _not_reporting,
    _all_on_four_chips,
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


@pytest.mark.parametrize("key,held", [
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("head_dim", True),
    ("qk_rope_head_dim", True), ("v_head_dim", True),
    ("kv_lora_rank", True), ("q_lora_rank", True),
    ("num_experts_per_tok", True), ("sliding_window", True),
    ("ssm_state_size", True), ("expansion_factor", True),
    ("num_hidden_layers", False), ("n_routed_experts", False),
    ("num_local_experts", False), ("vocab_size", False),
    ("num_nextn_predict_layers", False), ("spec_k", False)])
def test_a_width_is_held_and_depth_or_a_share_is_let_go(key, held):
    assert manifest.is_width_key(key) is held


# ---------------------------------------------------------------------------
# A later PR, in a directory of its own: a copy of ``benchmarks/`` that
# gains files and loses or changes none
# ---------------------------------------------------------------------------

FAMILY = "latent_moe"
CONFIG = "toy-latent-moe"
CELL = "toy-latent-moe.sft-tiny"

# The drawn architecture's key names at toy size: 20 heads that do not
# divide the hidden size, latent attention ranks, routed experts after a
# leading dense layer. Depth, experts held and vocabulary are cut.
TOY_CONFIG = {
    "family": FAMILY, "source": "https://example.org/toy/config.json",
    "hidden_size": 64, "num_attention_heads": 20, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 6, "qk_rope_head_dim": 2,
    "v_head_dim": 8, "intermediate_size": 320, "moe_intermediate_size": 48,
    "n_routed_experts": 8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 5, "vocab_size": 512,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "published": {"num_hidden_layers": 47, "n_routed_experts": 64,
                  "vocab_size": 4096, "hidden_size": 64},
    "assumed": {"num_hidden_layers": "one dense layer and four of 46",
                "n_routed_experts": "an eighth of 64: eight chips a layer",
                "vocab_size": "an eighth of the rows"},
    "precision": {"weights": "f32", "activations": "f32"},
    "program": {"batch": 4, "learning_rate": 0.05},
}

NEW_FILES = {
    f"families/{FAMILY}.py": '''
        """A toy family under the drawn architecture's key names: a latent
        projection (hidden -> kv_lora_rank) and a head, trained by SGD."""
        import dataclasses
        from benchmarks import manifest


        @dataclasses.dataclass(frozen=True)
        class Dims:
            vocab_size: int
            hidden: int
            heads: int
            kv_rank: int
            rope_dim: int
            experts: int
            experts_per_tok: int
            layers: int
            dense_layers: int
            widths: tuple


        def dims(config):
            c = config
            return Dims(c["vocab_size"], c["hidden_size"],
                        c["num_attention_heads"], c["kv_lora_rank"],
                        c["qk_rope_head_dim"], c["n_routed_experts"],
                        c["num_experts_per_tok"], c["num_hidden_layers"],
                        c["first_k_dense_replace"],
                        tuple(v for k, v in c.items()
                              if manifest.is_width_key(k)))


        def precisions(config):
            return {"stated": "float32", "control": "bfloat16"}


        def _weights(config, seed):
            return manifest.load_function(
                "weights_latent.seeded", manifest.BENCH_DIR)(dims(config), seed)


        class Program:
            def __init__(self, config, seed):
                import jax
                import jax.numpy as jnp
                w = {k: jnp.asarray(v) for k, v in _weights(config, seed).items()}
                self.embed = w.pop("embed")
                self.start = dict(w)
                self.state = {"params": w, "grad": None}
                self.batch = int(config["program"]["batch"])
                lr = float(config["program"]["learning_rate"])

                def loss_of(p, tokens):
                    logits = self.embed[tokens] @ p["w_kva"] @ p["w_out"]
                    ll = jnp.take_along_axis(
                        jax.nn.log_softmax(logits[:, :-1], -1),
                        tokens[:, 1:, None], -1)
                    return -jnp.mean(ll)

                @jax.jit
                def step(state, tokens):
                    loss, g = jax.value_and_grad(loss_of)(state["params"], tokens)
                    new = {k: v - lr * g[k] for k, v in state["params"].items()}
                    return {"params": new, "grad": g}, {"loss": loss}

                self.step = step

            @staticmethod
            def put(rows):
                import jax.numpy as jnp
                return jnp.asarray(rows)

            def first_grad(self, state):
                return {k: float((v ** 2).sum() ** 0.5)
                        for k, v in state["grad"].items()}

            def change(self, state):
                return {k: float(((v - self.start[k]) ** 2).sum() ** 0.5)
                        for k, v in state["params"].items()}

            def free(self):
                self.state = self.step = None


        def train_program(config, cell, seed, rehearse, say):
            say("FAMILY", {"family": "latent_moe"})
            return Program(config, seed)


        def train_reference(config, cell, seed, rows_list, precision):
            follow = manifest.load_module("reference", "latent_moe_plain",
                                          manifest.BENCH_DIR).follow
            return follow(_weights(config, seed), rows_list,
                          float(config["program"]["learning_rate"]), precision)
        ''',
    "reference/latent_moe_plain.py": '''
        """The toy family's plain reference: numpy, float64, by hand."""
        import numpy as np


        def follow(w, rows_list, lr, precision):
            embed = w["embed"].astype(np.float64)
            p = {k: w[k].astype(np.float64) for k in ("w_kva", "w_out")}
            start = {k: v.copy() for k, v in p.items()}
            out = {"losses": [], "first_grad": None}
            for i, rows in enumerate(rows_list):
                x = embed[rows[:, :-1]]
                if precision == "bfloat16":        # the control: 8 bits
                    x = np.round(x * 128) / 128
                h = x @ p["w_kva"]
                logits = h @ p["w_out"]
                logits -= logits.max(-1, keepdims=True)
                prob = np.exp(logits)
                prob /= prob.sum(-1, keepdims=True)
                tgt = rows[:, 1:]
                n = tgt.size
                picked = np.take_along_axis(prob, tgt[..., None], -1)
                out["losses"].append(float(-np.log(picked).mean()))
                d = prob.copy()
                np.put_along_axis(d, tgt[..., None], picked - 1.0, -1)
                d /= n
                g = {"w_out": np.einsum("bsr,bsv->rv", h, d),
                     "w_kva": np.einsum("bsd,bsr->dr", x, d @ p["w_out"].T)}
                if i == 0:
                    out["first_grad"] = {k: float(np.sqrt((v ** 2).sum()))
                                         for k, v in g.items()}
                p = {k: p[k] - lr * g[k] for k in p}
            out["change"] = {k: float(np.sqrt(((p[k] - start[k]) ** 2).sum()))
                             for k in p}
            return out
        ''',
    "weights_latent.py": '''
        """The toy family's seeded weights (numpy, from the seed alone)."""
        import numpy as np


        def seeded(dims, seed):
            rng = np.random.default_rng([int(seed), 0x1A7E])
            return {"embed": rng.normal(0, 1.0, (dims.vocab_size, dims.hidden)
                                        ).astype(np.float32),
                    "w_kva": rng.normal(0, dims.hidden ** -0.5,
                                        (dims.hidden, dims.kv_rank + dims.rope_dim)
                                        ).astype(np.float32),
                    "w_out": rng.normal(0, 0.25,
                                        (dims.kv_rank + dims.rope_dim,
                                         dims.vocab_size)).astype(np.float32)}
        ''',
    "flops_latent.py": '''
        """A work count of the toy family's own, from ITS dims."""


        def latent_cache_bytes_per_token(dims, seq, program):
            return 2.0 * (dims.kv_rank + dims.rope_dim) * (
                dims.layers - dims.dense_layers + dims.dense_layers)
        ''',
    "readers/work_count.py": '''
        """A metric that is a work count of the family's own dims."""
        from benchmarks import manifest


        def read(facts, ctx, work):
            config = ctx["config"]
            dims = manifest.load_family(config, ctx["bench_dir"]).dims(config)
            return manifest.load_function(work, ctx["bench_dir"])(
                dims, int(ctx["mix"]["seq"]), config["program"])
        ''',
    "readers/constant.py": '''
        def read(facts, ctx, value):
            return value
        ''',
    "traffic/replay.py": '''
        def generate(mix, seed, seconds, vocab_size, max_len=0):
            return {"loop": "open", "requests": [
                {"prompt": [1] * n, "max_new": 2, "due_s": float(i),
                 "phase": "window"} for i, n in enumerate(mix["lengths"])]}
        ''',
}

LAYER = "Latent attention cache (a later PR's module)"
NEW_DATA = {
    f"configs/{CONFIG}.json": TOY_CONFIG,
    "traffic/sft-tiny.json": {"generator": "batches", "seq": 2048,
                              "rows": "uniform_ids", "why": "a test"},
    "traffic/bursty.json": {"generator": "replay", "loop": "open",
                            "lengths": [3, 5, 7]},
    f"workloads/{CELL}.json": {
        "config": CONFIG, "traffic": "sft-tiny", "runner": "train_loop",
        "chips": 1, "traffic_overrides": {"seq": 16},
        "end_to_end": ["train_tokens_per_s", "setup_s"],
        "correct": {"reference_steps": 3,
                    "limits": {"loss_gap": 1e-4, "first_grad_gap": 1e-3,
                               "param_change_gap": 1e-3}},
        "why": "a test"},
    "metrics/latent_cache_bytes.json": {
        "layer": LAYER, "unit": "B/token", "moves": "train_tokens_per_s",
        "reader": "work_count",
        "args": {"work": "flops_latent.latent_cache_bytes_per_token"}},
    "metrics/absent_ms.json": {
        "layer": LAYER, "unit": "ms", "moves": "train_tokens_per_s",
        "reader": "constant", "args": {"value": None}},
}


def _digests(top):
    out = {}
    for folder, _, names in os.walk(top):
        for n in names:
            path = os.path.join(folder, n)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def later_pr(tmp_path_factory, spec):
    """A checkout in which a later PR has added a family: the committed
    ``benchmarks/`` copied, new files written beside the copies, the
    program linked in, and the manifest grown by entries."""
    root = tmp_path_factory.mktemp("later_pr")
    bench_dir = root / "benchmarks"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "skypilot_tpu"), root / "skypilot_tpu")
    before = _digests(bench_dir)
    (bench_dir / "reference").mkdir(exist_ok=True)
    for rel, text in NEW_FILES.items():
        assert rel not in before, rel
        (bench_dir / rel).write_text(textwrap.dedent(text).lstrip())
    for rel, obj in NEW_DATA.items():
        assert rel not in before, rel
        (bench_dir / rel).write_text(json.dumps(obj))
    grown = copy.deepcopy(spec)
    grown["configs"].append(
        {"name": CONFIG, "source": TOY_CONFIG["source"],
         "file": f"benchmarks/configs/{CONFIG}.json",
         "reduced": TOY_CONFIG["reduced"], "why": "a test"})
    grown["workloads"].append(
        {"name": CELL, "config": CONFIG, "traffic": "sft-tiny", "chips": 1,
         "why": "a test"})
    next(m for m in grown["end_to_end"]
         if m["name"] == "train_tokens_per_s")["workloads"].append(CELL)
    grown["per_layer"].extend(
        {"name": n, "unit": u, "better": "lower", "source": "program_counter",
         "layer": LAYER, "moves": "train_tokens_per_s", "workloads": [CELL]}
        for n, u in (("latent_cache_bytes", "B/token"), ("absent_ms", "ms")))
    (root / "BENCHMARK.json").write_text(json.dumps(grown))
    return {"root": str(root), "bench_dir": str(bench_dir), "grown": grown,
            "before": before}


def test_adding_a_family_needs_new_files_only(later_pr):
    """The grown manifest is valid (its depth cut included), the harness
    finds every new piece by name, and the cell of the new family runs
    end to end through the general child — with no file that was there
    before written to."""
    root, bdir = later_pr["root"], later_pr["bench_dir"]
    grown = later_pr["grown"]
    manifest.validate(grown, root=root)

    cell = manifest.load_workload(CELL, bdir)
    config = manifest.load_config(cell["config"], bdir)
    assert manifest.family_name(config) == FAMILY
    dims = manifest.load_family(config, bdir).dims(config)
    assert dims.heads == 20 and dims.hidden % dims.heads    # no head size
    assert (dims.layers, dims.experts) == (5, 8)
    mix = manifest.load_traffic(cell, bdir)
    assert mix["seq"] == 16                          # the cell's override

    # a traffic mix with a generator of its own
    bursty = manifest.load_traffic({"traffic": "bursty",
                                    "traffic_overrides": {"lengths": [2, 4]}},
                                   bdir)
    gen = manifest.load_module("traffic", bursty["generator"], bdir)
    assert [len(r["prompt"]) for r in
            gen.generate(bursty, 1, 10, 100)["requests"]] == [2, 4]

    # a metric whose work count takes the new family's dims; a reader
    # that finds nothing returns nothing and is left out
    entries = manifest.cell_metrics(grown, CELL, "per_layer")
    assert [m["name"] for m in entries] == ["latent_cache_bytes",
                                            "absent_ms"]
    ctx = {"config": config, "cell": cell, "mix": mix, "bench_dir": bdir}
    got = bench_run.read_metrics(ctx, {"facts": {}}, entries, bdir)
    assert got == {"latent_cache_bytes": {
        "value": 2.0 * (16 + 2) * 5, "unit": "B/token"}}

    # the cell, through ``benchmarks.run`` in that checkout
    rc, lines, err = bench(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                            "--seconds", "1", "--trace", "0", "--rehearse",
                            "--control"], cwd=root,
                           env={"PYTHONPATH": ""})
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["correct"] is True and obj["attempted"] >= 3
    assert set(obj["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(obj["compared"]) >= {"loss_gap", "first_grad_gap",
                                    "param_change_gap"}
    ref = json.loads([l for l in lines if l.startswith("REFERENCE ")][0][10:])
    assert len(ref["reference_losses"]) == 3
    assert ref["control"]["first_grad_gap"] > 3 * ref["first_grad_gap"]

    after = _digests(bdir)
    assert {k: after[k] for k in later_pr["before"]} == later_pr["before"]
    assert set(after) - set(later_pr["before"]) >= set(NEW_FILES) | set(
        NEW_DATA)


def _rewrite_config(later_pr, **changes):
    path = os.path.join(later_pr["bench_dir"], "configs", CONFIG + ".json")
    cfg = copy.deepcopy(TOY_CONFIG)
    for key, value in changes.items():
        if key == "published":
            cfg["published"] = dict(cfg["published"], **value)
        elif value is None:
            cfg.pop(key)
        else:
            cfg[key] = value
    with open(path, "w") as f:
        json.dump(cfg, f)
    grown = copy.deepcopy(later_pr["grown"])
    grown["configs"][-1]["reduced"] = cfg["reduced"]
    return grown


@pytest.mark.parametrize("changes,ok", [
    ({}, True),
    ({"num_hidden_layers": 5, "first_k_dense_replace": 1}, True),
    ({"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size",
                  "hidden_size"]}, False),
    ({"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size",
                  "kv_lora_rank"], "published": {"kv_lora_rank": 512}}, False),
    ({"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size",
                  "moe_intermediate_size"]}, False),
    ({"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size",
                  "num_experts_per_tok"]}, False),
    ({"num_hidden_layers": 4}, False),      # three after the dense one
    ({"n_routed_experts": 7}, False),
    ({"vocab_size": 455}, False),           # a ninth of 4096
    ({"published": {"vocab_size": 4097}}, False),
    ({"published": {"hidden_size": 2048}}, False),   # a width moved, unsaid
    ({"reduced": ["num_hidden_layers", "n_routed_experts"]}, False),
    ({"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size",
                  "num_nextn_predict_layers"],
      "num_nextn_predict_layers": 0}, False),        # no published value
], ids=lambda v: "")
def test_reduced_keys_published_values_and_floors(later_pr, changes, ok):
    grown = _rewrite_config(later_pr, **changes)
    try:
        if ok:
            manifest.validate(grown, root=later_pr["root"])
        else:
            with pytest.raises(manifest.ManifestError):
                manifest.validate(grown, root=later_pr["root"])
    finally:
        _rewrite_config(later_pr)


def test_a_function_that_counts_work_is_found_by_name(tmp_path, spec):
    """A metric's file names its FLOP or byte count as
    ``<module>.<function>``; a later PR's count is a new file beside
    ``flops.py`` and no reader changes. A configuration may say which
    count is its own (``metric_args``)."""
    named = 0
    for entry in spec["per_layer"]:
        args = manifest.load_metric(entry["name"]).get("args") or {}
        for ref in (args.get("flops_function"), args.get("work")):
            if ref:
                named += 1
                assert callable(manifest.load_function(ref))
    assert named >= 2
    for c in spec["configs"]:
        cfg = manifest.load_config(c["name"])
        for args in (cfg.get("metric_args") or {}).values():
            for ref in args.values():
                assert callable(manifest.load_function(ref))
    (tmp_path / "flops_moe.py").write_text(
        "def routed_flops_per_token(dims, seq, program):\n"
        "    return 2.0 * dims['hidden'] * program['experts_per_token']\n")
    got = manifest.load_function("flops_moe.routed_flops_per_token",
                                 str(tmp_path))
    assert got({"hidden": 4096}, 2048, {"experts_per_token": 2}) \
        == 4.0 * 4096
    for bad in ("flops.no_such_function", "no_such_file.f",
                "../flops.least_seconds", "readers.train_mfu.read"):
        with pytest.raises(manifest.ManifestError):
            manifest.load_function(bad)


def test_a_name_cannot_leave_the_directory():
    with pytest.raises(manifest.ManifestError):
        manifest.load_workload("../BENCHMARK")
    with pytest.raises(manifest.ManifestError):
        manifest.load_module("readers", "no_such_reader")
    with pytest.raises(manifest.ManifestError):
        manifest.load_family({"family": "no_such_family"})


# ---------------------------------------------------------------------------
# An open-loop cell's rate is a stated share of a stated knee, and its tails
# stand on enough requests
# ---------------------------------------------------------------------------

def _open_loop_cells():
    spec = manifest.load_manifest()
    cells = [manifest.load_workload(w["name"]) for w in spec["workloads"]]
    return [c["name"] for c in cells
            if "rate_rps" in (c.get("traffic_overrides") or {})]


def _num(x: float) -> str:
    return f"{x:g}" if x != int(x) else f"{x:.1f}"


@pytest.mark.parametrize("name", _open_loop_cells())
def test_a_cells_rate_is_the_stated_share_of_its_knee(spec, name):
    """The file's ``knee`` says which sweep found which rate; the cell's
    rate is that share of it rounded DOWN to 0.1 req/s; the ``why``
    names knee, share and rate, and is the one ``BENCHMARK.json``
    prints."""
    cell = manifest.load_workload(name)
    knee, rate = cell["knee"], cell["traffic_overrides"]["rate_rps"]
    assert rate == int(knee["share"] * knee["rate_rps"] * 10 + 1e-9) / 10
    assert 0.5 <= knee["share"] <= 0.9 and knee["swept"]
    (entry,) = [w for w in spec["workloads"] if w["name"] == name]
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    for number in (rate, knee["rate_rps"], knee["share"]):
        assert _num(number) in cell["why"], (number, cell["why"])
    assert "knee" in cell["why"]


@pytest.mark.parametrize("name", _open_loop_cells())
def test_a_cells_tails_stand_on_enough_requests(spec, name):
    """``stats.supported_tail``: the highest percentile with ten samples
    beyond it. A cell whose window judges fewer says so, and why, in its
    file's ``tail_note``."""
    import re

    from benchmarks import stats
    cell = manifest.load_workload(name)
    judged = round(cell["traffic_overrides"]["rate_rps"]
                   * spec["run_seconds"])
    tail = stats.supported_tail(judged) or 0
    named = [int(m.group(1)) for m in (
        re.search(r"_p(\d+)_", e["name"]) for e in
        manifest.cell_metrics(spec, name, "end_to_end")) if m]
    assert named
    if max(named) > tail:
        note = cell.get("tail_note", "")
        assert str(judged) in note and len(note) > 60, (judged, tail)
    else:
        assert "tail_note" not in cell


@pytest.mark.parametrize("name", _open_loop_cells())
def test_a_cell_whose_generator_polls_says_why(name):
    """The generator waits between events; a cell whose file makes it
    spin (``generator_polls``, one core for a run's length) gives the
    measured comparison that it rests on."""
    why = manifest.load_workload(name).get("generator_polls")
    if why is not None:
        assert "PERF.md" in why and "runs" in why and len(why) > 100
