"""Where the benchmark's data lives and how a name finds its file.

Everything that belongs to one cell, one configuration, one traffic mix
or one per-layer metric is a file of its own under the benchmark's
directory, found by the name ``BENCHMARK.json`` gives it:

    workloads/<cell>.json     runner, configuration, traffic mix + overrides
    configs/<config>.json     the sizes as run, source, reduced, assumed
    traffic/<mix>.json        parameters one general generator reads
    traffic/<generator>.py    the generators (``generate(params, ...)``)
    metrics/<metric>.json     layer, unit, moves, reader + its arguments
    readers/<reader>.py       ``read(facts, ctx, **args) -> number | None``
    runners/<runner>.py       ``run(ctx) -> result``

A later PR adds files and entries and edits none. Standard library only:
the parent of a run never imports JAX.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
_SOURCES = ("device_trace", "program_span", "program_counter",
            "host_clock")
_WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
                "head_dim", "expansion", "experts_per_tok")


class ManifestError(ValueError):
    pass


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return _load(os.path.join(root, "BENCHMARK.json"))


def data_file(kind: str, name: str, bench_dir: str = BENCH_DIR) -> str:
    if not _NAME.match(name):
        raise ManifestError(f"bad {kind} name {name!r}")
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.isfile(path):
        raise ManifestError(f"no {kind} file for {name!r}: {path}")
    return path


def load_workload(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    cell = _load(data_file("workloads", name, bench_dir))
    cell.setdefault("name", name)
    return cell


def load_config(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    cfg = _load(data_file("configs", name, bench_dir))
    cfg.setdefault("name", name)
    return cfg


def load_traffic(cell: Dict[str, Any],
                 bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """The cell's mix with the cell's own overrides (its rate) applied."""
    mix = _load(data_file("traffic", cell["traffic"], bench_dir))
    mix.update(cell.get("traffic_overrides") or {})
    return mix


def load_metric(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    m = _load(data_file("metrics", name, bench_dir))
    m.setdefault("name", name)
    return m


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module, found by file so
    that a directory outside the package (a test's, a later PR's) works
    the same way."""
    if not _NAME.match(name):
        raise ManifestError(f"bad {kind} module name {name!r}")
    return _module_at(os.path.join(bench_dir, kind, name + ".py"),
                      f"benchmarks.{kind}.{name}")


def _module_at(path: str, qualified: str):
    if not os.path.isfile(path):
        raise ManifestError(f"no module {qualified!r}: {path}")
    spec = importlib.util.spec_from_file_location(qualified, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_function(ref: str, bench_dir: str = BENCH_DIR):
    """``"<module>.<function>"`` -> that function of
    ``<bench_dir>/<module>.py``: the functions that count operations and
    bytes (``flops.py``, or a file a later PR puts beside it), named by a
    metric's file."""
    module, _, name = ref.rpartition(".")
    if not _NAME.match(module) or "." in module or not name.isidentifier():
        raise ManifestError(f"bad function reference {ref!r}")
    mod = _module_at(os.path.join(bench_dir, module + ".py"),
                     f"benchmarks.{module}")
    fn = getattr(mod, name, None)
    if not callable(fn):
        raise ManifestError(f"no function {name!r} in {module}.py")
    return fn


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """A configuration file's sizes under the names the arithmetic uses."""
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    norm_eps: float
    max_seq_len: int
    tie_embeddings: bool

    def num_params(self) -> int:
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        attn = (2 * d * self.n_heads * self.head_dim
                + 2 * d * self.n_kv_heads * self.head_dim)
        per_layer = attn + 3 * d * ff + 2 * d
        emb = v * d if self.tie_embeddings else 2 * v * d
        return self.n_layers * per_layer + emb + d


def model_dims(config: Dict[str, Any]) -> ModelDims:
    """From the source's own key names (a Hugging Face ``config.json``)."""
    heads = int(config["num_attention_heads"])
    hidden = int(config["hidden_size"])
    return ModelDims(
        vocab_size=int(config["vocab_size"]),
        d_model=hidden,
        n_layers=int(config["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or hidden // heads),
        d_ff=int(config["intermediate_size"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)))


# ---------------------------------------------------------------------------
# Validation: the contract's limits, checked before any run
# ---------------------------------------------------------------------------

def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _exact_keys(entry: Dict, required: List[str], optional=()) -> None:
    keys = set(entry)
    _need(set(required) <= keys and keys <= set(required) | set(optional),
          f"entry {entry.get('name')!r} has keys {sorted(keys)}; wants "
          f"{sorted(required)} (+ optionally {sorted(optional)})")


def _line(text: Any, what: str) -> None:
    _need(isinstance(text, str) and 1 <= len(text) <= 200
          and "\n" not in text and "\t" not in text,
          f"{what} must be 1-200 characters on one line: {text!r}")


def is_width_key(key: str) -> bool:
    k = key.lower()
    return (k.endswith("_dim") or k.endswith("_rank")
            or any(w in k for w in _WIDTH_WORDS))


def validate(manifest: Dict[str, Any], root: str = ROOT,
             bench_dir: Optional[str] = None) -> None:
    """Raise :class:`ManifestError` on the first breach."""
    _exact_keys(manifest, ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"])
    paths = manifest["paths"]
    _need(1 <= len(paths) <= 16, "1 to 16 paths")
    for p in paths:
        _need(bool(_PATH.match(p)) and not p.startswith("/")
              and ".." not in p.split("/"), f"bad path {p!r}")
    bench_dir = bench_dir or os.path.join(root, paths[0])
    cmd = manifest["command"]
    _need(1 <= len(cmd) <= 32, "command of 1 to 32 words")
    for word in cmd:
        _line(word, "command word")
        _need(not word.startswith("/") and ".." not in word.split("/"),
              f"command word {word!r} leaves the repo")
    rs = manifest["run_seconds"]
    _need(isinstance(rs, int) and 1 <= rs <= 51, "run_seconds 1..51")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in paths)

    configs = manifest["configs"]
    _need(1 <= len(configs) <= 24, "1 to 24 configs")
    files = set()
    for c in configs:
        _exact_keys(c, ["name", "source", "file", "reduced", "why"])
        _need(bool(_NAME.match(c["name"])), f"bad name {c['name']!r}")
        _line(c["source"], "source")
        _line(c["why"], "why")
        _need(under_paths(c["file"]) and c["file"] not in files,
              f"config file {c['file']!r} outside paths or shared")
        files.add(c["file"])
        _need(os.path.isfile(os.path.join(root, c["file"])),
              f"config file {c['file']!r} missing")
        _need(len(c["reduced"]) <= 16, "at most 16 reduced keys")
        for key in c["reduced"]:
            _need(bool(_NAME.match(key)), f"bad reduced key {key!r}")
            _need(not is_width_key(key),
                  f"reduced may never name a width: {key!r}")
    config_names = [c["name"] for c in configs]
    _need(len(set(config_names)) == len(config_names), "config names repeat")

    cells = manifest["workloads"]
    _need(1 <= len(cells) <= 24, "1 to 24 workloads")
    seen = set()
    for w in cells:
        _exact_keys(w, ["name", "config", "traffic", "chips", "why"])
        for key in ("name", "config", "traffic"):
            _need(bool(_NAME.match(w[key])), f"bad {key} {w[key]!r}")
        _need(w["config"] in config_names,
              f"cell {w['name']} names no configuration")
        _need(w["chips"] in (1, 4), "chips is 1 or 4")
        _line(w["why"], "why")
        _need((w["config"], w["traffic"]) not in seen,
              f"pair {w['config']}/{w['traffic']} appears twice")
        seen.add((w["config"], w["traffic"]))
        cell = load_workload(w["name"], bench_dir)
        for key in ("config", "traffic", "chips"):
            _need(cell[key] == w[key],
                  f"{w['name']}: {key} differs from its workload file")
        load_config(cell["config"], bench_dir)
        load_traffic(cell, bench_dir)
        load_module("runners", cell["runner"], bench_dir)
    cell_names = [w["name"] for w in cells]
    _need(len(set(cell_names)) == len(cell_names), "cell names repeat")
    _need(all(any(w["config"] == c for w in cells) for c in config_names),
          "a configuration is used by no cell")
    four = sum(1 for w in cells if w["chips"] == 4)
    _need(four <= max(1, len(cells) // 4), "too many four-chip cells")

    e2e = manifest["end_to_end"]
    _need(1 <= len(e2e) <= 16, "1 to 16 end-to-end metrics")
    reports: Dict[str, List[str]] = {}
    for m in e2e:
        _exact_keys(m, ["name", "unit", "better", "bound", "source"],
                    ["workloads"])
        _check_metric(m, cell_names)
        _need(m["source"] in ("host_clock", "device_trace"),
              f"{m['name']}: end-to-end source is host_clock or "
              f"device_trace")
        _need(0 < m["bound"] <= 0.1, f"{m['name']}: bound in (0, 0.1]")
        reports[m["name"]] = m.get("workloads", cell_names)
    _need("setup_s" in reports and "workloads" not in
          next(m for m in e2e if m["name"] == "setup_s"),
          "setup_s must be reported by every cell")
    for cell in cell_names:
        n = sum(1 for name, ws in reports.items()
                if cell in ws and name != "setup_s")
        _need(n >= 1, f"cell {cell} reports only setup_s")

    per_layer = manifest["per_layer"]
    _need(1 <= len(per_layer) <= 128, "1 to 128 per-layer metrics")
    covered = set()
    for m in per_layer:
        _exact_keys(m, ["name", "unit", "better", "source", "layer",
                        "moves"], ["workloads"])
        _check_metric(m, cell_names)
        _need(m["source"] in _SOURCES, f"{m['name']}: bad source")
        _line(m["layer"], "layer")
        _need(m["moves"] in reports,
              f"{m['name']}: moves names no end-to-end metric")
        for cell in m.get("workloads", reports[m["moves"]]):
            _need(cell in reports[m["moves"]],
                  f"{m['name']}: cell {cell} does not report "
                  f"{m['moves']}")
            covered.add(cell)
        spec = load_metric(m["name"], bench_dir)
        load_module("readers", spec["reader"], bench_dir)
    _need(covered >= set(cell_names), "a cell has no per-layer metric")
    names = [m["name"] for m in e2e + per_layer]
    _need(len(set(names)) == len(names), "metric names repeat")


def _check_metric(m: Dict[str, Any], cell_names: List[str]) -> None:
    _need(bool(_NAME.match(m["name"])), f"bad metric name {m['name']!r}")
    _need(bool(_UNIT.match(m["unit"])),
          f"{m['name']}: bad unit {m['unit']!r}")
    _need(m["better"] in ("lower", "higher"), f"{m['name']}: better?")
    for cell in m.get("workloads", []):
        _need(cell in cell_names, f"{m['name']}: unknown cell {cell!r}")


def cell_metrics(manifest: Dict[str, Any], cell: str, which: str
                 ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    e2e_of = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    out = []
    for m in manifest[which]:
        ws = m.get("workloads")
        if ws is None and which == "per_layer":
            ws = e2e_of.get(m["moves"])
        if ws is None or cell in ws:
            out.append(m)
    return out
