"""One cell, one run:

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, traced,
``breakdown``). With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics. Everything else —
medians, sample counts, generator lateness, each number compared beside
its limit — goes on earlier lines and into ``results.json`` in the
output directory.

``--rehearse`` runs the cell's tiny stand-in on the CPU to prove paths
and control flow; it reports ``device.platform: "cpu"`` and never a
device metric. Without it, a run that finds no TPU fails and prints no
result. This process never imports JAX.
"""

from __future__ import annotations

import time

_T_START = time.time()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

if __package__ in (None, ""):      # ``python3 benchmarks/run.py``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import manifest   # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_cache() -> str:
    """The persistent compile cache: where the environment says, else a
    FIXED directory inside the checkout (the path is part of the cache's
    key). Children inherit it; the program's ``compile_cache.configure``
    honours the variable and sets no other."""
    if not os.environ.get(CACHE_ENV):
        os.environ[CACHE_ENV] = os.path.join(manifest.ROOT, ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                          "0.2")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    os.makedirs(os.environ[CACHE_ENV], exist_ok=True)
    return os.environ[CACHE_ENV]


def merge(base, over):
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if (isinstance(v, dict)
                                       and isinstance(out.get(k), dict)) else v
    return out


def read_metrics(ctx, result, specs, bench_dir):
    """Per-layer metrics: each a reader of its own; one that finds
    nothing to read returns ``None`` and is left out of the line."""
    out = {}
    for entry in specs:
        spec = manifest.load_metric(entry["name"], bench_dir)
        reader = manifest.load_module("readers", spec["reader"], bench_dir)
        value = reader.read(result.get("facts") or {}, ctx,
                            **(spec.get("args") or {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="also compute the low-precision control "
                         "(the builder's readings; never the driver's runs)")
    ap.add_argument("--out", default=None,
                    help="output directory (default bench_out/<cell>/...)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(manifest.ROOT, "skypilot_tpu")):
        sys.stderr.write("the program (skypilot_tpu/) is not in this "
                         "checkout: nothing to measure\n")
        return 2
    spec = manifest.load_manifest()
    bench_dir = manifest.BENCH_DIR
    cell = manifest.load_workload(args.workload, bench_dir)
    config = manifest.load_config(cell["config"], bench_dir)
    if args.rehearse:
        cell = merge(cell, cell.get("rehearse"))
        config = merge(config, config.get("rehearse"))
    mix = manifest.load_traffic(cell, bench_dir)
    cache_dir = configure_cache()
    out_dir = args.out or os.path.join(
        manifest.ROOT, "bench_out", cell["name"],
        f"seed{args.seed}_trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    ctx = {"cell": cell, "config": config, "mix": mix, "seed": args.seed,
           "seconds": float(args.seconds), "trace": args.trace,
           "rehearse": args.rehearse, "control": args.control,
           "out_dir": os.path.abspath(out_dir), "bench_dir": bench_dir,
           "t_start": _T_START, "manifest": spec}
    assert "jax" not in sys.modules, "the parent must stay off the chip"
    runner = manifest.load_module("runners", cell["runner"], bench_dir)
    try:
        result = runner.run(ctx)
    except RuntimeError as e:
        sys.stderr.write(f"run failed: {e}\n")
        return 1
    assert "jax" not in sys.modules, "the parent must stay off the chip"

    device = result["device"]
    on_chip = device.get("platform") == "tpu"
    if not on_chip and not args.rehearse:
        sys.stderr.write(f"not a chip run: {device}\n")
        return 1
    for c in result["checks"]:
        print("CHECK", json.dumps(c))
    print("INFO", json.dumps(result.get("info", {})))
    if ctx.get("reference"):
        print("REFERENCE", json.dumps(ctx["reference"]))

    which = "per_layer" if args.trace else "end_to_end"
    wanted = manifest.cell_metrics(spec, cell["name"], which)
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        metrics = read_metrics(ctx, result, wanted, bench_dir) \
            if on_chip else {}
        red = (result.get("facts") or {}).get("trace")
        if red and on_chip:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in red["ops"][:10]],
                "idle_gaps": red["idle_gaps"][:10]}
        elif red:
            print("REHEARSAL_TRACE", json.dumps(
                {"modules": red["modules"], "window_s": red["window_s"]}))
    else:
        metrics = {m["name"]: {"value": result["values"][m["name"]],
                               "unit": m["unit"]}
                   for m in wanted if m["name"] in result["values"]}
        missing = [m["name"] for m in wanted
                   if m["name"] not in result["values"]]
        if missing:
            sys.stderr.write(f"the run produced no {missing}\n")
            return 1
    line["metrics"] = metrics
    line["device"] = device
    with open(os.path.join(out_dir, "results.json"), "w") as f:
        json.dump({"line": line, "checks": result["checks"],
                   "info": result.get("info"), "facts": {
                       k: v for k, v in (result.get("facts") or {}).items()
                       if k != "counters"},
                   "reference": ctx.get("reference"),
                   "cache_dir": cache_dir, "args": vars(args)}, f)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
