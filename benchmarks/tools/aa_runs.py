"""Two sides of one commit, as the driver pairs a parent with a change:

    python3 -m benchmarks.tools.aa_runs --workload <cell> --seeds 6 --seed0 <n> --out chiprun_out/aa_<cell>

Each run is the benchmark's own command (``python3 -m benchmarks.run
--workload <cell> --seed <n> --seconds <run_seconds> --trace 0``) in a
process of its own; the two sides share their seeds pairwise and take
turns at going first. Prints one ``AA_RUN`` line a run (every run made:
none is dropped) and one ``AA_METRIC`` line a metric: each side's median
and spread (distance between the quartiles over the median,
``stats.iqr_share``; beside it the spread without the run farthest from
the median, which is what the driver holds against half a bound), and
how far apart the two medians lie. A bound is then about five times the
wider spread. ``--also`` names further numbers of a run's ``INFO`` to
carry through the same arithmetic (``ttft_ms.p90``), so that another
percentile can be judged from the same runs. Never part of a run of the
benchmark; never imports JAX."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from benchmarks import manifest, stats


def _dig(obj, dotted: str):
    for key in dotted.split("."):
        obj = (obj or {}).get(key)
    return obj


def trimmed_spread(values) -> float:
    """The spread once the run farthest from the median is left out."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return stats.iqr_share([v for i, v in enumerate(values) if i != far])


def summarise(rows, names):
    """-> one dict a metric: medians, spreads, the medians' distance."""
    out = []
    for name in names:
        sides = {}
        for side in sorted({r["side"] for r in rows}):
            xs = [r["values"][name] for r in rows
                  if r["side"] == side and r["values"].get(name) is not None]
            if len(xs) >= 3:
                sides[side] = {"n": len(xs), "median": statistics.median(xs),
                               "spread": stats.iqr_share(xs),
                               "spread_trimmed": trimmed_spread(xs),
                               "values": xs}
        row = {"metric": name, "sides": sides}
        if len(sides) == 2:
            a, b = (sides[s]["median"] for s in sorted(sides))
            row["medians_apart"] = abs(b - a) / a
        out.append(row)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--seed0", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--also", default="")
    ap.add_argument("--out", default="bench_out/aa")
    args = ap.parse_args()
    spec = manifest.load_manifest()
    seconds = args.seconds or spec["run_seconds"]
    names = [m["name"] for m in manifest.cell_metrics(
        spec, args.workload, "end_to_end")]
    also = [a for a in args.also.split(",") if a]
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for i in range(args.seeds):
        seed = args.seed0 + i
        for side in ((0, 1), (1, 0))[i % 2]:
            run_dir = os.path.join(out_dir, f"seed{seed}_side{side}")
            t = time.time()
            done = subprocess.run(
                spec["command"] + [
                    "--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", "0",
                    "--out", run_dir],
                cwd=manifest.ROOT, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE)
            text = done.stdout.decode("utf-8", "replace")
            lines = text.strip().splitlines()
            row = {"side": side, "seed": seed, "rc": done.returncode,
                   "took_s": time.time() - t, "first_of_call": not rows,
                   "values": {}}
            info = {}
            for ln in lines:
                if ln.startswith("INFO "):
                    info = json.loads(ln[5:])
            if done.returncode == 0 and lines:
                line = json.loads(lines[-1])
                row.update(correct=line["correct"], failed=line["failed"],
                           judged=line["attempted"],
                           peak_bytes=line["device"]["memory_peak_bytes"])
                row["values"] = {k: v["value"]
                                 for k, v in line["metrics"].items()}
                row["compared"] = {k: v["value"]
                                   for k, v in line["compared"].items()}
            else:
                row["stderr"] = done.stderr.decode("utf-8",
                                                   "replace")[-1500:]
            for a in also:
                row["values"][a] = _dig(info, a)
            row["info"] = {k: info.get(k) for k in (
                "generator_lateness_ms", "generator_loop_ms",
                "http_overhead_ms", "in_flight",
                "arrivals_after_1s_quiet_share", "share_inside_limits",
                "compiles_in_window", "ttft_ms", "tpot_ms",
                "tokens_per_s", "steps", "step_ms")
                if k in info}
            rows.append(row)
            print("AA_RUN", json.dumps(row), flush=True)
    summary = summarise(rows, names + also)
    for row in summary:
        print("AA_METRIC", json.dumps(row), flush=True)
    with open(os.path.join(out_dir, "aa.json"), "w") as f:
        json.dump({"workload": args.workload, "seconds": seconds,
                   "runs": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
