"""Find a serve configuration's knee, once, when a cell is defined:

    python3 -m benchmarks.tools.knee_sweep --workload <cell> --rates 2,2.4,2.8,3.2,3.6,4,4.5,5,6,7,8,9,10 --step-seconds 60

One server (one set-up), then each rate as an open-loop step of the
cell's own mix, started on an empty server and drained before the next.
A step has to last several request lifetimes (PR 37's sweeps: a request
of the chat mix lives ~2 s at 2 req/s and ~5 s at 8, at 17-36 ms a
token; one of the long-prompt mix 1-4 s); its first third is the ramp
and is not judged. A rate is SUSTAINED when, over the last two thirds of
its step, the tokens received keep up with the tokens asked for (>= 0.9
of the output tokens of the requests due then) AND the requests in
flight, averaged over the last third, are no more than 1.2 times their
average over the middle third (than 1.2 requests, where that was under
one). Above the knee the backlog, and with it the time to a first
token, grows all through the step. Two things the rule does, both seen in PR 37's tables
(``PERF.md`` section 6): it calls a rate sustained whose queue stands
still at a length (7.5 and 8.0 req/s of the chat mix keep 50-57 requests
for 32 slots and a first token waits 3 s), and with one or two requests
in flight it trips on the arrivals' own noise (1.25 -> 2.0 in flight is
"growth" of 1.6). Read the first-token times by third beside it. The
knee is the highest sustained rate below the first that is not (low
rates that trip on noise apart); the cell's file then fixes 0.8 of it
(``knee`` and ``traffic_overrides.rate_rps`` there), and the table goes
into ``PERF.md``. Never part of a run of the benchmark."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import loadgen, manifest, stats
from benchmarks.process import Child, child_argv, child_env
from benchmarks.run import configure_cache, merge
from benchmarks.runners import serve_http


KEEP_UP = 0.9          # received / asked, last two thirds of a step
GROWTH = 1.2           # mean in flight, last third / middle third


def in_flight(records, t: float) -> int:
    return sum(1 for r in records if r.sent is not None and r.sent <= t
               and (r.end is None or r.end > t))


def judge(records, t0: float, step_s: float) -> dict:
    """One step's row: what was asked and received, the requests in
    flight, the latencies by third, and whether the rate was sustained."""
    third = step_s / 3
    a, b, c = t0 + third, t0 + 2 * third, t0 + step_s
    good = [r for r in records if r.ok]

    def mean_in_flight(lo, hi):
        ticks = [lo + (hi - lo) * (i + 0.5) / 20 for i in range(20)]
        return sum(in_flight(records, t) for t in ticks) / len(ticks)

    def ttft_ms(lo, hi, q):
        xs = [(r.first - r.due) * 1e3 for r in good
              if r.first is not None and lo <= r.due < hi]
        return stats.percentile(xs, q) if xs else None

    asked = sum(r.request["max_new"] for r in records if a <= r.due < c)
    got = sum(1 for r in records for t in r.stamps if a <= t < c)
    mid, last = mean_in_flight(a, b), mean_in_flight(b, c)
    gaps = [g for g in (stats.mean_gap_ms(r.stamps) for r in good)
            if g is not None]
    return {"sent": len(records), "failed": len(records) - len(good),
            "asked_tokens": asked, "received_tokens": got,
            "received_per_s": got / (c - a),
            "in_flight_mid": mid, "in_flight_last": last,
            "in_flight_5s": [in_flight(records, t0 + 5.0 * i)
                             for i in range(1, int(step_s // 5) + 1)],
            "sustained": bool(got >= KEEP_UP * asked
                              and last <= GROWTH * max(mid, 1.0)),
            "ttft_p50_ms_by_third": [ttft_ms(t0, a, 50), ttft_ms(a, b, 50),
                                     ttft_ms(b, c, 50)],
            "ttft_p95_ms_by_third": [ttft_ms(t0, a, 95), ttft_ms(a, b, 95),
                                     ttft_ms(b, c, 95)],
            "tpot_p50_ms": stats.percentile(gaps, 50) if gaps else None,
            "tpot_p90_ms": stats.percentile(gaps, 90) if gaps else None,
            "generator_late_ms_max": max(
                ((r.sent - r.due) * 1e3 for r in records
                 if r.sent is not None), default=None),
            "drain_s": max((r.end or t0) for r in records) - c}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--out", default="bench_out/knee")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_workload(args.workload)
    config = manifest.load_config(cell["config"])
    if args.rehearse:
        cell = merge(cell, cell.get("rehearse"))
        config = merge(config, config.get("rehearse"))
    mix = manifest.load_traffic(cell)
    mix["lead_in_s"] = 0
    configure_cache()
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    ctx = {"rehearse": args.rehearse}
    dims = manifest.load_family(config).dims(config)
    gen = manifest.load_module("traffic", mix["generator"])
    config_file = os.path.join(out_dir, "config_as_run.json")
    with open(config_file, "w") as f:
        json.dump(config, f)
    port = serve_http.free_port()
    argv = child_argv("serve") + [
        "--config-file", config_file, "--seed", str(args.seed),
        "--port", str(port), "--chips", str(cell["chips"]),
        "--rehearse", str(int(args.rehearse))]
    child = Child(argv, child_env(ctx), os.path.join(out_dir, "server.log"))
    rows = []
    try:
        if child.expect("DEVICE", 300.0) is None:
            raise RuntimeError("no device")
        serve_http.wait_ready(child, port, 1150.0)
        for i, rate in enumerate(float(x) for x in args.rates.split(",")):
            plan = gen.generate(dict(mix, rate_rps=rate), args.seed + i,
                                args.step_seconds, dims.vocab_size,
                                int(config["program"]["max_len"]))
            with loadgen.collector_off():
                t0 = time.monotonic() + 0.3
                records = loadgen.run(
                    "127.0.0.1", port,
                    [(t0 + r["due_s"], r) for r in plan["requests"]])
            row = dict({"rate_rps": rate},
                       **judge(records, t0, args.step_seconds))
            rows.append(row)
            print("KNEE", json.dumps(row), flush=True)
    finally:
        child.stop()
    with open(os.path.join(out_dir, "knee.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
