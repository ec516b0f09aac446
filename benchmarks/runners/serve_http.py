"""Runner of the serve cells: starts the program's HTTP server (through
the benchmark's wrapper, in a child that owns the chip), offers it the
cell's traffic from this process, and does the metric arithmetic.

This process never imports JAX: it is the load generator and must stay
off the chip.
"""

from __future__ import annotations

import json
import os
import socket
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import loadgen, manifest, stats
from benchmarks.process import Child, child_argv, child_env, reduce_trace

_HOST = "127.0.0.1"
TTFT_LIMIT_S = 1.0
GAP_LIMIT_MS = 50.0
TRACE_SECONDS = 5.0
_ENGINE_TTFT = "skytpu_ttft_seconds"
_COMPILE_COUNTERS = ("skytpu_programs_compiled_total",
                     "skytpu_unexpected_compiles_total")


def free_port() -> int:
    with socket.socket() as s:
        s.bind((_HOST, 0))
        return s.getsockname()[1]


def wait_ready(child: Child, port: int, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise RuntimeError(
                f"the server exited with code {child.proc.returncode} "
                f"before it was ready (see its log)")
        try:
            status, _ = loadgen.http_get(_HOST, port, "/health", 2.0)
            if status == 200:
                return
        except OSError:
            pass
        time.sleep(0.25)
    raise RuntimeError("the server was not ready in time")


def _scrape(port: int) -> Dict[str, float]:
    status, body = loadgen.http_get(_HOST, port, "/metrics", 10.0)
    if status != 200:
        raise RuntimeError(f"/metrics answered {status}")
    return loadgen.parse_prometheus(body.decode("utf-8", "replace"))


def _vocab_size(ctx: Dict[str, Any]) -> int:
    """All this runner asks of the model: how many ids there are (and
    ``program.max_len``)."""
    return int(manifest.load_family(ctx["config"], ctx["bench_dir"])
               .dims(ctx["config"]).vocab_size)


def _in_vocab(rec: loadgen.Record, vocab: int) -> bool:
    return all(0 <= t < vocab for t in rec.tokens)


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, config, mix = ctx["cell"], ctx["config"], ctx["mix"]
    seconds, seed, out_dir = ctx["seconds"], ctx["seed"], ctx["out_dir"]
    vocab = _vocab_size(ctx)
    gen = manifest.load_module("traffic", mix["generator"], ctx["bench_dir"])
    plan = gen.generate(mix, seed, seconds, vocab,
                        int(config["program"]["max_len"]))
    port = free_port()
    config_file = os.path.join(out_dir, "config_as_run.json")
    with open(config_file, "w") as f:
        json.dump(config, f)
    argv = child_argv("serve") + [
        "--config-file", config_file, "--seed", str(seed),
        "--port", str(port), "--chips", str(cell["chips"]),
        "--trace", str(ctx["trace"]),
        "--rehearse", str(int(ctx["rehearse"]))]
    env = child_env(ctx)
    child = Child(argv, env, os.path.join(out_dir, "server.log"))
    facts: Dict[str, Any] = {}
    try:
        device = child.expect("DEVICE", 300.0)
        if device is None:
            raise RuntimeError("the server child opened no device "
                               "(see server.log)")
        wait_ready(child, port, 1150.0)
        ready_wall = time.time()
        before = _scrape(port)
        trace_dir = os.path.join(out_dir, "trace")
        result = _drive(ctx, plan, child, port, trace_dir, facts)
        after = _scrape(port)
        child.command("mem")
        mem = child.expect("MEM", 120.0)
        if mem is None:
            raise RuntimeError("the server did not report its memory")
    finally:
        child.stop()
    result["device"] = dict(device[1])
    result["device"]["memory_peak_bytes"] = (
        int(mem[1]["peak_bytes_in_use"]) if mem else 0)
    result["info"]["ready_s"] = ready_wall - ctx["t_start"]
    result["info"]["memory"] = mem[1] if mem else None

    compiles = {k: after.get(k, 0.0) - before.get(k, 0.0)
                for k in _COMPILE_COUNTERS}
    result["info"]["compiles_in_window"] = compiles
    result["checks"].append(
        {"name": "compiles_in_window", "value": sum(compiles.values()),
         "limit": 0, "ok": sum(compiles.values()) == 0})
    # What the HTTP layer and the server's queue add to a first token:
    # the client's mean (from the send) less the engine's own histogram.
    n = after.get(_ENGINE_TTFT + "_count", 0.0) \
        - before.get(_ENGINE_TTFT + "_count", 0.0)
    if n > 0 and result["info"]["ttft_from_send_mean_ms"] is not None:
        engine_ms = (after.get(_ENGINE_TTFT + "_sum", 0.0)
                     - before.get(_ENGINE_TTFT + "_sum", 0.0)) * 1e3 / n
        result["info"]["http_overhead_ms"] = (
            result["info"]["ttft_from_send_mean_ms"] - engine_ms)

    # The plain reference, once the server has gone and the chip is free.
    sample = _sample(result.pop("_finished"), seed,
                     int(cell["correct"]["sample_requests"]))
    result["checks"].extend(_reference(ctx, config_file, sample, env))
    result["correct"] = all(c["ok"] for c in result["checks"])
    result["facts"] = facts
    if ctx["trace"]:
        facts["trace"] = reduce_trace(ctx, trace_dir, env)
    return result


def _drive(ctx, plan, child: Child, port: int, trace_dir: str,
           facts: Dict[str, Any]) -> Dict[str, Any]:
    """Offer the load; return metrics and what the readers need."""
    seconds = ctx["seconds"]
    vocab = _vocab_size(ctx)
    lead = -min([r["due_s"] for r in plan["requests"]] + [0.0])
    with loadgen.collector_off():     # before the due times are fixed
        t0 = time.monotonic() + 0.3 + lead
        t1 = t0 + seconds
        t0_wall = time.time() + (t0 - time.monotonic())
        timers = []
        trace_at: Dict[str, Optional[float]] = {"start": None, "stop": None}
        if ctx["trace"]:
            span = min(TRACE_SECONDS, seconds / 2)
            t_on = t0 + (seconds - span) / 2

            def trace_stop():
                trace_at["stop"] = time.monotonic()
                child.command("trace_stop")

            timers = [
                (t_on, lambda: child.command(f"trace_start {trace_dir}")),
                (t_on + span, trace_stop)]

        loop_ms: Dict[str, float] = {}
        records = loadgen.run(
            _HOST, port, [(t0 + r["due_s"], r) for r in plan["requests"]],
            timers=timers, loop_info=loop_ms,
            poll=bool(ctx["cell"].get("generator_polls")))

    if ctx["trace"]:
        started = child.expect("TRACE", 30.0)
        trace_at["start"] = started[2] if started else None
        child.expect("TRACE", 240.0)         # "stopped": file written

    judged = [r for r in records if r.request["phase"] == "window"]
    good, bad = [], []
    for r in judged:
        (good if r.ok and _in_vocab(r, vocab) else bad).append(r)

    # Which of these are end-to-end metrics is the manifest's say.
    values: Dict[str, float] = {"setup_s": t0_wall - ctx["t_start"]}
    ttft = [r.first - r.due for r in good if r.first is not None]
    gaps = [g for g in (stats.mean_gap_ms(r.stamps) for r in good)
            if g is not None]
    for q in (50, 90, 95):
        if ttft:
            values[f"ttft_p{q}_ms"] = stats.percentile(ttft, q) * 1e3
        if gaps:
            values[f"tpot_p{q}_ms"] = stats.percentile(gaps, q)
    facts["client"] = dict(values)       # reader ``client_value``
    late = [r.sent - r.due for r in records if r.sent is not None]
    dues = sorted(r.due for r in records)
    ticks = [t0 + seconds * (i + 0.5) / 200 for i in range(200)]
    both = [r for r in good if r.first is not None
            and r.first - r.due <= TTFT_LIMIT_S
            and (stats.mean_gap_ms(r.stamps) or 0.0) <= GAP_LIMIT_MS]
    sent_ttft = [r.first - r.sent for r in records
                 if r.first is not None and r.sent is not None]
    info: Dict[str, Any] = {
        "requests_sent": len(records),
        "generator_lateness_ms": {
            "median": stats.percentile(late, 50) * 1e3 if late else None,
            "max": max(late) * 1e3 if late else None},
        # The loop's own worst moments (``loadgen.run``): a late generator
        # shows in the first three, a slow accept or read in the last.
        "generator_loop_ms": loop_ms,
        # Of the window's arrivals, the share due a second or more after
        # the one before (the server's ``open_window_s``: it has gone
        # over to long bursts by then).
        "arrivals_after_1s_quiet_share": sum(
            1 for a, b in zip(dues, dues[1:]) if b >= t0 and b - a >= 1.0)
        / max(len(judged), 1),
        "share_inside_limits": len(both) / max(len(judged), 1),
        "out_tokens_in_window": sum(1 for r in judged for t in r.stamps
                                    if t0 <= t < t1),
        "in_flight": {"window_opens": _in_flight(records, t0),
                      "window_closes": _in_flight(records, t1),
                      "window_mean": sum(_in_flight(records, t)
                                         for t in ticks) / len(ticks)},
        "ttft_ms": stats.summary(t * 1e3 for t in ttft),
        "ttft_from_send_mean_ms": (sum(sent_ttft) * 1e3 / len(sent_ttft)
                                   if sent_ttft else None),
        "tpot_ms": stats.summary(gaps),
        "prompt_tokens": sum(len(r.request["prompt"]) for r in judged),
        "output_tokens": sum(len(r.tokens) for r in judged)}

    checks = [{"name": "requests_failed", "value": len(bad), "limit": 0,
               "ok": not bad},
              {"name": "requests_judged_min", "value": len(judged),
               "limit": 1, "ok": len(judged) >= 1, "at_least": True}]
    if bad:
        info["first_failure"] = {
            "status": bad[0].status, "error": bad[0].error,
            "tokens": len(bad[0].tokens), "asked": bad[0].request["max_new"]}

    # What the per-layer readers need from the traced stretch.
    if ctx["trace"] and trace_at["start"] and trace_at["stop"]:
        a, b = trace_at["start"], trace_at["stop"]
        facts["traced"] = {
            "seconds": b - a,
            "output_tokens": sum(1 for r in records for t in r.stamps
                                 if a <= t < b),
            "prompt_tokens": sum(len(r.request["prompt"]) for r in records
                                 if r.first is not None and a <= r.first < b),
            "first_tokens": sum(1 for r in records
                                if r.first is not None and a <= r.first < b)}
    return {"attempted": len(judged), "failed": len(bad), "values": values,
            "info": info, "checks": checks, "_finished": good}


def _in_flight(records: List[loadgen.Record], t: float) -> int:
    return sum(1 for r in records if r.sent is not None and r.sent <= t
               and (r.end is None or r.end > t))


def _sample(finished: List[loadgen.Record], seed: int, k: int
            ) -> List[Dict[str, Any]]:
    """The longest finished request and ``k - 1`` others drawn from the
    seed: some hundreds of served tokens."""
    if not finished:
        return []
    by_len = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i].request["prompt"]) + len(finished[i].tokens)))
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    rest = by_len[1:]
    picks = [by_len[0]] + [rest[i] for i in rng.permutation(len(rest))[:k - 1]]
    return [{"prompt": finished[i].request["prompt"],
             "tokens": finished[i].tokens} for i in picks]


_GAP_CHECKS = (("served_logit_gap_max", "served_gap_max"),
               ("served_logit_gap_mean", "served_gap_mean"))


def _reference(ctx, config_file: str, sample, env) -> List[Dict[str, Any]]:
    """Run the plain reference over the sample; one check per number
    compared: the widest and the mean gap by which a served token's
    reference logit lies below the reference's best at its position."""
    limits = ctx["cell"]["correct"]["limits"]

    def failed(note: str):
        return [{"name": name, "value": None, "limit": float(limits[name]),
                 "ok": False, "note": note} for name, _ in _GAP_CHECKS]

    if not sample:
        return failed("no finished request to compare")
    sample_file = os.path.join(ctx["out_dir"], "reference_sample.json")
    with open(sample_file, "w") as f:
        json.dump(sample, f)
    argv = child_argv("reference_serve") + [
        "--config-file", config_file, "--seed", str(ctx["seed"]),
        "--sample", sample_file, "--chips", str(ctx["cell"]["chips"]),
        "--control", str(int(ctx.get("control", 0))),
        "--rehearse", str(int(ctx["rehearse"]))]
    child = Child(argv, env, os.path.join(ctx["out_dir"], "reference.log"))
    try:
        got = child.expect("REFERENCE", 900.0)
    finally:
        child.stop()
    if got is None:
        return failed("the reference gave no number")
    ref = got[1]
    ctx["reference"] = ref
    return [{"name": name, "value": ref[key], "limit": float(limits[name]),
             "ok": ref[key] <= float(limits[name]),
             "positions": ref["positions"]} for name, key in _GAP_CHECKS]
