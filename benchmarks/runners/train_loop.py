"""Runner of the training cells: one child holds the chip(s), builds the
step and its state through the program's own builders, drives the first
steps and the window, then runs the plain reference (see
``children/train_child.py``). This process never imports JAX."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

from benchmarks.process import Child, child_argv, child_env, reduce_trace


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    out_dir = ctx["out_dir"]
    files = {}
    for key in ("config", "cell", "mix"):
        files[key] = os.path.join(out_dir, f"{key}_as_run.json")
        with open(files[key], "w") as f:
            json.dump(ctx[key], f)
    trace_dir = os.path.join(out_dir, "trace") if ctx["trace"] else ""
    argv = child_argv("train") + [
        "--config-file", files["config"], "--cell-file", files["cell"],
        "--mix-file", files["mix"], "--seed", str(ctx["seed"]),
        "--seconds", str(ctx["seconds"]), "--trace-dir", trace_dir,
        "--control", str(int(ctx.get("control", 0))),
        "--rehearse", str(int(ctx["rehearse"]))]
    env = child_env(ctx)
    if ctx["rehearse"] and ctx["cell"].get("mesh"):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4")
    child = Child(argv, env, os.path.join(out_dir, "train.log"))
    try:
        device = child.expect("DEVICE", 300.0)
        if device is None:
            raise RuntimeError("the training child opened no device "
                               "(see train.log)")
        window = child.expect("WINDOW", 1150.0)
        if window is None:
            raise RuntimeError("the training child never reached its "
                               "window (see train.log)")
        got = child.expect("RESULT", ctx["seconds"] + 900.0)
        if got is None:
            raise RuntimeError("the training child gave no result "
                               "(see train.log)")
    finally:
        child.stop()
    res = got[1]
    res["values"]["setup_s"] = window[1]["start_wall"] - ctx["t_start"]
    dev = dict(device[1])
    dev["memory_peak_bytes"] = int(res.pop("memory_peak_bytes"))
    ctx["reference"] = res.pop("reference")
    facts = res.pop("facts")
    if ctx["trace"]:
        facts["trace"] = reduce_trace(ctx, trace_dir, env)
    return {"attempted": res["attempted"], "failed": res["failed"],
            "values": res["values"], "info": res["info"],
            "checks": res["checks"],
            "correct": all(c["ok"] for c in res["checks"]),
            "device": dev, "facts": facts}
