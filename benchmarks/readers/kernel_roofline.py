"""A kernel's share of its roofline: the least time the chip could take
for the work the step REQUIRES of the kernel (``work`` names the function
that counts it, ``"<module>.<function>"`` under the benchmark's directory,
called as ``fn(dims, batch, seq)``) over the device time of every op of
the given kinds (a custom call's kind is its target), per step, in
percent. Recomputed forwards are in the measured time and not in the
required work, so the share can only fall short of 100."""

from benchmarks import flops, manifest


def read(facts, ctx, kinds, work):
    trace, traced = facts.get("trace"), facts.get("traced")
    device = facts.get("device") or {}
    if not trace or not traced or trace.get("platform") != "tpu":
        return None
    seconds = sum(trace.get("kinds", {}).get(k, 0.0) for k in kinds)
    steps = traced.get("steps", 0)
    if seconds <= 0 or steps <= 0:
        return None
    need = manifest.load_function(work, ctx["bench_dir"])(
        manifest.model_dims(ctx["config"]),
        int(ctx["config"]["program"]["batch"]), int(ctx["mix"]["seq"]))
    floor = flops.least_seconds(need, device["kind"])
    return 100.0 * floor["seconds"] / (seconds / steps)
