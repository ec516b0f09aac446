"""Device milliseconds of one named Pallas kernel per launch of the
modules that run it (per training step for ``jit_step``): the seconds of
the Mosaic ops whose ``pallas_call`` carries that ``name=``, over the
modules' launches in the trace."""

from benchmarks import spans


def read(facts, ctx, modules, kernel):
    red = spans.load(facts, ctx)
    if not red:
        return None
    groups = spans.module_groups(red, modules)
    launches = sum(g["n"] for g in groups)
    seconds = sum(g["kernels"].get(kernel, 0.0) for g in groups)
    if launches <= 0 or seconds <= 0:
        return None
    return seconds * 1e3 / launches
