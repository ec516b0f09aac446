"""Share of some XLA modules' device time spent under one of the
program's named scopes (``spans.SCOPES``; an op counts under the
innermost scope on its path), in percent of the modules' own event
seconds."""

from benchmarks import spans


def read(facts, ctx, modules, scope):
    red = spans.load(facts, ctx)
    if not red:
        return None
    groups = spans.module_groups(red, modules)
    total = sum(g["s"] for g in groups)
    # No op of these modules names ANY scope: the program has none (the
    # parent of ISSUE 25), which is not the same as a share of zero.
    if total <= 0 or not any(g["scopes"] for g in groups):
        return None
    return 100.0 * sum(g["scopes"].get(scope, 0.0) for g in groups) / total
