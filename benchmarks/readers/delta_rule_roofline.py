"""Roofline share of the gated delta rule at PREFILL: the least time the
chip could take for the rule over the prompt tokens the traced stretch's
prefill programs were given, over the device seconds those programs
spent under the rule's scope, in percent.

The tokens are the program's own count where the work is dispatched —
``chunk_tokens`` of every ``engine.chunk.dispatch`` and
``prompt_tokens`` of every ``engine.wave.dispatch`` annotation in the
trace — and so is how often a slot's state moved: once a chunk, once
more when the chunk ``carried`` a resident state, once a wave row. The
work is ``<module>.<function>(dims, tokens, states_moved)`` of the
metric's file (required work only); the seconds are grouped with the
metric's own list of scopes, as ``scoped_ops`` does.

A program without the scope or the annotations (the parent of the PR
that adds them), a family whose dims lack the rule's sizes, or a trace
without a device plane gives ``None``.
"""

from __future__ import annotations

from benchmarks import flops, manifest, spans
from benchmarks.readers import scoped_ops

CHUNK, WAVE = "engine.chunk.dispatch", "engine.wave.dispatch"


def read(facts, ctx, modules, scope, scopes, work):
    scoped = scoped_ops._load(facts, ctx, scopes)
    red = spans.load(facts, ctx)
    if not scoped or not red:
        return None
    seconds = sum(g["scopes"].get(scope, 0.0)
                  for g in spans.module_groups(scoped, modules))
    chunks = spans.annotations_named(red, CHUNK)
    waves = spans.annotations_named(red, WAVE)
    tokens = spans.sum_args(chunks, "chunk_tokens") \
        + spans.sum_args(waves, "prompt_tokens")
    moved = len(chunks) + spans.sum_args(chunks, "carried") \
        + spans.sum_args(waves, "rows")
    device = scoped_ops._device_kind(facts, ctx)
    dims = manifest.load_family(ctx["config"], ctx["bench_dir"]).dims(
        ctx["config"])
    if seconds <= 0 or tokens <= 0 or not device \
            or not hasattr(dims, "lin_k_dim"):
        return None
    need = manifest.load_function(work, ctx["bench_dir"])(dims, tokens, moved)
    return 100.0 * flops.least_seconds(need, device)["seconds"] / seconds
