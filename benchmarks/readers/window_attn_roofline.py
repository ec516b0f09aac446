"""Roofline share of the sliding-window attention: the least time the
chip could take for what the window layers of the traced stretch's
programs REQUIRE, over the device seconds those programs spent under the
layers' scope (``window_attn``), in percent. ``phase`` picks the side:

* ``decode``: over the decode bursts that lie whole inside the trace
  (the launches ``decode_device_ms_per_step`` counts), the ring rows a
  program-step must read — ``window_rows`` of each burst's
  ``engine.decode.dispatch`` annotation, the sum over its live slots of
  ``min(rows, window)``, weighted by its ``k`` steps — against the
  scope's seconds a step;
* ``prefill``: the key rows the prefill programs must score —
  ``window_keys`` of every ``engine.chunk.dispatch`` and
  ``engine.wave.dispatch`` annotation in the trace, the sum over a
  program's real tokens of ``min(p + 1, window)`` — against the scope's
  seconds in the ``modules``.

The work is ``<module>.<function>`` of the metric's file (required work
only); the seconds are grouped with the metric's own list of scopes, as
``scoped_ops`` does.

A program without the scope or the annotations (the parent of the PR
that adds them), a family whose dims have no window, or a trace without
a device plane gives ``None``.
"""

from __future__ import annotations

from benchmarks import flops, manifest, spans
from benchmarks.readers import scoped_ops

CHUNK, WAVE = "engine.chunk.dispatch", "engine.wave.dispatch"


def read(facts, ctx, modules, scope, scopes, work, phase):
    scoped = scoped_ops._load(facts, ctx, scopes)
    red = spans.load(facts, ctx)
    if not scoped or not red:
        return None
    device = scoped_ops._device_kind(facts, ctx)
    dims = manifest.load_family(ctx["config"], ctx["bench_dir"]).dims(
        ctx["config"])
    if not device or not hasattr(dims, "window"):
        return None
    fn = manifest.load_function(work, ctx["bench_dir"])
    if phase == "decode":
        bursts = spans.annotations_named(red, spans.DISPATCH,
                                         counted_decode_only=True)
        steps = spans.sum_args(bursts, "k")
        row_steps = spans.sum_args(bursts, "k", "window_rows")
        seconds = scoped["decode"]["scopes_s"].get(scope, 0.0)
        if steps <= 0 or row_steps <= 0 or seconds <= 0:
            return None
        need, seconds = fn(dims, row_steps / steps), seconds / steps
    else:
        seconds = sum(g["scopes"].get(scope, 0.0)
                      for g in spans.module_groups(scoped, modules))
        chunks = spans.annotations_named(red, CHUNK)
        waves = spans.annotations_named(red, WAVE)
        keys = spans.sum_args(chunks, "window_keys") \
            + spans.sum_args(waves, "window_keys")
        tokens = spans.sum_args(chunks, "chunk_tokens") \
            + spans.sum_args(waves, "prompt_tokens")
        if keys <= 0 or seconds <= 0:
            return None
        need = fn(dims, keys, tokens)
    return 100.0 * flops.least_seconds(need, device)["seconds"] / seconds
