"""Device milliseconds a decode step: device seconds of the decode
module launches (``_decode*``, ``_verify``) over the sum of ``k``, the
steps each launch ran, as the engine's ``engine.decode.dispatch``
annotation of the same launch states it. Only bursts that lie whole
inside the trace count, on both sides (``spans.pair_decode``)."""

from benchmarks import spans


def read(facts, ctx):
    red = spans.load(facts, ctx)
    if not red:
        return None
    d = red["decode"]
    if d["steps"] <= 0 or d["device_s"] <= 0:
        return None
    return d["device_s"] * 1e3 / d["steps"]
