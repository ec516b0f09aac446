"""A ratio of two sums over the program's own host annotations in the
traced stretch, in percent. Each side is a list of terms ``[annotation
name, argument, ...]``: the sum over that annotation's events of the
product of the named arguments (an event lacking one is skipped: a
chunk that is not final carries no ``ttft_ms``). With ``counted_decode``
the decode annotations are held to the bursts that lie whole inside the
trace, the same launches whose device time ``decode_step_time`` counts.
Counts and host milliseconds the program measured where the work
happens; no device time enters."""

from benchmarks import spans


def read(facts, ctx, numerator, denominator, counted_decode=False):
    red = spans.load(facts, ctx)
    if not red:
        return None

    def side(terms):
        return sum(spans.sum_args(
            spans.annotations_named(red, t[0], counted_decode), *t[1:])
            for t in terms)

    den = side(denominator)
    if den <= 0:
        return None
    return 100.0 * side(numerator) / den
