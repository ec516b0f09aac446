"""A latency the load generator measured at the client over the WHOLE
window of this (traced) run, by the host's clock: ``name`` is one of the
runner's own values (``ttft_p95_ms``, ``tpot_p90_ms``, ...). For a cell
whose runs spread too widely to hold that number to a bound end to end
and which still has to show it (``PERF.md`` section 2: the chat cell's
first-token tail). Nothing from a run that was not on the chip."""


def read(facts, ctx, name):
    value = (facts.get("client") or {}).get(name)
    trace = facts.get("trace") or {}
    if value is None or trace.get("platform") != "tpu":
        return None
    return value
