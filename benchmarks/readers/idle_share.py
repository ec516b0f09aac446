"""Share of the traced window in which no operation ran on the device:
1 - union of device-op intervals / window, in percent."""


def read(facts, ctx):
    trace = facts.get("trace")
    if not trace or trace.get("platform") != "tpu" \
            or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
