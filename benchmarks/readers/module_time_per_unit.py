"""Device time of some XLA modules in the traced stretch, per unit of
work done in it: ``ms`` per thousand prompt tokens, per thousand output
tokens, or per step."""

import re


def read(facts, ctx, modules, per):
    trace, traced = facts.get("trace"), facts.get("traced")
    if not trace or not traced or trace.get("platform") != "tpu":
        return None
    pats = [re.compile(p) for p in modules]
    seconds = sum(m["s"] for name, m in trace["modules"].items()
                  if any(p.search(name) for p in pats))
    units = {"prompt_ktok": traced.get("prompt_tokens", 0) / 1e3,
             "output_ktok": traced.get("output_tokens", 0) / 1e3,
             "step": traced.get("steps", 0)}[per]
    if seconds <= 0 or units <= 0:
        return None
    return seconds * 1e3 / units
