"""Where ``setup_s`` went, by the program's own account: the event lines
it echoes on standard error while it starts (``startup.phase {phase,
s}``, one a phase as it closes; ``program.compiled {fun_name, trace_s,
lower_s, compile_s, load_s, cache_hit}``, one a finished compile), read
from the child's log in the run's output directory.

Only what lies before the measured window counts: in ``server.log`` the
events up to ``server.listening``, in ``train.log`` those up to the
``BENCH_WINDOW`` line's ``start_wall`` (the reference compiles after
it). One of three readings:

``phases``        the seconds of these start-up phases, summed
``stages``        these fields of every ``program.compiled``, summed
``cache_misses``  how many of them read ``cache_hit: false``

Seconds by the program's host clock and counts it keeps itself; nothing
from a run that was not on the chip, and nothing (``None``) from a
program that echoes no such line."""

import json
import os

_WINDOW = "BENCH_WINDOW "


def events_before_window(out_dir):
    """``[(name, attrs)]`` of the program's events before the window,
    or ``None`` where no log says when the window began."""
    for log, serve in (("server.log", True), ("train.log", False)):
        path = os.path.join(out_dir, log)
        if os.path.isfile(path):
            break
    else:
        return None
    events, cut = [], None
    with open(path, encoding="utf-8", errors="replace") as f:
        for line in f:
            if not serve and line.startswith(_WINDOW) and cut is None:
                try:
                    cut = float(json.loads(line[len(_WINDOW):])["start_wall"])
                except (ValueError, KeyError, TypeError):
                    pass
                continue
            if not line.startswith('{"kind": "event"'):
                continue
            try:
                rec = json.loads(line)
                name, ts = rec["name"], float(rec["ts_s"])
            except (ValueError, KeyError, TypeError):
                continue
            if serve and name == "server.listening" and cut is None:
                cut = ts
            events.append((ts, name, rec.get("attrs") or {}))
    if cut is None:
        return None
    return [(name, attrs) for ts, name, attrs in events if ts <= cut]


def read(facts, ctx, phases=None, stages=None, cache_misses=False):
    if (facts.get("trace") or {}).get("platform") != "tpu":
        return None
    events = events_before_window(ctx["out_dir"])
    if events is None:
        return None
    if phases:
        found = [float(a["s"]) for name, a in events
                 if name == "startup.phase" and a.get("phase") in phases]
        return sum(found) if found else None
    compiled = [a for name, a in events if name == "program.compiled"]
    if not compiled:
        return None
    if cache_misses:
        return sum(1 for a in compiled if a.get("cache_hit") is False)
    return sum(float(a.get(stage, 0.0)) for a in compiled
               for stage in stages)
