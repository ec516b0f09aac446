"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

One function, :func:`reduce_xplane`, turns the file into a plain dict
that the per-layer readers take their metrics from:

* ``window_s``  — the traced window: first to last event of any plane;
* ``busy_s``    — seconds in which an operation ran on the device: the
  union of the op intervals of each device plane, averaged over planes;
* ``modules``   — device seconds and launches per XLA module (a jitted
  function's name, e.g. ``jit__decode_burst``), numeric suffix removed;
* ``ops``       — device seconds per op (HLO instruction) name, the
  largest first; ``kinds`` — device seconds per opcode, a custom call
  under its target (``tpu_custom_call`` is a Mosaic / Pallas kernel);
* ``idle_gaps`` — the idle seconds of the first device by what the host
  was doing at the time (the innermost of the benchmark's annotations).

Only this file knows how a trace is laid out. On a TPU the device planes
are ``/device:TPU:<n>`` with the lines ``XLA Modules`` and ``XLA Ops``;
the CPU backend (rehearsals and tests) has no device plane, and its
``tf_XLAPjRtCpuClient`` host lines stand in, marked ``platform: cpu`` so
that no number from them is ever printed under a device metric's name.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

_SUFFIX = re.compile(r"\(\d+\)$")
# Host annotations the benchmark's own wrappers put round their calls;
# only these name an idle gap (the runtime's own host events are legion).
ANNOTATION_PREFIXES = ("server.", "engine.", "bench.")
# Ops whose event spans the ops of their body: counted in the busy union
# (harmlessly), left out of the per-op and per-kind sums.
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union_seconds(intervals: Iterable[Interval]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_of(intervals: Iterable[Interval], lo: float, hi: float
            ) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` not covered by ``intervals``."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def flatten_spans(host_spans: List[Tuple[str, float, float]]
                  ) -> List[Tuple[float, float, str]]:
    """Nested annotation spans -> disjoint ``(start, end, label)``
    segments, each labelled by the innermost span that covers it."""
    events = []
    for name, s, e in host_spans:
        if e > s:
            events.append((s, 1, -(e - s), name, e))
    events.sort()
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []      # (name, end)
    cur = None

    def emit(upto: float) -> None:
        nonlocal cur
        if stack and cur is not None and upto > cur:
            out.append((cur, upto, stack[-1][0]))
        cur = upto

    for s, _, _, name, e in events:
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((name, e))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def attribute_gap(gap: Interval, segments: List[Tuple[float, float, str]],
                  starts: Optional[List[float]] = None) -> Dict[str, float]:
    """What the host was doing during ``gap``: its seconds split by the
    innermost annotation running at the time, the rest ``unattributed``
    (no annotated call was running: the loop slept or waited)."""
    if starts is None:
        starts = [seg[0] for seg in segments]
    i = max(bisect.bisect_right(starts, gap[0]) - 1, 0)
    share: Dict[str, float] = {}
    covered = 0.0
    while i < len(segments) and segments[i][0] < gap[1]:
        ov = _overlap(gap, segments[i][:2])
        if ov > 0:
            share[segments[i][2]] = share.get(segments[i][2], 0.0) + ov
            covered += ov
        i += 1
    rest = (gap[1] - gap[0]) - covered
    if rest > 1e-12:
        share["unattributed"] = rest
    return share


def module_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name).strip()


_OPCODE = re.compile(r"[})\]] ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@functools.lru_cache(maxsize=1 << 16)
def op_name(event_name: str) -> Tuple[str, str]:
    """A device op event is named by its whole HLO line (``%fusion.3 =
    bf16[...] fusion(...)``). Returns the instruction's name and its
    kind: the opcode, or for a ``custom-call`` its target — a Mosaic
    (Pallas) kernel is ``tpu_custom_call``. An operand that merely
    mentions ``%custom-call.7`` does not make an op a custom call."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name, ""
    m = _OPCODE.search(rest)
    kind = m.group(1) if m else ""
    if kind == "custom-call":
        t = _TARGET.search(rest)
        kind = t.group(1) if t else kind
    return head.lstrip("%").strip(), kind


def reduce_events(device_planes: List[Dict[str, List[Tuple]]],
                  host_spans: List[Tuple[str, float, float]],
                  window: Interval, platform: str,
                  top: int = 10) -> Dict[str, Any]:
    """The arithmetic, on plain tuples (``(name, start_s, end_s,
    kind)``), so that it can be checked without a trace file.

    ``device_planes``: one dict per device with ``"ops"`` and
    ``"modules"`` event lists."""
    lo, hi = window
    busy = []
    ops: Dict[str, float] = {}
    kinds: Dict[str, float] = {}
    mods: Dict[str, Dict[str, float]] = {}
    for plane in device_planes:
        busy.append(union_seconds((s, e) for _, s, e, _ in plane["ops"]))
        for name, s, e, kind in plane["ops"]:
            if kind in CONTAINERS:
                continue          # its body's ops are events of their own
            ops[name] = ops.get(name, 0.0) + (e - s)
            if kind:
                kinds[kind] = kinds.get(kind, 0.0) + (e - s)
        for name, s, e, _ in plane["modules"]:
            m = mods.setdefault(module_name(name), {"s": 0.0, "n": 0})
            m["s"] += e - s
            m["n"] += 1
    n = max(len(device_planes), 1)
    gaps: List[List[Any]] = []
    if device_planes:
        first = device_planes[0]
        idle = gaps_of(((s, e) for _, s, e, _ in first["ops"]), lo, hi)
        idle.sort(key=lambda g: g[0] - g[1])
        by_name: Dict[str, float] = {}
        segments = flatten_spans(host_spans)
        starts = [seg[0] for seg in segments]
        for g in idle:
            for label, sec in attribute_gap(g, segments, starts).items():
                by_name[label] = by_name.get(label, 0.0) + sec
        gaps = [[k, v] for k, v in sorted(by_name.items(),
                                          key=lambda kv: -kv[1])[:top]]
    return {
        "platform": platform,
        "devices": len(device_planes),
        "window_s": hi - lo,
        "busy_s": sum(busy) / n,
        "modules": {k: {"s": v["s"] / n, "n": v["n"] / n}
                    for k, v in mods.items()},
        "ops": [[k, v / n] for k, v in sorted(ops.items(),
                                              key=lambda kv: -kv[1])],
        "kinds": {k: v / n for k, v in kinds.items()},
        "idle_gaps": gaps,
    }


def reduce_xplane(path: str, top: int = 10) -> Dict[str, Any]:
    """Read one ``.xplane.pb`` (needs ``jax``; run it where JAX is held
    to the CPU, never in the parent of a run)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_planes: List[Dict[str, List[Tuple]]] = []
    cpu_planes: List[Dict[str, List[Tuple]]] = []
    host_spans: List[Tuple[str, float, float]] = []
    lo, hi = float("inf"), float("-inf")

    def events(line):
        for ev in line.events:
            s = ev.start_ns * 1e-9
            yield ev, s, s + ev.duration_ns * 1e-9

    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and " " not in name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev, s, e in events(line):
                        short, kind = op_name(ev.name)
                        ops.append((short, s, e, kind))
                elif line.name == "XLA Modules":
                    for ev, s, e in events(line):
                        modules.append((ev.name, s, e, ""))
            for _, s, e, _ in ops + modules:
                lo, hi = min(lo, s), max(hi, e)
            device_planes.append({"ops": ops, "modules": modules})
        elif name == "/host:CPU":
            cpu = {"ops": [], "modules": []}
            for line in plane.lines:
                is_exec = line.name.startswith("tf_XLAPjRtCpuClient")
                for ev, s, e in events(line):
                    if e <= s:
                        continue
                    lo, hi = min(lo, s), max(hi, e)
                    if ev.name.startswith(ANNOTATION_PREFIXES):
                        host_spans.append((ev.name, s, e))
                    elif is_exec and not ev.name.startswith("Thread"):
                        mod = dict(ev.stats).get("hlo_module")
                        if mod:
                            cpu["ops"].append((ev.name, s, e, ""))
                            cpu["modules"].append((str(mod), s, e, ""))
            cpu_planes.append(cpu)
    platform = "tpu"
    if not device_planes:
        platform = "cpu"
        device_planes = [p for p in cpu_planes if p["ops"]][:1]
    if lo == float("inf"):
        lo, hi = 0.0, 0.0
    return reduce_events(device_planes, host_spans, (lo, hi), platform, top)
