"""Second reduction of a ``jax.profiler`` trace: what the PROGRAM says
about itself, on the profiler's clock.

``benchmarks/trace.py`` reads what any traced program gives (device
busy time, XLA module and op seconds). This file reads what the program
adds since ISSUE 25:

* the host phases ``skypilot_tpu.utils.timeline.phase`` enters — every
  ``server.`` / ``engine.`` / ``train.`` annotation with its arguments
  (steps ``k``, live ``slots``, ``rows``, ``why``, queue and first-token
  milliseconds, prompt and padded tokens ...), its start, duration and
  the host thread it came from;
* the names on the device timeline — device-op seconds per XLA module
  grouped by the innermost ``jax.named_scope`` of the op (``kv_gather``,
  ``base_matmul`` ...) and by Pallas kernel name (``flash_fwd`` ...).

Decode steps and tokens are counted over exactly the launches whose
device time is counted: a burst counts when its fetch annotation, all of
its dispatch annotations (``parts`` of them, same ``seq``) and as many
device launches lie inside the trace; bursts cut by either edge of the
traced stretch are dropped on both sides of every ratio. Dispatches and
launches are paired in order ON THE HOST'S CLOCK: the runtime's own
``DoEnqueueProgram`` event carries the ``run_id`` of the device module
event it starts, and the device's clock runs a millisecond or two off
the host's (``device_clock_lag_s``), too much to pair across.

Layout knowledge beyond ``trace.py``'s: a host annotation's arguments
are the event's own stats; a device op's scope path is the ``tf_op``
stat of its event METADATA (``jit(f)/decode_step/kv_gather/gather:``),
which ``jax.profiler.ProfileData`` does not expose — so the file is
parsed with ``google.protobuf`` from a descriptor of the few XPlane
fields used here. No JAX: the parent of a run may call this.

A program without annotations or scopes (the parent commit of ISSUE 25)
reduces to empty groups, and every reader on top returns ``None``.
"""

from __future__ import annotations

import bisect
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks import trace

PREFIXES = ("server.", "engine.", "train.")
# The names the program puts on the device timeline with
# ``jax.named_scope`` (infer/kvcache.py, infer/engine.py,
# models/llama.py, train/qlora.py, train/trainer.py). An op belongs to
# the INNERMOST of these on its scope path; other path components are
# JAX's own (``while``, ``body``, ``jvp(...)``, the primitive).
SCOPES = ("decode_step", "qkv_proj", "kv_gather", "attn_core", "kv_write",
          "out_ffn", "lm_head", "sample", "embed", "norm", "attn", "mlp",
          "xent", "base_matmul", "lora", "optimizer")
DECODE_MODULES = ("_decode", "_verify")
DISPATCH, FETCH = "engine.decode.dispatch", "engine.decode.fetch"
ENQUEUE = "DoEnqueueProgram"     # the runtime's host event, with run_id
MOSAIC = "tpu_custom_call"
_IDENT = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_DOT_N = re.compile(r"\.\d+$")

Annotation = Tuple[str, float, float, int, Dict[str, Any]]
# (name, start_s, end_s, host line, arguments)
Op = Tuple[str, float, float, str, str]
# (instruction, start_s, end_s, kind, scope path)


# ---------------------------------------------------------------------------
# The file
# ---------------------------------------------------------------------------

def _xspace_class():
    """Message class for the XPlane fields read here (tsl's
    ``xplane.proto``; field numbers are its wire contract)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmarks_spans_xplane.proto", package="bench_xplane",
        syntax="proto3")

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if tname:
                f.type_name = ".bench_xplane." + tname

    i64, u64, dbl = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    raw, sub = F.TYPE_BYTES, F.TYPE_MESSAGE
    msg("XStat", ("metadata_id", 1, i64, one, None),
        ("double_value", 2, dbl, one, None),
        ("uint64_value", 3, u64, one, None),
        ("int64_value", 4, i64, one, None),
        ("str_value", 5, raw, one, None),
        ("bytes_value", 6, raw, one, None),
        ("ref_value", 7, u64, one, None))
    msg("XEvent", ("metadata_id", 1, i64, one, None),
        ("offset_ps", 2, i64, one, None),
        ("duration_ps", 3, i64, one, None),
        ("stats", 4, sub, many, "XStat"))
    msg("XLine", ("id", 1, i64, one, None), ("name", 2, raw, one, None),
        ("timestamp_ns", 3, i64, one, None),
        ("events", 4, sub, many, "XEvent"))
    msg("XEventMetadata", ("id", 1, i64, one, None),
        ("name", 2, raw, one, None), ("stats", 5, sub, many, "XStat"))
    msg("XStatMetadata", ("id", 1, i64, one, None),
        ("name", 2, raw, one, None))
    msg("EventMetadataEntry", ("key", 1, i64, one, None),
        ("value", 2, sub, one, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, i64, one, None),
        ("value", 2, sub, one, "XStatMetadata"))
    msg("XPlane", ("id", 1, i64, one, None), ("name", 2, raw, one, None),
        ("lines", 3, sub, many, "XLine"),
        ("event_metadata", 4, sub, many, "EventMetadataEntry"),
        ("stat_metadata", 5, sub, many, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, sub, many, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def _stat_value(stat, stat_names: Dict[int, str]):
    """One XStat's value: the ``oneof`` member that is set (strings may
    be interned as a reference to a stat-metadata name)."""
    which = [f.name for f, _ in stat.ListFields() if f.name != "metadata_id"]
    if not which:
        return 0
    name = which[0]
    value = getattr(stat, name)
    if name == "ref_value":
        return stat_names.get(value, "")
    if name in ("str_value", "bytes_value"):
        return _text(value)
    return value


def _stat_named(event, name: str, stat_names: Dict[int, str]):
    for st in event.stats:
        if stat_names.get(st.metadata_id) == name:
            return _stat_value(st, stat_names)
    return None


def read_xspace(path: str) -> Dict[str, Any]:
    """``{"device": [{"ops": [Op], "modules": [(name, s, e, enqueued)]}],
    "annotations": [Annotation]}`` of one ``.xplane.pb``; ``enqueued``
    is when the host handed that launch to the device, on the host's
    clock (``None`` where the runtime left no such event)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    device: List[Dict[str, List[Tuple]]] = []
    annotations: List[Annotation] = []
    enqueued: Dict[int, float] = {}
    host_line = 0
    # Seconds from the earliest line's timestamp: nanoseconds since the
    # epoch do not fit a float's mantissa to the picosecond.
    origin_ns = min((line.timestamp_ns for plane in space.planes
                     for line in plane.lines), default=0)
    for plane in space.planes:
        pname = _text(plane.name)
        stat_names = {e.key: _text(e.value.name)
                      for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}

        def events(line):
            base = (line.timestamp_ns - origin_ns) * 1e-9
            for ev in line.events:
                s = base + ev.offset_ps * 1e-12
                yield ev, s, s + ev.duration_ps * 1e-12

        if pname.startswith("/device:TPU:") and " " not in pname:
            scope_of: Dict[int, Tuple[str, str, str]] = {}
            ops: List[Op] = []
            modules: List[Tuple] = []
            for line in plane.lines:
                lname = _text(line.name)
                if lname == "XLA Ops":
                    for ev, s, e in events(line):
                        known = scope_of.get(ev.metadata_id)
                        if known is None:
                            m = meta[ev.metadata_id]
                            short, kind = trace.op_name(_text(m.name))
                            path_ = ""
                            for st in m.stats:
                                if stat_names.get(st.metadata_id) \
                                        == "tf_op":
                                    path_ = str(_stat_value(st,
                                                            stat_names))
                            known = (short, kind, path_)
                            scope_of[ev.metadata_id] = known
                        ops.append((known[0], s, e, known[1], known[2]))
                elif lname == "XLA Modules":
                    for ev, s, e in events(line):
                        modules.append((trace.module_name(_text(
                            meta[ev.metadata_id].name)), s, e,
                            _stat_named(ev, "run_id", stat_names)))
            device.append({"ops": ops, "modules": modules})
        elif pname == "/host:CPU":
            for line in plane.lines:
                found = False
                for ev, s, e in events(line):
                    name = _text(meta[ev.metadata_id].name)
                    if name == ENQUEUE:
                        run = _stat_named(ev, "run_id", stat_names)
                        if run is not None:
                            enqueued[run] = s
                    if not name.startswith(PREFIXES):
                        continue
                    found = True
                    args = {stat_names.get(st.metadata_id, "?"):
                            _stat_value(st, stat_names)
                            for st in ev.stats}
                    annotations.append((name, s, e, host_line, args))
                host_line += found
    annotations.sort(key=lambda a: a[1])
    for plane_ in device:
        plane_["modules"] = [(name, s, e, enqueued.get(run))
                             for name, s, e, run in plane_["modules"]]
    return {"device": device, "annotations": annotations}


# ---------------------------------------------------------------------------
# The arithmetic, on plain tuples
# ---------------------------------------------------------------------------

def innermost_scope(path: str, scopes: Sequence[str] = SCOPES) -> str:
    """The innermost of the program's scopes on an op's scope path
    (``jit(step)/transpose(jvp(attn))/base_matmul/dot_general:`` ->
    ``base_matmul``); ``""`` when the path names none."""
    for part in reversed(path.split("/")):
        idents = _IDENT.findall(part)
        if idents and idents[-1] in scopes:
            return idents[-1]
    return ""


def kernel_name(instruction: str, path: str) -> str:
    """A Mosaic op's kernel: the ``name=`` of its ``pallas_call`` — the
    scope-path component before ``pallas_call`` — else the instruction's
    own name without its numeric suffix."""
    parts = path.rstrip(":").split("/")
    if len(parts) >= 2 and parts[-1] == "pallas_call":
        idents = _IDENT.findall(parts[-2])
        if idents:
            return idents[-1]
    return _DOT_N.sub("", instruction)


def _holder(starts: List[float], ends: List[float], t: float) -> int:
    """Index of the interval (sorted, disjoint) that holds ``t``, or -1."""
    i = bisect.bisect_right(starts, t) - 1
    return i if i >= 0 and t < ends[i] else -1


def group_ops(ops: Iterable[Op], modules: List[Tuple],
              scopes: Sequence[str] = SCOPES) -> Dict[str, Dict[str, Any]]:
    """Per XLA module: seconds and launches of its events, and the
    seconds of the ops that ran inside them by innermost scope and by
    kernel. Container ops (``while`` ...) span their bodies and are left
    out, as in ``trace.py``; an op outside every recorded module event
    (a launch cut by the trace's edge) is dropped."""
    mods = sorted(modules, key=lambda m: m[1])
    starts, ends = [m[1] for m in mods], [m[2] for m in mods]
    out: Dict[str, Dict[str, Any]] = {}
    for name, s, e, *_ in mods:
        g = out.setdefault(name, {"s": 0.0, "n": 0, "ops_s": 0.0,
                                  "scopes": {}, "kernels": {}})
        g["s"] += e - s
        g["n"] += 1
    for instr, s, e, kind, path in ops:
        if kind in trace.CONTAINERS:
            continue
        i = _holder(starts, ends, s)
        if i < 0:
            continue
        g = out[mods[i][0]]
        g["ops_s"] += e - s
        scope = innermost_scope(path, scopes)
        if scope:
            g["scopes"][scope] = g["scopes"].get(scope, 0.0) + (e - s)
        if kind == MOSAIC:
            k = kernel_name(instr, path)
            g["kernels"][k] = g["kernels"].get(k, 0.0) + (e - s)
    return out


def pair_decode(annotations: Iterable[Annotation],
                launches: List[Tuple[float, float, Optional[float]]]
                ) -> List[Dict[str, Any]]:
    """The decode bursts that lie whole inside the trace.

    ``launches``: ``(start, end, enqueued)`` of the decode module events
    of one device. Programs run in the order they were dispatched, and a
    launch is handed to the device after its dispatch began, so: walk
    the launches in order beside the dispatch annotations in order; a
    launch enqueued before the next unpaired dispatch began belongs to a
    dispatch from before the trace and is passed over, else the two are
    a pair. (Without the runtime's enqueue event the launch's own start
    stands in, on the device's clock.) A burst counts when its fetch and
    all ``parts`` of its dispatches, each with its launch, are there."""
    dispatches = sorted((a for a in annotations if a[0] == DISPATCH),
                        key=lambda a: a[1])
    fetches = {int(a[4].get("seq", -1)): a
               for a in annotations if a[0] == FETCH}
    by_seq: Dict[int, List[Tuple[Annotation, Tuple[float, float]]]] = {}
    n_disp: Dict[int, int] = {}
    for d in dispatches:
        seq = int(d[4].get("seq", -1))
        n_disp[seq] = n_disp.get(seq, 0) + 1
    j = 0
    for s, e, enq in sorted(launches, key=lambda l: (
            l[2] if l[2] is not None else l[0])):
        if j >= len(dispatches):
            break
        if (enq if enq is not None else s) < dispatches[j][1]:
            continue
        d = dispatches[j]
        by_seq.setdefault(int(d[4].get("seq", -1)), []).append((d, (s, e)))
        j += 1
    out = []
    for seq in sorted(by_seq):
        f = fetches.get(seq)
        parts = int(f[4].get("parts", 0)) if f else 0
        if not parts or n_disp[seq] != parts \
                or len(by_seq[seq]) != parts:
            continue
        out.append({"seq": seq, "fetch": f,
                    "dispatches": [d for d, _ in by_seq[seq]],
                    "launches": [l for _, l in by_seq[seq]]})
    return out


def sum_args(events: Iterable[Annotation], *args: str) -> float:
    """Sum over events of the product of the named arguments; an event
    that lacks one of them is skipped."""
    total = 0.0
    for a in events:
        v = 1.0
        for name in args:
            if name not in a[4]:
                break
            v *= float(a[4][name])
        else:
            total += v
    return total


def steps_on_device(ops: Iterable[Op], launches: List[Tuple[float, float]],
                    scopes: Sequence[str] = ("lm_head", "sample")) -> int:
    """Decode steps counted on the device alone: every iteration of the
    burst scan (scope ``decode_step``) runs the head and samples once,
    outside the scan over layers, so inside each launch the least often
    seen op of those scopes ran once a step. (On the v5e the sampling
    ops fuse into the head's; either scope will do.)"""
    spans = sorted(launches)
    starts, ends = [s for s, _ in spans], [e for _, e in spans]
    counts: List[Dict[str, int]] = [{} for _ in spans]
    for instr, s, e, kind, path in ops:
        if kind in trace.CONTAINERS \
                or innermost_scope(path) not in scopes:
            continue
        i = _holder(starts, ends, s)
        if i >= 0:
            counts[i][instr] = counts[i].get(instr, 0) + 1
    return sum(min(c.values()) for c in counts if c)


def reduce_events(device: List[Dict[str, List[Tuple]]],
                  annotations: List[Annotation]) -> Dict[str, Any]:
    """Everything the readers take, as plain JSON-able values."""
    first = device[0] if device else {"ops": [], "modules": []}
    modules = group_ops(first["ops"], first["modules"])
    launches = [(s, e, enq) for name, s, e, enq in first["modules"]
                if any(p in name for p in DECODE_MODULES)]
    bursts = pair_decode(annotations, launches)
    # How far the device's clock runs behind the host's, at least: no
    # launch can start before the host enqueued it.
    lag = max((enq - s for _, s, _, enq in first["modules"]
               if enq is not None), default=None)
    disp = [d for b in bursts for d in b["dispatches"]]
    counted = [l for b in bursts for l in b["launches"]]
    by_why: Dict[str, Dict[str, float]] = {}
    by_program: Dict[str, Dict[str, float]] = {}
    for b in bursts:
        for d, (s, e) in zip(b["dispatches"], b["launches"]):
            k = int(d[4].get("k", 0))
            w = by_why.setdefault(str(d[4].get("why", "")),
                                  {"launches": 0, "steps": 0})
            w["launches"] += 1
            w["steps"] += k
            p = by_program.setdefault(
                f"k={k} span={d[4].get('span')}",
                {"launches": 0, "steps": 0, "device_s": 0.0, "slots": 0})
            p["launches"] += 1
            p["steps"] += k
            p["device_s"] += e - s
            p["slots"] += int(d[4].get("slots", 0))
    phases: Dict[str, Dict[str, float]] = {}
    for name, s, e, _, _ in annotations:
        p = phases.setdefault(name, {"n": 0, "s": 0.0})
        p["n"] += 1
        p["s"] += e - s
    return {
        "platform": "tpu" if device else "cpu",
        "device_clock_lag_s": lag,
        "host_lines": len({a[3] for a in annotations}),
        "phases": phases,
        "annotations": [[n, s, e - s, line, args]
                        for n, s, e, line, args in annotations],
        "modules": modules,
        "decode": {
            "bursts": len(bursts), "launches": len(counted),
            "launches_seen": len(launches),
            "device_s": sum(e - s for s, e in counted),
            "steps": sum_args(disp, "k"),
            "row_steps": sum_args(disp, "k", "rows"),
            "live_row_steps": sum_args(disp, "k", "slots"),
            "tokens": sum_args((b["fetch"] for b in bursts), "tokens"),
            "retired": sum_args((b["fetch"] for b in bursts), "retired"),
            "by_why": by_why, "by_program": by_program,
            "steps_on_device": steps_on_device(first["ops"], counted),
            "seqs": [b["seq"] for b in bursts]},
    }


def reduce_xplane(path: str) -> Dict[str, Any]:
    got = read_xspace(path)
    return reduce_events(got["device"], got["annotations"])


# ---------------------------------------------------------------------------
# For the readers
# ---------------------------------------------------------------------------

def load(facts: Dict[str, Any], ctx: Dict[str, Any]
         ) -> Optional[Dict[str, Any]]:
    """The reduction of this run's trace, made once and kept in the
    run's output directory; ``None`` where there is no trace file, no
    ``google.protobuf`` to read it with, or no device plane in it (a CPU
    rehearsal: no number from it may stand under a device metric)."""
    red = facts.get("trace") or {}
    path = red.get("file")
    if not path or not os.path.isfile(path):
        return None
    cache = os.path.join(ctx["out_dir"], "spans_reduced.json")
    if os.path.isfile(cache) \
            and os.path.getmtime(cache) >= os.path.getmtime(path):
        with open(cache) as f:
            out = json.load(f)
    else:
        try:
            out = reduce_xplane(path)
        except ImportError:
            return None
        with open(cache, "w") as f:
            json.dump(out, f)
    return out if out.get("platform") == "tpu" else None


def annotations_named(red: Dict[str, Any], name: str,
                      counted_decode_only: bool = False
                      ) -> List[Annotation]:
    """The reduction's annotations of one name, as ``Annotation``
    tuples; with ``counted_decode_only`` only those of decode bursts
    that lie whole inside the trace."""
    seqs = set(red["decode"]["seqs"])
    out = []
    for n, s, d, line, args in red["annotations"]:
        if n != name:
            continue
        if counted_decode_only and args.get("seq") not in seqs:
            continue
        out.append((n, s, s + d, line, args))
    return out


def module_groups(red: Dict[str, Any], patterns: Sequence[str]
                  ) -> List[Dict[str, Any]]:
    pats = [re.compile(p) for p in patterns]
    return [g for name, g in red["modules"].items()
            if any(p.search(name) for p in pats)]

