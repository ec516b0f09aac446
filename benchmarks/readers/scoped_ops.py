"""Device time under ONE of the program's named scopes, read with the
metric's OWN list of scopes.

``spans.SCOPES`` is a closed tuple and the cached reduction
(``spans.load``) groups by it, so a scope a later model adds
(``moe_experts``) is invisible to ``scope_share``. This reader parses
the run's trace itself (``spans.read_xspace``) and groups with the list
its metric file gives (``scopes``: every name the program nests, so that
an op still counts under its INNERMOST scope and no outer scope swallows
``moe_experts`` nor is swallowed by it). ``ops`` (optional): patterns of
instruction names that belong to ``scope`` though their path names no
scope at all — the compiler's own kernels (``ragged-dot-none``: the
grouped expert products) keep no ``jax.named_scope`` of the call they
replace.

Two numbers, by the metric file's arguments:

* a share (no ``work``): seconds under ``scope`` over the seconds of the
  ``modules``' own events, in percent (``scope_share``'s definition);
* a roofline share (``work: "<module>.<function>"``): over the decode
  bursts that lie whole inside the trace (``spans.pair_decode``: the same
  launches ``decode_device_ms_per_step`` counts), the least time the chip
  could take for the work a program-step REQUIRES of the scope —
  ``fn(dims, rows)``, ``rows`` the mean live rows a step of those bursts,
  from the engine's dispatch annotations — over the device seconds under
  the scope a step, in percent.

A program without the scope (the parent of the PR that adds it), a trace
without a device plane, or no ``google.protobuf`` gives ``None``.
"""

from __future__ import annotations

import hashlib
import json
import os
import re

from benchmarks import flops, manifest, spans


def _reduce(path: str, scopes, scope, ops):
    got = spans.read_xspace(path)
    first = got["device"][0] if got["device"] else None
    if first is None:
        return {"platform": "cpu"}
    pats = [re.compile(p) for p in ops]
    if pats:
        first["ops"] = [
            (instr, s, e, kind,
             f"/{scope}/" if any(p.search(instr) for p in pats)
             and not spans.innermost_scope(path_, scopes) else path_)
            for instr, s, e, kind, path_ in first["ops"]]
    modules = spans.group_ops(first["ops"], first["modules"], scopes=scopes)
    launches = [(s, e, enq) for name, s, e, enq in first["modules"]
                if any(p in name for p in spans.DECODE_MODULES)]
    bursts = spans.pair_decode(got["annotations"], launches)
    counted = sorted(l for b in bursts for l in b["launches"])
    starts, ends = [s for s, _ in counted], [e for _, e in counted]
    in_bursts = {}
    for instr, s, e, kind, path_ in first["ops"]:
        if kind in spans.trace.CONTAINERS \
                or spans._holder(starts, ends, s) < 0:
            continue
        scope = spans.innermost_scope(path_, scopes)
        if scope:
            in_bursts[scope] = in_bursts.get(scope, 0.0) + (e - s)
    disp = [d for b in bursts for d in b["dispatches"]]
    return {"platform": "tpu", "modules": modules,
            "decode": {"scopes_s": in_bursts,
                       "device_s": sum(e - s for s, e in counted),
                       "steps": spans.sum_args(disp, "k"),
                       "live_row_steps": spans.sum_args(disp, "k", "slots")}}


def _load(facts, ctx, scopes, scope="", ops=()):
    path = (facts.get("trace") or {}).get("file")
    if not path or not os.path.isfile(path):
        return None
    tag = hashlib.sha1(",".join(
        list(scopes) + [scope] * bool(ops) + list(ops)).encode()
    ).hexdigest()[:12]
    cache = os.path.join(ctx["out_dir"], f"scoped_ops_{tag}.json")
    if os.path.isfile(cache) \
            and os.path.getmtime(cache) >= os.path.getmtime(path):
        with open(cache) as f:
            red = json.load(f)
    else:
        try:
            red = _reduce(path, tuple(scopes), scope, tuple(ops))
        except ImportError:
            return None
        with open(cache, "w") as f:
            json.dump(red, f)
    return red if red.get("platform") == "tpu" else None


def _device_kind(facts, ctx):
    """The chip the run was on: a training run's facts say; a serve
    run's do not, but its child's first line (``BENCH_DEVICE``, kept in
    the run's ``server.log``) does."""
    kind = (facts.get("device") or {}).get("kind")
    log = os.path.join(ctx["out_dir"], "server.log")
    if not kind and os.path.isfile(log):
        with open(log, errors="replace") as f:
            for line in f:
                if line.startswith("BENCH_DEVICE "):
                    return json.loads(line.split(" ", 1)[1]).get("kind")
    return kind


def read(facts, ctx, modules, scope, scopes, work=None, ops=()):
    red = _load(facts, ctx, scopes, scope, ops)
    if not red:
        return None
    if work is None:
        groups = spans.module_groups(red, modules)
        total = sum(g["s"] for g in groups)
        if total <= 0 or not any(scope in g["scopes"] for g in groups):
            return None
        return 100.0 * sum(g["scopes"].get(scope, 0.0)
                           for g in groups) / total
    d = red["decode"]
    seconds = d["scopes_s"].get(scope, 0.0)
    device = _device_kind(facts, ctx)
    if d["steps"] <= 0 or seconds <= 0 or not device:
        return None
    dims = manifest.load_family(ctx["config"], ctx["bench_dir"]).dims(
        ctx["config"])
    need = manifest.load_function(work, ctx["bench_dir"])(
        dims, d["live_row_steps"] / d["steps"])
    floor = flops.least_seconds(need, device)
    return 100.0 * floor["seconds"] / (seconds / d["steps"])
