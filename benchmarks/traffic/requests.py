"""The one general request generator: a mix's parameters + a seed ->
the requests a serve cell sends.

A mix (``traffic/<mix>.json``) gives

    rate_rps       mean arrivals per second, open loop (a cell overrides
                   it with its own fraction of its configuration's knee)
    arrivals       {"process": "poisson"} or {"process": "gamma", "cv": 3}
    prompt_tokens  a length distribution (below)
    output_tokens  a length distribution
    shared_prefix  optional {"pool": n, "tokens": <dist>, "share": 0..1}:
                   that share of requests start with one of ``pool``
                   prefixes (chosen Zipf-like, rank r with weight 1/r)
    lead_in_s      seconds of the same mix sent before the window opens,
                   so the window starts in steady state
    shape_seed     fixes the schedule (lengths, gaps, prefix picks) for
                   every ``--seed``

A length distribution is {"dist": "lognormal", "median", "sigma", "min",
"max"} or {"dist": "fixed", "value"} or {"dist": "uniform", "min", "max"}.

Every ``--seed`` replays the SAME schedule — the same lengths at the same
due times, fixed by ``shape_seed`` — with other token ids (and, in the
child, other weights). A window holds tens to hundreds of requests (56
of the long-prompt mix, 192 of the chat mix at their cells' rates, PR
37) that each live for a tenth of it or less, a tail is the slowest
dozen of them, and an arrival waits for whatever burst the server has in
flight and for a slot when all are taken, so another order of the same
arrivals is another experiment whose numbers differ by more than any
bound could admit: the seed changes what is computed, not when. What the driver's spread then
shows is the run-to-run noise of one schedule, not the variance across
arrivals; ``PERF.md`` says so. numpy only; never JAX.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List

import numpy as np

def _lengths(rng, spec: Dict[str, Any], n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    if dist == "lognormal":
        x = rng.lognormal(np.log(float(spec["median"])),
                          float(spec["sigma"]), n)
        return np.clip(np.rint(x), int(spec["min"]),
                       int(spec["max"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def _gaps(rng, spec: Dict[str, Any], n: int) -> np.ndarray:
    process = spec.get("process", "poisson")
    if process == "poisson":
        return rng.exponential(1.0, n)
    if process == "gamma":
        cv = float(spec["cv"])
        shape = 1.0 / (cv * cv)
        return rng.gamma(shape, 1.0 / shape, n)
    raise ValueError(f"unknown arrival process {process!r}")


def _shape_set(mix: Dict[str, Any], stream: int, n: int):
    """The fixed set of (prompt, output) lengths, prefix picks and gaps."""
    rng = np.random.default_rng([int(mix.get("shape_seed", 0)), stream])
    prompts = _lengths(rng, mix["prompt_tokens"], n)
    outputs = _lengths(rng, mix["output_tokens"], n)
    gaps = _gaps(rng, mix.get("arrivals") or {}, n)
    sp = mix.get("shared_prefix") or {}
    pick = np.full(n, -1, np.int64)
    if sp.get("share", 0) > 0:
        pool = int(sp["pool"])
        weights = 1.0 / np.arange(1, pool + 1)
        chosen = rng.choice(pool, n, p=weights / weights.sum())
        pick = np.where(rng.random(n) < float(sp["share"]), chosen, -1)
    return prompts, outputs, gaps, pick


def _body(tokens: List[int], max_new: int) -> bytes:
    return json.dumps({"tokens": tokens, "max_new_tokens": int(max_new),
                       "stream": True},
                      separators=(",", ":")).encode()


def _build(mix, rng, vocab_size, max_len, prompts, outputs, pick,
           prefixes) -> List[Dict[str, Any]]:
    out = []
    for p_len, o_len, k in zip(prompts, outputs, pick):
        p_len, o_len = int(p_len), int(o_len)
        head: List[int] = [] if k < 0 else prefixes[int(k)]
        p_len = max(p_len, len(head) + 1)
        if max_len:
            p_len = min(p_len, max_len - o_len)
        tail = rng.integers(1, vocab_size, p_len - len(head)).tolist()
        tokens = (head + tail)[:p_len]
        out.append({"prompt": tokens, "max_new": o_len,
                    "body": _body(tokens, o_len)})
    return out


def generate(mix: Dict[str, Any], seed: int, seconds: float,
             vocab_size: int, max_len: int = 0) -> Dict[str, Any]:
    """-> {"requests": [...]}; each request has ``prompt``, ``max_new``,
    ``body`` (the POST bytes), ``phase`` ("lead_in" | "window") and
    ``due_s`` relative to the opening of the window."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    sp = mix.get("shared_prefix") or {}
    prefixes: List[List[int]] = []
    if sp.get("share", 0) > 0:
        prng = np.random.default_rng([int(mix.get("shape_seed", 0)), 77])
        for n in _lengths(prng, sp["tokens"], int(sp["pool"])):
            prefixes.append(prng.integers(1, vocab_size, int(n)).tolist())
    lead_in = float(mix.get("lead_in_s", 0.0))
    rate = float(mix["rate_rps"])
    requests = []
    for stream, phase, span in ((1, "lead_in", lead_in),
                                (0, "window", float(seconds))):
        n = int(round(rate * span))
        if n <= 0:
            continue
        prompts, outputs, gaps, pick = _shape_set(mix, stream, n)
        # Scaled so that the n arrivals span exactly this phase.
        due = (np.cumsum(gaps) - gaps[0]) * (span / gaps.sum())
        if phase == "lead_in":
            due = due - span
        built = _build(mix, rng, vocab_size, max_len, prompts, outputs,
                       pick, prefixes)
        for r, t in zip(built, due):
            r.update(phase=phase, due_s=float(t))
        requests.extend(built)
    requests.sort(key=lambda r: r["due_s"])
    return {"requests": requests}
