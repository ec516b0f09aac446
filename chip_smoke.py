#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the entry points a user is told to run, once each, at full model
width on ONE TPU v5e chip, and checks what comes out:

* ``serve``  — ``python -m skypilot_tpu.infer.server`` with the flags of
  ``llm/serve-8b-w8a8.yaml`` (llama3-8b: all 32 layers, every published
  Llama-3.1-8B width, int8 weights + int8 KV, 32 slots, random weights).
  Six ``/generate`` requests made from ``--seed``: two prompts of 384-512
  tokens (the batched-wave prefill) and four of 768-1024 (chunked
  prefill at 512), 32 new tokens each, three blocking and three
  streamed, the last long prompt sent twice so its second answer is a
  prefix-cache hit.
* ``train``  — ``python -m skypilot_tpu.train.run`` twice: the 8B QLoRA
  recipe of ``llm/qlora-8b.yaml`` cut to 3 steps, then full training of
  llama3-400m (every parameter updated, float32 AdamW) for 4 steps.
* ``launch`` — the framework's front door: ``skypilot_tpu.client.cli
  launch`` of a one-host task on the local cloud whose ``run`` is a
  2-step llama3-400m ``train.run``, then ``logs`` and ``down``. The
  job's own log must name a TPU device.

``--chips 4`` runs ONLY what exists only across chips, and what it is
compared with: llama3-400m trained under ``--tp 2`` (mesh fsdp 2 x tp 2)
against the same steps in a child that sees one chip; llama3-1b under
``--tp 2`` (its float32 AdamW state fits no single chip); llama3-8b in
bf16 served under ``--tp 4``; and llama3-1b served under ``--tp 4`` and
``--tp 1`` on the same requests.

The chip belongs to one process at a time, so this parent NEVER imports
JAX (asserted before every spawn): each phase is one child started the
way a user starts it, one after another. Every child reports the device
it opened; anything but ``tpu`` kills the child, fails the phase and ends
the run — nothing here runs a smaller model on a CPU instead.

stdout: one JSON object per phase, then as the LAST line exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
built from the children's reports. Exit code 0 only if every phase
passed. Child logs go to ``--out`` (default ``chiprun_out/chip_smoke``).

Budget: the whole default run must finish inside 1200 s with a cold
compile cache (``--deadline``, default 1150 s, is enforced across the
phases); measured times are in CHANGES.md. The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Stdlib-only modules of the package (importing them never imports JAX).
from skypilot_tpu.observability import metrics as metrics_lib  # noqa: E402
from skypilot_tpu.utils import compile_cache  # noqa: E402

# The only platform a child may report. tests/test_chip_compile.py
# rehearses the control flow on the CPU by passing "cpu" to run() with
# tiny plans; the command line has no way to change it.
REQUIRED_PLATFORM = "tpu"

PY = sys.executable
SERVER = [PY, "-m", "skypilot_tpu.infer.server"]
TRAIN = [PY, "-m", "skypilot_tpu.train.run"]
CLI = [PY, "-m", "skypilot_tpu.client.cli"]

# First-loss agreement between the tp-sharded run and the one-chip run
# of the same seed and batch. Both compute the same float32 mean of
# per-token cross-entropies (~ln 32768 = 10.4) from bf16 matmuls; tensor
# parallelism splits each contraction across two chips, so partial sums
# round in a different order. That moves single logits by ~1e-2 but the
# mean over 16k tokens by well under 1e-2. A mis-sharded weight does not
# hide in that band: it changes every logit of every token.
TP_LOSS_TOL = 0.02
# Per-device bytes after the state / the weights + cache are built:
# (max - min) / mean. Sharded trees are level to within a few percent
# (replicated norms and scalars are tiny); a tree built on device 0 and
# resharded later shows as a multiple.
BALANCE_BAND = 0.2
# Tokens of each answer compared between two runs of the same prompt.
# Random weights leave near-ties in the logits and a different summation
# order may flip one (ROADMAP D0): a divergence after the first token is
# printed with its position and tolerated.
COMPARE_TOKENS = 8
# Between two LAYOUTS (tp 4 vs tp 1) even a first token can sit on such
# a tie: tensor parallelism rounds each layer's partial sums to bf16
# before adding them, which moves a logit by ~1e-2 of its spread, and
# the gap between the top two of 128k random logits is below that for a
# few percent of tokens. So one or two of the six answers may disagree
# at token 0 and are printed; a mis-sharded model disagrees on ALL of
# them (a chance match is 1 in 128k), and more than this many fails.
MAX_FIRST_TOKEN_FLIPS = 2

PLAN_ONE_CHIP: Dict[str, Any] = {
    "serve": {
        # llm/serve-8b-w8a8.yaml:31-35
        "args": ["--config", "llama3-8b", "--weights-int8", "--kv-int8",
                 "--slots", "32", "--max-len", "1280", "--max-burst", "32",
                 "--open-burst", "4", "--admit-wave", "4"],
        "vocab": 128_256, "short": (384, 512), "long": (768, 1024),
        "new_tokens": 32,
    },
    "train": [
        # llm/qlora-8b.yaml, shortened
        {"name": "qlora-8b",
         "args": ["--config", "llama3-8b", "--qlora", "16",
                  "--qlora-random-base", "--xent-chunk", "512",
                  "--steps", "3", "--seq", "2048", "--batch", "2",
                  "--log-every", "1"]},
        # the largest config train.run holds with float32 AdamW on 16 GB
        {"name": "full-400m",
         "args": ["--config", "llama3-400m", "--steps", "4", "--seq", "2048",
                  "--batch", "6", "--log-every", "1"]},
    ],
    # Same shapes as full-400m above, so even a cold run finds this
    # program in the cache the train phase just filled.
    "launch": {"args": ["--config", "llama3-400m", "--steps", "2", "--seq",
                        "2048", "--batch", "6", "--log-every", "1"]},
}

_TRAIN_400M_TP = ["--config", "llama3-400m", "--steps", "3", "--seq", "2048",
                  "--batch", "8", "--log-every", "1"]
_SERVE_SHAPE = {"short": (384, 512), "long": (768, 1024), "new_tokens": 32}
PLAN_FOUR_CHIPS: Dict[str, Any] = {
    "train_tp": {
        "sharded": {"name": "400m-tp2", "args": _TRAIN_400M_TP + ["--tp", "2"]},
        "one_chip": {"name": "400m-1chip", "args": _TRAIN_400M_TP},
        # ~6 GB of float32 AdamW state a chip: trains only across chips.
        "big": {"name": "1b-tp2",
                "args": ["--config", "llama3-1b", "--tp", "2", "--steps", "3",
                         "--seq", "2048", "--log-every", "1"]},
    },
    "serve_tp": {
        # 16 GB of bf16 weights: exists only across chips.
        "big": dict(_SERVE_SHAPE, name="8b-bf16-tp4", vocab=128_256, tp=4,
                    args=["--config", "llama3-8b", "--tp", "4", "--slots",
                          "32", "--max-len", "1280"]),
        "sharded": dict(_SERVE_SHAPE, name="1b-tp4", vocab=128_256, tp=4,
                        args=["--config", "llama3-1b", "--tp", "4", "--slots",
                              "32", "--max-len", "1280"]),
        "one_chip": dict(_SERVE_SHAPE, name="1b-tp1", vocab=128_256, tp=1,
                         args=["--config", "llama3-1b", "--tp", "1",
                               "--slots", "32", "--max-len", "1280"]),
    },
}

# What makes a JAX child on a multi-chip host open exactly one chip
# (libtpu reads these; the programs have no option for it).
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}


def _parent_is_off_jax() -> bool:
    """One process holds the chip at a time: a parent that has touched
    JAX would hold it against every child. (The CPU rehearsal in
    tests/test_chip_compile.py runs inside pytest, which has imported
    JAX, and stubs this.)"""
    return "jax" not in sys.modules


class PhaseFailed(Exception):
    """A check of the phase did not hold; the message says which."""


class WrongDevice(PhaseFailed):
    """A child opened something other than REQUIRED_PLATFORM: the run
    ends here (every later phase would open the same thing)."""


class Run:
    """State shared by the phases of one invocation: where logs go, the
    deadline, the required platform, every device report seen, and
    every child started (so all of them can be stopped at the end)."""

    def __init__(self, out_dir: str, seed: int, deadline_s: float,
                 platform: str, four_chips: bool = False, emit=print):
        self.out_dir = out_dir
        self.seed = seed
        self.platform = platform
        self.t_end = time.monotonic() + deadline_s
        self.cache_dir = compile_cache.configure()
        self.devices: List[dict] = []
        self.children: List["Child"] = []
        self.four_chips = four_chips
        self.emit = emit
        self.ok = True
        self.wrong_device = False
        os.makedirs(out_dir, exist_ok=True)

    def new_record(self, phase: str, argv: List[str]) -> Dict[str, Any]:
        return {"phase": phase, "argv": argv, "ok": False,
                "cache_dir": self.cache_dir,
                "cache_warm": compile_cache.is_warm(self.cache_dir)}

    def report(self, rec: dict) -> bool:
        """Print a phase's line (everything but the raw answers) and
        fold its verdict into the run's. Returns the verdict."""
        self.ok = self.ok and rec["ok"]
        self.emit(json.dumps({k: v for k, v in rec.items()
                              if k != "answers"}))
        return rec["ok"]

    def fail(self, rec: dict, e: Exception) -> None:
        rec["ok"] = False
        rec["error"] = str(e)
        if isinstance(e, WrongDevice):
            self.wrong_device = True

    def remaining(self, cap: float) -> float:
        left = self.t_end - time.monotonic()
        if left <= 0:
            raise PhaseFailed("the run's deadline passed")
        return min(cap, left)

    def env(self, extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env["PYTHONUNBUFFERED"] = "1"
        env.update(extra or {})
        return env

    def check_device(self, device: Optional[dict], want_count: int) -> dict:
        """Record a child's device report; refuse anything but the
        required platform at the expected device count."""
        if not isinstance(device, dict) or "platform" not in device:
            raise PhaseFailed(f"child reported no device: {device!r}")
        self.devices.append(device)
        if device["platform"] != self.platform:
            raise WrongDevice(
                f"child opened {device['platform']!r} "
                f"({device.get('device_kind')!r}), not {self.platform!r}")
        if device.get("count") != want_count:
            raise PhaseFailed(f"child sees {device.get('count')} devices, "
                              f"expected {want_count}")
        return device

    def spawn(self, name: str, argv: Sequence[str],
              env_extra: Optional[Dict[str, str]] = None,
              cwd: Optional[str] = None) -> "Child":
        assert _parent_is_off_jax(), "chip_smoke's parent imported jax"
        child = Child(name, list(argv), self.env(env_extra),
                      os.path.join(self.out_dir, name), cwd or REPO)
        self.children.append(child)
        return child

    def stop_all(self) -> None:
        for child in self.children:
            child.stop()


class Child:
    """One child process: its own session (so the whole group can be
    stopped), stdout and stderr read by threads into memory and into
    ``<log_base>.out`` / ``.err``, with the time each line arrived."""

    def __init__(self, name: str, argv: List[str], env: Dict[str, str],
                 log_base: str, cwd: str):
        self.name = name
        self.argv = argv
        self.t0 = time.monotonic()
        self.lines: Dict[str, List[tuple]] = {"out": [], "err": []}
        self._lock = threading.Lock()
        self.proc = subprocess.Popen(
            argv, env=env, cwd=cwd, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, stdin=subprocess.DEVNULL, text=True,
            errors="replace", start_new_session=True)
        self._threads = [
            threading.Thread(target=self._pump, daemon=True,
                             args=(self.proc.stdout, "out", log_base + ".out")),
            threading.Thread(target=self._pump, daemon=True,
                             args=(self.proc.stderr, "err", log_base + ".err")),
        ]
        for t in self._threads:
            t.start()

    def _pump(self, pipe, key: str, path: str) -> None:
        with open(path, "w") as log:
            for line in pipe:
                stamp = time.monotonic() - self.t0
                log.write(line)
                log.flush()
                with self._lock:
                    self.lines[key].append((stamp, line.rstrip("\n")))

    def snapshot(self, key: str) -> List[tuple]:
        with self._lock:
            return list(self.lines[key])

    def text(self, key: str) -> str:
        return "\n".join(line for _, line in self.snapshot(key))

    def wait_for_line(self, key: str, pred, timeout: float):
        """First ``(stamp, line)`` of stream ``key`` satisfying ``pred``;
        raises if the child exits or the timeout passes first."""
        t_end = time.monotonic() + timeout
        seen = 0
        while True:
            lines = self.snapshot(key)
            for stamp, line in lines[seen:]:
                if pred(line):
                    return stamp, line
            seen = len(lines)
            if self.proc.poll() is not None:
                # drain what the pumps are still writing
                for t in self._threads:
                    t.join(timeout=5)
                for stamp, line in self.snapshot(key)[seen:]:
                    if pred(line):
                        return stamp, line
                raise PhaseFailed(
                    f"{self.name} exited with {self.proc.returncode} before "
                    f"the expected line; stderr ends: {self.tail('err')}")
            if time.monotonic() > t_end:
                raise PhaseFailed(f"{self.name}: no expected line in "
                                  f"{timeout:.0f}s; stderr ends: "
                                  f"{self.tail('err')}")
            time.sleep(0.1)

    def wait(self, timeout: float) -> int:
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{self.name} still running after "
                              f"{timeout:.0f}s; stderr ends: "
                              f"{self.tail('err')}") from None
        for t in self._threads:
            t.join(timeout=10)
        return self.proc.returncode

    def tail(self, key: str, n: int = 6) -> str:
        return " | ".join(line[-300:] for _, line in self.snapshot(key)[-n:])

    def stop(self, sig: int = signal.SIGTERM, grace: float = 20.0) -> None:
        """Signal the child's whole process group; SIGKILL what stays."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, sig)
            except ProcessLookupError:
                pass
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)   # stragglers
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        for t in self._threads:
            t.join(timeout=10)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_prompts(seed: int, vocab: int, short: tuple, long: tuple
                 ) -> List[List[int]]:
    """Two short prompts and three long ones, from ``seed``."""
    rng = random.Random(seed)
    lens = [rng.randint(*short) for _ in range(2)] + \
           [rng.randint(*long) for _ in range(3)]
    return [[rng.randrange(1, vocab) for _ in range(n)] for n in lens]


def make_requests(prompts: List[List[int]]) -> List[dict]:
    """The six requests, in the order sent: three blocking and three
    streamed; the third long prompt goes twice, the repeat last and
    streamed (its trailer carries ``cache_hit``)."""
    s0, s1, l0, l1, l2 = range(5)
    order = [(s0, False), (l0, False), (s1, True), (l1, True),
             (l2, False), (l2, True)]
    return [{"prompt": i, "tokens": prompts[i], "stream": stream}
            for i, stream in order]


def _post_generate(port: int, tokens: List[int], new_tokens: int,
                   stream: bool, timeout: float) -> dict:
    """One /generate call. Returns status, the answer's tokens, its
    trailer fields, and (streamed) when the first and last line came."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    body = json.dumps({"tokens": tokens, "max_new_tokens": new_tokens,
                       "stream": stream})
    t0 = time.monotonic()
    try:
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json",
                              "Connection": "close"})
        resp = conn.getresponse()
        out: Dict[str, Any] = {"status": resp.status, "stream": stream,
                               "prompt_len": len(tokens)}
        if resp.status != 200:
            out["body"] = resp.read(2000).decode(errors="replace")
            return out
        if not stream:
            ans = json.loads(resp.read())
            out.update(tokens=ans["tokens"], cache_hit=ans.get("cache_hit"),
                       ttft_ms=ans.get("ttft_ms"),
                       prefill_chunks=ans.get("prefill_chunks"))
        else:
            toks: List[int] = []
            stamps: List[float] = []
            trailer: dict = {}
            while True:
                line = resp.readline()
                if not line:
                    break
                stamps.append(time.monotonic() - t0)
                rec = json.loads(line)
                if "error" in rec:
                    out["error"] = rec
                    break
                toks.extend(rec.get("tokens", []))
                if rec.get("done"):
                    trailer = rec
            # (read to the end: closing on unread bytes resets the
            # connection under the server's handler thread)
            out.update(tokens=toks, cache_hit=trailer.get("cache_hit"),
                       ttft_ms=trailer.get("ttft_ms"),
                       n_tokens=trailer.get("n_tokens"), lines=len(stamps),
                       first_line_s=stamps[0] if stamps else None,
                       last_line_s=stamps[-1] if stamps else None)
        out["wall_s"] = round(time.monotonic() - t0, 3)
        return out
    finally:
        conn.close()


def _get(port: int, path: str, timeout: float = 10.0) -> tuple:
    """(status, body text) of one GET."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path, headers={"Connection": "close"})
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _first_divergence(a: List[int], b: List[int], n: Optional[int] = None
                      ) -> Optional[int]:
    """Index of the first differing token among the first ``n``
    (None = the whole answers), or None when they agree."""
    a, b = (a, b) if n is None else (a[:n], b[:n])
    if len(a) != len(b):
        return min(len(a), len(b))
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)


def _metric_samples(families: dict, name: str) -> List[tuple]:
    return families.get(name, {}).get("samples", [])


def _balance(values: List[Optional[int]]) -> Optional[float]:
    """(max - min) / mean of per-device bytes; None if not reported."""
    if not values or any(v is None for v in values):
        return None
    mean = sum(values) / len(values)
    return (max(values) - min(values)) / mean if mean else None


def serve_once(run: Run, name: str, spec: dict) -> dict:
    """Start one server, wait until it reports its device and is ready,
    send the six requests, read /metrics, stop it. Returns the phase
    record; the first check that does not hold ends the phase and is
    the record's ``error``."""
    want_devices = 4 if run.four_chips else 1
    port = _free_port()
    argv = SERVER + spec["args"] + ["--port", str(port)]
    rec = run.new_record(name, argv)
    t0 = time.monotonic()
    child = run.spawn(name, argv)
    try:
        # The listening event is the earliest device report: read it
        # before waiting out the warm-up, so a CPU is refused at once.
        _, line = child.wait_for_line(
            "err", lambda s: '"server.listening"' in s, run.remaining(900))
        run.check_device(json.loads(line)["attrs"].get("device"),
                         want_devices)
        while True:
            run.remaining(900)
            if child.proc.poll() is not None:
                raise PhaseFailed(f"server exited with "
                                  f"{child.proc.returncode}: "
                                  f"{child.tail('err')}")
            try:
                status, body = _get(port, "/health")
            except (OSError, http.client.HTTPException):
                status = None
            if status == 200:
                break
            time.sleep(0.5)
        rec["ready_s"] = round(time.monotonic() - t0, 1)
        device = run.check_device(json.loads(body).get("device"),
                                  want_devices)

        prompts = make_prompts(run.seed, spec["vocab"], spec["short"],
                               spec["long"])
        answers = []
        for req in make_requests(prompts):
            ans = _post_generate(port, req["tokens"], spec["new_tokens"],
                                 req["stream"], run.remaining(600))
            ans["prompt"] = req["prompt"]
            answers.append(ans)
            if ans["status"] != 200 or "error" in ans:
                raise PhaseFailed(f"request {len(answers)} failed: {ans}")
            toks = ans["tokens"]
            if len(toks) != spec["new_tokens"]:
                raise PhaseFailed(f"request {len(answers)}: {len(toks)} "
                                  f"tokens, asked {spec['new_tokens']}")
            if not all(isinstance(t, int) and 0 <= t < spec["vocab"]
                       for t in toks):
                raise PhaseFailed(f"request {len(answers)}: token outside "
                                  f"the vocabulary: {toks}")
            if req["stream"]:
                if ans["n_tokens"] != spec["new_tokens"]:
                    raise PhaseFailed(f"request {len(answers)}: trailer "
                                      f"counts {ans['n_tokens']} tokens")
                if not (ans["lines"] >= 2
                        and ans["first_line_s"] < ans["last_line_s"]):
                    raise PhaseFailed(
                        f"request {len(answers)} did not stream: "
                        f"{ans['lines']} lines, first at "
                        f"{ans['first_line_s']}, last at "
                        f"{ans['last_line_s']}")
        rec["requests"] = [
            {k: a.get(k) for k in ("prompt", "prompt_len", "stream",
                                   "cache_hit", "ttft_ms", "wall_s",
                                   "prefill_chunks")}
            for a in answers]
        rec["answers"] = [a["tokens"] for a in answers]

        # The repeated prompt: a cache hit, and the same answer. The
        # cached prefill is bit-identical to the cold one (same chunk
        # program over the same rows), so the FIRST token must match;
        # later tokens come from decode bursts whose length the server
        # picks from wall-clock arrival windows, and a different burst
        # program may flip a near-tie of random weights (see
        # COMPARE_TOKENS).
        first, repeat = answers[-2], answers[-1]
        if repeat["cache_hit"] is not True:
            raise PhaseFailed(f"the repeated prompt was not a cache hit: "
                              f"{rec['requests'][-1]}")
        div = _first_divergence(first["tokens"], repeat["tokens"])
        rec["repeat_diverged_at"] = div
        if div is not None and div < COMPARE_TOKENS:
            raise PhaseFailed(
                f"the repeated prompt's answer diverges at token {div}: "
                f"{first['tokens']} vs {repeat['tokens']}")

        fam = metrics_lib.parse_exposition(_get(port, "/metrics")[1])
        codes = {lab.get("code"): int(v) for lab, v in _metric_samples(
            fam, "skytpu_http_requests_total")
            if lab.get("route") == "/generate"}
        finished = sum(v for _, v in _metric_samples(
            fam, "skytpu_requests_finished_total"))
        recoveries = sum(v for _, v in _metric_samples(
            fam, "skytpu_engine_recoveries_total"))
        rec["generate_codes"] = codes
        if codes != {"200": len(answers)} or finished != len(answers) \
                or recoveries:
            raise PhaseFailed(
                f"/metrics: /generate codes {codes}, finished {finished}, "
                f"engine recoveries {recoveries} (want {len(answers)} x 200)")
        chunks = sum(v for _, v in _metric_samples(
            fam, "skytpu_prefill_chunks_total"))
        waves = sum(v for _, v in _metric_samples(
            fam, "skytpu_prefill_requests_total"))
        if not chunks or not waves:
            raise PhaseFailed(f"a prefill path did not run: {chunks} chunks, "
                              f"{waves} wave-prefilled requests")
        rec["attention"] = sorted(
            "{impl}@{seq}x{heads}x{head_dim}".format(**lab)
            for lab, _ in _metric_samples(
                fam, "skytpu_attention_traced_total"))
        rec["programs"] = sorted(
            lab["program"] for lab, _ in _metric_samples(
                fam, "skytpu_compile_seconds")
            if lab.get("__name__", "").endswith("_count"))

        memory = json.loads(_get(port, "/health")[1])["device"]["memory"]
        rec["device"] = {k: device[k]
                         for k in ("platform", "device_kind", "count")}
        rec["peak_bytes_in_use"] = [m.get("peak_bytes_in_use")
                                    for m in memory]
        rec["bytes_in_use"] = [m.get("bytes_in_use") for m in memory]

        child.stop(signal.SIGTERM)
        if "Traceback (most recent call last)" in child.text("err"):
            raise PhaseFailed(f"the server's log has a traceback: "
                              f"{child.tail('err', 12)}")
        rec["ok"] = True
    except PhaseFailed as e:
        run.fail(rec, e)
    finally:
        child.stop()
        rec["wall_s"] = round(time.monotonic() - t0, 1)
    return rec


def phase_serve(run: Run, plan: dict) -> None:
    run.report(serve_once(run, "serve", plan["serve"]))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

_STEP_RE = re.compile(r"step (\d+)/(\d+) loss=(\S+)")


def _parse_summary(text: str) -> dict:
    """The last stdout line that is a JSON object."""
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise PhaseFailed(f"no JSON summary line on stdout: {text[-400:]!r}")


def check_train_log(err_lines: List[tuple], summary: dict, steps: int
                    ) -> dict:
    """Losses from the ``step i/N loss=`` lines: one per step, finite,
    the last not above the first. Returns what the record keeps."""
    found = [(stamp, _STEP_RE.search(line)) for stamp, line in err_lines]
    found = [(stamp, m) for stamp, m in found if m]
    losses = [float(m.group(3)) for _, m in found]
    if len(losses) != steps:
        raise PhaseFailed(f"{len(losses)} step lines, expected {steps}")
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise PhaseFailed(f"a loss is not finite: {losses}")
    if losses[-1] > losses[0]:
        raise PhaseFailed(f"the loss rose: {losses}")
    if summary.get("steps") != steps:
        raise PhaseFailed(f"summary counts {summary.get('steps')} steps")
    return {"losses": losses, "first_step_s": round(found[0][0], 1)}


def _check_mesh_line(run: Run, line: str) -> None:
    """``mesh: {...} over N devices (tpu: TPU v5 lite)`` — train.run's
    first stderr line names the platform and device kind it opened."""
    m = re.search(r"\((\w+): (.*)\)$", line)
    if not m:
        raise PhaseFailed(f"no device in train.run's mesh line: {line!r}")
    if m.group(1) != run.platform:
        run.devices.append({"platform": m.group(1),
                            "device_kind": m.group(2)})
        raise WrongDevice(f"train.run opened {m.group(1)!r} "
                          f"({m.group(2)!r}), not {run.platform!r}")


def train_once(run: Run, name: str, args: List[str], want_devices: int,
               env_extra: Optional[Dict[str, str]] = None) -> dict:
    argv = TRAIN + args
    rec = run.new_record(name, argv)
    t0 = time.monotonic()
    child = run.spawn(name, argv, env_extra)
    try:
        # train.run names its device on stderr before it builds
        # anything: a CPU is refused without waiting for 8B steps.
        _, line = child.wait_for_line(
            "err", lambda s: s.startswith("mesh: "), run.remaining(300))
        _check_mesh_line(run, line)
        code = child.wait(run.remaining(900))
        if code != 0:
            raise PhaseFailed(f"train.run exited with {code}: "
                              f"{child.tail('err', 10)}")
        summary = _parse_summary(child.text("out"))
        device = run.check_device(summary.get("device"), want_devices)
        steps = int(args[args.index("--steps") + 1])
        rec.update(check_train_log(child.snapshot("err"), summary, steps))
        rec.update(
            device={k: device[k]
                    for k in ("platform", "device_kind", "count")},
            attention=summary.get("attention"),
            mesh=summary.get("mesh"),
            tokens_per_sec_per_chip=summary.get("tokens_per_sec_per_chip"),
            state_bytes_in_use=summary.get("state_bytes_in_use"),
            peak_bytes_in_use=[mem.get("peak_bytes_in_use")
                               for mem in device["memory"]])
        rec["ok"] = True
    except PhaseFailed as e:
        run.fail(rec, e)
    finally:
        child.stop()
        rec["wall_s"] = round(time.monotonic() - t0, 1)
    return rec


def phase_train(run: Run, plan: dict) -> None:
    for job in plan["train"]:
        rec = train_once(run, "train:" + job["name"], job["args"], 1)
        if not run.report(rec) and run.wrong_device:
            return


# ---------------------------------------------------------------------------
# launch
# ---------------------------------------------------------------------------

def _cli(run: Run, name: str, args: List[str], home: str, timeout: float
         ) -> Child:
    child = run.spawn(name, CLI + args, {"SKYPILOT_TPU_HOME": home})
    child.wait(timeout)
    return child


def phase_launch(run: Run, plan: dict) -> None:
    """launch -> logs -> down of a one-host task on the local cloud,
    with a fresh SKYPILOT_TPU_HOME under the output directory."""
    home = os.path.join(run.out_dir, "launch_home")
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(home)
    task = os.path.join(run.out_dir, "launch_task.yaml")
    job = " ".join(TRAIN + plan["launch"]["args"])
    with open(task, "w") as f:
        f.write("name: smoke\nresources:\n  cloud: local\n"
                f"run: |\n  {job}\n")
    argv = CLI + ["launch", task, "-c", "smoke", "--cloud", "local"]
    rec = run.new_record("launch", argv)
    rec["run"] = job
    t0 = time.monotonic()
    try:
        child = _cli(run, "launch", argv[len(CLI):], home, run.remaining(900))
        if child.proc.returncode != 0:
            raise PhaseFailed(f"launch exited with {child.proc.returncode}: "
                              f"{child.tail('out')} {child.tail('err')}")
        logs = _cli(run, "launch-logs", ["logs", "smoke", "1", "--no-follow"],
                    home, run.remaining(120))
        # The job's own log, every line prefixed "(rank-0) ".
        body = [re.sub(r"^\(rank-\d+\) ", "", line)
                for _, line in logs.snapshot("out")]
        mesh = next((s for s in body if s.startswith("mesh: ")), "")
        _check_mesh_line(run, mesh)
        summary = _parse_summary("\n".join(body))
        device = run.check_device(summary.get("device"), 1)
        steps = int(plan["launch"]["args"][
            plan["launch"]["args"].index("--steps") + 1])
        rec.update(check_train_log([(0.0, s) for s in body], summary, steps))
        del rec["first_step_s"]     # the job's log carries no arrival times
        rec.update(job_log_device=mesh,
                   device={k: device[k]
                           for k in ("platform", "device_kind", "count")},
                   attention=summary.get("attention"),
                   peak_bytes_in_use=[mem.get("peak_bytes_in_use")
                                      for mem in device["memory"]])
        rec["ok"] = True
    except PhaseFailed as e:
        run.fail(rec, e)
    finally:
        # Tear the cluster down whatever happened above.
        try:
            down = _cli(run, "launch-down", ["down", "smoke"], home, 120)
            rec["down_exit"] = down.proc.returncode
            if rec["ok"] and down.proc.returncode != 0:
                run.fail(rec, PhaseFailed(
                    f"down exited with {down.proc.returncode}: "
                    f"{down.tail('err')}"))
        except PhaseFailed as e:
            run.fail(rec, e)
        rec["wall_s"] = round(time.monotonic() - t0, 1)
    run.report(rec)


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------

def _require_balanced(run: Run, rec: dict, key: str, n: int) -> None:
    """Per-device bytes level within BALANCE_BAND, else the record fails."""
    values = (rec.get(key) or [])[:n]
    spread = _balance(values)
    rec[key + "_spread"] = None if spread is None else round(spread, 4)
    if rec["ok"] and (spread is None or spread > BALANCE_BAND):
        run.fail(rec, PhaseFailed(
            f"per-device {key} {values}: spread {spread} > {BALANCE_BAND}"))


def phase_train_tp(run: Run, plan: dict) -> None:
    """(a) llama3-400m under --tp 2 on the four chips against the same
    steps on one chip; then llama3-1b, which trains only across chips."""
    p = plan["train_tp"]
    sharded = train_once(run, "train:" + p["sharded"]["name"],
                         p["sharded"]["args"], 4)
    _require_balanced(run, sharded, "state_bytes_in_use", 4)
    if not run.report(sharded):
        return
    one = train_once(run, "train:" + p["one_chip"]["name"],
                     p["one_chip"]["args"], 1, env_extra=ONE_CHIP_ENV)
    if one["ok"]:
        gap = abs(sharded["losses"][0] - one["losses"][0])
        one["first_loss_gap"] = round(gap, 5)
        if gap > TP_LOSS_TOL:
            run.fail(one, PhaseFailed(
                f"first loss {sharded['losses'][0]} under tp vs "
                f"{one['losses'][0]} on one chip: gap {gap:.4f} > "
                f"{TP_LOSS_TOL}"))
    if not run.report(one) and run.wrong_device:
        return
    big = train_once(run, "train:" + p["big"]["name"], p["big"]["args"], 4)
    _require_balanced(run, big, "state_bytes_in_use", 4)
    run.report(big)


def phase_serve_tp(run: Run, plan: dict) -> None:
    """(b) llama3-8b in bf16 under --tp 4; llama3-1b under --tp 4 and
    --tp 1 on the same six requests, answers compared."""
    p = plan["serve_tp"]
    big = serve_once(run, "serve:" + p["big"]["name"], p["big"])
    _require_balanced(run, big, "bytes_in_use", p["big"]["tp"])
    if not run.report(big) and run.wrong_device:
        return
    sharded = serve_once(run, "serve:" + p["sharded"]["name"], p["sharded"])
    _require_balanced(run, sharded, "bytes_in_use", p["sharded"]["tp"])
    if not run.report(sharded):
        return
    one = serve_once(run, "serve:" + p["one_chip"]["name"], p["one_chip"])
    if one["ok"]:
        divs = [_first_divergence(a, b, COMPARE_TOKENS)
                for a, b in zip(sharded["answers"], one["answers"])]
        one["tp4_vs_tp1_diverged_at"] = divs
        if divs.count(0) > MAX_FIRST_TOKEN_FLIPS:
            run.fail(one, PhaseFailed(
                f"tp 4 and tp 1 disagree from the first token in "
                f"{divs.count(0)} of {len(divs)} answers: divergence "
                f"positions {divs}"))
    run.report(one)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run(phases, plan: dict, out_dir: str, seed: int = 0,
        deadline_s: float = 1150.0, platform: str = REQUIRED_PLATFORM,
        four_chips: bool = False, emit=print) -> int:
    """Run ``phases`` (functions of (Run, plan) that report their own
    records) in order, then print the contract's last line. Returns
    the exit code."""
    state = Run(out_dir, seed, deadline_s, platform, four_chips, emit)
    try:
        for phase in phases:
            try:
                phase(state, plan)
            except PhaseFailed as e:     # outside any record (deadline)
                state.ok = False
                emit(json.dumps({"phase": phase.__name__, "ok": False,
                                 "error": str(e)}))
            if state.wrong_device:
                break
    finally:
        state.stop_all()
    # Built from the children's reports, never from a JAX import here:
    # the device every child opened (in a four-chip run, as the
    # children that saw all four report it).
    seen = state.devices
    if not seen or any(d.get("platform") != platform for d in seen):
        state.ok = False
    want_count = 4 if four_chips else 1
    full = [d for d in seen if d.get("platform") == platform
            and d.get("count") == want_count]
    last = (full or seen or [{}])[0]
    emit(json.dumps({"ok": state.ok, "device": {
        "platform": last.get("platform"), "kind": last.get("device_kind"),
        "count": last.get("count")}}))
    return 0 if state.ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the request prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the multi-chip phases and what they are "
                         "compared with (needs a four-chip host)")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for child logs and the launch phase's "
                         "SKYPILOT_TPU_HOME")
    ap.add_argument("--deadline", type=float, default=None,
                    help="seconds after which no phase starts or waits "
                         "(default 1150; 3300 with --chips 4)")
    args = ap.parse_args()
    if args.chips == 4:
        return run([phase_train_tp, phase_serve_tp], PLAN_FOUR_CHIPS,
                   args.out, args.seed, args.deadline or 3300.0,
                   four_chips=True)
    return run([phase_serve, phase_train, phase_launch], PLAN_ONE_CHIP,
               args.out, args.seed, args.deadline or 1150.0)


if __name__ == "__main__":
    sys.exit(main())
