"""Child processes of a run: the parent never imports JAX, so whatever
touches the chip (or reads a trace) runs in a child whose ``BENCH_`` lines
come back over a pipe."""

from __future__ import annotations

import json
import os
import queue
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from benchmarks import manifest


# The modules that run as children, by role. (The benchmark's tests put a
# broken stand-in here to see ``correct`` come out false.)
CHILDREN = {"serve": "benchmarks.children.serve_child",
            "reference_serve": "benchmarks.children.reference_serve",
            "train": "benchmarks.children.train_child",
            "reduce_trace": "benchmarks.children.reduce_trace"}


def child_argv(role: str) -> List[str]:
    return [sys.executable, "-m", CHILDREN[role]]


class Child:
    """A child process whose ``BENCH_`` lines arrive on a queue, each
    stamped with the monotonic time it was read."""

    def __init__(self, argv: List[str], env: Dict[str, str], log_path: str):
        self.log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            argv, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.log, cwd=manifest.ROOT)
        self.lines: "queue.Queue" = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for raw in self.proc.stdout:
            now = time.monotonic()
            self.log.write(raw)
            line = raw.decode("utf-8", "replace").rstrip("\n")
            if line.startswith("BENCH_"):
                tag, _, body = line.partition(" ")
                try:
                    self.lines.put((tag[6:], json.loads(body), now))
                except ValueError:
                    pass

    def command(self, text: str) -> None:
        try:
            self.proc.stdin.write(text.encode() + b"\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError):
            pass

    def expect(self, tag: str, timeout: float):
        """The next ``BENCH_<tag>`` line (others are kept aside)."""
        deadline = time.monotonic() + timeout
        kept = []
        try:
            while True:
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                try:
                    item = self.lines.get(timeout=min(left, 0.5))
                except queue.Empty:
                    if self.proc.poll() is not None:
                        return None
                    continue
                if item[0] == tag:
                    return item
                kept.append(item)
        finally:
            for item in kept:
                self.lines.put(item)

    def stop(self) -> None:
        """End the child and wait until it has ended."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=5)
        for stream in (self.proc.stdin, self.proc.stdout):
            try:
                stream.close()
            except OSError:
                pass
        self.log.close()


def child_env(ctx: Dict[str, Any]) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = manifest.ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if ctx["rehearse"]:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def reduce_trace(ctx, trace_dir: str, env) -> Optional[Dict[str, Any]]:
    out = os.path.join(ctx["out_dir"], "trace_reduced.json")
    env = dict(env, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        child_argv("reduce_trace") + [trace_dir, out], env=env,
        cwd=manifest.ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=300)
    if done.returncode != 0:
        sys.stderr.write(done.stdout.decode("utf-8", "replace")[-2000:])
        return None
    with open(out) as f:
        return json.load(f)
