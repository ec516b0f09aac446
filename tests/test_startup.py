"""Start-up on the record (ISSUE 40): the compile ledger fed by
``jax.monitoring``, the compile watch's split of a first dispatch, the
start-up phases and the ``startup`` record ``server.listening`` and
``train.ready`` carry — on the CPU, so counts and sums, never a time."""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.observability import flight as fl
from skypilot_tpu.observability import metrics as metrics_lib
from skypilot_tpu.observability import tracing
from skypilot_tpu.utils import timeline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("trace_s", "lower_s", "compile_s", "load_s")


def _compiled_since(n):
    return fl.COMPILES.records()[n:]


def _toy(scale):
    """A jitted function no other test compiles (the constant is in its
    HLO), calling an inner jit so that JAX's trace events nest."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) * scale

    def f(x):
        for _ in range(6):
            x = inner(x) + jnp.tanh(x @ x)
        return x

    return jax.jit(f)


# ---------------------------------------------------------------------------
# The ledger.

def test_ledger_keeps_one_record_a_compile_and_none_a_dispatch():
    assert fl.COMPILES.install() and fl.COMPILES.install()   # idempotent
    f = _toy(2.03125)
    x = jnp.ones((16, 16))
    jax.block_until_ready(x)
    n = len(fl.COMPILES.records())
    before = fl.COMPILES.totals()
    f(x).block_until_ready()
    (rec,) = [r for r in _compiled_since(n) if r["fun_name"] == "jit(f)"]
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert rec["compile_s"] + rec["load_s"] > 0
    after = fl.COMPILES.totals()
    assert after["functions"] >= before["functions"] + 1
    for stage in ("trace_s", "lower_s"):
        assert after[stage] > before[stage]
    # a dispatch of what is compiled fires no listener
    n = len(fl.COMPILES.records())
    f(x).block_until_ready()
    assert _compiled_since(n) == []
    assert fl.COMPILES.totals() == after


def test_each_compile_is_one_echoed_event(capfd):
    f = _toy(2.0625)
    capfd.readouterr()
    f(jnp.ones((16, 16))).block_until_ready()
    lines = [json.loads(line) for line in capfd.readouterr().err.splitlines()
             if line.startswith('{"kind": "event"')]
    mine = [e for e in lines if e["name"] == "program.compiled"
            and e["attrs"]["fun_name"] == "jit(f)"]
    assert len(mine) == 1
    assert set(mine[0]["attrs"]) == {"fun_name", "trace_s", "lower_s",
                                     "compile_s", "load_s", "cache_hit"}


def test_a_cache_hit_is_a_load_and_no_miss(tmp_path):
    from jax._src import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    try:
        jax.config.update(names[0], str(tmp_path))
        jax.config.update(names[1], 0.0)
        jax.config.update(names[2], 0)
        cc.reset_cache()
        x = jnp.ones((16, 16))
        n = len(fl.COMPILES.records())
        _toy(2.09375)(x).block_until_ready()
        (cold,) = [r for r in _compiled_since(n)
                   if r["fun_name"] == "jit(f)"]
        assert cold["cache_hit"] is False and cold["load_s"] == 0.0
        assert cold["compile_s"] > 0
        misses = fl.COMPILES.totals()["cache_misses"]
        hits = fl.COMPILES.totals()["cache_hits"]
        jax.clear_caches()
        n = len(fl.COMPILES.records())
        _toy(2.09375)(x).block_until_ready()
        (warm,) = [r for r in _compiled_since(n)
                   if r["fun_name"] == "jit(f)"]
        assert warm["cache_hit"] is True and warm["load_s"] > 0
        assert warm["compile_s"] >= 0 and warm["trace_s"] > 0
        assert fl.COMPILES.totals()["cache_misses"] == misses
        assert fl.COMPILES.totals()["cache_hits"] > hits
    finally:
        for n, v in old.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_nested_traces_are_not_counted_twice():
    """JAX times a function's tracing round the tracing of each jitted
    function it calls: summed as they come, the events of one compile
    exceed its wall many times over."""
    @jax.jit
    def inner(x):
        return jnp.cos(x) * 1.015625

    def deep(x):
        for _ in range(40):
            x = inner(x) + 1.0
        return x

    before = fl.COMPILES.thread_totals()
    t0 = time.monotonic()
    jax.jit(deep)(jnp.ones((8,))).block_until_ready()
    wall = time.monotonic() - t0
    after = fl.COMPILES.thread_totals()
    gained = sum(after[s] - before[s] for s in STAGES)
    assert 0 < gained <= wall + 1e-6


def test_counters_follow_the_ledger_and_wait_out_a_suppression():
    def stage_seconds():
        snap = metrics_lib.REGISTRY.snapshot()
        return sum(s["value"] for s in
                   snap["skytpu_compile_stage_seconds_total"]["samples"])

    fl.COMPILES.publish()
    t = fl.COMPILES.totals()
    assert stage_seconds() == pytest.approx(sum(t[s] for s in STAGES))
    with metrics_lib.suppress():
        _toy(2.15625)(jnp.ones((16, 16))).block_until_ready()
        held = stage_seconds()
    t = fl.COMPILES.totals()
    assert held < sum(t[s] for s in STAGES)      # discarded inside ...
    fl.COMPILES.publish()                        # ... carried outside
    assert stage_seconds() == pytest.approx(sum(t[s] for s in STAGES))


# ---------------------------------------------------------------------------
# The compile watch's split.

def test_watch_split_sums_to_its_first_dispatch_wall():
    watch = fl.CompileWatch()
    f = watch.wrap("toy", _toy(2.21875))
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    f(x).block_until_ready()
    (key,) = watch.summary()
    wall = watch.summary()[key]
    split = watch.splits()[key]
    assert set(split) == set(STAGES) | {"execute_s", "cache_hit"}
    staged = sum(split[s] for s in STAGES)
    assert 0 < staged <= wall + 1e-6        # no second counted twice
    assert staged + split["execute_s"] == pytest.approx(wall, abs=1e-6)
    assert split["trace_s"] > 0 and split["lower_s"] > 0


def test_watch_split_of_a_function_that_compiles_nothing():
    watch = fl.CompileWatch()
    watch.wrap("plain", lambda: time.sleep(0.01))()
    (split,) = watch.splits().values()
    assert sum(split[s] for s in STAGES) == 0.0
    assert split["cache_hit"] is None
    assert split["execute_s"] == pytest.approx(watch.summary()["plain"])


# ---------------------------------------------------------------------------
# Start-up phases.

def test_first_phase_stamps_before_main_and_children_stay_out_of_the_sum(
        capfd):
    startup = fl.Startup()
    capfd.readouterr()
    with startup.phase("weights"):
        time.sleep(0.01)
    with startup.phase("warm_grid"):
        with startup.phase("warm_grid.decode"):
            time.sleep(0.01)
    phases = startup.phases()
    assert list(phases) == ["before_main", "weights", "warm_grid.decode",
                            "warm_grid"]
    assert phases["before_main"] > 0        # this process's age
    assert phases["warm_grid"] >= phases["warm_grid.decode"] >= 0.01
    rep = startup.report()
    top = sum(v for k, v in rep["phases"].items() if "." not in k)
    # Five numbers, each rounded to four decimals: up to 2.5e-4 apart.
    assert rep["total_s"] == pytest.approx(top + rep["unattributed_s"],
                                           abs=3e-4)
    assert set(rep) == {"total_s", "phases", "unattributed_s", "compile",
                        "memory"}
    assert set(rep["compile"]) == {"programs", "trace_s", "lower_s",
                                   "compile_s", "load_s", "cache_hits",
                                   "cache_misses", "slowest"}
    assert len(rep["compile"]["slowest"]) <= 5
    echoed = [json.loads(line)["attrs"]
              for line in capfd.readouterr().err.splitlines()
              if '"startup.phase"' in line]
    assert [e["phase"] for e in echoed] == list(phases)
    snap = metrics_lib.REGISTRY.snapshot()["skytpu_startup_seconds"]
    assert {"weights", "warm_grid", "warm_grid.decode"} <= {
        s["labels"]["phase"] for s in snap["samples"]}


def test_process_start_is_this_processes():
    start = fl.process_start_s()
    assert start is not None
    assert 0 < time.time() - start < 24 * 3600


def test_a_startup_phase_never_calls_the_profiler(monkeypatch):
    """Start-up is kept by the host clock through ``timeline.Event``;
    ``timeline.phase`` stays the one place that touches the profiler,
    and untraced it is as inert as before."""
    made = []

    class Spy:
        def __init__(self, *a, **kw):
            made.append(a)

        def __enter__(self):
            made.append("enter")

        def __exit__(self, *exc):
            made.append("exit")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    monkeypatch.delenv(timeline.ENV_VAR, raising=False)
    before = len(timeline._events)
    with fl.Startup().phase("weights"):
        pass
    assert made == [] and len(timeline._events) == before
    with timeline.phase("engine.decode.dispatch", k=4):
        pass
    assert made == [("engine.decode.dispatch",), "enter", "exit"]
    assert len(timeline._events) == before


def test_state_builders_keep_the_state_phase():
    from skypilot_tpu.models import llama
    from skypilot_tpu.train import lora as lora_lib
    from skypilot_tpu.train import qlora as qlora_lib
    from skypilot_tpu.train import trainer
    cfg = llama.CONFIGS["llama3-tiny"]
    tc = trainer.TrainConfig()
    seen = fl.STARTUP.phases().get("state", 0.0)
    trainer.create_train_state(cfg, tc, None)
    once = fl.STARTUP.phases()["state"]
    assert once > seen
    qlora_lib.create_qlora_state(cfg, lora_lib.LoRAConfig(rank=2), tc)
    assert fl.STARTUP.phases()["state"] > once


# ---------------------------------------------------------------------------
# A toy server start, through the real entry point.

def _events(lines, name):
    out = []
    for line in lines:
        if line.startswith('{"kind": "event"') and f'"{name}"' in line:
            rec = json.loads(line)
            if rec["name"] == name:
                out.append(rec)
    return out


@pytest.fixture(scope="module")
def started_server():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "skypilot_tpu.infer.server",
         "--config", "llama3-tiny", "--port", str(port), "--slots", "2",
         "--max-len", "64", "--span-buckets", "0",
         "--prefill-chunk", "16", "--kv-block", "16", "--spec-k", "0",
         "--warm-grid"],
        env=env, cwd=ROOT, stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE, text=True)
    lines = []
    listening = threading.Event()

    def pump():
        for line in proc.stderr:
            lines.append(line)
            if '"server.listening"' in line:
                listening.set()

    threading.Thread(target=pump, daemon=True).start()
    try:
        assert listening.wait(240), "".join(lines[-20:])[-2000:]
        yield port, lines
    finally:
        proc.terminate()
        try:
            proc.wait(20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def test_listening_carries_a_startup_record_that_sums_to_its_wall(
        started_server):
    _, lines = started_server
    (event,) = _events(lines, "server.listening")
    rep = event["attrs"]["startup"]
    top = {k: v for k, v in rep["phases"].items() if "." not in k}
    assert list(top) == ["before_main", "imports", "backend", "weights",
                         "engine_init", "warm_grid", "gc_freeze", "listen"]
    assert {k for k in rep["phases"] if "." in k} == {
        "warm_grid.decode", "warm_grid.chunk", "warm_grid.wave",
        "warm_grid.small"}
    assert sum(top.values()) + rep["unattributed_s"] == pytest.approx(
        rep["total_s"], abs=1e-3)
    assert 0 <= rep["unattributed_s"] < 0.05 * rep["total_s"]
    families = sum(v for k, v in rep["phases"].items() if "." in k)
    assert families <= top["warm_grid"] + 1e-3
    # one echoed line a phase, and the event's phases are those lines
    echoed = {e["attrs"]["phase"]: e["attrs"]["s"]
              for e in _events(lines, "startup.phase")}
    assert echoed == pytest.approx(rep["phases"])
    assert not _events(lines, "server.programs_warmed")


def test_listening_names_the_grids_programs_and_what_they_cost(
        started_server):
    _, lines = started_server
    (event,) = _events(lines, "server.listening")
    comp = event["attrs"]["startup"]["compile"]
    assert comp["programs"] > 0 and len(comp["slowest"]) == 5
    for prog in comp["slowest"]:
        assert "[" in prog["program"] or prog["program"] in (
            "claim", "copy_block", "export_blocks", "import_blocks")
        assert set(STAGES) | {"execute_s", "cache_hit"} <= set(prog)
    compiled = [e["attrs"] for e in _events(lines, "program.compiled")]
    for stage in STAGES:
        assert comp[stage] == pytest.approx(
            sum(c[stage] for c in compiled), abs=1e-4 * len(compiled))
    assert comp["cache_misses"] == sum(
        1 for c in compiled if c["cache_hit"] is False)
    # all of it lies inside the phases that compile
    phases = event["attrs"]["startup"]["phases"]
    assert sum(comp[s] for s in STAGES) <= (
        phases["weights"] + phases["engine_init"] + phases["warm_grid"])


def test_metrics_and_the_event_agree_on_a_warm_grid_replica(
        started_server):
    """``warm_programs`` republishes the watch's compile metrics after
    its suppressed sweep; the ledger's split rides the same republish,
    so ``skytpu top``'s compile column and the event read the same."""
    port, lines = started_server
    (event,) = _events(lines, "server.listening")
    comp = event["attrs"]["startup"]["compile"]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
        series = metrics_lib.parse_exposition(r.read().decode())

    def value(name, **labels):
        return sum(v for got, v in series[name]["samples"]
                   if all(got.get(k) == want
                          for k, want in labels.items()))

    assert value("skytpu_programs_compiled_total") == comp["programs"]
    for stage in ("trace", "lower", "compile", "load"):
        assert value("skytpu_compile_stage_seconds_total",
                     stage=stage) == pytest.approx(comp[stage + "_s"],
                                                   abs=1e-3)
    assert value("skytpu_compile_cache_hits_total") == comp["cache_hits"]
    assert value("skytpu_compile_cache_misses_total") \
        == comp["cache_misses"]
    phases = event["attrs"]["startup"]["phases"]
    for name in ("weights", "warm_grid", "warm_grid.decode", "listen"):
        assert value("skytpu_startup_seconds", phase=name) \
            == pytest.approx(phases[name], abs=1e-3)


def test_nothing_compiles_after_warmup_in_a_served_run(started_server):
    port, lines = started_server
    n = len(_events(lines, "program.compiled"))
    assert n > 0
    for prompt in ([1, 2, 3], list(range(1, 30))):     # a wave, chunks
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"tokens": prompt,
                             "max_new_tokens": 6}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert len(json.loads(r.read())["tokens"]) == 6
    time.sleep(0.2)
    assert len(_events(lines, "program.compiled")) == n
    assert not _events(lines, "engine.unexpected_compile")


# ---------------------------------------------------------------------------
# The trainer's entry point.

def test_train_run_announces_ready_with_the_same_record():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    done = subprocess.run(
        [sys.executable, "-m", "skypilot_tpu.train.run", "--config",
         "llama3-tiny", "--steps", "2", "--seq", "32", "--batch", "8"],
        env=env, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stderr.splitlines()
    (ready,) = _events(lines, "train.ready")
    rep = ready["attrs"]["startup"]
    top = {k: v for k, v in rep["phases"].items() if "." not in k}
    assert list(top) == ["before_main", "imports", "backend", "state",
                         "first_step"]
    assert sum(top.values()) + rep["unattributed_s"] == pytest.approx(
        rep["total_s"], abs=1e-3)
    assert [p["program"] for p in rep["compile"]["slowest"]][0] \
        .startswith("train_step[")
    # the step compiled inside first_step, and nothing after ready
    compiled = _events(lines, "program.compiled")
    assert any(e["attrs"]["fun_name"] == "jit(step)" for e in compiled)
    assert all(e["ts_s"] <= ready["ts_s"] for e in compiled
               if e["attrs"]["fun_name"] == "jit(step)")
    assert json.loads(done.stdout.splitlines()[-1])["steps"] == 2
