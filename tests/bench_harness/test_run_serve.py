"""A serve cell driven end to end at a tiny size on the CPU
(``--rehearse`` skips only the harness's look for a chip): the last
line's contract, no device metric from a CPU, and ``correct`` coming out
false when the timed path is broken underneath."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def bench(args, cwd=ROOT, env=None, timeout=600, broken=None):
    """``benchmarks.run`` as the driver starts it; ``broken`` names a
    child to replace by its broken stand-in (``broken_run.py``)."""
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.pop("XLA_FLAGS", None)
    full.update(env or {})
    entry = ["-m", "benchmarks.run"] if broken is None else [
        os.path.join(HERE, "broken_run.py"), broken]
    done = subprocess.run(
        [sys.executable] + entry + args, cwd=cwd, env=full,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=timeout)
    lines = done.stdout.decode().strip().splitlines()
    return done.returncode, lines, done.stderr.decode()


def last_line(lines):
    obj = json.loads(lines[-1])
    assert set(obj) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    return obj


@pytest.mark.parametrize("cell,names", [
    # its first-token tail is a per-layer reading since PR 37 (PERF.md 2)
    ("mistral-7b-w8a8.chat-steady", {"tpot_p90_ms", "setup_s"}),
    # not a cell of BENCHMARK.json (PERF.md section 7): only the metric
    # every cell reports is printed
    ("internlm2-1.8b-bf16.chat-steady", {"setup_s"}),
])
def test_serve_cell_rehearses_end_to_end(tmp_path, cell, names):
    rc, lines, err = bench(["--workload", cell, "--seed", str(2 ** 31 + 17),
                            "--seconds", "5", "--trace", "0", "--rehearse",
                            "--out", str(tmp_path / "out")])
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] >= 3
    assert set(obj["metrics"]) == names
    assert all(m["value"] > 0 for m in obj["metrics"].values())
    assert obj["device"]["platform"] == "cpu"
    checks = [json.loads(l[6:]) for l in lines if l.startswith("CHECK ")]
    by_name = {c["name"]: c for c in checks}
    # every number compared is printed beside its limit
    assert {"requests_failed", "compiles_in_window",
            "served_logit_gap_max", "served_logit_gap_mean"} <= set(by_name)
    assert all("limit" in c and "value" in c for c in checks)
    assert by_name["compiles_in_window"]["value"] == 0
    results = json.load(open(tmp_path / "out" / "results.json"))
    assert results["info"]["out_tokens_in_window"] > 0


@pytest.mark.parametrize("cell", ["internlm2-1.8b-bf16.chat-steady"])
def test_traced_rehearsal_prints_no_device_metric(tmp_path, cell):
    rc, lines, err = bench(["--workload", cell, "--seed", "5", "--seconds", "5", "--trace", "1",
                            "--rehearse", "--out", str(tmp_path / "out")])
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["metrics"] == {} and "breakdown" not in obj
    assert "busy_s" not in obj["device"]
    assert obj["correct"] is True
    # the reduction still ran, on the CPU stand-in, and found the programs
    reh = [l for l in lines if l.startswith("REHEARSAL_TRACE ")]
    assert reh and "jit__decode_burst" in reh[0]


@pytest.mark.parametrize("cell", ["mistral-7b-w8a8.chat-steady"])
def test_altered_tokens_are_not_correct(tmp_path, cell):
    """Every greedy token altered where it is produced: requests still
    end 200 with every token, and the reference catches it."""
    rc, lines, err = bench(["--workload", cell, "--seed", "9", "--seconds", "4", "--trace", "0",
                            "--rehearse", "--out", str(tmp_path / "out")],
                           broken="serve=broken_serve_child")
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["failed"] == 0 and obj["correct"] is False


@pytest.mark.parametrize("cell,log", [
    ("internlm2-1.8b-bf16.chat-steady", "server.log"),
    ("internlm2-1.8b.pretrain-4chip", "train.log")])
def test_no_chip_no_number(tmp_path, cell, log):
    rc, lines, err = bench(["--workload", cell, "--seed", "1", "--seconds", "2", "--trace", "0",
                            "--out", str(tmp_path / "out")])
    assert rc != 0
    assert not any(l.startswith("{") for l in lines)
    assert "no accelerator" in (
        err + open(tmp_path / "out" / log).read())


def test_nothing_to_measure_outside_the_repo(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the command fails and prints no result."""
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    rc, lines, err = bench(["--workload", spec["workloads"][0]["name"],
                            "--seed", "1", "--seconds", "2", "--trace", "0",
                            "--rehearse"], cwd=str(tmp_path),
                           env={"PYTHONPATH": ""})
    assert rc != 0 and not any(l.startswith("{") for l in lines)
