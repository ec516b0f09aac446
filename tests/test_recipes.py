"""Recipes: every shipped YAML parses; train-run entry point works."""

import glob
import json
import os
import subprocess
import sys

import pytest
import yaml

from skypilot_tpu.task import Task

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(REPO, "examples", "*.yaml"))
    + glob.glob(os.path.join(REPO, "llm", "*.yaml"))))
def test_recipe_yaml_parses(path):
    # from_yaml_all handles single- and multi-document (pipeline) YAMLs.
    tasks = Task.from_yaml_all(path)
    assert tasks
    for task in tasks:
        assert task.run
        assert task.resources


def test_train_run_cli_smoke(tmp_path):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               SKYTPU_CALLBACK_LOG_DIR=str(tmp_path),
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "skypilot_tpu.train.run",
         "--config", "llama3-tiny", "--steps", "3", "--seq", "64",
         "--tp", "2", "--log-every", "1",
         "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "2"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["steps"] == 3
    assert out["tokens_per_sec"] > 0
    assert (tmp_path / "ck").exists()

    # Resume from the saved checkpoint.
    proc2 = subprocess.run(
        [sys.executable, "-m", "skypilot_tpu.train.run",
         "--config", "llama3-tiny", "--steps", "5", "--seq", "64",
         "--tp", "2", "--ckpt-dir", str(tmp_path / "ck"), "--resume"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc2.returncode == 0, proc2.stderr[-2000:]
    assert "resumed from step 3" in proc2.stderr
    out2 = json.loads(proc2.stdout.strip().splitlines()[-1])
    assert out2["steps"] == 2


def test_collectives_bench_smoke():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "collectives_bench.py"),
         "--mb", "1", "--iters", "2"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert out["all_reduce"]["algbw_gbps"] > 0
    assert out["all_gather"]["time_ms"] > 0
    assert out["ppermute"]["time_ms"] > 0


def test_evaluate_cli_smoke(tmp_path):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO)
    # Train 2 steps with a checkpoint, then evaluate from it.
    proc = subprocess.run(
        [sys.executable, "-m", "skypilot_tpu.train.run",
         "--config", "llama3-tiny", "--steps", "2", "--seq", "64",
         "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "2"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-1500:]
    proc = subprocess.run(
        [sys.executable, "-m", "skypilot_tpu.train.evaluate",
         "--config", "llama3-tiny", "--seq", "64", "--batches", "2",
         "--batch", "2", "--ckpt-dir", str(tmp_path / "ck"), "--packed"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-1500:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["batches"] == 2
    assert out["perplexity"] > 1.0
    assert "restored step 2" in proc.stderr


def test_train_run_qlora_cli_smoke(tmp_path):
    """--qlora: int8-quantized base + adapters via the CLI, single
    virtual device (the flag is the single-chip path)."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               SKYTPU_CALLBACK_LOG_DIR=str(tmp_path),
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "skypilot_tpu.train.run",
         "--config", "llama3-tiny", "--qlora", "4", "--steps", "3",
         "--seq", "64", "--log-every", "1"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "QLoRA rank 4" in proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["steps"] == 3
