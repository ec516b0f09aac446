"""The training runner end to end at a tiny size on the CPU: the QLoRA
cell, the step that returns its state unchanged (``correct`` false), and
the full-training branch on a mesh over four virtual devices."""

import json


from test_run_serve import bench, last_line


def _checks(lines):
    return {c["name"]: c for c in
            (json.loads(l[6:]) for l in lines if l.startswith("CHECK "))}


def test_qlora_cell_rehearses_and_its_control_is_apart(tmp_path):
    rc, lines, err = bench(["--workload", "mistral-7b-qlora.sft-2k",
                            "--seed", str(2 ** 32 + 3), "--seconds", "3",
                            "--trace", "0", "--rehearse", "--control",
                            "--out", str(tmp_path / "out")])
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["correct"] is True
    assert set(obj["metrics"]) == {"train_tokens_per_s", "setup_s"}
    checks = _checks(lines)
    assert {"loss_gap", "first_grad_gap", "param_change_gap"} <= set(checks)
    ref = json.loads([l for l in lines if l.startswith("REFERENCE ")][0][10:])
    assert len(ref["reference_losses"]) == len(ref["program_losses"]) >= 2
    # the control — the reference with int8 activations in the program's
    # place — is apart from a sound run in the first gradient
    assert ref["control"]["first_grad_gap"] > 3 * ref["first_grad_gap"]


def test_a_step_that_returns_its_state_is_not_correct(tmp_path):
    rc, lines, err = bench(["--workload", "mistral-7b-qlora.sft-2k",
                            "--seed", "4", "--seconds", "2", "--trace", "0",
                            "--rehearse", "--out", str(tmp_path / "out")],
                           broken="train=broken_train_child")
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["correct"] is False
    checks = _checks(lines)
    assert checks["param_change_gap"]["ok"] is False
    assert checks["losses_finite"]["ok"] is True


def test_full_training_branch_on_four_virtual_devices(tmp_path):
    """The four-chip cell of PERF.md's Open questions is data only: its
    workload and configuration files are here, and the runner's mesh path
    (fsdp 2 x tp 2) runs on four virtual CPU devices."""
    rc, lines, err = bench(["--workload", "internlm2-1.8b.pretrain-4chip",
                            "--seed", "6", "--seconds", "2", "--trace", "0",
                            "--rehearse", "--out", str(tmp_path / "out")])
    assert rc == 0, err[-2000:]
    obj = last_line(lines)
    assert obj["correct"] is True and obj["attempted"] >= 2
    log = open(tmp_path / "out" / "train.log").read()
    mesh = json.loads([l for l in log.splitlines()
                       if l.startswith("BENCH_MESH ")][0][11:])
    assert mesh["tp"] == 2 and mesh["fsdp"] == 2
    assert _checks(lines)["loss_gap"]["ok"] is True
