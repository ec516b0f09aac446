"""Multislice env contract: MEGASCALE_* injection by the gang driver and
jax.distributed bootstrap purely from the injected env (VERDICT r1 #2
done-when)."""

import os
import socket
import subprocess
import sys

from skypilot_tpu.runtime import constants
from skypilot_tpu.runtime.driver import build_job_env


def _meta(n_slices, hosts_per_slice=1):
    hosts = []
    for s in range(n_slices):
        for w in range(hosts_per_slice):
            hosts.append({"host_id": len(hosts), "node_id": s,
                          "worker_id": w,
                          "internal_ip": f"10.0.{s}.{w + 1}",
                          "workspace": None, "kind": "ssh"})
    return {"provider": "gcp", "cluster_name": "ms", "zone": "z",
            "head_host_id": 0, "hosts": hosts}


def test_driver_injects_megascale_on_multislice():
    meta = _meta(n_slices=2, hosts_per_slice=2)
    env = build_job_env(meta, 7, meta["hosts"][3])
    assert env[constants.ENV_MEGASCALE_NUM_SLICES] == "2"
    assert env[constants.ENV_MEGASCALE_SLICE_ID] == "1"
    assert env[constants.ENV_MEGASCALE_COORDINATOR] == \
        f"10.0.0.1:{constants.MEGASCALE_PORT}"
    # Global jax.distributed contract spans all slices.
    assert env[constants.ENV_NUM_PROCESSES] == "4"
    assert env[constants.ENV_PROCESS_ID] == "3"
    assert env[constants.ENV_NODE_RANK] == "1"
    assert env[constants.ENV_WORKER_ID] == "1"


def test_no_megascale_on_single_slice():
    meta = _meta(n_slices=1, hosts_per_slice=4)
    env = build_job_env(meta, 1, meta["hosts"][2])
    assert constants.ENV_MEGASCALE_NUM_SLICES not in env
    assert env[constants.ENV_NUM_PROCESSES] == "4"


_CHILD = """
import os
from skypilot_tpu.parallel.distributed import initialize_from_env
topo = initialize_from_env()
import jax
assert jax.process_count() == 2, jax.process_count()
assert jax.process_index() == topo.process_id
print("RESULT", topo.process_id, jax.device_count(), flush=True)
"""


def test_jax_distributed_initializes_from_injected_env():
    """Two CPU processes rendezvous using ONLY the env the driver
    injects — the contract a real multi-host slice job relies on."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    meta = _meta(n_slices=2, hosts_per_slice=1)
    procs = []
    for hid in (0, 1):
        env = dict(os.environ)
        env.update(build_job_env(meta, 1, meta["hosts"][hid]))
        env[constants.ENV_COORDINATOR] = f"127.0.0.1:{port}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep +
            env.get("PYTHONPATH", ""))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=120) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}\n{err}"
    results = sorted(o.strip().splitlines()[-1] for o, _ in outs)
    assert results[0].startswith("RESULT 0")
    assert results[1].startswith("RESULT 1")
