"""Tensor-parallel serving: the engine sharded over a tp mesh must be
TOKEN-EXACT against the single-device engine — XLA SPMD partitions the
unchanged prefill/decode programs from the input shardings alone
(weights split Megatron-style, the KV cache by kv_heads).

This is the multi-chip serving story (JetStream runs TP on real pods;
reference serves via external engines): one chip can't hold a 70B —
``infer.server --tp N`` can. Runs on the virtual CPU mesh.

Parity holds where accumulation is associative: fp32 activations and
the int8 (w8a8) path. Under bf16 activations the TP all-reduce adds
per-device partial sums that were each rounded to 8 mantissa bits,
while the single-device dot rounds once after the full contraction —
the logits then differ at bf16 epsilon and greedy argmax flips on
near-ties (observed: the tiny model's top-2 logits tie exactly at
bf16 resolution). So the parity tests run the tiny config in fp32;
the int8 test exercises the quantized path whose integer accumulation
is exact under any partitioning.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import kvcache
from skypilot_tpu.models import llama
from skypilot_tpu.parallel import sharding as sh

# heads=4, kv_heads=2 -> tp<=2; fp32 so TP reduction order cannot
# perturb greedy argmax (see module docstring).
CFG = dataclasses.replace(llama.CONFIGS["llama3-tiny"],
                          dtype=jnp.float32)
PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12]]


def _mesh(tp):
    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")
    return Mesh(np.array(jax.devices()[:tp]), ("tp",))


def _params():
    return llama.init_params(jax.random.key(0), CFG)


def _generate(**engine_kwargs):
    e = eng.InferenceEngine(_params(), CFG, n_slots=4, max_len=32,
                            prompt_buckets=(8,), **engine_kwargs)
    return e.generate(PROMPTS, max_new_tokens=6)


def test_tp_engine_matches_single_device():
    base = _generate()
    tp = _generate(mesh=_mesh(2))
    assert tp == base


def test_tp_engine_matches_w8a8_and_kv_int8():
    """The quantized path shards too: int8 weights + their per-channel
    scales split by the same logical names, int8 KV by kv_heads."""
    base = _generate(weights_int8=True, kv_int8=True)
    tp = _generate(weights_int8=True, kv_int8=True, mesh=_mesh(2))
    assert tp == base


def test_tp_shardings_actually_split():
    """The big tensors really are distributed — not silently
    replicated (a replicated wq would make --tp a no-op memory-wise)."""
    mesh = _mesh(2)
    e = eng.InferenceEngine(_params(), CFG, n_slots=2, max_len=32,
                            prompt_buckets=(8,), mesh=mesh)
    wq = e.params["blocks"]["wq"]
    assert "tp" in str(wq.sharding.spec)
    assert e.cache["k"].sharding.spec[3] == "tp"    # kv_heads dim
    # Norms replicate (no rule for 'embed'/'layer').
    assert e.params["blocks"]["ln1"].sharding.spec == \
        jax.sharding.PartitionSpec(None, None) or \
        not any(e.params["blocks"]["ln1"].sharding.spec)


def test_tp_reset_preserves_shardings():
    """After an engine failure + reset, the cache must stay sharded —
    a replicated rebuild would OOM the very next decode on a model
    that only fits sharded."""
    mesh = _mesh(2)
    e = eng.InferenceEngine(_params(), CFG, n_slots=2, max_len=32,
                            prompt_buckets=(8,), mesh=mesh)
    e.generate(PROMPTS[:1], max_new_tokens=3)
    before = e.cache["k"].sharding
    e.reset()
    assert e.cache["k"].sharding == before
    assert e.generate(PROMPTS[:1], max_new_tokens=3)


def test_qweight_logical_axes_match_quantized_tree():
    """The axes tree must mirror quantize_block_weights' structure —
    a drifted name would silently replicate that tensor."""
    params = _params()
    q = {"blocks": kvcache.quantize_block_weights(params),
         "head": kvcache.quantize_head(params, CFG)}
    axes = kvcache.qweight_logical_axes(CFG)
    flat_q = jax.tree_util.tree_flatten_with_path(q)[0]
    for path, arr in flat_q:
        node = axes
        for p in path:
            node = node[p.key]
        assert isinstance(node, tuple), path
        assert len(node) == arr.ndim, (path, node, arr.shape)


def test_sharded_init_materializes_on_mesh():
    """sharded_init builds params jit-with-out_shardings: every big
    tensor lands tp-split (a 70B must never materialize replicated on
    device 0 first), and the engine accepts them unchanged."""
    mesh = _mesh(2)
    params = eng.InferenceEngine.sharded_init(CFG, mesh)
    assert "tp" in str(params["blocks"]["wq"].sharding.spec)
    assert "tp" in str(params["embed"].sharding.spec)  # vocab-split
    e = eng.InferenceEngine(params, CFG, n_slots=2, max_len=32,
                            prompt_buckets=(8,), mesh=mesh)
    base = _generate()
    assert e.generate(PROMPTS, max_new_tokens=6) == base


@pytest.mark.slow
def test_server_main_tp_end_to_end(tmp_path):
    """`infer.server --tp 2` as a real subprocess: /health flips ready
    and /generate streams tokens — the full CLI surface of TP serving,
    not just the engine (the virtual CPU mesh stands in for chips)."""
    import json
    import os
    import socket
    import subprocess
    import sys
    import time
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.Popen(
        [sys.executable, "-m", "skypilot_tpu.infer.server",
         "--config", "llama3-tiny", "--port", str(port),
         "--tp", "2", "--slots", "2", "--max-len", "64"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 300
        while True:
            assert time.time() < deadline, "server never became ready"
            assert proc.poll() is None, "server process died"
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/health",
                        timeout=5) as r:
                    if r.status == 200:
                        break
            except OSError:
                pass
            time.sleep(1)
        body = json.dumps({"tokens": [1, 2, 3],
                           "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        assert len(out["tokens"]) == 4
    finally:
        proc.terminate()
        proc.wait(timeout=10)
