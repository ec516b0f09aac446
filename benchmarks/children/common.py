"""What every process that touches JAX for the benchmark shares."""

from __future__ import annotations

import json
import sys
from typing import Any, Dict


def say(tag: str, obj) -> None:
    """One ``BENCH_<tag> <json>`` line for the parent."""
    sys.stdout.write(f"BENCH_{tag} {json.dumps(obj)}\n")
    sys.stdout.flush()


def require_device(chips: int, rehearse: bool) -> None:
    """No chip, no number: anything but the TPUs the cell asks for ends
    the process — except under ``--rehearse``, which runs tiny shapes on
    the CPU and is never reported as a device."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            sys.exit("--rehearse is a CPU rehearsal; found " + platform)
        return
    if platform != "tpu":
        sys.exit(f"no accelerator: JAX opened {platform!r}, the cell "
                 f"needs {chips} TPU chip(s)")
    if len(devices) < chips:
        sys.exit(f"the cell needs {chips} chips, JAX found "
                 f"{len(devices)}")


def device_info() -> Dict[str, Any]:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def peak_memory_bytes() -> int:
    import jax
    return max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.local_devices()), default=0)


def register_llama_config(name: str, dims, **overrides):
    """The configuration's published sizes as the program's
    ``LlamaConfig``, registered under the configuration's name — in this
    process only; ``models/llama.py`` is not edited."""
    from skypilot_tpu.models import llama
    if dims.head_dim * dims.n_heads != dims.d_model:
        raise SystemExit(f"{name}: head_dim {dims.head_dim} x "
                         f"{dims.n_heads} heads != hidden {dims.d_model}; "
                         f"LlamaConfig derives the head size")
    cfg = llama.LlamaConfig(
        vocab_size=dims.vocab_size, d_model=dims.d_model,
        n_layers=dims.n_layers, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_ff=dims.d_ff,
        rope_theta=dims.rope_theta, norm_eps=dims.norm_eps,
        max_seq_len=dims.max_seq_len,
        tie_embeddings=dims.tie_embeddings, **overrides)
    llama.CONFIGS[name] = cfg
    return cfg


def start_trace(trace_dir: str) -> None:
    """A device + host-annotation trace without the Python call tracer
    (every Python call of a serving loop as an event swamps the file and
    slows the host)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
