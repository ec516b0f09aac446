"""Pipeline-schedule microbenchmark: bubble fraction + activation
memory, GPipe vs the 1F1B-equivalent streaming schedule, at pp=2 and
pp=4.

Run on the virtual CPU mesh (no TPU needed):

    JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pp_schedule_bench.py

What it shows (the honest 1F1B story for a dense lockstep-SPMD
pipeline):

* Bubble fraction is (S-1)/(M+S-1) for BOTH schedules — synchronous
  1F1B does not beat GPipe on steady-state bubble; measured step times
  confirm they match at equal M.
* What 1F1B changes is MEMORY: GPipe buffers every microbatch's
  output ([M, b, S, D]) on top of the O(B) inputs; the streaming
  schedule drops that buffer, so its footprint grows strictly more
  slowly in M (what remains is the input batch itself — this script
  holds b fixed, so B = M*b still grows). At a fixed memory budget
  the lower slope is exactly what lets M rise — and the bubble
  fraction falls with M.

Prints one JSON line per (pp, schedule, M) plus a summary.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main() -> None:
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel import pipeline as pl
    from skypilot_tpu.parallel import sharding as sh

    n_dev = jax.device_count()
    base = pl.CONFIGS["pp-tiny"]
    rows = []
    for pp in (2, 4):
        if n_dev % pp:
            log(f"skipping pp={pp}: {n_dev} devices not divisible")
            continue
        for M in (4, 8, 16):
            for schedule in ("gpipe", "1f1b"):
                cfg = dataclasses.replace(base, n_stages=pp,
                                          n_microbatches=M,
                                          schedule=schedule)
                mesh = mesh_lib.make_mesh(
                    mesh_lib.default_shape_for(n_dev, pp=pp))
                params = pl.init_params(jax.random.key(0), cfg)
                p_sh = sh.logical_to_sharding(
                    pl.param_logical_axes(cfg), mesh, sh.DEFAULT_RULES)
                params = jax.device_put(params, p_sh)
                constrain = sh.make_constrain(mesh, sh.ACT_RULES)
                B = M * 2
                batch = {"tokens": jnp.ones((B, 64), jnp.int32),
                         "mask": None, "segment_ids": None}
                fn = jax.jit(lambda p, b: pl.loss_fn(
                    p, b, cfg, constrain)[0])
                lowered = fn.lower(params, batch)
                compiled = lowered.compile()
                temp_mb = (compiled.memory_analysis().temp_size_in_bytes
                           / 1e6)
                loss = float(fn(params, batch))       # warm + check
                t0 = time.time()
                reps = 5
                for _ in range(reps):
                    loss = fn(params, batch)
                float(loss)
                dt = (time.time() - t0) / reps
                bubble = (pp - 1) / (M + pp - 1)
                rows.append({"pp": pp, "schedule": schedule, "M": M,
                             "step_ms": round(dt * 1e3, 1),
                             "temp_mb": round(temp_mb, 2),
                             "bubble_frac": round(bubble, 4)})
                log(f"pp={pp} {schedule:5s} M={M:2d}: "
                    f"step {dt*1e3:7.1f}ms temp {temp_mb:8.2f}MB "
                    f"bubble {bubble:.1%}")

    # Summary: the memory slope is the schedule difference; the bubble
    # column shows why raising M (which 1F1B's flat memory permits)
    # is the real lever.
    print(json.dumps({"metric": "pp_schedule_bench", "rows": rows}))


if __name__ == "__main__":
    main()
