"""Record the small trace ``test_spans.py`` reduces (run ONCE on a chip:
``chiprun -- python tests/bench_harness/data/make_spans_fixture.py
chiprun_out/spans_fixture``; the two files it writes are committed
beside this script).

A stand-in program with the real one's names: XLA modules
``jit__decode_burst`` and ``jit_step``, the scopes of ``spans.SCOPES``,
Pallas kernels called ``flash_fwd`` / ``flash_bwd_dkv`` /
``flash_bwd_dq``, and a double-buffered decode loop that annotates
through the program's own ``timeline.phase``. Burst 1 is dispatched
BEFORE the trace starts and the last burst is fetched AFTER it stops, so
both edges cut a burst; burst 4 is two programs (two span groups).
"""

import functools
import glob
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))
from skypilot_tpu.utils import timeline  # noqa: E402

ROWS, D, BURSTS = 8, 1024, 7


def _scale(x_ref, o_ref, *, by):
    o_ref[...] = x_ref[...] * by


def kernel(name, x, by):
    return pl.pallas_call(
        functools.partial(_scale, by=by), name=name,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=jax.default_backend() != "tpu")(x)


@functools.partial(jax.jit, static_argnames=("k",))
def _decode_burst(w, pool, x, *, k):
    def step(carry, _):
        with jax.named_scope("decode_step"):
            x = carry
            with jax.named_scope("qkv_proj"):
                q = x @ w
            with jax.named_scope("kv_gather"):
                rows = jnp.take(pool, jnp.arange(ROWS)[::-1], axis=0)
            with jax.named_scope("attn_core"):
                o = jnp.tanh(q * rows) @ w.T
            with jax.named_scope("sample"):
                tok = jnp.argmax(o, axis=-1)
            return o, tok
    x, toks = jax.lax.scan(step, x, None, length=k)
    with jax.named_scope("kv_write"):
        pool = pool.at[0].set(x[0])
    return pool, x, toks


@jax.jit
def step(w, wq, s, x):
    with jax.named_scope("attn"):
        with jax.named_scope("base_matmul"):
            h = x @ (wq.astype(jnp.bfloat16) * s)
        a = kernel("flash_fwd", h, 2.0)
        a = a + kernel("flash_fwd", h, 0.5)      # the recomputed forward
        dkv = kernel("flash_bwd_dkv", a, 3.0)
        dq = kernel("flash_bwd_dq", a, 4.0)
    with jax.named_scope("optimizer"):
        w = w - 1e-3 * (dkv + dq).T @ x
    return w, jnp.mean(a.astype(jnp.float32))


def main(out: str) -> None:
    os.makedirs(out, exist_ok=True)
    w = jnp.eye(D, dtype=jnp.bfloat16)
    wq = jnp.ones((D, D), jnp.int8)
    s = jnp.full((1, D), 0.01, jnp.bfloat16)
    pool = jnp.ones((ROWS, D), jnp.bfloat16)
    x = jnp.ones((ROWS, D), jnp.bfloat16)
    for k in (2, 4):
        jax.block_until_ready(_decode_burst(w, pool, x, k=k))
    jax.block_until_ready(step(w, wq, s, x))

    bursts = {}

    def dispatch(seq):
        k = 2 if seq % 2 == 0 else 4
        parts = 2 if seq == 4 else 1
        toks = []
        for part in range(parts):
            with timeline.phase("engine.decode.dispatch", seq=seq, k=k,
                                slots=3 + part, rows=ROWS, span=256,
                                why="open" if k == 2 else "full",
                                waiting=0):
                _, _, t = _decode_burst(w, pool, x, k=k)
            toks.append(t)
        bursts[seq] = {"k": k, "parts": parts, "toks": toks}

    def fetch(seq):
        b = bursts[seq]
        with timeline.phase("engine.decode.fetch", seq=seq, k=b["k"],
                            parts=b["parts"], waiting=0) as ph:
            for t in b["toks"]:
                np.asarray(t)
            b["tokens"] = 3 * b["k"] * b["parts"]
            ph.set(tokens=b["tokens"], retired=seq % 2)

    dispatch(1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    for seq in range(2, BURSTS + 1):
        dispatch(seq)
        fetch(seq - 1)
    with timeline.phase("engine.wave.dispatch", rows=3, padded_rows=4,
                        bucket=128, prompt_tokens=200, queue_ms_sum=30.0,
                        queue_ms_max=20.0):
        pass
    with timeline.phase("engine.wave.fetch", rows=3, first_tokens=3,
                        queue_ms_sum=30.0, ttft_ms_sum=120.0):
        pass
    with timeline.phase("engine.chunk.dispatch", chunk_tokens=100,
                        padded_tokens=256, final=1, queue_ms=10.0):
        pass
    with timeline.phase("engine.chunk.fetch", final=1, queue_ms=10.0,
                        ttft_ms=80.0):
        pass
    with timeline.phase("engine.chunk.fetch", final=0):
        pass
    for i in range(3):
        with timeline.phase("train.step", step_num=i, tokens=ROWS * D):
            w, loss = step(w, wq, s, x)
        with timeline.phase("train.loss_fetch"):
            float(loss)
    jax.profiler.stop_trace()
    fetch(BURSTS)

    whole = [q for q in range(2, BURSTS)]
    expected = {
        "platform": jax.devices()[0].platform,
        "seqs": whole,
        "launches": sum(bursts[q]["parts"] for q in whole),
        "steps": sum(bursts[q]["k"] * bursts[q]["parts"] for q in whole),
        "tokens": sum(bursts[q]["tokens"] for q in whole),
        "row_steps": sum(bursts[q]["k"] * bursts[q]["parts"] * ROWS
                         for q in whole),
        "train_steps": 3}
    path = sorted(glob.glob(os.path.join(
        out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(out, "spans_fixture.xplane.pb"))
    with open(os.path.join(out, "spans_fixture.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected), os.path.getsize(path))


if __name__ == "__main__":
    main(sys.argv[1])
