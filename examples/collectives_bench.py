"""ICI/DCN collectives microbenchmark: psum / all-gather / ppermute.

The TPU-native equivalent of the reference's NCCL all-reduce test
(reference: examples/nccl_test.yaml — torch.distributed all_reduce_bench
reporting busbw): times XLA collectives over the device mesh and reports
algorithmic + bus bandwidth per collective.

Run on any slice:  python examples/collectives_bench.py [--mb 64]
(on CPU it runs on the virtual device mesh — numbers are meaningless
but the harness is exercised.)
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=float, default=64.0,
                    help="payload megabytes")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()).reshape(n), ("x",))
    elems = int(args.mb * 1e6 / 4)
    elems -= elems % max(n, 1)
    x = jnp.ones((elems,), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("x")))
    bytes_total = elems * 4

    def timed(fn, arg):
        fn = jax.jit(fn)
        out = fn(arg)
        _ = float(jnp.sum(out))            # compile + real sync
        t0 = time.time()
        for _ in range(args.iters):
            out = fn(arg)
        _ = float(jnp.sum(out))            # host fetch = sync
        return (time.time() - t0) / args.iters

    results = {}

    ar = jax.shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                   in_specs=P("x"), out_specs=P("x"))
    t = timed(ar, xs)
    # Ring all-reduce moves 2*(n-1)/n of the data per link.
    results["all_reduce"] = {
        "time_ms": round(t * 1e3, 3),
        "algbw_gbps": round(bytes_total / t / 1e9, 2),
        "busbw_gbps": round(bytes_total / t / 1e9 * 2 * (n - 1) / n, 2),
    }

    # all_gather replicates its output; the replication checker can't
    # infer that, so it is disabled.
    ag = jax.shard_map(lambda v: jax.lax.all_gather(v, "x", tiled=True),
                       mesh=mesh, in_specs=P("x"), out_specs=P(None),
                       check_vma=False)
    t = timed(ag, xs)
    results["all_gather"] = {
        "time_ms": round(t * 1e3, 3),
        "algbw_gbps": round(bytes_total / t / 1e9, 2),
        "busbw_gbps": round(bytes_total / t / 1e9 * (n - 1) / n, 2),
    }

    perm = [(i, (i + 1) % n) for i in range(n)]
    pp = jax.shard_map(lambda v: jax.lax.ppermute(v, "x", perm), mesh=mesh,
                   in_specs=P("x"), out_specs=P("x"))
    t = timed(pp, xs)
    results["ppermute"] = {
        "time_ms": round(t * 1e3, 3),
        "algbw_gbps": round(bytes_total / t / 1e9, 2),
    }

    print(json.dumps({
        "devices": n,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        "payload_mb": args.mb,
        **results,
    }))


if __name__ == "__main__":
    main()
