"""The process that holds the chip in a serve cell: the benchmark's own
wrapper round the program's normal entry point.

It (1) refuses to go on without the device the cell asks for, (2)
registers the configuration's published widths under its name in
``llama.CONFIGS``, (3) hands the program the benchmark's seeded weights
in place of the program's own random ones, (4) answers a few one-line
commands on stdin (trace start/stop, memory), and then (5) calls
``skypilot_tpu.infer.server.main()`` with the configuration's flags —
HTTP server, scheduler, engine and kernels are the program's, untouched.

Lines it prints on stdout for the parent start with ``BENCH_``.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading


def _annotate(cls, method: str, label: str) -> None:
    """Wrap ``cls.method`` in a host ``TraceAnnotation`` (traced runs
    only: the idle gaps of the device trace are attributed to these)."""
    import jax
    inner = getattr(cls, method)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(label):
            return inner(*a, **kw)

    wrapped.__name__ = getattr(inner, "__name__", method)
    wrapped.__wrapped__ = inner
    setattr(cls, method, wrapped)


def _control_loop() -> None:
    """Commands from the parent, one per line on stdin."""
    import jax

    from benchmarks.children import common
    tracing = False
    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        try:
            if cmd[0] == "trace_start" and not tracing:
                common.start_trace(cmd[1])
                tracing = True
                common.say("TRACE", {"started": cmd[1]})
            elif cmd[0] == "trace_stop" and tracing:
                jax.profiler.stop_trace()
                tracing = False
                common.say("TRACE", {"stopped": True})
            elif cmd[0] == "mem":
                stats = [d.memory_stats() or {} for d in jax.local_devices()]
                common.say("MEM", {
                    "peak_bytes_in_use": max(
                        (s.get("peak_bytes_in_use", 0) for s in stats),
                        default=0),
                    "bytes_in_use": max(
                        (s.get("bytes_in_use", 0) for s in stats),
                        default=0),
                    "bytes_limit": max(
                        (s.get("bytes_limit", 0) for s in stats),
                        default=0)})
        except Exception as e:  # noqa: BLE001 — report, keep serving
            common.say("ERROR", {"command": cmd[0], "error": repr(e)})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()
    with open(args.config_file) as f:
        config = json.load(f)

    import jax

    from benchmarks import manifest, weights
    from benchmarks.children import common

    common.require_device(args.chips, bool(args.rehearse))
    common.say("DEVICE", common.device_info())

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.infer import server

    name = config["name"]
    cfg = common.register_llama_config(name, manifest.model_dims(config))
    kind = "int8" if config["precision"]["weights"] == "int8" else "float"
    flags = list(config["program"]["flags"])
    if bool(kind == "int8") != ("--weights-int8" in flags):
        raise SystemExit(f"{name}: precision.weights and the program's "
                         f"flags disagree")

    def seeded_weights(cfg_, *, weights_int8=False, mesh=None, **_):
        if mesh is not None or cfg_ is not cfg:
            raise SystemExit("the benchmark's weights are for the "
                             "one-chip serve cells")
        out = weights.build_serving(args.seed, cfg, kind)
        jax.block_until_ready(out)
        common.say("WEIGHTS", {"kind": kind, "seed": args.seed})
        return out

    # The program's own builder draws with jax.random from its seed 0;
    # the cell's weights come from --seed and from the benchmark.
    eng.random_serving_weights = seeded_weights

    if args.trace:
        _annotate(server.ModelServer, "_step", "server._step")
        _annotate(server.ModelServer, "_drain_inbox", "server._drain_inbox")
        _annotate(server.ModelServer, "_flush_streams",
                  "server._flush_streams")
        _annotate(server.ModelServer, "_complete_burst",
                  "server._complete_burst")
        _annotate(eng.InferenceEngine, "step", "engine.step")

    threading.Thread(target=_control_loop, daemon=True).start()
    sys.argv = ["skypilot_tpu.infer.server", "--config", name,
                "--port", str(args.port)] + flags
    server.main()


if __name__ == "__main__":
    main()
