"""The process that holds the chip(s) in a training cell.

A loop of the benchmark's own over exactly the builders ``train/run.py``
calls (``train.run``'s own loop cannot end on a clock and syncs only
every ``--log-every`` steps): every step ends in the fetched loss, and
the window ends at the first step boundary after ``--seconds``.

Set-up builds ONE object — the compiled step with its state — drives it
from the seed through its first steps (whose losses, first gradient and
parameter change the plain reference then follows), and hands that same
object to the window. The reference runs after the window, once the
program's state is freed, so ``memory_peak_bytes`` is the program's.

Branches (``config.program.branch``):

``qlora``  ``qlora.create_qlora_state`` + ``qlora.make_qlora_train_step``
           over the benchmark's seeded int8 base, one chip.
``full``   ``trainer.create_train_state`` + ``trainer.make_train_step``
           on a ``mesh_lib`` mesh taken from the workload file.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time


def _find_mu(opt_state):
    """Adam's first moment inside an optax chain's state."""
    import jax
    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(opt_state)
    if len(found) != 1:
        raise SystemExit(f"expected one Adam state, found {len(found)}")
    return found[0]


def _leaf_norms(tree):
    """{path: L2 norm} of every leaf, as Python floats."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
        for p, a in flat}


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """The widest gap between the program's norm and the reference's
    over the leaves, each against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but
    zero)."""
    import statistics
    median = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
               for k in ref)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--cell-file", required=True)
    ap.add_argument("--mix-file", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", default="")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()
    config = json.load(open(args.config_file))
    cell = json.load(open(args.cell_file))
    mix = json.load(open(args.mix_file))
    prog = config["program"]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import manifest, weights
    from benchmarks.children import common
    from benchmarks.reference import decoder

    chips = int(cell["chips"])
    common.require_device(chips, bool(args.rehearse))
    device = common.device_info()
    common.say("DEVICE", device)
    if args.rehearse:
        from skypilot_tpu.ops import paged_attention
        paged_attention.INTERPRET = True

    from skypilot_tpu.train import trainer

    dims = manifest.model_dims(config)
    name = config["name"]
    cfg = common.register_llama_config(
        name, dims, xent_chunk=int(prog.get("xent_chunk", 0)))
    tc = trainer.TrainConfig(**prog.get("optimizer", {}))
    opt = dict(prog.get("optimizer", {}))
    for k, v in (("learning_rate", tc.learning_rate),
                 ("weight_decay", tc.weight_decay), ("beta1", tc.beta1),
                 ("beta2", tc.beta2), ("grad_clip", tc.grad_clip),
                 ("warmup_steps", tc.warmup_steps),
                 ("total_steps", tc.total_steps)):
        opt.setdefault(k, v)
    batch = int(prog["batch"])
    seq = int(mix["seq"])
    key = jnp.asarray(weights.seed_key(args.seed))
    branch = prog["branch"]

    if branch == "qlora":
        from skypilot_tpu.train import lora as lora_lib
        from skypilot_tpu.train import qlora as qlora_lib
        rank = int(prog["lora_rank"])
        lc = lora_lib.LoRAConfig(rank=rank,
                                 alpha=float(prog["lora_alpha"]))
        fp_params, qweights = weights.build_serving(args.seed, cfg, "int8")
        state = qlora_lib.create_qlora_state(cfg, lc, tc)
        state["params"] = weights.build_lora(args.seed, cfg, rank)
        raw_step = qlora_lib.make_qlora_train_step(cfg, lc, tc)

        def step_fn(s, b):
            return raw_step(s, qweights, fp_params, b)

        def put(rows):
            return {"tokens": jnp.asarray(rows)}
    elif branch == "full":
        from skypilot_tpu.parallel import mesh as mesh_lib
        shape = mesh_lib.default_shape_for(
            jax.device_count() if args.rehearse else chips,
            **(cell.get("mesh") or {}))
        mesh = mesh_lib.make_mesh(shape)
        state = trainer.create_train_state(cfg, tc, mesh)
        shardings = jax.tree.map(lambda a: a.sharding, state["params"])
        state["params"] = jax.jit(
            lambda k: weights.float_serving_tree(k, cfg, jnp.float32),
            out_shardings=shardings)(key)
        step_fn = trainer.make_train_step(cfg, tc, mesh)
        common.say("MESH", shape.as_dict())

        def put(rows):
            return {"tokens": jnp.asarray(rows)}
    else:
        raise SystemExit(f"unknown training branch {branch!r}")
    jax.block_until_ready(state)

    gen = manifest.load_module("traffic", mix["generator"],
                               manifest.BENCH_DIR)
    rows_of = gen.generate(mix, args.seed, batch, dims.vocab_size)

    # --- first steps: driven through the window's own call and feed ----
    n_ref = int(cell["correct"]["reference_steps"])
    start_params = jax.tree.map(jnp.copy, state["params"]) \
        if branch == "qlora" else None
    first_rows, first_losses = [], []
    first_grad = None
    for i in range(n_ref):
        rows = next(rows_of)
        first_rows.append(rows)
        state, metrics = step_fn(state, put(rows))
        first_losses.append(float(metrics["loss"]))
        if i == 0 and branch == "qlora":
            mu = _find_mu(state["opt_state"])
            first_grad = _leaf_norms(jax.tree.map(
                lambda m: m.astype(jnp.float32) / (1.0 - opt["beta1"]), mu))
    change = None
    if branch == "qlora":
        change = _leaf_norms(jax.tree.map(
            lambda a, b: a - b, state["params"], start_params))
        del start_params

    # --- the window ---------------------------------------------------
    tracing = bool(args.trace_dir)
    t_trace_on = args.seconds / 3 if tracing else None
    trace_steps_wanted = 4
    traced = {"steps": 0, "seconds": 0.0}
    trace_state = "off"
    steps = 0
    losses = []
    common.say("WINDOW", {"start_wall": time.time()})
    t0 = time.monotonic()
    while True:
        rows = next(rows_of)
        with jax.profiler.TraceAnnotation("bench.step_dispatch"):
            state, metrics = step_fn(state, put(rows))
        with jax.profiler.TraceAnnotation("bench.loss_fetch"):
            losses.append(float(metrics["loss"]))
        steps += 1
        now = time.monotonic() - t0
        if trace_state == "on":
            traced["steps"] += 1
            if traced["steps"] >= trace_steps_wanted:
                jax.profiler.stop_trace()
                traced["seconds"] = time.monotonic() - t_on
                trace_state = "done"
        elif tracing and trace_state == "off" and now >= t_trace_on:
            common.start_trace(args.trace_dir)
            t_on = time.monotonic()
            trace_state = "on"
        if now >= args.seconds:
            break
    elapsed = time.monotonic() - t0
    if trace_state == "on":
        jax.profiler.stop_trace()
        traced["seconds"] = time.monotonic() - t_on
    peak = common.peak_memory_bytes()
    tokens_per_s = steps * batch * seq / elapsed
    finite = all(np.isfinite(v) for v in losses + first_losses)

    # --- the plain reference, once the program's state is freed --------
    del state, metrics, step_fn
    if branch == "qlora":
        del qweights, fp_params, raw_step
    gc.collect()
    jax.clear_caches()
    t_ref = time.time()
    limits = cell["correct"]["limits"]
    checks = [{"name": "losses_finite", "value": int(not finite),
               "limit": 0, "ok": bool(finite)}]
    readings = {}
    if branch == "qlora":
        def follow(prec):
            ref = decoder.Reference(dims, "int8", prec, lora_rank=rank,
                                    lora_scale=lc.scale)
            lora = weights.build_lora(args.seed, dims, rank)
            start = lora
            mu = jax.tree.map(jnp.zeros_like, lora)
            nu = jax.tree.map(jnp.zeros_like, lora)
            out = {"losses": [], "first_grad": None}
            for i, rows in enumerate(first_rows):
                loss, grads = ref.loss_and_grads(key, jnp.asarray(rows),
                                                 lora)
                out["losses"].append(float(loss))
                grads = decoder.clip_by_global_norm(grads,
                                                    opt["grad_clip"])
                if i == 0:
                    out["first_grad"] = _leaf_norms(grads)
                lora, mu, nu = decoder.adamw_update(lora, grads, mu, nu,
                                                    i, opt)
            out["change"] = _leaf_norms(jax.tree.map(
                lambda a, b: a - b, lora, start))
            return out

        def compare(got_losses, got_grad, got_change, want):
            return {
                "loss_gap": max(abs(a - b) for a, b in
                                zip(got_losses, want["losses"])),
                "first_grad_gap": worst_leaf_gap(got_grad,
                                                 want["first_grad"]),
                "param_change_gap": worst_leaf_gap(got_change,
                                                   want["change"])}

        want = follow(decoder.stated_precision(config))
        readings = compare(first_losses, first_grad, change, want)
        readings["reference_losses"] = want["losses"]
        readings["program_losses"] = first_losses
        for name in ("loss_gap", "first_grad_gap", "param_change_gap"):
            checks.append({"name": name, "value": readings[name],
                           "limit": limits[name],
                           "ok": readings[name] <= limits[name]})
        if args.control:
            for label, mild in (("control", False),
                                ("control_mild", True)):
                low = follow(decoder.control_precision(config, mild))
                readings[label] = compare(low["losses"], low["first_grad"],
                                          low["change"], want)
    else:
        ref = decoder.Reference(dims, "float32", decoder.Precision())
        loss = float(ref.loss(key, jnp.asarray(first_rows[0])))
        readings = {"loss_gap": abs(loss - first_losses[0]),
                    "reference_losses": [loss],
                    "program_losses": first_losses}
        checks.append({"name": "loss_gap", "value": readings["loss_gap"],
                       "limit": limits["loss_gap"],
                       "ok": readings["loss_gap"] <= limits["loss_gap"]})
    readings["seconds"] = time.time() - t_ref

    common.say("RESULT", {
        "values": {"train_tokens_per_s": tokens_per_s},
        "attempted": steps, "failed": 0 if finite else steps,
        "checks": checks, "reference": readings,
        "memory_peak_bytes": peak,
        "info": {"steps": steps, "elapsed_s": elapsed, "batch": batch,
                 "seq": seq, "step_ms": elapsed * 1e3 / steps,
                 "first_losses": first_losses,
                 "last_loss": losses[-1], "branch": branch},
        "facts": {"traced": traced if tracing else None,
                  "train_tokens_per_s": tokens_per_s,
                  "device": device}})


if __name__ == "__main__":
    main()
