"""Training recipe entry point: ``python -m skypilot_tpu.train.run``.

The in-tree replacement for the reference's external workload recipes
(reference: examples/tpu/v6e/train-llama3-8b.yaml runs a PyTorch/XLA
HF Trainer; llm/llama-3_1-finetuning/ runs torchtune). One process per
TPU host; multi-host slices initialize jax.distributed from the env
contract injected by the runtime (SKYTPU_COORDINATOR, SKYTPU_NUM_HOSTS,
SKYTPU_HOST_ID — runtime/driver.py).

Examples::

    # single host, FSDP over all local chips:
    python -m skypilot_tpu.train.run --config llama3-400m --steps 100

    # 4-host v5p-16, fsdp x tp, checkpoints to a bucket mount:
    python -m skypilot_tpu.train.run --config llama3-8b --tp 4 \
        --steps 1000 --ckpt-dir /outputs/ckpts --ckpt-every 100

    # MoE with expert parallelism:
    python -m skypilot_tpu.train.run --model moe --config moe-small --ep 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main() -> None:
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama",
                    choices=("llama", "moe", "pipeline"))
    ap.add_argument("--config", default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch (0 = 4 x data-parallel degree)")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--xent-chunk", type=int, default=None,
                    help="chunked cross-entropy: the [B,S,vocab] logits "
                         "never materialize (512 is the measured v5e "
                         "sweet spot; 0 = unchunked)")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--packed", action="store_true",
                    help="packed-sequence input pipeline (segment-aware "
                         "attention) over synthetic variable-length docs")
    ap.add_argument("--zigzag", action="store_true",
                    help="zigzag (load-balanced causal) ring attention "
                         "for sp>1; llama only")
    ap.add_argument("--lora", type=int, default=0, metavar="RANK",
                    help="LoRA finetune: train rank-RANK adapters over "
                         "frozen base weights (llama only)")
    ap.add_argument("--qlora", type=int, default=0, metavar="RANK",
                    help="QLoRA finetune: int8-quantized frozen base + "
                         "rank-RANK adapters — 8B-class on one 16 GB "
                         "chip (llama only, single chip; use --lora "
                         "for sharded multi-chip)")
    ap.add_argument("--qlora-random-base", action="store_true",
                    help="random int8 base generated ON device (bench/"
                         "smoke: skips the fp init an 8B config can't "
                         "fit)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--profiler-port", type=int, default=0,
                    help="start the JAX profiler's server on this port so "
                         "a device trace (with the loop's train.* "
                         "phases on the same clock) can be captured "
                         "from the running job; 0 = off")
    args = ap.parse_args()
    if args.packed and args.model == "pipeline":
        ap.error("--packed is not supported with --model pipeline")
    if args.lora and args.model != "llama":
        ap.error("--lora currently supports --model llama only")
    if args.lora < 0:
        ap.error("--lora rank must be positive")
    if args.qlora:
        if args.model != "llama":
            ap.error("--qlora currently supports --model llama only")
        if args.lora:
            ap.error("--lora and --qlora are mutually exclusive")
        if args.qlora < 0:
            ap.error("--qlora rank must be positive")
    if args.zigzag and args.model not in ("llama", "moe"):
        # Only llama's and moe's forwards apply the zigzag permute;
        # letting the rule reach another model would silently mis-mask
        # attention.
        ap.error("--zigzag supports --model llama or moe only")

    # Multi-host: join the cluster-wide jax.distributed rendezvous using
    # the runtime's env contract (runtime/constants.py) before touching
    # devices. Multislice (MEGASCALE_*) is consumed by libtpu directly.
    # Start-up goes on the record phase by phase (flight.STARTUP), as
    # the model server's does; ``train.ready`` carries the account once
    # the first step has run (docs/observability.md §Start-up).
    from skypilot_tpu.observability import flight, tracing
    startup = flight.STARTUP
    with startup.phase("imports"):
        from skypilot_tpu.parallel.distributed import initialize_from_env
        initialize_from_env()

        import jax

        import skypilot_tpu.callbacks as sky_callback
        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.parallel import sharding as sh_rules
        from skypilot_tpu.train import trainer
        from skypilot_tpu.utils import timeline
    with startup.phase("backend"):
        jax.devices()              # the backend starts here
    if args.profiler_port:
        jax.profiler.start_server(args.profiler_port)

    if args.model == "llama":
        from skypilot_tpu.models import llama as model
        default_cfg = "llama3-400m"
    elif args.model == "moe":
        from skypilot_tpu.models import moe as model
        default_cfg = "moe-small"
    else:
        from skypilot_tpu.parallel import pipeline as model
        default_cfg = "pp-tiny"
    cfg = model.CONFIGS[args.config or default_cfg]
    if args.xent_chunk is not None:
        import dataclasses
        if not hasattr(cfg, "xent_chunk"):
            ap.error(f"--xent-chunk is not supported by {args.model}")
        cfg = dataclasses.replace(cfg, xent_chunk=args.xent_chunk)
    args.seq = min(args.seq, cfg.max_seq_len)

    n = jax.device_count()
    shape = mesh_lib.default_shape_for(n, tp=args.tp, sp=args.sp,
                                       dp=args.dp, ep=args.ep, pp=args.pp)
    mesh = mesh_lib.make_mesh(shape)
    log(f"mesh: {shape.as_dict()} over {n} devices "
        f"({jax.devices()[0].platform}: {jax.devices()[0].device_kind})")

    data_degree = shape.dp * shape.fsdp
    batch = args.batch or 4 * data_degree
    if batch % data_degree:
        batch = data_degree * max(1, batch // data_degree)
    micro = getattr(cfg, "n_microbatches", None)
    if micro and batch % micro:
        batch = micro * max(1, batch // micro)

    tc = trainer.TrainConfig(learning_rate=args.lr,
                             warmup_steps=max(1, min(100, args.steps // 10)),
                             total_steps=args.steps)

    act_rules = sh_rules.ACT_RULES
    if args.zigzag:
        act_rules = dict(act_rules, seq_layout="zigzag")

    # Goodput forensics: the trainer's own compile watch (a mid-run
    # retrace is a typed train.unexpected_compile), sampled device-time
    # calibration, the per-step phase ledger, and the anomaly watchdog
    # over the losses the logging cadence fetches anyway. The roofline
    # peak is published here so skytpu top's train MFU has its
    # denominator in a train-only process.
    from skypilot_tpu.observability import attribution
    from skypilot_tpu.observability import goodput as goodput_lib
    peak_f, peak_bw = attribution.device_peaks()
    attribution.ROOFLINE_PEAK_FLOPS.set(peak_f * n)
    attribution.ROOFLINE_PEAK_BW.set(peak_bw * n)
    watch = flight.CompileWatch(event_name="train.unexpected_compile")
    watch.calibrator = attribution.DeviceTimeCalibrator()
    gp = goodput_lib.GoodputRecorder(
        param_count=cfg.num_params() if hasattr(cfg, "num_params") else 0,
        watch=watch, calibrator=watch.calibrator)
    watchdog = goodput_lib.AnomalyWatchdog(goodput=gp)

    mgr = None
    start_step = 0
    state = None
    step_counts = {}    # further arguments of the train.step annotation
    if args.ckpt_dir:
        from skypilot_tpu.train import checkpoints
        mgr = checkpoints.CheckpointManager(args.ckpt_dir)

    if args.qlora:
        if n > 1:
            # The int8 base is unsharded: pin everything to one chip
            # (the whole point is one-16GB-chip finetuning) rather
            # than dying on multi-chip hosts like v5e-8. Sharded
            # multi-chip finetuning is --lora.
            log(f"--qlora is single-chip: using 1 of {n} devices "
                f"(use --lora for sharded multi-chip finetuning)")
            jax.config.update("jax_default_device", jax.devices()[0])
            n = 1
            batch = args.batch or 4
        from skypilot_tpu.infer import kvcache
        from skypilot_tpu.train import lora as lora_lib
        from skypilot_tpu.train import qlora as qlora_lib
        lc = lora_lib.LoRAConfig(rank=args.qlora)
        with startup.phase("weights"):       # the frozen int8 base
            if args.qlora_random_base:
                fp_params, qweights = kvcache.random_quantized_params(cfg)
            else:
                base = jax.jit(
                    lambda r: model.init_params(r, cfg))(jax.random.key(1))
                qweights = {
                    "blocks": jax.jit(
                        kvcache.quantize_block_weights)(base),
                    "head": jax.jit(
                        lambda p: kvcache.quantize_head(p, cfg))(base),
                }
                fp_params = kvcache.slim_params(base)
                del base   # the int8 copy replaces the fp block weights
            jax.block_until_ready((fp_params, qweights))
        log(f"QLoRA rank {args.qlora}: "
            f"{lora_lib.num_trainable_params(cfg, lc):,} trainable over "
            f"an int8 base of {cfg.num_params():,} params")
        if mgr and args.resume and mgr.latest_step() is not None:
            gp.load_stamps(mgr.directory)
            # The adapter state tree is identical to --lora's.
            with gp.account("restart_replay"):
                state = mgr.restore(
                    lora_lib.abstract_lora_state(cfg, lc, tc, mesh=None))
            start_step = int(mgr.latest_step())
            log(f"resumed from step {start_step}")
        else:
            state = qlora_lib.create_qlora_state(cfg, lc, tc)
        raw_step = qlora_lib.make_qlora_train_step(cfg, lc, tc)
        step_fn = lambda s, b: raw_step(s, qweights, fp_params, b)
        kept = raw_step.kept(
            state, qweights, fp_params,
            {"tokens": jax.ShapeDtypeStruct((batch, args.seq), "int32")})
        log("QLoRA keeps {n_keep} of {n_layers} layers' frozen-base "
            "products and flash residuals for the backward pass "
            "({kept_bytes:,} bytes, by the device's memory limit); the "
            "rest compute them twice".format(**kept))
        step_counts = {k: kept[k] for k in ("n_keep", "kept_bytes")}
    elif args.lora:
        from skypilot_tpu.train import lora as lora_lib
        lc = lora_lib.LoRAConfig(rank=args.lora)
        base_sh = lora_lib.base_param_shardings(cfg, mesh, model)
        with startup.phase("weights"):       # the frozen float base
            base_params = jax.block_until_ready(jax.jit(
                lambda r: model.init_params(r, cfg),
                out_shardings=base_sh)(jax.random.key(1)))
        log(f"LoRA rank {args.lora}: "
            f"{lora_lib.num_trainable_params(cfg, lc):,} trainable / "
            f"{cfg.num_params():,} base params (frozen)")
        if mgr and args.resume and mgr.latest_step() is not None:
            gp.load_stamps(mgr.directory)
            with gp.account("restart_replay"):
                state = mgr.restore(
                    lora_lib.abstract_lora_state(cfg, lc, tc, mesh))
            start_step = int(mgr.latest_step())
            log(f"resumed from step {start_step}")
        else:
            state = lora_lib.create_lora_state(cfg, lc, tc, mesh)
        raw_step = lora_lib.make_lora_train_step(cfg, lc, tc, mesh,
                                                 model=model,
                                                 base_sh=base_sh,
                                                 act_rules=act_rules)
        step_fn = lambda s, b: raw_step(s, base_params, b)
    else:
        step_fn = trainer.make_train_step(cfg, tc, mesh, model=model,
                                          act_rules=act_rules,
                                          watch=watch)
        if mgr and args.resume and mgr.latest_step() is not None:
            gp.load_stamps(mgr.directory)
            with gp.account("restart_replay"):
                target = trainer.create_abstract_state(cfg, tc, mesh,
                                                       model=model)
                state = mgr.restore(target)
            start_step = int(mgr.latest_step())
            log(f"resumed from step {start_step}")
        if state is None:
            with gp.account("warmup_compile"):
                state = trainer.create_train_state(cfg, tc, mesh,
                                                   model=model)

    # Per-device bytes once the train state exists and before any step
    # runs: under a mesh they should be level — a state piled on
    # device 0 shows here, not as an allocator crash mid-run.
    jax.block_until_ready(state)
    state_bytes = [m.get("bytes_in_use")
                   for m in attribution.device_report()["memory"]]

    if args.packed:
        import jax.numpy as jnp

        from skypilot_tpu.data import input_pipeline as ip

        def batch_stream():
            seed = 0
            while True:
                docs = ip.synthetic_doc_stream(
                    256, cfg.vocab_size, mean_len=args.seq // 3,
                    seed=seed)
                yield from ip.packed_batches(docs, batch, args.seq)
                seed += 1

        batches = ip.prefetch(
            batch_stream(),
            device_put=lambda b: {k: jnp.asarray(v)
                                  for k, v in b.items()})
    else:
        batches = None
        batch_data = trainer.synthetic_batch(cfg, batch, args.seq)
    if start_step >= args.steps:
        log(f"checkpoint already at step {start_step}; nothing to train")
        if mgr:
            mgr.close()
        print(json.dumps({"steps": 0, "resumed_step": start_step,
                          "mesh": shape.as_dict()}))
        return
    sky_callback.init(total_steps=args.steps)
    t0 = time.time()
    for step in range(start_step, args.steps):
        gp.step_start(step)
        if batches is not None:
            with gp.phase("data_wait"):
                batch_data = next(batches)
        tokens = getattr(batch_data.get("tokens"), "size", 0) \
            if hasattr(batch_data, "get") else 0
        with sky_callback.step():
            with gp.phase("compute", tokens=tokens, **step_counts):
                if step == start_step:
                    # The step that compiles (goodput books it to
                    # warmup_compile), held to its end on the device.
                    with startup.phase("first_step"):
                        state, metrics = jax.block_until_ready(
                            step_fn(state, batch_data))
                else:
                    state, metrics = step_fn(state, batch_data)
        if step == start_step:
            # Every program the loop can reach is compiled now; from
            # here a new key is a mid-run retrace worth alarming on.
            watch.declare_warm()
            tracing.add_event(
                "train.ready",
                {"device": attribution.device_report(),
                 "startup": startup.report([watch])}, echo=True)
        loss = grad_norm = None
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            with gp.phase("eval"):
                # The deliberate host fetch the logging cadence always
                # paid; grad_norm rides the same sync.
                with timeline.phase("train.loss_fetch"):
                    loss = float(metrics["loss"])
                gn = metrics.get("grad_norm") \
                    if hasattr(metrics, "get") else None
                grad_norm = float(gn) if gn is not None else None
                trainer.observe_loss(loss)
            anomaly = watchdog.observe(step + 1, loss, grad_norm)
            if anomaly:
                log(f"step {step + 1}: train.anomaly "
                    f"{anomaly['kind']} {anomaly}")
            log(f"step {step + 1}/{args.steps} loss={loss:.4f}")
        if mgr and (step + 1) % args.ckpt_every == 0:
            with gp.phase("ckpt_save"):
                mgr.save(step + 1, state)
                gp.persist(mgr.directory)
        gp.step_end(tokens=tokens, loss=loss, grad_norm=grad_norm)
    loss = float(metrics["loss"])  # host fetch = real sync
    wall = time.time() - t0
    if mgr:
        with gp.account("ckpt_stall"):
            if mgr.latest_step() != args.steps:
                mgr.save(args.steps, state, force=True)
            mgr.wait()
        gp.persist(mgr.directory)
        mgr.close()
    snap = gp.snapshot()
    tokens_per_s = batch * args.seq * (args.steps - start_step) / wall
    from skypilot_tpu.ops import attention as attn_ops
    print(json.dumps({
        "final_loss": round(loss, 4),
        "steps": args.steps - start_step,
        "wall_s": round(wall, 2),
        "tokens_per_sec": round(tokens_per_s, 1),
        "tokens_per_sec_per_chip": round(tokens_per_s / n, 1),
        "goodput": round(snap["goodput_ratio"], 4),
        "mesh": shape.as_dict(),
        # Where it ran and what it compiled: the device as JAX reports
        # it (with each local device's memory_stats), per-device bytes
        # right after the train state was built, and the attention
        # implementation every traced program chose.
        "device": attribution.device_report(),
        "state_bytes_in_use": state_bytes,
        "attention": attn_ops.traced_impls(),
    }))


if __name__ == "__main__":
    main()
