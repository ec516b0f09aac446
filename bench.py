"""Benchmark: Llama-family train step throughput on the local accelerator.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Metric: training tokens/sec/chip on the largest pre-baked Llama config
that fits the local chip. ``vs_baseline`` is an *MFU ratio* against the
reference's own TPU training anchor, so it is fair across chip
generations and model sizes:

  reference anchor (BASELINE.md): Llama-3-8B PyTorch/XLA on v6e-8 at
  0.476 samples/s. At the example's seq_len=8192 that is 487.4
  tokens/s/chip => MFU = 487.4 * 6 * 8.03e9 / 918e12 = 2.56%.

  vs_baseline = our_MFU / 0.0256.

All progress chatter goes to stderr; stdout carries only the JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


REF_MFU = 487.4 * 6 * 8.03e9 / 918e12  # 0.02558 (see module docstring)


def peak_for(device) -> float:
    """Peak dense bf16 FLOP/s of ``device`` from THE peaks table
    (observability/attribution.py); an unknown device_kind raises."""
    from skypilot_tpu.observability import attribution
    return attribution.peaks_for(device).bf16_flops


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="llama3-1b",
                    help="llama config name")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--remat-policy", default=None,
                    choices=("none", "dots"))
    ap.add_argument("--xent-chunk", type=int, default=None)
    ap.add_argument("--param-dtype", default=None,
                    choices=("float32", "bfloat16"))
    ap.add_argument("--mu-dtype", default=None,
                    choices=("float32", "bfloat16"))
    ap.add_argument("--serve", dest="serve", action="store_true",
                    default=None, help="append serving TTFT/throughput "
                    "metrics (default: on TPU only)")
    ap.add_argument("--no-serve", dest="serve", action="store_false")
    ap.add_argument("--serve-config", default="llama3-8b",
                    help="serve bench config (llama3-8b runs w8a8 — "
                         "the baseline's 7/8B serving class)")
    ap.add_argument("--qlora", dest="qlora", action="store_true",
                    default=None, help="append the 8B-class QLoRA train "
                    "bench (default: on TPU only)")
    ap.add_argument("--no-qlora", dest="qlora", action="store_false")
    ap.add_argument("--qlora-config", default="llama3-8b")
    ap.add_argument("--qlora-batch", type=int, default=2)
    ap.add_argument("--qlora-seq", type=int, default=2048)
    ap.add_argument("--qlora-rank", type=int, default=16)
    ap.add_argument("--goodput", dest="goodput", action="store_true",
                    default=True, help="gate the train goodput "
                    "recorder's parity + overhead contract (default on)")
    ap.add_argument("--no-goodput", dest="goodput", action="store_false")
    ap.add_argument("--emit-metrics", action="store_true", default=False,
                    help="snapshot the observability registry into the "
                         "output JSON under 'observability' — the same "
                         "counters/histograms production scrapes from "
                         "/metrics, so BENCH records carry them")
    args = ap.parse_args()

    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()

    import jax

    from skypilot_tpu.models import llama
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import trainer

    devices = jax.devices()
    n_chips = len(devices)
    dev = devices[0]
    kind = getattr(dev, "device_kind", "cpu")
    on_cpu = jax.default_backend() == "cpu"
    log(f"bench: {n_chips}x {kind} backend={jax.default_backend()}")

    if args.config == "llama3-1b":
        # The default: the 1B-class config (pure bf16 train state +
        # chunked xent + full remat fit ~1.5B params inside 16 GB).
        # Measured sweet spot on a 16G v5e: batch 6, full recompute,
        # bf16 params+moments, 512-token xent chunks.
        if args.xent_chunk is None:
            args.xent_chunk = 512
        if args.mu_dtype is None:
            args.mu_dtype = "bfloat16"
        if args.param_dtype is None:
            args.param_dtype = "bfloat16"
        if args.remat_policy is None:
            args.remat_policy = "none"
    if args.batch is None:
        # batch 6/chip is the sweet spot for both 400M (dots remat) and
        # 1B (full remat) on a 16G v5e.
        args.batch = 2 if on_cpu else 6 * max(n_chips, 1)
    if on_cpu and args.seq > 256:
        args.seq = 128

    if args.remat_policy is None:
        args.remat_policy = "dots"
    cfg = llama.CONFIGS[args.config]
    import dataclasses

    import jax.numpy as jnp
    cfg = dataclasses.replace(cfg, remat_policy=args.remat_policy)
    if args.xent_chunk is not None:
        cfg = dataclasses.replace(cfg, xent_chunk=args.xent_chunk)
    if args.param_dtype is not None:
        cfg = dataclasses.replace(cfg,
                                  param_dtype=jnp.dtype(args.param_dtype))
    seq = min(args.seq, cfg.max_seq_len)
    mesh = mesh_lib.make_mesh() if n_chips > 1 else None

    tc = trainer.TrainConfig(warmup_steps=10, total_steps=1000,
                             mu_dtype=args.mu_dtype)
    t0 = time.time()
    state = trainer.create_train_state(cfg, tc, mesh)
    step = trainer.make_train_step(cfg, tc, mesh)
    batch = trainer.synthetic_batch(cfg, args.batch, seq)
    state, metrics = step(state, batch)
    # The timed loop is chained through donated state, so fetching the
    # final loss waits on every step.
    first_loss = float(metrics["loss"])
    log(f"compile+first step: {time.time()-t0:.1f}s loss={first_loss:.3f}")

    for _ in range(args.warmup - 1):
        state, metrics = step(state, batch)
    float(metrics["loss"])

    t0 = time.time()
    for _ in range(args.steps):
        state, metrics = step(state, batch)
    float(metrics["loss"])  # host fetch = real sync
    dt = (time.time() - t0) / args.steps

    tokens_per_step = args.batch * seq
    tok_s = tokens_per_step / dt
    tok_s_chip = tok_s / n_chips

    n_params = cfg.num_params()
    # 6N per token + attention: ~6 * layers * seq * d_model per token
    # (QK^T + AV, causal-halved, fwd+bwd).
    flops_per_token = 6 * n_params + 6 * cfg.n_layers * seq * cfg.d_model
    mfu = tok_s_chip * flops_per_token / peak_for(dev)

    out = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tok_s_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / REF_MFU, 3),
        "mfu": round(mfu, 4),
        "config": args.config,
        "n_params": n_params,
        "batch": args.batch,
        "seq": seq,
        "n_chips": n_chips,
        "device": kind,
        "step_time_s": round(dt, 4),
        "baseline_note": "vs_baseline = MFU ratio vs reference "
                         "Llama-3-8B@v6e-8 anchor (MFU 2.56%, BASELINE.md)",
    }

    # Goodput recorder contract (docs/observability.md §Training
    # goodput): recorder-off training is bit-identical (the recorder
    # never touches batches or state) and recorder-on stays within a
    # 1.01x step-time budget — the same no-op-guard bound the serving
    # flight recorder holds.
    if args.goodput:
        try:
            gp_res = _goodput_bench(trainer, cfg, tc, mesh,
                                    args.batch, seq)
            out.update(gp_res)
            # Parity gates everywhere; the overhead bound only on
            # hardware (the serving recorder's precedent) — a shared
            # CPU box jitters tiny steps by ~10%, far above the
            # recorder's measured ~50us/step cost.
            out["train_goodput_regressed"] = bool(
                (not on_cpu
                 and gp_res["train_goodput_overhead"] > 1.01)
                or not gp_res["train_goodput_parity_ok"])
            if out["train_goodput_regressed"]:
                log("TRAIN GOODPUT REGRESSION: "
                    f"overhead=x{gp_res['train_goodput_overhead']} "
                    f"(> 1.01) or parity broken "
                    f"(parity_ok={gp_res['train_goodput_parity_ok']})")
        except Exception as e:  # noqa: BLE001 — 1B metric must print
            log(f"goodput bench failed: {e}")
            out["train_goodput_error"] = str(e)[:200]

    # Free the 1B train state before the 8B phases.
    del state, step, batch
    import gc
    gc.collect()

    # 8B-class finetune — the metric BASELINE.json actually names
    # ("Llama-3-8B finetune tokens/sec/chip"). int8 frozen base + LoRA
    # + full remat fit 8B on one 16 GB chip; see train/qlora.py.
    if args.qlora is None:
        args.qlora = not on_cpu
    if args.qlora:
        try:
            q = _qlora_bench(args, dev, n_chips, on_cpu)
            out.update(q)
        except Exception as e:  # noqa: BLE001 — 1B metric must print
            log(f"qlora bench failed: {e}")
            out["qlora_8b_error"] = str(e)[:200]
        gc.collect()

    # Serving metrics in the same artifact (reference anchors: JetStream
    # Llama-2-7B on v6e — median TTFT 1829.33 ms, 2147.98 out tok/s).
    # Streaming TTFT through a real LB (first streamed byte), on the
    # same 7/8B model class as the anchor via w8a8 + int8 KV.
    if args.serve is None:
        args.serve = not on_cpu
    if args.serve:
        try:
            from skypilot_tpu.infer import bench_serve
            serve_cfg = args.serve_config
            big = "8b" in serve_cfg
            # Realistic prompts (512-1024 token mix), 5 timed runs on
            # the warm server, worst run reported: the r3 driver
            # artifact showed 5x run-to-run TTFT variance, so a single
            # lucky run proves nothing. 32 slots (the r4 KV-cache
            # layout fix freed the HBM for them) at 24 concurrent
            # requests — serving headroom, like production; admission
            # waves of 4 run ONE batched prefill each (padded -> one
            # compiled program per bucket) and the wave programs are
            # dispatched pipelined (first-token fetches overlap later
            # waves' prefill); decode bursts stay short (open_burst)
            # while traffic is arriving and slots remain, and go long
            # (max_burst 32, amortizing per-dispatch host cost) once
            # slots are full or arrivals go quiet. The full_load
            # companion phase measures 32/32 on the same warm server.
            serve = bench_serve.run_http(
                config=serve_cfg, requests=24, slots=32,
                new_tokens=192, max_burst=32, open_burst=4,
                admit_wave=4, repeats=5, full_load=True,
                weights_int8=big, kv_int8=big)
            # Chip-normalized throughput: our tok/s per peak-TFLOP vs
            # the anchor's tok/s per peak-TFLOP on ITS chip (v6e,
            # 918 TF) — the serve analog of the train metric's MFU
            # ratio, so a v5e result reads fairly against a v6e anchor.
            from skypilot_tpu.infer.bench_serve import REF_TOK_S
            from skypilot_tpu.observability import attribution
            ref_peak = attribution.PEAKS["TPU v6 lite"].bf16_flops
            norm = ((serve["out_tok_s"] / peak_for(dev))
                    / (REF_TOK_S / ref_peak))
            out.update({
                "serve_median_ttft_ms": serve["median_ttft_ms"],
                "serve_worst_run_median_ttft_ms":
                    serve["worst_run_median_ttft_ms"],
                "serve_p99_ttft_ms": serve["p99_ttft_ms"],
                "serve_out_tok_s": serve["out_tok_s"],
                "serve_tpot_ms": serve["tpot_ms"],
                "serve_vs_baseline_tpot": serve["vs_baseline_tpot"],
                "serve_vs_baseline_tok_s_normalized": round(norm, 3),
                "serve_tok_s_normalization": (
                    f"(ours/{peak_for(dev)/1e12:.0f}TF) / "
                    f"(anchor {REF_TOK_S}/{ref_peak/1e12:.0f}TF v6e)"),
                "serve_vs_baseline_ttft": serve["vs_baseline_ttft"],
                "serve_worst_run_vs_baseline_ttft":
                    serve["worst_run_vs_baseline_ttft"],
                "serve_regressed": serve["regressed"],
                "serve_worst_run_regressed":
                    serve["worst_run_regressed"],
                "serve_worst_run_below_1p2x":
                    serve["worst_run_below_1p2x"],
                "serve_runs": serve["runs"],
                "serve_prompt_mean_len": serve["prompt_mean_len"],
                "serve_prompt_max_len": serve["prompt_max_len"],
                "serve_new_tokens": serve["new_tokens"],
                "serve_config": serve["config"],
                "serve_transport": serve["transport"],
                "serve_weights_int8": serve["weights_int8"],
            })
            if serve.get("full_load"):
                # Throughput-optimal companion: every slot filled on
                # the same warm server (the 24-request numbers above
                # keep serving headroom for the TTFT metric).
                fl = serve["full_load"]
                out["serve_full_load_requests"] = fl["requests"]
                out["serve_full_load_out_tok_s"] = fl["out_tok_s"]
                out["serve_full_load_median_ttft_ms"] = \
                    fl["median_ttft_ms"]
                out["serve_full_load_tpot_ms"] = fl.get("tpot_ms")
                out["serve_full_load_regressed"] = fl["regressed"]
                if fl["regressed"]:
                    log("SERVE REGRESSION (full load): median TTFT "
                        f"{fl['median_ttft_ms']}ms >= anchor "
                        f"{bench_serve.REF_TTFT_MS}ms")
            if serve["worst_run_below_1p2x"]:
                log("serve worst-run margin below the 1.2x gate: "
                    f"{serve['worst_run_median_ttft_ms']}ms vs anchor "
                    f"{bench_serve.REF_TTFT_MS}ms")
            if serve["regressed"]:
                # Loud regression guard (VERDICT r3): a serve TTFT
                # worse than the anchor must not ship silently.
                log("SERVE REGRESSION: median-of-runs TTFT "
                    f"{serve['median_ttft_ms']}ms >= anchor "
                    f"{bench_serve.REF_TTFT_MS}ms")
            elif serve["worst_run_regressed"]:
                log("serve worst-run above anchor (median still beats): "
                    f"{serve['worst_run_median_ttft_ms']}ms >= "
                    f"{bench_serve.REF_TTFT_MS}ms")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"serve bench failed: {e}")
            out["serve_error"] = str(e)[:200]
        # Prefix-cache + chunked-prefill phase (engine-only, its own
        # guard): warm-prefix TTFT and the decode-interference numbers
        # ride the same BENCH artifact so the r-trajectory captures
        # this PR's effect.
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            ps = _bs.run_prefix_share(config=serve_cfg,
                                      weights_int8=big, kv_int8=big)
            out["serve_prefix_cold_ttft_ms"] = ps["cold_ttft_ms"]
            out["serve_prefix_warm_ttft_ms"] = ps["warm_ttft_ms"]
            out["serve_prefix_warm_speedup"] = ps["warm_speedup"]
            out["serve_prefix_hit_rate"] = ps["hit_rate"]
            out["serve_prefix_parity_ok"] = ps["parity_ok"]
            out["serve_decode_stall_ms"] = ps["decode_stall_p99_ms"]
            out["serve_tpot_admission_ratio"] = \
                ps["interference"]["tpot_admission_ratio"]
            out["serve_tpot_admission_ratio_monolith"] = \
                ps["interference"]["monolith_ratio"]
            # Gates: warm >= 30% below cold; decode TPOT p99 during
            # admission <= 1.3x idle (vs the monolith's multi-x spike).
            out["serve_prefix_regressed"] = bool(
                not ps["warm_below_70pct_of_cold"]
                or not ps["parity_ok"])
            out["serve_interference_regressed"] = bool(
                ps["interference"]["tpot_admission_ratio"] > 1.3)
            if out["serve_prefix_regressed"]:
                log("SERVE PREFIX REGRESSION: warm "
                    f"{ps['warm_ttft_ms']}ms vs cold "
                    f"{ps['cold_ttft_ms']}ms "
                    f"(parity_ok={ps['parity_ok']})")
            if out["serve_interference_regressed"]:
                log("SERVE INTERFERENCE REGRESSION: admission TPOT "
                    f"p99 x{ps['interference']['tpot_admission_ratio']}"
                    " > 1.3x idle")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"prefix-share bench failed: {e}")
            out["serve_prefix_error"] = str(e)[:200]
        # Paged KV-cache occupancy phase: max concurrent slots at the
        # SAME KV HBM bytes, paged vs contiguous, with greedy parity —
        # the >=4x-slots-at-equal-HBM claim tracked release over
        # release (plus blocks/token so allocator efficiency is too).
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            oc = _bs.run_occupancy(config=serve_cfg, weights_int8=big,
                                   kv_int8=big)
            out["serve_kv_hbm_bytes"] = oc["kv_hbm_bytes"]
            out["serve_slots"] = oc["paged_slots"]
            out["serve_slots_contiguous"] = oc["contiguous_slots"]
            out["serve_blocks_per_token"] = oc["blocks_per_token"]
            out["serve_kv_block"] = oc["kv_block"]
            out["serve_occupancy_x"] = oc["occupancy_x"]
            out["serve_paged_parity_ok"] = oc["parity_ok"]
            # Gate: >=4x slots at equal HBM, bit-equal greedy output.
            out["serve_occupancy_regressed"] = oc["occupancy_regressed"]
            if oc["occupancy_regressed"]:
                log("SERVE OCCUPANCY REGRESSION: "
                    f"{oc['paged_slots']} paged vs "
                    f"{oc['contiguous_slots']} contiguous slots "
                    f"(x{oc['occupancy_x']}, "
                    f"parity_ok={oc['parity_ok']})")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"occupancy bench failed: {e}")
            out["serve_occupancy_error"] = str(e)[:200]
        # Speculative-decoding phase. Headline: the MODEL-backed
        # drafter + async draft/verify pipeline on the NON-repetitive
        # workload (the honest one — n-gram speculation is a wash
        # there by design and rides along as a reported column).
        # Secondary: the PR 8 repetition-heavy n-gram column + the
        # oracle-draft ceiling, keys and meanings unchanged. The
        # >= 1.5x wall-clock gates bind on TPU runs only (the
        # kernel-bench precedent: a compute-bound CPU cannot show a
        # memory-bandwidth win); parity and the pipeline-overlap
        # structure gate everywhere.
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            sp = _bs.run_spec(config=serve_cfg, weights_int8=big,
                              kv_int8=big)
            on_tpu = sp["backend"] == "tpu"
            out["serve_spec_model_speedup"] = sp["model_speedup"]
            out["serve_spec_model_accept_rate"] = \
                sp["model_accept_rate"]
            out["serve_spec_model_tpot_off_ms"] = \
                sp["model_tpot_off_ms"]
            out["serve_spec_model_tpot_ms"] = sp["tpot_model_ms"]
            out["serve_spec_model_tpot_sync_ms"] = \
                sp["tpot_model_sync_ms"]
            out["serve_spec_pipeline_ratio"] = sp["pipeline_ratio"]
            out["serve_spec_overlap_ok"] = sp["overlap_ok"]
            out["serve_spec_ngram_nonrep_speedup"] = \
                sp["ngram_nonrep_speedup"]
            out["serve_spec_ngram_nonrep_accept_rate"] = \
                sp["ngram_nonrep_accept_rate"]
            out["serve_spec_model_parity_ok"] = bool(
                sp["model_parity_ok"] and sp["model_sync_parity_ok"]
                and sp["ngram_nonrep_parity_ok"])
            # Gate: >= 1.5x decode tok/s from the model drafter on the
            # non-repetitive workload (TPU; the tentpole target is
            # 2x), bit-identical greedy output in every mode, and the
            # pipeline's draft dispatches structurally inside verify
            # windows.
            out["serve_spec_model_regressed"] = bool(
                not out["serve_spec_model_parity_ok"]
                or not sp["overlap_ok"]
                or (on_tpu and sp["model_speedup"] < 1.5))
            if out["serve_spec_model_regressed"]:
                log("SERVE SPEC MODEL REGRESSION: "
                    f"x{sp['model_speedup']} (< 1.5 on TPU) or "
                    f"parity broken "
                    f"(model={sp['model_parity_ok']}, "
                    f"sync={sp['model_sync_parity_ok']}, "
                    f"ngram={sp['ngram_nonrep_parity_ok']}) or "
                    f"overlap_ok={sp['overlap_ok']}")
            out["serve_spec_speedup"] = sp["speedup"]
            out["serve_spec_accept_rate"] = sp["accept_rate"]
            out["serve_spec_tpot_off_ms"] = sp["tpot_off_ms"]
            out["serve_spec_tpot_ms"] = sp["tpot_spec_ms"]
            out["serve_spec_oracle_speedup"] = sp["oracle_speedup"]
            out["serve_spec_oracle_accept_rate"] = \
                sp["oracle_accept_rate"]
            out["serve_spec_parity_ok"] = bool(
                sp["parity_ok"] and sp["oracle_parity_ok"])
            # Secondary gate: the repetition-heavy n-gram column keeps
            # its floor on TPU with bit-identical greedy output.
            out["serve_spec_regressed"] = bool(
                (on_tpu and sp["speedup"] < 1.5)
                or not out["serve_spec_parity_ok"])
            if out["serve_spec_regressed"]:
                log("SERVE SPEC REGRESSION: "
                    f"x{sp['speedup']} (< 1.5) or parity broken "
                    f"(ngram={sp['parity_ok']}, "
                    f"oracle={sp['oracle_parity_ok']}, "
                    f"accept={sp['accept_rate']})")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"spec bench failed: {e}")
            out["serve_spec_error"] = str(e)[:200]
        # Span-bucketed decode attention phase: decode TPOT with the
        # span ladder vs the full-view read on the same engine, short
        # active conversations on a long-max_len engine — the decode
        # BANDWIDTH lever (the occupancy phase above covers capacity).
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            sa = _bs.run_span(config=serve_cfg, weights_int8=big,
                              kv_int8=big)
            out["serve_span_speedup"] = sa["speedup"]
            out["serve_span_tpot_full_ms"] = sa["tpot_full_ms"]
            out["serve_span_tpot_ms"] = sa["tpot_span_ms"]
            out["serve_span_rows"] = sa["rows_span"]
            out["serve_span_rows_full"] = sa["rows_full"]
            out["serve_span_programs"] = sa["n_span_programs"]
            out["serve_span_parity_ok"] = sa["parity_ok"]
            # Gate: >= 1.5x decode tok/s for active lengths <=
            # max_len/8 with bit-identical greedy output (the
            # tentpole target is 2x; 1.5x is the regression floor).
            out["serve_span_regressed"] = bool(
                sa["speedup"] < 1.5 or not sa["parity_ok"])
            if out["serve_span_regressed"]:
                log("SERVE SPAN REGRESSION: "
                    f"x{sa['speedup']} (< 1.5) or parity broken "
                    f"(parity_ok={sa['parity_ok']}, "
                    f"rows {sa['rows_span']}/{sa['rows_full']})")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"span bench failed: {e}")
            out["serve_span_error"] = str(e)[:200]
        # Pallas paged decode-attention kernel phase: kernel-vs-gather
        # decode TPOT on the same engine at low occupancy (where the
        # gather transient dominates), greedy parity vs the gather
        # oracle. PARITY is required everywhere; the SPEEDUP gate only
        # binds on real TPU runs — on CPU the kernel executes in
        # Pallas interpret mode, where wall-clock is meaningless.
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            ke = _bs.run_kernel(config=serve_cfg, weights_int8=big,
                                kv_int8=big)
            out["serve_kernel_speedup"] = ke["speedup"]
            out["serve_kernel_tpot_gather_ms"] = ke["tpot_gather_ms"]
            out["serve_kernel_tpot_ms"] = ke["tpot_kernel_ms"]
            out["serve_kernel_parity_ok"] = bool(
                ke["parity_ok"] and ke["kernel_programs_ok"])
            on_tpu = ke["backend"] == "tpu"
            if "span_under_kernel_speedup" in ke:
                out["serve_kernel_span_speedup"] = \
                    ke["span_under_kernel_speedup"]
                out["serve_kernel_occupancy_x"] = \
                    ke["occupancy_under_kernel_x"]
            out["serve_kernel_regressed"] = bool(
                not out["serve_kernel_parity_ok"]
                or (on_tpu and ke["speedup"] < 1.2)
                or (on_tpu and not ke.get(
                    "span_under_kernel_parity_ok", True))
                or (on_tpu and not ke.get(
                    "occupancy_under_kernel_ok", True)))
            if out["serve_kernel_regressed"]:
                log("SERVE KERNEL REGRESSION: "
                    f"x{ke['speedup']} or parity broken "
                    f"(parity_ok={ke['parity_ok']}, "
                    f"programs_ok={ke['kernel_programs_ok']}, "
                    f"backend={ke['backend']})")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"kernel bench failed: {e}")
            out["serve_kernel_error"] = str(e)[:200]
        # Multi-tenant QoS phase: background-tenant TPOT isolation
        # under a hot tenant (WFQ + admission control) and
        # preemption-by-eviction parity — the production-hardening
        # gates (ROADMAP item 4).
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            qs = _bs.run_qos(config=serve_cfg, weights_int8=big,
                             kv_int8=big)
            out["serve_qos_fairness_ratio"] = qs["fairness_ratio"]
            out["serve_qos_bg_ttft_wfq_ratio"] = \
                qs["bg_ttft_wfq_ratio"]
            out["serve_qos_bg_ttft_fifo_ratio"] = \
                qs["bg_ttft_fifo_ratio"]
            out["serve_qos_preemptions"] = qs["preemptions"]
            out["serve_preempt_parity_ok"] = bool(
                qs["preempt_parity_ok"] and qs["sched_parity_ok"])
            # Gates: background TPOT p99 <= 1.3x idle under a hot
            # tenant, preempted-request parity exact.
            out["serve_qos_regressed"] = bool(
                qs["fairness_ratio"] > 1.3
                or not out["serve_preempt_parity_ok"])
            if out["serve_qos_regressed"]:
                log("SERVE QOS REGRESSION: fairness "
                    f"x{qs['fairness_ratio']} (> 1.3) or parity "
                    f"broken (preempt={qs['preempt_parity_ok']}, "
                    f"sched={qs['sched_parity_ok']})")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"qos bench failed: {e}")
            out["serve_qos_error"] = str(e)[:200]
        # Multi-LoRA adapter-catalog phase (ROADMAP item 5): N-adapter
        # mixed decode TPOT vs single-adapter on the same engine.
        # Gates: overhead <= 1.15x, greedy parity vs per-adapter
        # sequential runs exact, and ZERO unexpected compiles while
        # adapters hot-load/evict mid-traffic (adapter count/identity
        # must never enter program identity).
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            adp = _bs.run_adapters(config=serve_cfg, weights_int8=big,
                                   kv_int8=big)
            out["serve_adapter_overhead"] = adp["overhead_ratio"]
            out["serve_adapter_tpot_single_ms"] = adp["tpot_single_ms"]
            out["serve_adapter_tpot_mixed_ms"] = adp["tpot_mixed_ms"]
            out["serve_adapter_parity_ok"] = adp["parity_ok"]
            out["serve_adapter_hot_loads"] = adp["hot_loads"]
            out["serve_adapter_unexpected_compiles"] = \
                adp["unexpected_compiles"]
            out["serve_adapter_regressed"] = bool(
                adp["overhead_ratio"] > 1.15
                or not adp["parity_ok"]
                or adp["unexpected_compiles"] != 0)
            if out["serve_adapter_regressed"]:
                log("SERVE ADAPTER REGRESSION: "
                    f"x{adp['overhead_ratio']} (> 1.15) or parity "
                    f"broken (parity_ok={adp['parity_ok']}, "
                    f"unexpected={adp['unexpected_compiles']})")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"adapter bench failed: {e}")
            out["serve_adapter_error"] = str(e)[:200]
        # Flight recorder + compile watch phase: the introspection
        # contract over the full mixed workload (chunked admission +
        # spec decode + span selection, paged + contiguous). Gates:
        # nothing may compile inside the timed serving window, every
        # burst must carry a matching flight record, and the recorder
        # must be a no-op guard when off (<1% TPOT).
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            fli = _bs.run_flight(config=serve_cfg, weights_int8=big,
                                 kv_int8=big)
            out["serve_warmup_compile_s"] = fli["warmup_compile_s"]
            out["serve_unexpected_compiles"] = \
                fli["unexpected_compiles"]
            out["serve_flight_records"] = fli["n_records"]
            out["serve_flight_overhead"] = fli["overhead_ratio"]
            out["serve_flight_coverage_ok"] = fli["coverage_ok"]
            out["serve_flight_parity_ok"] = fli["parity_ok"]
            out["serve_flight_calibration_parity_ok"] = \
                fli["calibration_parity_ok"]
            out["serve_flight_calibration_samples"] = \
                fli["calibration_samples"]
            # Request forensics rides the same bench: the per-request
            # ledger/tail machinery must be output-invariant when off
            # and <=1% TPOT when on (same bound as the recorder).
            out["serve_forensics_overhead"] = \
                fli["forensics_overhead_ratio"]
            out["serve_forensics_parity_ok"] = \
                fli["forensics_parity_ok"]
            out["serve_flight_regressed"] = bool(
                fli["unexpected_compiles"] != 0
                or not fli["coverage_ok"] or not fli["parity_ok"]
                or not fli["calibration_parity_ok"]
                or not fli["forensics_parity_ok"]
                or fli["overhead_ratio"] > 1.01
                or fli["forensics_overhead_ratio"] > 1.01)
            if out["serve_flight_regressed"]:
                log("SERVE FLIGHT REGRESSION: "
                    f"unexpected={fli['unexpected_compiles']} "
                    f"coverage={fli['coverage_ok']} "
                    f"parity={fli['parity_ok']} "
                    f"cal_parity={fli['calibration_parity_ok']} "
                    f"forensics_parity={fli['forensics_parity_ok']} "
                    f"overhead=x{fli['overhead_ratio']} "
                    f"forensics=x{fli['forensics_overhead_ratio']} "
                    f"(> 1.01)")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"flight bench failed: {e}")
            out["serve_flight_error"] = str(e)[:200]
        # Fleet prefix-affinity phase: consistent-hash routing on the
        # chunk-aligned prefix digest through the real LB. Gates:
        # fleet prefix hit rate >= 0.8 under affinity (the least-load
        # control lands near 1/N), warm TTFT >= 30% below cold, and
        # greedy parity between the cold and warm passes.
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            af = _bs.run_affinity(config=serve_cfg, weights_int8=big,
                                  kv_int8=big)
            out["serve_affinity_hit_rate"] = af["affinity_hit_rate"]
            out["serve_affinity_control_hit_rate"] = \
                af["control_hit_rate"]
            out["serve_affinity_cold_ttft_ms"] = af["cold_ttft_ms"]
            out["serve_affinity_warm_ttft_ms"] = af["warm_ttft_ms"]
            out["serve_affinity_parity_ok"] = af["parity_ok"]
            out["serve_affinity_regressed"] = not af["gate_ok"]
            if not af["gate_ok"]:
                log("SERVE AFFINITY REGRESSION: hit rate "
                    f"{af['affinity_hit_rate']} (< 0.8) or warm "
                    f"{af['warm_ttft_ms']}ms vs cold "
                    f"{af['cold_ttft_ms']}ms (< 30% saving) or "
                    f"parity broken ({af['parity_ok']})")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"affinity bench failed: {e}")
            out["serve_affinity_error"] = str(e)[:200]
        # Disaggregated prefill/decode phase: 1-prefill + 2-decode
        # fleet behind the real LB. Gates: two-tier output
        # bit-identical to single-tier across {fp32, int8 KV} x
        # {spec on/off}, decode-tier TPOT under heavy prefill <= 1.1x
        # idle (TPU only; the single-tier interleave ratio rides
        # along as the contrast), zero unexpected compiles on either
        # tier, and the handoff.transfer chaos retry with zero lost
        # requests and zero leaked prefill-tier blocks.
        try:
            from skypilot_tpu.infer import bench_serve as _bs
            dg = _bs.run_disagg(config=serve_cfg)
            out["serve_disagg_parity_ok"] = dg["parity_ok"]
            out["serve_disagg_isolation_ratio"] = \
                dg["isolation_ratio"]
            out["serve_disagg_single_tier_ratio"] = \
                dg["single_tier_ratio"]
            out["serve_disagg_chaos_parity_ok"] = \
                dg["chaos_parity_ok"]
            out["serve_disagg_leaked_blocks"] = dg["leaked_blocks"]
            out["serve_disagg_unexpected_compiles"] = \
                dg["unexpected_compiles"]
            out["serve_disagg_regressed"] = not dg["gate_ok"]
            if not dg["gate_ok"]:
                log("SERVE DISAGG REGRESSION: parity "
                    f"{dg['parity_ok']}/{dg['chaos_parity_ok']}, "
                    f"isolation x{dg['isolation_ratio']} (> 1.1), "
                    f"leaked={dg['leaked_blocks']}, "
                    f"unexpected={dg['unexpected_compiles']}")
        except Exception as e:  # noqa: BLE001 — train metric must print
            log(f"disagg bench failed: {e}")
            out["serve_disagg_error"] = str(e)[:200]
    if args.emit_metrics:
        from skypilot_tpu.observability import metrics as obs_metrics
        # Only families something actually recorded into: a bench run
        # exercises a slice of the stack, and all-zero families for the
        # rest would bury the signal. A labeled child exists only once
        # someone called labels(); unlabeled families always carry their
        # implicit default child, so those need a nonzero value/count.
        def _recorded(fam):
            for s in fam["samples"]:
                if s["labels"] or s.get("count", 0) or s.get("value", 0):
                    return True
            return False

        snap = obs_metrics.REGISTRY.snapshot()
        out["observability"] = {
            name: fam for name, fam in snap.items() if _recorded(fam)}
    print(json.dumps(out), flush=True)
    failed = sorted(k for k in out if k.endswith("_error"))
    if failed:
        # The JSON line above still carries every phase that ran; a
        # phase that raised must not read as a green run.
        log(f"bench: failed phases: {', '.join(failed)}")
        sys.exit(1)


def _goodput_bench(trainer, cfg, tc, mesh, batch_size, seq,
                   steps=6, reps=2) -> dict:
    """Recorder-off vs recorder-on parity + overhead for the goodput
    step ledger. One jitted step function serves both modes (the
    recorder wraps the CALL SITE, never the program), each run starts
    from a device copy of the same initial state, and the best
    per-mode step time over ``reps`` interleaved runs is compared so
    wall-clock drift doesn't masquerade as recorder overhead."""
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.observability import flight
    from skypilot_tpu.observability import goodput as goodput_lib

    step_fn = trainer.make_train_step(cfg, tc, mesh)
    batch = trainer.synthetic_batch(cfg, batch_size, seq, seed=0)
    state0 = trainer.create_train_state(cfg, tc, mesh, seed=0)
    # One throwaway compile so neither mode's timed loop pays it.
    warm_state, m = step_fn(jax.tree.map(jnp.copy, state0), batch)
    float(m["loss"])
    del warm_state

    best = {"off": None, "on": None}
    final = {}
    for _ in range(reps):
        for mode in ("off", "on"):
            state = jax.tree.map(jnp.copy, state0)
            rec = flight.FlightRecorder()   # isolated ring
            gp = goodput_lib.GoodputRecorder(
                recorder=rec, enable=(mode == "on"))
            t0 = time.time()
            for i in range(steps):
                gp.step_start(i)
                with gp.phase("compute"):
                    state, m = step_fn(state, batch)
                gp.step_end(tokens=batch_size * seq)
            loss = float(m["loss"])  # host fetch = real sync
            dt = (time.time() - t0) / steps
            final[mode] = loss
            if best[mode] is None or dt < best[mode]:
                best[mode] = dt
    overhead = (best["on"] / best["off"]
                if best["off"] and best["off"] > 0 else 1.0)
    parity = final["on"] == final["off"]
    log(f"goodput bench: off={best['off']*1e3:.2f}ms/step "
        f"on={best['on']*1e3:.2f}ms/step x{overhead:.4f} "
        f"parity={parity}")
    return {
        "train_goodput_overhead": round(overhead, 4),
        "train_goodput_parity_ok": parity,
        "train_goodput_step_ms_off": round(best["off"] * 1e3, 3),
        "train_goodput_step_ms_on": round(best["on"] * 1e3, 3),
    }


def _qlora_bench(args, dev, n_chips, on_cpu) -> dict:
    """8B-class QLoRA finetune throughput on one chip."""
    import dataclasses

    from skypilot_tpu.infer import kvcache
    from skypilot_tpu.models import llama
    from skypilot_tpu.train import qlora, trainer
    from skypilot_tpu.train.lora import LoRAConfig

    config = args.qlora_config
    batch_size = args.qlora_batch if not on_cpu else 2
    seq = args.qlora_seq if not on_cpu else 128
    cfg = dataclasses.replace(
        llama.CONFIGS[config], remat_policy="none",
        xent_chunk=(512 if args.xent_chunk is None else args.xent_chunk))
    seq = min(seq, cfg.max_seq_len)
    lc = LoRAConfig(rank=args.qlora_rank)
    tc = trainer.TrainConfig(warmup_steps=10, total_steps=1000)

    log(f"qlora bench: {config} r={lc.rank} batch={batch_size} seq={seq}")
    t0 = time.time()
    # Weights generate ON DEVICE: no 8 GB host-side tree to build and
    # ship over PCIe.
    fp_params, qweights = kvcache.random_quantized_params(cfg, seed=0)
    state = qlora.create_qlora_state(cfg, lc, tc)
    step = qlora.make_qlora_train_step(cfg, lc, tc)
    batch = trainer.synthetic_batch(cfg, batch_size, seq)
    state, metrics = step(state, qweights, fp_params, batch)
    first_loss = float(metrics["loss"])  # host fetch = sync
    log(f"qlora compile+first step: {time.time()-t0:.1f}s "
        f"loss={first_loss:.3f}")

    for _ in range(max(args.warmup - 1, 0)):
        state, metrics = step(state, qweights, fp_params, batch)
    float(metrics["loss"])

    t0 = time.time()
    for _ in range(args.steps):
        state, metrics = step(state, qweights, fp_params, batch)
    float(metrics["loss"])
    dt = (time.time() - t0) / args.steps

    tok_s_chip = batch_size * seq / dt / max(n_chips, 1)
    n_params = cfg.num_params()
    # Two FLOP bases, both reported (VERDICT r3: mixing bases makes the
    # ratio unimpeachable-proof):
    #  - 4N: the work this step actually does — frozen base runs fwd
    #    (2N) + activation-grad bwd (2N), no weight-grad pass. The
    #    honest hardware-utilization number.
    #  - 6N: the anchor's basis (full-train FLOPs). On this basis the
    #    ratio reduces to peak-normalized tokens/s vs the anchor's
    #    finetune tokens/s — the apples-to-apples throughput ratio.
    attn = cfg.n_layers * seq * cfg.d_model
    mfu_4n = tok_s_chip * (4 * n_params + 4 * attn) / peak_for(dev)
    mfu_6n = tok_s_chip * (6 * n_params + 6 * attn) / peak_for(dev)
    return {
        "qlora_8b_tokens_per_sec_per_chip": round(tok_s_chip, 2),
        "qlora_8b_mfu_4n": round(mfu_4n, 4),
        "qlora_8b_mfu_6n_basis": round(mfu_6n, 4),
        "qlora_8b_vs_baseline": round(mfu_6n / REF_MFU, 3),
        "qlora_8b_vs_baseline_4n": round(mfu_4n / REF_MFU, 3),
        "qlora_8b_config": config,
        "qlora_8b_n_params": n_params,
        "qlora_8b_batch": batch_size,
        "qlora_8b_seq": seq,
        "qlora_8b_rank": args.qlora_rank,
        "qlora_8b_step_time_s": round(dt, 4),
        "qlora_8b_note": "int8 frozen base + LoRA. vs_baseline uses "
                         "the anchor's own 6N FLOP basis (= chip-peak-"
                         "normalized tokens/s ratio); mfu_4n is the "
                         "actual work done (no weight-grad pass)",
    }


if __name__ == "__main__":
    main()
