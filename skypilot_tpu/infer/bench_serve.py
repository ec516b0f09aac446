"""Serving benchmark: TTFT + token throughput on the local accelerator.

Prints ONE JSON line, same contract as the repo-root bench.py:
  {"metric": "serve_median_ttft", "value": ..., "unit": "ms",
   "vs_baseline": ...}

vs_baseline compares against the reference's JetStream anchor on TPU
(reference: examples/tpu/v6e/README.md — median TTFT 1829.33 ms,
2147.98 output tok/s for Llama-2-7B on v6e; BASELINE.md). Ratio > 1
means faster than baseline (baseline_ttft / our_ttft).

Usage: python -m skypilot_tpu.infer.bench_serve [--config llama3-400m]
       [--requests 16] [--slots 8] [--prompt-len 96] [--new-tokens 64]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


REF_TTFT_MS = 1829.33
REF_TOK_S = 2147.98
# Anchor's per-token latency (reference: examples/tpu/v6e/README.md
# §Serve — median TPOT for the same JetStream Llama-2-7B run).
REF_TPOT_MS = 18.88


def run(config=None, requests=16, slots=16, prompt_len=96,
        new_tokens=64, max_burst=32, kv_int8=False,
        weights_int8=False, admit_wave=None) -> dict:
    """Run the serving benchmark; returns the metrics dict (also usable
    by the repo-root bench.py to fold serving numbers into its single
    JSON artifact)."""
    import jax
    import numpy as np

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    cfg, e = _build_engine(config, slots, prompt_len, new_tokens,
                           kv_int8, weights_int8, max_wave=admit_wave)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]

    # Warmup: compile the full-wave admission program and the burst
    # decode programs at the measured run's own burst size.
    for p in [prompts[0]] * slots:
        e.add_request(p, max_new_tokens=new_tokens)
    e.run_to_completion(max_burst=max_burst)
    e.finished.clear()

    t0 = time.time()
    for p in prompts:
        e.add_request(p, max_new_tokens=new_tokens)
    done = e.run_to_completion(max_burst=max_burst)
    # Host fetch: the wall clock stops when the device has.
    float(e.cache["length"][0])
    wall = time.time() - t0

    ttfts = sorted((r.first_token_s - r.submit_s) * 1e3 for r in done)
    med_ttft = ttfts[len(ttfts) // 2]
    total_tokens = sum(len(r.tokens) for r in done)
    tok_s = total_tokens / wall
    req_s = len(done) / wall

    log(f"requests={len(done)} wall={wall:.2f}s median_ttft={med_ttft:.1f}ms "
        f"tok/s={tok_s:.1f} req/s={req_s:.2f}")
    return {
        "median_ttft_ms": round(med_ttft, 2),
        "out_tok_s": round(tok_s, 2),
        "req_per_s": round(req_s, 3),
        "vs_baseline_ttft": round(REF_TTFT_MS / max(med_ttft, 1e-9), 3),
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }


def _build_engine(config, slots, prompt_len, new_tokens, kv_int8,
                  weights_int8, max_wave=None, buckets=None,
                  pad_waves=False, prefill_chunk=None,
                  prefix_pool=None):
    import jax

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama
    cfg = llama.CONFIGS[config]
    log(f"serve bench: {config} on {jax.devices()[0].device_kind}")
    max_len = prompt_len + new_tokens + 8
    if buckets is None:
        buckets = (prompt_len,)
    kw = dict(n_slots=slots, max_len=max_len, prompt_buckets=buckets,
              kv_int8=kv_int8, max_wave=max_wave, pad_waves=pad_waves,
              prefill_chunk=prefill_chunk, prefix_pool=prefix_pool)
    # The server's own builder: int8 weights without the float tree
    # they would quantize from, float weights in the compute dtype.
    params, qw = eng.random_serving_weights(cfg,
                                            weights_int8=weights_int8)
    return cfg, eng.InferenceEngine(params, cfg, qweights=qw, **kw)


def _mixed_prompts(rng, vocab, requests, lo=512, hi=1024):
    """Realistic prompt-length mix, every prompt >= ``lo`` tokens: half
    at exactly ``lo`` (short-bucket), half uniform in (3/4*hi, hi] —
    including full ``hi``-token prompts. Returns (prompts, buckets)."""
    lens = []
    for i in range(requests):
        if i % 2 == 0:
            lens.append(lo)
        else:
            lens.append(int(rng.integers(hi - hi // 4 + 1, hi + 1)))
    prompts = [rng.integers(1, vocab, n).tolist() for n in lens]
    return prompts, (lo, hi)


def _client_wave(host, port, payloads, timeout=600.0, stagger_s=0.0,
                 bodies=None):
    """Fire every payload concurrently from ONE thread (raw sockets +
    a selector). A thread-per-request client adds GIL scheduling jitter
    that rivals the TTFTs being measured on a single-core host — the
    r3 driver artifact showed 5x run-to-run TTFT variance.

    ``stagger_s`` paces arrivals: request i is sent at i*stagger_s —
    an open-ish workload instead of one instantaneous burst, so
    admission overlaps decode the way production traffic does.

    Returns [(ttft_s, n_tokens, total_s)] aligned with payloads.
    TTFT is wall time from request send to the first BODY byte (the
    response headers go out before any token and don't count).
    ``bodies``, if a list, collects each raw response body (chunked
    framing included) in payload order — the failover gate parses the
    NDJSON token lines out of it for bit-identity checks.
    """
    import re
    import selectors
    import socket

    sel = selectors.DefaultSelector()
    conns = []
    t_start = time.time()
    unsent = []
    for i, body in enumerate(payloads):
        s = socket.create_connection((host, port))
        head = (f"POST /generate HTTP/1.1\r\nHost: {host}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        st = {"sock": s, "t0": None, "buf": b"", "first": None,
              "hdr_end": None, "done": None}
        conns.append(st)
        unsent.append((t_start + i * stagger_s, s, head + body, st))

    def send_due():
        while unsent and time.time() >= unsent[0][0]:
            _, s, data, st = unsent.pop(0)
            s.sendall(data)            # still blocking: full send
            st["t0"] = time.time()
            s.setblocking(False)
            sel.register(s, selectors.EVENT_READ, st)

    send_due()
    deadline = time.time() + timeout
    live = len(conns)
    while live and time.time() < deadline:
        wait = 1.0
        if unsent:
            wait = max(min(wait, unsent[0][0] - time.time()), 0.0)
        events = sel.select(timeout=wait)
        send_due()
        for key, _ in events:
            st = key.data
            try:
                piece = st["sock"].recv(1 << 16)
            except BlockingIOError:
                continue
            now = time.time()
            if not piece:   # server closed early — treat as done
                sel.unregister(st["sock"])
                st["done"] = st["done"] or now
                live -= 1
                continue
            st["buf"] += piece
            if st["hdr_end"] is None:
                pos = st["buf"].find(b"\r\n\r\n")
                if pos >= 0:
                    st["hdr_end"] = pos + 4
                    hdrs = st["buf"][:pos].lower()
                    # Error paths (400/500, LB 503) respond with
                    # Content-Length over the same keep-alive socket —
                    # no chunked terminator, no close; completion must
                    # come from the framed length.
                    m = re.search(rb"content-length:\s*(\d+)", hdrs)
                    if m:
                        st["clen"] = int(m.group(1))
            if (st["first"] is None and st["hdr_end"] is not None
                    and len(st["buf"]) > st["hdr_end"]):
                st["first"] = now
            done = False
            if st["hdr_end"] is not None and st.get("clen") is not None:
                done = (len(st["buf"]) - st["hdr_end"] >= st["clen"])
            # Chunked body ends with the zero-length chunk.
            elif st["buf"].endswith(b"0\r\n\r\n"):
                done = True
            if done:
                sel.unregister(st["sock"])
                st["done"] = now
                live -= 1
    sel.close()
    out = []
    for st in conns:
        st["sock"].close()
        status = st["buf"].split(b"\r\n", 1)[0]
        if st["done"] is None or st["first"] is None:
            raise AssertionError(
                f"request did not complete (status line {status!r})")
        if b" 200 " not in status + b" ":
            raise AssertionError(f"non-200 response: {status!r} "
                                 f"{st['buf'][:300]!r}")
        body = st["buf"][st["hdr_end"]:]
        if re.search(rb'"error"\s*:', body):
            # A mid-stream engine failure ends the 200 stream with an
            # {"error": ...} line — counting it as a 0-token success
            # would silently corrupt the bench numbers.
            raise AssertionError(f"engine error mid-stream: "
                                 f"{body[:300]!r}")
        m = re.search(rb'"n_tokens":\s*(\d+)', st["buf"])
        n_tok = int(m.group(1)) if m else 0
        out.append((st["first"] - st["t0"], n_tok,
                    st["done"] - st["t0"]))
        if bodies is not None:
            bodies.append(body)
    return out


def run_http(config=None, requests=16, slots=16, prompt_len=None,
             new_tokens=64, max_burst=8, kv_int8=False,
             weights_int8=False, admit_wave=None, open_burst=4,
             repeats=1, prompt_lo=512, prompt_hi=1024,
             stagger_s=0.0, coalesce_s=0.012, full_load=False) -> dict:
    """End-to-end streaming bench: requests go over HTTP through a REAL
    load balancer to the model server, and TTFT is the wall time to the
    FIRST STREAMED BYTE of each response — the JetStream comparison
    (reference: examples/tpu/v6e/README.md measures streaming TTFT),
    not an engine-internal timestamp.

    ``prompt_len=None`` uses a realistic length mix in
    [prompt_lo, prompt_hi] (every prompt >= prompt_lo; see
    :func:`_mixed_prompts`); an int pins every prompt to that length.
    ``repeats`` runs the timed wave N times back-to-back on the warm
    server and reports the median-of-runs AND the worst run — a
    serving number is only real if the worst run clears the bar too.
    """
    import json as _json
    import os
    import socket
    import tempfile
    import threading

    import jax
    import numpy as np

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    if admit_wave is None:
        # pad_waves below needs a wave cap: without one the engine
        # silently falls back to power-of-two padding and a novel
        # (bucket, rows) pair can hit a mid-measurement XLA compile.
        admit_wave = 4

    home = tempfile.mkdtemp(prefix="skytpu-bench-serve-")
    os.environ["SKYPILOT_TPU_HOME"] = home

    from skypilot_tpu.infer import server as srv
    from skypilot_tpu.models import llama
    from skypilot_tpu.serve import load_balancer, serve_state
    from skypilot_tpu.serve.serve_state import ReplicaStatus

    cfg = llama.CONFIGS[config]
    rng = np.random.default_rng(0)
    if prompt_len is None:
        prompts, (lo, hi) = _mixed_prompts(rng, cfg.vocab_size,
                                           requests, prompt_lo,
                                           prompt_hi)
        if on_cpu:   # keep CPU CI fast; shape behavior is identical
            prompts = [p[:max(len(p) // 8, 4)] for p in prompts]
            lo, hi = lo // 8, hi // 8
        buckets = (lo, hi)
        max_prompt = hi
    else:
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(requests)]
        buckets = (prompt_len,)
        max_prompt = prompt_len
    mean_len = sum(len(p) for p in prompts) / len(prompts)

    _, engine = _build_engine(config, slots, max_prompt, new_tokens,
                              kv_int8, weights_int8,
                              max_wave=admit_wave, buckets=buckets,
                              pad_waves=True)
    # Both row rungs of every bucket: the warmup wave below lands on
    # whichever rung its arrivals happen to fill, and a lone straggler
    # in a timed wave must not compile the other.
    engine.warm_programs(max_burst=max_burst)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    model_port, lb_port = free_port(), free_port()
    model, httpd = srv.serve(engine, host="127.0.0.1", port=model_port,
                             max_burst=max_burst,
                             open_burst=open_burst,
                             coalesce_s=coalesce_s)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert model._ready.wait(timeout=600), "model warmup timed out"

    serve_state.add_service("bench", {}, {}, lb_port)
    serve_state.upsert_replica("bench", 1, "bench-replica",
                               ReplicaStatus.READY,
                               f"http://127.0.0.1:{model_port}")
    lb = load_balancer._ThreadingServer(
        ("127.0.0.1", lb_port),
        load_balancer.make_handler("bench",
                                   load_balancer.LeastLoadPolicy()))
    threading.Thread(target=lb.serve_forever, daemon=True).start()

    payloads = [_json.dumps({"tokens": p, "max_new_tokens": new_tokens,
                             "stream": True}).encode()
                for p in prompts]

    # Warmup: the same concurrent wave as the measurement — runs the
    # admission programs and both decode burst sizes (open_burst while
    # slots drain in, max_burst once full) outside the timed window.
    _client_wave("127.0.0.1", lb_port, payloads)

    def _tpots(res):
        # Per-request TPOT: stream time after the first byte, averaged
        # over the remaining tokens (chunk-granular at the burst size,
        # honest over ~190 intervals). The anchor reports the same
        # decode-side per-token latency (REF_TPOT_MS).
        return [(tot - ttft) / max(n - 1, 1) * 1e3
                for (ttft, n, tot) in res if n > 1]

    runs = []
    all_ttfts = []
    all_tpots = []
    for rep in range(max(repeats, 1)):
        t0 = time.time()
        res = _client_wave("127.0.0.1", lb_port, payloads,
                           stagger_s=stagger_s)
        wall = time.time() - t0
        ttfts = sorted(r[0] * 1e3 for r in res)
        all_ttfts.extend(ttfts)
        all_tpots.extend(_tpots(res))
        total_tokens = sum(r[1] for r in res)
        runs.append({
            "median_ttft_ms": round(ttfts[len(ttfts) // 2], 2),
            "max_ttft_ms": round(ttfts[-1], 2),
            "out_tok_s": round(total_tokens / wall, 2),
            "wall_s": round(wall, 3),
        })
        log(f"run {rep + 1}/{repeats}: median_ttft="
            f"{runs[-1]['median_ttft_ms']:.1f}ms "
            f"max={runs[-1]['max_ttft_ms']:.1f}ms "
            f"tok/s={runs[-1]['out_tok_s']:.1f}")

    # Second phase on the SAME warm server: every slot filled
    # (throughput-optimal load, vs the headroom load above that the
    # TTFT numbers use). Engine-only decode at 32 full slots measures
    # ~1.4k tok/s on v5e (staged burst); this reports what survives
    # HTTP + LB (~1.24k).
    full = None
    if full_load and requests >= slots:
        log(f"full-load phase skipped: requests ({requests}) already "
            f">= slots ({slots}) — the headline phase IS full load")
    if full_load and requests < slots:
        if prompt_len is None:
            fl_prompts, _ = _mixed_prompts(rng, cfg.vocab_size, slots,
                                           prompt_lo, prompt_hi)
            if on_cpu:
                fl_prompts = [p[:max(len(p) // 8, 4)]
                              for p in fl_prompts]
        else:
            # Pinned-length benches must stay inside the engine's
            # buckets — the mixed draw would exceed max_prompt.
            fl_prompts = [rng.integers(1, cfg.vocab_size,
                                       prompt_len).tolist()
                          for _ in range(slots)]
        fl_payloads = [_json.dumps({"tokens": p,
                                    "max_new_tokens": new_tokens,
                                    "stream": True}).encode()
                       for p in fl_prompts]
        _client_wave("127.0.0.1", lb_port, fl_payloads)   # warm shapes
        fl_runs = []
        fl_tpots = []
        for rep in range(3):
            t0 = time.time()
            res = _client_wave("127.0.0.1", lb_port, fl_payloads)
            wall = time.time() - t0
            ttfts = sorted(r[0] * 1e3 for r in res)
            fl_tpots.extend(_tpots(res))
            fl_runs.append({
                "median_ttft_ms": round(ttfts[len(ttfts) // 2], 2),
                "out_tok_s": round(sum(r[1] for r in res) / wall, 2),
                "wall_s": round(wall, 3),
            })
            log(f"full-load run {rep + 1}/3: "
                f"median_ttft={fl_runs[-1]['median_ttft_ms']:.1f}ms "
                f"tok/s={fl_runs[-1]['out_tok_s']:.1f}")
        # Median across runs — same reporting discipline as the
        # headline phase (a lucky run must not become the record).
        toks_sorted = sorted(r["out_tok_s"] for r in fl_runs)
        ttft_sorted = sorted(r["median_ttft_ms"] for r in fl_runs)
        fl_tpots.sort()
        full = {
            "requests": slots,
            "out_tok_s": toks_sorted[len(toks_sorted) // 2],
            "median_ttft_ms": ttft_sorted[len(ttft_sorted) // 2],
            "tpot_ms": (round(fl_tpots[len(fl_tpots) // 2], 2)
                        if fl_tpots else None),
            # Full-load TTFT clears the anchor by only ~15% historically
            # (r4: 1557 ms vs 1829) — a separate guard so a small
            # regression here is loud too.
            "regressed": bool(ttft_sorted[len(ttft_sorted) // 2]
                              >= REF_TTFT_MS),
            "runs": fl_runs,
        }

    lb.shutdown()
    httpd.shutdown()
    model.shutdown()

    medians = sorted(r["median_ttft_ms"] for r in runs)
    med_ttft = medians[len(medians) // 2]
    worst_ttft = medians[-1]
    all_ttfts.sort()
    p99_ttft = all_ttfts[min(len(all_ttfts) - 1,
                             int(len(all_ttfts) * 0.99))]
    toks = sorted(r["out_tok_s"] for r in runs)
    tok_s = toks[len(toks) // 2]
    wall_total = sum(r["wall_s"] for r in runs)
    req_s = requests * len(runs) / wall_total
    all_tpots.sort()
    tpot = all_tpots[len(all_tpots) // 2] if all_tpots else None
    log(f"http/lb streaming x{len(runs)}: median-of-runs "
        f"{med_ttft:.1f}ms worst-run {worst_ttft:.1f}ms "
        f"p99(all) {p99_ttft:.1f}ms tok/s {tok_s:.1f} "
        f"tpot {tpot if tpot is None else round(tpot, 2)}ms")
    return {
        "median_ttft_ms": round(med_ttft, 2),
        "worst_run_median_ttft_ms": round(worst_ttft, 2),
        "p99_ttft_ms": round(p99_ttft, 2),
        "out_tok_s": round(tok_s, 2),
        "req_per_s": round(req_s, 3),
        "tpot_ms": round(tpot, 2) if tpot is not None else None,
        "vs_baseline_tpot": (round(REF_TPOT_MS / tpot, 3)
                             if tpot else None),
        "vs_baseline_ttft": round(REF_TTFT_MS / max(med_ttft, 1e-9), 3),
        "worst_run_vs_baseline_ttft": round(
            REF_TTFT_MS / max(worst_ttft, 1e-9), 3),
        # r5 gate: serving changes must keep the WORST run at least
        # 1.2x faster than the anchor, not just the median.
        "worst_run_below_1p2x": bool(
            worst_ttft * 1.2 > REF_TTFT_MS),
        # The headline guard keys on the MEDIAN of runs (the anchor
        # comparison the r3 verdict set); the worst run is reported and
        # separately flagged — on a shared/loaded host it can absorb
        # scheduler noise a median shrugs off (measured: a concurrent
        # test suite on the same core moved worst runs ~30%).
        "regressed": bool(med_ttft >= REF_TTFT_MS),
        "worst_run_regressed": bool(worst_ttft >= REF_TTFT_MS),
        "runs": runs,
        "prompt_mean_len": round(mean_len, 1),
        "prompt_max_len": max(len(p) for p in prompts),
        "new_tokens": new_tokens,
        "stagger_s": stagger_s,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
        "transport": "http_lb_streaming",
        **({"full_load": full} if full else {}),
    }


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def _p99(xs):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * 0.99))] if xs else None


def _interference(engine, fillers, longs, burst, idle_bursts=8):
    """Decode-interference report: per-token decode cadence while long
    prompts are being admitted vs idle decode.

    ``fillers`` (short prompts, long generations) occupy slots and keep
    decoding; once steady, ``longs`` (long prompts) are injected and
    the scheduler runs the server's alternation (one prefill chunk —
    or, chunk-disabled, the whole monolith wave — between decode
    bursts). TPOT here is the REQUEST-experienced cadence: the wall
    interval between consecutive burst completions divided by the burst
    size, so time decode spent stalled behind prefill is charged to it.
    Returns stats in ms plus the admission-vs-idle p99 ratio.
    """
    import time as _time

    for p in fillers:
        engine.add_request(p, max_new_tokens=engine.max_len)
    engine.admit()
    engine.decode_burst(burst)            # warm the cadence
    idle = []
    for _ in range(idle_bursts):
        t0 = _time.time()
        engine.decode_burst(burst)
        idle.append(_time.time() - t0)
    for p in longs:
        engine.add_request(p, max_new_tokens=4)
    intervals, stalls = [], []
    t_last = _time.time()
    while engine.waiting or engine.chunking:
        engine.admit()
        if engine.chunking:
            t0 = _time.time()
            engine.prefill_chunk_step()
            # Honest host sync: a non-final chunk is dispatched and not
            # awaited, and the stall is the chunk's time on the device.
            float(engine.cache["length"][0])
            stalls.append(_time.time() - t0)
        engine.decode_burst(burst)
        now = _time.time()
        intervals.append(now - t_last)
        t_last = now
    # Drain and reset so the caller gets a quiet engine back.
    engine.reset()
    idle_tpot = _median(idle) / burst * 1e3
    adm_p99 = (_p99(intervals) / burst * 1e3 if intervals
               else idle_tpot)
    return {
        "idle_tpot_ms": round(idle_tpot, 3),
        "admission_tpot_p99_ms": round(adm_p99, 3),
        "tpot_admission_ratio": round(adm_p99 / max(idle_tpot, 1e-9),
                                      3),
        "decode_stall_p99_ms": (round(_p99(stalls) * 1e3, 3)
                                if stalls else 0.0),
        "admission_bursts": len(intervals),
    }


def run_prefix_share(config=None, requests=12, slots=16,
                     system_len=None, tail_len=None, new_tokens=None,
                     max_burst=16, prefill_chunk=None, prefix_pool=8,
                     kv_int8=False, weights_int8=False,
                     smoke=False) -> dict:
    """Prefix-share workload: every prompt = one shared system prompt +
    a unique tail (the dominant production shape). Measures cold
    (empty prefix cache) vs warm (system prompt resident) TTFT on the
    same engine, asserts greedy token parity between the two passes,
    and appends the decode-interference report (chunked scheduler vs
    the per-bucket monolith). ``smoke=True`` shrinks everything to a
    CPU-CI-sized regression guard (run_smoke)."""
    import jax
    import numpy as np

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    if system_len is None:
        system_len = 24 if small else 768
    if tail_len is None:
        tail_len = 6 if small else 48
    if new_tokens is None:
        new_tokens = 6 if small else 48
    if prefill_chunk is None:
        prefill_chunk = 8 if small else 256
    if small:
        requests = min(requests, 4)
        slots = min(slots, 4)
        max_burst = min(max_burst, 4)
        prefix_pool = min(prefix_pool, 4)
    requests = min(requests, slots)   # one admission pass => all cold
    bucket = system_len + tail_len
    short_bucket = min(32, bucket)
    # Row headroom so the interference phase's filler requests never
    # push the burst cap below the measured burst size — a shrunken k
    # would compile a fresh decode program mid-measurement.
    iburst = min(max_burst, 4 if small else 8)
    headroom = 48 if small else 0
    cfg, e = _build_engine(config, slots, bucket,
                           new_tokens + headroom, kv_int8,
                           weights_int8, buckets=(short_bucket, bucket),
                           prefill_chunk=prefill_chunk,
                           prefix_pool=prefix_pool)
    rng = np.random.default_rng(0)
    system = rng.integers(1, cfg.vocab_size, system_len).tolist()

    def make_prompts(salt):
        return [system + rng.integers(1, cfg.vocab_size,
                                      tail_len).tolist()
                for _ in range(requests)]

    prompts = make_prompts(0)

    # Warmup: compile claim/chunk/pool-store/decode programs (first
    # request, cold) AND the pool-load path (second, identical request
    # hits the prefix just stored) — the warm timed pass must not pay
    # a first-sight XLA compile.
    e.add_request(prompts[0], max_new_tokens=2)
    e.run_to_completion(max_burst=max_burst)
    e.add_request(prompts[0], max_new_tokens=2)
    e.run_to_completion(max_burst=max_burst)
    e.finished.clear()
    e.clear_prefix_cache()

    def timed_pass(ps):
        for p in ps:
            e.add_request(p, max_new_tokens=new_tokens)
        done = e.run_to_completion(max_burst=max_burst)
        float(e.cache["length"][0])     # honest host sync
        ttfts = [(r.first_token_s - r.submit_s) * 1e3 for r in done]
        out = {tuple(r.prompt): list(r.tokens) for r in done}
        hits = sum(1 for r in done if r.cached_len > 0)
        chunks = sum(r.n_chunks for r in done)
        e.finished.clear()
        return _median(ttfts), out, hits, chunks

    cold_ttft, cold_out, cold_hits, cold_chunks = timed_pass(prompts)
    warm_ttft, warm_out, warm_hits, warm_chunks = timed_pass(prompts)
    parity_ok = all(warm_out[k] == cold_out[k] for k in cold_out)

    log(f"prefix-share: cold={cold_ttft:.1f}ms warm={warm_ttft:.1f}ms "
        f"hits {warm_hits}/{requests} parity={parity_ok}")

    n_f = max(slots // 2, 1)
    fillers = [rng.integers(1, cfg.vocab_size, 4).tolist()
               for _ in range(n_f)]
    longs = [rng.integers(1, cfg.vocab_size, bucket).tolist()
             for _ in range(min(slots - n_f, n_f, 4))]
    interference = _interference(e, fillers, longs, burst=iburst,
                                 idle_bursts=4 if small else 8)
    # Free the chunked engine BEFORE building the monolith comparison:
    # two live 8B-class weight sets would not fit the 16 GB chip the
    # engine is sized for (the OOM would silently eat this phase's
    # numbers via bench.py's guard).
    del e, timed_pass          # timed_pass's closure also pins the engine
    import gc
    gc.collect()
    # The same workload against the per-bucket monolith: the
    # interference chunked prefill removes.
    _, e_mono = _build_engine(config, slots, bucket,
                              new_tokens + headroom, kv_int8,
                              weights_int8,
                              buckets=(short_bucket, bucket),
                              prefill_chunk=0, prefix_pool=0)
    # Warm the exact wave shapes the measured window will admit (the
    # monolith's long-bucket wave would otherwise compile mid-window).
    for p in longs:
        e_mono.add_request(p, max_new_tokens=2)
    e_mono.run_to_completion(max_burst=iburst)
    e_mono.generate([fillers[0]], max_new_tokens=2)
    e_mono.finished.clear()
    mono = _interference(e_mono, fillers, longs, burst=iburst,
                         idle_bursts=4 if small else 8)
    interference["monolith_tpot_p99_ms"] = mono["admission_tpot_p99_ms"]
    interference["monolith_ratio"] = mono["tpot_admission_ratio"]
    log(f"interference: idle {interference['idle_tpot_ms']}ms/tok, "
        f"admission p99 {interference['admission_tpot_p99_ms']} "
        f"(x{interference['tpot_admission_ratio']}), monolith "
        f"x{interference['monolith_ratio']}")

    return {
        "cold_ttft_ms": round(cold_ttft, 2),
        "warm_ttft_ms": round(warm_ttft, 2),
        "warm_speedup": round(cold_ttft / max(warm_ttft, 1e-9), 3),
        # Acceptance bar: warm-prefix median TTFT >= 30% below cold.
        "warm_below_70pct_of_cold": bool(warm_ttft <= 0.7 * cold_ttft),
        "hit_rate": round(warm_hits / max(requests, 1), 3),
        "cold_hits": cold_hits,
        "parity_ok": bool(parity_ok),
        "prefix_hits": warm_hits,
        # Structural (timing-independent) evidence of reuse: chunk
        # programs run per pass — the warm pass prefills suffixes only.
        "cold_chunks": cold_chunks,
        "warm_chunks": warm_chunks,
        "decode_stall_p99_ms": interference["decode_stall_p99_ms"],
        "interference": interference,
        "requests": requests,
        "system_len": system_len,
        "tail_len": tail_len,
        "prefill_chunk": prefill_chunk,
        "prefix_pool": prefix_pool,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }


def run_smoke() -> dict:
    """CI-sized prefix-share + interference pass (tier-1 regression
    guard for the chunk scheduler; see tests/test_prefix_cache.py)."""
    return run_prefix_share(smoke=True)


class _OracleDrafter:
    """Replays a known-correct continuation as the draft — the
    acceptance CEILING for the verify path: every burst accepts the
    full draft, so the measured speedup is what the fixed-K verify
    program delivers when drafts are right, independent of how
    n-gram-predictable the (random-weight) bench model's output is."""

    def __init__(self, out):
        self.out = list(out)
        self._gen = 0

    def catch_up(self, prompt, generated):
        self._gen = len(generated)

    def draft(self, k):
        return self.out[self._gen:self._gen + k]


def run_spec(config=None, spec_k=4, requests=None, prompt_len=16,
             new_tokens=None, max_burst=8, kv_int8=False,
             weights_int8=False, smoke=False,
             draft_layers=None) -> dict:
    """Speculative-decoding bench, two workloads on two engines.

    **Phase A — non-repetitive (the headline, the honest one).**
    Random prompts at the config's FULL vocabulary: the random-weight
    target's greedy trajectories don't cycle, so prompt-lookup has
    nothing to look up — n-gram speculation is a wash here by design,
    and any win must come from the MODEL drafter. The draft model is
    the truncated-layer draft of a self-distilled target
    (``draft.self_distilled_pair``: the target's upper residual blocks
    carry zeroed output projections — the distillation endpoint — so
    the half-cost draft agrees with the target and acceptance is
    near-1.0 without a training run; the zeroed layers still pay their
    full matmul cost, so the baseline TPOT is honest). Five decode
    passes on ONE engine (same weights, same compiled programs — only
    routing flips): spec-off, model-draft pipelined (the shipped
    default), model-draft synchronous (isolates the async pipeline's
    contribution), n-gram (the honest wash column), plus the
    structural overlap check (flight records must show a draft
    dispatch INSIDE a verify's dispatch->fetch window).

    **Phase B — repetition-heavy (the secondary n-gram column).**
    PR 8's original workload verbatim — vocab 16 so the random
    model's trajectories cycle within a few dozen tokens, the regime
    prompt-lookup pays in — with the n-gram and oracle-draft-ceiling
    passes unchanged (the old keys keep their meanings release over
    release).

    TTFT is out of scope by construction: speculation only replaces
    decode bursts — admission, chunking and prefill are untouched (the
    --prefix-share and full-load benches guard TTFT).

    ``smoke=True``: CI-sized (tier-1 wiring in tests/test_spec_decode
    .py + tests/test_draft_model.py) — asserts parity, acceptance and
    overlap STRUCTURE, never wall-clock (a compute-bound CPU cannot
    show a memory-bandwidth win; the speedup gates bind on TPU).
    """
    import dataclasses
    import time as _time

    import jax
    import numpy as np

    from skypilot_tpu.infer import draft as draft_lib
    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama
    from skypilot_tpu.observability import flight as flight_lib

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    if requests is None:
        requests = 4 if small else 8
    if new_tokens is None:
        new_tokens = 96 if small else 256
    spec_k = max(int(spec_k), 1)
    slots = requests
    max_len = 128 if small else 512
    assert prompt_len + new_tokens + spec_k + 1 <= max_len
    # Separate streams: phase B keeps PR 8's exact prompts (seed 0) so
    # its columns stay comparable release over release.
    rng_a = np.random.default_rng(1)
    rng_b = np.random.default_rng(0)

    def decode_pass(e, prompts, spec_on, factory=None,
                    ngram_factory=None, draft_engine=None,
                    pipeline=False):
        """One admit-then-decode pass; TPOT measured over the decode
        loop only (admission/prefill excluded — spec does not touch
        them). Returns (outputs, tpot_s, drafted, accepted, bursts)."""
        e.spec_k = spec_k if spec_on else 0
        e._spec_drafter_factory = factory or ngram_factory
        e.draft_engine = draft_engine
        e.spec_pipeline = bool(pipeline) and draft_engine is not None
        d0, a0 = e._spec_drafted_total, e._spec_accepted_total
        ids = [e.add_request(p, max_new_tokens=new_tokens)
               for p in prompts]
        e.admit()
        t0 = _time.time()
        bursts = 0
        while e.slot_req:
            e.decode_burst(max_burst)
            bursts += 1
        float(e.cache["length"][0])     # honest host sync
        wall = _time.time() - t0
        by_rid = {r.rid: list(r.tokens) for r in e.finished}
        outs = [by_rid[i] for i in ids]
        e.finished.clear()
        # First tokens came from admission; TPOT charges decode only.
        dtoks = sum(len(o) for o in outs) - len(outs)
        return (outs, wall / max(dtoks, 1),
                e._spec_drafted_total - d0,
                e._spec_accepted_total - a0, bursts)

    # -- Phase A: non-repetitive workload, model drafter ------------------
    cfg_a = llama.CONFIGS[config]
    if draft_layers is None:
        draft_layers = max(cfg_a.n_layers // 2, 1)
    params_a = llama.init_params(jax.random.key(0), cfg_a)
    target, dparams, dcfg = draft_lib.self_distilled_pair(
        params_a, cfg_a, draft_layers)
    del params_a
    qw_t = qw_d = None
    if weights_int8:
        # w8a8 phase A (the production serving config the gate must
        # describe): quantize the distilled target's blocks + head
        # ONCE; the draft's quantized tree is the literal layer slice
        # of the target's — the zeroed upper blocks quantize to exact
        # zeros, so the agreement regime survives quantization (both
        # models read the SAME int8 weights for the shared layers).
        from skypilot_tpu.infer import kvcache
        qw_t = jax.jit(lambda p: {
            "blocks": kvcache.quantize_block_weights(p),
            "head": kvcache.quantize_head(p, cfg_a)})(target)
        qw_d = {"blocks": {
                    name: {k: v[:draft_layers]
                           for k, v in qw_t["blocks"][name].items()}
                    for name in qw_t["blocks"]},
                "head": qw_t["head"]}
        dparams = kvcache.slim_params(dparams)
    log(f"spec bench A: {config} (vocab {cfg_a.vocab_size}, "
        f"non-repetitive) K={spec_k} draft={draft_layers}/"
        f"{cfg_a.n_layers} layers requests={requests} "
        f"new_tokens={new_tokens} w8a8={bool(weights_int8)}")
    fl = flight_lib.FlightRecorder()
    e_a = eng.InferenceEngine(
        target, cfg_a, n_slots=slots, max_len=max_len,
        prompt_buckets=(prompt_len,), kv_int8=kv_int8,
        qweights=qw_t,
        prefill_chunk=0, prefix_pool=0, max_wave=slots,
        pad_waves=True, spec_k=spec_k, flight_recorder=fl)
    ngram_factory_a = e_a._spec_drafter_factory
    de = draft_lib.DraftEngine(dparams, dcfg, n_slots=slots,
                               max_len=max_len, kv_int8=kv_int8,
                               qweights=qw_d)
    prompts_a = [rng_a.integers(1, cfg_a.vocab_size,
                                prompt_len).tolist()
                 for _ in range(requests)]

    def pass_a(spec_on, draft_engine=None, pipeline=False):
        return decode_pass(e_a, prompts_a, spec_on,
                           ngram_factory=ngram_factory_a,
                           draft_engine=draft_engine,
                           pipeline=pipeline)

    # Warmups: the off pass covers the plain bursts; the pipelined
    # model pass covers verify + the drafter's rollout (k AND k+1)
    # and steady-state sync programs; the SYNC model pass additionally
    # reaches the per-round bonus-row ingest at every span rung it
    # crosses (pipelined steady state never ingests) — without it the
    # sync column pays mid-window compiles and the pipeline ratio
    # overstates. The n-gram pass dispatches a subset of the above.
    pass_a(False)
    pass_a(True, draft_engine=de, pipeline=True)
    de.reset()
    pass_a(True, draft_engine=de, pipeline=False)
    de.reset()

    out_off_a, tpot_off_a, _, _, bursts_off_a = pass_a(False)
    seq0 = fl.seq()
    out_m, tpot_m, dr_m, ac_m, bursts_m = pass_a(
        True, draft_engine=de, pipeline=True)
    recs = fl.since(seq0)
    reuse_hits, rollouts = de.reuse_hits, de.rollouts
    de.reset()
    out_ms, tpot_ms, dr_ms, ac_ms, bursts_ms = pass_a(
        True, draft_engine=de, pipeline=False)
    de.reset()
    out_ng, tpot_ng, dr_ng, ac_ng, bursts_ng = pass_a(True)

    # Structural overlap evidence: a "draft" record whose dispatch
    # landed INSIDE a verify record's dispatch->fetch window — the
    # pipeline's whole point, timing-free.
    verify_recs = [r for r in recs if r.get("burst") == "verify"]
    draft_recs = [r for r in recs if r.get("burst") == "draft"]
    overlapped = 0
    for d in draft_recs:
        for v in verify_recs:
            if (v["ts_s"] <= d["ts_s"]
                    <= v["ts_s"] + float(v.get("dur_s", 0.0))):
                overlapped += 1
                break
    overlap_ok = bool(draft_recs) and overlapped == len(draft_recs)

    model_parity = out_m == out_off_a
    sync_parity = out_ms == out_off_a
    ngram_parity = out_ng == out_off_a
    rate_m = ac_m / max(dr_m, 1)
    rate_ng = ac_ng / max(dr_ng, 1)
    log(f"spec A: off {tpot_off_a * 1e3:.2f}ms/tok "
        f"model(pipe) {tpot_m * 1e3:.2f}ms (accept {rate_m:.2f}, "
        f"{overlapped}/{len(draft_recs)} draft dispatches "
        f"overlapped, {reuse_hits} rounds predraft-served) "
        f"model(sync) {tpot_ms * 1e3:.2f}ms "
        f"ngram {tpot_ng * 1e3:.2f}ms (accept {rate_ng:.2f}) "
        f"parity={model_parity}/{sync_parity}/{ngram_parity}")

    # -- Phase B: repetition-heavy workload, n-gram + oracle (PR 8) -------
    # Small vocab => the random model's greedy decode cycles quickly
    # (the repetition-heavy regime); block weights — the decode cost —
    # keep the config's full size.
    cfg_b = dataclasses.replace(llama.CONFIGS[config], vocab_size=16)
    log(f"spec bench B: {config} (vocab 16, repetition-heavy) "
        f"K={spec_k}")
    kw = dict(n_slots=slots, max_len=max_len,
              prompt_buckets=(prompt_len,), kv_int8=kv_int8,
              prefill_chunk=0, prefix_pool=0, max_wave=slots,
              pad_waves=True, spec_k=spec_k)
    if weights_int8:
        from skypilot_tpu.infer import kvcache
        params_b, qw = kvcache.random_quantized_params(cfg_b)
        e_b = eng.InferenceEngine(params_b, cfg_b, qweights=qw, **kw)
    else:
        params_b = llama.init_params(jax.random.key(0), cfg_b)
        e_b = eng.InferenceEngine(params_b, cfg_b, **kw)
    ngram_factory_b = e_b._spec_drafter_factory
    prompts_b = [rng_b.integers(1, cfg_b.vocab_size,
                                prompt_len).tolist()
                 for _ in range(requests)]

    def pass_b(spec_on, factory=None):
        return decode_pass(e_b, prompts_b, spec_on, factory=factory,
                           ngram_factory=ngram_factory_b)

    # Warmup: compile the admission program, the plain burst at the
    # measured size AND the verify program outside any timed window.
    pass_b(False)
    pass_b(True)

    out_off, tpot_off, _, _, bursts_off = pass_b(False)
    out_on, tpot_on, drafted, accepted, bursts_on = pass_b(True)
    oracle = {tuple(p): o for p, o in zip(prompts_b, out_off)}
    out_or, tpot_or, dr_or, ac_or, bursts_or = pass_b(
        True,
        factory=lambda req: _OracleDrafter(oracle[tuple(req.prompt)]))

    parity_ok = out_on == out_off
    oracle_parity_ok = out_or == out_off
    rate = accepted / max(drafted, 1)
    oracle_rate = ac_or / max(dr_or, 1)
    dtoks = sum(len(o) for o in out_off) - len(out_off)
    log(f"spec B: off {tpot_off * 1e3:.2f}ms/tok ({bursts_off} bursts) "
        f"ngram {tpot_on * 1e3:.2f}ms ({bursts_on} bursts, "
        f"accept {rate:.2f}) oracle {tpot_or * 1e3:.2f}ms "
        f"({bursts_or} bursts, accept {oracle_rate:.2f}) "
        f"parity={parity_ok}/{oracle_parity_ok}")
    return {
        # -- Phase A (non-repetitive, model drafter): the headline.
        "backend": jax.default_backend(),
        "model_tpot_off_ms": round(tpot_off_a * 1e3, 3),
        "tpot_model_ms": round(tpot_m * 1e3, 3),
        "tpot_model_sync_ms": round(tpot_ms * 1e3, 3),
        "tpot_ngram_nonrep_ms": round(tpot_ng * 1e3, 3),
        # Wall-clock ratios: bench.py binds the >=1.5x gate on TPU
        # runs only (the kernel-bench precedent — a compute-bound CPU
        # cannot show a memory-bandwidth win); parity and overlap
        # structure gate everywhere.
        "model_speedup": round(tpot_off_a / max(tpot_m, 1e-9), 3),
        "model_sync_speedup": round(tpot_off_a / max(tpot_ms, 1e-9),
                                    3),
        "pipeline_ratio": round(tpot_ms / max(tpot_m, 1e-9), 3),
        "ngram_nonrep_speedup": round(tpot_off_a / max(tpot_ng, 1e-9),
                                      3),
        "model_accept_rate": round(rate_m, 3),
        "model_sync_accept_rate": round(ac_ms / max(dr_ms, 1), 3),
        "ngram_nonrep_accept_rate": round(rate_ng, 3),
        "model_parity_ok": bool(model_parity),
        "model_sync_parity_ok": bool(sync_parity),
        "ngram_nonrep_parity_ok": bool(ngram_parity),
        "overlap_ok": bool(overlap_ok),
        "draft_records": len(draft_recs),
        "draft_reuse_hits": int(reuse_hits),
        "draft_rollouts": int(rollouts),
        "draft_layers": int(draft_layers),
        "bursts_model": int(bursts_m),
        "bursts_model_sync": int(bursts_ms),
        # -- Phase B (repetition-heavy, n-gram + oracle): the PR 8
        # keys, meanings unchanged release over release.
        "tpot_off_ms": round(tpot_off * 1e3, 3),
        "tpot_spec_ms": round(tpot_on * 1e3, 3),
        "tpot_oracle_ms": round(tpot_or * 1e3, 3),
        "speedup": round(tpot_off / max(tpot_on, 1e-9), 3),
        "oracle_speedup": round(tpot_off / max(tpot_or, 1e-9), 3),
        "accept_rate": round(rate, 3),
        "oracle_accept_rate": round(oracle_rate, 3),
        "drafted": int(drafted),
        "accepted": int(accepted),
        "parity_ok": bool(parity_ok),
        "oracle_parity_ok": bool(oracle_parity_ok),
        # Structural (timing-free) evidence the verify path carried
        # the decode: device dispatches per pass.
        "bursts_off": int(bursts_off),
        "bursts_spec": int(bursts_on),
        "bursts_oracle": int(bursts_or),
        "decode_tokens": int(dtoks),
        "spec_k": spec_k,
        "requests": requests,
        "new_tokens": new_tokens,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }


def run_spec_smoke() -> dict:
    """CI-sized spec pass (tier-1 wiring: tests/test_spec_decode.py +
    tests/test_draft_model.py assert parity on every column, oracle
    acceptance == 1.0, model-draft acceptance structure and the
    pipeline-overlap records; wall-clock is reported, never gated, on
    CPU)."""
    return run_spec(smoke=True)


def run_occupancy(config=None, smoke=False, kv_int8=False,
                  weights_int8=False, factor=8, max_burst=4,
                  kv_kernel=False) -> dict:
    """High-occupancy decode sweep: max concurrent decode slots at the
    SAME KV HBM bytes, paged block-table cache vs the contiguous
    layout.

    The workload is the shape paging exists for: requests needing
    max_len/8 rows each (prompt + full token budget) against an engine
    sized for max_len worst cases. The contiguous engine's slot count
    is pinned by HBM/max_len; the paged engine gets the IDENTICAL pool
    bytes ((slots+1) * max_len rows worth of blocks) and ``factor`` x
    the slots — admission itself proves the blocks suffice, and the
    greedy outputs must match the contiguous engine token-for-token
    (the paged-vs-contiguous parity gate, at full occupancy).
    ``serve_blocks_per_token`` reports allocated-block rows per
    resident token at peak (eager allocation: the over-reservation a
    lazy allocator would shave).
    """
    import jax
    import numpy as np

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    cfg = llama.CONFIGS[config]
    max_len = 64 if small else 4096
    kv_block = 8 if small else 256
    plen = 4 if small else 256
    new_tokens = max_len // 8 - plen
    slots_c = 2 if small else 8
    requests = slots_c * factor
    log(f"occupancy bench: {config} max_len={max_len} "
        f"block={kv_block} need={plen + new_tokens} rows/req")

    if weights_int8:
        from skypilot_tpu.infer import kvcache
        params, qw = kvcache.random_quantized_params(cfg)
    else:
        params, qw = llama.init_params(jax.random.key(0), cfg), None
    kw = dict(max_len=max_len, prompt_buckets=(plen,),
              kv_int8=kv_int8, qweights=qw, prefill_chunk=0,
              prefix_pool=0, max_wave=8, pad_waves=True)
    nb = max_len // kv_block
    # kv_kernel: the paged engine reads through the Pallas kernel; the
    # contiguous twin has no block table and falls back to the gather
    # — the parity assert below then spans kernel-vs-gather AND
    # paged-vs-contiguous at once (the PR 9 composition re-run).
    e_paged = eng.InferenceEngine(params, cfg,
                                  n_slots=slots_c * factor,
                                  kv_block=kv_block,
                                  kv_blocks=(slots_c + 1) * nb,
                                  kv_kernel=kv_kernel, **kw)
    e_contig = eng.InferenceEngine(params, cfg, n_slots=slots_c,
                                   kv_block=0, **kw)

    def kv_bytes(e):
        return sum(int(e.cache[n].nbytes)
                   for n in ("k", "v", "k_scale", "v_scale")
                   if n in e.cache)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, plen).tolist()
               for _ in range(requests)]

    def drive(e):
        ids = [e.add_request(p, max_new_tokens=new_tokens)
               for p in prompts]
        peak, bpt = 0, None
        while e.waiting or e.chunking or e.slot_req:
            # Occupancy is sampled right after admission — before the
            # decode burst can retire short requests — so the peak is
            # the number of requests the cache actually held at once.
            e.admit()
            while e.chunking:
                e.prefill_chunk_step()
            occ = len(e.slot_req)
            if occ >= peak:
                peak = occ
                if e.paged:
                    toks = sum(len(r.prompt) + len(r.tokens)
                               for r in e.slot_req.values())
                    bpt = (e.blocks_used * e.kv_block
                           / max(toks, 1))
            e.decode_burst(max_burst=max_burst)
        by_rid = {r.rid: r.tokens for r in e.finished}
        e.finished.clear()
        return [by_rid[i] for i in ids], peak, bpt

    out_c, peak_c, _ = drive(e_contig)
    out_p, peak_p, bpt = drive(e_paged)
    parity_ok = out_p == out_c
    leak_free = e_paged.blocks_used == 0
    bytes_p, bytes_c = kv_bytes(e_paged), kv_bytes(e_contig)
    occupancy_x = peak_p / max(peak_c, 1)
    log(f"occupancy: contiguous {peak_c} slots vs paged {peak_p} "
        f"at {bytes_p / 1e6:.1f} MB KV ({occupancy_x:.1f}x, "
        f"parity={parity_ok})")
    return {
        "kv_hbm_bytes": bytes_p,
        "kv_hbm_bytes_contiguous": bytes_c,
        "same_hbm": bool(bytes_p == bytes_c),
        "paged_slots": peak_p,
        "contiguous_slots": peak_c,
        "occupancy_x": round(occupancy_x, 2),
        "blocks_per_token": round(bpt, 3) if bpt else None,
        "kv_block": kv_block,
        "parity_ok": bool(parity_ok),
        "leak_free": bool(leak_free),
        # Acceptance bar: >= 4x concurrent slots at equal KV HBM.
        "occupancy_regressed": bool(occupancy_x < 4 or not parity_ok
                                    or bytes_p != bytes_c),
        "requests": requests,
        "max_len": max_len,
        "new_tokens": new_tokens,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
        "kv_kernel": bool(kv_kernel),
    }


def run_span(config=None, requests=None, prompt_len=None,
             new_tokens=None, max_burst=8, kv_int8=False,
             weights_int8=False, spec_k=0, smoke=False,
             kv_kernel=False) -> dict:
    """Span-bucketed decode attention bench: span-on vs full-view
    decode TPOT on the SAME engine (same weights, same block pool —
    the ladder is host-side dispatch state, so toggling it only
    routes bursts to differently-sliced compiled programs), greedy
    parity asserted.

    Workload: the shape span bucketing exists for — SHORT active
    conversations on a LONG-max_len engine. Every request needs
    <= max_len/8 rows; the full-view baseline still gathers max_len
    rows per slot per layer per burst step, the span path gathers the
    active bucket. TTFT is out of scope: span selection touches only
    the decode/verify/chunk big-cache read (admission waves are
    span-free).

    ``spec_k``: run the comparison through the verify path instead of
    plain bursts (the span x spec composition). ``smoke=True``:
    CI-sized — parity and dispatch structure are asserted in tier-1
    (tests/test_span_attn.py); wall-clock is reported, gated only by
    bench.py on hardware.
    """
    import time as _time

    import jax
    import numpy as np

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    cfg = llama.CONFIGS[config]
    max_len = 2048 if small else 4096
    kv_block = 64 if small else 256
    if requests is None:
        requests = 8
    if prompt_len is None:
        prompt_len = 16 if small else 128
    if new_tokens is None:
        new_tokens = 96 if small else 256
    slots = requests
    need = prompt_len + new_tokens + (spec_k + 1 if spec_k else 0)
    assert need <= max_len // 8, "workload must fit the smallest rungs"
    log(f"span bench: {config} max_len={max_len} block={kv_block} "
        f"active<={need} rows/req requests={requests}")

    kw = dict(n_slots=slots, max_len=max_len,
              prompt_buckets=(prompt_len,), kv_int8=kv_int8,
              prefill_chunk=0, prefix_pool=0, max_wave=slots,
              pad_waves=True, kv_block=kv_block, spec_k=spec_k,
              kv_kernel=kv_kernel)
    if weights_int8:
        from skypilot_tpu.infer import kvcache
        params, qw = kvcache.random_quantized_params(cfg)
        e = eng.InferenceEngine(params, cfg, qweights=qw, **kw)
    else:
        params = llama.init_params(jax.random.key(0), cfg)
        e = eng.InferenceEngine(params, cfg, **kw)
    ladder = e.span_ladder
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]

    def decode_pass(span_on):
        """One admit-then-decode pass; TPOT over the decode loop only
        (admission is span-free). Returns (outputs, tpot_s, rows)
        where rows is the largest span actually dispatched."""
        e.span_ladder = ladder if span_on else (e.max_len,)
        e.decode_programs.clear()
        ids = [e.add_request(p, max_new_tokens=new_tokens)
               for p in prompts]
        e.admit()
        t0 = _time.time()
        while e.slot_req:
            e.decode_burst(max_burst)
        float(e.cache["length"][0])     # honest host sync
        wall = _time.time() - t0
        by_rid = {r.rid: list(r.tokens) for r in e.finished}
        outs = [by_rid[i] for i in ids]
        e.finished.clear()
        rows = max((s if s is not None else e.max_len)
                   for _, _, s in e.decode_programs)
        dtoks = sum(len(o) for o in outs) - len(outs)
        return outs, wall / max(dtoks, 1), rows

    # Warmup compiles both modes' programs outside the timed window.
    decode_pass(False)
    decode_pass(True)

    out_full, tpot_full, rows_full = decode_pass(False)
    out_span, tpot_span, rows_span = decode_pass(True)
    e.span_ladder = ladder
    parity_ok = out_span == out_full
    # Dispatch structure (timing-free): the span pass must actually
    # have read a fraction of the full view, with a ladder-bounded
    # program count.
    n_programs = len(e.decode_programs)
    log(f"span: full {tpot_full * 1e3:.2f}ms/tok ({rows_full} rows) "
        f"span {tpot_span * 1e3:.2f}ms ({rows_span} rows, "
        f"{n_programs} programs) parity={parity_ok}")
    return {
        "tpot_full_ms": round(tpot_full * 1e3, 3),
        "tpot_span_ms": round(tpot_span * 1e3, 3),
        # Wall-clock decode ratio — the regression gate input
        # (bench.py gates >= 1.5x on hardware; the tentpole target
        # is 2x for active lengths <= max_len/8).
        "speedup": round(tpot_full / max(tpot_span, 1e-9), 3),
        "rows_full": int(rows_full),
        "rows_span": int(rows_span),
        "rows_ratio": round(rows_full / max(rows_span, 1), 2),
        "span_ladder": list(ladder),
        "n_span_programs": int(n_programs),
        "parity_ok": bool(parity_ok),
        "max_len": max_len,
        "kv_block": kv_block,
        "requests": requests,
        "new_tokens": new_tokens,
        "spec_k": spec_k,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
        "kv_kernel": bool(kv_kernel),
    }


def run_span_smoke() -> dict:
    """CI-sized span pass (tier-1 wiring: tests/test_span_attn.py
    asserts parity and the rows/program structure; wall-clock is
    reported, never gated, on CPU)."""
    return run_span(smoke=True)


def run_kernel(config=None, requests=None, prompt_len=None,
               new_tokens=None, max_burst=8, kv_int8=False,
               weights_int8=False, spec_k=0, smoke=False) -> dict:
    """Pallas paged decode-attention kernel bench: kernel-vs-gather
    decode TPOT on the SAME engine (the kernel flag is a static jit
    argument — flipping it routes bursts to the other compiled
    program; weights, block pool and RNG stream are shared), greedy
    parity asserted against the gather oracle.

    Workload: LOW occupancy-utilization — a few active requests on an
    engine sized for many slots. The gather path materializes the
    [slots, span, G, hd] logical view per layer per burst step
    REGARDLESS of how many slots are active, so its fixed per-burst
    transient cost is amortized over the fewest tokens exactly here;
    the kernel never builds the view, which is the whole win.

    ``smoke=True`` / CPU: the kernel runs in Pallas interpret mode —
    parity and program identity (compile-watch keys carry
    ``kernel=True``) are the asserts; wall-clock is reported but
    MEANINGLESS on interpret (gated only by bench.py on real TPU
    runs). Full (hardware) mode additionally re-runs the span and
    occupancy benches under the kernel, confirming the PR 9 gates
    still hold on the kernel path.
    """
    import time as _time

    import jax
    import numpy as np

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    cfg = llama.CONFIGS[config]
    max_len = 256 if small else 4096
    kv_block = 32 if small else 256
    slots = 8 if small else 16
    if requests is None:
        requests = 2 if small else 4
    if prompt_len is None:
        prompt_len = 8 if small else 128
    if new_tokens is None:
        new_tokens = 16 if small else 256
    log(f"kernel bench: {config} max_len={max_len} block={kv_block} "
        f"slots={slots} active={requests} (low occupancy)")

    kw = dict(n_slots=slots, max_len=max_len,
              prompt_buckets=(prompt_len,), kv_int8=kv_int8,
              prefill_chunk=0, prefix_pool=0, max_wave=slots,
              pad_waves=True, kv_block=kv_block, spec_k=spec_k,
              kv_kernel=True)
    if weights_int8:
        from skypilot_tpu.infer import kvcache
        params, qw = kvcache.random_quantized_params(cfg)
        e = eng.InferenceEngine(params, cfg, qweights=qw, **kw)
    else:
        params = llama.init_params(jax.random.key(0), cfg)
        e = eng.InferenceEngine(params, cfg, **kw)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]

    def decode_pass(kernel_on):
        """One admit-then-decode pass; TPOT over the decode loop only
        (admission is kernel-free: prefill waves never read the big
        cache)."""
        e.kv_kernel = kernel_on
        ids = [e.add_request(p, max_new_tokens=new_tokens)
               for p in prompts]
        e.admit()
        t0 = _time.time()
        while e.slot_req:
            e.decode_burst(max_burst)
        float(e.cache["length"][0])     # honest host sync
        wall = _time.time() - t0
        by_rid = {r.rid: list(r.tokens) for r in e.finished}
        outs = [by_rid[i] for i in ids]
        e.finished.clear()
        dtoks = sum(len(o) for o in outs) - len(outs)
        return outs, wall / max(dtoks, 1)

    # Warmup compiles both modes' programs outside the timed window.
    decode_pass(False)
    decode_pass(True)

    out_gather, tpot_gather = decode_pass(False)
    out_kernel, tpot_kernel = decode_pass(True)
    e.kv_kernel = True
    parity_ok = out_kernel == out_gather
    # Program identity: the kernel flag must live in the compile-watch
    # keys (never a retrace surface — both values were warmed above).
    keys = e.compile_watch.summary()
    kernel_programs_ok = (
        any("kernel=True" in k for k in keys)
        and any("kernel=False" in k for k in keys))
    speedup = tpot_gather / max(tpot_kernel, 1e-9)
    log(f"kernel: gather {tpot_gather * 1e3:.2f}ms/tok kernel "
        f"{tpot_kernel * 1e3:.2f}ms/tok ({speedup:.2f}x, "
        f"parity={parity_ok}, backend={jax.default_backend()})")
    out = {
        "tpot_gather_ms": round(tpot_gather * 1e3, 3),
        "tpot_kernel_ms": round(tpot_kernel * 1e3, 3),
        # Informational on CPU (interpret mode); gated on TPU runs.
        "speedup": round(speedup, 3),
        "parity_ok": bool(parity_ok),
        "kernel_programs_ok": bool(kernel_programs_ok),
        "backend": jax.default_backend(),
        "active_requests": requests,
        "slots": slots,
        "max_len": max_len,
        "kv_block": kv_block,
        "span_ladder": list(e.span_ladder),
        "new_tokens": new_tokens,
        "spec_k": spec_k,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }
    if not small:
        # The PR 9 gates, re-run on the kernel path (hardware only:
        # interpret-mode wall-clock would drown the comparison).
        sa = run_span(config=config, kv_int8=kv_int8,
                      weights_int8=weights_int8, kv_kernel=True)
        out["span_under_kernel_speedup"] = sa["speedup"]
        out["span_under_kernel_parity_ok"] = sa["parity_ok"]
        oc = run_occupancy(config=config, kv_int8=kv_int8,
                           weights_int8=weights_int8, kv_kernel=True)
        out["occupancy_under_kernel_x"] = oc["occupancy_x"]
        out["occupancy_under_kernel_ok"] = (
            not oc["occupancy_regressed"])
    return out


def run_kernel_smoke() -> dict:
    """CI-sized kernel pass (tier-1 wiring: tests/test_paged_attention
    .py asserts parity and program identity; interpret-mode wall-clock
    is reported, never gated, on CPU)."""
    return run_kernel(smoke=True)


def run_adapters(config=None, n_adapters=8, requests=None,
                 prompt_len=None, new_tokens=None, max_burst=8,
                 kv_int8=False, weights_int8=False, spec_k=0,
                 smoke=False) -> dict:
    """Multi-LoRA adapter-catalog bench (docs/serving.md §Adapter
    catalog): N-adapters-vs-1 decode TPOT overhead on the SAME engine.

    Three phases, one engine:

    1. BASELINE — every request generates under ONE fine-tune
       (decode gathers one pool slot's (A, B) per layer).
    2. MIXED — the same requests spread over ``n_adapters``
       fine-tunes in one continuous batch. The gather indexes differ;
       the program is IDENTICAL (adapter id is slot data, exactly like
       the span rung), so the overhead gate (bench.py:
       ``serve_adapter_overhead`` <= 1.15x) is pure gather cost.
       Greedy parity is asserted against per-request sequential runs
       — a mixed batch must emit exactly what each fine-tune emits
       alone.
    3. HOT-LOAD CHURN — more fine-tunes than pool slots cycle through
       traffic under ``declare_warmup_complete``: every demand load is
       an LRU evict + install DISPATCH, and the compile watch gates
       ZERO unexpected compiles (adapter count/identity never enters
       program identity — the ROADMAP item 5 watch item).

    ``smoke=True`` / CPU: CI-sized; wall-clock is reported, the 1.15x
    gate binds via bench.py (structure/parity/compile gates bind
    everywhere).
    """
    import time as _time

    import jax
    import numpy as np

    from skypilot_tpu.infer import adapters as ad_lib
    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    cfg = llama.CONFIGS[config]
    rank = 4 if small else 16
    if requests is None:
        requests = n_adapters if small else 2 * n_adapters
    if prompt_len is None:
        prompt_len = 16 if small else 128
    if new_tokens is None:
        new_tokens = 32 if small else 256
    slots = requests
    max_len = 256 if small else 2048
    log(f"adapter bench: {config} rank={rank} n_adapters={n_adapters} "
        f"requests={requests}")

    catalog = ad_lib.AdapterCatalog(cfg, n_adapters=n_adapters + 1,
                                    rank=rank)
    shapes = ad_lib.target_shapes(cfg, rank)
    L = cfg.n_layers
    # Registered fine-tunes: n_adapters for the mixed phase plus as
    # many again for the churn phase (they cannot all be resident).
    names = [f"ft-{i}" for i in range(2 * n_adapters)]
    for i, name in enumerate(names):
        r = np.random.default_rng(100 + i)
        catalog.register(name, params={
            t: {"a": r.normal(size=(L,) + sa).astype(np.float32) * 0.02,
                "b": r.normal(size=(L,) + sb).astype(np.float32) * 0.02}
            for t, (sa, sb) in shapes.items()})

    kw = dict(n_slots=slots, max_len=max_len,
              prompt_buckets=(prompt_len,), kv_int8=kv_int8,
              prefill_chunk=0, prefix_pool=0, max_wave=slots,
              pad_waves=True, spec_k=spec_k, adapters=catalog)
    if weights_int8:
        from skypilot_tpu.infer import kvcache
        params, qw = kvcache.random_quantized_params(cfg)
        e = eng.InferenceEngine(params, cfg, qweights=qw, **kw)
    else:
        params = llama.init_params(jax.random.key(0), cfg)
        e = eng.InferenceEngine(params, cfg, **kw)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]

    # Production startup: pre-compile the grid (incl. the adapter
    # gather + hot-load programs), then arm the compile watch — every
    # phase below runs under the zero-unexpected-compiles contract.
    e.warm_programs(max_burst=max_burst)
    e.declare_warmup_complete()

    def decode_pass(adapter_names):
        ids = [e.add_request(p, max_new_tokens=new_tokens, adapter=a)
               for p, a in zip(prompts, adapter_names)]
        e.admit()
        t0 = _time.time()
        while e.slot_req:
            e.decode_burst(max_burst)
        float(e.cache["length"][0])     # honest host sync
        wall = _time.time() - t0
        by_rid = {r.rid: list(r.tokens) for r in e.finished}
        outs = [by_rid[i] for i in ids]
        e.finished.clear()
        dtoks = sum(len(o) for o in outs) - len(outs)
        return outs, wall / max(dtoks, 1)

    single = [names[0]] * requests
    mixed = [names[i % n_adapters] for i in range(requests)]

    # Warm both gather patterns' caches/adapters outside the window.
    decode_pass(single)
    decode_pass(mixed)

    out_single, tpot_single = decode_pass(single)
    out_mixed, tpot_mixed = decode_pass(mixed)

    # Greedy parity: the mixed batch must emit exactly what each
    # fine-tune emits alone (sequential single-request passes).
    parity_ok = True
    for p, a, want in zip(prompts, mixed, out_mixed):
        rid = e.add_request(p, max_new_tokens=new_tokens, adapter=a)
        e.admit()
        while e.slot_req:
            e.decode_burst(max_burst)
        got = {r.rid: list(r.tokens) for r in e.finished}[rid]
        e.finished.clear()
        if got != want:
            parity_ok = False
            break

    # Hot-load churn: cycle through 2x the pool's fine-tunes under
    # live decode — every wave demand-loads (LRU evict + install),
    # and nothing may compile.
    loads_before = catalog.loads
    for i in range(0, len(names), n_adapters):
        batch = [names[(i + j) % len(names)]
                 for j in range(min(n_adapters, requests))]
        for p, a in zip(prompts, batch):
            e.add_request(p, max_new_tokens=4, adapter=a)
        e.run_to_completion()
        e.finished.clear()
    churn_loads = catalog.loads - loads_before
    unexpected = list(e.compile_watch.unexpected)

    overhead = tpot_mixed / max(tpot_single, 1e-9)
    log(f"adapters: single {tpot_single * 1e3:.2f}ms/tok mixed "
        f"{tpot_mixed * 1e3:.2f}ms/tok (x{overhead:.3f}) "
        f"parity={parity_ok} churn_loads={churn_loads} "
        f"evictions={catalog.evictions} unexpected={len(unexpected)}")
    return {
        "tpot_single_ms": round(tpot_single * 1e3, 3),
        "tpot_mixed_ms": round(tpot_mixed * 1e3, 3),
        # The regression-gate input: bench.py gates <= 1.15x.
        "overhead_ratio": round(overhead, 3),
        "parity_ok": bool(parity_ok),
        "hot_loads": int(churn_loads),
        "evictions": int(catalog.evictions),
        "unexpected_compiles": len(unexpected),
        "n_adapters": n_adapters,
        "rank": rank,
        "requests": requests,
        "new_tokens": new_tokens,
        "spec_k": spec_k,
        "backend": jax.default_backend(),
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }


def run_adapters_smoke() -> dict:
    """CI-sized adapter-catalog pass (tier-1 wiring:
    tests/test_adapters.py asserts parity, churn and the
    zero-compile contract; CPU wall-clock is reported, the 1.15x
    TPOT gate binds via bench.py)."""
    return run_adapters(smoke=True, n_adapters=4)


def run_flight(config=None, requests=None, new_tokens=None,
               max_burst=8, spec_k=4, kv_int8=False,
               weights_int8=False, smoke=False) -> dict:
    """Flight recorder + compile watch bench over the FULL mixed
    workload: chunked admission with prefix reuse + speculative decode
    + span selection, on a paged engine AND a contiguous twin.

    Per layout:

      1. ``warm_programs()`` sweeps the program grid, one untimed
         workload pass covers anything workload-specific, then the
         engine declares warmup complete — the production startup
         sequence (`--warm-grid`).
      2. The TIMED window runs the same mixed workload and asserts
         the introspection contract: ``unexpected_compiles == 0``
         (nothing compiled mid-traffic), and every decode/verify
         program the engine selected (``decode_programs``) has flight
         records whose program identity matches — and vice versa
         (records never claim a program the engine didn't dispatch).
      3. Recorder-on vs recorder-off passes measure the no-op-guard
         overhead (``overhead_ratio``; greedy outputs must be
         identical — recording can never perturb generation).

    ``smoke=True``: CI-sized — structure and the zero-unexpected gate
    are asserted in tier-1 (tests/test_flight.py); the <1% overhead
    bound is gated only by bench.py on hardware (CPU wall-clock noise
    swamps it).
    """
    import dataclasses
    import time as _time

    import jax
    import numpy as np

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.models import llama
    from skypilot_tpu.observability import flight as flight_lib

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    if requests is None:
        requests = 6 if small else 16
    if new_tokens is None:
        new_tokens = 24 if small else 128
    max_len = 256 if small else 2048
    chunk = 24 if small else 256
    kv_block = 32 if small else 128   # not dividing chunk-aligned
    #                                   prefixes cleanly -> COW runs
    short_len, long_a, long_b = (12, 60, 72) if small \
        else (96, 640, 768)
    shared = 2 * chunk                # chunk-aligned shared prefix
    slots = requests
    # Small vocab: the random model's greedy decode cycles, so the
    # n-gram drafter actually drafts (the run_spec regime).
    cfg = dataclasses.replace(llama.CONFIGS[config], vocab_size=16)
    rng = np.random.default_rng(0)
    base = rng.integers(1, cfg.vocab_size, shared).tolist()
    prompts = (
        [rng.integers(1, cfg.vocab_size, short_len).tolist()
         for _ in range(requests - 4)]
        + [base + rng.integers(1, cfg.vocab_size,
                               long_a - shared).tolist(),
           base + rng.integers(1, cfg.vocab_size,
                               long_b - shared).tolist()] * 2)
    log(f"flight bench: {config} (vocab 16) max_len={max_len} "
        f"chunk={chunk} block={kv_block} K={spec_k} "
        f"requests={len(prompts)}")

    def build(paged):
        kw = dict(n_slots=slots, max_len=max_len,
                  prompt_buckets=(16 if small else 128, max_len),
                  kv_int8=kv_int8, prefill_chunk=chunk,
                  prefix_pool=4, max_wave=slots, pad_waves=True,
                  spec_k=spec_k, kv_block=kv_block if paged else 0,
                  flight_recorder=flight_lib.FlightRecorder())
        if weights_int8:
            from skypilot_tpu.infer import kvcache
            params, qw = kvcache.random_quantized_params(cfg)
            return eng.InferenceEngine(params, cfg, qweights=qw, **kw)
        params = llama.init_params(jax.random.key(0), cfg)
        return eng.InferenceEngine(params, cfg, **kw)

    def workload(e):
        ids = [e.add_request(p, max_new_tokens=new_tokens)
               for p in prompts]
        t0 = _time.time()
        e.run_to_completion(max_burst)
        wall = _time.time() - t0
        by_rid = {r.rid: list(r.tokens) for r in e.finished}
        outs = [by_rid[i] for i in ids]
        e.finished.clear()
        toks = sum(len(o) for o in outs)
        return outs, wall / max(toks, 1)

    layouts = {}
    for paged in (True, False):
        e = build(paged)
        rec = e.flight
        # Production startup: grid sweep + one untimed workload pass,
        # then arm the watch.
        warmed = e.warm_programs(max_burst=max_burst)
        workload(e)
        warm_compile_s = e.compile_watch.total_compile_s()
        e.declare_warmup_complete()
        # Timed window.
        e.decode_programs.clear()
        seq0 = rec.seq()
        out_on, tpot_on = workload(e)
        window = rec.since(seq0)
        unexpected = list(e.compile_watch.unexpected)
        # Coverage: flight-record program identity <-> the programs
        # the engine actually selected, both directions.
        rec_dv = {(r["program"]["k"], r["program"]["span"])
                  for r in window if r["burst"] in ("decode",
                                                    "verify")}
        eng_dv = {(k, s) for kind, k, s in e.decode_programs
                  if kind in ("burst", "verify")}
        n_chunks = sum(1 for r in window if r["burst"] == "chunk")
        n_waves = sum(1 for r in window if r["burst"] == "wave")
        coverage_ok = (rec_dv == eng_dv and n_chunks > 0
                       and n_waves > 0)
        # Recorder-off guard: same workload, recorder disabled —
        # identical greedy output, best-of TPOT for the ratio.
        rec.enabled = False
        out_off, tpot_off = workload(e)
        rec.enabled = True
        _, tpot_on2 = workload(e)
        rec.enabled = False
        _, tpot_off2 = workload(e)
        rec.enabled = True
        tpot_on = min(tpot_on, tpot_on2)
        tpot_off = min(tpot_off, tpot_off2)
        # Calibration parity: every pass above ran with the device-time
        # calibrator at its default cadence (the bracket rides the
        # compile-watch hit path whether or not the recorder is on), so
        # overhead_ratio already prices calibration into BOTH sides.
        # Here the off-switch itself is gated: SKYTPU_DEVTIME_EVERY=0
        # must produce bit-identical greedy tokens — the bracket only
        # ever observes, never perturbs.
        cal_samples = e.devtime.samples
        prev_every = os.environ.get("SKYTPU_DEVTIME_EVERY")
        os.environ["SKYTPU_DEVTIME_EVERY"] = "0"
        try:
            out_nocal, _ = workload(e)
        finally:
            if prev_every is None:
                os.environ.pop("SKYTPU_DEVTIME_EVERY", None)
            else:
                os.environ["SKYTPU_DEVTIME_EVERY"] = prev_every
        # Forensics guard: the request-ledger machinery (stall-episode
        # bookkeeping, the retire record, the P^2 tail observe) rides
        # the retire path. The timed window above ran forensics-ON (the
        # default), so measure the off side the same best-of-two way.
        # Off must be bit-identical greedy output — forensics observes
        # retirement, it never steers scheduling.
        e.forensics = False
        out_foff, tpot_foff = workload(e)
        e.forensics = True
        _, tpot_fon = workload(e)
        e.forensics = False
        _, tpot_foff2 = workload(e)
        e.forensics = True
        tpot_fon = min(tpot_on, tpot_fon)
        tpot_foff = min(tpot_foff, tpot_foff2)
        layouts["paged" if paged else "contig"] = {
            "programs_warmed": warmed,
            "warmup_compile_s": round(warm_compile_s, 3),
            "unexpected_compiles": len(unexpected),
            "unexpected": unexpected,
            "coverage_ok": bool(coverage_ok),
            "parity_ok": bool(out_on == out_off),
            "calibration_parity_ok": bool(out_nocal == out_on),
            "calibration_samples": int(cal_samples),
            "n_records": len(window),
            "n_chunk_records": n_chunks,
            "n_wave_records": n_waves,
            "tpot_on_ms": round(tpot_on * 1e3, 3),
            "tpot_off_ms": round(tpot_off * 1e3, 3),
            "overhead_ratio": round(tpot_on / max(tpot_off, 1e-9), 4),
            "forensics_parity_ok": bool(out_foff == out_on),
            "tpot_forensics_on_ms": round(tpot_fon * 1e3, 3),
            "tpot_forensics_off_ms": round(tpot_foff * 1e3, 3),
            "forensics_overhead_ratio": round(
                tpot_fon / max(tpot_foff, 1e-9), 4),
        }
        log(f"flight {'paged' if paged else 'contig'}: "
            f"{layouts['paged' if paged else 'contig']}")
    agg = {
        "warmup_compile_s": round(
            sum(v["warmup_compile_s"] for v in layouts.values()), 3),
        "unexpected_compiles": sum(v["unexpected_compiles"]
                                   for v in layouts.values()),
        "coverage_ok": all(v["coverage_ok"] for v in layouts.values()),
        "parity_ok": all(v["parity_ok"] for v in layouts.values()),
        "calibration_parity_ok": all(v["calibration_parity_ok"]
                                     for v in layouts.values()),
        "calibration_samples": sum(v["calibration_samples"]
                                   for v in layouts.values()),
        "n_records": sum(v["n_records"] for v in layouts.values()),
        # Worst layout: the gate must catch a recorder change that
        # slows only one of the two decode paths.
        "overhead_ratio": max(v["overhead_ratio"]
                              for v in layouts.values()),
        "forensics_parity_ok": all(v["forensics_parity_ok"]
                                   for v in layouts.values()),
        "forensics_overhead_ratio": max(v["forensics_overhead_ratio"]
                                        for v in layouts.values()),
        "layouts": layouts,
        "config": config,
        "spec_k": spec_k,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }
    return agg


def run_flight_smoke() -> dict:
    """CI-sized flight pass (tier-1 wiring: tests/test_flight.py
    asserts the zero-unexpected + coverage structure; overhead is
    reported, never gated, on CPU)."""
    return run_flight(smoke=True)


def run_qos(config=None, slots=None, bg_requests=None,
            hot_requests=None, new_tokens=None, max_burst=8,
            kv_int8=False, weights_int8=False, smoke=False) -> dict:
    """Multi-tenant QoS bench: weighted-fair-queueing isolation under a
    hot tenant, and preemption-by-eviction greedy parity.

    Two phases on CI-sized engines (docs/serving.md §Multi-tenant
    QoS):

    1. **Fairness** — a background tenant's requests run (a) alone
       (idle), (b) behind a hot tenant's flood under WFQ, and (c) the
       same flood under plain FIFO (the control). Gates: background
       TPOT p99 under contention <= 1.3x idle while the hot tenant
       queues, and — the structural win — WFQ admits the background
       tenant ahead of the flood while FIFO strands it
       (``bg_ttft_fifo_ratio`` shows the damage WFQ undoes).

    2. **Preemption parity** — a low-priority request is evicted
       mid-decode by a high-priority arrival (1-slot engine: eviction
       is the only way in), resumes warm from the prefix cache, and
       must produce BIT-IDENTICAL greedy output to an unpreempted run
       — across {fp32, int8 KV} x {spec-on, spec-off} on the paged
       layout (``smoke=True`` runs the fp32 pair only; tests/test_qos
       .py covers the full matrix). Zero leaked blocks after retire +
       cache clear (allocator audit) is asserted, not reported.
    """
    import dataclasses
    import time as _time

    import jax
    import numpy as np

    from skypilot_tpu.infer import engine as eng
    from skypilot_tpu.infer import qos as qos_lib
    from skypilot_tpu.models import llama

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    slots = slots or (4 if small else 8)
    bg_requests = bg_requests or 2
    hot_requests = hot_requests or (3 * slots)
    new_tokens = new_tokens or (16 if small else 64)
    prompt_len = 12
    max_len = 64 if small else 256
    cfg = llama.CONFIGS[config]
    log(f"qos bench: {config} slots={slots} bg={bg_requests} "
        f"hot={hot_requests} new_tokens={new_tokens}")

    def build(n_slots, qos=None, spec_k=0, chunk=0, pool=0,
              buckets=None, kv_int8=kv_int8):
        kw = dict(n_slots=n_slots, max_len=max_len,
                  prompt_buckets=buckets or (prompt_len,),
                  kv_int8=kv_int8, prefill_chunk=chunk,
                  prefix_pool=pool, max_wave=n_slots, pad_waves=True,
                  spec_k=spec_k, qos=qos)
        if weights_int8:
            from skypilot_tpu.infer import kvcache
            params, qw = kvcache.random_quantized_params(cfg)
            return eng.InferenceEngine(params, cfg, qweights=qw, **kw)
        params = llama.init_params(jax.random.key(0), cfg)
        return eng.InferenceEngine(params, cfg, **kw)

    rng = np.random.default_rng(0)
    bg_prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                  for _ in range(bg_requests)]
    hot_prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(hot_requests)]

    def fairness_pass(e, with_hot):
        """Hot flood enqueued FIRST (worst case for the background
        tenant), then background; per-background-request TTFT and
        TPOT collected at retirement."""
        ids = []
        if with_hot:
            for p in hot_prompts:
                e.add_request(p, max_new_tokens=new_tokens,
                              tenant="hot")
        for p in bg_prompts:
            ids.append(e.add_request(p, max_new_tokens=new_tokens,
                                     tenant="background"))
        done_s: dict = {}
        while e.waiting or e.chunking or e.slot_req:
            e.step_burst(max_burst)
            now = _time.time()
            for r in e.finished:
                done_s.setdefault(r.rid, now)
        by_rid = {r.rid: r for r in e.finished}
        ttfts, tpots = [], []
        for rid in ids:
            r = by_rid[rid]
            ttfts.append(r.first_token_s - r.submit_s)
            if len(r.tokens) > 1:
                tpots.append((done_s[rid] - r.first_token_s)
                             / (len(r.tokens) - 1))
        outs = [by_rid[i].tokens for i in ids]
        e.finished.clear()
        return ttfts, tpots, outs

    # Warmup compiles, then idle / WFQ-contended / FIFO-contended on
    # fresh schedulers (bucket state must not leak between passes).
    e = build(slots, qos=qos_lib.FairScheduler())
    fairness_pass(e, with_hot=False)
    idle_ttft, idle_tpot, idle_out = fairness_pass(e, with_hot=False)
    e.qos = qos_lib.FairScheduler()
    wfq_ttft, wfq_tpot, wfq_out = fairness_pass(e, with_hot=True)
    e.qos = None
    fifo_ttft, _fifo_tpot, fifo_out = fairness_pass(e, with_hot=True)

    # Scheduling must never change tokens: same engine, same greedy
    # stream per request.
    sched_parity = (idle_out == wfq_out == fifo_out)
    fairness_ratio = _p99(wfq_tpot) / max(_p99(idle_tpot), 1e-9)
    ttft_wfq_ratio = _p99(wfq_ttft) / max(_p99(idle_ttft), 1e-9)
    ttft_fifo_ratio = _p99(fifo_ttft) / max(_p99(idle_ttft), 1e-9)
    log(f"qos fairness: bg TPOT p99 x{fairness_ratio:.2f} vs idle "
        f"(bg TTFT p99 x{ttft_wfq_ratio:.1f} wfq / "
        f"x{ttft_fifo_ratio:.1f} fifo), sched parity={sched_parity}")

    # Phase 2: preemption-by-eviction parity. 1-slot engine, chunked
    # prefill + prefix cache on (the warm-resume path), high-priority
    # arrival evicts the low-priority resident mid-decode.
    # The full run sweeps the kv dtype too — {fp32, int8} x
    # {spec-off, spec-on}, the acceptance matrix; smoke (and a run
    # pinned by --kv-int8, whose fairness phase already chose its
    # dtype) runs only that dtype's spec pair.
    dtypes = [kv_int8] if (smoke or kv_int8) else [False, True]
    combos = [(k, i8) for i8 in dtypes for k in (0, 4)]
    parity_ok = True
    preemptions = 0
    resumed_rows = 0
    low_prompt = list(range(5, 5 + prompt_len))
    hi_prompt = [3, 1, 4]
    for spec_k, i8 in combos:
        ref = build(1, chunk=8, pool=4, spec_k=spec_k, kv_int8=i8,
                    buckets=(prompt_len + new_tokens + 8,))
        want = ref.generate([low_prompt],
                            max_new_tokens=new_tokens)[0]
        e2 = build(1, qos=qos_lib.FairScheduler(), chunk=8, pool=4,
                   spec_k=spec_k, kv_int8=i8,
                   buckets=(prompt_len + new_tokens + 8,))
        rid_low = e2.add_request(low_prompt,
                                 max_new_tokens=new_tokens,
                                 priority=0)
        while not e2.slot_req:
            e2.step_burst(max_burst=2)
        for _ in range(2):
            e2.decode_burst(max_burst=2)
        e2.add_request(hi_prompt, max_new_tokens=4, priority=1)
        e2.run_to_completion(max_burst=2)
        by_rid = {r.rid: r for r in e2.finished}
        low = by_rid[rid_low]
        parity_ok = parity_ok and (low.tokens == want
                                   and low.preemptions >= 1)
        preemptions += low.preemptions
        resumed_rows += low.resumed_len
        e2.clear_prefix_cache()
        assert e2.allocator.used == 0, (
            f"block leak after preemption cycle: {e2.allocator.used}")
    log(f"qos preempt: parity={parity_ok} preemptions={preemptions} "
        f"resumed_rows={resumed_rows}")

    return {
        "fairness_ratio": round(fairness_ratio, 3),
        "bg_tpot_idle_p99_ms": round(_p99(idle_tpot) * 1e3, 3),
        "bg_tpot_contended_p99_ms": round(_p99(wfq_tpot) * 1e3, 3),
        "bg_ttft_wfq_ratio": round(ttft_wfq_ratio, 3),
        "bg_ttft_fifo_ratio": round(ttft_fifo_ratio, 3),
        "sched_parity_ok": bool(sched_parity),
        "preempt_parity_ok": bool(parity_ok),
        "preemptions": int(preemptions),
        "preempt_resumed_rows": int(resumed_rows),
        "slots": slots,
        "bg_requests": bg_requests,
        "hot_requests": hot_requests,
        "new_tokens": new_tokens,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }


def run_qos_smoke() -> dict:
    """CI-sized QoS pass (tier-1 wiring: tests/test_qos.py asserts
    scheduling + preemption parity and the fairness structure;
    wall-clock ratios are reported, gated only on hardware)."""
    return run_qos(smoke=True)


def _ndjson_objs(body):
    """The NDJSON objects in a raw chunked response body. The server
    writes one JSON line per chunk, so splitting on newlines recovers
    the lines; the hex chunk-size framing lines are dropped (some hex
    strings parse as JSON numbers — only dicts survive)."""
    objs = []
    for line in body.split(b"\n"):
        line = line.strip()
        if not line.startswith(b"{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            objs.append(obj)
    return objs


def run_failover(config=None, requests=None, slots=4, new_tokens=None,
                 max_burst=8, kv_int8=False, weights_int8=False,
                 smoke=False) -> dict:
    """Serving fault-tolerance gate, chaos-verified end to end over
    HTTP through the real LB against two live replicas
    (docs/robustness.md §Replica loss & rolling update):

    1. **Engine crash recovery** — a seeded ``engine.dispatch`` fault
       (seam=decode) crashes one replica's engine mid-wave; the model
       server resets the engine and re-admits every in-flight request
       through the resume path. Gates: every stream completes cleanly,
       tokens BIT-IDENTICAL to the fault-free control, and >= 1
       recovery observed (``skytpu_engine_recoveries_total`` plus the
       done-line ``recoveries`` trailer).

    2. **Mid-stream failover** — a seeded ``replica.kill`` fault drops
       one stream's connection with no terminal chunk (to the LB that
       replica was SIGKILLed mid-stream); the LB replays
       prompt + committed tokens on the surviving replica with the
       budget reduced by what already streamed. Gates: the client sees
       ONE gapless duplicate-free stream bit-identical to the control,
       and >= 1 failover counted (``skytpu_lb_failovers_total``).

    Zero lost requests is asserted structurally: :func:`_client_wave`
    raises on any non-200, in-stream error line, or unterminated
    stream, so a passing wave IS the zero-shed/zero-truncation gate.
    """
    import json as _json
    import socket
    import tempfile
    import threading

    import jax
    import numpy as np

    from skypilot_tpu import chaos

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    requests = requests or (6 if small else 16)
    new_tokens = new_tokens or (12 if small else 32)
    prompt_len = 12
    # A failover replay's prompt is prompt + committed (up to one token
    # short of the full budget): the bucket must fit the longest
    # replay, not just the original prompts.
    max_prompt = prompt_len + new_tokens
    buckets = (max_prompt,)
    log(f"failover gate: {config} replicas=2 slots={slots} "
        f"requests={requests} new_tokens={new_tokens}")

    home = tempfile.mkdtemp(prefix="skytpu-bench-failover-")
    os.environ["SKYPILOT_TPU_HOME"] = home

    from skypilot_tpu.infer import engine as eng_mod
    from skypilot_tpu.infer import server as srv
    from skypilot_tpu.models import llama
    from skypilot_tpu.serve import load_balancer, serve_state
    from skypilot_tpu.serve.serve_state import ReplicaStatus

    cfg = llama.CONFIGS[config]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]

    chaos.deactivate()   # warmup + control must run fault-free

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    lb_port = free_port()
    serve_state.add_service("bench-failover", {}, {}, lb_port)
    models, httpds = [], []
    for i in range(2):
        # Same seed -> identical weights: a resumed suffix from the
        # surviving replica must be what the dead one would have
        # produced. Chunked prefill + a prefix pool put the crash
        # resume on the warm path (contexts stay > prefill_chunk, the
        # parity-covered regime).
        _, engine = _build_engine(config, slots, max_prompt,
                                  new_tokens, kv_int8, weights_int8,
                                  max_wave=4, buckets=buckets,
                                  pad_waves=True, prefill_chunk=8,
                                  prefix_pool=8)
        port = free_port()
        model, httpd = srv.serve(engine, host="127.0.0.1", port=port,
                                 max_burst=max_burst, open_burst=4,
                                 coalesce_s=0.0)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        models.append(model)
        httpds.append(httpd)
        serve_state.upsert_replica("bench-failover", i + 1,
                                   f"bench-failover-{i + 1}",
                                   ReplicaStatus.READY,
                                   f"http://127.0.0.1:{port}")
    for model in models:
        assert model._ready.wait(timeout=600), "model warmup timed out"
    lb = load_balancer._ThreadingServer(
        ("127.0.0.1", lb_port),
        load_balancer.make_handler("bench-failover",
                                   load_balancer.LeastLoadPolicy()))
    threading.Thread(target=lb.serve_forever, daemon=True).start()

    payloads = [_json.dumps({"tokens": p, "max_new_tokens": new_tokens,
                             "stream": True}).encode()
                for p in prompts]

    def wave():
        """One concurrent wave; returns (token sequences, done-line
        trailers), both in payload order."""
        bodies = []
        _client_wave("127.0.0.1", lb_port, payloads, bodies=bodies)
        seqs, trailers = [], []
        for body in bodies:
            objs = _ndjson_objs(body)
            toks = []
            for o in objs:
                toks.extend(int(t) for t in o.get("tokens") or [])
            done = [o for o in objs if o.get("done")]
            assert done, f"stream ended without a done line: {objs!r}"
            seqs.append(toks)
            trailers.append(done[-1])
        return seqs, trailers

    def _total(metric):
        return sum(child.value for _, child in metric.children())

    try:
        wave()                        # warm: compiles outside the gate
        want, _ = wave()              # fault-free control
        assert all(len(s) == new_tokens for s in want), (
            f"control wave short: {[len(s) for s in want]}")

        # Phase 1: engine crash recovery. One decode dispatch fault;
        # the wave must come back bit-identical with >= 1 recovery.
        rec0 = _total(eng_mod.ENGINE_RECOVERIES)
        chaos.configure({"seed": 7, "faults": [
            {"point": "engine.dispatch", "match": {"seam": "decode"},
             "times": 1}]})
        crash_seqs, crash_trailers = wave()
        crash_fired = len(chaos.injector().fired)
        chaos.deactivate()
        recoveries = _total(eng_mod.ENGINE_RECOVERIES) - rec0
        trailer_recoveries = sum(t.get("recoveries", 0)
                                 for t in crash_trailers)
        crash_parity = crash_seqs == want
        log(f"failover phase 1 (engine crash): parity={crash_parity} "
            f"fired={crash_fired} recoveries={recoveries} "
            f"rode_through={trailer_recoveries}")

        # Phase 2: replica death mid-stream. The kill fires on the 3rd
        # chunk write (after=2: past connect, tokens committed); the
        # LB stitches the suffix from the surviving replica.
        fo0 = _total(load_balancer.LB_FAILOVERS)
        chaos.configure({"seed": 11, "faults": [
            {"point": "replica.kill", "times": 1, "after": 2}]})
        kill_seqs, kill_trailers = wave()
        kill_fired = len(chaos.injector().fired)
        chaos.deactivate()
        failovers = _total(load_balancer.LB_FAILOVERS) - fo0
        trailer_failovers = sum(t.get("failovers", 0)
                                for t in kill_trailers)
        kill_parity = kill_seqs == want
        log(f"failover phase 2 (replica kill): parity={kill_parity} "
            f"fired={kill_fired} failovers={failovers} "
            f"stitched={trailer_failovers}")
    finally:
        chaos.deactivate()
        lb.shutdown()
        for httpd in httpds:
            httpd.shutdown()
        for model in models:
            model.shutdown()

    gate_ok = (crash_parity and kill_parity
               and crash_fired >= 1 and recoveries >= 1
               and kill_fired >= 1 and failovers >= 1)
    return {
        "gate_ok": bool(gate_ok),
        "crash_parity_ok": bool(crash_parity),
        "kill_parity_ok": bool(kill_parity),
        "recoveries": int(recoveries),
        "trailer_recoveries": int(trailer_recoveries),
        "failovers": int(failovers),
        "trailer_failovers": int(trailer_failovers),
        # Structural: _client_wave raised on any lost/short stream.
        "lost_requests": 0,
        "requests": requests,
        "new_tokens": new_tokens,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }


def run_failover_smoke() -> dict:
    """CI-sized fault-tolerance pass (tier-1 wiring: tests/
    test_serve_recovery.py asserts gate_ok; wall-clock is never
    gated on CPU)."""
    return run_failover(smoke=True)


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wave_token_seqs(port, payloads, ttfts=None):
    """Fire ``payloads`` concurrently at the LB and return each
    response's token sequence in payload order (blocking JSON and
    NDJSON stream bodies both parse through _ndjson_objs). ``ttfts``,
    if a list, collects per-request TTFT seconds."""
    bodies = []
    res = _client_wave("127.0.0.1", port, payloads, bodies=bodies)
    if ttfts is not None:
        ttfts.extend(r[0] for r in res)
    seqs = []
    for body in bodies:
        toks = []
        for o in _ndjson_objs(body):
            toks.extend(int(t) for t in o.get("tokens") or [])
        seqs.append(toks)
    return seqs


def _stream_token_times(port, payload, timeout=600.0):
    """One streaming request; returns (tokens, arrival times) with one
    wall-clock stamp PER TOKEN (a multi-token chunk stamps all its
    tokens at the chunk's arrival). Mean TPOT over the stream is
    (t_last - t_first) / (n - 1) — per-gap medians would undercount
    when the server coalesces tokens into one write."""
    import socket

    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    head = ("POST /generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n").encode()
    s.sendall(head + payload)
    buf = b""
    toks, times = [], []
    deadline = time.time() + timeout
    try:
        while time.time() < deadline:
            piece = s.recv(1 << 16)
            if not piece:
                break
            now = time.time()
            buf += piece
            objs = _ndjson_objs(buf)
            fresh = []
            for o in objs:
                fresh.extend(int(t) for t in o.get("tokens") or [])
            while len(toks) < len(fresh):
                toks.append(fresh[len(toks)])
                times.append(now)
            if any(o.get("done") or o.get("error") for o in objs):
                break
    finally:
        s.close()
    assert toks, f"stream produced no tokens: {buf[:300]!r}"
    return toks, times


def _mean_tpot_ms(times):
    if len(times) < 2 or times[-1] <= times[0]:
        return 0.0
    return (times[-1] - times[0]) * 1e3 / (len(times) - 1)


def run_affinity(config=None, families=None, per_family=None,
                 slots=None, new_tokens=None, kv_int8=False,
                 weights_int8=False, smoke=False) -> dict:
    """Fleet prefix-affinity gate: N replicas behind the real LB, the
    prefix-share workload (shared system prompts + unique tails) fired
    THROUGH the LB.

    The claim under test: consistent-hash routing on the chunk-aligned
    prefix digest turns N per-replica prefix caches into one fleet
    cache. With plain least-load routing a family's requests spread —
    only the ~1/N that happen to land on the replica holding the
    prefix hit. With affinity every family pins to its rendezvous
    replica: after one cold request per family the measured wave is
    all hits.

    Phases (fleet shared, families fresh per phase so each starts
    cold): (A) affinity OFF control — seed one request per family,
    then the full wave; fleet hit rate lands near 1/N. (B) affinity
    ON — same shape; gate: hit rate >= 0.8. (C) affinity ON cold-vs-
    warm TTFT on a third family set — the same payload wave twice;
    gates: warm median TTFT >= 30% below cold, tokens bit-identical
    between the passes. Hit rates are read from the engines' own
    prefix tallies (the replicas live in-process), so the gate
    measures real cache behavior, not routing bookkeeping.

    Load spill is pinned OFF (SKYTPU_LB_SPILL high) for the measured
    waves: this bench isolates PLACEMENT; the spill rule has its own
    tier-1 coverage (tests/test_disagg.py).
    """
    import json as _json
    import tempfile
    import threading

    import jax
    import numpy as np

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    n_replicas = 3
    families = families or (3 if small else 6)
    per_family = per_family or (4 if small else 8)
    slots = slots or (per_family if small else 16)
    new_tokens = new_tokens or (4 if small else 32)
    # The system prompt must dwarf the fixed per-request cost (HTTP +
    # admission + dispatch, ~tens of ms on a CPU host): the 30%-below-
    # cold TTFT gate measures prefill compute SAVED, and a too-short
    # prefix would drown the saving in constant overhead.
    system_len = 120 if small else 768
    tail_len = 4 if small else 48
    chunk = 8 if small else 256
    bucket = system_len + tail_len
    log(f"affinity gate: {config} replicas={n_replicas} "
        f"families={families} per_family={per_family} "
        f"system_len={system_len} chunk={chunk}")

    home = tempfile.mkdtemp(prefix="skytpu-bench-affinity-")
    os.environ["SKYPILOT_TPU_HOME"] = home
    env_prev = {k: os.environ.get(k)
                for k in ("SKYTPU_PREFILL_CHUNK", "SKYTPU_LB_SPILL",
                          "SKYTPU_LB_PREFIX_AFFINITY")}
    os.environ["SKYTPU_PREFILL_CHUNK"] = str(chunk)
    os.environ["SKYTPU_LB_SPILL"] = str(4096)

    from skypilot_tpu import chaos
    from skypilot_tpu.infer import server as srv
    from skypilot_tpu.serve import load_balancer, serve_state
    from skypilot_tpu.serve.serve_state import ReplicaStatus

    chaos.deactivate()
    load_balancer._adapter_cache.clear()
    load_balancer._disagg_cache.clear()

    rng = np.random.default_rng(0)
    cfg = None
    engines, models, httpds = [], [], []
    lb_port = _free_port()
    serve_state.add_service("bench-affinity", {}, {}, lb_port)
    for i in range(n_replicas):
        cfg, engine = _build_engine(config, slots, bucket, new_tokens,
                                    kv_int8, weights_int8,
                                    buckets=(bucket,),
                                    prefill_chunk=chunk,
                                    prefix_pool=4 * families)
        port = _free_port()
        model, httpd = srv.serve(engine, host="127.0.0.1", port=port,
                                 max_burst=slots, open_burst=4,
                                 coalesce_s=0.0)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        engines.append(engine)
        models.append(model)
        httpds.append(httpd)
        serve_state.upsert_replica("bench-affinity", i + 1,
                                   f"bench-affinity-{i + 1}",
                                   ReplicaStatus.READY,
                                   f"http://127.0.0.1:{port}")
    for model in models:
        assert model._ready.wait(timeout=600), "model warmup timed out"
    lb = load_balancer._ThreadingServer(
        ("127.0.0.1", lb_port),
        load_balancer.make_handler("bench-affinity",
                                   load_balancer.LeastLoadPolicy()))
    threading.Thread(target=lb.serve_forever, daemon=True).start()

    def mk_families(n):
        """n fresh prefix families: a shared system prompt + unique
        tails per member; every prompt exactly ``bucket`` tokens."""
        out = []
        for _ in range(n):
            system = rng.integers(1, cfg.vocab_size,
                                  system_len).tolist()
            out.append([system + rng.integers(1, cfg.vocab_size,
                                              tail_len).tolist()
                        for _ in range(per_family)])
        return out

    def payload(p):
        return _json.dumps({"tokens": p,
                            "max_new_tokens": new_tokens}).encode()

    def fleet_hits():
        return (sum(e._prefix_hit_n for e in engines),
                sum(e._prefix_miss_n for e in engines))

    def measured_wave(fam_set):
        """Seed one request per family (fleet warms), then the full
        interleaved wave; returns the wave's fleet hit rate."""
        _wave_token_seqs(lb_port, [payload(f[0]) for f in fam_set])
        wave = [payload(f[i]) for i in range(1, per_family)
                for f in fam_set]
        h0, m0 = fleet_hits()
        _wave_token_seqs(lb_port, wave)
        h1, m1 = fleet_hits()
        seen = (h1 - h0) + (m1 - m0)
        return (h1 - h0) / max(seen, 1)

    try:
        # Warmup: compile every program the measured waves reach —
        # cold store, warm pool-load, and the concurrent wave shapes —
        # on every replica (direct, bypassing routing).
        warm_fams = mk_families(1)
        for url in serve_state.ready_urls("bench-affinity"):
            port = int(url.rsplit(":", 1)[1])
            for _ in range(2):
                _wave_token_seqs(port, [payload(p)
                                        for p in warm_fams[0]])

        os.environ["SKYTPU_LB_PREFIX_AFFINITY"] = "0"
        control_hit_rate = measured_wave(mk_families(families))
        os.environ["SKYTPU_LB_PREFIX_AFFINITY"] = "1"
        affinity_hit_rate = measured_wave(mk_families(families))
        log(f"affinity: fleet hit rate {affinity_hit_rate:.2f} "
            f"(control {control_hit_rate:.2f}, ~1/{n_replicas} "
            f"expected)")

        # Cold-vs-warm TTFT + parity: one request per fresh family,
        # the identical wave twice. Streaming: _client_wave stamps
        # TTFT at the first BODY byte, which for a blocking response
        # is the whole JSON (TTFT would absorb every decode token).
        ttft_fams = mk_families(max(families, 3))
        ttft_wave = [_json.dumps({"tokens": f[0],
                                  "max_new_tokens": new_tokens,
                                  "stream": True}).encode()
                     for f in ttft_fams]
        cold_ttfts, warm_ttfts = [], []
        cold_seqs = _wave_token_seqs(lb_port, ttft_wave,
                                     ttfts=cold_ttfts)
        warm_seqs = _wave_token_seqs(lb_port, ttft_wave,
                                     ttfts=warm_ttfts)
        cold_ttft = _median(cold_ttfts) * 1e3
        warm_ttft = _median(warm_ttfts) * 1e3
        parity_ok = warm_seqs == cold_seqs
        log(f"affinity TTFT: cold={cold_ttft:.1f}ms "
            f"warm={warm_ttft:.1f}ms parity={parity_ok}")
    finally:
        lb.shutdown()
        for httpd in httpds:
            httpd.shutdown()
        for model in models:
            model.shutdown()
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    gate_ok = (affinity_hit_rate >= 0.8 and parity_ok
               and warm_ttft <= 0.7 * cold_ttft)
    return {
        "gate_ok": bool(gate_ok),
        "affinity_hit_rate": round(affinity_hit_rate, 3),
        "control_hit_rate": round(control_hit_rate, 3),
        "cold_ttft_ms": round(cold_ttft, 2),
        "warm_ttft_ms": round(warm_ttft, 2),
        "warm_below_70pct_of_cold": bool(warm_ttft <= 0.7 * cold_ttft),
        "parity_ok": bool(parity_ok),
        "replicas": n_replicas,
        "families": families,
        "per_family": per_family,
        "system_len": system_len,
        "prefill_chunk": chunk,
        "config": config,
        "kv_int8": kv_int8,
        "weights_int8": weights_int8,
    }


def run_affinity_smoke() -> dict:
    """CI-sized prefix-affinity pass (tier-1 wiring in
    tests/test_disagg.py covers the routing pieces; this gates the
    fleet-cache economics end to end)."""
    return run_affinity(smoke=True)


def run_disagg(config=None, requests=None, slots=4, new_tokens=None,
               smoke=False) -> dict:
    """Disaggregated prefill/decode serving gate, end to end over HTTP
    through the real LB (docs/serving.md §Disaggregated serving).

    **Parity sweep** — for each of {fp32, int8 KV} x {spec on/off}: a
    1-prefill + 2-decode fleet; every request through the LB runs
    chunked admission on the prefill tier, hands its paged KV blocks
    to a decode replica, and must return tokens BIT-IDENTICAL to the
    same prompt served single-tier (direct to a decode replica). The
    handoff counter must account for every request.

    **Isolation** — on the fp32 fleet: decode-tier streaming TPOT
    while the prefill tier chews a continuous heavy prefill load,
    vs the same engines' idle TPOT, vs a single-tier fleet (same 3
    replicas, no tiers) interleaving both workloads. Gate (TPU only —
    CPU wall-clock is reported, never gated): loaded/idle <= 1.1x.

    **Introspection** — after warmup the fleet's compile watches are
    armed: the measured phases (streams, prefill load, chaos retries)
    must compile NOTHING on either tier.

    **Fault tolerance** — a seeded ``handoff.transfer`` fault kills a
    decode replica's transfer mid-stream; the LB retries the export on
    the survivor. Gates: every stream completes bit-identical to the
    fault-free control (zero lost requests — _client_wave raises on
    any short/errored stream), and the prefill tier ends with its
    block pool exactly equal to its resident refcounted prefixes
    (zero leaked blocks).
    """
    import gc
    import json as _json
    import tempfile
    import threading

    import jax
    import numpy as np

    on_cpu = jax.default_backend() == "cpu"
    if config is None:
        config = "llama3-tiny" if on_cpu else "llama3-400m"
    small = smoke or on_cpu
    requests = requests or (4 if small else 12)
    new_tokens = new_tokens or (6 if small else 32)
    probe_tokens = 24 if small else 64
    prompt_len = 12 if small else 256
    load_len = 48 if small else 1024
    chunk = 8 if small else 256
    buckets = (prompt_len + new_tokens, load_len)
    max_prompt = load_len
    log(f"disagg gate: {config} tiers=1p+2d slots={slots} "
        f"requests={requests} new_tokens={new_tokens}")

    home = tempfile.mkdtemp(prefix="skytpu-bench-disagg-")
    os.environ["SKYPILOT_TPU_HOME"] = home
    env_prev = {k: os.environ.get(k)
                for k in ("SKYTPU_PREFILL_CHUNK", "SKYTPU_LB_SPILL")}
    os.environ["SKYTPU_PREFILL_CHUNK"] = str(chunk)

    from skypilot_tpu import chaos
    from skypilot_tpu.infer import engine as eng_mod
    from skypilot_tpu.infer import server as srv
    from skypilot_tpu.models import llama
    from skypilot_tpu.serve import load_balancer, serve_state
    from skypilot_tpu.serve.serve_state import ReplicaStatus

    chaos.deactivate()
    load_balancer._adapter_cache.clear()
    cfg = llama.CONFIGS[config]
    rng = np.random.default_rng(0)

    def build_fleet(tag, kv_int8_v, spec_k):
        """1 prefill + 2 decode replicas behind a fresh LB, registered
        as a disaggregated service."""
        params = llama.init_params(jax.random.key(0), cfg)
        engines, models, httpds, urls = [], [], [], []
        for _ in range(3):
            engine = eng_mod.InferenceEngine(
                params, cfg, n_slots=slots,
                max_len=max_prompt + probe_tokens + 8,
                prompt_buckets=buckets, kv_int8=kv_int8_v,
                prefill_chunk=chunk, prefix_pool=8 * requests,
                spec_k=spec_k)
            port = _free_port()
            model, httpd = srv.serve(engine, host="127.0.0.1",
                                     port=port, max_burst=slots,
                                     open_burst=4, coalesce_s=0.0)
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            engines.append(engine)
            models.append(model)
            httpds.append(httpd)
            urls.append(f"http://127.0.0.1:{port}")
        for model in models:
            assert model._ready.wait(timeout=600), \
                "model warmup timed out"
        service = f"bench-disagg-{tag}"
        serve_state.add_service(
            service, {"disaggregation": {"prefill_replicas": 1,
                                         "decode_replicas": 2}},
            {}, 0)
        for i, tier in enumerate(("prefill", "decode", "decode")):
            serve_state.upsert_replica(service, i + 1,
                                       f"{service}-{i + 1}",
                                       ReplicaStatus.READY, urls[i],
                                       tier=tier)
        load_balancer._disagg_cache.clear()
        lb_port = _free_port()
        lb = load_balancer._ThreadingServer(
            ("127.0.0.1", lb_port),
            load_balancer.make_handler(
                service, load_balancer.LeastLoadPolicy()))
        threading.Thread(target=lb.serve_forever, daemon=True).start()
        return {"engines": engines, "models": models, "httpds": httpds,
                "urls": urls, "lb": lb, "lb_port": lb_port,
                "service": service}

    def teardown(fleet):
        fleet["lb"].shutdown()
        for httpd in fleet["httpds"]:
            httpd.shutdown()
        for model in fleet["models"]:
            model.shutdown()
        serve_state.remove_service(fleet["service"])

    def payload(p, n, stream=False):
        d = {"tokens": p, "max_new_tokens": n}
        if stream:
            d["stream"] = True
        return _json.dumps(d).encode()

    def handoff_ok_count():
        return load_balancer.LB_HANDOFFS.labels(result="ok").value

    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]

    def parity_pass(fleet):
        """Via-LB wave vs single-tier direct (one decode replica);
        returns (parity_ok, handoffs) for this fleet."""
        decode_port = int(fleet["urls"][1].rsplit(":", 1)[1])
        _wave_token_seqs(fleet["lb_port"],
                         [payload(p, new_tokens) for p in prompts])
        ref = _wave_token_seqs(decode_port,
                               [payload(p, new_tokens)
                                for p in prompts])
        h0 = handoff_ok_count()
        got = _wave_token_seqs(fleet["lb_port"],
                               [payload(p, new_tokens)
                                for p in prompts])
        handoffs = handoff_ok_count() - h0
        return got == ref, int(handoffs)

    variants = [("fp32", False, 0), ("int8kv", True, 0),
                ("spec", False, 3), ("int8kv_spec", True, 3)]
    variant_parity = {}
    for tag, kv_v, spec_v in variants[1:]:
        fleet = build_fleet(tag, kv_v, spec_v)
        try:
            ok, handoffs = parity_pass(fleet)
            variant_parity[tag] = {"parity_ok": bool(ok),
                                   "handoffs": handoffs}
            log(f"disagg parity [{tag}]: parity={ok} "
                f"handoffs={handoffs}/{requests}")
        finally:
            teardown(fleet)
        gc.collect()

    # Main fp32 fleet: parity + isolation + compile watch + chaos.
    fleet = build_fleet("fp32", False, 0)
    engines = fleet["engines"]
    lb_port = fleet["lb_port"]
    try:
        ok, handoffs = parity_pass(fleet)
        variant_parity["fp32"] = {"parity_ok": bool(ok),
                                  "handoffs": handoffs}
        log(f"disagg parity [fp32]: parity={ok} "
            f"handoffs={handoffs}/{requests}")

        probe_prompt = rng.integers(1, cfg.vocab_size,
                                    prompt_len).tolist()
        probe = payload(probe_prompt, probe_tokens, stream=True)
        load_prompts = [rng.integers(1, cfg.vocab_size,
                                     load_len).tolist()
                        for _ in range(max(requests, 4))]
        load_wave = [payload(p, 2) for p in load_prompts]

        # Single-tier baseline fleet state: the SAME replicas, no
        # tiers — decode streams and heavy prefill interleave on the
        # same engines (registered second so its warm caches don't
        # perturb the disagg measurements, which run first).
        serve_state.add_service("bench-disagg-single", {}, {}, 0)
        for i, url in enumerate(fleet["urls"]):
            serve_state.upsert_replica("bench-disagg-single", i + 1,
                                       f"bds-{i + 1}",
                                       ReplicaStatus.READY, url)
        single_lb_port = _free_port()
        single_lb = load_balancer._ThreadingServer(
            ("127.0.0.1", single_lb_port),
            load_balancer.make_handler(
                "bench-disagg-single",
                load_balancer.LeastLoadPolicy()))
        threading.Thread(target=single_lb.serve_forever,
                         daemon=True).start()

        # Warm every program the measured phases reach — stream +
        # handoff paths on both decode replicas, the heavy-prefill
        # shapes, and the single-tier stream — then arm the watches:
        # anything compiling after this line is a gate failure.
        for _ in range(2):
            _stream_token_times(lb_port, probe)
            _wave_token_seqs(lb_port, load_wave)
            _stream_token_times(single_lb_port, probe)
            _wave_token_seqs(single_lb_port, load_wave)
        chaos.configure({"seed": 5, "faults": [
            {"point": "handoff.transfer", "times": 1}]})
        _stream_token_times(lb_port, probe)
        chaos.deactivate()
        for e in engines:
            e.compile_watch.declare_warm()

        def measured_stream(port, background):
            """Stream TPOT while (optionally) a thread keeps the fleet
            under continuous heavy prefill load."""
            stop = threading.Event()

            def pump():
                n = 0
                while not stop.is_set() and n < 50:
                    _wave_token_seqs(port, load_wave)
                    n += 1

            t = None
            if background:
                t = threading.Thread(target=pump, daemon=True)
                t.start()
                time.sleep(0.05)   # load in flight before the probe
            try:
                _, times = _stream_token_times(port, probe)
            finally:
                stop.set()
                if t is not None:
                    t.join(timeout=600)
            return _mean_tpot_ms(times)

        idle_tpot = measured_stream(lb_port, background=False)
        loaded_tpot = measured_stream(lb_port, background=True)
        single_idle_tpot = measured_stream(single_lb_port,
                                           background=False)
        single_loaded_tpot = measured_stream(single_lb_port,
                                             background=True)
        isolation_ratio = loaded_tpot / max(idle_tpot, 1e-9)
        single_ratio = single_loaded_tpot / max(single_idle_tpot,
                                                1e-9)
        log(f"disagg isolation: decode TPOT idle={idle_tpot:.2f}ms "
            f"loaded={loaded_tpot:.2f}ms (x{isolation_ratio:.2f}); "
            f"single-tier x{single_ratio:.2f}")

        # Chaos: a decode replica dies mid-handoff; the export retries
        # on the survivor. Streams must come back bit-identical.
        chaos_wave = [payload(p, new_tokens, stream=True)
                      for p in prompts]
        want = _wave_token_seqs(lb_port, chaos_wave)
        retry0 = load_balancer.LB_HANDOFFS.labels(
            result="retry").value
        chaos.configure({"seed": 3, "faults": [
            {"point": "handoff.transfer", "times": 1}]})
        got = _wave_token_seqs(lb_port, chaos_wave)
        chaos_fired = len(chaos.injector().fired)
        chaos.deactivate()
        chaos_retries = load_balancer.LB_HANDOFFS.labels(
            result="retry").value - retry0
        chaos_parity = got == want
        log(f"disagg chaos: parity={chaos_parity} "
            f"fired={chaos_fired} retries={chaos_retries}")

        unexpected = [k for e in engines
                      for k in e.compile_watch.unexpected]
        # Donor audit: every prefill-tier block is owned by a resident
        # refcounted prefix — handoffs (including the chaos-retried
        # one) left nothing dangling.
        pf = engines[0]
        resident = (sum(len(p) for p in pf._prefix_index.payloads())
                    if pf._prefix_index else 0)
        leaked = pf.blocks_used - resident
        single_lb.shutdown()
        serve_state.remove_service("bench-disagg-single")
    finally:
        chaos.deactivate()
        teardown(fleet)
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    parity_all = all(v["parity_ok"] for v in variant_parity.values())
    handoffs_all = all(v["handoffs"] == requests
                      for v in variant_parity.values())
    gate_ok = (parity_all and handoffs_all and chaos_parity
               and chaos_fired >= 1 and chaos_retries >= 1
               and not unexpected and leaked == 0
               and (on_cpu or isolation_ratio <= 1.1))
    return {
        "gate_ok": bool(gate_ok),
        "parity_ok": bool(parity_all),
        "variants": variant_parity,
        "handoffs_accounted": bool(handoffs_all),
        "idle_tpot_ms": round(idle_tpot, 3),
        "loaded_tpot_ms": round(loaded_tpot, 3),
        "isolation_ratio": round(isolation_ratio, 3),
        "single_tier_ratio": round(single_ratio, 3),
        # The <= 1.1x isolation gate binds on TPU only (CPU decode is
        # compute-bound: the probe stream and the prefill pump share
        # cores, so wall-clock there measures the host, not the tier
        # split); the ratio is still reported for the record.
        "isolation_gated": bool(not on_cpu),
        "chaos_parity_ok": bool(chaos_parity),
        "chaos_fired": int(chaos_fired),
        "chaos_retries": int(chaos_retries),
        "lost_requests": 0,   # structural: _client_wave raises
        "leaked_blocks": int(leaked),
        "unexpected_compiles": len(unexpected),
        "unexpected": unexpected,
        "requests": requests,
        "new_tokens": new_tokens,
        "config": config,
    }


def run_disagg_smoke() -> dict:
    """CI-sized disaggregation pass (tier-1 wiring in
    tests/test_disagg.py covers the protocol; this gates the fleet
    behavior — parity sweep, compile watch, chaos — end to end)."""
    return run_disagg(smoke=True)


def main() -> None:
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="pin every prompt to this length (default: "
                         "realistic 512-1024 mix for HTTP runs, 96 "
                         "for --engine-only)")
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--max-burst", type=int, default=32)
    ap.add_argument("--open-burst", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=1,
                    help="timed runs on the warm server; the summary "
                         "reports median-of-runs and the worst run")
    ap.add_argument("--stagger", type=float, default=0.0,
                    help="seconds between request arrivals (0 = one "
                         "instantaneous burst)")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--weights-int8", action="store_true")
    ap.add_argument("--admit-wave", type=int, default=None,
                    help="cap admission waves: early waves' first "
                         "tokens stream (HTTP) / stamp TTFT (engine) "
                         "while later waves prefill")
    ap.add_argument("--engine-only", action="store_true",
                    help="bench the engine directly (no HTTP/LB; "
                         "engine-internal TTFT)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="prefix-share workload (shared system prompt "
                         "+ unique tails): warm-vs-cold TTFT, greedy "
                         "parity, and the decode-interference report")
    ap.add_argument("--prefix-pool", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized prefix-share pass (tier-1 "
                         "regression guard for the chunk scheduler)")
    ap.add_argument("--occupancy", action="store_true",
                    help="high-occupancy sweep: max concurrent slots "
                         "at equal KV HBM, paged vs contiguous, with "
                         "greedy parity (the paged-cache headline)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decoding bench: the NON-"
                         "repetitive workload with the model-backed "
                         "drafter (pipelined + sync + the honest "
                         "n-gram wash column) as the headline, plus "
                         "the repetition-heavy secondary n-gram "
                         "column and the oracle-draft ceiling; greedy "
                         "parity asserted everywhere (combine with "
                         "--smoke for the CI-sized pass)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft length K for --spec")
    ap.add_argument("--span", action="store_true",
                    help="span-bucketed decode attention bench: "
                         "span-on vs full-view decode TPOT on the "
                         "same engine (short active conversations on "
                         "a long-max_len engine), greedy parity "
                         "asserted (combine with --smoke for the "
                         "CI-sized pass)")
    ap.add_argument("--kernel", action="store_true",
                    help="Pallas paged decode-attention kernel bench: "
                         "kernel-vs-gather decode TPOT on the same "
                         "engine at low occupancy (where the gather "
                         "transient dominates), greedy parity "
                         "asserted; combined with --span/--occupancy "
                         "it re-runs THOSE benches with the kernel "
                         "enabled instead (combine with --smoke for "
                         "the CI-sized pass)")
    ap.add_argument("--qos", action="store_true",
                    help="multi-tenant QoS bench: background-tenant "
                         "TPOT/TTFT isolation under a hot tenant "
                         "(WFQ vs FIFO control) and preemption-by-"
                         "eviction greedy parity with the allocator "
                         "audit (combine with --smoke for the "
                         "CI-sized pass)")
    ap.add_argument("--adapters", action="store_true",
                    help="multi-LoRA adapter-catalog bench: N-adapter "
                         "mixed-workload decode TPOT vs a single-"
                         "adapter baseline on the same engine, greedy "
                         "parity vs per-adapter sequential runs, and "
                         "zero unexpected compiles while adapters "
                         "hot-load/evict mid-traffic (combine with "
                         "--smoke for the CI-sized pass)")
    ap.add_argument("--n-adapters", type=int, default=8,
                    help="fine-tunes in the mixed workload for "
                         "--adapters (pool sized to hold them; the "
                         "churn phase registers 2x as many)")
    ap.add_argument("--flight", action="store_true",
                    help="flight recorder + compile watch bench: the "
                         "full mixed workload (chunked admission + "
                         "spec decode + span selection, paged + "
                         "contiguous) with warm-grid startup — gates "
                         "zero unexpected compiles in the timed "
                         "window, per-burst record coverage, and the "
                         "recorder-off no-op guard (combine with "
                         "--smoke for the CI-sized pass)")
    ap.add_argument("--failover", action="store_true",
                    help="serving fault-tolerance gate: two live "
                         "replicas behind the real LB; a seeded "
                         "engine.dispatch fault (crash -> reset -> "
                         "bit-identical resume) then a seeded "
                         "replica.kill mid-stream (LB failover -> "
                         "gapless stitched stream) — gates parity "
                         "with the fault-free control and zero lost "
                         "requests (combine with --smoke for the "
                         "CI-sized pass)")
    ap.add_argument("--affinity", action="store_true",
                    help="fleet prefix-affinity gate: N replicas "
                         "behind the real LB, prefix families routed "
                         "by consistent hash on the chunk-aligned "
                         "prefix digest — gates fleet prefix hit-rate "
                         ">= 0.8 (vs the ~1/N least-load control), "
                         "warm TTFT >= 30% below cold, and greedy "
                         "parity (combine with --smoke for the "
                         "CI-sized pass)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode gate: 1-prefill"
                         " + 2-decode fleet behind the real LB — "
                         "gates two-tier output bit-identical to "
                         "single-tier across {fp32, int8 KV} x {spec "
                         "on/off}, decode-tier TPOT isolation under "
                         "heavy prefill (<= 1.1x idle, TPU only), "
                         "zero unexpected compiles on either tier, "
                         "and the handoff.transfer chaos retry with "
                         "zero lost requests / zero leaked blocks "
                         "(combine with --smoke for the CI-sized "
                         "pass)")
    args = ap.parse_args()
    if args.affinity:
        r = run_affinity(config=args.config, kv_int8=args.kv_int8,
                         weights_int8=args.weights_int8,
                         smoke=args.smoke)
        print(json.dumps({
            "metric": "serve_affinity_hit_rate",
            "value": r["affinity_hit_rate"],
            "unit": "fleet_prefix_hit_rate",
            **{k: r[k] for k in (
                "gate_ok", "control_hit_rate", "cold_ttft_ms",
                "warm_ttft_ms", "warm_below_70pct_of_cold",
                "parity_ok", "replicas", "families", "per_family",
                "config")},
        }))
        if not r["gate_ok"]:
            sys.exit(1)
        return
    if args.disagg:
        r = run_disagg(config=args.config, smoke=args.smoke)
        print(json.dumps({
            "metric": "serve_disagg_isolation_ratio",
            "value": r["isolation_ratio"],
            "unit": "x_decode_tpot_loaded_vs_idle",
            **{k: r[k] for k in (
                "gate_ok", "parity_ok", "variants",
                "handoffs_accounted", "single_tier_ratio",
                "isolation_gated", "chaos_parity_ok", "chaos_fired",
                "chaos_retries", "lost_requests", "leaked_blocks",
                "unexpected_compiles", "requests", "config")},
        }))
        if not r["gate_ok"]:
            sys.exit(1)
        return
    if args.failover:
        r = run_failover(config=args.config, kv_int8=args.kv_int8,
                         weights_int8=args.weights_int8,
                         smoke=args.smoke)
        print(json.dumps({
            "metric": "serve_failover_gate",
            "value": 1.0 if r["gate_ok"] else 0.0,
            "unit": "bool",
            **{k: r[k] for k in (
                "crash_parity_ok", "kill_parity_ok", "recoveries",
                "trailer_recoveries", "failovers",
                "trailer_failovers", "lost_requests", "requests",
                "new_tokens", "config")},
        }))
        if not r["gate_ok"]:
            sys.exit(1)
        return
    if args.adapters:
        r = run_adapters(config=args.config,
                         n_adapters=args.n_adapters,
                         kv_int8=args.kv_int8,
                         weights_int8=args.weights_int8,
                         spec_k=(args.spec_k if args.spec else 0),
                         smoke=args.smoke)
        print(json.dumps({
            "metric": "serve_adapter_overhead",
            "value": r["overhead_ratio"],
            "unit": "x_mixed_decode_tpot_vs_single",
            **{k: r[k] for k in (
                "tpot_single_ms", "tpot_mixed_ms", "parity_ok",
                "hot_loads", "evictions", "unexpected_compiles",
                "n_adapters", "rank", "backend", "config")},
        }))
        return
    if args.qos:
        r = run_qos(config=args.config, kv_int8=args.kv_int8,
                    weights_int8=args.weights_int8, smoke=args.smoke)
        print(json.dumps({
            "metric": "serve_qos_fairness_ratio",
            "value": r["fairness_ratio"],
            "unit": "x_bg_tpot_p99_vs_idle",
            **{k: r[k] for k in (
                "bg_tpot_idle_p99_ms", "bg_tpot_contended_p99_ms",
                "bg_ttft_wfq_ratio", "bg_ttft_fifo_ratio",
                "sched_parity_ok", "preempt_parity_ok",
                "preemptions", "preempt_resumed_rows", "config")},
        }))
        return
    if args.flight:
        r = run_flight(config=args.config, kv_int8=args.kv_int8,
                       weights_int8=args.weights_int8,
                       smoke=args.smoke)
        print(json.dumps({
            "metric": "serve_unexpected_compiles",
            "value": r["unexpected_compiles"],
            "unit": "programs_compiled_in_timed_window",
            **{k: r[k] for k in (
                "warmup_compile_s", "coverage_ok", "parity_ok",
                "calibration_parity_ok", "calibration_samples",
                "n_records", "overhead_ratio", "layouts", "config")},
        }))
        return
    if args.span:
        r = run_span(config=args.config, kv_int8=args.kv_int8,
                     weights_int8=args.weights_int8,
                     smoke=args.smoke, kv_kernel=args.kernel)
        print(json.dumps({
            "metric": "serve_span_speedup",
            "value": r["speedup"],
            "unit": "x_decode_tok_s_vs_full_view",
            **{k: r[k] for k in (
                "tpot_full_ms", "tpot_span_ms", "rows_full",
                "rows_span", "rows_ratio", "span_ladder",
                "n_span_programs", "parity_ok", "kv_kernel",
                "config")},
        }))
        return
    if args.kernel and not args.occupancy:
        # --kernel alone = the kernel-vs-gather bench; combined with
        # --span/--occupancy those branches run THEIR bench with the
        # kernel enabled instead (--span is dispatched above,
        # --occupancy below).
        r = run_kernel(config=args.config, kv_int8=args.kv_int8,
                       weights_int8=args.weights_int8,
                       spec_k=(args.spec_k if args.spec else 0),
                       smoke=args.smoke)
        print(json.dumps({
            "metric": "serve_kernel_speedup",
            "value": r["speedup"],
            "unit": "x_decode_tok_s_vs_gather",
            **{k: r[k] for k in (
                "tpot_gather_ms", "tpot_kernel_ms", "parity_ok",
                "kernel_programs_ok", "backend", "active_requests",
                "slots", "span_ladder", "config")},
        }))
        return
    if args.spec:
        r = run_spec(config=args.config, spec_k=args.spec_k,
                     kv_int8=args.kv_int8,
                     weights_int8=args.weights_int8,
                     smoke=args.smoke)
        print(json.dumps({
            "metric": "serve_spec_model_speedup",
            "value": r["model_speedup"],
            "unit": "x_decode_tok_s_vs_spec_off",
            **{k: r[k] for k in (
                "model_tpot_off_ms", "tpot_model_ms",
                "tpot_model_sync_ms", "pipeline_ratio",
                "model_accept_rate", "model_parity_ok",
                "overlap_ok", "draft_reuse_hits", "draft_layers",
                "ngram_nonrep_speedup", "ngram_nonrep_accept_rate",
                "tpot_off_ms", "tpot_spec_ms", "tpot_oracle_ms",
                "speedup", "oracle_speedup", "accept_rate",
                "oracle_accept_rate", "parity_ok",
                "oracle_parity_ok", "spec_k", "config", "backend")},
        }))
        return
    if args.occupancy:
        r = run_occupancy(config=args.config, kv_int8=args.kv_int8,
                          weights_int8=args.weights_int8,
                          kv_kernel=args.kernel)
        print(json.dumps({
            "metric": "serve_occupancy_x",
            "value": r["occupancy_x"],
            "unit": "x_slots_at_equal_hbm",
            **{k: r[k] for k in (
                "kv_hbm_bytes", "paged_slots", "contiguous_slots",
                "blocks_per_token", "kv_block", "parity_ok",
                "occupancy_regressed", "kv_kernel", "config")},
        }))
        return
    if args.smoke or args.prefix_share:
        if args.smoke:
            r = run_smoke()
        else:
            r = run_prefix_share(
                config=args.config, requests=args.requests,
                slots=args.slots, new_tokens=args.new_tokens,
                max_burst=args.max_burst,
                prefill_chunk=args.prefill_chunk,
                prefix_pool=args.prefix_pool,
                kv_int8=args.kv_int8, weights_int8=args.weights_int8)
        print(json.dumps({
            "metric": "serve_prefix_warm_ttft",
            "value": r["warm_ttft_ms"],
            "unit": "ms",
            "cold_ttft_ms": r["cold_ttft_ms"],
            "warm_speedup": r["warm_speedup"],
            "parity_ok": r["parity_ok"],
            "hit_rate": r["hit_rate"],
            "decode_stall_p99_ms": r["decode_stall_p99_ms"],
            "interference": r["interference"],
            "config": r["config"],
        }))
        return
    if args.engine_only:
        r = run(config=args.config, requests=args.requests,
                slots=args.slots, prompt_len=args.prompt_len or 96,
                new_tokens=args.new_tokens, max_burst=args.max_burst,
                kv_int8=args.kv_int8, weights_int8=args.weights_int8,
                admit_wave=args.admit_wave)
    else:
        r = run_http(config=args.config, requests=args.requests,
                     slots=args.slots, prompt_len=args.prompt_len,
                     new_tokens=args.new_tokens,
                     max_burst=args.max_burst, kv_int8=args.kv_int8,
                     weights_int8=args.weights_int8,
                     admit_wave=args.admit_wave,
                     open_burst=args.open_burst,
                     repeats=args.repeats, stagger_s=args.stagger)
    out = {
        "metric": "serve_median_ttft",
        "value": r["median_ttft_ms"],
        "unit": "ms",
        "vs_baseline": r["vs_baseline_ttft"],
        "output_tok_per_s": r["out_tok_s"],
        "req_per_s": r["req_per_s"],
        "config": r["config"],
        "kv_int8": r["kv_int8"],
        "weights_int8": r["weights_int8"],
    }
    if "p99_ttft_ms" in r:
        out["p99_ttft_ms"] = r["p99_ttft_ms"]
        out["transport"] = r["transport"]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
