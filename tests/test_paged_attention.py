"""Pallas paged decode-attention kernel: block-table-native KV reads.

Tier-1 guards for the PR-12 kernel (ROADMAP item 1's final half —
the gather transient's removal), run in Pallas interpret mode on CPU
(the flash-attention precedent):

* Kernel numerics vs a numpy online-softmax reference: fuzzed slot
  lengths (0, partial final blocks, full), scattered physical block
  ids, sentinel table entries, span-bounded sweeps, fp32 and int8
  pools with per-(block, head, row) scales.
* Greedy parity vs the XLA gather oracle — the gather path is kept
  VERBATIM and stays runtime-selectable (the flag off) — across
  {fp32, int8 KV} x {spec on, off} x the span-rung ladder x
  partial final blocks, through the real engine (chunked admission,
  prefix reuse, span regrouping). Workloads are pinned: the oracle's
  own bf16 weight-cast sets a ~1e-3 logit noise floor, so EXACT ties
  (a tiny random-weight model produces them; PR 6's test_infer_tp
  lesson) can flip under any summation reorganization — the
  layer-level test below asserts parity wherever the top-2 gap
  exceeds that floor, seed-robustly.
* Program identity: the kernel flag rides the compile-watch key
  (never a retrace surface), warm_programs covers the kernel grid and
  live traffic then compiles NOTHING new.
* Observability: decode/verify flight records carry
  ``attn=kernel|gather``; the path counter feeds ``skytpu top``.
* Fallback: a contiguous engine requesting the kernel falls back to
  the gather (typed event), bit-identical behavior.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.infer import engine as eng
from skypilot_tpu.models import llama
from skypilot_tpu.observability import flight as flight_lib
from skypilot_tpu.ops import paged_attention as pa


@pytest.fixture(scope="module")
def cfg():
    # fp32 activations: reorganization noise is not amplified by bf16
    # output casts (the PR 6 lesson); the int8 cells cover the
    # quantized cache.
    return dataclasses.replace(llama.CONFIGS["llama3-tiny"],
                               dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.key(0), cfg)


def _engine(params, cfg, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", (32,))
    kw.setdefault("kv_block", 16)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefix_pool", 4)
    return eng.InferenceEngine(params, cfg, **kw)


# Pinned parity workload (seed 1): prompt lengths cross the chunk
# boundary (20 > chunk 8 -> chunked admission with a partial final
# chunk; 5, 3 ride waves), none block-aligned (partial final BLOCKS),
# and active rows sweep span rungs 8 -> 32 of the default ladder.
_PROMPT_LENS = (5, 11, 3, 20)
_SEED = 1


def _prompts(cfg, seed=_SEED):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist()
            for n in _PROMPT_LENS]


# -- kernel vs numpy reference ----------------------------------------------

def _np_reference(q, kp, vp, ks, vs, table, lengths, layer, span):
    """Online-softmax stats the kernel must reproduce, in numpy."""
    B, G, R, hd = q.shape
    n_blocks, bl = kp.shape[1], kp.shape[2]
    nbs = -(-span // bl)
    acc = np.zeros((B, G, R, hd), np.float64)
    m = np.full((B, G, R), -1e30, np.float64)
    l = np.zeros((B, G, R), np.float64)
    for b in range(B):
        n = int(lengths[b])
        cols_k, cols_v, sk_cols, sv_cols = [], [], [], []
        for j in range(nbs):
            t = int(table[b, j])
            if j * bl >= n:
                continue
            t = 0 if t >= n_blocks else t
            cols_k.append(kp[layer, t].astype(np.float64))
            cols_v.append(vp[layer, t].astype(np.float64))
            if ks is not None:
                sk_cols.append(ks[layer, t].astype(np.float64))
                sv_cols.append(vs[layer, t].astype(np.float64))
        if not cols_k:
            continue
        K = np.concatenate(cols_k)              # [M, G, hd]
        V = np.concatenate(cols_v)
        M_ = K.shape[0]
        col = np.arange(M_)
        for g in range(G):
            s = (q[b, g].astype(np.float64) * hd ** -0.5) @ K[:, g].T
            if ks is not None:
                s = s * np.concatenate(
                    [c[g] for c in sk_cols])[None, :]
            s = np.where(col[None, :] < n, s, -1e30)
            mm = s.max(1)
            p = np.exp(s - mm[:, None])
            ll = p.sum(1)
            if vs is not None:
                pv = p * np.concatenate(
                    [c[g] for c in sv_cols])[None, :]
            else:
                pv = p
            acc[b, g] = pv @ V[:, g]
            m[b, g] = mm
            l[b, g] = ll
    return acc, m, l


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_kernel_vs_numpy_fuzz(quant):
    rng = np.random.default_rng(0)
    L, n_blocks, bl, G, hd = 2, 12, 8, 2, 16
    B, R = 4, 3
    nb = 5
    if quant:
        kp = rng.integers(-127, 128,
                          (L, n_blocks, bl, G, hd)).astype(np.int8)
        vp = rng.integers(-127, 128,
                          (L, n_blocks, bl, G, hd)).astype(np.int8)
        ks = (rng.random((L, n_blocks, G, bl)) * 0.02
              + 1e-3).astype(np.float32)
        vs = (rng.random((L, n_blocks, G, bl)) * 0.02
              + 1e-3).astype(np.float32)
    else:
        kp = rng.standard_normal(
            (L, n_blocks, bl, G, hd)).astype(np.float32)
        vp = rng.standard_normal(
            (L, n_blocks, bl, G, hd)).astype(np.float32)
        ks = vs = None
    for trial in range(4):
        q = rng.standard_normal((B, G, R, hd)).astype(np.float32)
        table = np.full((B, nb + 1), n_blocks, np.int32)
        lengths = np.zeros((B,), np.int32)
        for b in range(B):
            # Fuzz: 0 rows, partial final blocks, full allocations,
            # scattered physical ids, sentinel tails.
            n = int(rng.integers(0, nb * bl + 1))
            have = -(-n // bl)
            table[b, :have] = rng.choice(n_blocks, size=have,
                                         replace=False)
            lengths[b] = n
        span = int(rng.integers(1, nb * bl + 1))
        layer = int(rng.integers(0, L))
        acc, m, l = pa.paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            None if ks is None else jnp.asarray(ks),
            None if vs is None else jnp.asarray(vs),
            jnp.asarray(table), jnp.asarray(lengths),
            jnp.int32(layer), span_blocks=-(-span // bl))
        racc, rm, rl = _np_reference(q, kp, vp, ks, vs, table,
                                     lengths, layer, span)
        acc, m, l = np.asarray(acc), np.asarray(m), np.asarray(l)
        for b in range(B):
            n = min(int(lengths[b]), -(-span // bl) * bl)
            if n == 0:
                assert np.all(m[b] == -1e30)
                assert np.all(l[b] == 0)
                continue
            # The kernel only sweeps span_blocks; the reference's mask
            # bound must match what the kernel saw.
            r2acc, r2m, r2l = racc[b], rm[b], rl[b]
            assert np.allclose(m[b], r2m, rtol=1e-5, atol=1e-5)
            assert np.allclose(l[b], r2l, rtol=1e-4, atol=1e-5)
            assert np.allclose(acc[b], r2acc, rtol=1e-3, atol=1e-4)


def test_kernel_under_scan_traced_layer():
    """The layer index is a TRACED scalar (the engine calls the kernel
    inside the layer scan) — scalar prefetch must route it."""
    rng = np.random.default_rng(1)
    L, n_blocks, bl, G, hd = 3, 6, 8, 1, 16
    kp = rng.standard_normal((L, n_blocks, bl, G, hd)).astype(np.float32)
    vp = rng.standard_normal((L, n_blocks, bl, G, hd)).astype(np.float32)
    q = rng.standard_normal((1, G, 2, hd)).astype(np.float32)
    table = np.array([[2, 4, n_blocks]], np.int32)
    lengths = np.array([13], np.int32)

    def body(i, _):
        return i + 1, pa.paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            None, None, jnp.asarray(table), jnp.asarray(lengths), i,
            span_blocks=2)[0]

    _, accs = jax.lax.scan(body, jnp.int32(0), None, length=L)
    for li in range(L):
        racc, _, _ = _np_reference(q, kp, vp, None, None, table,
                                   lengths, li, 16)
        assert np.allclose(np.asarray(accs)[li], racc, rtol=1e-4,
                           atol=1e-5), f"layer {li}"


# -- layer-level logits: gap-aware greedy parity (seed-robust) --------------

@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp", "int8"])
def test_layer_logits_close_and_untied_argmax_equal(params, cfg,
                                                    kv_int8):
    """One staged decode step's logits, kernel vs gather, on a REAL
    mid-generation cache: logits agree within the oracle's bf16
    weight-cast noise floor, and argmax agrees on every slot whose
    top-2 gap exceeds it — the seed-robust statement of greedy parity
    (exact ties flip under ANY summation reorganization)."""
    from skypilot_tpu.infer import kvcache

    e = _engine(params, cfg, kv_int8=kv_int8, kv_kernel=False)
    for p in _prompts(cfg):
        e.add_request(p, max_new_tokens=4)
    e.admit()
    while e.chunking:
        e.prefill_chunk_step()
    e.step_decode_once()
    cache = {k: jnp.copy(v) for k, v in e.cache.items()}
    table = e.table_device()
    L, G, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    B = cache["length"].shape[0]
    quant = "k_scale" in cache
    kdt = cache["k"].dtype

    def one_step_logits(kernel):
        c = {k: jnp.copy(v) for k, v in cache.items()}
        pos0 = c["length"]
        batch_ix = jnp.arange(B)
        tiles = kvcache._live_tiles(None, pos0, table)
        sk = jnp.zeros((L, B, 1, G, hd), kdt)
        sv = jnp.zeros((L, B, 1, G, hd), kdt)
        zero = jnp.zeros((), jnp.float32)
        sks = (jnp.zeros((L, B, 1, G), c["k_scale"].dtype)
               if quant else zero)
        svs = (jnp.zeros((L, B, 1, G), c["k_scale"].dtype)
               if quant else zero)
        x = params["embed"].astype(cfg.dtype)[c["last_token"][:, None]]
        cos, sin = llama.rope_frequencies(cfg, pos0[:, None])
        stage_valid = jnp.arange(1)[None, :] <= 0
        i = jnp.int32(0)
        for li in range(L):
            layer = jax.tree.map(lambda w: w[li], params["blocks"])
            x, sk, sv, sks, svs = kvcache._staged_attn_layer(
                cfg, c, table, layer, None, x, cos, sin, i, 0,
                sk, sv, sks, svs, pos0, stage_valid, batch_ix, tiles,
                None, li == li and kernel)
            i = i + 1
        return np.asarray(kvcache._head(cfg, params, None, x))

    lg = one_step_logits(False)
    lk = one_step_logits(True)
    noise = np.abs(lg - lk).max()
    assert noise < 0.05, f"kernel-vs-gather logit delta {noise}"
    for s in range(B - 1):          # spare slot excluded
        top2 = np.sort(lg[s])[-2:]
        if top2[1] - top2[0] > 0.1:
            assert lg[s].argmax() == lk[s].argmax(), f"slot {s}"


# -- engine greedy-parity matrix (pinned workloads) -------------------------

@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("spec_k", [0, 3], ids=["spec0", "spec3"])
def test_engine_parity_matrix(params, cfg, kv_int8, spec_k):
    """Kernel-on greedy output == the gather oracle, end to end
    through the engine: chunked admission (partial final chunks),
    wave admission, prefix reuse, span regrouping over rungs 8..32,
    partial final blocks (no prompt is block-aligned), spec verify
    when spec_k > 0. Workload pinned (module docstring: exact ties)."""
    def gen(kv_kernel):
        e = _engine(params, cfg, kv_int8=kv_int8, spec_k=spec_k,
                    kv_kernel=kv_kernel)
        assert e.kv_kernel == kv_kernel
        return e.generate(_prompts(cfg), max_new_tokens=8)

    assert gen(True) == gen(False)


def test_parity_with_ladder_disabled(params, cfg):
    """span_buckets=0 (full-view reads, span=None -> the kernel
    sweeps the whole table) produces oracle-identical output; the
    laddered rungs (incl. the sub-block rung 8 < block 16) are swept
    by the matrix above via the default ladder."""
    def gen(kv_kernel):
        e = _engine(params, cfg, span_buckets=0, kv_kernel=kv_kernel)
        return e.generate(_prompts(cfg), max_new_tokens=8)

    assert gen(True) == gen(False)


# -- program identity + retrace discipline ----------------------------------

def test_kernel_flag_in_program_identity_and_warm_grid(params, cfg):
    """The kernel flag rides the compile-watch key; warm_programs
    covers the kernel grid, and live traffic after
    declare_warmup_complete compiles NOTHING (acceptance criterion:
    zero unexpected compiles with the kernel enabled)."""
    e = _engine(params, cfg, kv_kernel=True, max_wave=2,
                pad_waves=True)
    n = e.warm_programs(max_burst=8)
    assert n > 0
    assert any("kernel=True" in k for k in e.compile_watch.summary())
    e.declare_warmup_complete()
    out = e.generate(_prompts(cfg), max_new_tokens=8)
    assert out and all(len(t) == 8 for t in out)
    assert e.compile_watch.unexpected == [], \
        f"mid-traffic compiles: {e.compile_watch.unexpected}"
    # Dispatched program keys stay ladder-bounded (kind, width, span):
    # the kernel adds no cardinality — it is engine-constant.
    spans = {s for _, _, s in e.decode_programs}
    allowed = {None} | {s for s in e.span_ladder}
    assert spans <= allowed


# -- fallback + observability -----------------------------------------------

def test_contiguous_fallback(params, cfg):
    """A contiguous engine requesting the kernel falls back to the
    gather path (the kernel is block-table-native) and still serves;
    the flag reads False so records/benches tell the truth."""
    e = _engine(params, cfg, kv_block=0, kv_kernel=True)
    assert e.paged is False and e.kv_kernel is False
    out = e.generate(_prompts(cfg), max_new_tokens=4)
    assert all(len(t) == 4 for t in out)


def test_flight_records_attn_path(params, cfg):
    """decode/verify/chunk records carry attn=kernel when the flag is
    on; decode1 (not kernel-wired) says gather; the path counter
    moves."""
    rec = flight_lib.FlightRecorder(capacity=256)
    rec.enabled = True
    before = eng.DECODE_ATTN_PATH.labels(path="kernel").value
    e = _engine(params, cfg, kv_kernel=True, spec_k=3,
                flight_recorder=rec)
    e.generate(_prompts(cfg), max_new_tokens=6)
    e2 = _engine(params, cfg, kv_kernel=True, flight_recorder=rec)
    for p in _prompts(cfg)[:2]:
        e2.add_request(p, max_new_tokens=2)
    e2.admit()
    while e2.chunking:
        e2.prefill_chunk_step()
    e2.step_decode_once()
    kinds = {}
    for r in rec.tail():
        prog = r.get("program") or {}
        if "attn" in prog:
            kinds.setdefault(r["burst"], set()).add(prog["attn"])
    assert kinds.get("decode", set()) | kinds.get("verify", set()) \
        <= {"kernel"}
    assert "kernel" in (kinds.get("decode", set())
                        | kinds.get("verify", set()))
    assert kinds.get("chunk") == {"kernel"}
    assert kinds.get("decode1") == {"gather"}
    assert eng.DECODE_ATTN_PATH.labels(path="kernel").value > before


def test_gather_engine_records_gather(params, cfg):
    rec = flight_lib.FlightRecorder(capacity=64)
    rec.enabled = True
    e = _engine(params, cfg, kv_kernel=False, flight_recorder=rec)
    e.generate(_prompts(cfg)[:2], max_new_tokens=3)
    attns = {(r.get("program") or {}).get("attn")
             for r in rec.tail() if r["burst"] == "decode"}
    assert attns == {"gather"}


def test_env_knob(params, cfg, monkeypatch):
    monkeypatch.setenv("SKYTPU_KV_KERNEL", "1")
    assert _engine(params, cfg).kv_kernel is True
    monkeypatch.setenv("SKYTPU_KV_KERNEL", "0")
    assert _engine(params, cfg).kv_kernel is False
    monkeypatch.delenv("SKYTPU_KV_KERNEL")
    assert _engine(params, cfg).kv_kernel is False
    # ctor wins over env
    monkeypatch.setenv("SKYTPU_KV_KERNEL", "1")
    assert _engine(params, cfg, kv_kernel=False).kv_kernel is False
