"""Inference engine: KV-cache decode parity + continuous batching."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import kvcache, sampling
from skypilot_tpu.models import llama


@pytest.fixture(scope="module")
def cfg():
    return llama.CONFIGS["llama3-tiny"]


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def moe_setup():
    """moe-tiny with generous capacity (no routing drops) + params —
    shared by every MoE inference test."""
    import dataclasses

    from skypilot_tpu.models import moe
    mcfg = dataclasses.replace(moe.CONFIGS["moe-tiny"],
                               capacity_factor=4.0)
    return moe, mcfg, moe.init_params(jax.random.key(0), mcfg)


def greedy_reference(params, cfg, prompt, n_new):
    """Greedy decode via repeated full forwards (the slow oracle)."""
    toks = list(prompt)
    for _ in range(n_new):
        logits = llama.forward(params, jnp.asarray([toks], jnp.int32), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_incremental_decode_matches_full_forward(cfg, params):
    prompt = [3, 17, 42, 7, 99]
    n_new = 8
    want = greedy_reference(params, cfg, prompt, n_new)

    e = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                            prompt_buckets=(16, 64))
    got = e.generate([prompt], max_new_tokens=n_new)[0]
    assert got == want


def test_continuous_batching_isolation(cfg, params):
    """Staggered concurrent requests decode exactly like solo runs."""
    p1, p2 = [5, 9, 31], [44, 2, 8, 19, 3, 27]
    want1 = greedy_reference(params, cfg, p1, 6)
    want2 = greedy_reference(params, cfg, p2, 6)

    e = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                            prompt_buckets=(16,))
    r1 = e.add_request(p1, max_new_tokens=6)
    e.step()   # r1 decodes alone for two steps
    e.step()
    r2 = e.add_request(p2, max_new_tokens=6)
    e.run_to_completion()
    by_rid = {r.rid: r.tokens for r in e.finished}
    assert by_rid[r1] == want1
    assert by_rid[r2] == want2


def test_slots_recycled(cfg, params):
    e = eng.InferenceEngine(params, cfg, n_slots=1, max_len=32,
                            prompt_buckets=(16,))
    outs = e.generate([[1, 2, 3], [4, 5, 6], [7, 8]], max_new_tokens=3)
    assert len(outs) == 3
    assert all(len(o) == 3 for o in outs)
    assert len(e.free_slots) == 1


def test_ttft_recorded(cfg, params):
    e = eng.InferenceEngine(params, cfg, n_slots=1, max_len=32,
                            prompt_buckets=(16,))
    e.add_request([1, 2, 3, 4], max_new_tokens=2)
    e.run_to_completion()
    req = e.finished[0]
    assert req.first_token_s is not None
    assert req.first_token_s >= req.submit_s


def test_eos_stops_decode(cfg, params):
    # Find the greedy first token, then declare it EOS: request must
    # retire after a single token.
    prompt = [3, 17, 42]
    first = greedy_reference(params, cfg, prompt, 1)[0]
    e = eng.InferenceEngine(params, cfg, n_slots=1, max_len=32,
                            prompt_buckets=(16,), eos_id=first)
    out = e.generate([prompt], max_new_tokens=10)[0]
    assert out == [first]


def test_sampling_temperature_valid(cfg, params):
    sp = sampling.SamplingParams(temperature=0.8, top_k=10)
    e = eng.InferenceEngine(params, cfg, n_slots=1, max_len=32,
                            prompt_buckets=(16,), sampling_params=sp)
    out = e.generate([[1, 2, 3]], max_new_tokens=5)[0]
    assert len(out) == 5
    assert all(0 <= t < cfg.vocab_size for t in out)


def test_oversized_prompt_rejected_at_submit(cfg, params):
    e = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                            prompt_buckets=(16,))
    with pytest.raises(ValueError):
        e.add_request(list(range(17)), max_new_tokens=2)
    # Engine is untouched: a valid request still goes through.
    out = e.generate([[1, 2, 3]], max_new_tokens=2)[0]
    assert len(out) == 2
    assert len(e.free_slots) == 2


def test_mixed_bucket_admission(cfg, params):
    """Prompts from different buckets admit in separate waves but all
    decode correctly together."""
    e = eng.InferenceEngine(params, cfg, n_slots=4, max_len=96,
                            prompt_buckets=(8, 32))
    short1, short2 = [1, 2, 3], [9, 8]
    long1 = list(range(1, 21))
    want_s1 = greedy_reference(params, cfg, short1, 4)
    want_l1 = greedy_reference(params, cfg, long1, 4)
    outs = e.generate([short1, long1, short2], max_new_tokens=4)
    assert outs[0] == want_s1
    assert outs[1] == want_l1
    assert len(outs[2]) == 4


def test_max_wave_splits_admission(cfg, params):
    """max_wave caps admission waves: 5 same-bucket requests admit in
    ceil(5/2)=3 waves (on_wave fires per wave), results identical to
    the unsplit engine."""
    e = eng.InferenceEngine(params, cfg, n_slots=8, max_len=64,
                            prompt_buckets=(8,), max_wave=2)
    prompts = [[i + 1, i + 2] for i in range(5)]
    for p in prompts:
        e.add_request(p, max_new_tokens=3)
    waves = []
    e.step_burst(max_burst=4, on_wave=lambda: waves.append(
        len(e.slot_req) + len(e.finished)))
    assert len(waves) == 3
    assert waves == [2, 4, 5]  # cumulative admissions per wave
    e.run_to_completion()
    got = {r.rid: r.tokens for r in e.finished}

    ref = eng.InferenceEngine(params, cfg, n_slots=8, max_len=64,
                              prompt_buckets=(8,))
    want = ref.generate(prompts, max_new_tokens=3)
    assert [got[i] for i in sorted(got)] == want


def test_engine_with_tp_sharded_params(cfg, params):
    """Engine serves correctly with tensor-parallel sharded weights."""
    from skypilot_tpu.parallel import mesh as mesh_lib, sharding as sh
    from skypilot_tpu.models import llama as llama_mod

    prompt = [3, 17, 42, 7]
    want = greedy_reference(params, cfg, prompt, 4)

    mesh = mesh_lib.make_mesh(mesh_lib.MeshShape(fsdp=2, tp=4))
    p_sh = sh.logical_to_sharding(
        llama_mod.param_logical_axes(cfg), mesh, sh.DEFAULT_RULES,
        shapes=params)  # divisibility guard: tiny dims stay replicated
    sharded = jax.device_put(params, p_sh)
    e = eng.InferenceEngine(sharded, cfg, n_slots=2, max_len=64,
                            prompt_buckets=(16,))
    got = e.generate([prompt], max_new_tokens=4)[0]
    assert got == want


def test_moe_engine_serves(moe_setup):
    """The engine serves sparse MoE models: incremental decode logits
    match the full forward (generous capacity so no routing drops)."""
    moe, mcfg, mparams = moe_setup
    prompt = [3, 17, 42, 7]

    # Incremental: prefill then two decode steps.
    cache = kvcache.init_cache(mcfg, 1, 32)
    padded = np.zeros((16,), np.int32)
    padded[:len(prompt)] = prompt
    prefix, logits0 = kvcache.prefill(
        mparams, jnp.asarray(padded), jnp.asarray(4), mcfg)
    tok0 = int(jnp.argmax(logits0))
    cache = kvcache.insert(cache, prefix, jnp.asarray(0),
                           jnp.asarray(4), jnp.asarray(tok0))
    cache, logits1 = kvcache.decode_step(mparams, cache, mcfg)

    # Oracle: full forward over prompt + tok0.
    full, _ = moe.forward(mparams,
                          jnp.asarray([prompt + [tok0]], jnp.int32), mcfg)
    np.testing.assert_allclose(np.asarray(logits1[0]),
                               np.asarray(full[0, -1]),
                               rtol=2e-2, atol=6e-2)
    np.testing.assert_allclose(
        np.asarray(logits0), np.asarray(
            moe.forward(mparams, jnp.asarray([prompt], jnp.int32),
                        mcfg)[0][0, -1]), rtol=2e-2, atol=6e-2)

    # End-to-end through the engine.
    e = eng.InferenceEngine(mparams, mcfg, n_slots=2, max_len=32,
                            prompt_buckets=(16,))
    out = e.generate([prompt], max_new_tokens=4)[0]
    assert len(out) == 4
    assert all(0 <= t < mcfg.vocab_size for t in out)


def test_kv_int8_quantize_roundtrip():
    x = jax.random.normal(jax.random.key(0), (4, 7, 2, 64)) * 3.0
    q, scale = kvcache.quantize_rows(x)
    assert q.dtype == jnp.int8 and scale.shape == (4, 7, 2)
    back = kvcache.dequantize_rows(q, scale)
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    assert err <= float(np.abs(np.asarray(x)).max()) / 127 + 1e-6


def test_kv_int8_cache_shapes(cfg):
    c = kvcache.init_cache(cfg, 3, 16, kv_int8=True)
    assert c["k"].dtype == jnp.int8
    # Row dim minormost: [..., G] minor would tile-pad 8->128 (16x).
    assert c["k_scale"].shape == (cfg.n_layers, 3, cfg.n_kv_heads, 16)
    axes = kvcache.cache_logical_axes(c)
    assert "k_scale" in axes
    assert "k_scale" not in kvcache.cache_logical_axes()


def test_kv_int8_engine_matches_fp_closely(cfg, params):
    """int8 KV decode tracks the fp cache closely: greedy generations
    agree on a short horizon (per-row absmax error is ~1/127)."""
    prompt = list(range(1, 25))
    sp = sampling.SamplingParams(temperature=0.0)  # greedy
    e_fp = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                               prompt_buckets=(32,), sampling_params=sp)
    e_q = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                              prompt_buckets=(32,), sampling_params=sp,
                              kv_int8=True)
    out_fp = e_fp.generate([prompt], max_new_tokens=8)[0]
    out_q = e_q.generate([prompt], max_new_tokens=8)[0]
    assert len(out_q) == len(out_fp)
    # First token comes from the (unquantized) prefill: must agree.
    assert out_q[0] == out_fp[0]
    # The rest run over the int8 cache; demand strong agreement.
    same = sum(a == b for a, b in zip(out_q, out_fp))
    assert same >= len(out_fp) - 1, (out_fp, out_q)


def test_weights_int8_engine_generates_sensibly(cfg, params):
    """w8a8 decode: greedy output stays close to the fp engine (per-
    channel weight + per-token activation int8; ~1% matmul error)."""
    prompt = list(range(1, 20))
    sp = sampling.SamplingParams(temperature=0.0)
    e_fp = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                               prompt_buckets=(32,), sampling_params=sp)
    e_q = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                              prompt_buckets=(32,), sampling_params=sp,
                              weights_int8=True)
    out_fp = e_fp.generate([prompt], max_new_tokens=6)[0]
    out_q = e_q.generate([prompt], max_new_tokens=6)[0]
    assert len(out_q) == len(out_fp)
    # Prefill AND decode are quantized (that is what frees the fp
    # weights): demand strong but not exact agreement.
    same = sum(a == b for a, b in zip(out_q, out_fp))
    assert same >= len(out_fp) - 2, (out_fp, out_q)


def test_weights_int8_composes_with_kv_int8(cfg, params):
    sp = sampling.SamplingParams(temperature=0.0)
    e = eng.InferenceEngine(params, cfg, n_slots=1, max_len=48,
                            prompt_buckets=(16,), sampling_params=sp,
                            kv_int8=True, weights_int8=True)
    out = e.generate([[5, 9, 31]], max_new_tokens=5)[0]
    assert len(out) == 5
    assert all(0 <= t < cfg.vocab_size for t in out)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp", "int8"])
def test_wave_and_chunk_rows_land_alike(cfg, kv_int8):
    """One row writer under every program: a prompt's rows written by
    ``insert`` through a block table (scattered blocks, a partial last
    one) and read back through ``_gather_kv_layer`` are, bit for bit,
    the rows the contiguous ``insert`` stores, and the rows
    ``prefill_chunk`` leaves for the same tokens through the same table
    (same addresses, same scale layout; the values agree to the two
    attentions' summation order)."""
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg)
    n, S, bl = 13, 16, 8
    toks = np.zeros((S,), np.int32)
    toks[:n] = np.random.default_rng(0).integers(1, cfg.vocab_size, n)
    toks, i32 = jnp.asarray(toks), lambda v: jnp.asarray(v, jnp.int32)
    tbl = np.full((2, 5), 6, np.int32)           # 6 blocks; 6 = sentinel
    tbl[1, :4] = [4, 1, 5, 2]
    table, slot = jnp.asarray(tbl), i32(1)
    prefix, logits = kvcache.prefill(params, toks, i32(n), cfg)
    first = i32(jnp.argmax(logits))
    paged0 = kvcache.init_paged_cache(cfg, 2, 6, bl, kv_int8=kv_int8)
    waved = kvcache.insert(paged0, prefix, slot, i32(n), first, table=table)
    contig = kvcache.insert(kvcache.init_cache(cfg, 2, 4 * bl, kv_int8),
                            prefix, slot, i32(n), first)
    chunked, _, tok = kvcache.prefill_chunk(
        params, paged0, toks, i32(0), i32(n), slot, i32(n),
        jax.random.key(0), cfg, sampling.SamplingParams(), final=True,
        table=table)
    assert int(tok) == int(first)
    assert int(chunked["length"][1]) == int(waved["length"][1]) == n

    def rows(cache, tbl, layer):
        """Slot 1's first n rows as read back: k, v [n, G, hd] (+ the
        two scales [n, G])."""
        k, v, ks, vs = kvcache._gather_kv_layer(cache, layer, tbl,
                                                jnp.arange(2))
        out = [np.asarray(k[1, :n]), np.asarray(v[1, :n])]
        if ks is not None:
            out += [np.asarray(ks[1, :, :n].T), np.asarray(vs[1, :, :n].T)]
        return out

    def values(r):
        if not kv_int8:
            return r
        return [np.asarray(kvcache.dequantize_rows(r[i], r[i + 2]))
                for i in (0, 1)]

    for layer in range(cfg.n_layers):
        through_table = rows(waved, table, layer)
        for got, want in zip(through_table, rows(contig, None, layer)):
            assert np.array_equal(got, want)
        # (the chunk's attention dots run in bf16, the wave's in fp32)
        for got, want in zip(values(rows(chunked, table, layer)),
                             values(through_table)):
            np.testing.assert_allclose(got, want, atol=0.08)


@pytest.mark.parametrize("family", ["llama", "moe"])
def test_staged_burst_cache_matches_oracle(family, cfg, params,
                                           moe_setup):
    """The staged burst's ONE-flush cache write must leave the cache
    exactly as the per-step path would: after a burst, a single
    decode_step's logits agree with a full forward over the whole
    generated sequence (wrong flush indices/lengths would corrupt
    attention here, not just shift tokens). Parametrized over the
    dense llama path and the MoE (_ffn experts) branch."""
    if family == "llama":
        mcfg, mparams = cfg, params
        fwd = lambda seq: llama.forward(
            mparams, jnp.asarray([seq], jnp.int32), mcfg)[0, -1]
    else:
        moe, mcfg, mparams = moe_setup
        fwd = lambda seq: moe.forward(
            mparams, jnp.asarray([seq], jnp.int32), mcfg)[0][0, -1]
    e = eng.InferenceEngine(mparams, mcfg, n_slots=2, max_len=64,
                            prompt_buckets=(8,))
    prompt = [3, 17, 42, 7]
    e.add_request(list(prompt), max_new_tokens=16)
    e.admit()
    out = e.decode_burst(max_burst=4)       # staged program, k=4
    (req,) = e.slot_req.values()
    assert len(req.tokens) == 5             # admission token + burst
    assert list(out.values())[0] == req.tokens[1:]

    # Logits for the NEXT position via the burst-flushed cache...
    _, logits = kvcache.decode_step(e.params, e.cache, mcfg,
                                    table=e.table_device())
    got = np.asarray(logits[req.slot])
    # ...vs the from-scratch oracle over prompt + generated tokens.
    want = np.asarray(fwd(prompt + req.tokens))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=6e-2)
