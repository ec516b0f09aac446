"""The latent-cache family (``models/glm_moe.py`` through
``infer/latent.py``) against its plain reference
(``benchmarks/reference/glm_moe.py``), at a tiny size on the CPU with the
benchmark's seeded weights and a FLOAT32 program:

(a) the model's whole-sequence forward = the reference (logits);
(b) prefill through waves and through chunks, then decode through the
    paged latent cache — single steps and bursts, two span rungs, a
    prefix hit, a copy-on-write block — = the reference's full forward at
    every position: as logits where a program exposes them
    (``decode_step``, the wave) and as greedy tokens behind a top-2
    margin guard where it returns tokens only;
(c) absorbed = materialised attention;
(d) the expert layer = the reference under forced imbalance (every token
    to the same experts; experts with no token): nothing is dropped; the
    few-row form visits exactly the experts its LIVE rows chose, and a
    burst's ``experts_read`` is that count, delivered with its tokens;
(e) the selection bias changes WHICH experts are chosen, never their
    weights;
(f) the options the family does not serve are refused by name.
"""

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks import weights_glm_moe as G
from benchmarks.families import glm_moe as family
from benchmarks.reference import glm_moe as ref
from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import kvcache, latent, sampling
from skypilot_tpu.models import glm_moe as glm
from skypilot_tpu.models import llama, registry
from skypilot_tpu.ops import attention as attn_ops
from skypilot_tpu.ops import grouped_ffn
from skypilot_tpu.utils import timeline

SEED = 2_900_000_011          # more than 31 bits
# Float32 program against a float32 reference: what is left is the order
# of summation (absorbed against materialised products, sorted groups
# against a loop over experts). Logits have a standard deviation of ~1.
LOGIT_TOL = 2e-4
# A greedy token is compared only where the reference's best logit leads
# its second by more than this (else either token is a right answer).
MARGIN = 1e-3

TINY = {
    "name": "glm-moe-test", "family": "glm_moe", "vocab_size": 512,
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True,
    "rope_theta": 1000000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512,
    "precision": {"weights": "bf16", "activations": "bf16", "kv": "bf16"}}


@pytest.fixture(scope="module")
def dims():
    return family.dims(TINY)


@pytest.fixture(scope="module")
def cfg():
    return family.register(TINY, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(dims):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        G.build_serving(SEED, dims))


@pytest.fixture(scope="module")
def reference(dims):
    return ref.Reference(dims, ref.Precision())


def _key():
    return jnp.asarray(W.seed_key(SEED))


def _ref_logits(reference, seq):
    """The reference's logits at every position of one sequence (padded
    to a multiple of 16 so few shapes compile)."""
    n = -(-len(seq) // 16) * 16
    tokens = np.zeros((1, n), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(reference.logits(_key(), jnp.asarray(tokens)))[
        0, :len(seq)]


def _check_greedy(reference, prompt, out):
    """Every served token that the margin guard admits is the
    reference's argmax after the tokens before it."""
    logits = _ref_logits(reference, list(prompt) + list(out))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
    top2 = np.sort(rows, axis=-1)[:, -2:]
    judged = 0
    for tok, row, (second, best) in zip(out, rows, top2):
        if best - second > MARGIN:
            assert tok == int(row.argmax())
            judged += 1
    assert judged >= len(out) // 2       # the guard must not eat the test


def _engine(params, cfg, **kw):
    kw = dict(dict(n_slots=4, max_len=256, prompt_buckets=(32, 64, 256),
                   prefill_chunk=32, kv_block=16, prefix_pool=4, max_wave=2,
                   pad_waves=True, span_buckets=[64, 128]), **kw)
    return eng.InferenceEngine(params, cfg, **kw)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, n).tolist() for n in lengths]


# -- (a) ---------------------------------------------------------------------

def test_registry_finds_both_families(cfg):
    assert registry.get_config("glm-moe-test") is cfg
    assert registry.get_config("llama3-tiny").n_kv_heads == 2
    assert registry.model_for(cfg) is glm
    assert kvcache.programs_for(cfg) is latent
    assert kvcache.programs_for(registry.get_config("llama3-tiny")) \
        is kvcache
    with pytest.raises(KeyError, match="unknown serving config"):
        registry.get_config("no-such-model")


def test_seeded_tree_is_the_models_layout(cfg, dims, params):
    abstract = jax.eval_shape(
        lambda: glm.init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(params) == jax.tree.structure(abstract)
    assert [a.shape for a in jax.tree.leaves(params)] \
        == [a.shape for a in jax.tree.leaves(abstract)]
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == cfg.num_params() == dims.num_params()


def test_published_parameter_count():
    """The cut the cell serves: 1 dense + 6 expert layers of the
    published widths are 4.53 B parameters; the whole model 29.9 B."""
    cut = dataclasses.replace(glm.CONFIGS["glm-4.7-flash"], n_layers=7)
    assert cut.num_params() == 4_530_936_960
    assert round(glm.CONFIGS["glm-4.7-flash"].num_params() / 1e9, 1) == 29.9
    assert cut.latent_row_width == 576
    assert latent.token_bytes(cut) == 8064


def test_forward_equals_reference(cfg, params, reference):
    tokens = np.asarray(_prompts([48, 48], seed=1), np.int32)
    got = np.asarray(jax.jit(lambda p, t: glm.forward(p, t, cfg))(
        params, jnp.asarray(tokens)))
    want = np.asarray(reference.logits(_key(), jnp.asarray(tokens)))
    assert want.std() > 0.5
    assert np.abs(got - want).max() < LOGIT_TOL


def test_reference_query_blocks_need_not_divide_the_length(dims, reference,
                                                          monkeypatch):
    """The reference attends in blocks of query rows; a length that is
    no multiple of the block (a served sequence padded to 128) gives the
    same logits as one block over everything."""
    tokens = jnp.asarray(_prompts([80], seed=11), jnp.int32)
    whole = np.asarray(reference.logits(_key(), tokens))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)       # 2.5 blocks
    blocked = np.asarray(ref.Reference(dims, ref.Precision()).logits(
        _key(), tokens))
    assert np.abs(whole - blocked).max() < 1e-4


# -- (b) ---------------------------------------------------------------------

def test_wave_then_decode_steps_equal_reference_logits(cfg, params,
                                                       reference):
    """The programs that expose logits, driven directly: a wave's last
    positions, then eight single decode steps through the paged latent
    cache at a span rung (64) that covers the rows, logits compared at
    every position."""
    prompts = _prompts([20, 27], seed=2)
    n_blocks, bl = 12, 16
    cache = latent.init_paged_cache(cfg, 3, n_blocks, bl)
    table = np.full((3, 5), n_blocks, np.int32)
    table[0, :4] = [0, 1, 2, 3]
    table[1, :4] = [7, 6, 5, 4]                 # blocks in any order
    table = jnp.asarray(table)
    tokens = np.zeros((2, 32), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts])
    rows, logits = jax.jit(lambda p, t, n: latent.prefill_batch(
        p, t, n, cfg))(params, jnp.asarray(tokens), lens)
    seqs = [list(p) for p in prompts]
    for i, p in enumerate(prompts):
        want = _ref_logits(reference, p)[-1]
        assert np.abs(np.asarray(logits[i]) - want).max() < LOGIT_TOL
        first = int(want.argmax())
        cache = latent.insert(
            cache, {n: r[:, i] for n, r in rows.items()},
            jnp.asarray(i), lens[i], jnp.asarray(first), table=table)
        seqs[i].append(first)
    step = jax.jit(lambda p, c: latent.decode_step(p, c, cfg, table=table,
                                                   span=64))
    active = jnp.asarray([True, True, False])
    for _ in range(8):
        cache, logits = step(params, cache)
        toks = sampling.argmax_tokens(logits)
        cache = kvcache.commit_tokens(cache, toks, active)
        for i in range(2):
            want = _ref_logits(reference, seqs[i])[-1]
            assert np.abs(np.asarray(logits[i]) - want).max() < LOGIT_TOL
            seqs[i].append(int(toks[i]))
    assert list(np.asarray(cache["length"])) == [28, 35, 0]


def test_engine_waves_chunks_bursts_and_span_rungs(cfg, params, reference):
    """Through the engine: prompts on the wave path (<= 32) and on the
    chunk path (two to four chunks), decode bursts at two span rungs."""
    e = _engine(params, cfg)
    prompts = _prompts([10, 23, 40, 100], seed=3)
    outs = e.generate(prompts, max_new_tokens=12)
    for p, out in zip(prompts, outs):
        assert len(out) == 12
        _check_greedy(reference, p, out)
    kinds = {k.split("[")[0] for k in e.compile_watch.summary()}
    assert {"admit_wave", "prefill_chunk", "decode_burst"} <= kinds
    spans = {key[2] for key in e.decode_programs}
    assert len(spans) >= 2, spans


def test_engine_single_steps(cfg, params, reference):
    """``step()``: the one-token program (``jit__decode``)."""
    e = _engine(params, cfg)
    (prompt,) = _prompts([45], seed=4)
    rid = e.add_request(prompt, max_new_tokens=6)
    while e.waiting or e.chunking or e.slot_req:
        e.step()
    (req,) = [r for r in e.finished if r.rid == rid]
    _check_greedy(reference, prompt, req.tokens)
    assert any(k.startswith("decode1") for k in e.compile_watch.summary())


def test_prefix_hit_and_copy_on_write(cfg, params, reference):
    """chunk 32 over blocks of 24 rows: a stored 64-row prefix ends
    inside a block, so the store copies-on-share and the hit
    copies-on-write — latent blocks move like any blocks, and the warm
    answer is the cold one and the reference's."""
    e = _engine(params, cfg, max_len=240, kv_block=24,
                prompt_buckets=(32, 64, 240), span_buckets=[120])
    assert e.kv_block == 24
    system = _prompts([64], seed=5)[0]
    pa, pb = system + [31, 32, 33, 34, 35], system + [41, 42, 43]
    cow0 = eng.KV_COW_COPIES._require_default().value
    e.generate([pa], max_new_tokens=4)
    e.finished.clear()
    warm = e.generate([pb], max_new_tokens=8)[0]
    (req,) = e.finished
    assert req.cached_len == 64                   # suffix-only prefill
    assert eng.KV_COW_COPIES._require_default().value >= cow0 + 2
    _check_greedy(reference, pb, warm)
    e.finished.clear()
    e.clear_prefix_cache()
    assert e.generate([pb], max_new_tokens=8)[0] == warm


def test_warm_grid_covers_the_latent_programs(cfg, params):
    e = _engine(params, cfg)
    n = e.warm_programs(max_burst=8)
    assert n > 0
    e.declare_warmup_complete()
    alarms = eng.flight_lib.UNEXPECTED_COMPILES._require_default()
    before = alarms.value
    e.generate(_prompts([12, 70], seed=6), max_new_tokens=4)
    assert e.warm_programs(max_burst=8) == 0      # nothing new compiled
    assert alarms.value == before
    led = e.hbm_ledger.snapshot()
    assert led["latent_kv_pool"] > 0 and "kv_pool" not in led
    assert led["expert_weights"] == sum(
        params["moe"][n].nbytes for n in ("we_gate", "we_up", "we_down"))
    assert eng.KV_TOKEN_BYTES._require_default().value \
        == cfg.n_layers * 40 * 4                  # float32 rows of 32 + 8


# -- (c) ---------------------------------------------------------------------

def test_absorbed_equals_materialised(cfg, params):
    layer = jax.tree.map(lambda a: a[1], params["moe"])
    x = jax.random.normal(jax.random.key(7), (2, 24, cfg.d_model))
    cos, sin = glm.rope_tables(cfg, jnp.arange(24))
    q_nope, q_pe, c_kv, k_pe = glm.mla_project(cfg, layer, x, cos, sin)
    causal = jnp.tril(jnp.ones((24, 24), bool))[None]
    segments = [(c_kv[:, :10], k_pe[:, :10], causal[:, :, :10]),
                (c_kv[:, 10:], k_pe[:, 10:], causal[:, :, 10:])]
    absorbed, plain = (glm.latent_attention(
        cfg, layer["wkv_b"], q_nope, q_pe, segments, form)
        for form in (True, False))
    whole = glm.causal_attention(cfg, layer["wkv_b"], q_nope, q_pe, c_kv,
                                 k_pe)
    assert float(jnp.abs(plain).max()) > 0.1
    assert float(jnp.abs(absorbed - plain).max()) < 1e-5
    assert float(jnp.abs(whole - plain).max()) < 1e-5


# -- (d), (e) ----------------------------------------------------------------

def _expert_layer(params, dims, bias):
    layer = {n: a[0] for n, a in params["moe"].items()}
    layer["router_bias"] = jnp.asarray(bias, jnp.float32)
    return layer


BIASES = pytest.mark.parametrize("bias", [
    [9, 9, 0, 0, 0, 0, 0, 0],         # every token to experts 0 and 1
    [0, 0, 0, -9, -9, -9, 9, 0],      # one expert for all, three for none
    [0] * 8], ids=["all-to-two", "one-hot-three-empty", "free"])


def _shared(cfg, h, layer):
    return glm._swiglu(h, layer["ws_gate"], layer["ws_up"],
                       layer["ws_down"], jnp.float32)


@pytest.mark.parametrize("form", ["visited", "few-rows", "grouped",
                                  "grouped-kernel"])
@BIASES
def test_expert_layer_under_forced_imbalance(cfg, dims, params, bias, form,
                                             monkeypatch):
    rows = 96
    if form == "grouped-kernel":
        # The Pallas form of the grouped products (interpreted here):
        # taken on a TPU for whole tiles, so 128 rows x top-2 at widths
        # of 128, in a layer of the same seeded tensors.
        rows = 128
        dims = dataclasses.replace(dims, d_model=128, moe_d_ff=128)
        cfg = dataclasses.replace(cfg, d_model=128, moe_d_ff=128)
        params = {"moe": {n: a.astype(jnp.float32)[None]
                          for n, a in G.layer_tensors(
                              _key(), dims, np.uint32(1), True).items()}}
        monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    layer = _expert_layer(params, dims, bias)
    h = jax.random.normal(jax.random.key(8), (rows, cfg.d_model))
    idx, w = glm.route(cfg, h, layer)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=8)
    if bias[0] == 9:
        assert counts[0] == counts[1] == rows and counts[2:].sum() == 0
    if bias[6] == 9:
        assert counts[6] == rows and counts[3:6].sum() == 0
    if form == "visited":
        combine = glm.combine_weights(cfg, idx, w)
        ids, n = glm.touched_experts(cfg, idx)
        assert int(n) == np.count_nonzero(counts)    # a turn an expert
        got = glm.experts_visited(cfg, h, combine, ids, n, layer)
    elif form == "few-rows":
        got, n = glm.experts_few_rows(cfg, h, idx, w, layer)
        assert int(n) == np.count_nonzero(counts)
    else:
        assert (grouped_ffn.tiles_for(rows * 2, cfg.d_model, cfg.moe_d_ff)
                is not None) == (form == "grouped-kernel")
        got = glm.experts_grouped(cfg, h, idx, w, layer)
    want = ref.expert_ffn(h, layer, dims, ref.Precision())
    assert float(jnp.abs(want).max()) > 0.5
    # Every token-choice is in the result: nothing dropped.
    assert float(jnp.abs(got + _shared(cfg, h, layer) - want).max()) < 1e-4


@pytest.mark.parametrize("live", [
    [5], [3, 17], list(range(0, 33, 4)), list(range(33)), []],
    ids=["one-live", "two-live", "nine-live", "all-live", "none-live"])
@BIASES
def test_visit_reads_what_the_live_rows_chose(cfg, dims, params, bias, live):
    """A decode step's 33 rows of which ``live`` count: the touched list
    is numpy's sorted distinct choices of the live rows, a dead row's
    choices are not in it and its routed output is zero, live rows
    equal the reference and what they get alone."""
    layer = _expert_layer(params, dims, bias)
    h = jax.random.normal(jax.random.key(10), (33, cfg.d_model))
    mask = np.zeros((33,), bool)
    mask[live] = True
    idx, w = glm.route(cfg, h, layer)
    got, n = jax.jit(lambda *a: glm.experts_few_rows(cfg, *a))(
        h, idx, w, layer, jnp.asarray(mask))
    ids, n_listed = glm.touched_experts(cfg, idx, jnp.asarray(mask))
    mine = np.unique(np.asarray(idx)[mask])
    assert int(n) == int(n_listed) == len(mine)
    assert np.asarray(ids)[:len(mine)].tolist() == mine.tolist()
    if bias[0] == 9:
        assert int(n) == (2 if live else 0)
    if len(live) == 1:
        assert int(n) == cfg.experts_per_tok
    if bias == [0] * 8 and len(live) == 33:
        assert int(n) == 8                   # every expert, one by one
    if bias == [0] * 8 and 0 < len(live) <= 2:
        # the case is not vacuous: dead rows chose experts no live row did
        assert set(np.asarray(idx).ravel()) - set(mine)
    got = np.asarray(got)
    assert not got[~mask].any()
    if not live:
        return
    want = ref.expert_ffn(h, layer, dims, ref.Precision())
    assert np.abs((got + np.asarray(_shared(cfg, h, layer))
                   - np.asarray(want))[mask]).max() < 1e-4
    alone, n_alone = glm.experts_few_rows(cfg, h[mask], idx[mask], w[mask],
                                          layer)
    assert int(n_alone) == int(n)
    assert np.abs(got[mask] - np.asarray(alone)).max() < 1e-5


def test_row_mask_is_a_few_row_argument(cfg, dims, params):
    layer = _expert_layer(params, dims, [0] * 8)
    h = jnp.zeros((1, 96, cfg.d_model))
    with pytest.raises(ValueError, match="few-row"):
        glm.moe_ffn(cfg, h, layer, jnp.ones((1, 96), bool))
    y, _ = glm.moe_ffn(cfg, h, layer)              # chunks and waves
    assert y.shape == h.shape


def _ref_choices(dims, seq):
    """The reference's routing of one sequence: chosen experts
    [expert layers, positions, K], from its own layer functions."""
    key, prec = _key(), ref.Precision()
    x = G.embedding(key, dims).astype(jnp.float32)[
        jnp.asarray(seq, jnp.int32)[None]]
    out = []
    for i in range(dims.n_layers):
        moe = i >= dims.first_k_dense
        w = ref.layer_weights(key, dims, np.uint32(i), moe, prec)
        if moe:
            h = ref.rms_norm(x + ref.mla(x, w, dims, prec), w["ln2"],
                             dims.norm_eps)[0]
            out.append(np.asarray(ref.router(h, w, dims)[0]))
        x = ref.decoder_layer(x, w, dims, moe, prec)
    return np.stack(out)


def _ref_experts_read(dims, seqs, starts, k):
    """Experts a burst of ``k`` steps must read: per step and expert
    layer, the distinct experts chosen at position ``start + step`` of
    each live sequence."""
    choices = [_ref_choices(dims, s) for s in seqs]
    return [sum(len(np.unique(np.concatenate(
        [c[layer, at + step] for c, at in zip(choices, starts)])))
        for layer in range(choices[0].shape[0])) for step in range(k)]


def test_burst_of_two_live_slots_equals_reference_and_counts_their_experts(
        cfg, dims, params, reference):
    """Two live slots and a dead one through ``k = 4`` staged steps with
    the row mask: the live rows' logits are the reference's at every
    position, the step's count of experts read is the reference
    routing's, the dead row's choices are in neither, and the burst
    program hands the counts back in the last (the spare) slot's column."""
    prompts = _prompts([20, 27], seed=12)
    n_blocks, bl, k = 12, 16, 4
    cache = latent.init_paged_cache(cfg, 3, n_blocks, bl)
    table = np.full((3, 5), n_blocks, np.int32)
    table[0, :4] = [0, 1, 2, 3]
    table[1, :4] = [7, 6, 5, 4]
    table = jnp.asarray(table)
    tokens = np.zeros((2, 32), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts])
    rows, logits = jax.jit(lambda p, t, n: latent.prefill_batch(
        p, t, n, cfg))(params, jnp.asarray(tokens), lens)
    seqs = [list(p) for p in prompts]
    for i in range(2):
        first = int(np.asarray(logits[i]).argmax())
        cache = latent.insert(
            cache, {n: r[:, i] for n, r in rows.items()},
            jnp.asarray(i), lens[i], jnp.asarray(first), table=table)
        seqs[i].append(first)
    # The dead slot holds a stale token: it routes like any row.
    cache["last_token"] = cache["last_token"].at[2].set(77)
    active = jnp.asarray([True, True, False])

    def steps(p, c, live):
        def nxt(logits, s, last):
            tok = jnp.where(active, sampling.argmax_tokens(logits), last)
            return tok, logits
        return latent._staged_steps(p, c, cfg, table, 64, k,
                                    c["last_token"], nxt, live=live)[2:]

    got, reads = jax.jit(lambda p, c: steps(p, c, active))(params, cache)
    _, reads_all = jax.jit(lambda p, c: steps(p, c, None))(params, cache)
    got = np.asarray(got)                              # [k, 3, vocab]
    for s in range(k):
        for i in range(2):
            want = _ref_logits(reference, seqs[i])[-1]
            assert np.abs(got[s, i] - want).max() < LOGIT_TOL
            seqs[i].append(int(got[s, i].argmax()))
    want_reads = _ref_experts_read(dims, seqs, [len(p) for p in prompts], k)
    assert np.asarray(reads).tolist() == want_reads
    # (at most 2 rows x top-2 a layer)
    assert all(2 * 2 <= r <= 2 * 2 * cfg.experts_per_tok
               for r in want_reads)
    assert int(reads_all.sum()) > int(reads.sum())     # the dead row's
    _, _, toks = jax.jit(lambda p, c, r: latent.decode_burst_staged(
        p, c, r, active, k, cfg, sampling.SamplingParams(), table=table,
        span=64))(params, cache, jax.random.key(0))
    toks = np.asarray(toks)
    assert toks.shape == (k, 3)
    assert toks[:, 2].tolist() == want_reads
    assert toks[:, :2].tolist() == [[seqs[i][len(prompts[i]) + 1 + s]
                                     for i in range(2)] for s in range(k)]


def _fetch_records(path, t0_us):
    """The fetch records that began at or after ``t0_us`` (the
    timeline's buffer is the process's: another test's stay out)."""
    timeline.save_now()
    with open(path) as f:
        return [e["args"] for e in json.load(f)["traceEvents"]
                if e["name"] == "engine.decode.fetch"
                and e.get("ts", 0) >= t0_us]


def test_engine_burst_reports_experts_read_with_its_tokens(
        cfg, dims, params, tmp_path, monkeypatch):
    """One engine burst at 2 live slots of the pool: ``experts_read`` on
    the fetch record and the ``/metrics`` counter equal the count
    recomputed on the host from the reference's routing; an engine of
    the Llama family reports no such field."""
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    t0 = time.time() * 1e6
    e = _engine(params, cfg)
    prompts = _prompts([20, 27], seed=13)
    rids = [e.add_request(p, max_new_tokens=12) for p in prompts]
    e.admit()
    assert len(e.slot_req) == 2
    counter = latent.EXPERTS_READ._require_default()
    before = counter.value
    out = e.decode_burst(4)
    (rec,) = _fetch_records(path, t0)
    assert rec["k"] == 4 and rec["tokens"] == 8
    by_rid = {r.rid: r for r in e.slot_req.values()}
    seqs = [list(p) + by_rid[rid].tokens for p, rid in zip(prompts, rids)]
    assert all(len(out[rid]) == 4 for rid in rids)
    want = sum(_ref_experts_read(dims, seqs, [len(p) for p in prompts], 4))
    assert rec["experts_read"] == want == counter.value - before
    # about 2 rows x top-2 of 8 experts a layer, never all 8 x 2 x 4 steps
    assert want < 4 * cfg.n_moe_layers * cfg.n_routed_experts // 2

    lcfg = llama.CONFIGS["llama3-tiny"]
    le = eng.InferenceEngine(
        llama.init_params(jax.random.key(0), lcfg), lcfg, n_slots=4,
        max_len=128, prompt_buckets=(16, 32, 64, 128), kv_block=16)
    le.add_request(list(range(1, 9)), max_new_tokens=6)
    le.admit()
    assert le.decode_burst(4)
    records = _fetch_records(path, t0)
    assert len(records) == 2 and "experts_read" not in records[-1]
    assert records[-1]["tokens"] == 4
    assert counter.value - before == want


def test_selection_bias_moves_choices_not_weights(cfg, dims, params):
    h = jax.random.normal(jax.random.key(9), (256, cfg.d_model))
    free = _expert_layer(params, dims, [0] * 8)
    biased = _expert_layer(params, dims,
                           [0.3, -0.3, 0.2, -0.2, 0.1, -0.1, 0, 0])
    idx0, w0 = glm.route(cfg, h, free)
    idx1, w1 = glm.route(cfg, h, biased)
    same = np.asarray((jnp.sort(idx0, -1) == jnp.sort(idx1, -1)).all(-1))
    assert 0 < same.sum() < len(same)          # some choices moved
    s = jax.nn.sigmoid(h @ free["router"])     # the unbiased scores
    for idx, w in ((idx0, w0), (idx1, w1)):
        picked = jnp.take_along_axis(s, idx, -1)
        want = picked / picked.sum(-1, keepdims=True) * 1.8
        assert float(jnp.abs(w - want).max()) < 1e-5
    # The seeded bias of the benchmark's weights does the same.
    seeded = {n: a[0] for n, a in params["moe"].items()}
    idx2, _ = glm.route(cfg, h, seeded)
    moved = np.asarray((jnp.sort(idx0, -1) != jnp.sort(idx2, -1)).any(-1))
    assert 0 < moved.sum() < len(moved)


# -- (f) ---------------------------------------------------------------------

@pytest.mark.parametrize("option,kw", [
    ("kv_block=0", {"kv_block": 0}),
    ("kv_int8", {"kv_int8": True}),
    ("weights_int8", {"weights_int8": True}),
    ("tp", {"mesh": "a mesh"}),
    ("adapters", {"adapters": "a catalog"}),
    ("spec_k", {"spec_k": 4}),
    ("draft_model", {"draft_engine": "a drafter"}),
    ("kv_kernel", {"kv_kernel": True})])
def test_unsupported_options_are_refused_by_name(cfg, params, option, kw):
    with pytest.raises(eng.UnsupportedOptionError) as err:
        _engine(params, cfg, **kw)
    assert err.value.typed_error["type"] == "unsupported_option"
    assert err.value.typed_error["option"] == option


def test_serving_weights_builder_knows_the_family(cfg):
    params, qweights = eng.random_serving_weights(cfg)
    assert qweights is None
    assert params["moe"]["we_gate"].shape == (2, 8, 64, 32)
    assert params["moe"]["we_gate"].dtype == cfg.dtype
    with pytest.raises(eng.UnsupportedOptionError, match="weights_int8"):
        eng.random_serving_weights(cfg, weights_int8=True)
