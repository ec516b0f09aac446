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
    to the same experts; experts with no token): nothing is dropped;
(e) the selection bias changes WHICH experts are chosen, never their
    weights;
(f) the options the family does not serve are refused by name.
"""

import dataclasses

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
from skypilot_tpu.models import registry

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


@pytest.mark.parametrize("form", ["dense", "grouped"])
@pytest.mark.parametrize("bias", [
    [9, 9, 0, 0, 0, 0, 0, 0],         # every token to experts 0 and 1
    [0, 0, 0, -9, -9, -9, 9, 0],      # one expert for all, three for none
    [0] * 8], ids=["all-to-two", "one-hot-three-empty", "free"])
def test_expert_layer_under_forced_imbalance(cfg, dims, params, bias, form):
    layer = _expert_layer(params, dims, bias)
    h = jax.random.normal(jax.random.key(8), (96, cfg.d_model))
    idx, w = glm.route(cfg, h, layer)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=8)
    if bias[0] == 9:
        assert counts[0] == counts[1] == 96 and counts[2:].sum() == 0
    if bias[6] == 9:
        assert counts[6] == 96 and counts[3:6].sum() == 0
    run = glm.experts_dense if form == "dense" else glm.experts_grouped
    got = run(cfg, h, idx, w, layer) + glm._swiglu(
        h, layer["ws_gate"], layer["ws_up"], layer["ws_down"], jnp.float32)
    want = ref.expert_ffn(h, layer, dims, ref.Precision())
    assert float(jnp.abs(want).max()) > 0.5
    # Every token-choice is in the result: nothing dropped.
    assert float(jnp.abs(got - want).max()) < 1e-4


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
