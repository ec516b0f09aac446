"""The short-convolution family: ``models/lfm2_moe.py`` and its serve
programs (``infer/shortconv.py``) and the engine over them, against the
plain reference's FULL forward (``benchmarks/reference/lfm2_moe.py``),
at a tiny size on the CPU with the benchmark's seeded weights and a
FLOAT32 program. What must hold of two rows a slot beside paged K/V:

(a) the model file's ``forward`` is the reference's, over a layer list
    that repeats with no period, and each ASSUMED item of the reference
    is a switch that, turned off alone, moves the logits (all but the
    1e-20 of the weights' sum, which no tolerance can see);
(b) prefill by wave — two rows of unequal length, each row's tail taken
    at ITS last real token — and by chunks of unequal split, then decode
    through the cache, equal the reference's full forward pass: LOGITS,
    not tokens; the same run with every conv tail zeroed at every
    program boundary FAILS that comparison (the mechanism's own
    control), and a slot's last tenant's tails reach nothing;
(c) a decode program moves the tails of its live slots only;
(d) ``glm_moe.moe_ffn`` with ``n_shared_experts`` 0 reads no ``ws_*``
    tensor, for few rows and for many, and equals the definition;
(e) served tokens are the reference's, every refusal is typed, the HBM
    ledger and the dispatch annotations say what the family holds.

LOGIT_TOL: float32 program against float32 reference at ``highest``, the
two differing in summation order alone (one pass against chunks and
steps; an expert visit against a loop over all experts) — gaps measured
at 1e-6 to 2e-5 on logits of std 0.16; 2e-4 leaves ten times that and is
a thousand times under the smallest control (the zeroed tail: 0.1 and
more). A greedy token is compared only where the reference's best logit
leads its second by more than MARGIN.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks import weights_lfm2_moe as G
from benchmarks.families import lfm2_moe as family
from benchmarks.reference import lfm2_moe as ref
from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import sampling, shortconv
from skypilot_tpu.models import glm_moe, llama
from skypilot_tpu.models import lfm2_moe as lfm
from skypilot_tpu.utils import timeline

LOGIT_TOL = 2e-4
MARGIN = 5e-3
SEED = 20261003
C, F = "conv", "full_attention"
# No period: the attention layers lie 3, 2 and 2 apart.
TYPES = [C, F, C, C, F, C, F, C]
TINY = dict(
    name="lfm2-moe-test", conv_L_cache=3, conv_bias=False, hidden_size=64,
    intermediate_size=128, layer_types=TYPES, max_position_embeddings=512,
    moe_intermediate_size=32, norm_eps=1e-5, norm_topk_prob=True,
    num_attention_heads=4, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, num_hidden_layers=8, num_key_value_heads=2,
    head_dim=16, rope_theta=1_000_000, routed_scaling_factor=1,
    use_expert_bias=True, vocab_size=512)


def _built(config):
    """(dims, float32 program config, float32 seeded params, reference)."""
    dims = family.dims(config)
    cfg = family.register(config, dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          G.build_serving(SEED, dims))
    return dims, cfg, params, ref.Reference(dims, ref.Precision())


@pytest.fixture(scope="module")
def built():
    return _built(TINY)


@pytest.fixture(scope="module")
def cfg(built):
    return built[1]


@pytest.fixture(scope="module")
def params(built):
    return built[2]


@pytest.fixture(scope="module")
def reference(built):
    return built[3]


def _key():
    return jnp.asarray(W.seed_key(SEED))


def _ref_logits(reference, seq):
    n = -(-len(seq) // 16) * 16
    tokens = np.zeros((1, n), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(reference.logits(_key(), jnp.asarray(tokens)))[
        0, :len(seq)]


def _check_greedy(reference, prompt, out):
    logits = _ref_logits(reference, list(prompt) + list(out))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
    top2 = np.sort(rows, axis=-1)[:, -2:]
    judged = 0
    for tok, row, (second, best) in zip(out, rows, top2):
        if best - second > MARGIN:
            assert tok == int(row.argmax())
            judged += 1
    assert judged >= len(out) // 2       # the guard must not eat the test


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, n).tolist() for n in lengths]


def _events_since(path, t0_us):
    """The saved timeline's events that began at or after ``t0_us``
    (``time.time() * 1e6``, the timeline's own clock)."""
    with open(path) as f:
        return [ev for ev in json.load(f)["traceEvents"]
                if ev.get("ts", 0) >= t0_us]


def _engine(params, cfg, **kw):
    kw = dict(dict(n_slots=4, max_len=256, prompt_buckets=(32, 64, 256),
                   prefill_chunk=32, kv_block=16, max_wave=2,
                   pad_waves=True, span_buckets=[64, 128]), **kw)
    return eng.InferenceEngine(params, cfg, **kw)


def _table(n_slots, n_blocks, rows, cols=17):
    """Block table of ``cols - 1`` blocks a slot + the sentinel column."""
    table = np.full((n_slots, cols), n_blocks, np.int32)
    for slot, blocks in rows.items():
        table[slot, :len(blocks)] = blocks
    return jnp.asarray(table)


# -- (a): the model file -----------------------------------------------------

@pytest.mark.parametrize("types", [
    TYPES, [C, C, F, C, C, C, F, C], [F, C, C, C, C, C, C, F],
    [C] * 8], ids=["no-period", "published-head", "ends", "conv-only"])
def test_forward_is_the_reference_over_any_layer_list(types):
    """The stack is walked in the order the LIST gives, whatever it is:
    a list with no period, the first eight published entries, attention
    at both ends, no attention layer at all."""
    dims, cfg, params, reference = _built(
        dict(TINY, layer_types=types, name="lfm2-moe-test-list"))
    assert cfg.layer_types == tuple(types)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, 512, (2, 48)), jnp.int32)
    want = np.asarray(reference.logits(_key(), tokens))
    got = np.asarray(lfm.forward(params, tokens, cfg))
    assert np.abs(got - want).max() < LOGIT_TOL
    assert cfg.num_params() == dims.num_params() == sum(
        a.size for a in jax.tree.leaves(params))


@pytest.mark.parametrize("switch,moves", [
    ("tied_head", True), ("rope_half", True), ("qk_norm", True),
    ("expert_bias", True), ("weight_sum_eps", False)])
def test_each_assumed_item_is_a_switch(cfg, params, built, switch, moves):
    """Turned off alone, an assumed item moves the reference away from
    the program by a thousand tolerances — but for the 1e-20 of the
    weights' sum against the public code's 1e-6, which stays inside it
    (a departure noted, not one a test can hold)."""
    dims = built[0]
    assert switch in ref.ASSUMED
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        1, 512, (2, 48)), jnp.int32)
    got = np.asarray(lfm.forward(params, tokens, cfg))
    off = ref.Reference(dims, ref.Precision(), ref.ASSUMED - {switch})
    gap = np.abs(np.asarray(off.logits(_key(), tokens)) - got).max()
    assert (gap > 1000 * LOGIT_TOL) if moves else (gap < LOGIT_TOL)


def test_the_published_sizes_count_as_the_issue_counts_them():
    whole = lfm.CONFIGS["lfm2-8b-a1b"]
    assert whole.num_params() == 8_339_930_560
    assert (whole.conv_params(), whole.attn_params()) \
        == (16_783_360, 10_485_888)
    assert (whole.n_conv_layers, whole.n_full_layers) == (18, 6)
    # ... and its list has no period: the sixth attention layer follows
    # two conv layers where the others follow three.
    assert [b - a for a, b in zip(whole.full_layers,
                                  whole.full_layers[1:])] == [4, 4, 4, 4, 3]
    with pytest.raises(ValueError, match="shared"):
        lfm.Lfm2MoeConfig(n_shared_experts=1)
    with pytest.raises(ValueError, match="bias"):
        lfm.from_published(dict(TINY, conv_bias=True))


# -- (b), (c): programs ------------------------------------------------------

def _chunks_into_cache(params, cfg, cache, table, slot, seq, splits,
                       chunk=32, zero_tails=False):
    """``seq`` through ``prefill_chunk``, its pieces ``splits`` tokens
    long (each at most a chunk; a piece shorter than the chunk is padded,
    so a LATER chunk starts where the real tokens ended); returns (cache,
    the final chunk's first token). ``zero_tails``: the mechanism's
    control — every tail zeroed at every program boundary."""
    assert sum(splits) == len(seq) and max(splits) <= chunk
    fn = jax.jit(lambda c, t, s, n, f: shortconv.prefill_chunk(
        params, c, t, s, n, jnp.asarray(slot), jnp.asarray(len(seq)),
        jax.random.key(0), cfg, sampling.SamplingParams(), final=f,
        table=table), static_argnums=4)
    start, tok = 0, None
    for i, n in enumerate(splits):
        tokens = np.zeros((chunk,), np.int32)
        tokens[:n] = seq[start:start + n]
        if zero_tails:
            cache = dict(cache, conv=jnp.zeros_like(cache["conv"]))
        cache, _, tok = fn(cache, jnp.asarray(tokens), jnp.asarray(start),
                           jnp.asarray(n), i == len(splits) - 1)
        start += n
    return cache, int(tok)


def _steps(params, cfg, cache, table, slot, live, n, zero_tails=False):
    """``n`` single decode steps of ``slot`` fed greedily; returns (the
    steps' logits [n, vocab], the tokens fed)."""
    step = jax.jit(lambda c: shortconv.decode_step(
        params, c, cfg, table=table, live=live))
    out, fed = [], []
    for _ in range(n):
        if zero_tails:
            cache = dict(cache, conv=jnp.zeros_like(cache["conv"]))
        fed.append(int(cache["last_token"][slot]))
        cache, logits = step(cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cache = dict(cache, length=cache["length"] + live,
                     last_token=jnp.where(live, tok, cache["last_token"]))
        out.append(np.asarray(logits[slot]))
    return np.stack(out), fed


@pytest.mark.parametrize("splits", [[32, 32, 11], [7, 32, 1, 20, 15],
                                    [2, 1, 32, 32, 8]],
                         ids=["whole", "unequal", "shorter-than-a-tail"])
def test_chunks_of_unequal_split_then_steps_equal_one_pass(
        cfg, params, reference, splits):
    """A 75-token prompt in chunks of unequal split — pieces shorter
    than a chunk, one of ONE token (the new tail reaches back into the
    carried one) — into a slot whose last tenant left garbage, then five
    decode steps: every step's logits are the full forward's."""
    (seq,) = _prompts([75], seed=sum(splits[:2]))
    table = _table(3, 24, {1: list(range(3, 3 + 12))})
    cache = shortconv.init_paged_cache(cfg, 3, 24, 16)
    cache["conv"] = cache["conv"] + 7.0          # the last tenant's tails
    cache, first = _chunks_into_cache(params, cfg, cache, table, 1, seq,
                                      splits)
    live = jnp.asarray([False, True, False])
    got, fed = _steps(params, cfg, cache, table, 1, live, 5)
    want = _ref_logits(reference, seq + fed)
    assert first == fed[0] == int(want[74].argmax())
    assert np.abs(got - want[75:80]).max() < LOGIT_TOL


@pytest.mark.parametrize("where", ["chunks", "steps"])
def test_a_zeroed_tail_fails_the_same_comparison(cfg, params, reference,
                                                 where):
    """The mechanism's own control: the run of the test above with every
    conv tail zeroed at every program boundary — between chunks, or
    between decode steps — is NOT the full forward, by hundreds of
    tolerances. (Were the tails decoration, this would pass.)"""
    (seq,) = _prompts([75], seed=39)
    table = _table(3, 24, {1: list(range(3, 3 + 12))})
    cache = shortconv.init_paged_cache(cfg, 3, 24, 16)
    cache, _ = _chunks_into_cache(params, cfg, cache, table, 1, seq,
                                  [7, 32, 1, 20, 15],
                                  zero_tails=where == "chunks")
    live = jnp.asarray([False, True, False])
    got, fed = _steps(params, cfg, cache, table, 1, live, 5,
                      zero_tails=where == "steps")
    want = _ref_logits(reference, seq + fed)
    assert np.abs(got - want[75:80]).max() > 500 * LOGIT_TOL


def test_a_wave_takes_each_rows_tail_at_its_true_length(cfg, params,
                                                        reference):
    """Two prompts of unequal length in ONE wave, right-padded to the
    bucket: each row's logits are at ITS last token, its tails those
    after ITS last token (the reference's ``z`` there — not the padding's
    —, checked through a decode step each), and a third slot is left as
    it was."""
    pa, pb = _prompts([20, 9], seed=3)
    tokens = np.zeros((2, 32), np.int32)
    tokens[0, :20], tokens[1, :9] = pa, pb
    lens = jnp.asarray([20, 9])
    prefix, logits = jax.jit(lambda t, n: shortconv.prefill_batch(
        params, t, n, cfg))(jnp.asarray(tokens), lens)
    for row, p in enumerate((pa, pb)):
        want = _ref_logits(reference, p)
        assert np.abs(np.asarray(logits[row]) - want[-1]).max() < LOGIT_TOL
    assert prefix["conv"].shape == (cfg.n_conv_layers, 2, 2, 64)
    # The short row's tails are NOT what the padded row ends with.
    _, unpadded = lfm.forward_hidden(params, jnp.asarray(tokens[1:, :9]),
                                     cfg)
    assert np.allclose(prefix["conv"][:, 1], unpadded["conv"][:, 0],
                       atol=1e-5)
    table = _table(3, 24, {0: [0, 1, 2], 2: [5, 6, 7]})
    cache = shortconv.init_paged_cache(cfg, 3, 24, 16)
    cache["conv"] = cache["conv"] - 3.0
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for row, slot in ((0, 0), (1, 2)):
        one = jax.tree.map(lambda a: a[:, row], prefix)
        cache = shortconv.insert(cache, one, slot, lens[row], first[row],
                                 table=table)
    assert np.all(np.asarray(cache["conv"][:, 1]) == -3.0)
    live = jnp.asarray([True, False, True])
    for slot, p in ((0, pa), (2, pb)):
        got, fed = _steps(params, cfg, cache, table, slot, live, 3)
        want = _ref_logits(reference, p + fed)
        assert np.abs(got - want[len(p):len(p) + 3]).max() < LOGIT_TOL


def test_a_burst_moves_the_tails_of_its_live_slots_only(cfg, params,
                                                        reference):
    """Four staged steps in one program for two live slots of three: the
    live slots' tokens are the reference's and their tails move on; the
    third slot — mid-prefill, free or, as here, the spare — keeps tail,
    rows and length, and its column of the tokens carries the experts
    read, at most what the steps hold."""
    pa, pb, pc = _prompts([40, 17, 25], seed=6)
    table = _table(3, 24, {0: [0, 1, 2, 3], 1: [4, 5, 6], 2: [7, 8, 9]})
    cache = shortconv.init_paged_cache(cfg, 3, 24, 16)
    for slot, p in enumerate((pa, pb, pc)):
        splits = [32] * (len(p) // 32) + ([len(p) % 32] if len(p) % 32
                                          else [])
        cache, _ = _chunks_into_cache(params, cfg, cache, table, slot, p,
                                      splits)
    active = jnp.asarray([True, True, False])
    before = jax.tree.map(np.asarray, cache)
    out, _, toks = jax.jit(lambda c: shortconv.decode_burst_staged(
        params, c, jax.random.key(1), active, 4, cfg,
        sampling.SamplingParams(), table=table))(cache)
    toks = np.asarray(toks)
    for slot, p in ((0, pa), (1, pb)):
        fed = [int(before["last_token"][slot])] + toks[:3, slot].tolist()
        rows = _ref_logits(reference, p + fed)[len(p):len(p) + 4]
        lead = np.sort(rows, axis=-1)
        clear = lead[:, -1] - lead[:, -2] > MARGIN
        assert clear.sum() >= 2
        for tok, row, ok in zip(toks[:, slot], rows, clear):
            assert not ok or tok == int(row.argmax())
        assert not np.array_equal(np.asarray(out["conv"][:, slot]),
                                  before["conv"][:, slot])
    assert np.array_equal(np.asarray(out["conv"][:, 2]),
                          before["conv"][:, 2])
    assert np.asarray(out["length"]).tolist() == [44, 21, 25]
    assert shortconv.experts_per_step(cfg) == cfg.n_moe_layers * 8 == 56
    # Two live rows choose at most 2 x top-2 experts a layer.
    assert all(cfg.n_moe_layers <= n <= cfg.n_moe_layers * 4
               for n in toks[:, -1])


# -- (d): the shared expert layer without a shared expert --------------------

@pytest.mark.parametrize("rows", [(1, 5), (3, 11), (2, 40), (1, 96)],
                         ids=["few-5", "few-33", "many-80", "many-96"])
def test_moe_ffn_without_a_shared_expert(cfg, params, built, rows):
    """``glm_moe.moe_ffn`` with ``n_shared_experts`` 0, for few rows (the
    visit) and for many (the grouped products): the layer holds no
    ``ws_*`` tensor, none is read — a layer that had one, filled with
    NaN, gives the same result — and the result is the definition's:
    every expert applied to every row, weighted where it was chosen."""
    dims = built[0]
    layer = params["layers"][1]
    assert cfg.n_shared_experts == 0
    assert not [n for n in layer if n.startswith("ws_")]
    B, S = rows
    assert (B * S <= glm_moe.DENSE_EXPERT_MAX_TOKENS) == (B * S <= 64)
    h = jnp.asarray(np.random.default_rng(B * S).normal(
        size=(B, S, 64)), jnp.float32)
    y, n = jax.jit(lambda h: glm_moe.moe_ffn(cfg, h, layer))(h)
    poisoned = dict(layer, ws_gate=jnp.full((64, 32), jnp.nan),
                    ws_up=jnp.full((64, 32), jnp.nan),
                    ws_down=jnp.full((32, 64), jnp.nan))
    again, _ = jax.jit(lambda h: glm_moe.moe_ffn(cfg, h, poisoned))(h)
    assert np.array_equal(np.asarray(y), np.asarray(again))
    want = ref.expert_ffn(h.reshape(B * S, 64), layer, dims,
                          ref.Precision())
    assert np.abs(np.asarray(y).reshape(B * S, 64)
                  - np.asarray(want)).max() < 1e-5
    assert (int(n) > 0) == (B * S <= 64)
    text = jax.jit(lambda h: glm_moe.moe_ffn(cfg, h, layer)).lower(
        h).as_text(debug_info=True)
    assert "/moe_experts/" in text and "/shared_expert/" not in text


# -- (e): through the engine -------------------------------------------------

def test_engine_waves_chunks_and_bursts(cfg, params, reference):
    """Through the engine: prompts on the wave path (<= 32, two a wave,
    of unequal length) and on the chunk path (two to five chunks, none a
    whole number), bursts at two span rungs; every served token is the
    reference's."""
    e = _engine(params, cfg)
    prompts = _prompts([10, 23, 40, 100, 150], seed=4)
    outs = e.generate(prompts, max_new_tokens=14)
    for p, out in zip(prompts, outs):
        assert len(out) == 14
        _check_greedy(reference, p, out)
    kinds = {k.split("[")[0] for k in e.compile_watch.summary()}
    assert {"admit_wave", "prefill_chunk", "decode_burst"} <= kinds
    assert len({key[2] for key in e.decode_programs}) >= 2


def test_the_serve_loop_queues_chunks_and_serves_the_same_tokens(
        cfg, params, reference, tmp_path, monkeypatch):
    """A prompt's non-final chunks are dispatched and not awaited
    (PR 47); the slot's conv tails are still carried from chunk to chunk in dispatch
    order."""
    from tests.test_infer_server import check_a_family_through_the_loop
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    check_a_family_through_the_loop(
        lambda: _engine(params, cfg), _prompts([100], seed=21)[0],
        lambda prompt, out: _check_greedy(reference, prompt, out), path)


def test_engine_single_steps(cfg, params, reference):
    """``step()``: the one-token program, with a second request
    mid-prefill while the first decodes (its tails must not move)."""
    e = _engine(params, cfg)
    pa, pb = _prompts([45, 90], seed=5)
    ra = e.add_request(pa, max_new_tokens=6)
    rb = e.add_request(pb, max_new_tokens=6)
    while e.waiting or e.chunking or e.slot_req:
        e.step()
    by_rid = {r.rid: r for r in e.finished}
    _check_greedy(reference, pa, by_rid[ra].tokens)
    _check_greedy(reference, pb, by_rid[rb].tokens)
    assert any(k.startswith("decode1") for k in e.compile_watch.summary())


@pytest.mark.parametrize("lengths", [(12, 20), (70, 45)],
                         ids=["waves", "chunks"])
def test_a_slot_rented_again_never_sees_its_last_tenant(cfg, params,
                                                        reference, lengths):
    """One slot, two tenants one after the other, and between them the
    slot's tails are overwritten with NaN for good measure: the second
    tenant's tokens are the reference's."""
    e = _engine(params, cfg, n_slots=1)
    first, second = _prompts(lengths, seed=7)
    e.generate([first], max_new_tokens=6)
    assert float(jnp.abs(e.cache["conv"][:, 0]).max()) > 0
    e.cache["conv"] = e.cache["conv"].at[:, 0].set(jnp.nan)
    e.finished.clear()
    out = e.generate([second], max_new_tokens=8)[0]
    _check_greedy(reference, second, out)


def test_preempt_and_recover_give_the_uninterrupted_continuation(
        cfg, params):
    """A decoding slot is evicted, and later the engine recovers
    mid-flight: every victim re-prefills its whole context through the
    chunk path, which rebuilds tails and K/V alike, and finishes with
    the tokens of a run that was never interrupted."""
    prompts = _prompts([50, 90, 40], seed=9)
    want = _engine(params, cfg, n_slots=2).generate(prompts,
                                                    max_new_tokens=10)
    e = _engine(params, cfg, n_slots=2)
    assert e._prefix_index is None
    rids = [e.add_request(p, max_new_tokens=10) for p in prompts]
    while not e.slot_req:
        e.step_burst(max_burst=2)
    e.decode_burst(max_burst=2)
    assert e.preempt_slot(next(iter(e.slot_req))) is True
    e.step_burst(max_burst=2)
    assert e.recover() == 3
    assert not e.slot_req and e.allocator.used == 0
    e.run_to_completion(max_burst=4)
    by_rid = {r.rid: r.tokens for r in e.finished}
    assert [by_rid[r] for r in rids] == want


@pytest.mark.parametrize("option,kw", [
    ("prefix_pool", {"prefix_pool": 8}),
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
    assert "short-convolution" in err.value.typed_error["family"]
    assert shortconv.UNSUPPORTED[option] in str(err.value)
    with pytest.raises(eng.UnsupportedOptionError):
        eng.refuse_options(shortconv, **{option: True})


def test_the_handoff_is_refused_by_name(cfg, params):
    e = _engine(params, cfg)
    (prompt,) = _prompts([70], seed=10)
    assert e.handoff_eligible(prompt, 8) is False
    with pytest.raises(eng.UnsupportedOptionError, match="import_prefix"):
        e.import_prefix(prompt, {"kv_block": 16, "tensors": {}})
    with pytest.raises(NotImplementedError, match="verify"):
        shortconv.verify_draft_staged()


def test_serving_weights_builder_knows_the_family(cfg):
    params, qweights = eng.random_serving_weights(cfg)
    assert qweights is None and len(params["layers"]) == 8
    assert params["layers"][0]["w_in"].shape == (64, 192)
    assert params["layers"][1]["we_gate"].shape == (8, 64, 32)
    assert params["layers"][1]["wq"].dtype == cfg.dtype
    assert "lm_head" not in params           # tied
    for kw in ({"weights_int8": True}, {"mesh": "a mesh"}):
        with pytest.raises(eng.UnsupportedOptionError):
            eng.random_serving_weights(cfg, **kw)


def test_warm_grid_ledger_and_token_bytes(cfg, params):
    """The warm grid covers the family's programs (nothing compiles
    under traffic afterwards); the HBM ledger has ``conv_tail`` beside
    ``kv_pool`` and the ``expert_weights`` view; a token's cache bytes
    count the ATTENTION layers only, a slot's tails the conv layers."""
    e = _engine(params, cfg)
    assert e.warm_programs(max_burst=8) > 0
    e.declare_warmup_complete()
    alarms = eng.flight_lib.UNEXPECTED_COMPILES._require_default()
    before = alarms.value
    e.generate(_prompts([12, 70], seed=11), max_new_tokens=4)
    assert e.warm_programs(max_burst=8) == 0
    assert alarms.value == before
    led = e.hbm_ledger.snapshot()
    slots = e.n_slots + 1
    assert led["conv_tail"] == slots * shortconv.slot_state_bytes(cfg) \
        == cfg.n_conv_layers * slots * 2 * 64 * 4
    assert led["kv_pool"] == e.cache["k"].nbytes * 2 + 2 * slots * 4
    assert led["expert_weights"] == cfg.n_moe_layers * 8 * 3 * 64 * 32 * 4
    assert "recurrent_state" not in led and "window_ring" not in led
    # float32 rows of 2 heads x 16, K and V, 3 attention layers
    assert eng.KV_TOKEN_BYTES._require_default().value \
        == shortconv.token_bytes(cfg) == 3 * 2 * 2 * 16 * 4
    assert e.cache["k"].shape[-1] == 32          # heads side by side


def test_a_lone_request_rides_a_one_row_wave_with_the_same_tokens(
        cfg, params):
    """Under ``pad_waves`` a lone request's wave is padded to ONE row
    and a fuller one to ``max_wave``: the request's tokens are the same
    from either program (its convolution tail is written per row), and after the
    warm grid neither wave size meets a program not yet compiled."""
    e = _engine(params, cfg, max_wave=4)
    assert e.warm_programs(max_burst=8) > 0
    e.declare_warmup_complete()
    programs = e.compile_watch.count
    prompts = _prompts([12, 20, 7, 30], seed=21)
    seq0 = e.flight.seq()
    alone = e.generate(prompts[:1], max_new_tokens=6)[0]
    e.reset()
    together = e.generate(prompts, max_new_tokens=6)[0]
    assert alone == together
    assert [r["program"]["rows"] for r in e.flight.since(seq0)
            if r["burst"] == "wave"] == [1, 4]
    assert e.compile_watch.count == programs
    assert e.compile_watch.unexpected == []


def test_dispatch_annotations_say_what_the_family_holds(
        cfg, params, tmp_path, monkeypatch):
    """``engine.decode.dispatch`` says ``experts_held`` — ``k`` x expert
    layers x experts — beside ``tiles``, ``kv_blocks`` and
    ``state_rows``; ``engine.decode.fetch`` says it again beside
    ``experts_read``, which never passes it; ``engine.chunk.dispatch``
    says ``carried``; an engine of the Llama family says none of them."""
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    # The timeline's buffer is the process's: read this test's events.
    t0 = time.time() * 1e6
    e = _engine(params, cfg)
    e.add_request(_prompts([75], seed=12)[0], max_new_tokens=24)
    e.add_request(_prompts([20], seed=13)[0], max_new_tokens=24)
    e.run_to_completion(max_burst=4)
    timeline.save_now()
    events = _events_since(path, t0)

    def args_of(name):
        return [ev["args"] for ev in events if ev["name"] == name]

    chunks = args_of("engine.chunk.dispatch")
    assert [c["chunk_tokens"] for c in chunks] == [32, 32, 11]
    assert [c["carried"] for c in chunks] == [0, 1, 1]
    bursts = args_of("engine.decode.dispatch")
    per_step = cfg.n_moe_layers * cfg.n_routed_experts
    assert bursts and all(
        b["experts_held"] == b["k"] * per_step and b["tiles"] == 1
        and b["state_rows"] == b["slots"] and b["kv_blocks"] >= b["slots"]
        for b in bursts)
    fetches = [f for f in args_of("engine.decode.fetch")
               if "experts_read" in f]
    assert len(fetches) == len(bursts)
    by_seq = {b["seq"]: b for b in bursts}
    for f in fetches:
        b = by_seq[f["seq"]]
        assert f["experts_held"] == b["experts_held"]
        # Each live row chooses top-2 of 8 a layer; a step reads at
        # least one expert a layer and at most 2 a live row.
        assert f["k"] * cfg.n_moe_layers <= f["experts_read"] \
            <= min(f["experts_held"],
                   f["k"] * cfg.n_moe_layers * 2 * b["slots"])
    lcfg = llama.CONFIGS["llama3-tiny"]
    le = eng.InferenceEngine(
        llama.init_params(jax.random.key(0), lcfg), lcfg, n_slots=2,
        max_len=128, prompt_buckets=(16, 128), prefill_chunk=32,
        kv_block=16)
    t1 = time.time() * 1e6
    le.add_request(list(range(1, 50)), max_new_tokens=4)
    le.run_to_completion(max_burst=4)
    timeline.save_now()
    later = _events_since(path, t1)
    mine = [ev["args"] for ev in later
            if ev["name"] in ("engine.decode.dispatch",
                              "engine.decode.fetch")]
    assert mine and not any(
        "experts_held" in a or "experts_read" in a for a in mine)
