"""The windowed family's serve programs (``infer/windowed.py``) and the
engine over them, against the plain reference's FULL forward under its
window MASK (``benchmarks/reference/afmoe.py``), at a tiny size on the
CPU with the benchmark's seeded weights and a FLOAT32 program. What must
hold of a ring of ``window`` rows per slot beside paged K/V:

(a) a window layer's result for query ``p`` equals full attention under
    the mask ``p - W < j <= p``, for ``p`` below, at and far beyond
    ``W``, and every ring row outside that window poisoned with NaN
    changes nothing;
(b) a chunk attends the ring's ``W - 1`` older rows and its own
    causally, THEN writes: a prompt longer than ``W``, no multiple of
    the chunk, equals one pass; a padded wave or last chunk writes only
    each row's real tokens;
(c) a burst's ``k`` staged steps see the window SLIDE and flush once,
    for the live rows only; a slot rented again never sees its last
    tenant's rows; ``preempt_slot`` + resume and ``recover()`` give the
    uninterrupted greedy continuation;
(d) served tokens are the reference's, every refusal is typed, the HBM
    ledger and the dispatch annotations say what the rings cost.

LOGIT_TOL as ``tests/test_afmoe.py``'s (float32 against float32,
summation order). A greedy token is compared only where the reference's
best logit leads its second by more than MARGIN.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks import weights_afmoe as G
from benchmarks.families import afmoe as family
from benchmarks.reference import afmoe as ref
from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import sampling, windowed
from skypilot_tpu.models import llama
from skypilot_tpu.utils import timeline
from tests.test_afmoe import LOGIT_TOL, SEED, TINY

MARGIN = 5e-3
WIN = TINY["sliding_window"]          # 32


@pytest.fixture(scope="module")
def dims():
    return family.dims(TINY)


@pytest.fixture(scope="module")
def cfg():
    return family.register(dict(TINY, name="afmoe-serve-test"),
                           dtype=jnp.float32)


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


def _engine(params, cfg, **kw):
    kw = dict(dict(n_slots=4, max_len=256, prompt_buckets=(32, 64, 256),
                   prefill_chunk=32, kv_block=16, max_wave=2,
                   pad_waves=True, span_buckets=[64, 128]), **kw)
    return eng.InferenceEngine(params, cfg, **kw)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, n).tolist() for n in lengths]


def _table(n_slots, n_blocks, rows, cols=17):
    """Block table of ``cols - 1`` blocks a slot + the sentinel column."""
    table = np.full((n_slots, cols), n_blocks, np.int32)
    for slot, blocks in rows.items():
        table[slot, :len(blocks)] = blocks
    return jnp.asarray(table)


def _chunks_into_cache(params, cfg, cache, table, slot, seq, chunk=32):
    """``seq`` through ``prefill_chunk`` a chunk at a time; returns
    (cache, the final chunk's first token)."""
    fn = jax.jit(lambda c, t, s, n, f: windowed.prefill_chunk(
        params, c, t, s, n, jnp.asarray(slot), jnp.asarray(len(seq)),
        jax.random.key(0), cfg, sampling.SamplingParams(), final=f,
        table=table), static_argnums=4)
    tok = None
    for start in range(0, len(seq), chunk):
        part = seq[start:start + chunk]
        tokens = np.zeros((chunk,), np.int32)
        tokens[:len(part)] = part
        cache, _, tok = fn(cache, jnp.asarray(tokens), jnp.asarray(start),
                           jnp.asarray(len(part)),
                           start + chunk >= len(seq))
    return cache, int(tok)


def _step_logits(params, cfg, cache, table, live=None):
    out, logits = jax.jit(lambda c: windowed.decode_step(
        params, c, cfg, table=table, live=live))(cache)
    return out, np.asarray(logits)


# -- (a), (b): programs ------------------------------------------------------

@pytest.mark.parametrize("length", [20, 32, 33, 75, 150],
                         ids=["below", "at", "just-past", "past",
                              "far-past"])
def test_chunks_then_a_step_equal_the_reference_under_its_mask(
        cfg, params, reference, length):
    """A prompt below, at and far beyond the window, no multiple of the
    chunk, through the chunk program and then ONE decode step: the step's
    logits are the reference's at that position (every window layer's
    query saw exactly ``p - W < j <= p``), and the ring rows OUTSIDE the
    step's window, poisoned with NaN, change nothing."""
    (seq,) = _prompts([length], seed=length)
    table = _table(3, 24, {1: list(range(3, 3 + 12))})
    cache = windowed.init_paged_cache(cfg, 3, 24, 16)
    # The slot's last tenant left garbage in every ring row.
    cache["win_k"] = cache["win_k"] + 7.0
    cache["win_v"] = cache["win_v"] - 5.0
    cache, first = _chunks_into_cache(params, cfg, cache, table, 1, seq)
    want = _ref_logits(reference, seq + [first])
    assert first == int(want[length - 1].argmax())
    live = jnp.asarray([False, True, False])
    _, got = _step_logits(params, cfg, cache, table, live)
    assert np.abs(got[1] - want[length]).max() < LOGIT_TOL
    # Ring rows whose position lies outside (length - W, length]: at most
    # the one row the step's own token will replace, and every row no
    # position of this tenant ever reached.
    held = np.asarray(windowed._ring_positions(cfg, jnp.asarray(length)))
    outside = (held < 0) | (held <= length - WIN)
    assert outside.sum() == max(WIN - length, 1 if length >= WIN else 0)
    poisoned = dict(cache)
    for name in ("win_k", "win_v"):
        poisoned[name] = cache[name].at[:, 1].set(
            jnp.where(outside[:, None], jnp.nan, cache[name][:, 1]))
    if outside.any():
        _, after = _step_logits(params, cfg, poisoned, table, live)
        assert np.array_equal(after[1], got[1])
    # ... and a ring row INSIDE the window does reach the result.
    inside = int(np.flatnonzero(~outside)[0])
    for name in ("win_k", "win_v"):
        poisoned[name] = cache[name].at[:, 1, inside].set(jnp.nan)
    _, broken = _step_logits(params, cfg, poisoned, table, live)
    assert not np.isfinite(broken[1]).all()


def test_a_later_chunk_ignores_ring_rows_outside_its_windows(cfg, params):
    """The third chunk of a prompt (positions 64-95) admits ring
    positions 33-63 only: rows that hold older positions, NaN, change
    nothing of what it computes or writes."""
    (seq,) = _prompts([96], seed=3)
    table = _table(2, 24, {0: list(range(12))})
    cache = windowed.init_paged_cache(cfg, 2, 24, 16)
    cache, _ = _chunks_into_cache(params, cfg, cache, table, 0, seq[:64])
    held = np.asarray(windowed._ring_positions(cfg, jnp.asarray(64)))
    assert sorted(held) == list(range(32, 64))
    outside = held <= 64 - WIN                       # position 32 alone
    assert outside.sum() == 1
    poisoned = dict(cache)
    for name in ("win_k", "win_v"):
        poisoned[name] = cache[name].at[:, 0].set(
            jnp.where(outside[:, None], jnp.nan, cache[name][:, 0]))

    def last_chunk(c):
        fn = jax.jit(lambda c: windowed.prefill_chunk(
            params, c, jnp.asarray(seq[64:], jnp.int32), jnp.asarray(64),
            jnp.asarray(32), jnp.asarray(0), jnp.asarray(96),
            jax.random.key(0), cfg, sampling.SamplingParams(), final=True,
            table=table))
        out, _, tok = fn(c)
        return out, int(tok)

    clean, tok = last_chunk(cache)
    dirty, tok2 = last_chunk(poisoned)
    assert tok == tok2
    assert np.isfinite(np.asarray(dirty["k"])).all()
    assert np.array_equal(np.asarray(clean["win_k"][:, 0]),
                          np.asarray(dirty["win_k"][:, 0]))
    assert np.array_equal(np.asarray(clean["k"]), np.asarray(dirty["k"]))


def test_a_padded_wave_writes_each_rows_real_tokens_only(cfg, params,
                                                         reference):
    """A wave of rows of 20, 45 and 9 real tokens in rows of 64 (one
    longer than the window): logits at each row's last real position are
    the reference's, each slot's ring holds exactly its last ``min(n,
    W)`` real rows — the one-pass K/V at those positions — and ring rows
    no real token maps to keep what they held."""
    prompts = _prompts([20, 45, 9], seed=5)
    table = _table(4, 24, {0: [0, 1, 2, 3], 1: [7, 6, 5, 4], 2: [8, 9, 10,
                                                                  11]})
    cache = windowed.init_paged_cache(cfg, 4, 24, 16)
    cache["win_k"] = cache["win_k"] + 3.0
    tokens = np.zeros((3, 64), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts])
    rows, logits = jax.jit(lambda t, n: windowed.prefill_batch(
        params, t, n, cfg))(jnp.asarray(tokens), lens)
    for i, p in enumerate(prompts):
        want = _ref_logits(reference, p)
        assert np.abs(np.asarray(logits[i]) - want[-1]).max() < LOGIT_TOL
        cache = windowed.insert(
            cache, {n: r[:, i] for n, r in rows.items()}, jnp.asarray(i),
            lens[i], jnp.asarray(int(np.asarray(logits[i]).argmax())),
            table=table)
    for i, p in enumerate(prompts):
        n = len(p)
        ring = np.asarray(cache["win_k"][:, i])
        for pos in range(max(0, n - WIN), n):
            assert np.array_equal(ring[:, pos % WIN],
                                  np.asarray(rows["win_k"][:, i, pos]))
        untouched = [r for r in range(WIN) if r >= n]
        assert (ring[:, untouched] == 3.0).all()
    assert (np.asarray(cache["win_k"][:, 3]) == 3.0).all()


# -- (c): bursts -------------------------------------------------------------

def test_a_burst_slides_the_window_and_flushes_live_rows_only(
        cfg, params, reference):
    """Two live slots — one whose ring WRAPS inside the burst (30 rows +
    6 steps pass 32) and one far past the window — and a dead slot that
    holds a ring, through ``k = 6`` staged steps: the live rows' logits
    are the reference's at every step (step ``s`` no longer sees
    position ``p + s - W``), the dead slot's ring is bit for bit what it
    was, and the live rings hold the burst's rows at ``position mod
    W``."""
    prompts = _prompts([30, 70, 40], seed=6)
    table = _table(4, 40, {0: list(range(0, 8)), 1: list(range(8, 16)),
                           2: list(range(16, 24))})
    cache = windowed.init_paged_cache(cfg, 4, 40, 16)
    firsts = []
    for slot, p in enumerate(prompts):
        cache, first = _chunks_into_cache(params, cfg, cache, table, slot, p)
        firsts.append(first)
    active = jnp.asarray([True, True, False, False])
    k = 6
    before = {n: np.asarray(cache[n]) for n in ("win_k", "win_v")}
    seqs = [list(p) + [f] for p, f in zip(prompts, firsts)]

    def next_token(logits, s, last):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(active, tok, last), logits

    out, last, logits, reads = jax.jit(lambda c: windowed._staged_steps(
        params, c, cfg, table, None, k, c["last_token"], next_token,
        live=active))(cache)
    logits = np.asarray(logits)                          # [k, B, vocab]
    for slot in (0, 1):
        seq = list(seqs[slot])
        for s in range(k):
            want = _ref_logits(reference, seq)[-1]
            assert np.abs(logits[s, slot] - want).max() < LOGIT_TOL, (slot, s)
            seq.append(int(logits[s, slot].argmax()))
    assert int(reads.min()) > 0
    after = {n: np.asarray(out[n]) for n in ("win_k", "win_v")}
    for n in after:
        assert np.array_equal(after[n][:, 2:], before[n][:, 2:])   # dead
        for slot, p in ((0, 30), (1, 70)):
            rows = [(p + s) % WIN for s in range(k)]
            others = [r for r in range(WIN) if r not in rows]
            assert np.array_equal(after[n][:, slot][:, others],
                                  before[n][:, slot][:, others])
            assert not np.array_equal(after[n][:, slot][:, rows],
                                      before[n][:, slot][:, rows])
    with pytest.raises(ValueError, match="laps a ring"):
        windowed._staged_steps(params, cache, cfg, table, None, WIN + 1,
                               cache["last_token"], next_token, live=active)


# -- through the engine ------------------------------------------------------

def test_engine_waves_chunks_bursts_and_span_rungs(cfg, params, reference):
    """Through the engine: prompts on the wave path (<= 32) and on the
    chunk path (two to five chunks, none a whole number, all past the
    window), bursts across the rings' wrap at two span rungs; every
    served token is the reference's."""
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
    (PR 47); the ring of the slot's window layers is still carried from chunk to chunk in dispatch
    order."""
    from tests.test_infer_server import check_a_family_through_the_loop
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    check_a_family_through_the_loop(
        lambda: _engine(params, cfg), _prompts([100], seed=21)[0],
        lambda prompt, out: _check_greedy(reference, prompt, out), path)


def test_engine_single_steps(cfg, params, reference):
    """``step()``: the one-token program, with a second request
    mid-prefill while the first decodes (its ring must not move)."""
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


@pytest.mark.parametrize("lengths", [(12, 20), (70, 45), (150, 40)],
                         ids=["waves", "chunks", "long-then-short"])
def test_a_slot_rented_again_never_sees_its_last_tenant(cfg, params,
                                                        reference, lengths):
    """One slot, two tenants one after the other, and between them the
    slot's rings are overwritten with NaN for good measure: the second
    tenant's tokens are the reference's (positions, not leftovers,
    decide what a ring row is worth)."""
    e = _engine(params, cfg, n_slots=1)
    first, second = _prompts(lengths, seed=7)
    e.generate([first], max_new_tokens=6)
    assert float(jnp.abs(e.cache["win_k"][:, 0]).max()) > 0
    e.cache["win_k"] = e.cache["win_k"].at[:, 0].set(jnp.nan)
    e.cache["win_v"] = e.cache["win_v"].at[:, 0].set(jnp.nan)
    e.finished.clear()
    out = e.generate([second], max_new_tokens=8)[0]
    _check_greedy(reference, second, out)
    assert out == _engine(params, cfg, n_slots=1).generate(
        [second], max_new_tokens=8)[0]


def test_preempt_and_resume_give_the_uninterrupted_continuation(
        cfg, params, reference):
    """A decoding slot is evicted and its request resumes cold: there is
    no prefix index, so the resume re-prefills prompt + committed tokens
    through the chunk path, which rebuilds rings and K/V alike."""
    (prompt,) = _prompts([50], seed=8)
    want = _engine(params, cfg).generate([prompt], max_new_tokens=16)[0]
    e = _engine(params, cfg)
    assert e._prefix_index is None
    rid = e.add_request(prompt, max_new_tokens=16)
    while not e.slot_req:
        e.step_burst(max_burst=4)
    e.decode_burst(max_burst=4)
    (slot,) = e.slot_req
    held = len(e.slot_req[slot].tokens)
    assert 0 < held < 16
    assert e.preempt_slot(slot) is True
    assert not e.slot_req and e.allocator.used == 0
    e.run_to_completion(max_burst=4)
    (req,) = [r for r in e.finished if r.rid == rid]
    assert req.preemptions == 1 and req.resumed_len == 0
    assert req.tokens == want
    _check_greedy(reference, prompt, req.tokens)


def test_recover_gives_the_uninterrupted_continuation(cfg, params):
    """``recover()`` mid-flight — one request decoding, one mid-prefill,
    one queued: every victim re-prefills and finishes with the tokens of
    a run that never crashed."""
    prompts = _prompts([50, 90, 40], seed=9)
    want = _engine(params, cfg, n_slots=2).generate(prompts,
                                                    max_new_tokens=10)
    e = _engine(params, cfg, n_slots=2)
    rids = [e.add_request(p, max_new_tokens=10) for p in prompts]
    while not e.slot_req:
        e.step_burst(max_burst=2)
    e.decode_burst(max_burst=2)
    assert e.chunking or e.waiting
    assert e.recover() == 3
    assert not e.slot_req and e.allocator.used == 0
    e.run_to_completion(max_burst=4)
    by_rid = {r.rid: r.tokens for r in e.finished}
    assert [by_rid[r] for r in rids] == want


# -- (d) ---------------------------------------------------------------------

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
    assert "windowed" in err.value.typed_error["family"]
    with pytest.raises(eng.UnsupportedOptionError):
        eng.refuse_options(windowed, **{option: True})


def test_the_environment_cannot_turn_the_prefix_pool_on(cfg, params,
                                                        monkeypatch):
    monkeypatch.setenv("SKYTPU_PREFIX_POOL", "8")
    with pytest.raises(eng.UnsupportedOptionError, match="prefix_pool"):
        _engine(params, cfg)
    monkeypatch.setenv("SKYTPU_PREFIX_POOL", "0")
    assert _engine(params, cfg).prefix_pool == 0


def test_the_handoff_is_refused_by_name(cfg, params):
    e = _engine(params, cfg)
    (prompt,) = _prompts([70], seed=10)
    assert e.handoff_eligible(prompt, 8) is False
    with pytest.raises(eng.UnsupportedOptionError, match="import_prefix"):
        e.import_prefix(prompt, {"kv_block": 16, "tensors": {}})
    rid = e.add_request(prompt, max_new_tokens=2)
    e.run_to_completion()
    (req,) = [r for r in e.finished if r.rid == rid]
    with pytest.raises(eng.UnsupportedOptionError, match="export_prefix"):
        e.export_prefix_for(req)


def test_serving_weights_builder_knows_the_family(cfg):
    params, qweights = eng.random_serving_weights(cfg)
    assert qweights is None
    assert (len(params["lead"]), len(params["period"]),
            len(params["tail"])) == (1, 3, 1)
    assert params["period"][0]["we_gate"].shape == (2, 8, 64, 32)
    assert params["period"][0]["wq"].dtype == cfg.dtype
    for kw in ({"weights_int8": True}, {"mesh": "a mesh"}):
        with pytest.raises(eng.UnsupportedOptionError):
            eng.random_serving_weights(cfg, **kw)
    with pytest.raises(NotImplementedError, match="verify"):
        windowed.verify_draft_staged()


def test_warm_grid_ledger_and_token_bytes(cfg, params):
    """The warm grid covers the family's programs (nothing compiles
    under traffic afterwards); the HBM ledger has ``window_ring`` beside
    ``kv_pool`` and the ``expert_weights`` view; a token's cache bytes
    count the GLOBAL layers only."""
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
    assert led["window_ring"] == slots * windowed.slot_state_bytes(cfg) \
        == cfg.n_win_layers * slots * 2 * WIN * 32 * 4
    assert led["kv_pool"] == e.cache["k"].nbytes * 2 + 2 * slots * 4
    assert led["expert_weights"] == cfg.n_moe_layers * 8 * 3 * 64 * 32 * 4
    assert "recurrent_state" not in led and "latent_kv_pool" not in led
    # float32 rows of 2 heads x 16, K and V, 2 global layers
    assert eng.KV_TOKEN_BYTES._require_default().value \
        == cfg.n_full_layers * 2 * 2 * 16 * 4


@pytest.mark.parametrize("start,n,window,want", [
    (0, 5, 32, 1 + 2 + 3 + 4 + 5), (0, 40, 32, 32 * 33 // 2 + 8 * 32),
    (30, 4, 32, 31 + 32 + 32 + 32), (64, 32, 32, 32 * 32), (0, 0, 32, 0),
    (0, 33_280, 2048, 2048 * 2049 // 2 + (33_280 - 2048) * 2048)])
def test_window_keys_arithmetic(start, n, window, want):
    assert eng.window_keys(start, n, window) == want \
        == sum(min(p + 1, window) for p in range(start, start + n))


def test_dispatch_annotations_say_window_rows_and_window_keys(
        cfg, params, tmp_path, monkeypatch):
    """``engine.decode.dispatch`` says ``window_rows`` — the sum over the
    round's slots of ``min(rows, W)`` — beside ``kv_blocks``, ``tiles``
    and ``state_rows``; ``engine.chunk.dispatch`` and
    ``engine.wave.dispatch`` say ``window_keys`` — the sum over the
    program's real tokens of ``min(p + 1, W)``; the flight record and
    /metrics carry them; an engine of the Llama family says neither."""
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    # The timeline's buffer is the process's: an earlier test's events
    # under the same worker must not be read.
    t0 = time.time() * 1e6
    e = _engine(params, cfg)
    e.add_request(_prompts([75], seed=12)[0], max_new_tokens=24)
    e.add_request(_prompts([20], seed=13)[0], max_new_tokens=24)
    held, round_slots = [], e._round_slots

    def spy(width):
        span, slots, promoted = round_slots(width)
        if slots:
            held.append(sum(
                min(len(r.prompt) + len(r.tokens) + e._inflight_tokens, WIN)
                for r in (e.slot_req[s] for s in slots)))
        return span, slots, promoted

    monkeypatch.setattr(e, "_round_slots", spy)
    seq0 = e.flight.seq()
    rows0 = eng.WINDOW_ROWS._require_default().value
    keys0 = eng.WINDOW_KEYS._require_default().value
    e.run_to_completion(max_burst=4)
    timeline.save_now()
    with open(path) as f:
        events = [ev for ev in json.load(f)["traceEvents"]
                  if ev.get("ts", 0) >= t0]
    chunks = [ev["args"] for ev in events
              if ev["name"] == "engine.chunk.dispatch"]
    assert [c["chunk_tokens"] for c in chunks] == [32, 32, 11]
    assert [c["window_keys"] for c in chunks] == [
        32 * 33 // 2, 32 * 32, 11 * 32]
    (wave,) = [ev["args"] for ev in events
               if ev["name"] == "engine.wave.dispatch"]
    assert wave["window_keys"] == 20 * 21 // 2
    bursts = [ev["args"] for ev in events
              if ev["name"] == "engine.decode.dispatch"]
    assert bursts and [b["window_rows"] for b in bursts] == held
    assert all(b["state_rows"] == b["slots"] and "kv_blocks" in b
               for b in bursts)
    # Two slots, one past the window: never more than W a slot, and the
    # short one's rows grow until they too reach it.
    assert max(held) == 2 * WIN and min(held) < 2 * WIN
    records = e.flight.since(seq0)
    assert [r["window_rows"] for r in records
            if r["burst"] == "decode"] == held
    assert [r["window_keys"] for r in records if r["burst"] == "chunk"] \
        == [c["window_keys"] for c in chunks]
    assert eng.WINDOW_ROWS._require_default().value - rows0 == sum(held)
    assert eng.WINDOW_KEYS._require_default().value - keys0 \
        == sum(c["window_keys"] for c in chunks) + wave["window_keys"]
    lcfg = llama.CONFIGS["llama3-tiny"]
    le = eng.InferenceEngine(
        llama.init_params(jax.random.key(0), lcfg), lcfg, n_slots=2,
        max_len=128, prompt_buckets=(16, 128), prefill_chunk=32,
        kv_block=16)
    t1, seq1 = time.time() * 1e6, le.flight.seq()
    le.add_request(list(range(1, 50)), max_new_tokens=4)
    le.run_to_completion(max_burst=4)
    timeline.save_now()
    with open(path) as f:
        later = [ev for ev in json.load(f)["traceEvents"]
                 if ev.get("ts", 0) >= t1]
    mine = [ev["args"] for ev in later if ev["name"].endswith(".dispatch")]
    assert mine and not any(
        "window_rows" in a or "window_keys" in a for a in mine)
    assert not any("window_rows" in r or "window_keys" in r
                   for r in le.flight.since(seq1))
