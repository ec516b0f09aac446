"""The hybrid family's serve programs (``infer/hybrid.py``) and the
engine over them, against the plain reference's FULL forward
(``benchmarks/reference/olmo_hybrid.py``), at a tiny size on the CPU with
the benchmark's seeded weights and a FLOAT32 program. What must hold of
a recurrent state per slot beside paged K/V:

(a) a wave of rows of different lengths leaves each row the state and
    the convolution tail of ITS last real token;
(b) a prompt longer than a chunk, no multiple of it, carries state
    chunk to chunk and equals one pass;
(c) a burst's ``k`` steps carry state in the scan, for the live rows
    only: a dead row — mid-prefill, free, the spare — keeps what it holds;
(d) a slot rented again starts from zero whatever its last tenant left;
(e) ``preempt_slot`` + resume and ``recover()`` give the uninterrupted
    greedy continuation (they re-prefill, which rebuilds the state);
(f) served tokens are the reference's, every refusal is typed, the HBM
    ledger and the dispatch annotations say what the state costs.

LOGIT_TOL as ``tests/test_olmo_hybrid.py``'s (float32 against float32,
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
from benchmarks import weights_olmo_hybrid as G
from benchmarks.families import olmo_hybrid as family
from benchmarks.reference import olmo_hybrid as ref
from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import hybrid, kvcache, sampling
from skypilot_tpu.models import llama
from skypilot_tpu.models import olmo_hybrid as oh
from skypilot_tpu.utils import timeline
from tests.test_olmo_hybrid import LOGIT_TOL, SEED, STATE_TOL, TINY

MARGIN = 5e-3


@pytest.fixture(scope="module")
def dims():
    return family.dims(TINY)


@pytest.fixture(scope="module")
def cfg():
    return family.register(dict(TINY, name="olmo-hybrid-serve-test"),
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


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, n).tolist() for n in lengths]


def _one_pass(params, cfg, seq):
    """(state, tail) [L_lin, ...] after the whole of ``seq``."""
    _, rows = oh.forward_hidden(params, jnp.asarray(seq, jnp.int32)[None],
                                cfg)
    return np.asarray(rows["state"][:, 0]), np.asarray(rows["conv"][:, 0])


def _table(n_slots, n_blocks, rows, cols=5):
    """Block table of ``cols - 1`` blocks a slot + the sentinel column."""
    table = np.full((n_slots, cols), n_blocks, np.int32)
    for slot, blocks in rows.items():
        table[slot, :len(blocks)] = blocks
    return jnp.asarray(table)


def _wave_into_cache(params, cfg, prompts, table, n_slots=3, n_blocks=12):
    cache = hybrid.init_paged_cache(cfg, n_slots, n_blocks, 16)
    tokens = np.zeros((len(prompts), 32), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts])
    rows, logits = jax.jit(lambda p, t, n: hybrid.prefill_batch(
        p, t, n, cfg))(params, jnp.asarray(tokens), lens)
    firsts = []
    for i in range(len(prompts)):
        first = int(np.asarray(logits[i]).argmax())
        cache = hybrid.insert(
            cache, {n: r[:, i] for n, r in rows.items()},
            jnp.asarray(i), lens[i], jnp.asarray(first), table=table)
        firsts.append(first)
    return cache, np.asarray(logits), firsts


# -- (a) ---------------------------------------------------------------------

def test_wave_of_mixed_lengths_then_decode_steps_equal_reference(
        cfg, params, reference):
    """A padded wave (20 and 27 real tokens in rows of 32): logits at
    each row's last real position, each slot's state and tail = one
    pass over its own prompt; then eight single steps through the cache
    (the dead third slot rides along), logits at every position."""
    prompts = _prompts([20, 27], seed=2)
    table = _table(3, 12, {0: [0, 1, 2, 3], 1: [7, 6, 5, 4]})
    cache, logits, firsts = _wave_into_cache(params, cfg, prompts, table)
    seqs = []
    for i, p in enumerate(prompts):
        want = _ref_logits(reference, p)[-1]
        assert np.abs(logits[i] - want).max() < LOGIT_TOL
        state, tail = _one_pass(params, cfg, p)
        assert np.abs(np.asarray(cache["state"][:, i]) - state).max() \
            < STATE_TOL
        assert np.abs(np.asarray(cache["conv"][:, i]) - tail).max() \
            < STATE_TOL
        seqs.append(list(p) + [firsts[i]])
    active = jnp.asarray([True, True, False])
    dead = np.asarray(cache["state"][:, 2]).copy()
    step = jax.jit(lambda p, c: hybrid.decode_step(
        p, c, cfg, table=table, span=64, live=active))
    for _ in range(8):
        cache, logits = step(params, cache)
        toks = sampling.argmax_tokens(logits)
        cache = kvcache.commit_tokens(cache, toks, active)
        for i in range(2):
            want = _ref_logits(reference, seqs[i])[-1]
            assert np.abs(np.asarray(logits[i]) - want).max() < LOGIT_TOL
            seqs[i].append(int(toks[i]))
    assert list(np.asarray(cache["length"])) == [28, 35, 0]
    assert np.array_equal(np.asarray(cache["state"][:, 2]), dead)
    # The states moved on with the tokens: one pass over what was fed.
    for i in range(2):
        state, tail = _one_pass(params, cfg, seqs[i][:-1])
        assert np.abs(np.asarray(cache["state"][:, i]) - state).max() \
            < STATE_TOL
        assert np.abs(np.asarray(cache["conv"][:, i]) - tail).max() \
            < STATE_TOL


# -- (b) ---------------------------------------------------------------------

def test_chunks_carry_state_and_equal_one_pass(cfg, params, reference):
    """75 tokens in chunks of 32 (32 + 32 + 11 real of 32) into a slot
    whose state holds garbage: the first chunk starts from zero, the
    later ones continue, the padded last chunk stops at its real tokens;
    state, tail and the first token's logits = one pass."""
    (prompt,) = _prompts([75], seed=3)
    n_blocks, C = 12, 32
    table = _table(2, n_blocks, {1: [3, 1, 4, 0, 2]}, cols=9)
    cache = hybrid.init_paged_cache(cfg, 2, n_blocks, 16)
    cache["state"] = cache["state"] + 7.0           # the last tenant's
    cache["conv"] = cache["conv"] - 3.0
    rng = jax.random.key(0)
    sp = sampling.SamplingParams()
    run = jax.jit(
        lambda p, c, t, start, n, new_len, r, final: hybrid.prefill_chunk(
            p, c, t, start, n, jnp.asarray(1), new_len, r, cfg, sp,
            final=final, table=table, span=128),
        static_argnames=("final",))
    for start in range(0, len(prompt), C):
        n = min(C, len(prompt) - start)
        final = start + n >= len(prompt)
        chunk = np.zeros((C,), np.int32)
        chunk[:n] = prompt[start:start + n]
        cache, rng, tok = run(
            params, cache, jnp.asarray(chunk), jnp.asarray(start),
            jnp.asarray(n), jnp.asarray(len(prompt) if final else 256),
            rng, final=final)
    want = _ref_logits(reference, prompt)[-1]
    top2 = np.sort(want)[-2:]
    if top2[1] - top2[0] > MARGIN:
        assert int(tok) == int(want.argmax())
    state, tail = _one_pass(params, cfg, prompt)
    assert np.abs(np.asarray(cache["state"][:, 1]) - state).max() < STATE_TOL
    assert np.abs(np.asarray(cache["conv"][:, 1]) - tail).max() < STATE_TOL
    # The other slot's garbage is untouched.
    assert float(jnp.abs(cache["state"][:, 0] - 7.0).max()) == 0.0
    # ... and one decode step on top reads the K/V the chunks wrote.
    cache, logits = hybrid.decode_step(
        params, cache, cfg, table=table, span=128,
        live=jnp.asarray([False, True]))
    want = _ref_logits(reference, prompt + [int(tok)])[-1]
    assert np.abs(np.asarray(logits[1]) - want).max() < LOGIT_TOL


def test_engine_waves_chunks_bursts_and_span_rungs(cfg, params, reference):
    """Through the engine: prompts on the wave path (<= 32) and on the
    chunk path (two to four chunks, none a whole number), bursts at two
    span rungs; every served token is the reference's."""
    e = _engine(params, cfg)
    prompts = _prompts([10, 23, 40, 100], seed=4)
    outs = e.generate(prompts, max_new_tokens=12)
    for p, out in zip(prompts, outs):
        assert len(out) == 12
        _check_greedy(reference, p, out)
    kinds = {k.split("[")[0] for k in e.compile_watch.summary()}
    assert {"admit_wave", "prefill_chunk", "decode_burst"} <= kinds
    assert len({key[2] for key in e.decode_programs}) >= 2


def test_the_serve_loop_queues_chunks_and_serves_the_same_tokens(
        cfg, params, reference, tmp_path, monkeypatch):
    """A prompt's non-final chunks are dispatched and not awaited
    (PR 47); the slot's recurrent state and conv tail are still carried from chunk to chunk in dispatch
    order."""
    from tests.test_infer_server import check_a_family_through_the_loop
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    check_a_family_through_the_loop(
        lambda: _engine(params, cfg), _prompts([100], seed=21)[0],
        lambda prompt, out: _check_greedy(reference, prompt, out), path)


def test_engine_single_steps(cfg, params, reference):
    """``step()``: the one-token program, with a second request
    mid-prefill while the first decodes (its state must not move)."""
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


# -- (c) ---------------------------------------------------------------------

def test_burst_carries_the_state_of_live_rows_only(cfg, params, reference):
    """Two live slots and a dead one that holds a state through ``k =
    4`` staged steps: the live rows' logits are the reference's at every
    step (the state moved on inside the scan), the dead row's state and
    tail are bit for bit what they were — with the mask; without it the
    dead row moves too, so the case is not vacuous."""
    prompts = _prompts([20, 27, 9], seed=6)
    table = _table(4, 12, {0: [0, 1, 2, 3], 1: [7, 6, 5, 4], 2: [8, 9]})
    cache, _, firsts = _wave_into_cache(params, cfg, prompts, table,
                                        n_slots=4)
    seqs = [list(p) + [f] for p, f in zip(prompts, firsts)]
    active = jnp.asarray([True, True, False, False])
    k = 4

    def steps(p, c, live):
        def nxt(logits, s, last):
            tok = jnp.where(active, sampling.argmax_tokens(logits), last)
            return tok, logits
        return hybrid._staged_steps(p, c, cfg, table, 64, k,
                                    c["last_token"], nxt, live=live)

    out, _, got = jax.jit(lambda p, c: steps(p, c, active))(params, cache)
    got = np.asarray(got)                              # [k, 4, vocab]
    for s in range(k):
        for i in range(2):
            want = _ref_logits(reference, seqs[i])[-1]
            assert np.abs(got[s, i] - want).max() < LOGIT_TOL
            seqs[i].append(int(got[s, i].argmax()))
    for name in hybrid.SLOT_STATE:
        assert np.array_equal(np.asarray(out[name][:, 2:]),
                              np.asarray(cache[name][:, 2:])), name
        assert not np.array_equal(np.asarray(out[name][:, :2]),
                                  np.asarray(cache[name][:, :2])), name
    every, _, _ = jax.jit(lambda p, c: steps(p, c, None))(params, cache)
    assert not np.array_equal(np.asarray(every["state"][:, 2]),
                              np.asarray(cache["state"][:, 2]))
    # The burst program: the same tokens, lengths advanced for the live.
    new, _, toks = jax.jit(lambda p, c, r: hybrid.decode_burst_staged(
        p, c, r, active, k, cfg, sampling.SamplingParams(), table=table,
        span=64))(params, cache, jax.random.key(0))
    assert np.asarray(toks)[:, :2].tolist() == [
        [seqs[i][len(prompts[i]) + 1 + s] for i in range(2)]
        for s in range(k)]
    assert list(np.asarray(new["length"])) == [24, 31, 9, 0]
    assert np.array_equal(np.asarray(new["state"]), np.asarray(out["state"]))


def test_a_tile_that_mixes_live_and_dead_slots(cfg, params):
    """Five live slots of seven: the second tile of four holds one live
    slot and dead ones (the order is live-first, tiles are whole). The
    dead ones keep their state; the live one moves."""
    n = 7
    cache = hybrid.init_paged_cache(cfg, n, 12, 16)
    rng = np.random.default_rng(0)
    cache["state"] = jnp.asarray(rng.normal(size=cache["state"].shape),
                                 jnp.float32)
    cache["last_token"] = jnp.arange(n, dtype=jnp.int32) + 5
    live = np.array([True, False, True, True, False, True, True])
    table = _table(n, 12, {i: [i] for i in range(n)})
    out, _ = hybrid.decode_step(params, cache, cfg, table=table, span=64,
                                live=jnp.asarray(live))
    moved = [not np.array_equal(np.asarray(out["state"][:, i]),
                                np.asarray(cache["state"][:, i]))
             for i in range(n)]
    assert moved == live.tolist()


def _unbounded_attend_in_place(cfg, cache, fi, table_rows, span, q, pos,
                               staged_k, staged_v, staged_mask):
    """The in-place read as it was before it was bounded by residency:
    every slot of the tile for every block of the span, one flat loop —
    kept here as the reference the bounded read must equal EXACTLY on
    the live rows (a skipped block added masked scores and exact-zero
    weights)."""
    T, _, nh, hd = q.shape
    G = cfg.n_kv_heads
    rep, f32 = nh // G, jnp.float32
    L, n_blocks, bl = cache["k"].shape[:3]
    P = -(-span // bl)
    fk, fv = (cache[n].reshape((L * n_blocks,) + cache[n].shape[2:])
              for n in ("k", "v"))
    at = fi * n_blocks + table_rows[:, :P]
    qf = q[:, 0].reshape(T, G, rep, hd).astype(f32) * hd ** -0.5

    def score(i, scores):
        t, j = i // P, i % P
        kp = jax.lax.dynamic_index_in_dim(fk, at[t, j], 0, False)[:, :G]
        s = jnp.einsum("mgk,grk->mgr", kp.astype(f32),
                       jax.lax.dynamic_index_in_dim(qf, t, 0, False))
        return jax.lax.dynamic_update_slice(scores, s[None],
                                            (t, j * bl, 0, 0))

    scores = jax.lax.fori_loop(0, T * P, score,
                               jnp.zeros((T, P * bl, G, rep), f32))
    neg = jnp.asarray(-1e30, f32)
    resident = jnp.arange(P * bl)[None, :] < pos[:, None]
    scores = jnp.where(resident[:, :, None, None], scores, neg)
    staged = jnp.einsum("tgrk,tmgk->tmgr", qf, staged_k.astype(f32))
    staged = jnp.where(staged_mask[0, 0][None, :, None, None], staged, neg)
    w = jax.nn.softmax(jnp.concatenate([scores, staged], axis=1), axis=1)
    w_res, w_st = w[:, :P * bl], w[:, P * bl:]

    def weigh(i, acc):
        t, j = i // P, i % P
        vp = jax.lax.dynamic_index_in_dim(fv, at[t, j], 0, False)[:, :G]
        wp = jax.lax.dynamic_slice(w_res, (t, j * bl, 0, 0),
                                   (1, bl, G, rep))[0]
        return acc.at[t].add(jnp.einsum("mgr,mgk->grk", wp, vp.astype(f32)))

    o = jax.lax.fori_loop(0, T * P, weigh, jnp.zeros((T, G, rep, hd), f32))
    o = o + jnp.einsum("tmgr,tmgk->tgrk", w_st, staged_v.astype(f32))
    return o.reshape(T, 1, nh, hd)


# One tile: a slot at the full span, a short one, a length exactly on a
# block boundary, a live slot of length 0, and a DEAD slot that holds
# rows (mid-prefill). Blocks 10 and 11 are nobody's; the table's
# sentinel (12) addresses the next layer's first block, or clamps to 11.
_TILE_LENGTHS = [64, 5, 32, 0, 40]
_TILE_LIVE = [True, True, True, True, False]
_TILE_BLOCKS = {0: [3, 1, 4, 0], 1: [2], 2: [7, 6], 4: [8, 9, 5]}


@pytest.mark.parametrize("fi", [0, 1], ids=["layer0", "last_layer"])
@pytest.mark.parametrize("case", ["numbers", "poison"])
def test_in_place_read_is_bounded_by_residency(cfg, case, fi):
    """``_attend_in_place`` reads a live slot's blocks up to the rows it
    holds and a dead slot's not at all. ``numbers``: the live rows equal
    gather + ``_attend`` (summation order) AND the unbounded in-place
    read bit for bit. ``poison``: every block of the pool that no live
    slot's rows reach — the dead slot's, the free ones, the other layer
    and with it the sentinel's target — is NaN, and the live rows are
    finite and unchanged (a NaN times a zero weight would show any block
    still read)."""
    n_blocks, bl, span, k = 12, 16, 64, 4
    T = len(_TILE_LENGTHS)
    rng = np.random.default_rng(41)
    cache = hybrid.init_paged_cache(cfg, T, n_blocks, bl)
    for name in ("k", "v"):
        cache[name] = jnp.asarray(rng.normal(size=cache[name].shape),
                                  jnp.float32)
    table = _table(T, n_blocks, _TILE_BLOCKS)
    lengths = jnp.asarray(_TILE_LENGTHS, jnp.int32)
    live = np.asarray(_TILE_LIVE)
    q = jnp.asarray(rng.normal(size=(T, 1, cfg.n_heads, cfg.head_dim)),
                    jnp.float32)
    sk, sv = (jnp.asarray(rng.normal(
        size=(T, k, cfg.n_kv_heads, cfg.head_dim)), jnp.float32)
        for _ in range(2))
    staged = (jnp.arange(k) <= 1)[None, None, :]
    rows = jnp.where(jnp.asarray(live), lengths, 0)
    held = jnp.stack([rows, hybrid._blocks_held(cache, rows, span // bl)], 1)
    assert np.asarray(held[:, 1]).tolist() == [4, 1, 2, 0, 0]

    def bounded(c):
        return np.asarray(jax.jit(lambda c: hybrid._attend_in_place(
            cfg, c, jnp.asarray(fi), table, span, q, held, sk, sv,
            staged))(c))

    def unbounded(c):
        return np.asarray(jax.jit(lambda c: _unbounded_attend_in_place(
            cfg, c, jnp.asarray(fi), table, span, q, lengths, sk, sv,
            staged))(c))

    got = bounded(cache)
    assert np.isfinite(got).all()
    if case == "numbers":
        ck, cv = hybrid._gather_kv(cfg, cache, fi, table, span)
        resident = (jnp.arange(span)[None, None, :]
                    < lengths[:, None, None])
        want = np.asarray(hybrid._attend(
            cfg, q, [(ck, cv, resident), (sk, sv, staged)]))
        assert np.abs(got - want)[live].max() < LOGIT_TOL
        exact = unbounded(cache)
        assert np.array_equal(got[live], exact[live])
        # The dead slot is not the unbounded read's: nothing of it is.
        assert not np.array_equal(got[~live], exact[~live])
        return
    keep = np.zeros(cache["k"].shape[:2], bool)
    for slot, blocks in _TILE_BLOCKS.items():
        if live[slot]:
            keep[fi, blocks[:-(-_TILE_LENGTHS[slot] // bl)]] = True
    assert keep.sum() == 7
    poisoned = dict(cache)
    for name in ("k", "v"):
        poisoned[name] = jnp.where(keep[:, :, None, None, None],
                                   cache[name], jnp.nan)
    after = bounded(poisoned)
    assert np.isfinite(after[live]).all()
    assert np.array_equal(after[live], got[live])
    # ... and the unbounded read does touch them: the case is not vacuous.
    assert not np.isfinite(unbounded(poisoned)[live]).all()


# -- (d) ---------------------------------------------------------------------

@pytest.mark.parametrize("lengths", [(12, 20), (70, 45)],
                         ids=["waves", "chunks"])
def test_a_slot_rented_again_starts_from_zero(cfg, params, reference,
                                              lengths):
    """One slot, two tenants one after the other, and between them the
    slot's state and tails are overwritten with garbage for good
    measure: the second tenant's tokens are the reference's."""
    e = _engine(params, cfg, n_slots=1)
    first, second = _prompts(lengths, seed=7)
    e.generate([first], max_new_tokens=6)
    assert float(jnp.abs(e.cache["state"][:, 0]).max()) > 0
    e.cache["state"] = e.cache["state"] * 0 + 9.0
    e.cache["conv"] = e.cache["conv"] * 0 - 4.0
    e.finished.clear()
    out = e.generate([second], max_new_tokens=8)[0]
    _check_greedy(reference, second, out)
    assert out == _engine(params, cfg, n_slots=1).generate(
        [second], max_new_tokens=8)[0]


# -- (e) ---------------------------------------------------------------------

def test_preempt_and_resume_give_the_uninterrupted_continuation(
        cfg, params, reference):
    """A decoding slot is evicted and its request resumes cold: there is
    no prefix index, so the resume re-prefills prompt + committed tokens
    through the chunk path, which rebuilds state and K/V alike."""
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


# -- (f) ---------------------------------------------------------------------

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
    assert "hybrid" in err.value.typed_error["family"]
    with pytest.raises(eng.UnsupportedOptionError):
        eng.refuse_hybrid_options(**{option: True})


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
    # The families whose blocks ARE all a sharer needs are not refused.
    lcfg = llama.CONFIGS["llama3-tiny"]
    le = eng.InferenceEngine(
        llama.init_params(jax.random.key(0), lcfg), lcfg, n_slots=2,
        max_len=64, prompt_buckets=(16, 64), kv_block=16)
    assert le.import_prefix([1, 2, 3], {"kv_block": 16}) == 0


def test_serving_weights_builder_knows_the_family(cfg):
    params, qweights = eng.random_serving_weights(cfg)
    assert qweights is None
    assert len(params["lin"]) == 3
    assert params["lin"][0]["wq"].shape == (2, 64, 4, 8)
    assert params["lin"][0]["wq"].dtype == cfg.dtype
    for kw in ({"weights_int8": True}, {"mesh": "a mesh"}):
        with pytest.raises(eng.UnsupportedOptionError):
            eng.random_serving_weights(cfg, **kw)
    with pytest.raises(NotImplementedError, match="verify"):
        hybrid.verify_draft_staged()


def test_warm_grid_ledger_and_token_bytes(cfg, params):
    """The warm grid covers the family's programs (nothing compiles
    under traffic afterwards); the HBM ledger has ``recurrent_state``
    beside ``kv_pool``; a token's cache bytes count the FULL layers
    only."""
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
    assert led["recurrent_state"] == cfg.n_lin_layers * slots * (
        4 * 16 * 8 * 4 + 3 * cfg.conv_channels * 4)
    assert led["recurrent_state"] == slots * hybrid.slot_state_bytes(cfg)
    assert led["kv_pool"] == e.cache["k"].nbytes * 2 + 2 * slots * 4
    assert "latent_kv_pool" not in led
    # float32 rows of 16 pooled heads (2 real) x 16, K and V, 2 full layers
    assert eng.KV_TOKEN_BYTES._require_default().value \
        == cfg.n_full_layers * 2 * 16 * 16 * 4


def test_a_lone_request_rides_a_one_row_wave_with_the_same_tokens(
        cfg, params):
    """Under ``pad_waves`` a lone request's wave is padded to ONE row
    and a fuller one to ``max_wave``: the request's tokens are the same
    from either program (its recurrent state is written per row), and after the
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


def test_dispatch_annotations_say_carried_and_state_rows(
        cfg, params, tmp_path, monkeypatch):
    """``engine.chunk.dispatch`` says whether the chunk continued a
    resident state, ``engine.decode.dispatch`` how many slots' states
    the burst updates and how many blocks hold its slots' rows
    (``kv_blocks``: what a layer reads, against the ``tiles * TILE *
    ceil(span / block)`` the rung alone would make it); the flight
    record carries the same count; an engine of the Llama family says
    none of the three."""
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    # The timeline's buffer is the process's: another test's events (an
    # engine of another family, under the same worker) must not be read.
    t0 = time.time() * 1e6
    e = _engine(params, cfg)
    e.add_request(_prompts([75], seed=12)[0], max_new_tokens=24)
    e.add_request(_prompts([20], seed=13)[0], max_new_tokens=24)
    held, round_slots = [], e._round_slots

    def spy(width):
        span, slots, promoted = round_slots(width)
        if slots:
            held.append(sum(
                -(-(len(r.prompt) + len(r.tokens) + e._inflight_tokens)
                  // 16) for r in (e.slot_req[s] for s in slots)))
        return span, slots, promoted

    monkeypatch.setattr(e, "_round_slots", spy)
    seq0 = e.flight.seq()
    e.run_to_completion(max_burst=4)
    lcfg = llama.CONFIGS["llama3-tiny"]
    le = eng.InferenceEngine(
        llama.init_params(jax.random.key(0), lcfg), lcfg, n_slots=2,
        max_len=128, prompt_buckets=(16, 128), prefill_chunk=32,
        kv_block=16)
    timeline.save_now()
    events = _events_since(path, t0)
    chunks = [ev["args"] for ev in events
              if ev["name"] == "engine.chunk.dispatch"]
    assert [c["carried"] for c in chunks] == [0, 1, 1]
    assert [c["chunk_tokens"] for c in chunks] == [32, 32, 11]
    bursts = [ev["args"] for ev in events
              if ev["name"] == "engine.decode.dispatch"]
    assert bursts and all(b["state_rows"] == b["slots"] for b in bursts)
    assert max(b["state_rows"] for b in bursts) == 2
    # What the spy counted from the requests themselves, dispatch by
    # dispatch; never the rung's blocks (75 and 20 tokens in blocks of
    # 16: 5..7 + 2..3 a layer where the rung alone reads 16 or 32).
    assert [b["kv_blocks"] for b in bursts] == held
    assert max(b["slots"] for b in bursts) == 2
    for b in bursts:
        assert 0 < b["kv_blocks"] <= (
            b["tiles"] * kvcache.TILE * -(-b["span"] // 16))
    assert sum(held) < sum(b["tiles"] * kvcache.TILE * -(-b["span"] // 16)
                           for b in bursts) / 2
    records = [r for r in e.flight.since(seq0) if r["burst"] == "decode"]
    assert [r["kv_blocks"] for r in records] == held
    assert all(r["kv_blocks"] <= r["tiles"] * kvcache.TILE * 8
               for r in records)
    t1, seq1 = time.time() * 1e6, le.flight.seq()
    le.add_request(list(range(1, 50)), max_new_tokens=4)
    le.run_to_completion(max_burst=4)
    timeline.save_now()
    later = _events_since(path, t1)
    mine = [ev["args"] for ev in later
            if ev["name"] in ("engine.chunk.dispatch",
                              "engine.decode.dispatch")]
    assert mine and not any(
        "carried" in a or "state_rows" in a or "kv_blocks" in a
        for a in mine)
    others = le.flight.since(seq1)
    assert others and not any("kv_blocks" in r for r in others)
