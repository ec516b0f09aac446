"""Speculative decoding: n-gram draft + fixed-K batched verify.

Tier-1 guards for the spec path's one non-negotiable claim — greedy
output is EXACTLY the spec-off output (fp32 and int8, paged and
contiguous, warm-prefix and chunked-admission prompts, EOS and
max_len edges) — plus the rollback invariant (rejected draft rows
leave the cache bit-equal to a never-drafted one), the drafter's
host-side semantics, the K knob, and the acceptance-collapse
fallback.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import kvcache, sampling
from skypilot_tpu.models import llama


@pytest.fixture(scope="module")
def cfg():
    # fp32: accumulation differences cannot hide behind bf16 eps (the
    # PR 6 test_infer_tp lesson); the int8 tests cover the quantized
    # cache, whose integer accumulation is exact.
    return dataclasses.replace(llama.CONFIGS["llama3-tiny"],
                               dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.key(0), cfg)


def _prompts(cfg, n=3, length=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, length).tolist()
            for _ in range(n)]


def _engine(params, cfg, spec_k=None, slots=4, max_len=128,
            buckets=(32,), **kw):
    return eng.InferenceEngine(params, cfg, n_slots=slots,
                               max_len=max_len, prompt_buckets=buckets,
                               spec_k=spec_k, **kw)


def _replay_drafter(outputs, transform=None):
    """Drafter factory replaying a known continuation per prompt: the
    ORACLE (transform=None — every draft accepted) or a derived
    always-wrong variant (e.g. transform shifting each token — every
    draft rejected). One implementation of the catch_up/draft
    protocol for every test that scripts drafts."""

    class Replay:
        def __init__(self, req):
            self.out = outputs[tuple(req.prompt)]
            self.seen = 0

        def catch_up(self, prompt, generated):
            self.seen = len(generated)

        def draft(self, k):
            nxt = self.out[self.seen:self.seen + k]
            return ([transform(t) for t in nxt] if transform
                    else list(nxt))

    return Replay


# -- drafter ----------------------------------------------------------------

def test_drafter_match_and_miss():
    d = eng.NGramDrafter([1, 2, 3, 9, 1, 2], n=2)
    # Tail [1, 2] occurred at position 0 with continuation [3, 9, 1].
    assert d.draft(3) == [3, 9, 1]
    assert d.draft(1) == [3]
    # Tail with no earlier occurrence: miss drafts nothing.
    assert eng.NGramDrafter([1, 2, 3, 4, 5], n=2).draft(4) == []


def test_drafter_self_extends_through_cycles():
    # A period-2 cycle: the nearest match sits at the tail, but the
    # draft keeps following the cycle through its own proposal.
    d = eng.NGramDrafter([7, 8, 7, 8, 7, 8], n=2)
    assert d.draft(6) == [7, 8, 7, 8, 7, 8]


def test_drafter_degenerate_short_context():
    assert eng.NGramDrafter([], n=2).draft(4) == []
    assert eng.NGramDrafter([5], n=2).draft(4) == []
    assert eng.NGramDrafter([5, 5], n=3).draft(4) == []
    # k <= 0 never drafts.
    assert eng.NGramDrafter([1, 2, 1, 2], n=2).draft(0) == []


def test_drafter_extend_and_catch_up():
    d = eng.NGramDrafter([1, 2, 3], n=2)
    d.catch_up([1, 2, 3], [1, 2])      # two tokens committed elsewhere
    assert d.tokens == [1, 2, 3, 1, 2]
    # [1, 2] (position 0) now has a continuation -> drafting works.
    assert d.draft(2) == [3, 1]
    # catch_up is idempotent.
    d.catch_up([1, 2, 3], [1, 2])
    assert d.tokens == [1, 2, 3, 1, 2]


# -- knobs ------------------------------------------------------------------

def test_spec_k_env_knob_and_clamp(params, cfg, monkeypatch):
    monkeypatch.setenv("SKYTPU_SPEC_K", "3")
    assert _engine(params, cfg).spec_k == 3
    monkeypatch.setenv("SKYTPU_SPEC_K", "0")
    assert _engine(params, cfg).spec_k == 0
    monkeypatch.delenv("SKYTPU_SPEC_K")
    # Library default: off. Ctor arg wins over env, clamped to [0, 16].
    assert _engine(params, cfg).spec_k == 0
    assert _engine(params, cfg, spec_k=-5).spec_k == 0
    assert _engine(params, cfg, spec_k=99).spec_k == 16
    # Greedy-exact only: temperature sampling forces spec off.
    e = _engine(params, cfg, spec_k=4,
                sampling_params=sampling.SamplingParams(temperature=0.7))
    assert e.spec_k == 0


# -- parity -----------------------------------------------------------------

@pytest.mark.parametrize("kv_block", [0, 8], ids=["contiguous", "paged"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp32", "int8"])
def test_spec_parity_layouts_and_dtypes(params, cfg, kv_block, kv_int8):
    """The headline guarantee: spec-on greedy generation is identical
    to spec-off, across both storage layouts and the int8 KV cache."""
    prompts = _prompts(cfg)
    off = _engine(params, cfg, kv_block=kv_block, kv_int8=kv_int8)
    want = off.generate(prompts, max_new_tokens=24)
    on = _engine(params, cfg, spec_k=4, kv_block=kv_block,
                 kv_int8=kv_int8)
    assert on.generate(prompts, max_new_tokens=24) == want
    assert on._spec_drafted_total >= 0  # path exercised without error


def test_spec_parity_weights_int8(cfg):
    """w8a8 decode: the verify program runs the same quantized matmuls
    as the plain burst."""
    params, qw = kvcache.random_quantized_params(cfg)
    prompts = _prompts(cfg, n=2)
    kw = dict(n_slots=2, max_len=96, prompt_buckets=(32,),
              qweights=qw, kv_block=8)
    want = eng.InferenceEngine(params, cfg, **kw).generate(
        prompts, max_new_tokens=16)
    got = eng.InferenceEngine(params, cfg, spec_k=3, **kw).generate(
        prompts, max_new_tokens=16)
    assert got == want


def test_spec_parity_warm_prefix_and_chunked_admission(params, cfg):
    """Spec decode composes with chunked prefill + prefix reuse: cold
    (chunked) and warm (suffix-only) admissions generate the spec-off
    tokens, and the warm pass still hits the prefix cache."""
    system = list(range(5, 21))                     # 16 tokens, 2 chunks
    pa, pb = system + [31, 32, 33], system + [41, 42]
    kw = dict(buckets=(48,), max_len=96, prefill_chunk=8,
              prefix_pool=4, kv_block=8)
    off = _engine(params, cfg, **kw)
    on = _engine(params, cfg, spec_k=4, **kw)
    want_a = off.generate([pa], max_new_tokens=10)[0]
    off.finished.clear()
    want_b = off.generate([pb], max_new_tokens=10)[0]   # warm hit
    got_a = on.generate([pa], max_new_tokens=10)[0]
    on.finished.clear()
    got_b = on.generate([pb], max_new_tokens=10)[0]
    (req_b,) = on.finished
    assert got_a == want_a and got_b == want_b
    assert req_b.cached_len == 16                   # hit survived spec


def test_spec_bursts_interleave_with_chunked_admission(params, cfg):
    """A verify burst scatters K+1 garbage rows for EVERY slot — a
    slot mid-chunked-prefill (claimed, length stamped to max_len) must
    drop them exactly as plain bursts do, or finished chunks corrupt.
    Same interleave as test_chunked_prefill_interleaves_with_decode,
    spec on."""
    kw = dict(max_len=96, buckets=(48,), prefill_chunk=8,
              prefix_pool=0, kv_block=8)
    short, long_p = [3, 1, 4], list(range(1, 29))   # 28 -> 4 chunks
    solo = _engine(params, cfg, **kw)
    want_short = solo.generate([short], max_new_tokens=12)[0]
    solo.finished.clear()
    want_long = solo.generate([long_p], max_new_tokens=4)[0]

    e = _engine(params, cfg, spec_k=4, **kw)
    e.add_request(short, max_new_tokens=12)
    e.step_burst(max_burst=2)                 # short active, decoding
    e.add_request(long_p, max_new_tokens=4)   # chunks interleave
    e.run_to_completion(max_burst=2)
    by_prompt = {tuple(r.prompt): r.tokens for r in e.finished}
    assert by_prompt[tuple(short)] == want_short
    assert by_prompt[tuple(long_p)] == want_long


def test_spec_parity_at_max_len_boundary(params, cfg):
    """Near max_len a slot lacks K+1 rows of headroom: it rides verify
    bursts with an empty draft (spare window rows past max_len drop),
    and generation still matches spec-off to the cap."""
    prompts = _prompts(cfg, n=2, length=12)
    off = _engine(params, cfg, slots=2, max_len=32)
    want = off.generate(prompts, max_new_tokens=64)   # capped by rows
    on = _engine(params, cfg, spec_k=4, slots=2, max_len=32)
    got = on.generate(prompts, max_new_tokens=64)
    assert got == want
    assert all(len(p) + len(t) == 32 for p, t in zip(prompts, want))


def test_tight_slot_does_not_disable_neighbors_spec(params, cfg):
    """One request within K+1 rows of max_len must not turn
    speculation off engine-wide: the tight slot drafts nothing while
    its neighbor keeps drafting (and accepting, via an oracle), and
    both outputs match spec-off exactly."""
    tight_p = list(range(1, 21))                  # 20 rows, cap at 32
    roomy_p = [3, 1, 4]
    off = _engine(params, cfg, slots=2, max_len=32, buckets=(24,))
    want_t = off.generate([tight_p], max_new_tokens=64)[0]
    off.finished.clear()
    want_r = off.generate([roomy_p], max_new_tokens=12)[0]
    oracle = {tuple(tight_p): want_t, tuple(roomy_p): want_r}
    on = _engine(params, cfg, spec_k=4, slots=2, max_len=32,
                 buckets=(24,), spec_drafter=_replay_drafter(oracle))
    on.add_request(tight_p, max_new_tokens=64)    # tight within bursts
    on.add_request(roomy_p, max_new_tokens=12)
    on.run_to_completion(max_burst=4)
    by_prompt = {tuple(r.prompt): r for r in on.finished}
    assert by_prompt[tuple(tight_p)].tokens == want_t
    assert by_prompt[tuple(roomy_p)].tokens == want_r
    # The roomy slot drafted (oracle: all accepted) even while the
    # tight slot was pinned to empty drafts.
    assert by_prompt[tuple(roomy_p)].spec_drafted > 0
    assert (by_prompt[tuple(roomy_p)].spec_accepted
            == by_prompt[tuple(roomy_p)].spec_drafted)
    # The tight slot stopped drafting once headroom ran out: it can
    # never have drafted past the point where rows + K + 1 > max_len.
    assert by_prompt[tuple(tight_p)].spec_drafted <= 32 - 20 - 5 + 4


def test_spec_parity_with_eos_mid_commit(params, cfg):
    """EOS inside an accepted run retires the request at the same
    token spec-off does (surplus committed tokens are discarded
    host-side)."""
    prompts = _prompts(cfg, n=2)
    ref = _engine(params, cfg).generate(prompts, max_new_tokens=24)
    eos = ref[0][len(ref[0]) // 2]                  # appears mid-output
    off = _engine(params, cfg)
    off.eos_id = eos
    want = off.generate(prompts, max_new_tokens=24)
    on = _engine(params, cfg, spec_k=4)
    on.eos_id = eos
    assert on.generate(prompts, max_new_tokens=24) == want
    assert any(len(t) < 24 for t in want)           # EOS actually fired


def test_spec_oracle_full_acceptance(params, cfg):
    """A drafter that replays the true continuation accepts everything:
    n_commit == K+1 per burst, acceptance rate exactly 1.0, and the
    output is still bit-identical (the bonus token past the draft is
    the plain path's next token)."""
    prompts = _prompts(cfg, n=2)
    want = _engine(params, cfg).generate(prompts, max_new_tokens=20)
    oracle = {tuple(p): o for p, o in zip(prompts, want)}
    on = _engine(params, cfg, spec_k=4,
                 spec_drafter=_replay_drafter(oracle))
    assert on.generate(prompts, max_new_tokens=20) == want
    assert on._spec_drafted_total > 0
    assert on._spec_accepted_total == on._spec_drafted_total


# -- rollback ---------------------------------------------------------------

def _paged_table():
    # Slot 0 owns blocks 0..7 logically in order; slot 1 + the sentinel
    # column stay unmapped (the engine's claim shape).
    tbl = np.full((2, 9), 10, np.int32)
    tbl[0, :8] = np.arange(8)
    return jnp.asarray(tbl)


def _seeded_cache(params, cfg, kv_int8, prompt, table=None):
    cache = (kvcache.init_cache(cfg, 2, 64, kv_int8=kv_int8)
             if table is None else
             kvcache.init_paged_cache(cfg, 2, 10, 8, kv_int8=kv_int8))
    prefix, logits = kvcache.prefill(
        params, jnp.asarray(prompt, jnp.int32),
        jnp.asarray(len(prompt), jnp.int32), cfg)
    first = int(np.argmax(np.asarray(logits)))
    cache = kvcache.insert(cache, prefix, jnp.asarray(0, jnp.int32),
                           jnp.asarray(len(prompt), jnp.int32),
                           jnp.asarray(first, jnp.int32), table=table)
    return cache


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp32", "int8"])
def test_rollback_leaves_kv_bit_equal(params, cfg, kv_int8, layout):
    """Kernel-level rollback invariant: a verify burst whose draft is
    fully REJECTED leaves every committed row (and length/last_token)
    bit-equal to the same burst run with no draft at all — rejected
    rows sit past the committed length and are never readable. Paged:
    the 'rollback' is purely the length not advancing; no block
    moves."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    K = 4
    table = _paged_table() if layout == "paged" else None
    cache = _seeded_cache(params, cfg, kv_int8, prompt, table=table)
    active = jnp.asarray(np.array([True, False]))

    # The model's actual next tokens (so the wrong draft provably
    # mismatches at position 0).
    _, ref_toks, _ = kvcache.verify_draft_staged(
        params, cache, jnp.zeros((2, K), jnp.int32),
        jnp.zeros((2,), jnp.int32), active, K, cfg, table=table)
    wrong = (np.asarray(ref_toks)[0, 0] + 1) % cfg.vocab_size
    draft = np.zeros((2, K), np.int32)
    draft[0] = wrong

    rej, toks_r, commit_r = kvcache.verify_draft_staged(
        params, cache, jnp.asarray(draft),
        jnp.asarray(np.array([K, 0], np.int32)), active, K, cfg,
        table=table)
    bare, toks_b, commit_b = kvcache.verify_draft_staged(
        params, cache, jnp.zeros((2, K), jnp.int32),
        jnp.zeros((2,), jnp.int32), active, K, cfg, table=table)

    assert int(commit_r[0]) == 1 and int(commit_b[0]) == 1
    assert int(commit_r[1]) == 0                    # inactive slot
    assert int(toks_r[0, 0]) == int(toks_b[0, 0])
    n = int(bare["length"][0])
    assert n == len(prompt) + 1
    assert int(rej["length"][0]) == n
    assert int(rej["last_token"][0]) == int(bare["last_token"][0])
    for name in ("k", "v", "k_scale", "v_scale"):
        if name not in cache:
            continue
        a, b = np.asarray(rej[name]), np.asarray(bare[name])
        if layout == "contiguous":
            rows_a = a[:, 0, :n] if name in ("k", "v") else a[:, 0, :, :n]
            rows_b = b[:, 0, :n] if name in ("k", "v") else b[:, 0, :, :n]
        else:
            # Logical rows 0..n-1 live in blocks 0..ceil(n/8)-1; the
            # committed region is rows [0, n) of the gathered view.
            ga = a[:, np.arange(8)]
            gb = b[:, np.arange(8)]
            if name in ("k", "v"):
                rows_a = ga.reshape(a.shape[0], 64, *a.shape[3:])[:, :n]
                rows_b = gb.reshape(b.shape[0], 64, *b.shape[3:])[:, :n]
            else:
                rows_a = ga.transpose(0, 2, 1, 3).reshape(
                    a.shape[0], a.shape[2], 64)[:, :, :n]
                rows_b = gb.transpose(0, 2, 1, 3).reshape(
                    b.shape[0], b.shape[2], 64)[:, :, :n]
        assert np.array_equal(rows_a, rows_b), name


def _same_cache(a, b):
    for name in a:
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), name


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("span", [None, 16], ids=["full", "span16"])
def test_step_burst_and_verify_are_one_scaffold(params, cfg, kv_int8,
                                                layout, span):
    """The three decode programs are drivers of ONE staged scaffold, so
    their equalities are exact, not up to summation order:

    (a) ``decode_step``'s logits ARE the first step's logits of a
        burst: a greedy burst of one emits their argmax and leaves the
        same cache, bit for bit, and the first of four staged steps
        computes the same logits (the three unused staged columns are
        masked to exact-zero weight);
    (b) ``verify_draft_staged`` fed the burst's own tokens as its draft
        accepts all of them and commits k + 1 tokens equal to the
        burst's, leaving the same cache, bit for bit.
    """
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    K = 3
    table = _paged_table() if layout == "paged" else None
    cache = _seeded_cache(params, cfg, kv_int8, prompt, table=table)
    # Both slots live (slot 1 is empty: it attends to its own staged
    # rows only), so every row either program writes is a fed-back one.
    active = jnp.asarray(np.array([True, True]))
    greedy = sampling.SamplingParams()
    kw = dict(table=table, span=span)

    # (a) a step is a burst of one.
    stepped, logits = kvcache.decode_step(params, cache, cfg, **kw)
    burst1, _, toks1 = kvcache.decode_burst_staged(
        params, cache, jax.random.key(0), active, 1, cfg, greedy, **kw)
    assert int(toks1[0, 0]) == int(np.argmax(np.asarray(logits[0])))
    _same_cache(kvcache.commit_tokens(stepped, toks1[0], active), burst1)
    _, _, first_of_four = kvcache._staged_steps(
        params, cache, cfg, K + 1, None, cache["last_token"],
        lambda last, x: last,
        lambda lg, last, x: (sampling.argmax_tokens(lg), lg), **kw)
    assert np.array_equal(np.asarray(first_of_four[0]), np.asarray(logits))

    # (b) verify over the burst's own tokens is the burst.
    burst, _, toks = kvcache.decode_burst_staged(
        params, cache, jax.random.key(0), active, K + 1, cfg, greedy, **kw)
    ver, toks_v, n_commit = kvcache.verify_draft_staged(
        params, cache, toks[:K].T, jnp.full((2,), K, jnp.int32), active, K,
        cfg, **kw)
    assert n_commit.tolist() == [K + 1, K + 1]
    assert np.array_equal(np.asarray(toks_v), np.asarray(toks.T))
    _same_cache(ver, burst)


def test_rejected_drafts_roll_back_engine_level(params, cfg):
    """An always-wrong drafter: zero acceptance, every draft rolled
    back, output still exactly spec-off (each burst commits only the
    correction token)."""
    prompts = _prompts(cfg, n=2)
    want = _engine(params, cfg).generate(prompts, max_new_tokens=16)
    oracle = {tuple(p): o for p, o in zip(prompts, want)}
    # Drafts (true_next + 1) mod vocab — mismatch guaranteed.
    on = _engine(params, cfg, spec_k=3, spec_drafter=_replay_drafter(
        oracle, transform=lambda t: (t + 1) % cfg.vocab_size))
    on.spec_min_rate = 0.0                  # keep drafting to the end
    assert on.generate(prompts, max_new_tokens=16) == want
    assert on._spec_drafted_total > 0
    assert on._spec_accepted_total == 0


# -- fallback ---------------------------------------------------------------

def test_acceptance_collapse_falls_back_per_request(params, cfg):
    """A request whose drafts never verify stops drafting once it
    crosses the collapse floor (spec_off), and the engine's bursts
    degrade to plain decode — bounded waste, same tokens."""
    prompts = _prompts(cfg, n=1, length=8)
    want = _engine(params, cfg).generate(prompts, max_new_tokens=32)
    oracle = {tuple(p): o for p, o in zip(prompts, want)}
    # Drafts (true_next + 1) mod vocab — never accepted.
    on = _engine(params, cfg, spec_k=4, spec_drafter=_replay_drafter(
        oracle, transform=lambda t: (t + 1) % cfg.vocab_size))
    on.spec_min_drafted = 8
    got = on.generate(prompts, max_new_tokens=32)
    assert got == want
    (req,) = on.finished
    assert req.spec_off                       # collapse fired
    assert req.spec_accepted == 0
    # Drafting stopped shortly after the floor, not at the end.
    assert 8 <= req.spec_drafted < 31
    assert on._spec_drafted_total == req.spec_drafted


def test_no_draft_everywhere_runs_plain_burst(params, cfg):
    """spec_decode_burst declines (returns None) when no active slot
    drafted — a K+1-wide verify with nothing to verify would be
    strictly worse than a plain burst."""
    e = _engine(params, cfg, spec_k=4,
                spec_drafter=lambda req: eng.NGramDrafter(req.prompt))
    # Distinct-token prompt: no repeated 2-gram, drafter always misses.
    e.add_request(list(range(1, 9)), max_new_tokens=4)
    e.admit()
    assert e.spec_decode_burst() is None
    out = e.decode_burst(4)                   # falls through to plain
    assert out and e._spec_drafted_total == 0


# -- metrics + bench wiring -------------------------------------------------

def test_spec_metrics_and_gauge(params, cfg):
    from skypilot_tpu.observability import metrics as metrics_lib

    def val(name):
        fam = metrics_lib.REGISTRY.snapshot()[name]
        return fam["samples"][0]["value"]

    d0, a0, r0 = (val("skytpu_spec_drafted_total"),
                  val("skytpu_spec_accepted_total"),
                  val("skytpu_spec_rollbacks_total"))

    class AlwaysDraft:
        """Two fixed tokens per burst — drafting is guaranteed without
        depending on the random model's n-gram structure; whether they
        verify is irrelevant to counter consistency."""

        def __init__(self, req):
            pass

        def catch_up(self, prompt, generated):
            pass

        def draft(self, k):
            return [0, 1][:k]

    on = _engine(params, cfg, spec_k=3, spec_drafter=AlwaysDraft)
    on.spec_min_rate = 0.0
    on.generate(_prompts(cfg, n=1), max_new_tokens=12)
    drafted = val("skytpu_spec_drafted_total") - d0
    accepted = val("skytpu_spec_accepted_total") - a0
    rolled = val("skytpu_spec_rollbacks_total") - r0
    assert drafted == on._spec_drafted_total > 0
    assert accepted == on._spec_accepted_total
    assert rolled == drafted - accepted
    rate = val("skytpu_spec_acceptance_rate")
    assert rate == pytest.approx(accepted / drafted)


def test_spec_smoke_bench_wiring():
    """CI-sized bench pass: parity on every column of both phases,
    oracle acceptance is exactly 1.0 (deterministic — no dependence on
    the random model's loop behavior), the model drafter accepts on
    the non-repetitive workload where n-gram drafting is a wash, and
    the pipeline's draft dispatches structurally overlap verify
    windows. Wall-clock speedups are reported, never asserted, on
    CPU."""
    from skypilot_tpu.infer import bench_serve
    r = bench_serve.run_spec_smoke()
    # Phase B (repetition-heavy, PR 8's columns unchanged).
    assert r["parity_ok"] and r["oracle_parity_ok"]
    assert r["oracle_accept_rate"] == 1.0
    assert r["drafted"] > 0
    assert 0.0 <= r["accept_rate"] <= 1.0
    assert r["bursts_spec"] > 0 and r["bursts_oracle"] > 0
    # Oracle bursts commit up to K+1 tokens per SLOT each:
    # structurally fewer dispatches than one-token decoding would need.
    assert (r["bursts_oracle"] * (r["spec_k"] + 1) * r["requests"]
            >= r["decode_tokens"])
    # Phase A (non-repetitive, model drafter): parity in every mode,
    # the distilled draft accepts where prompt-lookup cannot, and the
    # pipeline's overlap is structurally proven from flight records.
    assert r["model_parity_ok"] and r["model_sync_parity_ok"]
    assert r["ngram_nonrep_parity_ok"]
    assert r["model_accept_rate"] > 0.9
    assert r["ngram_nonrep_accept_rate"] < 0.5   # the honest wash
    assert r["overlap_ok"] and r["draft_records"] > 0
    assert r["draft_reuse_hits"] > 0
