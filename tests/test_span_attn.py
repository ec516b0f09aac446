"""Span-bucketed decode attention: ladder selection, bit parity vs
the full view, retrace discipline, one program a round, lazy block
growth.

Tier-1 guards for the PR-9 bandwidth refactor (ROADMAP item 1's
follow-up to the paged cache):

* Span-on greedy output is BIT-identical to the full-view programs —
  {fp32, int8 KV} x {paged, contiguous} x {spec-on, spec-off} — on
  mixed-length workloads: the span read is a prefix of the full view
  whose dropped rows all carried exact-zero softmax weight.
* Retrace discipline: a mixed-length run compiles at most one
  decode/verify program per span-ladder rung — never one per observed
  length.
* One program a round: a mixed-length round dispatches ONE burst or
  verify program, at the rung of its longest live slot, for every
  slot the pool backs; its tokens equal the full view's.
* Lazy growth (SKYTPU_KV_LAZY): admission reserves prompt + one burst
  of blocks, growth happens at dispatch, and the existing block-leak
  audits still hold (admit/retire -> clear -> 0 blocks used).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.infer import engine as eng
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


def _mixed_prompts(cfg, lengths=(5, 12, 30, 9), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist()
            for n in lengths]


def _engine(params, cfg, span_buckets=None, kv_block=8, max_len=64,
            slots=4, **kw):
    kw.setdefault("prompt_buckets", (16, 32))
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefix_pool", 2)
    return eng.InferenceEngine(params, cfg, n_slots=slots,
                               max_len=max_len, kv_block=kv_block,
                               span_buckets=span_buckets, **kw)


# -- ladder knob ------------------------------------------------------------

def test_span_ladder_default_and_knobs(params, cfg, monkeypatch):
    # Default: power-of-two ladder ending at max_len.
    e = _engine(params, cfg, kv_block=8, max_len=64)
    assert e.span_ladder == (8, 16, 32, 64)
    # Explicit rungs keep their values (no block alignment needed —
    # the paged gather covers whole blocks and slices to the span)
    # and max_len always closes the ladder.
    e = _engine(params, cfg, span_buckets=(12, 40), kv_block=8)
    assert e.span_ladder == (12, 40, 64)
    # 0 disables: the full view is the only rung.
    e = _engine(params, cfg, span_buckets=0)
    assert e.span_ladder == (64,)
    # Env knob (ctor arg None falls through).
    monkeypatch.setenv("SKYTPU_SPAN_BUCKETS", "16,32")
    e = _engine(params, cfg)
    assert e.span_ladder == (16, 32, 64)
    monkeypatch.setenv("SKYTPU_SPAN_BUCKETS", "0")
    e = _engine(params, cfg)
    assert e.span_ladder == (64,)
    # Contiguous layout: identical semantics.
    e = _engine(params, cfg, span_buckets=(12, 40), kv_block=0)
    assert e.span_ladder == (12, 40, 64)
    # A rung smaller than one block still buckets: the gather covers
    # the first block and slices — parity is the matrix test's job.
    e = _engine(params, cfg, span_buckets=(4,), kv_block=16)
    assert e.span_ladder == (4, 64)


def test_span_for_and_arg(params, cfg):
    e = _engine(params, cfg, kv_block=8, max_len=64)
    assert e._span_for(1) == 8
    assert e._span_for(8) == 8
    assert e._span_for(9) == 16
    assert e._span_for(64) == 64
    # max_len rung dispatches as the UNSLICED full-view program.
    assert e._span_arg(64) is None
    assert e._span_arg(16) == 16


# -- parity: span-on == full view across the whole matrix -------------------

@pytest.mark.parametrize("kv_block", [8, 0], ids=["paged", "contig"])
@pytest.mark.parametrize("kv_int8", [False, True],
                         ids=["fp32", "int8"])
@pytest.mark.parametrize("spec_k", [0, 3], ids=["spec-off", "spec-on"])
def test_span_parity_matrix(params, cfg, kv_block, kv_int8, spec_k):
    """Greedy output with the span ladder is bit-identical to the
    full-view programs: the rows a span read drops were all masked to
    exact-zero softmax weight, and the kept rows keep their order."""
    prompts = _mixed_prompts(cfg)

    def run(span_buckets):
        e = _engine(params, cfg, span_buckets=span_buckets,
                    kv_block=kv_block, kv_int8=kv_int8, spec_k=spec_k)
        outs = e.generate(prompts, max_new_tokens=20)
        return e, outs

    e_span, out_span = run(None)
    _, out_full = run(0)
    assert out_span == out_full
    # The span pass really ran bucketed programs (not just the
    # fallback): some dispatched burst read fewer than max_len rows.
    spans = [s for kind, *_, s in e_span.decode_programs
             if kind in ("burst", "verify") and s is not None]
    assert spans and min(spans) < e_span.max_len


def test_span_parity_weights_int8(cfg):
    """w8a8 engines (slim fp tree) span-bucket identically."""
    from skypilot_tpu.infer import kvcache
    params, qw = kvcache.random_quantized_params(cfg)
    prompts = _mixed_prompts(cfg)

    def run(span_buckets):
        e = _engine(params, cfg, span_buckets=span_buckets,
                    qweights=qw, kv_int8=True)
        return e.generate(prompts, max_new_tokens=16)

    assert run(None) == run(0)


# -- retrace discipline -----------------------------------------------------

def test_program_count_bounded_by_ladder(params, cfg):
    """A mixed-length workload (many distinct lengths) compiles at
    most one decode program and one verify program per ladder rung —
    the ladder, not the length distribution, bounds the compile
    count."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (3, 5, 7, 9, 11, 14, 17, 21, 25, 30)]
    e = _engine(params, cfg, slots=5, spec_k=3)
    e.generate(prompts, max_new_tokens=17)
    ladder = len(e.span_ladder)
    by_kind = {}
    for key in e.decode_programs:
        by_kind.setdefault(key[0], set()).add(key)
    # Burst width is pinned (max_burst rounds to one power of two
    # here), so each kind's program count is ladder-bounded.
    for kind in ("burst", "verify"):
        widths = {k[1] for k in by_kind.get(kind, ())}
        for w in widths:
            n = len([k for k in by_kind[kind] if k[1] == w])
            assert n <= ladder, (
                f"{kind}@{w}: {n} programs > ladder {ladder}")
    # Spans dispatched are ladder rungs (None = the max_len rung).
    for key in e.decode_programs:
        span = key[-1]
        assert span is None or span in e.span_ladder


# -- one program a round ----------------------------------------------------

def _short_and_long(cfg, seed=2):
    rng = np.random.default_rng(seed)
    return ([rng.integers(1, cfg.vocab_size, 4).tolist()
             for _ in range(3)]
            + [rng.integers(1, cfg.vocab_size, 30).tolist()])


def _count_calls(engine, attr):
    """Count the launches of one of the engine's device programs."""
    calls = []
    fn = getattr(engine, attr)

    def counted(*a, **kw):
        calls.append(kw.get("span"))
        return fn(*a, **kw)

    setattr(engine, attr, counted)
    return calls


def test_mixed_round_is_one_program_at_the_longest_rung(params, cfg):
    """Three short conversations and one long one decode in ONE burst
    program, at the long slot's rung, and all four get their tokens."""
    e = _engine(params, cfg, span_buckets=(8, 16), slots=4)
    assert e.span_ladder == (8, 16, 64)
    *short, long_p = _short_and_long(cfg)
    for p in short:
        e.add_request(p, max_new_tokens=12)
    long_rid = e.add_request(long_p, max_new_tokens=4)
    e.admit()
    while e.chunking:
        e.prefill_chunk_step()
    span, slots, promoted = e._round_slots(4)
    assert span == 64 and sorted(slots) == sorted(e.slot_req)
    assert promoted == 3           # the short slots ride above their rung
    launches = _count_calls(e, "_decode_burst_fn")
    handle = e.dispatch_decode_burst(max_burst=4)
    assert launches == [None]      # one launch: the max_len rung
    assert sorted(handle.slots) == sorted(slots)
    out = e.complete_decode_burst(handle)
    assert len(out) == 4
    assert all(len(t) == 4 for rid, t in out.items() if rid != long_rid)
    # The long conversation has retired at its budget: the next round
    # of short slots runs a small-span program again, nobody promoted.
    assert len(e.slot_req) == 3
    span, slots, promoted = e._round_slots(4)
    assert (span, promoted) == (16, 0)
    assert sorted(slots) == sorted(e.slot_req)
    handle = e.dispatch_decode_burst(max_burst=4)
    assert launches == [None, 16]
    assert len(e.complete_decode_burst(handle)) == 3


@pytest.mark.parametrize("kv_block", [8, 0], ids=["paged", "contig"])
@pytest.mark.parametrize("spec_k", [0, 3], ids=["spec-off", "spec-on"])
def test_one_program_round_matches_full_view(params, cfg, kv_block,
                                             spec_k):
    """The mixed round's greedy tokens equal the full-view engine's:
    which rung a slot rode does not reach its logits. Every decode
    round of the span engine is one launch."""
    prompts = _short_and_long(cfg)

    def run(span_buckets):
        e = _engine(params, cfg, span_buckets=span_buckets,
                    kv_block=kv_block, spec_k=spec_k, slots=4)
        bursts = _count_calls(e, "_decode_burst_fn")
        verifies = _count_calls(e, "_verify_fn")
        outs = e.generate(prompts, max_new_tokens=12)
        return outs, bursts + verifies, e._burst_seq

    out_span, launches, rounds = run((8, 16))
    out_full, _, _ = run(0)
    assert out_span == out_full
    assert len(launches) == rounds > 0
    assert None in launches and any(s is not None for s in launches)


def test_lazy_unbackable_slot_sits_one_round_out(params, cfg):
    """A lazy engine whose pool cannot back one slot leaves that slot
    out of the round's one program and serves it once a retirement
    has freed blocks; the tokens equal the eager engine's."""
    prompts = _mixed_prompts(cfg, lengths=(5, 9))
    budgets = (24, 40)

    def admitted(**kw):
        e = _engine(params, cfg, kv_block=8, slots=2, prefix_pool=0,
                    **kw)
        for p, m in zip(prompts, budgets):
            e.add_request(p, max_new_tokens=m)
        e.admit()
        while e.chunking:
            e.prefill_chunk_step()
        return e

    # 8 blocks: admission takes 3 + 4 (prompt + 16 rows of headroom),
    # the short request grows into the last one, and the long one
    # finds the pool dry until the short one retires.
    lazy = admitted(kv_lazy=True, kv_blocks=8)
    launches = _count_calls(lazy, "_decode_burst_fn")
    sat_out = served_after = None
    rounds = 0
    while lazy.slot_req:
        live = dict(lazy.slot_req)
        handle = lazy.dispatch_decode_burst(max_burst=4)
        assert handle is not None, "pool wedged"
        rounds += 1
        out = lazy.complete_decode_burst(handle)
        left = set(live) - set(handle.slots)
        if left and sat_out is None:
            (slot,) = left
            sat_out = live[slot]
            assert sat_out.rid not in out
        elif sat_out is not None and served_after is None \
                and sat_out.rid in out:
            served_after = rounds
    assert sat_out is not None and served_after is not None
    assert len(launches) == rounds          # one program a round
    eager = admitted(kv_lazy=False)
    while eager.slot_req:
        eager.decode_burst(max_burst=4)
    assert ({r.rid: r.tokens for r in lazy.finished}
            == {r.rid: r.tokens for r in eager.finished})
    assert lazy.blocks_used == 0


# -- lazy block growth ------------------------------------------------------

def test_lazy_reserves_less_and_grows(params, cfg):
    prompts = _mixed_prompts(cfg, lengths=(5, 9))

    def admit_only(kv_lazy):
        e = _engine(params, cfg, kv_block=8, kv_lazy=kv_lazy, slots=2,
                    prefix_pool=0)
        for p in prompts:
            e.add_request(p, max_new_tokens=40)
        e.admit()
        while e.chunking:
            e.prefill_chunk_step()
        return e

    lazy, eager = admit_only(True), admit_only(False)
    assert lazy.kv_lazy and not eager.kv_lazy
    # Admission-time reservation: prompt + one burst, not the full
    # max_new_tokens worst case.
    assert lazy.blocks_used < eager.blocks_used
    used0 = lazy.blocks_used
    while lazy.slot_req:
        lazy.decode_burst(max_burst=4)
    # Growth happened at dispatch (the budget needs more rows than
    # the admission reservation backed), and every grown block was
    # released at retirement (prefix pool is off here).
    assert max(len(r.tokens) for r in lazy.finished) > 1
    assert lazy.blocks_used == 0
    outs_l = {r.rid: r.tokens for r in lazy.finished}
    while eager.slot_req:
        eager.decode_burst(max_burst=4)
    outs_e = {r.rid: r.tokens for r in eager.finished}
    # Lazy-vs-eager greedy parity: growth only changes WHEN blocks
    # are mapped, never what the programs read.
    assert outs_l == outs_e
    assert used0 > 0


def test_lazy_block_leak_audit(params, cfg):
    """The existing audit extends to lazy mode: a full admit/decode/
    retire cycle plus a prefix-cache clear ends at 0 blocks used."""
    e = _engine(params, cfg, kv_lazy=True, spec_k=3)
    e.generate(_mixed_prompts(cfg), max_new_tokens=20)
    assert not e.slot_req and not e.chunking
    e.clear_prefix_cache()
    assert e.blocks_used == 0
    # And reset() from any state.
    e.generate(_mixed_prompts(cfg, seed=3), max_new_tokens=8)
    e.reset()
    assert e.blocks_used == 0


def test_lazy_env_knob(params, cfg, monkeypatch):
    monkeypatch.setenv("SKYTPU_KV_LAZY", "1")
    assert _engine(params, cfg).kv_lazy
    monkeypatch.delenv("SKYTPU_KV_LAZY")
    assert not _engine(params, cfg).kv_lazy
    # Contiguous engines have no pool to be lazy about.
    assert not _engine(params, cfg, kv_block=0, kv_lazy=True).kv_lazy


def test_lazy_grows_metric(params, cfg):
    from skypilot_tpu.observability import metrics as obs

    def grows():
        fam = obs.REGISTRY.snapshot().get("skytpu_kv_lazy_grows_total")
        if not fam:
            return 0
        return sum(s.get("value", 0) for s in fam["samples"])

    v0 = grows()
    e = _engine(params, cfg, kv_lazy=True, prefix_pool=0)
    e.generate(_mixed_prompts(cfg), max_new_tokens=30)
    assert grows() > v0


# -- bench wiring -----------------------------------------------------------

def test_span_smoke_bench_wiring():
    """CI-sized bench pass: parity, and the structural (timing-free)
    evidence — the span pass gathered a fraction of the full view
    with a ladder-bounded program count. Wall-clock speedup is
    reported, never asserted, on CPU."""
    from skypilot_tpu.infer import bench_serve
    r = bench_serve.run_span_smoke()
    assert r["parity_ok"]
    assert r["rows_span"] * 8 <= r["rows_full"]
    assert r["n_span_programs"] <= len(r["span_ladder"])
    assert r["speedup"] > 0
