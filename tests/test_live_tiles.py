"""A staged decode step visits its LIVE slots, a tile of slots a turn
(``kvcache._live_tiles`` / ``_visit_tiles``), against the all-rows
formulation it replaced — kept HERE as the plain reference: every slot's
resident rows gathered (the layer sliced out of the pool first) and
attended as one batch, in both cache families.

The per-row arithmetic did not change (the same dots at ``B = TILE``,
the same masks, one softmax over resident rows ++ staged columns), so
the Llama family's live rows are compared BIT FOR BIT, float32 weights
on the CPU, for every live mask the trip count distinguishes: none, one
slot, exactly a tile, a tile and one, all, scattered with the spare row
set. The latent family's turn also folds ``W_kvb`` into its rows' queries
and outputs — two products that are NOT batched by row, so their row
count went from B to TILE and the CPU's matmul blocks them otherwise:
its logits are held to :data:`LATENT_TOL`.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from skypilot_tpu.infer import kvcache, latent, sampling
from skypilot_tpu.models import glm_moe as glm
from skypilot_tpu.models import llama

T = kvcache.TILE
B = 2 * T + 1                 # 2 tiles of slots and the hidden spare
BL, NB = 8, 4                 # 4 blocks of 8 rows a slot: 32 rows
W = 3                         # staged steps a program
# Float32 logits (standard deviation ~1) whose only difference is the
# blocking of two un-batched products; measured: ~1e-6.
LATENT_TOL = 2e-5


def _mask(name):
    live = np.zeros((B,), bool)
    if name == "one":
        live[T + 1] = True
    elif name == "tile":
        live[0:2 * T:2] = True                # T slots, every other one
    elif name == "tile+1":
        live[:T + 1] = True
    elif name == "all":
        live[:] = True
    elif name == "scattered+spare":
        live[[1, T, B - 1]] = True
    return live


MASKS = ["none", "one", "tile", "tile+1", "all", "scattered+spare"]


def _table():
    """Slot b's blocks, scattered over the pool; the spare's row and
    every row's last column are the sentinel (= the block count)."""
    n_blocks = (B - 1) * NB
    perm = np.random.default_rng(7).permutation(n_blocks)
    table = np.full((B, NB + 1), n_blocks, np.int32)
    table[:B - 1, :NB] = perm.reshape(B - 1, NB)
    return table


def _lengths():
    """Resident rows a slot: 0 (empty), short and past a block's end;
    the spare holds none. Every slot keeps W rows of headroom."""
    n = np.random.default_rng(3).integers(0, BL * NB - W, B)
    n[2], n[B - 1] = 0, 0
    return n.astype(np.int32)


def _logical(cache, table, name, slot, n):
    """Slot ``slot``'s first ``n`` logical rows of tensor ``name``, all
    layers, read the plain way (numpy): [L, n, ...]."""
    pool = np.asarray(cache[name])
    rows = np.arange(n)
    if table is None:
        blk, off = np.full((n,), slot), rows
    else:
        blk, off = table[slot, rows // BL], rows % BL
    if name.endswith("_scale"):
        return pool[:, blk, :, off].transpose(1, 0, 2)    # [L, n, G]
    return pool[:, blk, off]


# ---------------------------------------------------------------------------
# The Llama family
# ---------------------------------------------------------------------------

# float32: an accumulation difference cannot hide behind bf16's epsilon.
CFG = dataclasses.replace(llama.CONFIGS["llama3-tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def _seeded_cache(params, cfg, kv_int8, paged):
    """Every slot holds its own random prompt, prefilled and inserted
    the way the engine does it."""
    table = _table() if paged else None
    cache = (kvcache.init_paged_cache(cfg, B, (B - 1) * NB, BL, kv_int8)
             if paged else kvcache.init_cache(cfg, B, BL * NB, kv_int8))
    rng = np.random.default_rng(11)
    ins = jax.jit(lambda c, toks, n, slot, tbl: kvcache.insert(
        c, kvcache.prefill(params, toks, n, cfg)[0], slot, n,
        toks[n - 1], table=tbl))
    for slot, n in enumerate(_lengths()):
        if n == 0:
            continue
        toks = np.zeros((BL * NB,), np.int32)
        toks[:n] = rng.integers(1, cfg.vocab_size, n)
        cache = ins(cache, jnp.asarray(toks), jnp.asarray(n),
                    jnp.asarray(slot, jnp.int32),
                    None if table is None else jnp.asarray(table))
    cache["last_token"] = jnp.asarray(
        rng.integers(1, cfg.vocab_size, B).astype(np.int32))
    return cache, table


def _ref_gather(cache, i, table, span):
    """Every slot's rows of layer ``i``, the layer sliced out first:
    k/v [B, M, G, hd], scales [B, G, M]."""
    ck, cv = cache["k"][i], cache["v"][i]
    cks = cvs = None
    if "k_scale" in cache:
        cks, cvs = cache["k_scale"][i], cache["v_scale"][i]
    if table is not None:
        bl = ck.shape[1]
        nb = table.shape[1] - 1 if span is None else -(-span // bl)
        tbl = table[:, :nb]
        n, G = tbl.shape[0], ck.shape[2]
        ck = ck[tbl].reshape(n, nb * bl, *ck.shape[2:])
        cv = cv[tbl].reshape(n, nb * bl, *cv.shape[2:])
        if cks is not None:
            cks = cks[tbl].transpose(0, 2, 1, 3).reshape(n, G, nb * bl)
            cvs = cvs[tbl].transpose(0, 2, 1, 3).reshape(n, G, nb * bl)
    if span is not None:
        ck, cv = ck[:, :span], cv[:, :span]
        if cks is not None:
            cks, cvs = cks[..., :span], cvs[..., :span]
    return ck, cv, cks, cvs


def _ref_steps(params, cache, cfg, table, span, active):
    """``W`` greedy staged steps, every slot attended as one batch:
    (cache' with the rows flushed, logits [W, B, vocab])."""
    quant = "k_scale" in cache
    kdt = cache["k"].dtype
    n, L = cache["length"].shape[0], cfg.n_layers
    G, hd = cfg.n_kv_heads, cfg.head_dim
    rep = cfg.n_heads // G
    M = span if span is not None else kvcache._logical_rows(cache, table)
    scale, neg = hd ** -0.5, jnp.float32(-1e30)
    bf = jnp.bfloat16
    f32 = dict(preferred_element_type=jnp.float32)
    pos0 = cache["length"]
    valid_cache = jnp.arange(M)[None, :] < pos0[:, None]
    sk = jnp.zeros((L, n, W, G, hd), kdt)
    sv = jnp.zeros((L, n, W, G, hd), kdt)
    if quant:
        sdt = cache["k_scale"].dtype
        sks, svs = (jnp.zeros((L, n, W, G), sdt) for _ in range(2))
    last, logits_all = cache["last_token"], []
    for s in range(W):
        x = params["embed"].astype(cfg.dtype)[last[:, None]]
        cos, sin = llama.rope_frequencies(cfg, (pos0 + s)[:, None])
        stage_valid = jnp.arange(W)[None, :] <= s
        for i in range(L):
            layer = jax.tree.map(lambda w: w[i], params["blocks"])
            q, kk, v = kvcache._layer_qkv(cfg, layer, None, x, cos, sin)
            if quant:
                kq, ksc = kvcache.quantize_rows(kk[:, 0])
                vq, vsc = kvcache.quantize_rows(v[:, 0])
                sk, sv = sk.at[i, :, s].set(kq), sv.at[i, :, s].set(vq)
                sks = sks.at[i, :, s].set(ksc.astype(sdt))
                svs = svs.at[i, :, s].set(vsc.astype(sdt))
            else:
                sk = sk.at[i, :, s].set(kk[:, 0].astype(kdt))
                sv = sv.at[i, :, s].set(v[:, 0].astype(kdt))
            qh = q[:, 0].reshape(n, G, rep, hd).astype(bf)
            ss = jnp.einsum("bgrk,bjgk->bgrj", qh, sk[i].astype(bf),
                            **f32) * scale
            if quant:
                ss = ss * sks[i].transpose(0, 2, 1)[:, :, None, :]
            ss = jnp.where(stage_valid[:, None, None, :], ss, neg)
            ck, cv, cks, cvs = _ref_gather(cache, i, table, span)
            sm = jnp.einsum("bgrk,bmgk->bgrm", qh, ck.astype(bf),
                            **f32) * scale
            if quant:
                sm = sm * cks[:, :, None, :]
            sm = jnp.where(valid_cache[:, None, None, :], sm, neg)
            w = jax.nn.softmax(jnp.concatenate([sm, ss], axis=-1), axis=-1)
            wm, ws = w[..., :M], w[..., M:]
            if quant:
                wm = wm * cvs[:, :, None, :]
                ws = ws * svs[i].transpose(0, 2, 1)[:, :, None, :]
            o = jnp.einsum("bgrm,bmgk->bgrk", wm.astype(bf), cv.astype(bf),
                           **f32)
            o = o + jnp.einsum("bgrj,bjgk->bgrk", ws.astype(bf),
                               sv[i].astype(bf), **f32)
            x = kvcache._layer_out_ffn(cfg, layer, None, x, o)
        logits = kvcache._head(cfg, params, None, x)
        logits_all.append(logits)
        last = jnp.where(active, sampling.argmax_tokens(logits), last)
    idx = pos0[:, None] + jnp.arange(W)[None, :]
    out = kvcache._write_rows(
        cache, table, jnp.arange(n)[:, None], idx,
        (sk, sv, sks, svs) if quant else (sk, sv))
    return out, jnp.stack(logits_all)


def _tiled_steps(params, cache, cfg, table, span, active, live):
    """The same ``W`` greedy steps through the module's own scaffold."""
    def emit(logits, last, x):
        return jnp.where(active, sampling.argmax_tokens(logits),
                         last), logits
    out, _, logits = kvcache._staged_steps(
        params, cache, cfg, W, None, cache["last_token"],
        lambda last, x: last, emit, table=table, span=span, live=live)
    return out, logits


@functools.lru_cache(maxsize=None)
def _llama_programs(span):
    """(reference, tiled with a live mask, tiled without) jitted once a
    span; layout and row dtype retrace by the arguments' structure."""
    return (
        jax.jit(lambda p, c, t, a: _ref_steps(p, c, CFG, t, span, a)),
        jax.jit(lambda p, c, t, a: _tiled_steps(p, c, CFG, t, span, a, a)),
        jax.jit(lambda p, c, t, a: _tiled_steps(p, c, CFG, t, span, a,
                                                None)))


@functools.lru_cache(maxsize=None)
def _llama_cache(kv_int8, paged):
    """(cache, table) — read only: the programs donate nothing."""
    return _seeded_cache(llama.init_params(jax.random.key(0), CFG), CFG,
                         kv_int8, paged)


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("span", [None, 32 - BL], ids=["full", "span24"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("paged", [True, False],
                         ids=["paged", "contiguous"])
def test_llama_tiled_visit_is_the_all_rows_attention(params, paged,
                                                     kv_int8, span, mask):
    """Live rows: logits of every staged step and the rows they leave in
    the cache equal the all-rows reference bit for bit. Dead rows: finite
    logits, resident rows untouched."""
    cache, table = _llama_cache(kv_int8, paged)
    lengths = _lengths()
    if span is not None:
        # A span program serves rounds whose LIVE slots fit under it.
        assert lengths.max() > span
    live = _mask(mask) & ((lengths <= span) if span is not None else True)
    tbl = None if table is None else jnp.asarray(table)
    ref, tiled, _ = _llama_programs(span)
    want_cache, want = ref(params, cache, tbl, jnp.asarray(live))
    got_cache, got = tiled(params, cache, tbl, jnp.asarray(live))
    want, got = np.asarray(want), np.asarray(got)
    assert np.isfinite(got).all()
    assert np.array_equal(got[:, live], want[:, live])
    for name in kvcache.row_tensors(cache):
        for slot in range(B - 1):
            n = lengths[slot] + (W if live[slot] else 0)
            after = _logical(got_cache, table, name, slot, n)
            before = _logical(want_cache if live[slot] else cache, table,
                              name, slot, n)
            assert np.array_equal(after, before), (name, slot)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("paged", [True, False],
                         ids=["paged", "contiguous"])
def test_llama_every_row_live_without_a_mask(params, paged, kv_int8):
    """No ``live`` (``decode_step``): every row is visited, ceil(B / T)
    turns, the pad rows landing on the spare — all rows equal the
    reference."""
    cache, table = _llama_cache(kv_int8, paged)
    tbl = None if table is None else jnp.asarray(table)
    every = jnp.ones((B,), bool)
    ref, _, unmasked = _llama_programs(None)
    _, want = ref(params, cache, tbl, every)
    _, got = unmasked(params, cache, tbl, every)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mask", ["one", "tile+1", "scattered+spare"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("paged", [True, False],
                         ids=["paged", "contiguous"])
def test_step_burst_and_verify_agree_on_the_live_rows(params, paged,
                                                      kv_int8, mask):
    """With some slots dead the three drivers still run one scaffold:
    ``decode_step`` (every row visited) gives the burst's first-step
    logits on the burst's live rows, and a verify fed the burst's own
    tokens accepts all of them, exactly."""
    cache, table = _llama_cache(kv_int8, paged)
    tbl = None if table is None else jnp.asarray(table)
    live = _mask(mask)
    active = jnp.asarray(live)
    K = W - 1
    _, logits = jax.jit(lambda p, c: kvcache.decode_step(
        p, c, CFG, table=tbl))(params, cache)
    _, _, toks = jax.jit(lambda p, c: kvcache.decode_burst_staged(
        p, c, jax.random.key(0), active, K + 1, CFG,
        sampling.SamplingParams(), table=tbl))(params, cache)
    toks = np.asarray(toks)                                   # [K + 1, B]
    assert np.array_equal(toks[0, live],
                          np.asarray(logits).argmax(-1)[live])
    _, toks_v, n_commit = jax.jit(
        lambda p, c, d: kvcache.verify_draft_staged(
            p, c, d, jnp.full((B,), K, jnp.int32), active, K, CFG,
            table=tbl))(params, cache, jnp.asarray(toks[:K].T))
    assert np.asarray(n_commit).tolist() == [
        K + 1 if a else 0 for a in live]
    assert np.array_equal(np.asarray(toks_v)[live], toks.T[live])


def test_dead_rows_write_no_cache_row(params):
    """An engine's dead slot (free: its table row all sentinel) leaves
    the pool as it was; only the live slots' blocks change."""
    cache, table = _llama_cache(True, True)
    live = _mask("scattered+spare")
    table = table.copy()
    table[~live] = table.max()
    _, tiled, _ = _llama_programs(None)
    out, logits = tiled(params, cache, jnp.asarray(table),
                        jnp.asarray(live))
    assert np.isfinite(np.asarray(logits)).all()
    owned = np.unique(table[live, :NB])
    owned = owned[owned < table.max()]
    others = np.setdiff1d(np.arange((B - 1) * NB), owned)
    for name in kvcache.row_tensors(cache):
        assert np.array_equal(np.asarray(out[name])[:, others],
                              np.asarray(cache[name])[:, others]), name
        assert not np.array_equal(np.asarray(out[name])[:, owned],
                                  np.asarray(cache[name])[:, owned])


def test_live_tiles_order_and_count():
    """Live slots first in slot order, then the dead, then the pad (the
    last slot); per-slot lengths and table rows ride in that order."""
    live = _mask("scattered+spare")
    pos0 = jnp.arange(B, dtype=jnp.int32) * 2
    table = jnp.asarray(_table())
    n_tiles, order, pos, rows = kvcache._live_tiles(
        jnp.asarray(live), pos0, table)
    order = np.asarray(order)
    assert int(n_tiles) == 1 and order.shape == (3 * T,)
    assert order[:3].tolist() == [1, T, B - 1]
    assert sorted(order[:B].tolist()) == list(range(B))
    assert (order[B:] == B - 1).all()
    assert np.array_equal(np.asarray(pos), order * 2)
    assert np.array_equal(np.asarray(rows), np.asarray(table)[order])
    for n_live, want in ((0, 0), (1, 1), (T, 1), (T + 1, 2), (B, 3)):
        live = np.arange(B) < n_live
        assert int(kvcache._live_tiles(jnp.asarray(live), pos0,
                                       None)[0]) == want
    n_tiles, order, _, rows = kvcache._live_tiles(None, pos0, None)
    assert n_tiles == 3 and rows is None
    assert np.asarray(order)[:B].tolist() == list(range(B))


# ---------------------------------------------------------------------------
# The latent family
# ---------------------------------------------------------------------------

GLM_CFG = dataclasses.replace(glm.CONFIGS["glm-moe-tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def glm_params():
    return glm.init_params(jax.random.key(1), GLM_CFG)


@pytest.fixture(scope="module")
def glm_cache(glm_params):
    cfg, table = GLM_CFG, _table()
    cache = latent.init_paged_cache(cfg, B, (B - 1) * NB, BL)
    rng = np.random.default_rng(13)
    ins = jax.jit(lambda c, toks, n, slot: latent.insert(
        c, {name: r[:, 0] for name, r in latent.prefill_batch(
            glm_params, toks[None], n[None], cfg)[0].items()},
        slot, n, toks[n - 1], table=jnp.asarray(table)))
    for slot, n in enumerate(_lengths()):
        if n == 0:
            continue
        toks = np.zeros((BL * NB,), np.int32)
        toks[:n] = rng.integers(1, cfg.vocab_size, n)
        cache = ins(cache, jnp.asarray(toks), jnp.asarray(n),
                    jnp.asarray(slot, jnp.int32))
    cache["last_token"] = jnp.asarray(
        rng.integers(1, cfg.vocab_size, B).astype(np.int32))
    return cache, table


def _ref_latent_steps(params, cache, cfg, table, span, active, live):
    """``latent._staged_steps`` with every slot's latent rows gathered
    and attended as one batch: (cache', logits [W, B, vocab], experts
    read [W])."""
    n = cache["length"].shape[0]
    M = span if span is not None else kvcache._logical_rows(cache, table)
    L, dt = cfg.n_layers, cache["c_kv"].dtype
    pos0 = cache["length"]
    resident = (jnp.arange(M)[None, :] < pos0[:, None])[:, None, :]
    batch_ix = jnp.arange(n)
    rows_live = None if live is None else live[:, None]
    nb = -(-M // BL)

    def step(carry, s):
        last, sc, sp_ = carry
        x = params["embed"].astype(cfg.dtype)[last[:, None]]
        cos, sin = glm.rope_tables(cfg, (pos0 + s)[:, None])
        staged = (jnp.arange(W) <= s)[None, None, :]

        def layer_fn(c2, layer, i, moe):
            x, sc, sp_ = c2
            q_nope, q_pe, c_kv, k_pe = glm.mla_project(cfg, layer, x, cos,
                                                       sin)
            sc = sc.at[i, batch_ix, s].set(c_kv[:, 0].astype(dt))
            sp_ = sp_.at[i, batch_ix, s].set(k_pe[:, 0].astype(dt))
            rc, rp = (lax.dynamic_index_in_dim(cache[name], i, 0, False)[
                table[:, :nb]].reshape(n, nb * BL, -1)[:, :M]
                for name in ("c_kv", "k_pe"))
            o = glm.latent_attention(
                cfg, layer["wkv_b"], q_nope, q_pe,
                [(rc, rp, resident),
                 (lax.dynamic_index_in_dim(sc, i, 0, False),
                  lax.dynamic_index_in_dim(sp_, i, 0, False), staged)],
                True)
            x, read = glm.out_ffn(cfg, layer, x, o, moe, rows_live)
            return (x, sc, sp_), read

        (x, sc, sp_), reads = glm.scan_layers(cfg, params, (x, sc, sp_),
                                              layer_fn)
        logits = glm.head_logits(cfg, params, x[:, 0])
        last = jnp.where(active, sampling.argmax_tokens(logits), last)
        return (last, sc, sp_), (logits, jnp.sum(reads))

    init = (cache["last_token"],
            jnp.zeros((L, n, W, cfg.kv_lora_rank), dt),
            jnp.zeros((L, n, W, cfg.qk_rope_head_dim), dt))
    (_, sc, sp_), (logits, reads) = lax.scan(step, init, jnp.arange(W))
    blk, off = kvcache._phys(cache, table, batch_ix[:, None],
                             pos0[:, None] + jnp.arange(W)[None, :])
    return latent._append_rows(cache, blk, off, sc, sp_), logits, reads


def _tiled_latent_steps(params, cache, cfg, table, span, active, live):
    def nxt(logits, s, last):
        return jnp.where(active, sampling.argmax_tokens(logits),
                         last), logits
    out, _, logits, reads = latent._staged_steps(
        params, cache, cfg, table, span, W, cache["last_token"], nxt,
        live=live)
    return out, logits, reads


@functools.lru_cache(maxsize=None)
def _latent_programs(span):
    cfg = GLM_CFG
    return (
        jax.jit(lambda p, c, t, a: _ref_latent_steps(p, c, cfg, t, span,
                                                     a, a)),
        jax.jit(lambda p, c, t, a: _tiled_latent_steps(p, c, cfg, t, span,
                                                       a, a)),
        jax.jit(lambda p, c, t, a: _ref_latent_steps(p, c, cfg, t, span,
                                                     a, None)),
        jax.jit(lambda p, c, t, a: _tiled_latent_steps(p, c, cfg, t, span,
                                                       a, None)))


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("span", [None, 32 - BL], ids=["full", "span24"])
def test_latent_tiled_visit_is_the_all_rows_attention(glm_params, glm_cache,
                                                      span, mask):
    """The latent family through the same two helpers: live rows' logits
    and the rows they leave behind equal the all-rows reference's to
    ``LATENT_TOL``, the experts read exactly; dead rows stay finite and
    leave resident rows alone, bit for bit."""
    cache, table = glm_cache
    lengths = _lengths()
    live = _mask(mask) & ((lengths <= span) if span is not None else True)
    ref, tiled, _, _ = _latent_programs(span)
    args = (glm_params, cache, jnp.asarray(table), jnp.asarray(live))
    want_cache, want, want_reads = ref(*args)
    got_cache, got, got_reads = tiled(*args)
    want, got = np.asarray(want), np.asarray(got)
    assert np.isfinite(got).all()
    assert np.abs(got[:, live] - want[:, live]).max(initial=0) < LATENT_TOL
    assert np.asarray(got_reads).tolist() == np.asarray(want_reads).tolist()
    for name in ("c_kv", "k_pe"):
        for slot in range(B - 1):
            n = lengths[slot]
            assert np.array_equal(
                _logical(got_cache, table, name, slot, n),
                _logical(cache, table, name, slot, n)), (name, slot)
            if live[slot]:
                new = [_logical(c, table, name, slot, n + W)[:, n:]
                       for c in (got_cache, want_cache)]
                assert np.abs(new[0] - new[1]).max() < LATENT_TOL


def test_latent_every_row_live_without_a_mask(glm_params, glm_cache):
    cache, table = glm_cache
    every = jnp.ones((B,), bool)
    _, _, ref, unmasked = _latent_programs(None)
    _, want, want_reads = ref(glm_params, cache, jnp.asarray(table), every)
    _, got, got_reads = unmasked(glm_params, cache, jnp.asarray(table),
                                 every)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < LATENT_TOL
    assert np.asarray(got_reads).tolist() == np.asarray(want_reads).tolist()
