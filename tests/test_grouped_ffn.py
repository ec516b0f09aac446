"""The grouped-SwiGLU kernel (``skypilot_tpu/ops/grouped_ffn.py``) in the
Pallas interpreter on the CPU, against a plain loop over experts in
float32 and against the ``lax.ragged_dot`` form it stands in for:

(a) both published width ratios (D : F = 4 : 3 and 2 : 1) at small
    sizes, under even routing, one expert taking every row, experts
    with no row, and group boundaries inside a row tile and off the
    sublane tile — with the tiles the shapes give and with forced small
    ones (several row tiles, several F blocks, groups that straddle);
(b) a whole stack of several layers with ``expert_base`` != 0, the other
    layers' matrices NaN: nothing of them is read;
(c) the visit lists: every row of every group is in exactly one visit,
    an empty group in none;
(d) ``glm_moe.experts_grouped`` takes the kernel where the backend test
    and the shapes allow and ``ragged_dot`` elsewhere, both give the
    loop's result, and the compile's record says which ran.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from skypilot_tpu.models import glm_moe as glm
from skypilot_tpu.observability import flight
from skypilot_tpu.ops import attention as attn_ops
from skypilot_tpu.ops import grouped_ffn as gf

WIDTHS = {"4-to-3": (512, 384), "2-to-1": (256, 128)}
M, E = 512, 8


def _routing(kind: str):
    """Rows an expert, summing to M."""
    if kind == "even":
        return [M // E] * E
    if kind == "one-takes-all":
        return [0, 0, 0, M, 0, 0, 0, 0]
    if kind == "three-empty":
        return [0, 200, 0, 56, 0, 131, 125, 0]
    if kind == "ragged-boundaries":          # none on a multiple of 16
        return [37, 91, 5, 130, 1, 99, 146, 3]
    if kind == "last-row-alone":
        return [M - 1, 0, 0, 0, 0, 0, 0, 1]
    raise KeyError(kind)


ROUTINGS = ["even", "one-takes-all", "three-empty", "ragged-boundaries",
            "last-row-alone"]


def _offsets(sizes):
    return jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)


def _operands(d, f, groups, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    xs = jax.random.normal(k[0], (M, d), dtype)
    w_gate = jax.random.normal(k[1], (groups, d, f), dtype) * d ** -0.5
    w_up = jax.random.normal(k[2], (groups, d, f), dtype) * d ** -0.5
    w_down = jax.random.normal(k[3], (groups, f, d), dtype) * f ** -0.5
    return xs, w_gate.astype(dtype), w_up.astype(dtype), w_down.astype(dtype)


def _loop(xs, w_gate, w_up, w_down, sizes, base=0):
    """One expert after another, float32 at ``highest``."""
    hi = dict(precision=lax.Precision.HIGHEST)
    out, at = [], 0
    for e, n in enumerate(sizes):
        x = xs[at:at + n].astype(jnp.float32)
        at += n
        g = jnp.dot(x, w_gate[base + e].astype(jnp.float32), **hi)
        u = jnp.dot(x, w_up[base + e].astype(jnp.float32), **hi)
        out.append(jnp.dot(jax.nn.silu(g) * u,
                           w_down[base + e].astype(jnp.float32), **hi))
    return jnp.concatenate(out)


def _ragged(xs, w_gate, w_up, w_down, sizes):
    sizes = jnp.asarray(sizes, jnp.int32)
    g = lax.ragged_dot(xs, w_gate, sizes)
    u = lax.ragged_dot(xs, w_up, sizes)
    return lax.ragged_dot(jax.nn.silu(g) * u, w_down, sizes)


# -- (a) ---------------------------------------------------------------------

@pytest.mark.parametrize("tiles", [None, (128, 128)],
                         ids=["tiles-from-shapes", "tiles-128x128"])
@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("widths", list(WIDTHS))
def test_kernel_is_the_loop_over_experts(widths, routing, tiles):
    d, f = WIDTHS[widths]
    sizes = _routing(routing)
    ops = _operands(d, f, E)
    got = gf.grouped_swiglu(*ops, _offsets(sizes), 0, tiles=tiles,
                            interpret=True)
    want = _loop(*ops, sizes)
    assert got.shape == (M, d) and got.dtype == jnp.float32
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(got - _ragged(*ops, sizes)).max()) < 2e-5


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_bf16_operands_float32_sums(widths):
    """bf16 operands as a served model has them: the kernel rounds
    ``silu(g) * u`` once, from float32; it stays as near the float32 loop
    as the ragged form (which rounds ``g`` and ``u`` too) does."""
    d, f = WIDTHS[widths]
    sizes = _routing("ragged-boundaries")
    ops = _operands(d, f, E, jnp.bfloat16)
    got = gf.grouped_swiglu(*ops, _offsets(sizes), 0, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _loop(*ops, sizes)
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    other = float(jnp.abs(_ragged(*ops, sizes).astype(jnp.float32)
                          - want).max())
    assert err < 0.05 and err <= other * 1.5


# -- (b) ---------------------------------------------------------------------

@pytest.mark.parametrize("layer", [0, 1, 2])
def test_only_this_layers_experts_are_read(layer):
    """A stack of three layers, the other two NaN: a read of any of
    their blocks would reach the result."""
    d, f = WIDTHS["2-to-1"]
    sizes = _routing("three-empty")
    xs, *ws = _operands(d, f, 3 * E)
    mine = (jnp.arange(3 * E) // E == layer)[:, None, None]
    ws = [jnp.where(mine, w, jnp.nan) for w in ws]
    base = jnp.asarray(layer * E, jnp.int32)
    got = jax.jit(lambda *a: gf.grouped_swiglu(
        *a, tiles=(128, 128), interpret=True))(xs, *ws, _offsets(sizes), base)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - _loop(xs, *ws, sizes, layer * E)).max()) < 2e-5


# -- (c) ---------------------------------------------------------------------

@pytest.mark.parametrize("tm", [128, 256, 512])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_visits_cover_each_groups_rows_once(routing, tm):
    sizes = _routing(routing)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    group, tile, n = (np.asarray(a) for a in gf.visits(
        _offsets(sizes), tm, M // tm))
    assert group.shape == tile.shape == (M // tm + E - 1,)
    seen = np.zeros((M,), int)
    for g, t in zip(group[:n], tile[:n]):
        lo, hi = max(offs[g], t * tm), min(offs[g + 1], (t + 1) * tm)
        assert hi > lo                      # no visit without a row
        seen[lo:hi] += 1
    assert (seen == 1).all()
    assert sorted(set(group[:n])) == [e for e in range(E) if sizes[e]]
    assert (np.diff(tile[:n]) >= 0).all()   # an output tile's visits adjoin
    straddling = sum(offs[e] // tm != (offs[e + 1] - 1) // tm
                     for e in range(E) if sizes[e])
    assert n <= np.count_nonzero(sizes) + (M // tm - 1) * (straddling > 0)


@pytest.mark.parametrize("m, d, f, want", [
    (2048, 2048, 1536, (1024, 512)),     # a GLM chunk
    (4096, 2048, 1024, (1024, 512)),     # a Trinity chunk
    (8192, 2048, 1536, (1024, 512)),     # GLM's widest wave
    (512, 2048, 1536, (512, 512)),
    (384, 2048, 1536, (128, 512)),
    (192, 2048, 1536, None),             # rows: no whole tile
    (2048, 2000, 1536, None),            # D: no whole lane tile
    (2048, 2048, 1000, None),            # F
    (0, 2048, 1536, None)])
def test_tiles_come_from_the_shapes(m, d, f, want):
    assert gf.tiles_for(m, d, f) == want
    if want:
        tm, tf = want
        assert m % tm == 0 and f % tf == 0 and tf % gf.LANES == 0
        assert gf._vmem_bytes(tm, tf, d, 2) <= gf._VMEM_BUDGET


# -- (d) ---------------------------------------------------------------------

@pytest.fixture
def expert_layer():
    """Layer 1 of a stack of three at the kernel's smallest widths."""
    cfg = dataclasses.replace(
        glm.CONFIGS["glm-moe-tiny"], d_model=128, moe_d_ff=128,
        dtype=jnp.float32)
    k = jax.random.split(jax.random.key(3), 5)
    n = 3 * cfg.n_routed_experts
    layer = {
        "router": jax.random.normal(k[0], (128, cfg.n_routed_experts)) * 0.1,
        "router_bias": jnp.zeros((cfg.n_routed_experts,)),
        "we_gate": jax.random.normal(k[1], (n, 128, 128)) * 128 ** -0.5,
        "we_up": jax.random.normal(k[2], (n, 128, 128)) * 128 ** -0.5,
        "we_down": jax.random.normal(k[3], (n, 128, 128)) * 128 ** -0.5,
        "expert_base": jnp.asarray(cfg.n_routed_experts, jnp.int32)}
    return cfg, layer


def _routed_loop(cfg, h, idx, w, layer):
    base = int(layer["expert_base"])
    hi = dict(precision=lax.Precision.HIGHEST)
    out = jnp.zeros(h.shape, jnp.float32)
    for e in range(cfg.n_routed_experts):
        g = jnp.dot(h, layer["we_gate"][base + e], **hi)
        u = jnp.dot(h, layer["we_up"][base + e], **hi)
        y = jnp.dot(jax.nn.silu(g) * u, layer["we_down"][base + e], **hi)
        out += y * jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)[:, None]
    return out


@pytest.mark.parametrize("rows, on_tpu, form", [
    (128, True, "grouped_swiglu@256x128x128"),
    (128, False, "ragged_dot@256x128x128"),
    (96, True, "ragged_dot@192x128x128")],
    ids=["kernel", "not-a-tpu", "rows-no-whole-tile"])
def test_experts_grouped_takes_the_form_its_call_allows(
        monkeypatch, expert_layer, rows, on_tpu, form):
    cfg, layer = expert_layer
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: on_tpu)
    calls = []
    kernel = gf.grouped_swiglu
    monkeypatch.setattr(gf, "grouped_swiglu", lambda *a, **kw: (
        calls.append(kw["tiles"]), kernel(*a, **kw))[1])
    h = jax.random.normal(jax.random.key(rows), (rows, 128))
    idx, w = glm.route(cfg, h, layer)

    @jax.jit
    def routed(h, idx, w, layer):
        return glm.experts_grouped(cfg, h, idx, w, layer)

    flight.COMPILES.install()
    before = len(flight.COMPILES.records())
    got = routed(h, idx, w, layer)
    assert calls == ([(256, 128)] if form.startswith("grouped") else [])
    want = _routed_loop(cfg, h, idx, w, layer)
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < 2e-5
    mine = [r for r in flight.COMPILES.records()[before:]
            if r["fun_name"] == "jit(routed)"]
    assert [r.get("expert_ffn") for r in mine] == [form]
