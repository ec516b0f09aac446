"""The windowed family's model (``skypilot_tpu/models/afmoe.py``) against
its plain reference (``benchmarks/reference/afmoe.py``), at a tiny size
on the CPU with the benchmark's seeded weights and a FLOAT32 program,
and the published file's arithmetic.

LOGIT_TOL: a float32 program against a float32 reference at ``highest``.
What is left is the order of summation (the program's expert layer sorts
token-choices into groups, the reference loops over experts; the
program's attention is one einsum, the reference's blocks of query
rows), carried through eight layers to logits of standard deviation ~1:
5e-6 observed. ASSUMED_MOVES: each point the configuration's file lists
under ``assumed``, switched off in the reference ALONE, moves the logits
by 2.6-5.9 — a thousand times the tolerance.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks import weights_afmoe as G
from benchmarks.families import afmoe as family
from benchmarks.reference import afmoe as ref
from skypilot_tpu.infer import kvcache, windowed
from skypilot_tpu.models import afmoe, glm_moe, registry
from skypilot_tpu.ops import attention as attn_ops
from skypilot_tpu.ops import grouped_ffn

SEED = 2_900_000_017          # more than 31 bits
LOGIT_TOL = 1e-3
ASSUMED_MOVES = 1.0

# A leading dense layer, then (window, full, window) twice and one
# window layer left over: lead, two scanned periods, a tail.
TYPES = ["sliding_attention", "sliding_attention", "full_attention"]
TINY = {
    "name": "afmoe-test", "family": "afmoe", "vocab_size": 512,
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": (TYPES * 3)[:8], "sliding_window": 32,
    "num_dense_layers": 1, "num_experts": 8, "num_shared_experts": 1,
    "num_experts_per_tok": 2, "route_scale": 2.826, "route_norm": True,
    "score_func": "sigmoid", "mup_enabled": True, "rope_theta": 10000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "n_group": 1,
    "topk_group": 1, "max_position_embeddings": 512,
    "tie_word_embeddings": False,
    "precision": {"weights": "bf16", "activations": "bf16", "kv": "bf16"}}

CONFIG_FILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "configs", "trinity-mini-bf16.json")


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


def _key():
    return jnp.asarray(W.seed_key(SEED))


def _tokens(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).integers(1, 512, shape),
                       jnp.int32)


def test_registry_finds_the_family(cfg):
    assert registry.get_config("afmoe-test") is cfg
    assert registry.model_for(cfg) is afmoe
    assert kvcache.programs_for(cfg) is windowed
    assert windowed.ring_rows(cfg) == 32
    for name in ("llama3-tiny", "glm-moe-tiny", "olmo-hybrid-tiny"):
        other = registry.get_config(name)
        assert kvcache.programs_for(other) is not windowed
        assert kvcache.programs_for(other).ring_rows(other) is None


def test_the_stack_is_run_by_kind(cfg):
    """lead | whole periods | tail, for the tiny stack, the cut and the
    published file; a pattern that is no period is refused."""
    assert afmoe.plan(cfg) == ((0,), 3, 2, (7,))
    assert cfg.win_layers == (0, 1, 3, 4, 6, 7)
    assert cfg.full_layers == (2, 5)
    whole = afmoe.CONFIGS["trinity-mini"]
    assert afmoe.plan(whole) == ((0, 1), 4, 7, (30, 31))
    assert (whole.n_win_layers, whole.n_full_layers) == (24, 8)
    with pytest.raises(ValueError, match="repeat with period"):
        dataclasses.replace(cfg, layer_types=tuple(
            ["sliding_attention"] * 2 + ["full_attention"]
            + ["sliding_attention"] * 5))
    with pytest.raises(ValueError, match="unknown layer type"):
        dataclasses.replace(cfg, layer_types=("linear_attention",) * 8)


def test_seeded_tree_is_the_models_layout(cfg, dims, params):
    abstract = jax.eval_shape(
        lambda: afmoe.init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(params) == jax.tree.structure(abstract)
    assert [a.shape for a in jax.tree.leaves(params)] \
        == [a.shape for a in jax.tree.leaves(abstract)]
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == cfg.num_params() == dims.num_params()
    assert dims.plan() == afmoe.plan(cfg)
    axes = afmoe.param_logical_axes(cfg)
    is_axes = lambda a: isinstance(a, tuple)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, abstract)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, axes,
                                           is_leaf=is_axes))
    for leaf, ax in zip(jax.tree.leaves(abstract),
                        jax.tree.leaves(axes, is_leaf=is_axes)):
        assert len(ax) == leaf.ndim


def test_a_layers_weights_do_not_depend_on_the_grouping(dims, params):
    """Layer 5 (the second period's second place) from the stacked tree
    is the reference's layer 5, generated alone; so are a lead and a
    tail layer."""
    for layer, held in ((5, lambda n: params["period"][1][n][1]),
                        (0, lambda n: params["lead"][0][n]),
                        (7, lambda n: params["tail"][0][n])):
        alone = G.layer_tensors(_key(), dims, np.uint32(layer), layer >= 1)
        for name, t in alone.items():
            assert np.array_equal(np.asarray(held(name)),
                                  np.asarray(t.astype(jnp.float32))), name


def test_published_parameter_count():
    """The published file is 26.124 B parameters; the cut the cell serves
    (the leading dense layer + one whole period of four expert layers)
    4.2415 B = 8.48 GB in bf16; a token holds 2048 B of K/V a layer in
    pool and ring alike, a slot 8 MB of ring a window layer."""
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    whole = afmoe.from_published(dict(config, **config["published"]))
    cut = afmoe.from_published(config)
    assert whole == afmoe.CONFIGS["trinity-mini"]
    assert whole.num_params() == 26_123_974_400 \
        == config["parameters_published_32_layers"]
    assert cut.num_params() == config["parameters"] == 4_241_534_720
    d = family.dims(config)
    assert d.num_params() == cut.num_params()
    assert cut.attn_params() == d.attn_params() == 27_263_232
    assert d.dense_layer_params() == 65_020_160
    assert d.expert_layer_params() == 839_131_520
    assert cut.expert_params() == 805_306_368
    assert d.expert_params() == 6_291_456
    assert cut.layer_types == ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")
    assert afmoe.plan(cut) == ((0,), 4, 1, ())
    assert (cut.n_win_layers, cut.n_full_layers, cut.n_moe_layers) \
        == (4, 1, 4)
    assert d.kv_row_bytes == 2 * cut.kv_width * 2 == 2048
    assert windowed.token_bytes(cut) == 2048
    assert windowed.token_bytes(whole) == 16_384
    assert windowed.slot_state_bytes(cut) == 4 * 2 * 2048 * 1024
    b = config["bytes"]
    assert b["weights_bf16"] == 2 * cut.num_params()
    assert b["window_rings_33_slots_x_4_layers"] \
        == 33 * windowed.slot_state_bytes(cut)
    assert b["kv_pool_2145_blocks_x_512_rows"] \
        == 2145 * 512 * windowed.token_bytes(cut)
    assert b["routed_experts_per_layer"] == 2 * cut.expert_params()


@pytest.mark.parametrize("broken,match", [
    ({"n_group": 2}, "group-limited"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"score_func": "softmax"}, "sigmoid"),
    ({"num_hidden_layers": 9}, "shorter")],
    ids=["groups", "rope-scaling", "softmax-router", "short-types"])
def test_what_is_not_built_is_refused(broken, match):
    with pytest.raises(ValueError, match=match):
        afmoe.from_published(dict(TINY, **broken))


def test_forward_equals_reference_past_the_window(cfg, dims, params):
    """Two sequences of 100 tokens — three windows long — through the
    model's whole-sequence forward and the reference's."""
    tokens = _tokens((2, 100), 1)
    got = jax.jit(lambda p, t: afmoe.forward(p, t, cfg))(params, tokens)
    want = ref.Reference(dims, ref.Precision()).logits(_key(), tokens)
    assert float(jnp.std(want)) > 0.5
    assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_the_published_depth_and_pattern_build_at_tiny_widths():
    """The uncut file's stack — 32 layers, (3 window + 1 global) x 8, two
    leading dense layers: lead (0, 1), seven scanned periods that START
    two places into the pattern, a tail of (window, global) — at tiny
    widths against the reference: the layer indices a scanned place
    hands the caches' layer axes are the stack's."""
    types = ["sliding_attention"] * 3 + ["full_attention"]
    deep = dict(TINY, name="afmoe-deep-test", num_hidden_layers=32,
                layer_types=types * 8, num_dense_layers=2)
    cfg = family.register(deep, dtype=jnp.float32)
    dims = family.dims(deep)
    assert afmoe.plan(cfg) == dims.plan() == ((0, 1), 4, 7, (30, 31))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          G.build_serving(SEED, dims))
    tokens = _tokens((1, 70), 7)
    got, rows = jax.jit(lambda p, t: afmoe.forward_hidden(p, t, cfg))(
        params, tokens)
    assert rows["k"].shape[:3] == (32, 1, 70)
    logits = afmoe.head_logits(cfg, params, got)
    want = ref.Reference(dims, ref.Precision()).logits(_key(), tokens)
    assert float(jnp.abs(logits - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("point", sorted(ref.ASSUMED))
def test_each_assumed_point_switched_off_in_the_reference_fails(
        cfg, dims, params, point):
    """The gate, the per-head q/k norm, rotation in the window layers
    only, the norms after each sub-layer, the embedding's scale: the
    program has each, and a reference without it is another model."""
    tokens = _tokens((1, 80), 2)
    got = jax.jit(lambda p, t: afmoe.forward(p, t, cfg))(params, tokens)
    off = ref.Reference(dims, ref.Precision(), ref.ASSUMED - {point})
    assert float(jnp.abs(got - off.logits(_key(), tokens)).max()) \
        > ASSUMED_MOVES


def test_both_controls_move_logits(dims):
    """The contract's control is below the stated precision; the
    mechanism's own (window layers that see every row) is no precision,
    moves nothing inside the first window and everything after it."""
    p = family.precisions(dict(TINY))
    assert p["control"].below(p["stated"])
    assert p["control_window"].window_all and not p["stated"].window_all
    tokens = _tokens((1, 96), 3)
    want = ref.Reference(dims, p["stated"]).logits(_key(), tokens)
    low = ref.Reference(dims, p["control"]).logits(_key(), tokens)
    assert float(jnp.abs(low - want).max()) > 1e-2
    wide = ref.Reference(dims, p["control_window"]).logits(_key(), tokens)
    assert float(jnp.abs(wide - want)[:, :32].max()) < 1e-4
    assert float(jnp.abs(wide - want)[:, 40:].max()) > 0.5


def test_reference_blocks_need_not_divide_the_length(dims, monkeypatch):
    tokens = _tokens((2, 70), 4)
    whole = ref.Reference(dims, ref.Precision()).logits(_key(), tokens)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "ROW_BLOCK", 24)
    monkeypatch.setattr(ref, "LOGIT_BLOCK", 5)
    blocked = ref.Reference(dims, ref.Precision())
    assert float(jnp.abs(blocked.logits(_key(), tokens) - whole).max()) \
        < 1e-4
    rows, cols = np.asarray([0, 1, 1, 0, 1, 0, 1]), \
        np.asarray([3, 69, 40, 33, 0, 68, 17])
    at = blocked.logits_at(_key(), tokens, rows, cols)
    assert at.shape == (7, 512)
    assert np.abs(at - np.asarray(whole)[rows, cols]).max() < 1e-4


# -- the expert layer IS glm_moe's, at other numbers ------------------------

def _expert_layer(dims, bias, **widths):
    """One expert layer at the PUBLISHED counts — 128 experts, top-8 —
    and small widths."""
    wide = dataclasses.replace(dims, n_routed_experts=128,
                               experts_per_tok=8, **widths)
    layer = {n: a.astype(jnp.float32) for n, a in G.layer_tensors(
        _key(), wide, np.uint32(3), True).items()}
    layer["router_bias"] = jnp.asarray(bias, jnp.float32)
    return wide, layer


def _bias(hot=(), cold=()):
    b = np.zeros((128,), np.float32)
    b[list(hot)] = 9.0
    b[list(cold)] = -9.0
    return b


@pytest.mark.parametrize("form", ["few-rows", "grouped", "grouped-kernel"])
@pytest.mark.parametrize("bias", [
    _bias(hot=[5]), _bias(hot=range(8)), _bias(cold=range(64, 128)),
    _bias()], ids=["one-for-all", "all-to-eight", "half-chosen-by-none",
                   "free"])
def test_expert_layer_at_128_top_8_under_skewed_routing(cfg, dims, bias,
                                                        form, monkeypatch):
    """The forms of ``glm_moe``'s expert layer at 128 experts, top-8,
    against the reference's loop over every expert: one expert chosen by
    every row, eight chosen by all, half chosen by none. Every
    token-choice is in the result: nothing dropped. (``grouped-kernel``:
    the Pallas form a TPU takes for whole tiles, interpreted — 128 rows
    at widths of 128.)"""
    rows, widths = 96, {}
    if form == "grouped-kernel":
        rows, widths = 128, dict(d_model=128, moe_d_ff=128)
        monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    wide, layer = _expert_layer(dims, bias, **widths)
    wcfg = dataclasses.replace(cfg, n_routed_experts=128, experts_per_tok=8,
                               **widths)
    h = jax.random.normal(jax.random.key(8), (rows, wcfg.d_model))
    idx, w = glm_moe.route(wcfg, h, layer)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=128)
    assert counts.sum() == rows * 8
    if bias[5] == 9 and bias[0] == 0:
        assert counts[5] == rows
    if bias[0] == 9:
        assert (counts[:8] == rows).all() and counts[8:].sum() == 0
    if bias[64] == -9:
        assert counts[64:].sum() == 0
    if form == "few-rows":
        got, n = glm_moe.experts_few_rows(wcfg, h, idx, w, layer)
        assert int(n) == np.count_nonzero(counts)
    else:
        assert (grouped_ffn.tiles_for(rows * 8, wcfg.d_model, wcfg.moe_d_ff)
                is not None) == (form == "grouped-kernel")
        got = glm_moe.experts_grouped(wcfg, h, idx, w, layer)
    shared = glm_moe._swiglu(h, layer["ws_gate"], layer["ws_up"],
                             layer["ws_down"], jnp.float32)
    want = ref.expert_ffn(h, layer, wide, ref.Precision())
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got + shared - want).max()) < 1e-4


def test_router_is_glm_moes_at_this_configs_numbers(cfg, dims):
    """``route_norm`` and ``route_scale`` reach the shared router under
    the names it reads; the weights of a row sum to the scale."""
    _, layer = _expert_layer(dims, _bias())
    wcfg = dataclasses.replace(cfg, n_routed_experts=128, experts_per_tok=8)
    h = jax.random.normal(jax.random.key(9), (16, cfg.d_model))
    idx, w = glm_moe.route(wcfg, h, layer)
    assert idx.shape == w.shape == (16, 8)
    assert np.allclose(np.asarray(w.sum(-1)), 2.826, atol=1e-4)
    chosen, picked = ref.router(h, layer, dataclasses.replace(
        dims, n_routed_experts=128, experts_per_tok=8))
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(
        np.asarray(chosen)))
    assert np.allclose(np.sort(np.asarray(w)), np.sort(np.asarray(picked)),
                       atol=1e-5)
