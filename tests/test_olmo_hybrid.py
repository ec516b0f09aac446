"""The hybrid family's model (``skypilot_tpu/models/olmo_hybrid.py``)
against its plain reference (``benchmarks/reference/olmo_hybrid.py``), at
a tiny size on the CPU with the benchmark's seeded weights and a FLOAT32
program, and the published file's arithmetic.

LOGIT_TOL: a float32 program against a float32 reference at ``highest``.
What is left is the order of summation — the chunked rule against the
reference's token-by-token scan (1e-5 on outputs of ~0.7, the op's own
test), carried through eight layers to logits of standard deviation ~1:
3e-4 observed.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks import weights_olmo_hybrid as G
from benchmarks.families import olmo_hybrid as family
from benchmarks.reference import olmo_hybrid as ref
from skypilot_tpu.infer import hybrid, kvcache
from skypilot_tpu.models import olmo_hybrid as oh
from skypilot_tpu.models import registry

SEED = 2_900_000_017          # more than 31 bits
LOGIT_TOL = 1e-3
# A state of the padded call against the same row alone: the same
# tokens through sub-chunks cut at the same places, so what differs is
# float32 summation order upstream (a wave of three rows against one),
# carried through the layers before: 1e-4 observed on states of ~0.1-1.
STATE_TOL = 5e-4

TYPES = ["linear_attention"] * 3 + ["full_attention"]
TINY = {
    "name": "olmo-hybrid-test", "family": "olmo_hybrid", "vocab_size": 512,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": TYPES * 4, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "rope_parameters": {"rope_theta": None},
    "precision": {"weights": "bf16", "activations": "bf16", "kv": "bf16"}}

CONFIG_FILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "configs", "olmo-hybrid-7b-bf16.json")


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
    assert registry.get_config("olmo-hybrid-test") is cfg
    assert registry.model_for(cfg) is oh
    assert kvcache.programs_for(cfg) is hybrid
    assert registry.model_for(registry.get_config("llama3-tiny")).__name__ \
        .endswith("llama")
    assert kvcache.programs_for(registry.get_config("llama3-tiny")) \
        is kvcache
    # A subclass of the Llama config another model file defines is llama's.
    assert kvcache.programs_for(registry.get_config("moe-tiny")) is kvcache \
        if "moe-tiny" in registry.serving_configs() else True


def test_seeded_tree_is_the_models_layout(cfg, dims, params):
    abstract = jax.eval_shape(lambda: oh.init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(params) == jax.tree.structure(abstract)
    assert [a.shape for a in jax.tree.leaves(params)] \
        == [a.shape for a in jax.tree.leaves(abstract)]
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == cfg.num_params() == dims.num_params()
    axes = oh.param_logical_axes(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, abstract)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    for leaf, ax in zip(jax.tree.leaves(abstract), jax.tree.leaves(
            axes, is_leaf=lambda a: isinstance(a, tuple))):
        assert len(ax) == leaf.ndim


def test_a_layers_weights_do_not_depend_on_the_grouping(dims, params):
    """Layer 5 (period 1, its second linear layer) from the stacked tree
    is the reference's layer 5, generated alone."""
    alone = G.layer_tensors(_key(), dims, np.uint32(5), True)
    for name, t in alone.items():
        assert np.array_equal(np.asarray(params["lin"][1][name][1]),
                              np.asarray(t.astype(jnp.float32))), name
    full = G.layer_tensors(_key(), dims, np.uint32(7), False)
    for name, t in full.items():
        assert np.array_equal(np.asarray(params["full"][name][1]),
                              np.asarray(t.astype(jnp.float32))), name


def test_published_parameter_count():
    """The published file is 7.431 B parameters; the cut the cell serves
    (16 of 32 layers: four whole periods) 4.101 B = 8.20 GB in bf16; a
    token holds 61 440 B of K/V in its 4 full layers (65 536 B as pooled:
    32 heads a row), a slot 2.21 MB of state + 69 KB of tails a linear
    layer."""
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    whole = oh.from_published(dict(config, num_hidden_layers=32))
    cut = oh.from_published(config)
    assert whole == oh.CONFIGS["olmo-hybrid-7b"]
    assert whole.num_params() == 7_430_870_688
    assert cut.num_params() == config["parameters"] == 4_100_788_944
    assert family.dims(config).num_params() == cut.num_params()
    assert round(whole.lin_mixer_params() / 1e6, 1) == 88.8
    assert round(whole.full_mixer_params() / 1e6, 1) == 59.0
    assert (cut.n_lin_layers, cut.n_full_layers, cut.period) == (12, 4, 4)
    assert cut.rope_theta is None and cut.conv_channels == 11_520
    assert 2 * cut.n_full_layers * cut.n_kv_heads * cut.head_dim * 2 \
        == 61_440
    assert hybrid.pool_heads(cut) == 32
    assert hybrid.token_bytes(cut) == 65_536
    assert hybrid.slot_state_bytes(cut) == 12 * (2_211_840 + 69_120)
    b = config["bytes"]
    assert b["weights_bf16"] == 2 * cut.num_params()
    assert b["recurrent_state_33_slots_x_12_layers"] \
        == 33 * hybrid.slot_state_bytes(cut)
    assert b["kv_per_token_as_pooled_32_heads"] == hybrid.token_bytes(cut)


@pytest.mark.parametrize("broken,match", [
    ({"layer_types": ["full_attention"] * 8}, "repeat"),
    ({"layer_types": (TYPES * 2)[:7] + ["linear_attention"]}, "repeat"),
    ({"layer_types": ["linear_attention"] * 8}, "no full_attention"),
    ({"linear_num_value_heads": 8}, "grouped"),
    ({"attention_bias": True}, "attention_bias")],
    ids=["no-linear", "broken-period", "no-full", "grouped", "bias"])
def test_what_is_not_built_is_refused(broken, match):
    with pytest.raises(ValueError, match=match):
        oh.from_published(dict(TINY, **broken))


def test_forward_equals_reference(cfg, dims, params):
    """Logits at every position of two sequences whose length (150) is
    no multiple of the 64-token sub-chunk."""
    tokens = _tokens((2, 150), seed=1)
    got = np.asarray(jax.jit(lambda p, t: oh.forward(p, t, cfg))(
        params, tokens))
    want = np.asarray(ref.Reference(dims, ref.Precision()).logits(
        _key(), tokens))
    assert want.std() > 0.5
    assert np.abs(got - want).max() < LOGIT_TOL


def test_rotation_is_taken_from_the_file(dims, params):
    """``rope_theta`` null: no rotation. A value: program and reference
    both rotate, and still agree — a correction is one value."""
    tokens = _tokens((1, 48), seed=2)
    rot = dict(TINY, rope_parameters={"rope_theta": 10000.0})
    cfg_r = oh.from_published(rot, dtype=jnp.float32)
    dims_r = family.dims(rot)
    got = np.asarray(oh.forward(params, tokens, cfg_r))
    want = np.asarray(ref.Reference(dims_r, ref.Precision()).logits(
        _key(), tokens))
    assert np.abs(got - want).max() < LOGIT_TOL
    plain = np.asarray(ref.Reference(dims, ref.Precision()).logits(
        _key(), tokens))
    assert np.abs(want - plain).max() > 0.05


def test_both_controls_are_below_the_stated_precision_and_move_logits(dims):
    prec = family.precisions(TINY)
    assert set(prec) == {"stated", "control", "control_state"}
    tokens = _tokens((1, 96), seed=3)
    stated = np.asarray(ref.Reference(dims, prec["stated"]).logits(
        _key(), tokens))
    for label in ("control", "control_state"):
        assert prec[label].below(prec["stated"])
        low = np.asarray(ref.Reference(dims, prec[label]).logits(
            _key(), tokens))
        assert np.abs(low - stated).max() > 10 * LOGIT_TOL, label


def test_reference_blocks_need_not_divide_the_length(dims, monkeypatch):
    tokens = _tokens((1, 80), seed=4)
    whole = np.asarray(ref.Reference(dims, ref.Precision()).logits(
        _key(), tokens))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    monkeypatch.setattr(ref, "ROW_BLOCK", 48)
    blocked = np.asarray(ref.Reference(dims, ref.Precision()).logits(
        _key(), tokens))
    assert np.abs(whole - blocked).max() < 1e-4


def test_mixer_state_after_padding_is_the_state_of_the_true_length(cfg,
                                                                   params):
    """``forward_hidden`` over right-padded rows: each row's state and
    convolution tail are those of ITS last real token."""
    lens = [48, 21, 2]
    tokens = np.zeros((3, 48), np.int32)
    rows = [np.asarray(_tokens((n,), seed=10 + n)) for n in lens]
    for i, r in enumerate(rows):
        tokens[i, :len(r)] = r
    _, padded = oh.forward_hidden(params, jnp.asarray(tokens), cfg,
                                  jnp.asarray(lens))
    for i, r in enumerate(rows):
        _, alone = oh.forward_hidden(params, jnp.asarray(r)[None], cfg)
        assert np.abs(np.asarray(padded["state"][:, i])
                      - np.asarray(alone["state"][:, 0])).max() < STATE_TOL
        assert np.abs(np.asarray(padded["conv"][:, i])
                      - np.asarray(alone["conv"][:, 0])).max() < STATE_TOL
        # ... and not the state some pad tokens later: one more token
        # moves it by far more than the tolerance.
        if len(r) > 2:
            _, short = oh.forward_hidden(params, jnp.asarray(r[:-1])[None],
                                         cfg)
            assert np.abs(np.asarray(short["state"][:, 0]) - np.asarray(
                alone["state"][:, 0])).max() > 20 * STATE_TOL
    assert float(jnp.abs(padded["state"]).max()) > 0.01


def test_init_params_decays_span_the_range():
    cfg = dataclasses.replace(oh.CONFIGS["olmo-hybrid-tiny"],
                              lin_heads=64, d_model=64)
    p = oh.init_params(jax.random.key(1), cfg)
    a = np.exp(np.asarray(p["lin"][0]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(p["lin"][0]["dt_bias"])))
    assert 1.0 <= a.min() < 3 and 12 < a.max() <= 16.0
    assert 1e-3 <= dt.min() < 3e-3 and 3e-2 < dt.max() <= 1e-1 + 1e-6
