"""Seeded weights of the ``olmo_hybrid`` family (gated-delta-rule linear
layers with a full-attention layer closing each period), made by the
benchmark and handed to the program.

The scheme is ``benchmarks/weights.py``'s: every tensor is a pure
function of ``(seed, tensor, layer, element index)`` through its integer
hash, so the whole tree is one jitted elementwise program, the reference
regenerates any single layer from the same function, and a new
``--seed`` never recompiles. What this file adds is the family's tensor
names (hash streams 201 and up: none of ``weights._TAGS`` nor of
``weights_glm_moe._TAGS``), their shapes, and the tree in
``models/olmo_hybrid.init_params``'s layout: ``lin`` a list with one
entry a place in the period, each stacked ``[periods, ...]``, and
``full`` stacked ``[periods, ...]``.
A layer's hash stream is keyed by its index in the WHOLE stack, so a
layer's weights do not depend on how the stack is grouped.

Every float leaf is stored in bf16 (the precision the configuration
states); matrices are uniform with std ``fan_in ** -0.5`` (the
convolution's fan-in is its width), norm scales 1 +- 0.1. The rule's two
per-head vectors take the Mamba-2 / Gated DeltaNet initialisation, drawn
from the hash: ``A`` uniform in ``A_RANGE`` stored as ``A_log``, ``dt``
log-uniform in ``DT_RANGE`` stored through softplus' inverse as
``dt_bias``. Against ``w_a . x`` of a few units either way that gives
decays from ~0 to ~1 a token.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks import weights as W

A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)

_TAGS = {name: 201 + i for i, name in enumerate((
    "embed", "lm_head", "final_norm", "mixer_norm", "ffn_norm", "w_gate",
    "w_up", "w_down", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "conv",
    "w_a", "w_b", "A_log", "dt_bias", "wg", "o_norm"))}


def ffn_shapes(d) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """Per-layer shape and number of contracted (leading) dims."""
    return {"w_gate": ((d.d_model, d.d_ff), 1),
            "w_up": ((d.d_model, d.d_ff), 1),
            "w_down": ((d.d_ff, d.d_model), 1)}


def mixer_shapes(d, linear: bool) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    D = d.d_model
    if not linear:
        return {"wq": ((D, d.n_heads, d.head_dim), 1),
                "wk": ((D, d.n_kv_heads, d.head_dim), 1),
                "wv": ((D, d.n_kv_heads, d.head_dim), 1),
                "wo": ((d.n_heads, d.head_dim, D), 2)}
    h, dk, dv = d.lin_heads, d.lin_k_dim, d.lin_v_dim
    return {"wq": ((D, h, dk), 1), "wk": ((D, h, dk), 1),
            "wv": ((D, h, dv), 1), "wg": ((D, h, dv), 1),
            "wo": ((h, dv, D), 2), "w_a": ((D, h), 1), "w_b": ((D, h), 1),
            "conv": ((d.conv_kernel, d.conv_channels), 1)}


def norm_widths(d, linear: bool) -> Dict[str, int]:
    out = {"mixer_norm": d.d_model, "ffn_norm": d.d_model}
    if linear:
        out["o_norm"] = d.lin_v_dim
    else:
        out["q_norm"] = d.n_heads * d.head_dim
        out["k_norm"] = d.n_kv_heads * d.head_dim
    return out


def matrix(key, name: str, layer, shape, n_contract: int,
           dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS[name], layer, shape))
    std = int(math.prod(shape[:n_contract])) ** -0.5
    return ((u - 0.5) * (math.sqrt(12.0) * std)).astype(dtype)


def norm_scale(key, name: str, layer, width: int, dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS[name], layer, (width,)))
    return (1.0 + 0.2 * (u - 0.5)).astype(dtype)


def a_log(key, layer, heads: int, dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS["A_log"], layer, (heads,)))
    lo, hi = A_RANGE
    return jnp.log(lo + (hi - lo) * u).astype(dtype)


def dt_bias(key, layer, heads: int, dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS["dt_bias"], layer, (heads,)))
    lo, hi = (math.log(x) for x in DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * u)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def embedding(key, d, dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS["embed"], 0, (d.vocab_size, d.d_model)))
    return ((u - 0.5) * (math.sqrt(12.0) * 0.02)).astype(dtype)


def head(key, d, dtype=jnp.bfloat16):
    return matrix(key, "lm_head", 0, (d.d_model, d.vocab_size), 1, dtype)


def layer_tensors(key, d, layer, linear: bool, dtype=jnp.bfloat16):
    """One layer's (``layer`` a scalar: its index in the whole stack) or
    several layers' (``layer`` a vector: a leading layer axis) tensors."""
    out = {name: matrix(key, name, layer, shape, nc, dtype)
           for name, (shape, nc)
           in {**mixer_shapes(d, linear), **ffn_shapes(d)}.items()}
    for name, width in norm_widths(d, linear).items():
        out[name] = norm_scale(key, name, layer, width, dtype)
    if linear:
        out["A_log"] = a_log(key, layer, d.lin_heads, dtype)
        out["dt_bias"] = dt_bias(key, layer, d.lin_heads, dtype)
    return out


def serving_tree(key, d, dtype=jnp.bfloat16):
    """``models/olmo_hybrid.init_params``'s layout, every leaf ``dtype``."""
    n, periods = d.lin_per_period, d.n_layers // (d.lin_per_period + 1)
    first = jnp.arange(periods, dtype=jnp.uint32) * (n + 1)
    return {"embed": embedding(key, d, dtype),
            "final_norm": norm_scale(key, "final_norm", 0, d.d_model, dtype),
            "lm_head": head(key, d, dtype),
            "lin": [layer_tensors(key, d, first + j, True, dtype)
                    for j in range(n)],
            "full": layer_tensors(key, d, first + n, False, dtype)}


def build_serving(seed: int, d):
    """The tree on the default device in one jitted call from the seed."""
    return jax.jit(lambda k: serving_tree(k, d))(
        jnp.asarray(W.seed_key(seed)))
