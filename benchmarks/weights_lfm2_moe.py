"""Seeded weights of the ``lfm2_moe`` family (gated short-convolution
layers with a grouped-query attention layer every few, routed experts
without a shared one, a tied head), made by the benchmark and handed to
the program.

The scheme is ``benchmarks/weights.py``'s: every tensor is a pure
function of ``(seed, tensor, layer, element index)`` through its integer
hash, so the whole tree is one jitted elementwise program, the reference
regenerates any single layer from the same function, and a new
``--seed`` never recompiles. What this file adds is the family's tensor
names (hash streams 401 and up: none of ``weights._TAGS``,
``weights_glm_moe._TAGS``, ``weights_olmo_hybrid._TAGS`` nor
``weights_afmoe._TAGS``), their shapes, and the tree in
``models/lfm2_moe.init_params``'s layout: ``layers``, a list of
per-layer trees in stack order. A layer's hash stream is keyed by its
index in the WHOLE stack.

Every float leaf is stored in bf16 (the precision the configuration
states); matrices are uniform with std ``fan_in ** -0.5`` (the
convolution's taps: fan-in ``K``), norm scales 1 +- 0.1, and the
router's selection bias (``expert_bias``, a buffer a checkpoint carries
and no gradient trains) uniform in +-``BIAS_RANGE`` as
``weights_glm_moe``'s. ``head`` is the untied head the model does NOT
have: the reference's ``tied_head`` switch, turned off, reads it.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks import weights as W

BIAS_RANGE = 0.1

_TAGS = {name: 401 + i for i, name in enumerate((
    "embed", "lm_head", "final_norm", "ln1", "ln2", "q_norm", "k_norm",
    "w_in", "conv", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up",
    "w_down", "router", "router_bias", "we_gate", "we_up", "we_down"))}


def op_shapes(d, conv: bool) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """The operator's matrices: per-layer shape and number of
    contracted (leading) dims."""
    D, nh, g, hd = d.d_model, d.n_heads, d.n_kv_heads, d.head_dim
    if conv:
        return {"w_in": ((D, 3 * D), 1), "conv": ((d.conv_kernel, D), 1),
                "w_out": ((D, D), 1)}
    return {"wq": ((D, nh, hd), 1), "wk": ((D, g, hd), 1),
            "wv": ((D, g, hd), 1), "wo": ((nh, hd, D), 2)}


def ffn_shapes(d, moe: bool) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """The matrices of the feed-forward. An expert tensor's leading dim
    is the expert, never contracted: its fan-in is dim 1."""
    D, f, e = d.d_model, d.moe_d_ff, d.n_routed_experts
    if not moe:
        return {"w_gate": ((D, d.d_ff), 1), "w_up": ((D, d.d_ff), 1),
                "w_down": ((d.d_ff, D), 1)}
    return {"router": ((D, e), 1), "we_gate": ((e, D, f), 1),
            "we_up": ((e, D, f), 1), "we_down": ((e, f, D), 1)}


def norm_widths(d, conv: bool) -> Dict[str, int]:
    out = {"ln1": d.d_model, "ln2": d.d_model}
    if not conv:
        out.update(q_norm=d.head_dim, k_norm=d.head_dim)
    return out


def _fan_in(name: str, shape, n_contract: int) -> int:
    dims = shape[1:1 + n_contract] if name.startswith("we_") \
        else shape[:n_contract]
    return int(math.prod(dims))


def matrix(key, name: str, layer, shape, n_contract: int,
           dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS[name], layer, shape))
    std = _fan_in(name, shape, n_contract) ** -0.5
    return ((u - 0.5) * (math.sqrt(12.0) * std)).astype(dtype)


def norm_scale(key, name: str, layer, width: int, dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS[name], layer, (width,)))
    return (1.0 + 0.2 * (u - 0.5)).astype(dtype)


def router_bias(key, layer, n_experts: int, dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS["router_bias"], layer, (n_experts,)))
    return ((u - 0.5) * (2.0 * BIAS_RANGE)).astype(dtype)


def embedding(key, d, dtype=jnp.bfloat16):
    u = W._unit(W._bits(key, _TAGS["embed"], 0, (d.vocab_size, d.d_model)))
    return ((u - 0.5) * (math.sqrt(12.0) * 0.02)).astype(dtype)


def head(key, d, dtype=jnp.bfloat16):
    return matrix(key, "lm_head", 0, (d.d_model, d.vocab_size), 1, dtype)


def layer_tensors(key, d, layer, conv: bool, moe: bool,
                  dtype=jnp.bfloat16):
    """One layer's tensors (``layer``: its index in the whole stack)."""
    out = {name: matrix(key, name, layer, shape, nc, dtype)
           for name, (shape, nc)
           in {**op_shapes(d, conv), **ffn_shapes(d, moe)}.items()}
    for name, width in norm_widths(d, conv).items():
        out[name] = norm_scale(key, name, layer, width, dtype)
    if moe:
        out["router_bias"] = router_bias(key, layer, d.n_routed_experts,
                                         dtype)
    return out


def serving_tree(key, d, dtype=jnp.bfloat16):
    """``models/lfm2_moe.init_params``'s layout, every leaf ``dtype``."""
    return {"embed": embedding(key, d, dtype),
            "final_norm": norm_scale(key, "final_norm", 0, d.d_model, dtype),
            "layers": [layer_tensors(key, d, jnp.uint32(i), d.is_conv(i),
                                     i >= d.n_dense_layers, dtype)
                       for i in range(d.n_layers)]}


def build_serving(seed: int, d):
    """The tree on the default device in one jitted call from the seed."""
    return jax.jit(lambda k: serving_tree(k, d))(
        jnp.asarray(W.seed_key(seed)))
