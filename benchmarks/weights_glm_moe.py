"""Seeded weights of the ``glm_moe`` family (MLA + shared and routed
experts), made by the benchmark and handed to the program.

The scheme is ``benchmarks/weights.py``'s: every tensor is a pure
function of ``(seed, tensor, layer, element index)`` through its integer
hash, so the whole tree is one jitted elementwise program, the reference
regenerates any single layer from the same function, and a new
``--seed`` never recompiles. What this file adds is the family's tensor
names (hash streams 101 and up: none of ``weights._TAGS``), their shapes,
and the tree in ``models/glm_moe.init_params``'s layout: two groups,
``dense`` and ``moe``, each stacked on a leading layer axis. A layer's
hash stream is keyed by its index in the WHOLE stack, so a layer's
weights do not depend on how the stack is grouped.

Every float leaf is stored in bf16 (the precision the configuration
states); matrices are uniform with std ``fan_in ** -0.5``, norm scales
1 +- 0.1, and the router's selection bias (``e_score_correction_bias``,
a trained buffer in a checkpoint) uniform in +-``BIAS_RANGE``: against
sigmoid scores that spread over about 0.3-0.7 it changes some of the
top-k choices and leaves most.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks import weights as W

BIAS_RANGE = 0.1

_TAGS = {name: 101 + i for i, name in enumerate((
    "embed", "lm_head", "final_norm", "ln1", "ln2", "q_norm", "kv_norm",
    "wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down",
    "router", "router_bias", "we_gate", "we_up", "we_down", "ws_gate",
    "ws_up", "ws_down"))}

_NORMS = ("ln1", "ln2", "q_norm", "kv_norm")


def attn_shapes(d) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """Per-layer shape and number of contracted (leading) dims."""
    h = d.n_heads
    return {"wq_a": ((d.d_model, d.q_lora_rank), 1),
            "wq_b": ((d.q_lora_rank, h, d.qk_nope + d.qk_rope), 1),
            "wkv_a": ((d.d_model, d.kv_lora_rank + d.qk_rope), 1),
            "wkv_b": ((d.kv_lora_rank, h, d.qk_nope + d.v_head), 1),
            "wo": ((h, d.v_head, d.d_model), 2)}


def ffn_shapes(d, moe: bool) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """The matrices after attention. An expert tensor's leading dim is
    the expert, never contracted: its fan-in is dim 1."""
    D, f, e = d.d_model, d.moe_d_ff, d.n_routed_experts
    if not moe:
        return {"w_gate": ((D, d.d_ff), 1), "w_up": ((D, d.d_ff), 1),
                "w_down": ((d.d_ff, D), 1)}
    fs = d.n_shared_experts * f
    return {"router": ((D, e), 1),
            "we_gate": ((e, D, f), 1), "we_up": ((e, D, f), 1),
            "we_down": ((e, f, D), 1),
            "ws_gate": ((D, fs), 1), "ws_up": ((D, fs), 1),
            "ws_down": ((fs, D), 1)}


def norm_widths(d) -> Dict[str, int]:
    return {"ln1": d.d_model, "ln2": d.d_model, "q_norm": d.q_lora_rank,
            "kv_norm": d.kv_lora_rank}


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


def layer_tensors(key, d, layer, moe: bool, dtype=jnp.bfloat16):
    """One layer's (``layer`` a scalar) or one group's (``layer`` a
    vector: a leading layer axis) tensors."""
    out = {name: matrix(key, name, layer, shape, nc, dtype)
           for name, (shape, nc)
           in {**attn_shapes(d), **ffn_shapes(d, moe)}.items()}
    for name, width in norm_widths(d).items():
        out[name] = norm_scale(key, name, layer, width, dtype)
    if moe:
        out["router_bias"] = router_bias(key, layer, d.n_routed_experts,
                                         dtype)
    return out


def serving_tree(key, d, dtype=jnp.bfloat16):
    """``models/glm_moe.init_params``'s layout, every leaf ``dtype``."""
    n_dense = d.first_k_dense
    tree = {"embed": embedding(key, d, dtype),
            "final_norm": norm_scale(key, "final_norm", 0, d.d_model, dtype),
            "lm_head": head(key, d, dtype)}
    if n_dense:
        tree["dense"] = layer_tensors(
            key, d, jnp.arange(n_dense, dtype=jnp.uint32), False, dtype)
    tree["moe"] = layer_tensors(
        key, d, jnp.arange(n_dense, d.n_layers, dtype=jnp.uint32), True,
        dtype)
    return tree


def build_serving(seed: int, d):
    """The tree on the default device in one jitted call from the seed."""
    return jax.jit(lambda k: serving_tree(k, d))(
        jnp.asarray(W.seed_key(seed)))
