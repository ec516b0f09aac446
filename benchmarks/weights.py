"""Seeded weights, made by the benchmark and handed to the program.

Every tensor is a pure function of ``(seed, name, layer, element index)``
through an integer hash (murmur3's finaliser), so

* the whole stacked tree is ONE jitted elementwise program per tree — no
  ``jax.random`` draw (each of those compiles for 11-22 s at 7B shapes),
  no temporaries beyond the finished leaves, nothing on the host;
* the reference regenerates any single layer from the same function and
  never holds more than one layer of a 7B model;
* the seed is a traced argument: a new ``--seed`` never recompiles.

Two storage kinds, the two the configurations state:

``int8``   ``{"w": int8, "s": float32 per output channel}`` exactly as
           ``kvcache.qeinsum`` / ``qlora.dequant_weight`` read them.
           Values are uniform in [-127, 127]; scales vary by channel
           (0.75-1.25 of ``fan_in**-0.5 / 73.9``) so a scale applied to
           the wrong axis shows.
``float``  the compute dtype (bf16), uniform with std ``fan_in**-0.5``.

The tree layouts are the program's input formats (``kvcache.
random_quantized_params`` and ``llama.init_params``); the values are the
benchmark's.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32
# std of an integer uniform on [-127, 127] with the -128 bucket folded
# into -127 (what ``_int8`` below produces).
_INT8_STD = 73.9

# One hash stream per tensor name.
_TAGS = {"embed": 1, "lm_head": 2, "final_norm": 3, "ln1": 4, "ln2": 5,
         "wq": 6, "wk": 7, "wv": 8, "wo": 9, "w_gate": 10, "w_up": 11,
         "w_down": 12, "lora_a": 13, "lora_b": 14}
# Tags of the per-channel scale of an int8 tensor, and of the k-th LoRA
# target, are offsets from these.
_SCALE_OFFSET = 32
_LORA_TARGETS = ("wq", "wk", "wv", "wo")


def block_shapes(cfg) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """Per-layer shape and number of contracted (leading) dims of the
    seven block matrices. ``cfg`` needs d_model, n_heads, n_kv_heads,
    head_dim, d_ff."""
    d, ff = cfg.d_model, cfg.d_ff
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {"wq": ((d, nh, hd), 1), "wk": ((d, nkv, hd), 1),
            "wv": ((d, nkv, hd), 1), "wo": ((nh, hd, d), 2),
            "w_gate": ((d, ff), 1), "w_up": ((d, ff), 1),
            "w_down": ((ff, d), 1)}


def seed_key(seed: int) -> np.ndarray:
    """``--seed`` (any whole number up to a little over 2**31, so more
    than 32 signed bits) as two uint32 words."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def _mix(x):
    x = x ^ (x >> 16)
    x = x * _U32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * _U32(0xC2B2AE35)
    return x ^ (x >> 16)


def _bits(key, tag: int, layer, shape) -> jax.Array:
    """uint32 hash per element. ``layer``: a traced scalar or an [L]
    vector (then the result gains a leading L axis)."""
    n = int(np.prod(shape))
    if n >= 2 ** 32:
        raise ValueError(f"tensor of {n} elements overflows the index")
    layer = jnp.asarray(layer, _U32)
    k = _mix(key[0] ^ _mix(key[1] + _U32(0x9E3779B9))
             ^ _mix(_U32(tag) * _U32(0x27D4EB2F)
                    + layer * _U32(0x165667B1) + _U32(1)))
    idx = jnp.arange(n, dtype=_U32).reshape(shape)
    if layer.ndim:
        k = k.reshape((-1,) + (1,) * len(shape))
        idx = idx[None]
    return _mix(idx * _U32(0x9E3779B1) + k)


def _unit(bits) -> jax.Array:
    """uint32 -> float32 uniform in [0, 1)."""
    return (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))


def _int8(bits) -> jax.Array:
    v = (bits & _U32(0xFF)).astype(jnp.int32) - 128
    return jnp.maximum(v, -127).astype(jnp.int8)


def _fan_in(shape, n_contract: int) -> int:
    return int(np.prod(shape[:n_contract]))


def int8_tensor(key, name: str, layer, shape, n_contract: int):
    """``{"w", "s"}``: uniform int8 and its per-output-channel scale."""
    tag = _TAGS[name]
    out_shape = tuple(shape[n_contract:])
    base = _fan_in(shape, n_contract) ** -0.5 / _INT8_STD
    u = _unit(_bits(key, tag + _SCALE_OFFSET, layer, out_shape))
    return {"w": _int8(_bits(key, tag, layer, shape)),
            "s": base * (0.75 + 0.5 * u)}


def float_tensor(key, name: str, layer, shape, std: float, dtype):
    """Uniform with the given std, rounded to ``dtype`` (the values the
    reference sees are the rounded ones, upcast)."""
    u = _unit(_bits(key, _TAGS[name], layer, shape))
    return ((u - 0.5) * (math.sqrt(12.0) * std)).astype(dtype)


def norm_scale(key, name: str, layer, d: int):
    u = _unit(_bits(key, _TAGS[name], layer, (d,)))
    return 1.0 + 0.2 * (u - 0.5)


def embedding(key, cfg, dtype=jnp.bfloat16):
    return float_tensor(key, "embed", 0, (cfg.vocab_size, cfg.d_model),
                        0.02, dtype)


def head(key, cfg, kind: str, dtype=jnp.bfloat16):
    shape = (cfg.d_model, cfg.vocab_size)
    if kind == "int8":
        return int8_tensor(key, "lm_head", 0, shape, 1)
    return float_tensor(key, "lm_head", 0, shape, cfg.d_model ** -0.5,
                        dtype)


def block_tensor(key, cfg, name: str, layer, kind: str,
                 dtype=jnp.bfloat16):
    shape, n_contract = block_shapes(cfg)[name]
    if kind == "int8":
        return int8_tensor(key, name, layer, shape, n_contract)
    return float_tensor(key, name, layer, shape,
                        _fan_in(shape, n_contract) ** -0.5, dtype)


def _lora_dims(cfg, target: str):
    shape, n_contract = block_shapes(cfg)[target]
    return tuple(shape[:n_contract]), tuple(shape[n_contract:])


def lora_pair(key, cfg, target: str, layer, rank: int):
    """A ~ std 1/sqrt(d_in) as LoRA initialises it; B small and NOT zero
    (std 0.01), a fine-tune a few hundred steps in: with B = 0 the first
    gradient of every A is exactly zero and the check of the first
    gradient would see half the leaves say nothing."""
    in_dims, out_dims = _lora_dims(cfg, target)
    t = 2 * _LORA_TARGETS.index(target)
    d_in = int(np.prod(in_dims))
    ua = _unit(_bits(key, _TAGS["lora_a"] + 64 + t, layer,
                     in_dims + (rank,)))
    ub = _unit(_bits(key, _TAGS["lora_b"] + 64 + t, layer,
                     (rank,) + out_dims))
    return {"a": (ua - 0.5) * (math.sqrt(12.0) * d_in ** -0.5),
            "b": (ub - 0.5) * (math.sqrt(12.0) * 0.01)}


# ---------------------------------------------------------------------------
# Whole stacked trees, in the program's input layouts
# ---------------------------------------------------------------------------

def _layers(cfg):
    return jnp.arange(cfg.n_layers, dtype=_U32)


def int8_serving_tree(key, cfg):
    """``(params, qweights)`` in ``kvcache.random_quantized_params``'s
    layout: slim float tree (embedding + norms) and stacked int8 blocks +
    head."""
    L = _layers(cfg)
    blocks = {name: block_tensor(key, cfg, name, L, "int8")
              for name in block_shapes(cfg)}
    params = {
        "embed": embedding(key, cfg),
        "final_norm": norm_scale(key, "final_norm", 0, cfg.d_model),
        "blocks": {"ln1": norm_scale(key, "ln1", L, cfg.d_model),
                   "ln2": norm_scale(key, "ln2", L, cfg.d_model)},
    }
    return params, {"blocks": blocks, "head": head(key, cfg, "int8")}


def float_serving_tree(key, cfg, dtype=jnp.bfloat16):
    """``llama.init_params``'s layout, every leaf in the compute dtype."""
    L = _layers(cfg)
    blocks = {name: block_tensor(key, cfg, name, L, "float", dtype)
              for name in block_shapes(cfg)}
    blocks["ln1"] = norm_scale(key, "ln1", L, cfg.d_model).astype(dtype)
    blocks["ln2"] = norm_scale(key, "ln2", L, cfg.d_model).astype(dtype)
    return {"embed": embedding(key, cfg, dtype),
            "blocks": blocks,
            "final_norm": norm_scale(key, "final_norm", 0,
                                     cfg.d_model).astype(dtype),
            "lm_head": head(key, cfg, "float", dtype)}


def lora_tree(key, cfg, rank: int):
    """``lora.init_lora_params``'s layout, float32."""
    L = _layers(cfg)
    return {t: lora_pair(key, cfg, t, L, rank) for t in _LORA_TARGETS}


def build_serving(seed: int, cfg, kind: str):
    """The tree on the default device in one jitted call from the seed."""
    key = jnp.asarray(seed_key(seed))
    if kind == "int8":
        params, qweights = jax.jit(
            lambda k: int8_serving_tree(k, cfg))(key)
        return params, qweights
    return jax.jit(lambda k: float_serving_tree(k, cfg))(key), None


def build_lora(seed: int, cfg, rank: int):
    return jax.jit(lambda k: lora_tree(k, cfg, rank))(
        jnp.asarray(seed_key(seed)))
