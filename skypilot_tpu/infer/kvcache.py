"""KV-cache prefill / decode steps for the Llama family.

TPU-first design notes
----------------------
* Everything is **static-shape**: the decode cache is a pre-allocated
  ``[L, slots, max_len, kv_heads, head_dim]`` buffer; per-slot lengths
  mask attention instead of resizing anything. One compiled prefill per
  prompt bucket, one compiled decode step, reused for the whole serving
  lifetime — no retracing, ever.
* Prefill is the plain causal forward (right-padded to a bucket length)
  that additionally emits each layer's post-rope K/V rows; padding rows
  never poison the cache because causal attention keeps positions
  < true_len independent of them, and decode masks rows >= length.
* Decode processes *all slots together*: [slots, 1] tokens through the
  stacked-layer ``lax.scan`` with the cache read-only inside it, ONE
  scatter per cache tensor after the loop to append every layer's K/V
  rows (:func:`_staged_steps`). This is the JetStream-style generate
  step — MXU-batched across requests. What costs time row for row —
  reading resident rows and attending them — is done for the LIVE
  slots only, a fixed tile of slots a turn (:func:`_visit_tiles`): the
  shapes stay static, the trip count is the data.
* The decoder layer is written ONCE (:func:`_layer_qkv`, the program's
  own attention, :func:`_layer_out_ffn`), with one head (:func:`_head`)
  and one row writer (:func:`_write_rows`); a program differs from the
  next only in which rows attention reads and which it leaves behind.
* Sharding composes with serving TP: cache kv-head dim maps to ``tp``,
  slot dim to (``dp``, ``fsdp``) via the standard rule table.
* Two storage layouts share ONE implementation of every program:
  the original contiguous ``[L, slots, max_len, ...]`` cache, and the
  **paged** block pool (``[L, n_blocks, block_len, ...]`` + a per-slot
  block table — see the "Paged block-pool layout" section) that decouples
  slot count from worst-case length. Each program takes an optional
  ``table``; reads/writes route through it, so paged-vs-contiguous
  outputs are bit-identical by construction.

Reference parity: the reference serves LLMs only through external
engines (reference: llm/vllm/serve.yaml, examples/tpu/v6e/README.md
JetStream section). This module is the in-tree TPU-native engine core.
"""

from __future__ import annotations

import importlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.infer import sampling as sampling_mod
from skypilot_tpu.models import llama, registry
from skypilot_tpu.observability import attribution
from skypilot_tpu.ops import paged_attention as paged_attn_ops
from skypilot_tpu.parallel import ring_attention as ra

Cache = Dict[str, jax.Array]

# The tensors of a cache that hold one ROW per token, in either layout:
# dims (layer, slot | block, row-in-slot | row-in-block, ...). Two
# kinds of cache exist and a cache says which it is by the tensors it
# holds: per-head keys and values ("k", "v", int8 with their scales),
# or the latent rows of an MLA model ("c_kv", "k_pe": one compressed
# row and one shared rope key per token, no heads axis —
# infer/latent.py). Whatever moves rows or blocks without looking
# inside them (copy-on-write, handoff, addressing) goes through
# :func:`row_tensors`, so it serves both.
ROW_TENSORS = ("k", "v", "k_scale", "v_scale", "c_kv", "k_pe")


def row_tensors(cache: Cache) -> Tuple[str, ...]:
    return tuple(n for n in ROW_TENSORS if n in cache)


def is_latent(cache: Cache) -> bool:
    return "c_kv" in cache


def _rows_per_unit(cache: Cache) -> int:
    """Dim 2 of the row tensors: max_len (contiguous) or block_len."""
    return cache[row_tensors(cache)[0]].shape[2]


# What the hidden spare slot's column of a decode burst's ``toks``
# carries instead of that slot's token, which is nobody's: nothing in
# this family; ``infer/latent.py`` puts ``experts_read`` there.
SPARE_COLUMN = None


def programs_for(cfg):
    """The module holding the serve programs of ``cfg``'s family, as the
    family's model module names it (``SERVE_PROGRAMS``): this module for
    the GQA decoders, ``infer/latent.py`` for the latent-cache family,
    ``infer/hybrid.py`` for the decoders that keep a recurrent state per
    slot beside their K/V rows, ``infer/windowed.py`` for those whose
    sliding-window layers keep a ring of rows per slot,
    ``infer/shortconv.py`` for those whose short-convolution layers keep
    a tail per slot. The engine's jitted entry points call
    through it and are otherwise one code path. What the engine uses of
    a family module, with this module's signatures:

    * ``init_paged_cache`` — the block pool and the per-slot
      ``length`` / ``last_token`` (and whatever else a slot holds);
    * ``prefill_batch`` + ``insert`` (``_admit_wave``), ``prefill_chunk``
      (``_prefill_chunk``), ``decode_step`` (``_decode``),
      ``decode_burst_staged`` (``_decode_burst``) and
      ``verify_draft_staged`` (``_verify``; a family without the program
      keeps the name and raises);
    * ``SPARE_COLUMN`` — what the spare slot's column of a burst's
      ``toks`` carries (``None``: nothing);
    * ``FAMILY`` and ``UNSUPPORTED`` — the family's name in a refusal
      and the engine options it cannot serve, each with its reason
      (``engine.refuse_options``);
    * ``SLOT_STATE`` — the cache tensors that hold one FIXED-size entry
      per slot instead of rows (``()``: none). A family that has them
      takes ``live`` in ``decode_step`` too, is told ``carried`` /
      ``state_rows`` in the dispatch annotations, and cannot share
      blocks (a block's rows are not all a sharer needs);
    * ``DECODE_READS_BLOCKS_HELD`` — whether a decode program's K/V
      read is bounded by residency (each live slot's blocks up to the
      rows it holds, not every tile slot's up to the span rung): the
      dispatch annotations then say ``kv_blocks``;
    * ``ring_rows(cfg)`` — the rows a sliding-window layer keeps per
      slot (``None``: the family has no such layer): the decode
      dispatch annotations then say ``window_rows``, the prefill ones
      ``window_keys``;
    * ``experts_per_step(cfg)`` — the routed experts a decode step
      would read if its rows chose them all, expert layers x experts
      (absent where the family has no expert layer): beside a burst's
      ``experts_read`` (``SPARE_COLUMN``) the decode annotations then
      say ``experts_held``, ``k`` times that;
    * ``token_bytes(cfg, cache)``, ``hbm_rows(cache, params)`` and
      ``roofline_dims(cfg)`` — the cache bytes a token holds, the HBM
      ledger's rows for what the family keeps on the device, and what
      the analytical cost model takes from the config.

    Blocks move (allocation, copy-on-write, handoff, addressing) through
    this module's :func:`row_tensors` helpers whatever the family."""
    return importlib.import_module(registry.model_for(cfg).SERVE_PROGRAMS)


# This family's answers (see :func:`programs_for`).
FAMILY = "GQA decoder"
UNSUPPORTED: Dict[str, str] = {}
SLOT_STATE: Tuple[str, ...] = ()
DECODE_READS_BLOCKS_HELD = False


def ring_rows(cfg) -> None:
    """No window layers: no ring (see ``kvcache.programs_for``)."""
    return None


def token_bytes(cfg: llama.LlamaConfig, cache: Cache) -> int:
    """Cache bytes a token holds, all layers, from the cache's ACTUAL
    dtypes (int8 rows count their float32-accounted scales)."""
    per_layer = 2 * cfg.n_kv_heads * cfg.head_dim * cache["k"].dtype.itemsize
    if "k_scale" in cache:
        per_layer += 2 * cfg.n_kv_heads * 4
    return cfg.n_layers * per_layer


def hbm_rows(cache: Cache, params) -> Dict[str, int]:
    """The HBM ledger's rows for what this family keeps resident."""
    return {"kv_pool": attribution.tensor_bytes(cache)}


def roofline_dims(cfg: llama.LlamaConfig) -> Dict[str, int]:
    """What the engine's analytical cost model takes from the config."""
    return {"param_count": cfg.num_params(), "n_layers": cfg.n_layers,
            "n_heads": cfg.n_heads, "head_dim": cfg.head_dim}


def init_cache(cfg: llama.LlamaConfig, n_slots: int,
               max_len: int, kv_int8: bool = False) -> Cache:
    """Pre-allocated decode state for ``n_slots`` concurrent requests.

    ``kv_int8``: store K/V rows as int8 with a per-(row, kv-head) absmax
    scale. Decode is HBM-bandwidth-bound on cache reads, so halving the
    bytes raises decode throughput AND doubles the requests that fit —
    the standard TPU serving trade (XLA fuses the dequant multiply into
    the attention einsums).
    """
    L, G, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    cache: Cache = {
        # Tokens generated + prompt rows present, per slot (0 = free).
        "length": jnp.zeros((n_slots,), jnp.int32),
        "last_token": jnp.zeros((n_slots,), jnp.int32),
    }
    if kv_int8:
        cache["k"] = jnp.zeros((L, n_slots, max_len, G, hd), jnp.int8)
        cache["v"] = jnp.zeros((L, n_slots, max_len, G, hd), jnp.int8)
        # Scales: [..., G, max_len] (row dim last) in BF16. Both choices
        # fight TPU tile padding: XLA lays the G=8 dim minormost
        # whatever the logical order, and an f32 minormost dim of 8
        # pads 8->128 — a 16x expansion that was 2x730 MB of HBM at 32
        # slots (per the XLA OOM allocation dump). bf16 tiles (16,128)
        # cap the waste at 2x, and scale precision is irrelevant at
        # absmax/127 granularity.
        cache["k_scale"] = jnp.zeros((L, n_slots, G, max_len),
                                     jnp.bfloat16)
        cache["v_scale"] = jnp.zeros((L, n_slots, G, max_len),
                                     jnp.bfloat16)
    else:
        cache["k"] = jnp.zeros((L, n_slots, max_len, G, hd), cfg.dtype)
        cache["v"] = jnp.zeros((L, n_slots, max_len, G, hd), cfg.dtype)
    return cache


# ---------------------------------------------------------------------------
# int8 weights (w8a8 decode)
# ---------------------------------------------------------------------------
# Decode reads EVERY weight once per token: int8 storage halves that HBM
# traffic and the s8xs8->s32 MXU path doubles matmul throughput
# (measured ~1.9x on a [16,2048]x[2048,8192] v5e matmul). Weights are
# quantized per OUTPUT channel once at engine init; activations per
# token inside the step; the products rescale by (ax * aw) / 127^2.
# Prefill runs the same w8a8 path, which is what lets the engine drop
# the fp weight copies entirely (slim_params) — the memory halving.

def quantize_weight(w: jax.Array, contract_ndim: int
                    ) -> Dict[str, jax.Array]:
    """Per-output-channel absmax int8. ``contract_ndim``: how many
    LEADING dims (after any layer dim handled by the caller) are
    contracted in the consuming einsum; the rest are output channels."""
    axes = tuple(range(contract_ndim))
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axes)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127,
                 127).astype(jnp.int8)
    return {"w": q, "s": scale}


# How many leading dims (after the layer dim) each block weight
# contracts in its consuming einsum. SINGLE definition: quantization
# and the sharding axes derive from the same map, so a new quantized
# weight can never get int8 data without sharding axes (it would
# silently replicate under --tp).
QUANT_CONTRACT = {"wq": 1, "wk": 1, "wv": 1, "wo": 2,
                  "w_gate": 1, "w_up": 1, "w_down": 1}


def quantize_block_weights(params: llama.Params) -> Dict[str, Dict]:
    """int8 copies of the stacked per-layer matmul weights (norms and
    the embedding table stay fp)."""
    blocks = params["blocks"]

    def per_layer(name, w):
        nd = QUANT_CONTRACT[name]
        # vmap over the leading layer dim.
        return jax.vmap(lambda x: quantize_weight(x, nd))(w)

    return {name: per_layer(name, blocks[name])
            for name in QUANT_CONTRACT}


def quantize_head(params: llama.Params,
                  cfg: llama.LlamaConfig) -> Dict[str, jax.Array]:
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    return quantize_weight(head, 1)


def qweight_logical_axes(cfg: llama.LlamaConfig) -> Dict[str, Dict]:
    """Logical axes for the ``{"blocks": ..., "head": ...}`` qweights
    tree (same names the fp params use, so one TP rule set shards
    both): ``w`` mirrors its fp tensor; ``s`` (per-output-channel
    scales) keeps ("layer",) + the NON-contracted output axes."""
    full = llama.param_logical_axes(cfg)["blocks"]
    blocks = {}
    for name, nd in QUANT_CONTRACT.items():
        axes = full[name]            # ("layer", <contracted...>, <out...>)
        blocks[name] = {"w": axes, "s": ("layer",) + axes[1 + nd:]}
    return {"blocks": blocks,
            "head": {"w": ("embed", "vocab"), "s": ("vocab",)}}


def _act_quant(x: jax.Array, n_contract: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Per-token int8: absmax over the TRAILING n_contract dims."""
    axes = tuple(range(x.ndim - n_contract, x.ndim))
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / scale[(...,) + (None,) * n_contract]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def qeinsum(eq: str, x: jax.Array, qw: Dict[str, jax.Array],
            n_contract: int, out_dtype) -> jax.Array:
    """w8a8 einsum: quantize x per token, s8xs8->s32 MXU matmul,
    rescale. ``n_contract``: contracted dims at x's tail (= qw's
    head)."""
    xq, sx = _act_quant(x, n_contract)
    acc = jnp.einsum(eq, xq, qw["w"],
                     preferred_element_type=jnp.int32).astype(jnp.float32)
    n_out = qw["s"].ndim
    scale = (sx[(...,) + (None,) * n_out]
             * qw["s"][(None,) * (acc.ndim - n_out) + (...,)])
    return (acc * scale).astype(out_dtype)


def proj(eq: str, x: jax.Array, layer: Dict, qlayer, name: str,
         n_contract: int, dtype) -> jax.Array:
    """One weight matmul, int8 (w8a8) when ``qlayer`` provides the
    weight, fp otherwise. Shared by prefill and decode so a fully
    quantized engine needs NO fp copy of the seven block matrices —
    that memory halving is what fits an 8B-class model on a 16 GB
    chip."""
    if qlayer is not None and name in qlayer:
        return qeinsum(eq, x, qlayer[name], n_contract, dtype)
    return jnp.einsum(eq, x, layer[name].astype(dtype))


# ---------------------------------------------------------------------------
# Multi-LoRA adapter gathers (infer/adapters.py)
# ---------------------------------------------------------------------------
# Per-slot LoRA: every program below takes an optional ``lora`` pool
# (per target {"a": [L, N, d_in..., r], "b": [L, N, r, d_out...]},
# layer axis leading so slices ride the decoder scan as xs) plus an
# ``aid`` vector of per-row adapter-pool slots. The delta is the
# factored pair x @ A[aid] @ B[aid] (alpha/rank already folded into B
# at load) added to the base projection — ONE gather per layer per
# target, rank static, so requests for different fine-tunes batch in
# one dispatch and adapter identity is pure device DATA (never program
# identity). Pool slot 0 is all zeros: base-model rows add an
# exact-zero delta, which is what makes an adapter-capable engine's
# base output bit-identical to an adapterless engine's.


def _layer_parts(layer_q, wq8: bool, has_lora: bool):
    """Unpack one scan step's xs slice into (layer, qlayer, llayer) —
    the single decoder between fp, w8a8 and adapter-pool variants."""
    if wq8 and has_lora:
        layer, qlayer, llayer = layer_q
    elif wq8:
        (layer, qlayer), llayer = layer_q, None
    elif has_lora:
        layer, llayer = layer_q
        qlayer = None
    else:
        layer, qlayer, llayer = layer_q, None, None
    return layer, qlayer, llayer


def _scan_xs(params, qweights, lora):
    """The decoder scan's xs: blocks (+ int8 blocks) (+ the adapter
    pool). A lora-less call builds the identical structure it always
    did — the adapterless trace is unchanged."""
    if qweights is not None and lora is not None:
        return (params["blocks"], qweights["blocks"], lora)
    if qweights is not None:
        return (params["blocks"], qweights["blocks"])
    if lora is not None:
        return (params["blocks"], lora)
    return params["blocks"]


def _lora_in_delta(h, ab, aid):
    """Per-slot delta for an embed->heads/kv target. h: [B, S, D];
    ab: ONE layer's pool slice {"a": [N, D, r], "b": [N, r, H, hd]};
    aid: [B] int32 pool slots (one gather per layer per target)."""
    a = ab["a"][aid].astype(h.dtype)               # [B, D, r]
    b = ab["b"][aid].astype(h.dtype)               # [B, r, H, hd]
    u = jnp.einsum("bsd,bdr->bsr", h, a)
    return jnp.einsum("bsr,brhk->bshk", u, b)


def _lora_out_delta(o, ab, aid):
    """Per-slot delta for the wo target. o (pre-projection attention
    output): [B, S, H, hd]; a: [N, H, hd, r]; b: [N, r, D]."""
    a = ab["a"][aid].astype(o.dtype)               # [B, H, hd, r]
    b = ab["b"][aid].astype(o.dtype)               # [B, r, D]
    u = jnp.einsum("bshk,bhkr->bsr", o, a)
    return jnp.einsum("bsr,brd->bsd", u, b)


def slim_params(params: llama.Params) -> llama.Params:
    """Drop the fp copies of quantized weights: blocks keep only the
    norms; lm_head is covered by the quantized head."""
    return {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "blocks": {"ln1": params["blocks"]["ln1"],
                   "ln2": params["blocks"]["ln2"]},
    }


def random_quantized_params(cfg: llama.LlamaConfig, seed: int = 0):
    """(slim fp params, qweights) with random int8 weights, built
    WITHOUT ever materializing the fp tree — how an 8B-class benchmark
    fits a 16 GB chip (the fp init alone would be 32 GB). Every leaf is
    generated ON DEVICE (jax.random): a host-side numpy tree would
    first be built in host memory and then ship ~8 GB through PCIe."""
    d, ff, v, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def q(shape, out_ndim):
        w = jax.random.randint(next(keys), shape, -127, 128,
                               dtype=jnp.int8)
        sshape = ((shape[0],) + tuple(shape[-out_ndim:])
                  if len(shape) > out_ndim + 1
                  else tuple(shape[-out_ndim:]))
        return {"w": w, "s": jnp.full(sshape, 0.02 / 127.0, jnp.float32)}

    blocks = {
        "wq": q((L, d, nh, hd), 2),
        "wk": q((L, d, nkv, hd), 2),
        "wv": q((L, d, nkv, hd), 2),
        "wo": q((L, nh, hd, d), 1),
        "w_gate": q((L, d, ff), 1),
        "w_up": q((L, d, ff), 1),
        "w_down": q((L, ff, d), 1),
    }
    head = {"w": jax.random.randint(next(keys), (d, v), -127, 128,
                                    dtype=jnp.int8),
            "s": jnp.full((v,), 0.02 / 127.0, jnp.float32)}
    params = {
        "embed": (jax.random.normal(next(keys), (v, d), jnp.bfloat16)
                  * 0.02),
        "final_norm": jnp.ones((d,), jnp.float32),
        "blocks": {"ln1": jnp.ones((L, d), jnp.float32),
                   "ln2": jnp.ones((L, d), jnp.float32)},
    }
    return params, {"blocks": blocks, "head": head}


def quantize_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., G, hd] -> (int8 values, [..., G] absmax scales)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_rows(q: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_logical_axes(cache: Cache | None = None) -> Dict[str, Tuple]:
    """Axes for the given cache's keys (quantization is derived from the
    cache itself, like insert/decode_step do; None = fp layout). The
    paged layout reuses the same names: its block dim takes "batch" and
    its block_len dim takes "seq_cache", so one TP rule set shards both
    layouts (kv_heads is dim 3 either way)."""
    axes = {
        "k": ("layer", "batch", "seq_cache", "kv_heads", "head_dim"),
        "v": ("layer", "batch", "seq_cache", "kv_heads", "head_dim"),
        "length": ("batch",),
        "last_token": ("batch",),
    }
    if cache is not None and "k_scale" in cache:
        axes["k_scale"] = ("layer", "batch", "kv_heads", "seq_cache")
        axes["v_scale"] = ("layer", "batch", "kv_heads", "seq_cache")
    if cache is not None and is_latent(cache):
        del axes["k"], axes["v"]
        axes["c_kv"] = ("layer", "batch", "seq_cache", None)
        axes["k_pe"] = ("layer", "batch", "seq_cache", None)
    return axes


# ---------------------------------------------------------------------------
# Paged block-pool layout
# ---------------------------------------------------------------------------
# The contiguous layout above charges every slot max_len rows of HBM
# rent regardless of actual length. The paged layout allocates
# fixed-size BLOCKS from one shared pool ([L, n_blocks, block_len, ...]
# per tensor) and gives each slot a BLOCK TABLE mapping logical block
# j -> physical block id. Shapes stay fully static — attention gathers
# a slot's blocks in logical order (same row ordering, same masked
# score set as the contiguous read, so the softmax sums are identical)
# and writes scatter through the table. The table carries one EXTRA
# column pinned to the sentinel (== n_blocks): any logical row past the
# slot's allocation maps there, and JAX scatter DROPS out-of-bounds
# updates — the same garbage-write safety net the contiguous layout
# gets from row indices >= max_len (gathers CLAMP, but clamped garbage
# rows are masked by `length` exactly as contiguous garbage rows are).
#
# Host-side bookkeeping (which blocks a slot owns, ref counts for
# prefix sharing) lives in BlockAllocator + the engine; a stored prefix
# is just ref-counted shared blocks mapped into a new slot's table —
# no row copies. Copy-on-write happens only when a shared block is
# PARTIAL (block_len does not divide the stored prefix length): the
# writer gets a fresh copy (`copy_block`) before its first write.


def init_paged_cache(cfg: llama.LlamaConfig, n_slots: int,
                     n_blocks: int, block_len: int,
                     kv_int8: bool = False) -> Cache:
    """Block-pool decode state: ``n_blocks`` physical blocks of
    ``block_len`` rows shared by ``n_slots`` slots. Per-slot
    length/last_token bookkeeping is identical to the contiguous
    layout; only the K/V storage is pooled."""
    L, G, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    cache: Cache = {
        "length": jnp.zeros((n_slots,), jnp.int32),
        "last_token": jnp.zeros((n_slots,), jnp.int32),
    }
    if kv_int8:
        cache["k"] = jnp.zeros((L, n_blocks, block_len, G, hd), jnp.int8)
        cache["v"] = jnp.zeros((L, n_blocks, block_len, G, hd), jnp.int8)
        # Same minormost-row-dim trade as init_cache's scales.
        cache["k_scale"] = jnp.zeros((L, n_blocks, G, block_len),
                                     jnp.bfloat16)
        cache["v_scale"] = jnp.zeros((L, n_blocks, G, block_len),
                                     jnp.bfloat16)
    else:
        cache["k"] = jnp.zeros((L, n_blocks, block_len, G, hd),
                               cfg.dtype)
        cache["v"] = jnp.zeros((L, n_blocks, block_len, G, hd),
                               cfg.dtype)
    return cache


class BlockAllocator:
    """Host-side ref-counted allocator over the paged block pool.

    Pure bookkeeping — no device state. Invariants (property-tested in
    tests/test_paged_kv.py): a block is FREE xor referenced; alloc
    hands out ref==1 blocks in ascending id order (deterministic);
    incref requires a live block; decref of a free block raises
    (double-free guard); a block is writable only at ref==1 — the
    engine must COW before writing a shared block.
    """

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self.reset()

    def reset(self) -> None:
        # Popped from the end: blocks hand out in ascending id order.
        self._free = list(range(self.n_blocks - 1, -1, -1))
        self._ref = [0] * self.n_blocks

    @property
    def used(self) -> int:
        return self.n_blocks - len(self._free)

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("KV block pool exhausted")
        b = self._free.pop()
        self._ref[b] = 1
        return b

    def incref(self, block: int) -> None:
        if self._ref[block] <= 0:
            raise RuntimeError(f"incref of free block {block}")
        self._ref[block] += 1

    def decref(self, block: int) -> None:
        if self._ref[block] <= 0:
            raise RuntimeError(f"double free of block {block}")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            self._free.append(block)

    def ref(self, block: int) -> int:
        return self._ref[block]

    def writable(self, block: int) -> bool:
        """Safe to scatter into: exactly one owner."""
        return self._ref[block] == 1


def copy_block(cache: Cache, src: jax.Array, dst: jax.Array) -> Cache:
    """Copy-on-write: duplicate one physical block's rows (and scales)
    into a freshly allocated block. All ``block_len`` rows copy (static
    shape); rows past the shared prefix are garbage in BOTH blocks and
    stay unreadable until the new owner overwrites them."""
    out = dict(cache)
    for name in row_tensors(cache):
        rows = lax.dynamic_index_in_dim(cache[name], src, 1,
                                        keepdims=False)
        out[name] = lax.dynamic_update_index_in_dim(cache[name], rows,
                                                    dst, 1)
    return out


def _logical_rows(cache: Cache, table) -> int:
    """Rows a slot's attention spans: max_len (contiguous) or
    blocks_per_slot * block_len (paged; the table's last column is the
    sentinel and holds no rows)."""
    if table is None:
        return _rows_per_unit(cache)
    return (table.shape[1] - 1) * _rows_per_unit(cache)


def _phys(cache: Cache, table, slots, idx):
    """(slot, logical row) -> scatter coordinates on the cache's two
    row-addressing dims: identity for contiguous, block-table lookup
    for paged. Overflow logical rows index the table's sentinel column
    (gathers clamp into it), resolving to block id == n_blocks, where
    scatter drops the write."""
    if table is None:
        return slots, idx
    bl = _rows_per_unit(cache)
    return table[slots, idx // bl], idx % bl


# Slots one turn of a staged step's attention reads and attends: a
# decode program visits its LIVE slots, this many a turn, and the
# number of turns is data (:func:`_live_tiles`, :func:`_visit_tiles`).
# Reading resident rows and attending them cost time row for row, and a
# serving round keeps a few of its slots live. One value for both cache
# families (``infer/latent.py`` visits by the same two helpers), timed
# on the chip at 4 / 8 / 16 (``PERF.md`` section 6): a turn's fixed cost
# is about one row's (GQA rows at span 640) to three rows' (latent rows
# at span 4352), so 4 is ahead of 8 wherever 1-4 or 9-12 slots are live
# and at most a turn's cost behind elsewhere.
TILE = 4


def _live_tiles(live, pos0, table):
    """Once a program: what :func:`_visit_tiles` walks — how many tiles
    of :data:`TILE` slots hold the live rows, the slots in visiting
    order, and per slot in that order its resident length and its table
    row (``None`` when contiguous), so a turn SLICES what a gather by
    slot id would fetch again every layer. ``live`` [B] bool — the rows
    whose tokens anyone keeps; ``None``: every row. The order is
    live-first (stable), padded to whole tiles with the LAST slot — an
    engine's hidden spare, whose table row is all sentinel: its gathered
    rows are garbage its length never admits, and the pad rows only
    rewrite that slot's own attention output. ``n_tiles`` is an int32
    scalar, or a Python int without ``live``."""
    n_slots = pos0.shape[0]
    pad = -n_slots % TILE
    if live is None:
        order = jnp.arange(n_slots, dtype=jnp.int32)
        n_tiles = (n_slots + pad) // TILE
    else:
        order = jnp.argsort(~live, stable=True).astype(jnp.int32)
        n_tiles = (jnp.sum(live, dtype=jnp.int32) + TILE - 1) // TILE
    order = jnp.concatenate(
        [order, jnp.full((pad,), n_slots - 1, jnp.int32)])
    return (n_tiles, order, pos0[order],
            None if table is None else table[order])


def _visit_tiles(tiles, n_slots: int, attend, row_shape):
    """A layer's attention output ``[B, *row_shape]`` float32 by visiting
    the live tiles: a turn takes :data:`TILE` slots — ``attend(ids,
    pos0, table_rows)``, each of the three for those slots, gives their
    rows ``[TILE, *row_shape]`` — and they land by slot id. Rows of no
    visited tile keep zeros (dead: they flow through the rest of the
    layer and their tokens are discarded). The trip count is the data —
    there is no second form for full or for empty programs."""
    n_tiles, *per_slot = tiles

    def turn(t, out):
        ids, pos, rows = (
            a if a is None else lax.dynamic_slice_in_dim(a, t * TILE, TILE)
            for a in per_slot)
        return out.at[ids].set(attend(ids, pos, rows))

    return lax.fori_loop(
        0, n_tiles, turn, jnp.zeros((n_slots,) + row_shape, jnp.float32))


def _resident_mask(pos0, n_rows: int):
    """[B, n_rows] bool: the rows resident when the program began
    (``< pos0`` [B]) — a constant of the program."""
    return jnp.arange(n_rows)[None, :] < pos0[:, None]


@jax.named_scope("kv_gather")
def _gather_kv_layer(cache: Cache, i, table_rows, ids, span=None):
    """Layer ``i``'s K/V (+ scales when int8) of the slots ``ids`` [T]:
    k/v [T, M, G, hd], scales [T, G, M]. Paged (``table_rows`` [T, nb +
    1]: those slots' rows of the block table): each slot's blocks in
    logical order; contiguous (``None``): the same read with slots as
    blocks (one of max_len rows each) — identical row ordering, so the
    attention sums match between the layouts bit for bit.

    The blocks are gathered STRAIGHT out of the pool seen as ``[L *
    blocks, block_len, ...]`` at ``i * blocks + id``. Slicing layer
    ``i`` out first made the compiler copy the layer's whole pool (K
    and V, 43 MB each at the 7B serving shapes) before the gather,
    every layer of every step. (A sentinel id gathers the next layer's
    first block, or clamps: garbage the caller's mask never admits.)

    ``span`` (static int): only the first ``span`` logical rows — the
    span-bucketed read. The rows kept are a PREFIX of the full view in
    the same order, and every row the caller's validity mask admits
    lies below the span by construction (the engine picks the bucket
    covering the longest active slot), so the masked score set — and
    the attention output — is bit-identical to the full read while the
    materialized K/V transient shrinks from max_len to span rows per
    slot. Paged: ceil(span / block_len) whole blocks of the table
    prefix, then cut to the span (a sub-block span reads its rows of
    one block)."""
    n_units, rows = cache["k"].shape[1:3]
    if table_rows is None:
        units = ids[:, None]                         # [T, 1]
    else:
        nb = table_rows.shape[1] - 1         # sentinel column: no rows
        if span is not None:
            nb = -(-span // rows)            # block-table prefix
        units = table_rows[:, :nb]                   # [T, nb]
    if span is not None:
        rows = min(rows, span)
    T, nb = units.shape
    at = i * n_units + units
    M = nb * rows if span is None else span

    def flat(name):
        pool = cache[name]
        return pool.reshape((-1,) + pool.shape[2:])

    def read_rows(name):                 # pool [L, units, rows, G, hd]
        got = flat(name)[at, :rows]
        return got.reshape(T, nb * rows, *got.shape[3:])[:, :M]

    def read_scales(name):               # pool [L, units, G, rows]
        got = flat(name)[at, :, :rows].transpose(0, 2, 1, 3)
        return got.reshape(T, got.shape[1], nb * rows)[..., :M]

    if "k_scale" in cache:
        return (read_rows("k"), read_rows("v"),
                read_scales("k_scale"), read_scales("v_scale"))
    return read_rows("k"), read_rows("v"), None, None


@jax.named_scope("kv_gather")
def _paged_attn_stats(cache: Cache, i, table, qh, lengths, span):
    """Big-cache attention stats via the Pallas paged-attention kernel
    (``SKYTPU_KV_KERNEL=1``): per (slot, kv-head) the kernel walks the
    slot's block table and streams its physical blocks through an
    online-softmax accumulator — the ``[slots, span, G, hd]`` logical
    view the gather path materializes per layer simply never exists.

    qh: [B, G, R, hd] query rows; lengths: [B] the per-slot validity
    bound (the same ``col < length`` rule the gather path's mask
    encodes); ``span`` (static) bounds the block sweep to the span
    rung's table prefix, exactly like the gather path. Returns the
    unnormalized stats ``(acc, m, l)`` for :func:`_merge_attn_parts`.
    """
    bl = cache["k"].shape[2]
    M = span if span is not None else (table.shape[1] - 1) * bl
    return paged_attn_ops.paged_attention(
        qh, cache["k"], cache["v"],
        cache.get("k_scale"), cache.get("v_scale"),
        table, lengths, i, span_blocks=-(-M // bl))


def _merge_attn_parts(acc, m, l, ss):
    """Two-block online-softmax combine: fold the staged-columns block
    into the kernel's big-cache stats. ``ss``: masked staged scores
    [..., W] (masked columns at -1e30). Returns (alpha, w_s, l_tot)
    where the final output is ``(acc * alpha + w_s @ v_staged) /
    l_tot`` — the same score set the one-shot softmax over
    [cache | staged] sees, summed in online order (greedy parity, not
    bit parity, vs the gather oracle). A slot with NO valid cache rows
    reports m == -1e30 and ``alpha`` underflows to exactly 0 — its
    (garbage) acc/l never contribute."""
    m_tot = jnp.maximum(m, jnp.max(ss, axis=-1))
    alpha = jnp.exp(m - m_tot)
    w_s = jnp.exp(ss - m_tot[..., None])
    l_tot = jnp.maximum(l * alpha + jnp.sum(w_s, axis=-1), 1e-30)
    return alpha, w_s, l_tot


# ---------------------------------------------------------------------------
# The decoder layer, the head and the row writer every program shares
# ---------------------------------------------------------------------------
# A program below is: embed -> scan over layers of (front half, ITS OWN
# attention, back half) -> head -> one write of the new rows.
# Quantization, projection, adapter and FFN changes land here once.

@jax.named_scope("qkv_proj")
def _layer_qkv(cfg, layer, qlayer, x, cos, sin, llayer=None, aid=None):
    """Layer front half: norm + q/k/v projections + rope. x: [B, S, D].
    ``llayer``/``aid``: one layer's adapter-pool slice + per-row pool
    ids — the per-row (A, B) gather adds its delta before rope, exactly
    as a merged weight would."""
    h = llama.rms_norm(x, layer["ln1"], cfg.norm_eps)
    q = proj("bsd,dhk->bshk", h, layer, qlayer, "wq", 1, cfg.dtype)
    k = proj("bsd,dhk->bshk", h, layer, qlayer, "wk", 1, cfg.dtype)
    v = proj("bsd,dhk->bshk", h, layer, qlayer, "wv", 1, cfg.dtype)
    if llayer is not None:
        q = q + _lora_in_delta(h, llayer["wq"], aid)
        k = k + _lora_in_delta(h, llayer["wk"], aid)
        v = v + _lora_in_delta(h, llayer["wv"], aid)
    q = llama.apply_rope(q, cos, sin)
    k = llama.apply_rope(k, cos, sin)
    return q, k, v


def _ffn(cfg: llama.LlamaConfig, h: jax.Array, layer: Dict,
         qlayer=None) -> jax.Array:
    """Post-norm FFN, h: [B, S, D] — the ONE place that asks which
    model this is: the sparse expert FFN when the config is an MoE (aux
    loss is irrelevant at inference and dropped; its experts stay
    float), else dense SwiGLU, w8a8 for the matrices ``qlayer`` holds.

    MoE + right-padded prefill is safe: capacity assignment is
    position-ordered, so padding rows (after true_len) can never evict
    a real token from an expert's buffer; decode steps see S=1 where
    top-k choices always fit.
    """
    if hasattr(cfg, "n_experts"):
        from skypilot_tpu.models import moe
        out, _ = moe.moe_ffn(cfg, h, layer)
        return out
    g = proj("bsd,df->bsf", h, layer, qlayer, "w_gate", 1, cfg.dtype)
    u = proj("bsd,df->bsf", h, layer, qlayer, "w_up", 1, cfg.dtype)
    return proj("bsf,fd->bsd", jax.nn.silu(g) * u, layer, qlayer,
                "w_down", 1, cfg.dtype)


@jax.named_scope("out_ffn")
def _layer_out_ffn(cfg, layer, qlayer, x, o, llayer=None, aid=None):
    """Layer back half: output projection (+ adapter delta) + residual
    + norm + FFN. x: [B, S, D]; o: the attention output for the same
    rows, ``[B, S, H, hd]`` — or still grouped by kv head and in the
    accumulator's fp32, as the paged programs leave it: it takes the
    projection's shape and dtype here, under this scope."""
    o = o.reshape(*x.shape[:2], cfg.n_heads, cfg.head_dim).astype(cfg.dtype)
    y = proj("bshk,hkd->bsd", o, layer, qlayer, "wo", 2, cfg.dtype)
    if llayer is not None:
        y = y + _lora_out_delta(o, llayer["wo"], aid)
    x = x + y
    h = llama.rms_norm(x, layer["ln2"], cfg.norm_eps)
    return x + _ffn(cfg, h, layer, qlayer)


@jax.named_scope("lm_head")
def _head(cfg, params, qweights, x, pick=None):
    """Final norm + LM head (fp or w8a8) -> fp32 logits for the rows it
    is asked for: ``x`` [..., D] is normed whole (as each program always
    has; norming only the picked rows is a program change), ``pick``
    (optional) takes the rows that get logits, and the product runs at
    exactly their shape — [W, D] (a wave), [D] (a chunk), or a decode
    step's [B, 1, D], whose step axis is dropped after the product."""
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if pick is not None:
        x = pick(x)
    if qweights is not None:
        logits = qeinsum("...d,dv->...v", x, qweights["head"], 1,
                         jnp.float32)
    else:
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("...d,dv->...v", x, head.astype(cfg.dtype))
    if logits.ndim == 3:
        logits = logits[:, 0]
    return logits.astype(jnp.float32)


def _write_rows(cache: Cache, table, slots, idx, rows) -> Cache:
    """Every layer's new rows land at logical ``(slots, idx)`` (arrays
    that broadcast to one shape ``I``) — through the block table when
    paged — by ONE scatter per cache tensor, after the layer loop (the
    stacks are megabyte-scale next to the gigabyte-scale cache, and the
    donated cache aliases through). ``rows``: ``(k, v)`` as
    ``[L, *I, G, hd]`` in the cache's dtype, then the two scales
    ``[L, *I, G]`` when the cache holds them. Scatter, not
    ``dynamic_update_slice``: a window may poke past max_len, and
    scatter DROPS out-of-bounds indices instead of clamping the whole
    window backwards over valid rows (paged: the overflow, and a dead
    or spare slot's rows, map to the sentinel block and drop the same
    way). The caller names the ``kv_write`` scope and stamps
    length / last_token."""
    blk, off = _phys(cache, table, slots, idx)
    out = dict(cache)
    out["k"] = cache["k"].at[:, blk, off].set(rows[0])
    out["v"] = cache["v"].at[:, blk, off].set(rows[1])
    if "k_scale" in cache:
        # Non-adjacent advanced indices lead with the broadcast dims:
        # the update is [*I, L, G].
        out["k_scale"] = cache["k_scale"].at[:, blk, :, off].set(
            jnp.moveaxis(rows[2], 0, -2))
        out["v_scale"] = cache["v_scale"].at[:, blk, :, off].set(
            jnp.moveaxis(rows[3], 0, -2))
    return out


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(params: llama.Params, tokens: jax.Array, true_len: jax.Array,
            cfg: llama.LlamaConfig, qweights=None) -> Tuple[Cache, jax.Array]:
    """Causal forward over ONE right-padded prompt ([S_bucket] int32);
    see :func:`prefill_batch` for the batched core. Returns
    ({"k","v"}: [L, S_bucket, G, hd], logits [vocab] fp32)."""
    prefix, logits = prefill_batch(params, tokens[None], true_len[None],
                                   cfg, qweights=qweights)
    return {"k": prefix["k"][:, 0], "v": prefix["v"][:, 0]}, logits[0]


def prefill_batch(params: llama.Params, tokens: jax.Array,
                  true_lens: jax.Array, cfg: llama.LlamaConfig,
                  qweights=None, lora=None, aid=None, mesh=None,
                  heads_axis=None) -> Tuple[Cache, jax.Array]:
    """Causal forward over a WAVE of right-padded prompts.

    tokens: [W, S_bucket] int32, true_lens: [W] int32.
    Returns ({"k","v"}: [L, W, S_bucket, G, hd] post-rope rows, logits
    at each request's last real position [W, vocab] fp32). One batched
    program per wave: the W requests share every weight read and the
    matmuls run at W x S rows — admission cost per request drops vs a
    scan of W single-request prefills. With ``qweights`` the block
    matmuls + head run w8a8 int8, so params may omit the fp matrices
    entirely (slim tree: embed + norms only). ``lora``/``aid``: the
    adapter pool + per-wave-row pool slots — each row's (A, B) pair
    gathers into the batched matmuls (dummy rows ride slot 0, the
    all-zeros base). ``mesh``/``heads_axis``: a tensor-parallel
    engine's mesh and the mesh axis its heads shard over — a bucket
    long enough for the flash kernel then runs it per head shard
    (``ring_attention.local_attention``).
    """
    wq8 = qweights is not None
    S = tokens.shape[1]
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.arange(S)
    cos, sin = llama.rope_frequencies(cfg, positions)

    def body(x, layer_q):
        layer, qlayer, llayer = _layer_parts(layer_q, wq8,
                                             lora is not None)
        q, k, v = _layer_qkv(cfg, layer, qlayer, x, cos, sin, llayer, aid)
        with jax.named_scope("attn_core"):
            o = ra.local_attention(q, k, v, mesh, causal=True,
                                   batch_axes=None, heads_axis=heads_axis)
        x = _layer_out_ffn(cfg, layer, qlayer, x, o, llayer, aid)
        return x, (k, v)

    xs = _scan_xs(params, qweights, lora)
    x, (ks, vs) = lax.scan(body, x, xs)        # ks: [L, W, S, G, hd]
    logits = _head(
        cfg, params, qweights, x,
        lambda x: jnp.take_along_axis(
            x, (true_lens - 1)[:, None, None], axis=1)[:, 0])      # [W, D]
    return {"k": ks, "v": vs}, logits


@jax.named_scope("kv_write")
def insert(cache: Cache, prefix: Cache, slot: jax.Array,
           true_len: jax.Array, first_token: jax.Array,
           table=None) -> Cache:
    """Install a prefilled prompt into a decode slot.

    prefix k/v: [L, S_bucket, G, hd]; rows >= true_len are padding but
    harmless — decode masks by ``length``. With a block ``table`` the
    rows scatter through the slot's table instead (values identical to
    the contiguous write, which is what makes paged-vs-contiguous
    generation bit-identical); the spare slot's all-sentinel row drops
    dummy-wave writes entirely.
    """
    pk, pv = prefix["k"], prefix["v"]
    quant = "k_scale" in cache
    if quant:
        pk, ks = quantize_rows(pk)          # ks/vs: [L, S, G]
        pv, vs = quantize_rows(pv)
        sdt = cache["k_scale"].dtype
        ks, vs = ks.astype(sdt), vs.astype(sdt)
    if table is None:
        out = dict(cache)
        if quant:
            out["k_scale"] = lax.dynamic_update_slice(
                cache["k_scale"], ks.transpose(0, 2, 1)[:, None],
                (0, slot, 0, 0))
            out["v_scale"] = lax.dynamic_update_slice(
                cache["v_scale"], vs.transpose(0, 2, 1)[:, None],
                (0, slot, 0, 0))
        out["k"] = lax.dynamic_update_slice(
            cache["k"], pk[:, None], (0, slot, 0, 0, 0))
        out["v"] = lax.dynamic_update_slice(
            cache["v"], pv[:, None], (0, slot, 0, 0, 0))
    else:
        out = _write_rows(cache, table, slot, jnp.arange(pk.shape[1]),
                          (pk, pv, ks, vs) if quant else (pk, pv))
    out["length"] = cache["length"].at[slot].set(true_len)
    out["last_token"] = cache["last_token"].at[slot].set(first_token)
    return out


# ---------------------------------------------------------------------------
# Prefix KV pool + chunked prefill
# ---------------------------------------------------------------------------
# Prefix reuse: a reserved pool of K/V rows holds prompt prefixes (one
# row = one prefix, full max_len rows) in a SEPARATE tensor from the
# decode cache, so decode programs never pay compute or scatter traffic
# for pool rows. Host-side bookkeeping (which prefix lives in which
# row, LRU) stays in the engine; the device side is two gather/scatter
# copy programs (slot->row to store, row->slot to load) plus the
# chunked-prefill program below, which prefills ONLY the suffix after a
# prefix hit — the same program that chunks long cold prompts.


def init_prefix_pool(cfg: llama.LlamaConfig, rows: int, max_len: int,
                     kv_int8: bool = False) -> Cache:
    """K/V rows reserved for the prefix cache (``rows`` resident
    prefixes). Same per-row layout (and int8 scales) as the decode
    cache so a row copy is a pure gather/scatter — no requantization,
    which is what makes cached-vs-cold generation bit-identical."""
    L, G, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    pool: Cache = {}
    if kv_int8:
        pool["k"] = jnp.zeros((L, rows, max_len, G, hd), jnp.int8)
        pool["v"] = jnp.zeros((L, rows, max_len, G, hd), jnp.int8)
        pool["k_scale"] = jnp.zeros((L, rows, G, max_len), jnp.bfloat16)
        pool["v_scale"] = jnp.zeros((L, rows, G, max_len), jnp.bfloat16)
    else:
        pool["k"] = jnp.zeros((L, rows, max_len, G, hd), cfg.dtype)
        pool["v"] = jnp.zeros((L, rows, max_len, G, hd), cfg.dtype)
    return pool


def pool_logical_axes(pool: Cache) -> Dict[str, Tuple]:
    """Sharding axes for the prefix pool: identical names to the decode
    cache's (row dim = "batch") so ONE TP rule set shards both and the
    row-copy programs stay layout-compatible under a mesh."""
    axes = {
        "k": ("layer", "batch", "seq_cache", "kv_heads", "head_dim"),
        "v": ("layer", "batch", "seq_cache", "kv_heads", "head_dim"),
    }
    if "k_scale" in pool:
        axes["k_scale"] = ("layer", "batch", "kv_heads", "seq_cache")
        axes["v_scale"] = ("layer", "batch", "kv_heads", "seq_cache")
    return axes


def pool_store(pool: Cache, cache: Cache, slot: jax.Array,
               row: jax.Array) -> Cache:
    """Copy a slot's K/V rows (all max_len of them — static shape) into
    a pool row. Rows past the prompt are garbage but harmless: the host
    index records the cached prefix length and a load's suffix prefill
    overwrites everything past it before decode can read it."""
    out = dict(pool)
    for name in pool:
        src = lax.dynamic_index_in_dim(cache[name], slot, 1,
                                       keepdims=False)
        out[name] = lax.dynamic_update_index_in_dim(pool[name], src,
                                                    row, 1)
    return out


def pool_load(cache: Cache, pool: Cache, row: jax.Array,
              slot: jax.Array, claim_len: jax.Array) -> Cache:
    """Copy a pool row into a decode slot AND claim the slot for an
    in-progress chunked prefill: length is stamped to ``claim_len``
    (= max_len) so interleaved decode bursts — which scatter a garbage
    row for EVERY slot at index ``length``, active or not — write out
    of bounds and get dropped instead of corrupting rows a finished
    chunk already wrote (see the engine's chunk scheduler)."""
    out = dict(cache)
    for name in pool:
        src = lax.dynamic_index_in_dim(pool[name], row, 1,
                                       keepdims=False)
        out[name] = lax.dynamic_update_index_in_dim(cache[name], src,
                                                    slot, 1)
    out["length"] = cache["length"].at[slot].set(claim_len)
    return out


def claim_slot(cache: Cache, slot: jax.Array,
               claim_len: jax.Array) -> Cache:
    """Claim a slot for a cold chunked prefill (no pool row to copy):
    same length stamp as :func:`pool_load`, same reason."""
    return dict(cache,
                length=cache["length"].at[slot].set(claim_len))


def export_blocks(cache: Cache, idx: jax.Array) -> Dict[str, jax.Array]:
    """Gather ``idx``-selected physical blocks' K/V rows (+ scales) out
    of the paged pool: [L, NB, block_len, G, hd] per tensor, the
    device half of a cross-replica KV handoff. ``idx`` is a FIXED-width
    [NB] vector (NB = blocks per slot) padded with the sentinel
    (== n_blocks); gathers CLAMP out-of-bounds indices, so padding rows
    come back as garbage the host masks by the true block count — one
    compiled program regardless of how many blocks transfer."""
    return {name: cache[name][:, idx] for name in row_tensors(cache)}


def import_blocks(cache: Cache, idx: jax.Array,
                  vals: Dict[str, jax.Array]) -> Cache:
    """Scatter exported block rows into freshly allocated physical
    blocks — the receive half of a cross-replica KV handoff. Same
    fixed-width padded ``idx`` as :func:`export_blocks`: sentinel
    positions scatter out of bounds and DROP (the block-table garbage
    net), so padding never corrupts the pool."""
    out = dict(cache)
    for name, v in vals.items():
        out[name] = cache[name].at[:, idx].set(
            v.astype(cache[name].dtype))
    return out


def sync_slots(cache: Cache, active: jax.Array, lengths: jax.Array,
               tokens: jax.Array) -> Cache:
    """Force selected slots' (length, last_token) bookkeeping to
    host-given values in ONE batched program — the draft engine's
    lockstep/rollback seam (infer/draft.py).

    The drafter's KV rows for a mispredicted rollout sit PAST the
    committed length by construction (the same free-rollback property
    the verifier's window rows have), so rolling a draft slot back to
    the verifier's commit point — or re-pointing its pending token at
    the correction token — is purely this bookkeeping write: no K/V
    row moves, no block moves. ``active`` masks which slots sync;
    inactive slots are untouched (the commit_tokens idiom)."""
    return dict(
        cache,
        length=jnp.where(active, lengths.astype(jnp.int32),
                         cache["length"]),
        last_token=jnp.where(active, tokens.astype(jnp.int32),
                             cache["last_token"]))


def prefill_chunk(params: llama.Params, cache: Cache,
                  tokens_c: jax.Array, start: jax.Array,
                  n_valid: jax.Array, slot: jax.Array,
                  new_len: jax.Array, rng: jax.Array,
                  cfg: llama.LlamaConfig, sp, *, final: bool,
                  qweights=None, table=None, span=None,
                  kv_kernel=False, lora=None, aid=None
                  ) -> Tuple[Cache, jax.Array, jax.Array]:
    """One chunk of an incremental prefill into a decode slot.

    tokens_c: [C] int32 right-padded chunk; start: row offset of this
    chunk in the slot's sequence (rows < start — a reused prefix and/or
    earlier chunks — are already in the cache); n_valid: real tokens in
    this chunk; new_len: length to stamp (max_len mid-prefill, the true
    total on the final chunk — see :func:`pool_load`). ``final`` is
    static: the final variant samples the request's first token from
    the last valid position (and is the only one that splits the RNG,
    so cached and cold paths consume identical RNG streams).

    Chunk attention = big-cache dot over the slot's rows masked to
    ``col < start`` ++ causal intra-chunk dot — the decode_burst_staged
    formulation at C query rows. ONE compiled program (two with
    ``final``) serves every bucket and every suffix offset, replacing
    the per-bucket O(S^2) prefill monoliths above the chunk size.
    Numerics match the monolithic prefill up to summation order (same
    score set, softmaxed with the chunk block concatenated after the
    cache block); cached-vs-cold CHUNKED runs are bit-identical because
    both read/write the same rows with the same program. int8 KV path
    included: chunk rows quantize exactly as ``insert`` would.

    With ``table`` the slot's rows live in pool blocks: reads gather
    the blocks in logical order (same score set, same summation order
    as the contiguous read) and writes scatter through the table —
    paged-vs-contiguous chunk prefills are bit-identical.

    ``span`` (static): the big-cache dot reads only the first ``span``
    logical rows — sufficient whenever span >= ``start`` (the mask
    admits no row past ``start``), so the engine picks the span bucket
    covering this chunk's offset and a long-max_len engine stops
    paying max_len rows of reads per chunk. Same masked score set,
    same summation order: bit-identical to the full-view chunk.

    ``kv_kernel`` (static, paged only): the big-cache block runs
    through the Pallas paged-attention kernel over this slot's block
    table (queries batched as ``C * rep`` rows per kv-head) and merges
    with the intra-chunk block via the online-softmax combine — same
    score set, online summation order, greedy parity vs the gather
    oracle (this function with the flag off).

    Returns (cache', rng', first_token — 0 unless ``final``).
    """
    C = tokens_c.shape[0]
    M = span if span is not None else _logical_rows(cache, table)
    G, hd = cfg.n_kv_heads, cfg.head_dim
    rep = cfg.n_heads // G
    scale = hd ** -0.5
    neg = jnp.asarray(-1e30, jnp.float32)
    quant = "k_scale" in cache
    wq8 = qweights is not None
    sdt = cache["k_scale"].dtype if quant else None
    kdt = cache["k"].dtype

    x = params["embed"].astype(cfg.dtype)[tokens_c][None]   # [1, C, D]
    positions = start + jnp.arange(C)
    cos, sin = llama.rope_frequencies(cfg, positions)
    col = jnp.arange(M)
    j = jnp.arange(C)
    # The chunk program runs ONE slot: its adapter id is the single
    # entry of aid_b ([1], aligned with x's batch dim).
    aid_b = aid[slot][None] if lora is not None else None
    # Padding columns (>= n_valid) are masked out of the intra-chunk
    # scores; padding ROWS compute garbage that lands past the prompt's
    # true length, where decode's validity mask never reads.
    intra_mask = (j[None, :] <= j[:, None]) & (j[None, :] < n_valid)
    slot_table = (None if table is None
                  else lax.dynamic_slice_in_dim(table, slot, 1, 0))

    def body(carry, layer_q):
        x, i = carry
        layer, qlayer, llayer = _layer_parts(layer_q, wq8,
                                             lora is not None)
        q, k, v = _layer_qkv(cfg, layer, qlayer, x, cos, sin, llayer,
                             aid_b)
        with jax.named_scope("attn_core"):
            kr, vr = k[0], v[0]                       # [C, G, hd]
            if quant:
                kq, ksc = quantize_rows(kr)
                vq, vsc = quantize_rows(vr)
                ys = (kq, vq, ksc.astype(sdt), vsc.astype(sdt))
            else:
                ys = (kr.astype(kdt), vr.astype(kdt))
            # bf16 dots, fp32 accumulation — int8 converts to bf16 exactly
            # (see _staged_attn_layer's note).
            qh = q[0].reshape(C, G, rep, hd).astype(jnp.bfloat16)
            ss = jnp.einsum("cgrk,jgk->cgrj", qh, kr.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32) * scale
            ss = jnp.where(intra_mask[:, None, None, :], ss, neg)
            if kv_kernel and table is not None:
                # Kernel big-cache block over THIS slot's table row: the
                # chunk's C * rep query rows batch into one (slot,
                # kv-head) grid cell each; the mask bound is ``start``
                # (rows below this chunk are the resident prefix).
                q_k = qh.transpose(1, 0, 2, 3).reshape(1, G, C * rep, hd)
                acc, m, l = _paged_attn_stats(
                    cache, i, slot_table, q_k, jnp.reshape(start, (1,)),
                    span)
                acc = acc.reshape(G, C, rep, hd).transpose(1, 0, 2, 3)
                m = m.reshape(G, C, rep).transpose(1, 0, 2)
                l = l.reshape(G, C, rep).transpose(1, 0, 2)
                alpha, w_s, l_tot = _merge_attn_parts(acc, m, l, ss)
                o = acc * alpha[..., None] + jnp.einsum(
                    "cgrj,jgk->cgrk", w_s.astype(jnp.bfloat16),
                    vr.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
                o = o / l_tot[..., None]
            else:
                # One slot's rows: the decode read at T = 1.
                ck, cv, cks, cvs = (
                    t if t is None else t[0] for t in _gather_kv_layer(
                        cache, i, slot_table, jnp.reshape(slot, (1,)),
                        span))
                sm = jnp.einsum("cgrk,mgk->cgrm", qh,
                                ck.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32) * scale
                if quant:
                    sm = sm * cks[None, :, None, :]
                sm = jnp.where(col[None, None, None, :] < start, sm, neg)
                w = jax.nn.softmax(jnp.concatenate([sm, ss], axis=-1),
                                   axis=-1)
                wm, ws = w[..., :M], w[..., M:]
                if quant:
                    wm = wm * cvs[None, :, None, :]
                o = jnp.einsum("cgrm,mgk->cgrk", wm.astype(jnp.bfloat16),
                               cv.astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)
                o = o + jnp.einsum("cgrj,jgk->cgrk",
                                   ws.astype(jnp.bfloat16),
                                   vr.astype(jnp.bfloat16),
                                   preferred_element_type=jnp.float32)
        x = _layer_out_ffn(cfg, layer, qlayer, x, o, llayer, aid_b)
        return (x, i + 1), ys

    xs = _scan_xs(params, qweights, lora)
    (x, _), ys = lax.scan(body, (x, jnp.int32(0)), xs)

    if final:
        logits = _head(
            cfg, params, qweights, x,
            lambda x: lax.dynamic_index_in_dim(x[0], n_valid - 1, 0,
                                               keepdims=False))      # [D]
        with jax.named_scope("sample"):
            rng, sub = jax.random.split(rng)
            tok = sampling_mod.sample(logits, sub, sp)
    else:
        tok = jnp.zeros((), jnp.int32)

    # Chunk rows land at logical [slot, start:start+C]; a final partial
    # chunk's window may poke past max_len (:func:`_write_rows` drops it).
    with jax.named_scope("kv_write"):
        out = _write_rows(cache, table, slot, start + jnp.arange(C), ys)
        out["length"] = cache["length"].at[slot].set(new_len)
        if final:
            out["last_token"] = cache["last_token"].at[slot].set(tok)
    return out, rng, tok


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

@jax.named_scope("kv_write")
def commit_tokens(cache: Cache, tokens: jax.Array,
                  active: jax.Array) -> Cache:
    """Append sampled tokens on active slots: bump lengths, set last."""
    return dict(
        cache,
        length=cache["length"] + active.astype(jnp.int32),
        last_token=jnp.where(active, tokens, cache["last_token"]))


def _staged_attn_layer(cfg, cache, table, layer, qlayer, x, cos, sin,
                       i, s, sk, sv, sks, svs, pos0, stage_valid,
                       batch_ix, tiles, span=None, kv_kernel=False,
                       llayer=None, aid=None):
    """One decoder layer of a staged step: the current step's K/V rows
    land in the staging buffers, attention runs as big-cache dot (the
    resident rows ``< pos0``) ++ staged-columns dot (columns masked by
    ``stage_valid``) under ONE softmax, and the big cache stays a pure
    invariant. The only decode attention in the module: the step, burst
    and verify programs all run THIS math (:func:`_staged_steps`), so
    the speculative parity guarantee and step == burst-of-one hold by
    construction — an edit here can never drift one without the others.

    The read of resident rows and the attention visit the LIVE slots,
    :data:`TILE` of them a turn (``tiles``: :func:`_live_tiles`): a
    turn gathers its slots' blocks, attends them exactly as the whole
    batch was attended — the same dots at ``B = TILE`` — and writes the
    output rows back by slot id; dead rows' attention output is zero.
    ``span`` (static) bounds the big-cache read to the first ``span``
    logical rows.

    ``kv_kernel`` (static): run the big-cache block through the Pallas
    paged-attention kernel instead of the gather — the kernel walks
    the block table per (slot, kv-head), every slot, and the
    logical-view transient never materializes. Requires a ``table``
    (the kernel is block-table-native; contiguous callers keep the
    gather); it masks by ``pos0`` too. The staged-columns block is
    UNCHANGED either way; the two blocks merge via the online-softmax
    combine (:func:`_merge_attn_parts`) — same score set, online
    summation order, greedy parity vs the gather oracle.
    Returns (x', sk, sv, sks, svs).
    """
    quant = "k_scale" in cache
    kdt = cache["k"].dtype
    sdt = cache["k_scale"].dtype if quant else None
    B = x.shape[0]
    G, hd = cfg.n_kv_heads, cfg.head_dim
    rep = cfg.n_heads // G
    M = span if span is not None else _logical_rows(cache, table)
    scale = hd ** -0.5
    neg = jnp.asarray(-1e30, jnp.float32)

    q, kk, v = _layer_qkv(cfg, layer, qlayer, x, cos, sin, llayer, aid)
    with jax.named_scope("attn_core"):
        if quant:
            kq, ksc = quantize_rows(kk[:, 0])
            vq, vsc = quantize_rows(v[:, 0])
            ksc, vsc = ksc.astype(sdt), vsc.astype(sdt)
            sk = sk.at[i, batch_ix, s].set(kq)
            sv = sv.at[i, batch_ix, s].set(vq)
            sks = sks.at[i, batch_ix, s].set(ksc)
            svs = svs.at[i, batch_ix, s].set(vsc)
        else:
            sk = sk.at[i, batch_ix, s].set(kk[:, 0].astype(kdt))
            sv = sv.at[i, batch_ix, s].set(v[:, 0].astype(kdt))
        lk = lax.dynamic_index_in_dim(sk, i, 0, False)
        lv = lax.dynamic_index_in_dim(sv, i, 0, False)
        lks = lvs = None
        if quant:
            lks = lax.dynamic_index_in_dim(sks, i, 0, False)
            lvs = lax.dynamic_index_in_dim(svs, i, 0, False)
        # The attention dots run in bf16 with fp32 ACCUMULATION. The
        # int8 cache converts to bf16 EXACTLY (integers <= 127 carry no
        # rounding in an 8-bit mantissa) and each bf16xbf16 product is
        # exact in the fp32 accumulator, so the scores match a full
        # fp32 dot while the materialized cache-sized intermediate is
        # half the size. Per-row scales stay linear in the contraction:
        # K's scale applies to the SCORES and V's folds into the
        # softmax weights — nothing dequantized at cache shape ever
        # hits fp32. The step's own row is read back from the staging
        # buffer, so its score uses the SAME quantized values a later
        # step's cache read will see.
        qh = q[:, 0].reshape(B, G, rep, hd).astype(jnp.bfloat16)

        def staged_scores(qh, lk, lks):
            ss = jnp.einsum("bgrk,bjgk->bgrj", qh, lk.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32) * scale
            if quant:
                ss = ss * lks.transpose(0, 2, 1)[:, :, None, :]
            return jnp.where(stage_valid[:, None, None, :], ss, neg)

        def attend(ids, pos, table_rows):
            """The gather path for the slots ``ids`` [T]: o [T, G, rep,
            hd]."""
            qt, lvt = qh[ids], lv[ids]
            ss = staged_scores(qt, lk[ids], lks[ids] if quant else None)
            ck, cv, cks, cvs = _gather_kv_layer(cache, i, table_rows, ids,
                                                span)
            sm = jnp.einsum("bgrk,bmgk->bgrm", qt,
                            ck.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32) * scale
            if quant:
                sm = sm * cks[:, :, None, :]
            sm = jnp.where(_resident_mask(pos, M)[:, None, None, :],
                           sm, neg)
            w = jax.nn.softmax(jnp.concatenate([sm, ss], axis=-1), axis=-1)
            wm, ws = w[..., :M], w[..., M:]
            if quant:
                wm = wm * cvs[:, :, None, :]
                ws = ws * lvs[ids].transpose(0, 2, 1)[:, :, None, :]
            o = jnp.einsum("bgrm,bmgk->bgrk", wm.astype(jnp.bfloat16),
                           cv.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
            return o + jnp.einsum("bgrj,bjgk->bgrk",
                                  ws.astype(jnp.bfloat16),
                                  lvt.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32)

        if kv_kernel and table is not None:
            ss = staged_scores(qh, lk, lks)
            acc, m, l = _paged_attn_stats(cache, i, table, qh, pos0, span)
            alpha, w_s, l_tot = _merge_attn_parts(acc, m, l, ss)
            if quant:
                w_s = w_s * lvs.transpose(0, 2, 1)[:, :, None, :]
            o = acc * alpha[..., None] + jnp.einsum(
                "bgrj,bjgk->bgrk", w_s.astype(jnp.bfloat16),
                lv.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)
            o = o / l_tot[..., None]
        else:
            o = _visit_tiles(tiles, B, attend, (G, rep, hd))
    x = _layer_out_ffn(cfg, layer, qlayer, x, o, llayer, aid)
    return x, sk, sv, sks, svs


def _staged_steps(params: llama.Params, cache: Cache,
                  cfg: llama.LlamaConfig, W: int, xs, state, token, emit,
                  qweights=None, table=None, span=None, kv_kernel=False,
                  lora=None, aid=None, live=None):
    """``W`` decode steps for every slot with the big cache a read-only
    scan INVARIANT — the one scaffold under :func:`decode_step` (W = 1),
    :func:`decode_burst_staged` and :func:`verify_draft_staged`
    (``latent._staged_steps`` is the latent family's).

    Each step's K/V rows land in a small STAGING buffer
    ([L, slots, W, G, hd] — megabytes) and attention runs as big-cache
    dot (rows < the start lengths, a CONSTANT mask) ++ staged-columns
    dot (cols <= step); ONE batched scatter per tensor flushes all W
    rows to logical [b, length[b] + j] after the step loop
    (:func:`_write_rows`). That is what decode's HBM budget lives on:
    a cache CARRIED through the layer scan round-tripped each layer's
    82 MB K/V slice through dynamic-slice / row-update /
    dynamic-update (~330 MB of copy traffic per layer, ~12 ms of a
    31 ms 8B step); scattering into the carried cache every step still
    paid 4 serialized scatters x 32 layers of fixed op overhead, XLA
    could not keep them fully in place (~2.3 ms of a 24.9 ms 8B step),
    and carried-cache reads fuse worse than invariant reads (measured:
    the staged burst decodes in ~18-20 ms/step, ~25% faster end to end).

    What a driver brings: ``xs`` (leading dim W; step s sees its slice
    ``x``), a carried ``state``, ``token(state, x) -> [B]`` the token
    step s consumes and ``emit(logits [B, vocab], state, x) ->
    (state', emitted)``. ``qweights`` runs the seven block matmuls +
    the head w8a8; ``table`` routes reads and the flush through the
    block table; ``span`` / ``kv_kernel`` as :func:`_staged_attn_layer`
    (the flush scatters through the FULL table, so writes are
    untouched by a span); ``lora``/``aid``: the adapter pool + per-slot
    ids; ``live`` [B] bool: the rows whose tokens the driver keeps —
    resident rows are read and attended for THEM, a tile of slots a turn
    (:func:`_live_tiles`; absent: every row). Returns (cache with the
    W rows flushed — length / last_token untouched, the driver's to
    stamp —, final state, emitted [W, ...]).
    """
    B = cache["length"].shape[0]
    G, hd = cfg.n_kv_heads, cfg.head_dim
    L = cfg.n_layers
    quant = "k_scale" in cache
    wq8 = qweights is not None
    sdt = cache["k_scale"].dtype if quant else None
    kdt = cache["k"].dtype

    # ``length`` counts rows already in the cache (prompt + committed
    # tokens); step s's row is written at index length + s.
    pos0 = cache["length"]
    batch_ix = jnp.arange(B)
    tiles = _live_tiles(live, pos0, table)

    stage_k = jnp.zeros((L, B, W, G, hd), kdt)
    stage_v = jnp.zeros((L, B, W, G, hd), kdt)
    zero = jnp.zeros((), jnp.float32)
    stage_ks = jnp.zeros((L, B, W, G), sdt) if quant else zero
    stage_vs = jnp.zeros((L, B, W, G), sdt) if quant else zero

    def step(carry, x_s):
        with jax.named_scope("decode_step"):
            x_in, s = x_s
            state, sk, sv, sks, svs = carry
            x = params["embed"].astype(cfg.dtype)[
                token(state, x_in)[:, None]]
            pos = pos0 + s
            cos, sin = llama.rope_frequencies(cfg, pos[:, None])
            stage_valid = jnp.arange(W)[None, :] <= s     # [1, W]

            def body(carry2, layer_q):
                x, i, sk, sv, sks, svs = carry2
                layer, qlayer, llayer = _layer_parts(layer_q, wq8,
                                                     lora is not None)
                x, sk, sv, sks, svs = _staged_attn_layer(
                    cfg, cache, table, layer, qlayer, x, cos, sin, i, s,
                    sk, sv, sks, svs, pos0, stage_valid, batch_ix, tiles,
                    span, kv_kernel, llayer, aid)
                return (x, i + 1, sk, sv, sks, svs), None

            xs_l = _scan_xs(params, qweights, lora)
            (x, _, sk, sv, sks, svs), _ = lax.scan(
                body, (x, jnp.int32(0), sk, sv, sks, svs), xs_l)
            logits = _head(cfg, params, qweights, x)
            state, emitted = emit(logits, state, x_in)
        return (state, sk, sv, sks, svs), emitted

    init = (state, stage_k, stage_v, stage_ks, stage_vs)
    (state, sk, sv, sks, svs), emitted = lax.scan(
        step, init, (xs, jnp.arange(W)))
    with jax.named_scope("kv_write"):
        idx = pos0[:, None] + jnp.arange(W)[None, :]           # [B, W]
        out = _write_rows(cache, table, batch_ix[:, None], idx,
                          (sk, sv, sks, svs))
    return out, state, emitted


def decode_step(params: llama.Params, cache: Cache,
                cfg: llama.LlamaConfig, qweights=None, table=None,
                span=None, lora=None, aid=None) -> Tuple[Cache, jax.Array]:
    """One token for every slot: a burst of one (:func:`_staged_steps`
    at W = 1) that emits its logits instead of sampling, so they equal
    :func:`decode_burst_staged`'s first step bit for bit. Returns
    (cache' with the pending row written at ``length``, logits
    [slots, vocab]); the caller samples and commits
    (:func:`commit_tokens`). ``span`` (static) is valid whenever every
    active slot's length <= span (the engine's span-bucket selection
    guarantees it): the rows dropped were all masked to exact-zero
    softmax weight.
    """
    out, _, logits = _staged_steps(
        params, cache, cfg, 1, None, (),
        lambda state, x: cache["last_token"],
        lambda logits, state, x: (state, logits),
        qweights=qweights, table=table, span=span, lora=lora, aid=aid)
    return out, logits[0]


def decode_burst_staged(params: llama.Params, cache: Cache,
                        rng: jax.Array, active: jax.Array, k: int,
                        cfg: llama.LlamaConfig, sp,
                        qweights=None, table=None, span=None,
                        kv_kernel=False, lora=None, aid=None
                        ) -> Tuple[Cache, jax.Array, jax.Array]:
    """k decode steps with a per-BURST cache flush (the engine's burst
    program; trace under jit with cache+rng donated): the staged
    scaffold (:func:`_staged_steps`) with each step's SAMPLED token fed
    to the next.

    Dead slots (inactive, or retired mid-burst) write rows past their
    logical end; flush indices beyond the buffer are DROPPED by JAX
    scatter OOB semantics, and reused slots are fully re-stamped by
    ``insert``. With a block ``table``, cache reads gather each slot's
    blocks in logical order and the flush scatters through the table
    (cleared/dead slot rows map to the sentinel block and drop).

    ``span`` (static): the big-cache read covers only the first
    ``span`` logical rows. Correct whenever every ACTIVE slot's
    burst-start length <= span (the engine's bucket selection); an
    inactive slot whose length exceeds the span computes garbage that
    is never committed, exactly like any other dead-slot row.

    ``kv_kernel`` (static): route the big-cache read through the
    Pallas paged-attention kernel (paged only — see
    :func:`_staged_attn_layer`); greedy parity vs this function with
    the flag off, which stays the oracle.
    Returns (cache', rng', toks [k, slots]).
    """
    rng, sub = jax.random.split(rng)
    keys = jax.random.split(sub, k)

    def emit(logits, last, key):
        with jax.named_scope("sample"):
            tok = sampling_mod.sample(logits, key, sp)
        return jnp.where(active, tok, last), tok

    out, last, toks = _staged_steps(
        params, cache, cfg, k, keys, cache["last_token"],
        lambda last, key: last, emit,
        qweights=qweights, table=table, span=span, kv_kernel=kv_kernel,
        lora=lora, aid=aid, live=active)
    out["length"] = cache["length"] + k * active.astype(jnp.int32)
    out["last_token"] = last
    return out, rng, toks


def verify_draft_staged(params: llama.Params, cache: Cache,
                        draft: jax.Array, n_draft: jax.Array,
                        active: jax.Array, k: int,
                        cfg: llama.LlamaConfig,
                        qweights=None, table=None, span=None,
                        kv_kernel=False, lora=None, aid=None
                        ) -> Tuple[Cache, jax.Array, jax.Array]:
    """Speculative-decode verify: score ``k`` drafted tokens per slot
    plus the correction position in ONE device call (the engine's
    verify program; trace under jit with the cache donated, ``k``
    static — one compiled program for the whole serving lifetime).

    draft: [B, k] int32 host-proposed tokens per slot (n-gram /
    prompt-lookup — the drafter never touches the device); n_draft:
    [B] int32 real draft tokens per slot (slots that drafted fewer
    than ``k`` pad and mask, exactly like a partial prefill chunk).

    The window is ``k + 1`` positions: position 0 consumes the slot's
    pending ``last_token`` (the same token a plain decode step would
    consume) and positions 1..k consume the draft. Structurally this
    is :func:`decode_burst_staged` with the sampled-token feedback
    replaced by the given window tokens and greedy argmax outputs —
    same big-cache dot over rows < the burst-start lengths, same
    staged intra-window dot, same single per-burst flush — so an
    ACCEPTED position's logits are computed from exactly the inputs
    the plain decode path would have fed it.

    Greedy-exact acceptance, ON DEVICE (no RNG anywhere — the greedy
    path's stream must stay untouched): out[s] = argmax after
    consuming window position s; the accepted prefix length is the
    longest run of out[s] == draft[s] over real (< n_draft) draft
    positions, and ``n_commit = n_match + 1`` committed tokens per
    active slot — the matched draft tokens plus the first correction
    (or bonus) token from the same pass. Committed outputs depend only
    on real tokens: out[s] for s <= n_match attends to window columns
    0..s, all of which are the pending token or MATCHED draft tokens.

    Rollback is free by construction: all ``k + 1`` window rows are
    scattered at logical rows length..length+k, but ``length`` only
    advances by ``n_commit`` — rejected rows sit past the committed
    length, invisible to the validity mask (contiguous) or sitting in
    already-allocated blocks (paged: a block-table length decrement,
    no block ever moves), and the next burst overwrites them. A slot
    without k + 1 rows of headroom below max_len rides the burst with
    an empty draft (the engine zeroes it): its correction row at
    ``length`` is always in bounds for an active request, and spare
    window rows past max_len drop via scatter-OOB (contiguous) or the
    sentinel block (paged).

    ``span`` (static): same bounded big-cache read as
    :func:`decode_burst_staged` — accepted positions see exactly the
    score set the plain decode path at the same span would, so the
    spec parity guarantee extends to every span bucket.

    Returns (cache', toks [B, k+1] — the window's argmax outputs, the
    first ``n_commit[b]`` of row b are the committed tokens —
    n_commit [B] int32, 0 for inactive slots).
    """
    # Window tokens: the pending token then the draft — the exact
    # sequence sequential decode would consume while every draft
    # position matches.
    window = jnp.concatenate(
        [cache["last_token"][:, None], draft.astype(jnp.int32)],
        axis=1)                                      # [B, k + 1]

    def emit(logits, state, tok):
        with jax.named_scope("sample"):
            return state, sampling_mod.argmax_tokens(logits)

    out, _, toks = _staged_steps(
        params, cache, cfg, k + 1, window.T, (),
        lambda state, tok: tok, emit,
        qweights=qweights, table=table, span=span, kv_kernel=kv_kernel,
        lora=lora, aid=aid, live=active)
    toks = toks.T                                     # [B, k + 1]

    # Accepted prefix: out[s] must reproduce draft position s, and
    # padding positions (>= n_draft) never match — a pad token that
    # happened to equal the argmax must not commit a token computed
    # from garbage input.
    match = ((toks[:, :k] == draft)
             & (jnp.arange(k)[None, :] < n_draft[:, None]))
    n_match = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                      axis=1)                          # [B]
    n_commit = jnp.where(active, n_match + 1, 0).astype(jnp.int32)

    out["length"] = cache["length"] + n_commit
    out["last_token"] = jnp.where(
        active, toks[jnp.arange(toks.shape[0]), n_match],
        cache["last_token"])
    return out, toks, n_commit
