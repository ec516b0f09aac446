"""Flash attention (FlashAttention-2) as Pallas TPU kernels.

Design (per the pallas TPU playbook):
* Grid (batch, heads, q-blocks); the KV sweep is a ``fori_loop`` inside
  the kernel with the online-softmax running max/sum carried in
  registers; accumulation in float32 scratch, output cast to the input
  dtype (bf16 on TPU -> MXU-native matmuls).
* Causal masking prunes whole KV blocks: q-block i only sweeps KV
  blocks 0..i, and only the diagonal block pays the element mask.
* Backward is the standard FA-2 split: a dKV kernel (grid over KV
  blocks, sweeping q-blocks >= diagonal) and a dQ kernel (grid over
  q-blocks, sweeping KV blocks <= diagonal), both recomputing P from
  the saved logsumexp instead of materializing S.

Used by ops.attention.gqa_attention on TPU for long sequences; the
einsum path remains the fallback (and the numerics oracle in tests,
which run this kernel with ``interpret=True`` on CPU).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
LANES = 128     # lane-replicated rowwise stats (Mosaic tiling)
SUBLANES = 8    # kv-side segment-id layout: [B, SUBLANES, S]
NEG_INF = -1e30


def _blocks(s: int, b: int) -> int:
    return (s + b - 1) // b


def _segment_mask(qseg_tile, kseg_ref, ki, block_k):
    """[Bq, Bk] same-segment mask.

    qseg_tile: [Bq, LANES] lane-replicated q segment ids;
    kseg_ref: [SUBLANES, S] ref with the seq dim in lanes (the official
    TPU layout trick — equality broadcasts [Bq, Bk] == [1, Bk] without
    any in-kernel transpose).
    """
    q_seg = jnp.tile(qseg_tile, (1, block_k // LANES))      # [Bq, Bk]
    k_seg = kseg_ref[:1, pl.ds(ki * block_k, block_k)]      # [1, Bk]
    return q_seg == k_seg


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                causal: bool, block_k: int, seq_len: int,
                qseg_ref=None, kseg_ref=None):
    qi = pl.program_id(2)
    block_q = q_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * scale  # [Bq, D]

    num_kv = _blocks(seq_len, block_k)
    if causal:
        # KV blocks strictly after this q block's end contribute nothing.
        num_kv_live = lax.div(qi * block_q + block_q - 1, block_k) + 1
    else:
        num_kv_live = num_kv

    def body(ki, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(ki * block_k, block_k), :]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Bq, Bk]
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if qseg_ref is not None:
            s = jnp.where(_segment_mask(qseg_ref[...], kseg_ref, ki,
                                        block_k), s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    d = q_ref.shape[1]
    init = (jnp.zeros((block_q, d), jnp.float32),
            jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32))
    acc, m, l = lax.fori_loop(0, num_kv_live, body, init)
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    # Lane-replicated (Bq, 128) layout: Mosaic cannot tile 1-lane blocks.
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape)


def _seg_layouts(segments, s):
    """[B, S] int32 -> (q-side [B, S, LANES], kv-side [B, SUBLANES, S])."""
    qseg = jax.lax.broadcast_in_dim(
        segments, (segments.shape[0], s, LANES), (0, 1))
    kseg = jax.lax.broadcast_in_dim(
        segments, (segments.shape[0], SUBLANES, s), (0, 2))
    return qseg, kseg


def _fwd(q, k, v, segments, *, causal: bool, block_q: int, block_k: int,
         interpret: bool):
    """q,k,v: [B, H, S, D]; segments: [B, S] int32 or None
    -> (o [B,H,S,D], lse [B,H,S,LANES] f32)."""
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    grid = (b, h, _blocks(s, block_q))
    qspec = pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0))
    kvspec = pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi, 0, 0))
    in_specs = [qspec, kvspec, kvspec]
    args = [q, k, v]
    if segments is not None:
        qseg, kseg = _seg_layouts(segments, s)
        in_specs += [
            pl.BlockSpec((1, block_q, LANES),
                         lambda bi, hi, qi: (bi, qi, 0)),
            pl.BlockSpec((1, SUBLANES, s), lambda bi, hi, qi: (bi, 0, 0)),
        ]
        args += [qseg, kseg]

    def kernel(q_ref, k_ref, v_ref, *rest):
        if segments is not None:
            qseg_ref, kseg_ref, o_ref, lse_ref = rest
            segrefs = dict(qseg_ref=qseg_ref.at[0],
                           kseg_ref=kseg_ref.at[0])
        else:
            o_ref, lse_ref = rest
            segrefs = {}
        _fwd_kernel(q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0],
                    o_ref.at[0, 0], lse_ref.at[0, 0],
                    scale=scale, causal=causal, block_k=block_k,
                    seq_len=s, **segrefs)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[qspec,
                   pl.BlockSpec((1, 1, block_q, LANES),
                                lambda bi, hi, qi: (bi, hi, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, LANES), jnp.float32)],
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale: float, causal: bool,
                    block_q: int, seq_len: int,
                    qseg_ref=None, kseg_ref=None):
    ki = pl.program_id(2)
    block_k = k_ref.shape[0]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    num_q = _blocks(seq_len, block_q)
    # Causal: q blocks before this KV block's start see nothing of it.
    q_start = lax.div(ki * block_k, block_q) if causal else 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[pl.ds(qi * block_q, block_q), :]
        do = do_ref[pl.ds(qi * block_q, block_q), :]
        lse = jnp.max(lse_ref[pl.ds(qi * block_q, block_q), :], axis=1,
                      keepdims=True)
        delta = jnp.max(delta_ref[pl.ds(qi * block_q, block_q), :], axis=1,
                        keepdims=True)
        q = q.astype(jnp.float32) * scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, k.shape[0]), 0)
            k_pos = ki * k.shape[0] + lax.broadcasted_iota(
                jnp.int32, (block_q, k.shape[0]), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if qseg_ref is not None:
            # kseg_ref here is the ki-blocked tile [SUBLANES, Bk]: the
            # kv index inside _segment_mask must be 0.
            qs = qseg_ref[pl.ds(qi * block_q, block_q), :]
            s = jnp.where(_segment_mask(qs, kseg_ref, 0, block_k), s,
                          NEG_INF)
        p = jnp.exp(s - lse)  # [Bq, Bk]
        do_f = do.astype(jnp.float32)
        dv_new = dv + jax.lax.dot_general(
            p, do_f, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # P^T dO
        dp = jax.lax.dot_general(do_f, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)  # [Bq, Bk]
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # dS^T q (already scaled)
        return dk_new, dv_new

    d = k.shape[1]
    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32))
    dk, dv = lax.fori_loop(q_start, num_q, body, init)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, scale: float, causal: bool, block_k: int,
                   seq_len: int, qseg_ref=None, kseg_ref=None):
    qi = pl.program_id(2)
    block_q = q_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * scale
    do = do_ref[...].astype(jnp.float32)
    lse = jnp.max(lse_ref[...], axis=1, keepdims=True)
    delta = jnp.max(delta_ref[...], axis=1, keepdims=True)
    num_kv = _blocks(seq_len, block_k)
    num_kv_live = (lax.div(qi * block_q + block_q - 1, block_k) + 1
                   if causal else num_kv)

    def body(ki, dq):
        k = k_ref[pl.ds(ki * block_k, block_k), :]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        k = k.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if qseg_ref is not None:
            s = jnp.where(_segment_mask(qseg_ref[...], kseg_ref, ki,
                                        block_k), s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, num_kv_live, body,
                       jnp.zeros((block_q, q.shape[1]), jnp.float32))
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, segments, o, lse = residuals
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    # delta = rowsum(dO * O): tiny elementwise+reduce, XLA fuses it well.
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,H,S,1]
    delta = jnp.broadcast_to(delta, (b, h, s, LANES))

    full = lambda bi, hi, i: (bi, hi, 0, 0)
    kv_blocked = pl.BlockSpec((1, 1, block_k, d),
                              lambda bi, hi, ki: (bi, hi, ki, 0))
    q_blocked = pl.BlockSpec((1, 1, block_q, d),
                             lambda bi, hi, qi: (bi, hi, qi, 0))
    seq_full_d = pl.BlockSpec((1, 1, s, d), full)
    seq_full_1 = pl.BlockSpec((1, 1, s, LANES), full)

    seg_args, dkv_seg_specs, dq_seg_specs = [], [], []
    if segments is not None:
        qseg, kseg = _seg_layouts(segments, s)
        seg_args = [qseg, kseg]
        dkv_seg_specs = [
            pl.BlockSpec((1, s, LANES), lambda bi, hi, ki: (bi, 0, 0)),
            pl.BlockSpec((1, SUBLANES, block_k),
                         lambda bi, hi, ki: (bi, 0, ki)),
        ]
        dq_seg_specs = [
            pl.BlockSpec((1, block_q, LANES),
                         lambda bi, hi, qi: (bi, qi, 0)),
            pl.BlockSpec((1, SUBLANES, s), lambda bi, hi, qi: (bi, 0, 0)),
        ]

    dkv_kernel = functools.partial(
        _pack_dkv, scale=scale, causal=causal, block_q=block_q, seq_len=s,
        with_segments=segments is not None)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, _blocks(s, block_k)),
        in_specs=[seq_full_d, kv_blocked, kv_blocked, seq_full_d,
                  seq_full_1, seq_full_1, *dkv_seg_specs],
        out_specs=[kv_blocked, kv_blocked],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, g, lse, delta, *seg_args)

    dq_kernel = functools.partial(
        _pack_dq, scale=scale, causal=causal, block_k=block_k, seq_len=s,
        with_segments=segments is not None)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, _blocks(s, block_q)),
        in_specs=[q_blocked, seq_full_d, seq_full_d, q_blocked,
                  pl.BlockSpec((1, 1, block_q, LANES),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
                  pl.BlockSpec((1, 1, block_q, LANES),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
                  *dq_seg_specs],
        out_specs=q_blocked,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, g, lse, delta, *seg_args)
    return dq, dk, dv, None


def _split_seg_refs(rest, with_segments, kw):
    """Shared unpack for the optional trailing (qseg, kseg) refs: the
    segment refs, when present, precede the output refs in ``rest``."""
    if with_segments:
        qseg_ref, kseg_ref, *outs = rest
        kw = dict(kw, qseg_ref=qseg_ref.at[0], kseg_ref=kseg_ref.at[0])
        return outs, kw
    return list(rest), kw


def _pack_dkv(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
              with_segments, **kw):
    (dk_ref, dv_ref), kw = _split_seg_refs(rest, with_segments, kw)
    _bwd_dkv_kernel(q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0],
                    do_ref.at[0, 0], lse_ref.at[0, 0], delta_ref.at[0, 0],
                    dk_ref.at[0, 0], dv_ref.at[0, 0], **kw)


def _pack_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
             with_segments, **kw):
    (dq_ref,), kw = _split_seg_refs(rest, with_segments, kw)
    _bwd_dq_kernel(q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0],
                   do_ref.at[0, 0], lse_ref.at[0, 0], delta_ref.at[0, 0],
                   dq_ref.at[0, 0], **kw)


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, segments, causal, block_q, block_k, interpret):
    o, _ = _fwd(q, k, v, segments, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret)
    return o


def _flash_fwd(q, k, v, segments, causal, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, segments, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    # Named, so that a rematerialised caller whose policy saves both
    # (train/qlora.py) runs this kernel once; inert under any other.
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, segments, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, residuals, g):
    return _bwd(causal, block_q, block_k, interpret, residuals, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def fit_block(s: int, want: int, lanes_only: bool = False):
    """Largest block <= ``want`` that divides ``s``: a multiple of 128
    rows where one exists, else (unless ``lanes_only``) a multiple of
    the 8-row sublane tile; None when neither divides ``s``."""
    for multiple in (LANES,) if lanes_only else (LANES, SUBLANES):
        for b in range(min(want, s) // multiple * multiple, 0, -multiple):
            if s % b == 0:
                return b
    return None


def flash_attention(q, k, v, causal: bool = True,
                    segment_ids=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q, k, v: [B, S, H, D] (same layout as ops.attention) -> [B, S, H, D].

    K/V must already be GQA-expanded to H heads (ops.attention does it).
    ``segment_ids`` [B, S] int32 enables packed-sequence masking (needs
    block_k to be a multiple of 128 for the lane-tiled compare).

    ``block_q``/``block_k`` are upper bounds: each shrinks to the
    largest divisor of S that keeps the tiling (S = 1280 runs at 256,
    not at an error). Only a sequence with no such divisor raises.
    """
    b, s, h, d = q.shape
    fit_q = fit_block(s, block_q)
    fit_k = fit_block(s, block_k, lanes_only=segment_ids is not None)
    if fit_q is None or fit_k is None:
        raise ValueError(
            f"seq len {s} has no block <= ({block_q}, {block_k}) that "
            f"divides it and keeps the tiling (multiples of {SUBLANES} "
            f"rows; the kv block a multiple of {LANES} under segment "
            f"masking) — pad the sequence to a multiple of {LANES}")
    block_q, block_k = fit_q, fit_k
    if segment_ids is not None:
        segment_ids = segment_ids.astype(jnp.int32)
    # [B,S,H,D] -> [B,H,S,D] for the kernels.
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    o = _flash(qt, kt, vt, segment_ids, causal, block_q, block_k,
               interpret)
    return o.swapaxes(1, 2)
