"""Context parallelism: ring attention + Ulysses all-to-all attention.

Long-sequence attention sharded over the ``sp`` mesh axis. Two schemes,
both expressed as shard_map'ed collectives so XLA schedules the ICI
traffic (and can overlap `ppermute` with the block matmuls):

* **Ring attention** (`ring_attention`): K/V blocks circulate around the
  ``sp`` ring via `lax.ppermute` while each device keeps its query shard;
  softmax is accumulated online (flash-style running max/sum), so no
  device ever materializes more than one remote K/V block. A custom VJP
  runs a second ring in the backward pass with dK/dV accumulators riding
  along with their K/V blocks — memory stays O(seq/sp) per device in both
  passes. GQA is native: the *unrepeated* K/V heads circulate (grouped
  einsums inside the ring body), so ICI volume and resident KV bytes are
  n_kv_heads-sized, not n_heads-sized.

* **Ulysses attention** (`ulysses_attention`): `lax.all_to_all` reshards
  [seq/sp, heads] -> [seq, heads/sp], runs ordinary (flash) attention on
  full sequences for a head subset, and reshards back. Cheaper in
  collective volume when heads >= sp; requires heads % sp == 0.

**Packed sequences** (`segment_ids` [B, S], 0 = padding) compose with
both schemes: in the ring, each block's segment ids circulate WITH its
K/V, and the ring body masks cross-segment pairs — so packed long-
context training runs under sequence parallelism (the flagship TPU
workload). Ulysses all-gathers the (tiny) id vector to mask the full
sequence locally.

Reference parity: the reference has NO sequence/context parallelism
anywhere (SURVEY.md §2.11 — long-context is delegated to workload
engines like vLLM/DeepSpeed). Here it is first-class, per the TPU-native
mandate: sequence parallelism shapes the core mesh design (the ``sp``
axis in parallel.mesh) rather than being an external recipe concern.

Causal note: the plain ring computes blocks entirely in the masked
future and zeroes them (uniform work per step keeps the collective
schedule static). `zigzag_ring_attention` removes that waste: the
zigzag chunk layout makes every step's needed work a single maskless
half-block einsum, balanced across devices — ~2x attention FLOPs saving
as sp grows. Models opt in via the activation-rule key
``seq_layout: zigzag`` (llama permutes once after the embedding).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _ring_perm(axis_size: int):
    return [(j, (j + 1) % axis_size) for j in range(axis_size)]


def _block_mask(my_idx, kv_idx, s_q: int, s_k: int):
    """Causal mask between global query/key positions of two ring blocks."""
    q_pos = my_idx * s_q + jnp.arange(s_q)
    k_pos = kv_idx * s_k + jnp.arange(s_k)
    return q_pos[:, None] >= k_pos[None, :]


def _allowed_mask(causal, has_seg, my_idx, kv_idx, s_q, s_k, q_seg, k_seg):
    """Combined causal+segment mask, broadcastable to [B,Hkv,G,Sq,Sk].
    None means everything is allowed."""
    allowed = None
    if causal:
        allowed = _block_mask(my_idx, kv_idx, s_q, s_k)  # [Sq, Sk]
    if has_seg:
        same = (q_seg[:, None, None, :, None]
                == k_seg[:, None, None, None, :])        # [B,1,1,Sq,Sk]
        allowed = same if allowed is None else (allowed & same)
    return allowed


def _group(q, n_kv: int):
    """[B, S, Hq, D] -> [B, S, Hkv, G, D] with G = Hq // Hkv."""
    B, S, Hq, D = q.shape
    return q.reshape(B, S, n_kv, Hq // n_kv, D)


# ---------------------------------------------------------------------------
# Forward ring
# ---------------------------------------------------------------------------

def _ring_fwd(axis_name: str, axis_size: int, causal: bool, has_seg: bool,
              q, k, v, seg):
    """Local q [B,S,Hq,D]; k/v [B,S,Hkv,D], Hq % Hkv == 0; seg [B,S].

    Returns (o [B,S,Hq,D], lse [B,Hkv,G,S]). Grouped (GQA) einsums: the
    circulating K/V stay at Hkv heads. With has_seg, the K/V block's
    segment ids ride the ring and cross-segment pairs are masked.
    """
    scale = q.shape[-1] ** -0.5
    my_idx = lax.axis_index(axis_name)
    B, S, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    perm = _ring_perm(axis_size)
    q5 = _group(q, Hkv)  # [B, S, Hkv, G, D]

    o0 = jnp.zeros((B, S, Hkv, Hq // Hkv, D), jnp.float32)
    m0 = jnp.full((B, Hkv, Hq // Hkv, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, Hq // Hkv, S), jnp.float32)

    def step(carry, i):
        o, m, l, k, v, kseg = carry
        kv_idx = (my_idx - i) % axis_size
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k,
                       preferred_element_type=jnp.float32) * scale
        allowed = _allowed_mask(causal, has_seg, my_idx, kv_idx, S, Sk,
                                seg, kseg)
        if allowed is not None:
            s = jnp.where(allowed, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        l = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        o = o * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        kseg = lax.ppermute(kseg, axis_name, perm)
        return (o, m_new, l, k, v, kseg), None

    (o, m, l, k, v, _), _ = lax.scan(step, (o0, m0, l0, k, v, seg),
                                     jnp.arange(axis_size))
    # axis_size permutes = identity: k/v are home again (used by the bwd).
    l_safe = jnp.maximum(l, 1e-30)
    o = (o / l_safe.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return o.reshape(B, S, Hq, D), lse


# ---------------------------------------------------------------------------
# Backward ring: dK/dV accumulators travel with their K/V blocks.
# ---------------------------------------------------------------------------

def _ring_bwd(axis_name: str, axis_size: int, causal: bool, has_seg: bool,
              res, do):
    q, k, v, o, lse, seg = res
    scale = q.shape[-1] ** -0.5
    my_idx = lax.axis_index(axis_name)
    B, S, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    perm = _ring_perm(axis_size)
    q5 = _group(q, Hkv)
    do5 = _group(do, Hkv)

    # delta_i = sum_d do_i * o_i  (rowwise), [B, Hkv, G, S]
    delta = jnp.einsum("bqhgd,bqhgd->bhgq", do5.astype(jnp.float32),
                       _group(o, Hkv).astype(jnp.float32))

    dq0 = jnp.zeros(q5.shape, jnp.float32)
    dk0 = jnp.zeros_like(k, jnp.float32)
    dv0 = jnp.zeros_like(v, jnp.float32)

    def step(carry, i):
        dq, k, v, dk, dv, kseg = carry
        kv_idx = (my_idx - i) % axis_size
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k,
                       preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[..., None])
        allowed = _allowed_mask(causal, has_seg, my_idx, kv_idx, S, Sk,
                                seg, kseg)
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        dv = dv + jnp.einsum("bhgqk,bqhgd->bkhd", p.astype(do.dtype), do5,
                             preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", do5, v,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        ds_c = ds.astype(q.dtype)
        dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds_c, k,
                             preferred_element_type=jnp.float32)
        dk = dk + jnp.einsum("bhgqk,bqhgd->bkhd", ds_c, q5,
                             preferred_element_type=jnp.float32)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        kseg = lax.ppermute(kseg, axis_name, perm)
        return (dq, k, v, dk, dv, kseg), None

    (dq, k, v, dk, dv, _), _ = lax.scan(step, (dq0, k, v, dk0, dv0, seg),
                                        jnp.arange(axis_size))
    dseg = np.zeros(seg.shape, dtype=jax.dtypes.float0)
    return (dq.reshape(B, S, Hq, D).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype), dseg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ring_attn(axis_name: str, axis_size: int, causal: bool, has_seg: bool,
               q, k, v, seg):
    o, _ = _ring_fwd(axis_name, axis_size, causal, has_seg, q, k, v, seg)
    return o


def _ring_attn_fwd(axis_name, axis_size, causal, has_seg, q, k, v, seg):
    o, lse = _ring_fwd(axis_name, axis_size, causal, has_seg, q, k, v, seg)
    return o, (q, k, v, o, lse, seg)


_ring_attn.defvjp(_ring_attn_fwd, _ring_bwd)


# ---------------------------------------------------------------------------
# Zigzag ring: load-balanced causal context parallelism
# ---------------------------------------------------------------------------
# The plain causal ring wastes work: at every step some device's whole
# K/V block is in its masked future, yet lockstep ppermutes mean nobody
# finishes early. The zigzag layout splits the sequence into 2n chunks
# and gives device i chunks (i, 2n-1-i). Then for any remote block from
# device j, exactly one of two MASKLESS half-einsums is needed:
#
#   i > j : ALL local queries attend the block's LOW chunk only
#           (q[2c] x k[:c]) — its high chunk is entirely future.
#   i < j : only the local HIGH-chunk queries attend, but to the whole
#           block (q[c:] x k[2c]) — low queries see only future.
#
# Both cases cost 2c^2 (vs the plain ring's 4c^2 per step), every
# device does the same amount at every step, and only the t=0 local
# block needs a mask at all. ~2x attention FLOPs saving as n grows.
# (This is the zigzag scheme from public ring-flash-attention work,
# expressed as lax.cond branches whose outputs share one accumulator
# pytree — XLA executes exactly one branch per step.)

def zigzag_indices(seq_len: int, n: int):
    """Global row order for the zigzag layout: shard i holds chunks
    (i, 2n-1-i). Returns (permute_idx, unpermute_idx)."""
    if seq_len % (2 * n) != 0:
        raise ValueError(f"seq {seq_len} not divisible by 2*{n}")
    c = seq_len // (2 * n)
    order = []
    for i in range(n):
        order.extend(range(i * c, (i + 1) * c))
        order.extend(range((2 * n - 1 - i) * c, (2 * n - i) * c))
    perm = np.asarray(order, dtype=np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len, dtype=np.int32)
    return perm, inv


def zigzag_permute(x, n: int, axis: int = 1):
    perm, _ = zigzag_indices(x.shape[axis], n)
    return jnp.take(x, perm, axis=axis)


def zigzag_unpermute(x, n: int, axis: int = 1):
    _, inv = zigzag_indices(x.shape[axis], n)
    return jnp.take(x, inv, axis=axis)


def apply_zigzag_layout(x, positions, segment_ids, mesh, rules):
    """The model-side half of the zigzag layout contract, shared by
    every decoder model (llama, moe): decide whether the zigzag layout
    applies (rules ask for it, the sequence mesh-axis is > 1, and S
    divides 2*n), permute activations/positions/segment ids once, and
    strip the layout key on fallback so the attention dispatch always
    agrees with the actual layout.

    x: [B, S, D] post-embedding activations. Returns
    ``(x, positions, segment_ids, layer_rules, use_zigzag, n_sp)``;
    the caller runs its decoder stack under ``layer_rules`` and, when
    ``use_zigzag``, un-permutes the final hidden states with
    ``zigzag_unpermute(x, n_sp)``.
    """
    use_zigzag, n_sp = False, 1
    if mesh is not None and rules is not None \
            and rules.get("seq_layout") == "zigzag":
        S = x.shape[1]
        seq_axis = rules.get("seq")
        n_sp = (mesh.shape.get(seq_axis, 1)
                if isinstance(seq_axis, str) else 1)
        use_zigzag = n_sp > 1 and S % (2 * n_sp) == 0
        if use_zigzag:
            x = zigzag_permute(x, n_sp)
            positions = zigzag_permute(positions, n_sp,
                                       axis=positions.ndim - 1)
            if segment_ids is not None:
                segment_ids = zigzag_permute(segment_ids, n_sp)
    layer_rules = rules
    if rules is not None and rules.get("seq_layout") == "zigzag" \
            and not use_zigzag:
        # Divisibility fallback: drop the layout key so the attention
        # dispatch agrees with the (unpermuted) layout.
        layer_rules = {k: v for k, v in rules.items()
                       if k != "seq_layout"}
    return x, positions, segment_ids, layer_rules, use_zigzag, n_sp


def _zz_positions(my_idx, n: int, c: int):
    """Global positions of this device's 2c local rows."""
    lo = my_idx * c + jnp.arange(c)
    hi = (2 * n - 1 - my_idx) * c + jnp.arange(c)
    return jnp.concatenate([lo, hi])


def _zz_seg_mask(q_seg, k_seg):
    """[B,1,1,Sq,Sk] same-segment mask (None when unsegmented)."""
    return (q_seg[:, None, None, :, None]
            == k_seg[:, None, None, None, :])


def _online_update(o, m, l, s, v5, allowed, v_dtype):
    """One online-softmax accumulation of scores s against values v5.
    o [B,S,Hkv,G,D] (S rows matching s's q dim), m/l [B,Hkv,G,S]."""
    if allowed is not None:
        s = jnp.where(allowed, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    if allowed is not None:
        p = jnp.where(allowed, p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_dtype), v5,
                    preferred_element_type=jnp.float32)
    o_new = o * alpha.transpose(0, 3, 1, 2)[..., None] + pv
    return o_new, m_new, l_new


def _zigzag_fwd(axis_name: str, axis_size: int, has_seg: bool,
                q, k, v, seg):
    """Zigzag-layout causal forward. Local q/k/v hold chunks
    (i, 2n-1-i) concatenated; returns (o, lse) in the same layout."""
    scale = q.shape[-1] ** -0.5
    n = axis_size
    my_idx = lax.axis_index(axis_name)
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    c = S // 2
    perm = _ring_perm(n)
    q5 = _group(q, Hkv)
    pos_q = _zz_positions(my_idx, n, c)

    o0 = jnp.zeros((B, S, Hkv, Hq // Hkv, D), jnp.float32)
    m0 = jnp.full((B, Hkv, Hq // Hkv, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, Hq // Hkv, S), jnp.float32)

    def step(carry, t):
        o, m, l, k, v, kseg = carry
        j = (my_idx - t) % n

        def local_block(_):
            # t == 0: the only masked step — full local attention with
            # the zigzag-position causal mask.
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k,
                           preferred_element_type=jnp.float32) * scale
            allowed = (pos_q[:, None] >= pos_q[None, :])
            if has_seg:
                allowed = allowed & _zz_seg_mask(seg, kseg)
            return _online_update(o, m, l, s, v, allowed, v.dtype)

        def low_only(_):
            # i > j: everything attends the block's low chunk; maskless.
            ka, va = k[:, :c], v[:, :c]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, ka,
                           preferred_element_type=jnp.float32) * scale
            allowed = (_zz_seg_mask(seg, kseg[:, :c]) if has_seg
                       else None)
            return _online_update(o, m, l, s, va, allowed, v.dtype)

        def high_rows(_):
            # i < j: only the high-chunk queries attend, to everything;
            # maskless. Low-row accumulators pass through untouched.
            qb = q5[:, c:]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k,
                           preferred_element_type=jnp.float32) * scale
            allowed = (_zz_seg_mask(seg[:, c:], kseg) if has_seg
                       else None)
            o_hi, m_hi, l_hi = _online_update(
                o[:, c:], m[..., c:], l[..., c:], s, v, allowed, v.dtype)
            return (jnp.concatenate([o[:, :c], o_hi], axis=1),
                    jnp.concatenate([m[..., :c], m_hi], axis=-1),
                    jnp.concatenate([l[..., :c], l_hi], axis=-1))

        o, m, l = lax.cond(
            t == 0, local_block,
            lambda _: lax.cond(my_idx > j, low_only, high_rows, _),
            None)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        kseg = lax.ppermute(kseg, axis_name, perm)
        return (o, m, l, k, v, kseg), None

    (o, m, l, k, v, _), _ = lax.scan(step, (o0, m0, l0, k, v, seg),
                                     jnp.arange(n))
    l_safe = jnp.maximum(l, 1e-30)
    o = (o / l_safe.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return o.reshape(B, S, Hq, D), lse


def _zigzag_bwd(axis_name: str, axis_size: int, has_seg: bool, res, do):
    q, k, v, o, lse, seg = res
    scale = q.shape[-1] ** -0.5
    n = axis_size
    my_idx = lax.axis_index(axis_name)
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    c = S // 2
    perm = _ring_perm(n)
    q5 = _group(q, Hkv)
    do5 = _group(do, Hkv)
    pos_q = _zz_positions(my_idx, n, c)
    delta = jnp.einsum("bqhgd,bqhgd->bhgq", do5.astype(jnp.float32),
                       _group(o, Hkv).astype(jnp.float32))

    dq0 = jnp.zeros(q5.shape, jnp.float32)
    dk0 = jnp.zeros_like(k, jnp.float32)
    dv0 = jnp.zeros_like(v, jnp.float32)

    def _block_grads(qp, dop, lsep, deltap, kp, vp, allowed):
        """Gradients of one maskless-or-masked sub-block.
        Returns (dq_part, dk_part, dv_part)."""
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qp, kp,
                       preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lsep[..., None])
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        dv = jnp.einsum("bhgqk,bqhgd->bkhd", p.astype(dop.dtype), dop,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", dop, vp,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - deltap[..., None]) * scale).astype(q.dtype)
        dq = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kp,
                        preferred_element_type=jnp.float32)
        dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qp,
                        preferred_element_type=jnp.float32)
        return dq, dk, dv

    def step(carry, t):
        dq, k, v, dk, dv, kseg = carry
        j = (my_idx - t) % n

        def local_block(_):
            allowed = (pos_q[:, None] >= pos_q[None, :])
            if has_seg:
                allowed = allowed & _zz_seg_mask(seg, kseg)
            dq_p, dk_p, dv_p = _block_grads(q5, do5, lse, delta, k, v,
                                            allowed)
            return dq + dq_p, dk + dk_p, dv + dv_p

        def low_only(_):
            allowed = (_zz_seg_mask(seg, kseg[:, :c]) if has_seg
                       else None)
            dq_p, dk_p, dv_p = _block_grads(q5, do5, lse, delta,
                                            k[:, :c], v[:, :c], allowed)
            zeros_k = jnp.zeros_like(dk[:, c:])
            return (dq + dq_p,
                    dk + jnp.concatenate([dk_p, zeros_k], axis=1),
                    dv + jnp.concatenate([dv_p, zeros_k], axis=1))

        def high_rows(_):
            allowed = (_zz_seg_mask(seg[:, c:], kseg) if has_seg
                       else None)
            dq_p, dk_p, dv_p = _block_grads(
                q5[:, c:], do5[:, c:], lse[..., c:], delta[..., c:],
                k, v, allowed)
            dq_new = jnp.concatenate([dq[:, :c], dq[:, c:] + dq_p],
                                     axis=1)
            return dq_new, dk + dk_p, dv + dv_p

        dq, dk, dv = lax.cond(
            t == 0, local_block,
            lambda _: lax.cond(my_idx > j, low_only, high_rows, _),
            None)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        kseg = lax.ppermute(kseg, axis_name, perm)
        return (dq, k, v, dk, dv, kseg), None

    (dq, k, v, dk, dv, _), _ = lax.scan(step, (dq0, k, v, dk0, dv0, seg),
                                        jnp.arange(n))
    dseg = np.zeros(seg.shape, dtype=jax.dtypes.float0)
    return (dq.reshape(B, S, Hq, D).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype), dseg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _zigzag_attn(axis_name: str, axis_size: int, has_seg: bool,
                 q, k, v, seg):
    o, _ = _zigzag_fwd(axis_name, axis_size, has_seg, q, k, v, seg)
    return o


def _zigzag_attn_fwd(axis_name, axis_size, has_seg, q, k, v, seg):
    o, lse = _zigzag_fwd(axis_name, axis_size, has_seg, q, k, v, seg)
    return o, (q, k, v, o, lse, seg)


_zigzag_attn.defvjp(_zigzag_attn_fwd, _zigzag_bwd)


def zigzag_ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                          batch_axes=("dp", "fsdp"),
                          heads_axis: Optional[str] = "tp",
                          segment_ids=None):
    """Load-balanced CAUSAL ring attention over `axis`. Inputs must be
    in the zigzag layout (`zigzag_permute` along the sequence, together
    with positions/segment ids); the output stays in that layout, so a
    model that permutes once at the input never pays a resharding
    (the loss is order-invariant under a jointly-permuted mask)."""
    n = mesh.shape[axis]
    if q.shape[1] % (2 * n) != 0:
        raise ValueError(
            f"zigzag needs seq divisible by 2*{axis}={2 * n}, got "
            f"{q.shape[1]}")
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(f"q heads {q.shape[2]} not a multiple of kv "
                         f"heads {k.shape[2]}")
    q_spec, kv_spec, seg_spec = _qkv_specs(mesh, axis, batch_axes,
                                           heads_axis, q, k)
    has_seg = segment_ids is not None
    seg = segment_ids if has_seg else _dummy_seg(q)
    fn = jax.shard_map(
        functools.partial(_zigzag_attn, axis, n, has_seg),
        mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec, seg_spec),
        out_specs=q_spec, check_vma=False)
    return fn(q, k, v, seg)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) local body
# ---------------------------------------------------------------------------

def _ulysses_local(axis_name: str, axis_size: int, causal: bool,
                   has_seg: bool, q, k, v, seg):
    """[B, S/n, H, D] local -> attention over full seq on H/n heads."""
    from skypilot_tpu.ops import attention as attn_ops
    # seq-sharded -> head-sharded: split heads (axis 2), concat seq (axis 1)
    q = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    seg_full = None
    if has_seg:
        # The id vector is tiny: gather the full sequence's ids locally.
        seg_full = lax.all_gather(seg, axis_name, axis=1, tiled=True)
    o = attn_ops.gqa_attention(q, k, v, causal=causal,
                               segment_ids=seg_full)
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


# ---------------------------------------------------------------------------
# Public API (global arrays, jit-compatible: shard_map inside jit)
# ---------------------------------------------------------------------------

def _batch_spec(batch_axes, mesh: Mesh, b: int):
    """Largest prefix of batch_axes whose product divides b (else None)."""
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    keep = []
    prod = 1
    for a in batch_axes or ():
        prod *= mesh.shape[a]
        if b % prod != 0:
            break
        keep.append(a)
    return tuple(keep) if keep else None


def _qkv_specs(mesh: Mesh, axis: str, batch_axes, heads_axis, q, k):
    """Shard specs with the same divisibility fallback as sharding.spec_for:
    a dim the mapped axis does not divide is replicated, not an error.

    Heads sharding is all-or-nothing across q AND kv: sharding q heads
    while replicating kv heads would re-pair grouped (GQA) heads with the
    wrong kv head inside each shard.
    """
    bspec = _batch_spec(batch_axes, mesh, q.shape[0])
    hspec = heads_axis
    if (heads_axis is None
            or q.shape[2] % mesh.shape[heads_axis] != 0
            or k.shape[2] % mesh.shape[heads_axis] != 0):
        hspec = None
    q_spec = P(bspec, axis, hspec, None)
    kv_spec = P(bspec, axis, hspec, None)
    seg_spec = P(bspec, axis)
    return q_spec, kv_spec, seg_spec


def _dummy_seg(q):
    return jnp.zeros((q.shape[0], q.shape[1]), jnp.int32)


def local_attention(q, k, v, mesh: Optional[Mesh], causal: bool = True,
                    batch_axes=("dp", "fsdp"),
                    heads_axis: Optional[str] = "tp",
                    segment_ids=None):
    """Attention with the sequence UNSHARDED under a mesh (no context
    parallelism; ``mesh=None`` is the plain single-device call):
    every (batch row, head) is independent, so the call
    runs per shard of the batch and heads axes. The einsum path needs
    no help — the SPMD compiler partitions it — but the Pallas flash
    kernel does: "Mosaic kernels cannot be automatically partitioned",
    so where the shapes pick the kernel it is wrapped in a
    ``shard_map`` over those two axes (same divisibility fallback as
    the ring: a dim its axis does not divide is replicated)."""
    from skypilot_tpu.ops import attention as attn_ops
    if mesh is None or mesh.size == 1 or not attn_ops.uses_flash(
            q.shape[1], q.shape[-1]):
        return attn_ops.gqa_attention(q, k, v, causal=causal,
                                      segment_ids=segment_ids)
    q_spec, kv_spec, seg_spec = _qkv_specs(mesh, None, batch_axes,
                                           heads_axis, q, k)
    has_seg = segment_ids is not None
    seg = segment_ids if has_seg else _dummy_seg(q)
    fn = jax.shard_map(
        lambda q, k, v, seg: attn_ops.gqa_attention(
            q, k, v, causal=causal,
            segment_ids=seg if has_seg else None),
        mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec, seg_spec),
        out_specs=q_spec, check_vma=False)
    return fn(q, k, v, seg)


def ring_attention(q, k, v, mesh: Mesh, causal: bool = True,
                   axis: str = "sp", batch_axes=("dp", "fsdp"),
                   heads_axis: Optional[str] = "tp",
                   segment_ids=None):
    """Ring attention over `axis`. q [B,S,Hq,D]; k/v [B,S,Hkv,D] (GQA ok:
    Hq % Hkv == 0; unrepeated K/V heads circulate the ring).
    ``segment_ids`` [B, S] enables packed-sequence masking."""
    n = mesh.shape[axis]
    if q.shape[1] % n != 0:
        raise ValueError(f"seq {q.shape[1]} not divisible by {axis}={n}")
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(f"q heads {q.shape[2]} not a multiple of kv heads "
                         f"{k.shape[2]}")
    q_spec, kv_spec, seg_spec = _qkv_specs(mesh, axis, batch_axes,
                                           heads_axis, q, k)
    has_seg = segment_ids is not None
    seg = segment_ids if has_seg else _dummy_seg(q)
    fn = jax.shard_map(
        functools.partial(_ring_attn, axis, n, causal, has_seg),
        mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec, seg_spec),
        out_specs=q_spec, check_vma=False)
    return fn(q, k, v, seg)


def ulysses_attention(q, k, v, mesh: Mesh, causal: bool = True,
                      axis: str = "sp", batch_axes=("dp", "fsdp"),
                      heads_axis: Optional[str] = "tp",
                      segment_ids=None):
    """All-to-all (Ulysses) sequence parallelism over `axis`.

    Requires per-shard head counts (q and kv) divisible by the sp size:
    the all_to_all converts the seq shard into a head shard.
    ``segment_ids`` [B, S] enables packed-sequence masking.
    """
    n = mesh.shape[axis]
    if q.shape[1] % n != 0:
        raise ValueError(f"seq {q.shape[1]} not divisible by {axis}={n}")
    q_spec, kv_spec, seg_spec = _qkv_specs(mesh, axis, batch_axes,
                                           heads_axis, q, k)
    tp = mesh.shape[heads_axis] if q_spec[2] is not None else 1
    for name, arr in (("q", q), ("kv", k)):
        local_heads = arr.shape[2] // (tp if arr.shape[2] % tp == 0 else 1)
        if local_heads % n != 0:
            raise ValueError(
                f"{name} heads/shard = {local_heads} not divisible by "
                f"{axis}={n}; use ring_attention instead")
    has_seg = segment_ids is not None
    seg = segment_ids if has_seg else _dummy_seg(q)
    fn = jax.shard_map(
        functools.partial(_ulysses_local, axis, n, causal, has_seg),
        mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec, seg_spec),
        out_specs=q_spec, check_vma=False)
    return fn(q, k, v, seg)


def context_parallel_attention(q, k, v, mesh: Mesh, causal: bool = True,
                               impl: str = "ring", **kw):
    """Dispatch: impl in {"ring", "ulysses"}."""
    if impl == "ulysses":
        return ulysses_attention(q, k, v, mesh, causal=causal, **kw)
    return ring_attention(q, k, v, mesh, causal=causal, **kw)
