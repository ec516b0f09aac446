"""The gated delta rule (Gated DeltaNet) and the short causal convolution
in front of it, in plain ``jax.numpy``.

Per head, with a state ``S`` [d_v, d_k] in float32, a decay ``a_t`` in
(0, 1] (given as its log ``g_t <= 0``) and a write strength ``b_t``::

    S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T
    o_t = S_t q_t

Two forms of the same recurrence:

* :func:`step_rule` — one token (decode): ``S' = a S``, ``S_t = S' +
  b (v - S' k) k^T``; reads and writes the state once.
* :func:`chunk_rule` — a run of tokens (prefill), the WY / UT-transform
  of the Gated DeltaNet paper over sub-chunks of :data:`CHUNK` tokens.
  With ``gamma_t`` the decay accumulated inside the sub-chunk and ``w_t
  = b_t (v_t - a_t S_{t-1} k_t)`` the "pseudo-value" a token really
  writes, ``S_t = gamma_t S_0 + sum_{i<=t} (gamma_t / gamma_i) w_i
  k_i^T``, so the ``w`` of a sub-chunk solve ``(I + A) W = b (V - gamma K
  S_0^T)`` with ``A[t, i] = b_t (gamma_t / gamma_i) (k_t . k_i)`` strictly
  lower triangular. The inverse of ``I + A`` is taken per sub-chunk for
  all of them at once (:func:`_inv_unit_lower`: forward substitution on
  16-row diagonal blocks, merged by products); what is left is a scan
  over the sub-chunks with three MXU-shaped products against the carried
  state. Every ratio of decays is formed as ``exp`` of a difference that
  is ``<= 0``, so a decay of ~0 underflows to 0 and nothing overflows.

A token with ``g = 0`` and ``b = 0`` leaves the state as it is and
writes nothing: that is what padding is given (:func:`mask_pad`).

All arithmetic here is float32 and its products run at
``Precision.HIGHEST``: on the TPU a float32 product otherwise rounds its
operands to bf16, which would keep the state in bf16 at every read. The
rule is 7 H d_k d_v FLOPs a token and layer, a few percent of the layer's
projections, so the passes cost little.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Tokens a sub-chunk of the chunked form holds.
CHUNK = 64
_BASE = 16          # rows the forward substitution walks one by one
_HI = lax.Precision.HIGHEST


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def mask_pad(g: jax.Array, beta: jax.Array, valid: jax.Array):
    """``(g, beta)`` [B, T, H] with the tokens that are not ``valid``
    [B, T] made no-ops: no decay, no write."""
    keep = valid[..., None]
    return jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)


# ---------------------------------------------------------------------------
# One token
# ---------------------------------------------------------------------------

@jax.named_scope("delta_rule")
def step_rule(q, k, v, g, beta, state) -> Tuple[jax.Array, jax.Array]:
    """One token for every row: ``q``, ``k`` [B, H, d_k] (normalised),
    ``v`` [B, H, d_v], ``g`` (log decay) and ``beta`` [B, H], ``state``
    [B, H, d_v, d_k] float32 -> (``o`` [B, H, d_v] float32, state')."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s = state * jnp.exp(g.astype(f32))[..., None, None]
    sk = jnp.einsum("bhvk,bhk->bhv", s, k, precision=_HI)
    w = beta.astype(f32)[..., None] * (v - sk)
    s = s + w[..., :, None] * k[..., None, :]
    return jnp.einsum("bhvk,bhk->bhv", s, q, precision=_HI), s


# ---------------------------------------------------------------------------
# A run of tokens
# ---------------------------------------------------------------------------

def _inv_unit_lower(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., n, n]
    (n a power of two times :data:`_BASE`, or at most it). Forward
    substitution row by row on the diagonal blocks of :data:`_BASE` rows,
    all blocks at once; two blocks merge as ``[[T1, 0], [-T2 a21 T1,
    T2]]``."""
    n = a.shape[-1]
    if n <= _BASE:
        t = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
        for i in range(1, n):
            # Rows >= i of ``t`` are still the identity's and a[i, j >= i]
            # is zero, so the whole row of ``a`` may multiply.
            row = jnp.einsum("...j,...jk->...k", a[..., i, :], t,
                             precision=_HI)
            t = t.at[..., i, :].add(-row)
        return t
    h = n // 2
    t = _inv_unit_lower(jnp.stack([a[..., :h, :h], a[..., h:, h:]], axis=-3))
    t1, t2 = t[..., 0, :, :], t[..., 1, :, :]
    t21 = -jnp.einsum("...ij,...jk,...kl->...il", t2, a[..., h:, :h], t1,
                      precision=_HI)
    top = jnp.concatenate([t1, jnp.zeros_like(t21)], axis=-1)
    return jnp.concatenate(
        [top, jnp.concatenate([t21, t2], axis=-1)], axis=-2)


@jax.named_scope("delta_rule")
def chunk_rule(q, k, v, g, beta, state, chunk: int = CHUNK
               ) -> Tuple[jax.Array, jax.Array]:
    """``T`` tokens for every row, equal to ``T`` calls of
    :func:`step_rule` up to rounding: ``q``, ``k`` [B, T, H, d_k]
    (normalised), ``v`` [B, T, H, d_v], ``g``, ``beta`` [B, T, H],
    ``state`` [B, H, d_v, d_k] float32 -> (``o`` [B, T, H, d_v] float32,
    state after the last token). ``T`` need not be a multiple of
    ``chunk``: the tail is padded with no-op tokens."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    n = -(-T // chunk)
    pad = n * chunk - T

    def heads_first(x):        # [B, T, H, ...] -> [B, H, n, chunk, ...]
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (heads_first(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                           # [B, H, n, C]
    diff = gc[..., :, None] - gc[..., None, :]            # g_t - g_i
    t_ix = jnp.arange(chunk)
    lower = t_ix[:, None] >= t_ix[None, :]
    strict = t_ix[:, None] > t_ix[None, :]
    kk = jnp.einsum("bhntk,bhnik->bhnti", k, k, precision=_HI)
    qk = jnp.einsum("bhntk,bhnik->bhnti", q, k, precision=_HI)
    a = jnp.exp(jnp.where(strict, diff, -jnp.inf)) * kk * beta[..., None]
    p = jnp.exp(jnp.where(lower, diff, -jnp.inf)) * qk
    t = _inv_unit_lower(a)
    decay = jnp.exp(gc)[..., None]
    u = jnp.einsum("bhnti,bhniv->bhntv", t, beta[..., None] * v,
                   precision=_HI)
    kc = jnp.einsum("bhnti,bhnik->bhntk", t, (beta[..., None] * decay) * k,
                    precision=_HI)
    qg = decay * q
    kd = jnp.exp(gc[..., -1:] - gc)[..., None] * k
    g_last = jnp.exp(gc[..., -1])                         # [B, H, n]

    def sub_chunk(s, xs):
        u_n, kc_n, qg_n, kd_n, p_n, gl_n = xs
        w = u_n - jnp.einsum("bhtk,bhvk->bhtv", kc_n, s, precision=_HI)
        o = jnp.einsum("bhtk,bhvk->bhtv", qg_n, s, precision=_HI) \
            + jnp.einsum("bhti,bhiv->bhtv", p_n, w, precision=_HI)
        s = s * gl_n[..., None, None] \
            + jnp.einsum("bhtv,bhtk->bhvk", w, kd_n, precision=_HI)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (u, kc, qg, kd, p, g_last))
    state, o = lax.scan(sub_chunk, state.astype(f32), xs)
    o = jnp.moveaxis(o, 0, 2)                             # [B, H, n, C, dv]
    o = jnp.moveaxis(o, 1, 3).reshape(B, n * chunk, H, dv)
    return o[:, :T], state


# ---------------------------------------------------------------------------
# The short causal convolution, with a carried tail
# ---------------------------------------------------------------------------

def carried_conv(x: jax.Array, w: jax.Array, tail: jax.Array,
                 n_valid: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution of width ``K`` over time, no
    activation: ``y_t = sum_j w[j] xx[t + j]`` with ``xx`` the carried
    ``tail`` (the ``K - 1`` inputs before ``x_0``) followed by ``x``, so
    ``w[K - 1]`` multiplies the current input. ``x`` [B, T, C], ``w``
    [K, C], ``tail`` [B, K - 1, C], ``n_valid`` [B] the real tokens of
    each row -> (``y`` [B, T, C] float32, the tail after each row's LAST
    REAL token: ``xx[n_valid : n_valid + K - 1]``, which reaches back
    into the carried tail when fewer than ``K - 1`` tokens are real)."""
    K = w.shape[0]
    T = x.shape[1]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    acc = sum(xx[:, j:j + T].astype(jnp.float32)
              * w[j].astype(jnp.float32) for j in range(K))
    new_tail = jax.vmap(
        lambda row, at: lax.dynamic_slice_in_dim(row, at, K - 1, 0))(
            xx, n_valid)
    return acc, new_tail.astype(tail.dtype)


def causal_conv(x: jax.Array, w: jax.Array, tail: jax.Array,
                n_valid: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """:func:`carried_conv`, then SiLU, in ``x``'s dtype (the linear
    mixer's convolution; ``models/lfm2_moe.py``'s has no activation)."""
    acc, new_tail = carried_conv(x, w, tail, n_valid)
    return jax.nn.silu(acc).astype(x.dtype), new_tail
