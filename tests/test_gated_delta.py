"""The gated delta rule (``skypilot_tpu/ops/gated_delta.py``) on the
CPU in float32: its chunked form, its one-token form and a float64
recurrence written here from the equation are one function.

Inputs are drawn where the rule is hardest: write strengths up to 2 (the
``allow_neg_eigval`` range: a transition eigenvalue of -1), decays from
~0 (a forgotten state) to ~1 (nothing forgotten), keys on the unit
sphere. TOL is float32 against float64 over some hundred tokens with a
non-normal transition: the chunked form's triangular solve amplifies
rounding by the ``b`` values it is given (1e-5 observed at ``|o|`` ~
0.7); the one-token form is the recurrence itself (1e-7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import gated_delta as gd

TOL = 1e-4
STEP_TOL = 2e-6
B, H, DK, DV = 2, 3, 24, 40


def _inputs(T, seed=0):
    rng = np.random.default_rng(seed)
    q = gd.l2_normalize(jnp.asarray(rng.normal(size=(B, T, H, DK)),
                                    jnp.float32)) * DK ** -0.5
    k = gd.l2_normalize(jnp.asarray(rng.normal(size=(B, T, H, DK)),
                                    jnp.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, DV)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, size=(B, T, H)), jnp.float32)
    # Decays of every size: u ** 0.01 ~ 1, u ** 1, u ** 30 ~ 0.
    alpha = rng.uniform(0, 1, size=(B, T, H)) \
        ** rng.choice([0.01, 1.0, 30.0], size=(B, T, H))
    g = jnp.log(jnp.asarray(np.maximum(alpha, 1e-30), jnp.float32))
    state = jnp.asarray(rng.normal(size=(B, H, DV, DK)), jnp.float32)
    return q, k, v, g, beta, state


def _recurrence(q, k, v, g, beta, state):
    """S_t = a S (I - b k k^T) + b v k^T, o_t = S_t q, in float64."""
    q, k, v, g, beta, S = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, state))
    T = q.shape[1]
    o = np.zeros((B, T, H, DV))
    eye = np.eye(DK)
    for t in range(T):
        for b in range(B):
            for h in range(H):
                kt, bt = k[b, t, h], beta[b, t, h]
                S[b, h] = np.exp(g[b, t, h]) * S[b, h] @ (
                    eye - bt * np.outer(kt, kt)) + bt * np.outer(v[b, t, h],
                                                                 kt)
                o[b, t, h] = S[b, h] @ q[b, t, h]
    return o, S


def _steps(q, k, v, g, beta, state):
    def one(s, xs):
        o, s = gd.step_rule(*xs, s)
        return s, o
    state, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


@pytest.mark.parametrize("T", [64, 128, 150, 37, 1],
                         ids=lambda t: f"T{t}")
def test_chunked_equals_one_token_equals_recurrence(T):
    """Lengths that are and are not multiples of the 64-token
    sub-chunk, down to one token."""
    args = _inputs(T, seed=T)
    want_o, want_s = _recurrence(*args)
    assert np.abs(want_o).max() > 0.3
    got_o, got_s = jax.jit(gd.chunk_rule)(*args)
    assert np.abs(np.asarray(got_o) - want_o).max() < TOL
    assert np.abs(np.asarray(got_s) - want_s).max() < TOL
    step_o, step_s = jax.jit(_steps)(*args)
    assert np.abs(np.asarray(step_o) - want_o).max() < STEP_TOL
    assert np.abs(np.asarray(step_s) - want_s).max() < STEP_TOL


def test_state_carried_across_two_calls():
    """A run cut anywhere and continued from the returned state is the
    run in one call."""
    q, k, v, g, beta, state = _inputs(150, seed=7)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    cut = 70
    o1, s1 = gd.chunk_rule(q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut],
                           beta[:, :cut], state)
    o2, s2 = gd.chunk_rule(q[:, cut:], k[:, cut:], v[:, cut:], g[:, cut:],
                           beta[:, cut:], s1)
    got = np.concatenate([np.asarray(o1), np.asarray(o2)], axis=1)
    assert np.abs(got - want_o).max() < TOL
    assert np.abs(np.asarray(s2) - want_s).max() < TOL


def test_padded_tail_leaves_the_state_of_the_last_real_token():
    """Rows of different true lengths in one padded call: pad tokens (no
    decay, no write) change neither the state nor the real outputs,
    whatever garbage their q, k, v hold."""
    T, lens = 96, np.array([96, 41])
    q, k, v, g, beta, state = _inputs(T, seed=9)
    valid = jnp.arange(T)[None, :] < jnp.asarray(lens)[:, None]
    g_m, beta_m = gd.mask_pad(g, beta, valid)
    got_o, got_s = gd.chunk_rule(q, k, v, g_m, beta_m, state)
    for b, n in enumerate(lens):
        want_o, want_s = _recurrence(q[:, :n], k[:, :n], v[:, :n], g[:, :n],
                                     beta[:, :n], state)
        assert np.abs(np.asarray(got_o)[b, :n] - want_o[b]).max() < TOL
        assert np.abs(np.asarray(got_s)[b] - want_s[b]).max() < TOL


def test_decay_of_zero_and_write_of_two_stay_finite():
    """The corners: a decay that underflows to 0 forgets everything
    without a NaN (ratios of decays are exp of differences <= 0), and
    b = 2 reflects the state along k."""
    T = 64
    q, k, v, g, beta, state = _inputs(T, seed=11)
    g = g.at[:, 10].set(-200.0)                 # exp(-200) == 0 in float32
    beta = beta.at[:, 20].set(2.0)
    got_o, got_s = gd.chunk_rule(q, k, v, g, beta, state)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.abs(np.asarray(got_o) - want_o).max() < TOL
    assert np.abs(np.asarray(got_s) - want_s).max() < TOL
    # Everything before token 10 is gone: another start state, same end.
    _, other = gd.chunk_rule(q, k, v, g, beta, state * 3.0 + 1.0)
    assert np.abs(np.asarray(other) - np.asarray(got_s)).max() < 1e-6


@pytest.mark.parametrize("n", [16, 32, 64])
def test_unit_lower_inverse(n):
    rng = np.random.default_rng(n)
    a = np.tril(rng.normal(size=(3, n, n)), -1).astype(np.float32) * 0.5
    got = np.asarray(gd._inv_unit_lower(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(n) + a.astype(np.float64))
    assert np.abs(got - want).max() < 1e-3 * np.abs(want).max()


def _conv_reference(x, w, tail):
    """y_t = silu(sum_j w[j] xx[t + j]), xx = tail ++ x, in float64."""
    xx = np.concatenate([tail, x], axis=1).astype(np.float64)
    K, T = w.shape[0], x.shape[1]
    acc = sum(xx[:, j:j + T] * w[j].astype(np.float64) for j in range(K))
    return acc / (1.0 + np.exp(-acc)), xx


@pytest.mark.parametrize("lens", [(12, 12), (12, 5), (2, 1), (0, 3)],
                         ids=["full", "padded", "shorter-than-tail",
                              "no-real-token"])
def test_causal_conv_carries_the_tail_of_the_last_real_token(lens):
    rng = np.random.default_rng(3)
    T, C, K = 12, 10, 4
    x = rng.normal(size=(2, T, C)).astype(np.float32)
    w = rng.normal(size=(K, C)).astype(np.float32)
    tail = rng.normal(size=(2, K - 1, C)).astype(np.float32)
    y, new_tail = gd.causal_conv(jnp.asarray(x), jnp.asarray(w),
                                 jnp.asarray(tail), jnp.asarray(lens))
    want, xx = _conv_reference(x, w, tail)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    for b, n in enumerate(lens):
        # The last K - 1 inputs before position n, reaching back into
        # the carried tail where fewer than K - 1 tokens are real.
        assert np.array_equal(np.asarray(new_tail)[b], xx[b, n:n + K - 1]
                              .astype(np.float32))


def test_causal_conv_in_two_calls_equals_one():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 20, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    zero = jnp.zeros((1, 3, 6), jnp.float32)
    whole, _ = gd.causal_conv(x, w, zero, jnp.asarray([20]))
    a, tail = gd.causal_conv(x[:, :7], w, zero, jnp.asarray([7]))
    b, _ = gd.causal_conv(x[:, 7:], w, tail, jnp.asarray([13]))
    got = jnp.concatenate([a, b], axis=1)
    assert float(jnp.abs(got - whole).max()) < 1e-6
