"""Operations and bytes the gated delta rule of the ``olmo_hybrid``
family REQUIRES, from shapes alone (``flops.py``'s rule: required work
only, so a share of a peak made from these cannot pass 100 % while the
time it is divided by covers everything executed).

Per token, head and linear layer the recurrence ``S' = a S``, ``S_t =
S' + b (v - S' k) k^T``, ``o = S_t q`` is 7 ``d_v d_k`` operations: one
for the decay, two each for ``S' k``, the rank-one update and ``S_t q``.
A chunked form executes more (the products inside a sub-chunk, the
triangular inverse); none of that is required.
"""

from __future__ import annotations

from typing import Dict

STATE_BYTES_PER_EL = 4           # the state is float32 wherever it lives


def rule_flops_per_token(dims) -> float:
    """One token, one linear layer, every head."""
    return 7.0 * dims.lin_heads * dims.lin_k_dim * dims.lin_v_dim


def state_bytes(dims) -> float:
    """One slot's state of one linear layer."""
    return float(dims.lin_heads * dims.lin_v_dim * dims.lin_k_dim
                 * STATE_BYTES_PER_EL)


def operand_bytes_per_token(dims, bytes_per_el: int = 2) -> float:
    """``q``, ``k``, ``v`` in and ``o`` out of the rule: one token, one
    linear layer, in the compute dtype."""
    return float(dims.lin_heads * 2 * (dims.lin_k_dim + dims.lin_v_dim)
                 * bytes_per_el)


def decode_state_work(dims, rows: float, bytes_per_el: int = 2
                      ) -> Dict[str, float]:
    """One decode step over ``rows`` live rows, every linear layer: each
    live row's state is read once and written once, its operands come
    and go, and the one-token rule runs. Bound by bytes: a row moves 4.4
    MB a layer for 3.9 MFLOP. (The convolution's tails, 3 % of a state,
    move outside the ``delta_rule`` scope whose time this is divided by,
    and are left out.)"""
    per_row = 2.0 * state_bytes(dims) \
        + operand_bytes_per_token(dims, bytes_per_el)
    return {"bytes": dims.n_lin_layers * rows * per_row,
            "flops": dims.n_lin_layers * rows * rule_flops_per_token(dims)}


def prefill_rule_work(dims, tokens: float, states_moved: float,
                      bytes_per_el: int = 2) -> Dict[str, float]:
    """The rule over ``tokens`` prompt tokens, every linear layer, in
    programs that read or wrote a slot's state ``states_moved`` times in
    all (a chunk that continues a resident state reads and writes it, a
    first chunk or a wave row only writes it)."""
    return {"flops": dims.n_lin_layers * tokens * rule_flops_per_token(dims),
            "bytes": dims.n_lin_layers * (
                tokens * operand_bytes_per_token(dims, bytes_per_el)
                + states_moved * state_bytes(dims))}
