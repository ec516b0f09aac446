"""Operations and bytes the sliding-window attention layers of the
``afmoe`` family REQUIRE, from shapes alone (``flops.py``'s rule:
required work only, so a share of a peak made from these cannot pass
100 % while the time it is divided by covers everything executed).

A window layer's query scores ``min(p + 1, window)`` key rows — itself
and what its window still holds — whatever holds them: a ring, paged
blocks freed behind the window, or a whole cache under a mask (which
executes more; none of that is required). Per key row and query: ``2
n_heads head_dim`` operations for the scores and as many for the
weighted values. At decode each of those key rows is READ once a step,
K and V: ``kv_row_bytes`` a row.
"""

from __future__ import annotations

from typing import Dict


def attn_flops_per_key(dims) -> float:
    """One query against one key row, every head: scores and values."""
    return 4.0 * dims.n_heads * dims.head_dim


def decode_ring_read_work(dims, window_rows: float) -> Dict[str, float]:
    """One decode step whose live slots hold ``window_rows`` rows in a
    window layer's view (the sum over them of ``min(rows, window)``),
    every window layer: those rows' K and V are read once, and the one
    query a slot scores them. Bound by bytes: 2048 B a row for 16 kFLOP."""
    return {"bytes": dims.n_win_layers * window_rows * dims.kv_row_bytes,
            "flops": dims.n_win_layers * window_rows
            * attn_flops_per_key(dims)}


def prefill_window_attn_work(dims, window_keys: float, tokens: float
                             ) -> Dict[str, float]:
    """Prefill programs over ``tokens`` prompt tokens that must score
    ``window_keys`` key rows in all (the sum over the tokens of ``min(p
    + 1, window)``), every window layer: the products, and each token's
    q and o rows and its K and V row moved once. Bound by compute."""
    per_token = 2 * dims.n_heads * dims.head_dim * 2 + dims.kv_row_bytes
    return {"flops": dims.n_win_layers * window_keys
            * attn_flops_per_key(dims),
            "bytes": dims.n_win_layers * tokens * per_token}
