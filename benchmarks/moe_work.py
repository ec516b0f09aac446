"""Operations and bytes the expert layer of the ``glm_moe`` family
REQUIRES, from shapes alone (``flops.py``'s rule: required work only, so
a share of a peak made from these cannot pass 100 % while the time it
is divided by covers everything executed).
"""

from __future__ import annotations

from typing import Dict


def expected_experts_touched(dims, rows: float) -> float:
    """Distinct routed experts that ``rows`` tokens choose in one layer
    when each picks ``experts_per_tok`` of ``n_routed_experts`` without
    preference: ``E (1 - (1 - K / E) ** rows)``. Seeded weights route
    almost evenly (PERF.md section 7: imbalance is untested on the
    chip), so the expectation stands for the count."""
    e, k = dims.n_routed_experts, dims.experts_per_tok
    return e * (1.0 - (1.0 - k / e) ** max(rows, 0.0))


def decode_expert_read_work(dims, rows: float, bytes_per_el: int = 2
                            ) -> Dict[str, float]:
    """One decode step over ``rows`` live rows, every expert layer: the
    expert weights the step must READ (each touched expert's three
    matrices once) and the multiply-adds of the rows' own choices.
    Bound by bytes at any row count a slot table allows (a v5e does the
    33-row FLOPs in 1/200 of the time it reads 1.2 GB a layer)."""
    touched = expected_experts_touched(dims, rows)
    per_expert = dims.expert_params()
    return {"bytes": dims.n_moe_layers * touched * per_expert * bytes_per_el,
            "flops": dims.n_moe_layers * rows * dims.experts_per_tok
            * 2.0 * per_expert}
