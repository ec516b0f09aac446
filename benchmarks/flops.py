"""Operations and bytes an algorithm REQUIRES, from shapes alone.

Required work only: recomputation (rematerialised forwards, the flash
kernel's second forward pass inside the backward) is executed work, not
required work, so a share of a peak computed from these can never pass
100 % while the time it is divided by covers everything executed.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmarks.peaks import peaks_for

LORA_TARGETS = ("wq", "wk", "wv", "wo")


def block_matmul_params(dims) -> int:
    """Weights of the seven matrices of one decoder block."""
    d, ff = dims.d_model, dims.d_ff
    attn = 2 * d * dims.n_heads * dims.head_dim \
        + 2 * d * dims.n_kv_heads * dims.head_dim
    return attn + 3 * d * ff


def lora_params(dims, rank: int,
                targets: Iterable[str] = LORA_TARGETS) -> int:
    d = dims.d_model
    q = dims.n_heads * dims.head_dim
    kv = dims.n_kv_heads * dims.head_dim
    out = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    return dims.n_layers * sum(rank * (out[t][0] + out[t][1])
                               for t in targets)


def attention_flops_per_sequence(dims, seq: int, backward: bool) -> int:
    """Causal attention of ONE layer over one sequence: the forward is
    two matmuls (QK^T and PV) over the lower triangle, 2 FLOPs a
    multiply-add: 2 * 2 * seq^2/2 * heads * head_dim; the backward is
    four such matmuls (dV, dP, dQ, dK)."""
    one_matmul = 2 * (seq * seq // 2) * dims.n_heads * dims.head_dim
    return (4 if backward else 2) * one_matmul


def qlora_train_flops_per_token(dims, seq: int, program) -> float:
    """Forward + backward through a FROZEN base with LoRA factors of
    rank ``program["lora_rank"]``:

    * base matmuls (blocks + head): forward 2 FLOPs a weight, backward
      2 more for the activation gradient — no weight gradient exists for
      a frozen matrix (full training would be 6);
    * LoRA factors: 2 forward + 4 backward a weight;
    * causal attention: forward 2 matmuls, backward 4, per layer.
    """
    base = dims.n_layers * block_matmul_params(dims) \
        + dims.d_model * dims.vocab_size
    attn = dims.n_layers * (
        attention_flops_per_sequence(dims, seq, False)
        + attention_flops_per_sequence(dims, seq, True)) / seq
    return 4.0 * base \
        + 6.0 * lora_params(dims, int(program["lora_rank"])) + attn


def full_train_flops_per_token(dims, seq: int, program=None) -> float:
    """Forward + backward with every weight trained: 6 FLOPs a matmul
    weight, plus causal attention."""
    base = dims.n_layers * block_matmul_params(dims) \
        + dims.d_model * dims.vocab_size
    attn = dims.n_layers * (
        attention_flops_per_sequence(dims, seq, False)
        + attention_flops_per_sequence(dims, seq, True)) / seq
    return 6.0 * base + attn


def flash_attention_step_work(dims, batch: int, seq: int,
                              bytes_per_el: int = 2) -> Dict[str, float]:
    """Required FLOPs and HBM bytes of all the attention of one training
    step (forward once + backward once, every layer, every row).

    Bytes: the forward reads q, k, v and writes o; the backward reads q,
    k, v, o, do and writes dq, dk, dv. (Softmax statistics are a
    head_dim-th of that and are left out, which only lowers the floor.)
    """
    q_el = seq * dims.n_heads * dims.head_dim
    kv_el = seq * dims.n_kv_heads * dims.head_dim
    fwd_bytes = (2 * q_el + 2 * kv_el) * bytes_per_el
    bwd_bytes = (4 * q_el + 4 * kv_el) * bytes_per_el
    rows = batch * dims.n_layers
    return {
        "flops": rows * (attention_flops_per_sequence(dims, seq, False)
                         + attention_flops_per_sequence(dims, seq, True)),
        "bytes": rows * (fwd_bytes + bwd_bytes)}


def least_seconds(work: Dict[str, float], device_kind: str,
                  flops_key: str = "bf16_flops") -> Dict[str, float]:
    """The roofline floor: the larger of FLOPs over peak FLOP/s and
    bytes over peak bytes/s, and which of the two binds."""
    peaks = peaks_for(device_kind)
    by_compute = work["flops"] / peaks[flops_key]
    by_memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_compute, by_memory),
            "bound": "compute" if by_compute >= by_memory else "memory",
            "compute_s": by_compute, "memory_s": by_memory}
