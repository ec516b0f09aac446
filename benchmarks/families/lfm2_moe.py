"""The ``lfm2_moe`` family: decoders whose layers are gated short
convolutions with a grouped-query attention layer every few, routed
experts (no shared one) after the leading dense layers and a tied head
(``lfm2_moe``: LFM2-8B-A1B), run by ``skypilot_tpu/models/lfm2_moe.py``
through ``infer/shortconv.py``.

``families/llama.py`` says what a family gives. This one serves only:
``train_program`` / ``train_reference`` are absent, and a training cell
of this family fails at its first call of them (ROADMAP M1: training the
expert block is what remains).

Its seeded weights are ``benchmarks/weights_lfm2_moe.py``, its plain
reference ``benchmarks/reference/lfm2_moe.py`` and, for the expert
layer it shares with the ``glm_moe`` family, its work counts
``benchmarks/moe_work.py`` (the names that file reads are here under
the same spelling).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

CONV, FULL = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """A configuration file's sizes under the names the family's own
    arithmetic (weights, reference, work counts) uses."""
    vocab_size: int
    d_model: int
    n_layers: int
    layer_types: Tuple[str, ...]
    n_heads: int
    n_kv_heads: int
    head_dim: int
    conv_kernel: int
    n_dense_layers: int
    d_ff: int
    moe_d_ff: int
    n_routed_experts: int
    experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rope_theta: float
    norm_eps: float
    max_seq_len: int

    def is_conv(self, layer: int) -> bool:
        return self.layer_types[layer] == CONV

    @property
    def n_conv_layers(self) -> int:
        return sum(t == CONV for t in self.layer_types)

    @property
    def n_full_layers(self) -> int:
        return self.n_layers - self.n_conv_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kv_token_bytes(self) -> int:
        """One token's K and V in every attention layer, bf16."""
        return self.n_full_layers * 2 * self.n_kv_heads * self.head_dim * 2

    @property
    def tail_bytes(self) -> int:
        """One slot's tail in ONE conv layer, bf16: the ``K - 1`` last
        inputs of its convolution."""
        return (self.conv_kernel - 1) * self.d_model * 2

    def conv_params(self) -> int:
        """One conv operator: in-projection, taps, out-projection."""
        d = self.d_model
        return 3 * d * d + self.conv_kernel * d + d * d

    def attn_params(self) -> int:
        d, q = self.d_model, self.n_heads * self.head_dim
        return (2 * d * q + 2 * d * self.n_kv_heads * self.head_dim
                + 2 * self.head_dim)

    def expert_params(self) -> int:
        """ONE routed expert's three matrices."""
        return 3 * self.d_model * self.moe_d_ff

    def dense_ffn_params(self) -> int:
        return 3 * self.d_model * self.d_ff

    def expert_ffn_params(self) -> int:
        """An expert layer's experts, router and selection bias."""
        e = self.n_routed_experts
        return e * self.expert_params() + self.d_model * e + e

    def num_params(self) -> int:
        """Tied: the embedding is the head."""
        return (self.n_conv_layers * self.conv_params()
                + self.n_full_layers * self.attn_params()
                + self.n_layers * 2 * self.d_model
                + self.n_dense_layers * self.dense_ffn_params()
                + self.n_moe_layers * self.expert_ffn_params()
                + self.vocab_size * self.d_model + self.d_model)


def dims(config: Dict[str, Any]) -> ModelDims:
    """From the source's own key names (the Hugging Face ``config.json``
    of ``lfm2_moe``)."""
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("rope_scaling", None), ("num_shared_experts", 0),
                      ("tie_word_embeddings", True)):
        if config.get(key, want) != want:
            raise SystemExit(f"{config.get('name')}: {key}="
                             f"{config.get(key)!r} is not built "
                             f"(wants {want!r})")
    n = int(config["num_hidden_layers"])
    types = tuple(config["layer_types"])[:n]
    if len(types) != n or any(t not in (CONV, FULL) for t in types):
        raise SystemExit(f"{config.get('name')}: layer_types must name "
                         f"{n} conv / full_attention layers")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return ModelDims(
        vocab_size=int(config["vocab_size"]), d_model=d, n_layers=n,
        layer_types=types, n_heads=h,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // h),
        conv_kernel=int(config["conv_L_cache"]),
        n_dense_layers=int(config["num_dense_layers"]),
        d_ff=int(config["intermediate_size"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        n_routed_experts=int(config["num_experts"]),
        experts_per_tok=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config.get("norm_topk_prob", True)),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


def register(config: Dict[str, Any], **overrides):
    """The configuration's sizes as the program's ``Lfm2MoeConfig``,
    registered under the configuration's name — in this process only."""
    from skypilot_tpu.models import lfm2_moe
    dims(config)                     # refuses what is not built
    cfg = lfm2_moe.from_published(config, **overrides)
    lfm2_moe.CONFIGS[config["name"]] = cfg
    return cfg


def serve_setup(config: Dict[str, Any], seed: int, say: Callable) -> None:
    """Register the configuration and hand the program the benchmark's
    seeded weights in place of the program's own random ones."""
    import jax

    from benchmarks import weights_lfm2_moe
    from skypilot_tpu.infer import engine as eng

    cfg = register(config)
    if config["precision"]["weights"] != "bf16":
        raise SystemExit(f"{config['name']}: the family serves bf16")

    def seeded_weights(cfg_, *, weights_int8=False, mesh=None, **_):
        if mesh is not None or weights_int8 or cfg_ is not cfg:
            raise SystemExit("the benchmark's weights are for the "
                             "one-chip bf16 serve cells")
        out = weights_lfm2_moe.build_serving(seed, dims(config))
        jax.block_until_ready(out)
        say("WEIGHTS", {"kind": "float", "seed": seed})
        return out, None

    eng.random_serving_weights = seeded_weights


def precisions(config: Dict[str, Any]) -> Dict[str, Any]:
    """The stated precision (bf16 values, float32 arithmetic), the
    contract's control — the nearest below it: int8 weights, activations
    and K/V rows — and the mechanism's own: conv layers whose carried
    tail is zero at every token (no precision at all: what a decode step
    that lost its slot's tail would compute)."""
    from benchmarks.reference import lfm2_moe as ref
    stated = ref.stated_precision(config)
    control = ref.control_precision(config)
    if not control.below(stated):
        raise SystemExit("the control's precision is not below the stated")
    return {"stated": stated, "control": control,
            "control_tail": ref.tail_control_precision(config)}


def serve_logits(config: Dict[str, Any], seed: int, precision, tokens,
                 rows, cols):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import weights
    from benchmarks.reference import lfm2_moe as ref
    key = jnp.asarray(weights.seed_key(seed))
    return np.asarray(ref.Reference(dims(config), precision).logits_at(
        key, jnp.asarray(tokens), rows, cols))
