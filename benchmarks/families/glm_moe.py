"""The ``glm_moe`` family: pre-norm decoders with latent attention (MLA)
in every layer, leading dense SwiGLU layers and shared + routed experts
after them (``glm4_moe_lite``: GLM-4.7-Flash), run by
``skypilot_tpu/models/glm_moe.py`` through ``infer/latent.py``.

``families/llama.py`` says what a family gives. This one serves only:
``train_program`` / ``train_reference`` are absent, and a training cell
of this family fails at its first call of them (ROADMAP M1: training the
block is what remains).

Its seeded weights are ``benchmarks/weights_glm_moe.py``, its plain
reference ``benchmarks/reference/glm_moe.py``, its work counts
``benchmarks/moe_work.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """A configuration file's sizes under the names the family's own
    arithmetic (weights, reference, work counts) uses."""
    vocab_size: int
    d_model: int
    n_layers: int
    first_k_dense: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int
    moe_d_ff: int
    n_routed_experts: int
    n_shared_experts: int
    experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rope_theta: float
    norm_eps: float
    max_seq_len: int

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    def attn_params(self) -> int:
        d, h = self.d_model, self.n_heads
        return (d * self.q_lora_rank
                + self.q_lora_rank * h * (self.qk_nope + self.qk_rope)
                + d * (self.kv_lora_rank + self.qk_rope)
                + self.kv_lora_rank * h * (self.qk_nope + self.v_head)
                + h * self.v_head * d)

    def expert_params(self) -> int:
        """ONE routed expert's three matrices."""
        return 3 * self.d_model * self.moe_d_ff

    def num_params(self) -> int:
        d = self.d_model
        norms = 2 * d + self.q_lora_rank + self.kv_lora_rank
        dense = self.attn_params() + norms + 3 * d * self.d_ff
        moe = (self.attn_params() + norms
               + d * self.n_routed_experts + self.n_routed_experts
               + (self.n_routed_experts + self.n_shared_experts)
               * self.expert_params())
        return (self.first_k_dense * dense + self.n_moe_layers * moe
                + 2 * self.vocab_size * d + d)


def dims(config: Dict[str, Any]) -> ModelDims:
    """From the source's own key names (the Hugging Face
    ``config.json`` of ``glm4_moe_lite``)."""
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("partial_rotary_factor", 1), ("rope_scaling", None),
                      ("tie_word_embeddings", False),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("topk_method", "noaux_tc")):
        if config.get(key, want) != want:
            raise SystemExit(f"{config.get('name')}: {key}="
                             f"{config.get(key)!r} is not built "
                             f"(wants {want!r})")
    return ModelDims(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        first_k_dense=int(config["first_k_dense_replace"]),
        n_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope=int(config["qk_nope_head_dim"]),
        qk_rope=int(config["qk_rope_head_dim"]),
        v_head=int(config["v_head_dim"]),
        d_ff=int(config["intermediate_size"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        n_routed_experts=int(config["n_routed_experts"]),
        n_shared_experts=int(config["n_shared_experts"]),
        experts_per_tok=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


def register(config: Dict[str, Any], **overrides):
    """The configuration's sizes as the program's ``GlmMoeConfig``,
    registered under the configuration's name — in this process only."""
    from skypilot_tpu.models import glm_moe
    dims(config)                     # refuses what is not built
    cfg = glm_moe.from_published(config, **overrides)
    glm_moe.CONFIGS[config["name"]] = cfg
    return cfg


def serve_setup(config: Dict[str, Any], seed: int, say: Callable) -> None:
    """Register the configuration and hand the program the benchmark's
    seeded weights in place of the program's own random ones."""
    import jax

    from benchmarks import weights_glm_moe
    from skypilot_tpu.infer import engine as eng

    cfg = register(config)
    if config["precision"]["weights"] != "bf16":
        raise SystemExit(f"{config['name']}: the family serves bf16")

    def seeded_weights(cfg_, *, weights_int8=False, mesh=None, **_):
        if mesh is not None or weights_int8 or cfg_ is not cfg:
            raise SystemExit("the benchmark's weights are for the "
                             "one-chip bf16 serve cells")
        out = weights_glm_moe.build_serving(seed, dims(config))
        jax.block_until_ready(out)
        say("WEIGHTS", {"kind": "float", "seed": seed})
        return out, None

    eng.random_serving_weights = seeded_weights


def precisions(config: Dict[str, Any]) -> Dict[str, Any]:
    """The stated precision (bf16 values, float32 arithmetic) and the
    contract's control, the nearest below it: int8 weights, activations
    and latent rows."""
    from benchmarks.reference import glm_moe as ref
    stated = ref.stated_precision(config)
    control = ref.control_precision(config)
    if not control.below(stated):
        raise SystemExit("the control's precision is not below the stated")
    return {"stated": stated, "control": control}


def serve_logits(config: Dict[str, Any], seed: int, precision, tokens,
                 rows, cols):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import weights
    from benchmarks.reference import glm_moe as ref
    key = jnp.asarray(weights.seed_key(seed))
    return np.asarray(ref.Reference(dims(config), precision).logits_at(
        key, jnp.asarray(tokens), rows, cols))
