"""The ``olmo_hybrid`` family: decoders whose layers are gated-delta-rule
linear attention with a full softmax-attention layer closing each period
(``olmo_hybrid``: Olmo-Hybrid-7B), run by
``skypilot_tpu/models/olmo_hybrid.py`` through ``infer/hybrid.py``.

``families/llama.py`` says what a family gives. This one serves only:
``train_program`` / ``train_reference`` are absent, and a training cell
of this family fails at its first call of them (ROADMAP M4: the rule's
backward is what remains).

Its seeded weights are ``benchmarks/weights_olmo_hybrid.py``, its plain
reference ``benchmarks/reference/olmo_hybrid.py``, its work counts
``benchmarks/delta_work.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """A configuration file's sizes under the names the family's own
    arithmetic (weights, reference, work counts) uses."""
    vocab_size: int
    d_model: int
    n_layers: int
    lin_per_period: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    lin_heads: int
    lin_k_dim: int
    lin_v_dim: int
    conv_kernel: int
    allow_neg_eigval: bool
    d_ff: int
    rope_theta: Optional[float]
    norm_eps: float
    max_seq_len: int

    @property
    def n_full_layers(self) -> int:
        return self.n_layers // (self.lin_per_period + 1)

    @property
    def n_lin_layers(self) -> int:
        return self.n_full_layers * self.lin_per_period

    @property
    def conv_channels(self) -> int:
        return self.lin_heads * (2 * self.lin_k_dim + self.lin_v_dim)

    def lin_layer_params(self) -> int:
        d, h = self.d_model, self.lin_heads
        mixer = (d * self.conv_channels
                 + self.conv_kernel * self.conv_channels
                 + 2 * d * h + 2 * h + 2 * d * h * self.lin_v_dim
                 + self.lin_v_dim)
        return mixer + 3 * d * self.d_ff + 2 * d

    def full_layer_params(self) -> int:
        d = self.d_model
        q = self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        return 2 * d * q + 2 * d * kv + q + kv + 3 * d * self.d_ff + 2 * d

    def num_params(self) -> int:
        return (self.n_lin_layers * self.lin_layer_params()
                + self.n_full_layers * self.full_layer_params()
                + 2 * self.vocab_size * self.d_model + self.d_model)


def dims(config: Dict[str, Any]) -> ModelDims:
    """From the source's own key names (the Hugging Face ``config.json``
    of ``olmo_hybrid``)."""
    for key, want in (("tie_word_embeddings", False),
                      ("attention_bias", False), ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise SystemExit(f"{config.get('name')}: {key}="
                             f"{config.get(key)!r} is not built "
                             f"(wants {want!r})")
    n = int(config["num_hidden_layers"])
    types = list(config["layer_types"])[:n]
    if "full_attention" not in types:
        raise SystemExit(f"{config.get('name')}: no full_attention layer")
    lin = types.index("full_attention")
    if len(types) != n or lin < 1 or types != (
            ["linear_attention"] * lin + ["full_attention"]) * (n // (lin + 1)):
        raise SystemExit(f"{config.get('name')}: layer_types must repeat "
                         "(linear_attention x n, full_attention) whole")
    if int(config["linear_num_key_heads"]) \
            != int(config["linear_num_value_heads"]):
        raise SystemExit(f"{config.get('name')}: grouped linear heads "
                         "are not built")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    theta = (config.get("rope_parameters") or {}).get("rope_theta")
    return ModelDims(
        vocab_size=int(config["vocab_size"]), d_model=d, n_layers=n,
        lin_per_period=lin, n_heads=h,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // h),
        lin_heads=int(config["linear_num_key_heads"]),
        lin_k_dim=int(config["linear_key_head_dim"]),
        lin_v_dim=int(config["linear_value_head_dim"]),
        conv_kernel=int(config["linear_conv_kernel_dim"]),
        allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        d_ff=int(config["intermediate_size"]),
        rope_theta=None if theta is None else float(theta),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


def register(config: Dict[str, Any], **overrides):
    """The configuration's sizes as the program's ``OlmoHybridConfig``,
    registered under the configuration's name — in this process only."""
    from skypilot_tpu.models import olmo_hybrid
    dims(config)                     # refuses what is not built
    cfg = olmo_hybrid.from_published(config, **overrides)
    olmo_hybrid.CONFIGS[config["name"]] = cfg
    return cfg


def serve_setup(config: Dict[str, Any], seed: int, say: Callable) -> None:
    """Register the configuration and hand the program the benchmark's
    seeded weights in place of the program's own random ones."""
    import jax

    from benchmarks import weights_olmo_hybrid
    from skypilot_tpu.infer import engine as eng

    cfg = register(config)
    if config["precision"]["weights"] != "bf16":
        raise SystemExit(f"{config['name']}: the family serves bf16")

    def seeded_weights(cfg_, *, weights_int8=False, mesh=None, **_):
        if mesh is not None or weights_int8 or cfg_ is not cfg:
            raise SystemExit("the benchmark's weights are for the "
                             "one-chip bf16 serve cells")
        out = weights_olmo_hybrid.build_serving(seed, dims(config))
        jax.block_until_ready(out)
        say("WEIGHTS", {"kind": "float", "seed": seed})
        return out, None

    eng.random_serving_weights = seeded_weights


def precisions(config: Dict[str, Any]) -> Dict[str, Any]:
    """The stated precision (bf16 values, float32 state and arithmetic),
    the contract's control — the nearest below it: int8 weights,
    activations and K/V rows — and the mechanism's own: the recurrent
    state held in bfloat16."""
    from benchmarks.reference import olmo_hybrid as ref
    stated = ref.stated_precision(config)
    out = {"stated": stated, "control": ref.control_precision(config),
           "control_state": ref.state_control_precision(config)}
    for label, low in out.items():
        if label != "stated" and not low.below(stated):
            raise SystemExit(f"{label}'s precision is not below the stated")
    return out


def serve_logits(config: Dict[str, Any], seed: int, precision, tokens,
                 rows, cols):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import weights
    from benchmarks.reference import olmo_hybrid as ref
    key = jnp.asarray(weights.seed_key(seed))
    return np.asarray(ref.Reference(dims(config), precision).logits_at(
        key, jnp.asarray(tokens), rows, cols))
