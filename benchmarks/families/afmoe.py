"""The ``afmoe`` family: decoders whose attention layers are
sliding-window ones with a global layer every few, a gated attention
output, and shared + routed experts after the leading dense layers
(``afmoe``: Trinity-Mini), run by ``skypilot_tpu/models/afmoe.py``
through ``infer/windowed.py``.

``families/llama.py`` says what a family gives. This one serves only:
``train_program`` / ``train_reference`` are absent, and a training cell
of this family fails at its first call of them (ROADMAP M1: training the
expert block is what remains).

Its seeded weights are ``benchmarks/weights_afmoe.py``, its plain
reference ``benchmarks/reference/afmoe.py``, its work counts
``benchmarks/window_work.py`` and, for the expert layer it shares with
the ``glm_moe`` family, ``benchmarks/moe_work.py`` (the names that file
reads are here under the same spelling).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

WINDOW, FULL = "sliding_attention", "full_attention"


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
    window: int
    n_dense_layers: int
    d_ff: int
    moe_d_ff: int
    n_routed_experts: int
    n_shared_experts: int
    experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    mup_enabled: bool
    rope_theta: float
    norm_eps: float
    max_seq_len: int

    def is_window(self, layer: int) -> bool:
        return self.layer_types[layer] == WINDOW

    @property
    def n_win_layers(self) -> int:
        return sum(t == WINDOW for t in self.layer_types)

    @property
    def n_full_layers(self) -> int:
        return self.n_layers - self.n_win_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kv_row_bytes(self) -> int:
        """One token's K and V in one layer, bf16."""
        return 2 * self.n_kv_heads * self.head_dim * 2

    def plan(self):
        """``models/afmoe.plan``'s arithmetic, from these sizes alone:
        (leading dense layers, period, whole periods, layers left)."""
        lead = min(self.n_dense_layers, self.n_layers)
        types = self.layer_types
        period = types.index(FULL) + 1 if FULL in types else 1
        n_periods = (self.n_layers - lead) // period
        return (tuple(range(lead)), period, n_periods,
                tuple(range(lead + n_periods * period, self.n_layers)))

    def attn_params(self) -> int:
        d, q = self.d_model, self.n_heads * self.head_dim
        return (3 * d * q + 2 * d * self.n_kv_heads * self.head_dim
                + 2 * self.head_dim)

    def expert_params(self) -> int:
        """ONE routed expert's three matrices."""
        return 3 * self.d_model * self.moe_d_ff

    def dense_layer_params(self) -> int:
        return self.attn_params() + 4 * self.d_model \
            + 3 * self.d_model * self.d_ff

    def expert_layer_params(self) -> int:
        return (self.attn_params() + 4 * self.d_model
                + self.d_model * self.n_routed_experts
                + self.n_routed_experts
                + (self.n_routed_experts + self.n_shared_experts)
                * self.expert_params())

    def num_params(self) -> int:
        return (self.n_dense_layers * self.dense_layer_params()
                + self.n_moe_layers * self.expert_layer_params()
                + 2 * self.vocab_size * self.d_model + self.d_model)


def dims(config: Dict[str, Any]) -> ModelDims:
    """From the source's own key names (the Hugging Face ``config.json``
    of ``afmoe``)."""
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("num_expert_groups", 1), ("num_limited_groups", 1),
                      ("rope_scaling", None), ("score_func", "sigmoid"),
                      ("tie_word_embeddings", False),
                      ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise SystemExit(f"{config.get('name')}: {key}="
                             f"{config.get(key)!r} is not built "
                             f"(wants {want!r})")
    n = int(config["num_hidden_layers"])
    types = tuple(config["layer_types"])[:n]
    if len(types) != n or any(t not in (WINDOW, FULL) for t in types):
        raise SystemExit(f"{config.get('name')}: layer_types must name "
                         f"{n} sliding_attention / full_attention layers")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return ModelDims(
        vocab_size=int(config["vocab_size"]), d_model=d, n_layers=n,
        layer_types=types, n_heads=h,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // h),
        window=int(config["sliding_window"]),
        n_dense_layers=int(config["num_dense_layers"]),
        d_ff=int(config["intermediate_size"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        n_routed_experts=int(config["num_experts"]),
        n_shared_experts=int(config["num_shared_experts"]),
        experts_per_tok=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["route_scale"]),
        norm_topk_prob=bool(config.get("route_norm", True)),
        mup_enabled=bool(config.get("mup_enabled", False)),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


def register(config: Dict[str, Any], **overrides):
    """The configuration's sizes as the program's ``AfmoeConfig``,
    registered under the configuration's name — in this process only."""
    from skypilot_tpu.models import afmoe
    dims(config)                     # refuses what is not built
    cfg = afmoe.from_published(config, **overrides)
    afmoe.CONFIGS[config["name"]] = cfg
    return cfg


def serve_setup(config: Dict[str, Any], seed: int, say: Callable) -> None:
    """Register the configuration and hand the program the benchmark's
    seeded weights in place of the program's own random ones."""
    import jax

    from benchmarks import weights_afmoe
    from skypilot_tpu.infer import engine as eng

    cfg = register(config)
    if config["precision"]["weights"] != "bf16":
        raise SystemExit(f"{config['name']}: the family serves bf16")

    def seeded_weights(cfg_, *, weights_int8=False, mesh=None, **_):
        if mesh is not None or weights_int8 or cfg_ is not cfg:
            raise SystemExit("the benchmark's weights are for the "
                             "one-chip bf16 serve cells")
        out = weights_afmoe.build_serving(seed, dims(config))
        jax.block_until_ready(out)
        say("WEIGHTS", {"kind": "float", "seed": seed})
        return out, None

    eng.random_serving_weights = seeded_weights


def precisions(config: Dict[str, Any]) -> Dict[str, Any]:
    """The stated precision (bf16 values, float32 arithmetic), the
    contract's control — the nearest below it: int8 weights, activations
    and K/V rows — and the mechanism's own: the window layers seeing
    every row (no precision at all: what a ring read without its mask,
    or a cache that never forgot, would compute)."""
    from benchmarks.reference import afmoe as ref
    stated = ref.stated_precision(config)
    control = ref.control_precision(config)
    if not control.below(stated):
        raise SystemExit("the control's precision is not below the stated")
    return {"stated": stated, "control": control,
            "control_window": ref.window_control_precision(config)}


def serve_logits(config: Dict[str, Any], seed: int, precision, tokens,
                 rows, cols):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import weights
    from benchmarks.reference import afmoe as ref
    key = jnp.asarray(weights.seed_key(seed))
    return np.asarray(ref.Reference(dims(config), precision).logits_at(
        key, jnp.asarray(tokens), rows, cols))
