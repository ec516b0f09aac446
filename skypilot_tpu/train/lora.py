"""LoRA finetuning: low-rank adapters over frozen base weights.

Functional design — the model code is untouched: adapters are merged
into a *derived* parameter tree inside the jitted step
(``w + (alpha/r) * A @ B``), so the forward runs exactly the base
model's HLO while gradients flow only through A/B. Optimizer state
exists only for the adapters (the whole point: an 8B base finetunes
with megabytes of trainable state).

Merging costs O(L * d * r * d_out) per step — noise next to the
forward for r <= 64 — and XLA fuses it with the consuming matmuls.

Reference parity: llm/llama-3_1-finetuning/lora.yaml (torchtune
``lora_finetune_distributed`` — the reference's flagship finetune
recipe, external). In-tree TPU-native equivalent.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skypilot_tpu.models import llama
from skypilot_tpu.observability import flight
from skypilot_tpu.parallel import sharding as sh
from skypilot_tpu.train import trainer

Params = Dict[str, Any]

# Single source of truth for adapter geometry: per target, the base
# weight's (input logical axes, output logical axes) after the leading
# layer axis. Everything else (shapes, logical axes, merge einsum)
# derives from this table.
_TARGETS: Dict[str, Tuple[Tuple[str, ...], Tuple[str, ...]]] = {
    "wq": (("embed",), ("heads", "head_dim")),
    "wk": (("embed",), ("kv_heads", "head_dim")),
    "wv": (("embed",), ("kv_heads", "head_dim")),
    "wo": (("heads", "head_dim"), ("embed",)),
}


def _dim(cfg: llama.LlamaConfig, axis: str) -> int:
    return {"embed": cfg.d_model, "heads": cfg.n_heads,
            "kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim}[axis]


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int = 16
    alpha: float = 32.0
    targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo")

    def __post_init__(self):
        if self.rank <= 0:
            raise ValueError(f"LoRA rank must be positive, got {self.rank}")
        for t in self.targets:
            if t not in _TARGETS:
                raise ValueError(f"unknown LoRA target {t!r}; "
                                 f"supported: {sorted(_TARGETS)}")

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def init_lora_params(rng: jax.Array, cfg: llama.LlamaConfig,
                     lc: LoRAConfig) -> Params:
    """A ~ N(0, 1/d_in), B = 0: adapted model == base model at init."""
    L = cfg.n_layers
    keys = jax.random.split(rng, len(lc.targets))
    adapters: Params = {}
    for key, t in zip(keys, lc.targets):
        in_axes, out_axes = _TARGETS[t]
        in_dims = tuple(_dim(cfg, a) for a in in_axes)
        out_dims = tuple(_dim(cfg, a) for a in out_axes)
        d_in = 1
        for x in in_dims:
            d_in *= x
        adapters[t] = {
            "a": (jax.random.normal(key, (L, *in_dims, lc.rank),
                                    jnp.float32) * (d_in ** -0.5)),
            "b": jnp.zeros((L, lc.rank, *out_dims), jnp.float32),
        }
    return adapters


def lora_logical_axes(cfg: llama.LlamaConfig, lc: LoRAConfig) -> Params:
    out: Params = {}
    for t in lc.targets:
        in_axes, out_axes = _TARGETS[t]
        out[t] = {"a": ("layer", *in_axes, None),
                  "b": ("layer", None, *out_axes)}
    return out


def merge(base: Params, adapters: Params, lc: LoRAConfig) -> Params:
    """base params + scaled A@B deltas on the targeted projections."""
    blocks = dict(base["blocks"])
    for t, ab in adapters.items():
        a, b = ab["a"], ab["b"]
        in_axes, _ = _TARGETS[t]
        if len(in_axes) == 1:
            # a: [L, d, r]; b: [L, r, *out] -> delta [L, d, *out]
            delta = jnp.einsum("ldr,lrhk->ldhk", a, b)
        else:
            # a: [L, h, hd, r]; b: [L, r, d] -> delta [L, h, hd, d]
            delta = jnp.einsum("lhkr,lrd->lhkd", a, b)
        blocks[t] = blocks[t] + (lc.scale * delta).astype(blocks[t].dtype)
    return {**base, "blocks": blocks}


def lora_state_shardings(cfg: llama.LlamaConfig, lc: LoRAConfig,
                         tc: trainer.TrainConfig, mesh: Mesh):
    opt = trainer.make_optimizer(tc)
    a_shapes = jax.eval_shape(
        lambda: init_lora_params(jax.random.key(0), cfg, lc))
    a_sh = sh.logical_to_sharding(lora_logical_axes(cfg, lc), mesh,
                                  sh.DEFAULT_RULES, shapes=a_shapes)
    opt_shapes = jax.eval_shape(opt.init, a_shapes)
    opt_sh = trainer.opt_state_shardings(a_sh, a_shapes, opt_shapes, mesh)
    return {"params": a_sh, "opt_state": opt_sh,
            "step": NamedSharding(mesh, P())}


def _state_init_fn(cfg: llama.LlamaConfig, lc: LoRAConfig, opt):
    """The single definition of the LoRA train-state tree (shared by
    create/abstract so restore targets can never diverge)."""

    def init_fn(rng):
        adapters = init_lora_params(rng, cfg, lc)
        return {"params": adapters, "opt_state": opt.init(adapters),
                "step": jnp.zeros((), jnp.int32)}

    return init_fn


def abstract_lora_state(cfg: llama.LlamaConfig, lc: LoRAConfig,
                        tc: trainer.TrainConfig, mesh: Optional[Mesh]):
    """ShapeDtypeStruct pytree (with shardings) — the checkpoint-restore
    target, nothing materialized."""
    opt = trainer.make_optimizer(tc)
    shapes = jax.eval_shape(_state_init_fn(cfg, lc, opt),
                            jax.random.key(0))
    if mesh is None:
        return shapes
    shardings = lora_state_shardings(cfg, lc, tc, mesh)
    return jax.tree.map(
        lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                            sharding=shd),
        shapes, shardings)


def create_lora_state(cfg: llama.LlamaConfig, lc: LoRAConfig,
                      tc: trainer.TrainConfig, mesh: Optional[Mesh],
                      seed: int = 0):
    opt = trainer.make_optimizer(tc)
    init_fn = _state_init_fn(cfg, lc, opt)
    rng = jax.random.key(seed)
    # Start-up phase ``state``, as trainer.create_train_state keeps it.
    with flight.STARTUP.phase("state"):
        if mesh is None:
            build = jax.jit(init_fn)
        else:
            build = jax.jit(init_fn, out_shardings=lora_state_shardings(
                cfg, lc, tc, mesh))
        return jax.block_until_ready(build(rng))


def base_param_shardings(cfg: llama.LlamaConfig, mesh: Mesh, model=llama):
    """Shardings for the frozen base parameter tree."""
    return sh.logical_to_sharding(
        model.param_logical_axes(cfg), mesh, sh.DEFAULT_RULES,
        shapes=jax.eval_shape(
            lambda: model.init_params(jax.random.key(0), cfg)))


def make_lora_train_step(cfg: llama.LlamaConfig, lc: LoRAConfig,
                         tc: trainer.TrainConfig,
                         mesh: Optional[Mesh],
                         model=llama, base_sh=None,
                         act_rules: sh.Rules = sh.ACT_RULES) -> Callable:
    """step(lora_state, base_params, batch) -> (lora_state, metrics).

    base_params are a frozen input (no gradient, no donation): the same
    base tree serves every step. Pass ``base_sh`` if already computed.
    """
    flight.COMPILES.install()
    opt = trainer.make_optimizer(tc)
    constrain = sh.make_constrain(mesh, act_rules)

    def step(state, base_params, batch):
        def lossf(adapters):
            params = merge(base_params, adapters, lc)
            return model.loss_fn(params, batch, cfg, constrain, mesh,
                                 act_rules)

        (loss, metrics), grads = jax.value_and_grad(
            lossf, has_aux=True)(state["params"])
        updates, new_opt = opt.update(grads, state["opt_state"],
                                      state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        metrics = dict(metrics, grad_norm=optax.global_norm(grads))
        return {"params": new_params, "opt_state": new_opt,
                "step": state["step"] + 1}, metrics

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,))
    shardings = lora_state_shardings(cfg, lc, tc, mesh)
    if base_sh is None:
        base_sh = base_param_shardings(cfg, mesh, model)
    batch_spec = NamedSharding(mesh, P(("dp", "fsdp")))
    return jax.jit(step, donate_argnums=(0,),
                   in_shardings=(shardings, base_sh, batch_spec),
                   out_shardings=(shardings, None))


def num_trainable_params(cfg: llama.LlamaConfig,
                         lc: LoRAConfig) -> int:
    shapes = jax.eval_shape(
        lambda: init_lora_params(jax.random.key(0), cfg, lc))
    return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
