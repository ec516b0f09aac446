"""Sharded training loop: optax AdamW + pjit over an explicit mesh.

Everything here is a *thin* orchestration of jitted functions:

  * ``create_train_state`` — params initialized *directly sharded* (init is
    jitted with ``out_shardings``, so no host-side full copy ever exists;
    an 8B model initializes fine on hosts with modest RAM).
  * ``make_train_step`` — one fused step: loss -> grad -> clip -> AdamW ->
    param update, donated state, with activation sharding constraints from
    the rule table. XLA inserts the reduce-scatter/all-gather pattern for
    FSDP and the per-layer all-reduces for TP.

Reference parity: the reference delegates training loops to external
workloads (reference: examples/tpu/v6e/train-llama3-8b.yaml runs
transformers Trainer under PyTorch/XLA). In-tree trainer is the TPU-native
replacement for that recipe layer.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import statistics
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skypilot_tpu.models import llama
from skypilot_tpu.observability import flight
from skypilot_tpu.observability import metrics as obs_metrics
from skypilot_tpu.observability import tracing
from skypilot_tpu.parallel import sharding as sh

# Step-time note: the histogram records the HOST-side step call. The
# state is donated, so dispatching step k+1 blocks until step k's
# buffers free — at steady state the call duration converges to the
# true device step time without ever forcing a host sync for the
# metric's sake.
STEP_SECONDS = obs_metrics.histogram(
    "skytpu_train_step_seconds",
    "Train step call latency (back-pressured by donated state to the "
    "device step time at steady state)")
TRAIN_STEPS = obs_metrics.counter(
    "skytpu_train_steps_total", "Train steps dispatched")
TRAIN_TOKENS = obs_metrics.counter(
    "skytpu_train_tokens_total", "Tokens dispatched to train steps")
TRAIN_TOKENS_PER_S = obs_metrics.gauge(
    "skytpu_train_tokens_per_second",
    "Dispatch-rate tokens/s, EMA over recent steps")
TRAIN_LOSS = obs_metrics.gauge(
    "skytpu_train_loss",
    "Most recently fetched training loss (see observe_loss)")
# Step-time regression pair for the SLO watchdog: the trailing median
# is the baseline ("what a step normally costs on this run"), and the
# watchdog compares the windowed mean (histogram sum/count delta)
# against it — a data-pipeline stall or a slow host shows up without
# anyone pre-configuring an absolute step-time threshold.
TRAIN_STEP_LAST = obs_metrics.gauge(
    "skytpu_train_step_last_seconds",
    "Most recent post-compile train step wall time")
TRAIN_STEP_MEDIAN = obs_metrics.gauge(
    "skytpu_train_step_median_seconds",
    "Trailing median of recent post-compile step times (SLO regression "
    "baseline)")
_MEDIAN_WINDOW = 101


def observe_loss(loss: float) -> None:
    """Record a fetched loss into the gauge. Called where the train
    loop already pays the host sync (its logging cadence) — the step
    wrapper itself never forces a device fetch."""
    TRAIN_LOSS.set(float(loss))


def _instrument_step(step_fn: Callable) -> Callable:
    ema = {"rate": 0.0, "warm": False}
    recent = collections.deque(maxlen=_MEDIAN_WINDOW)

    @functools.wraps(step_fn)
    def wrapper(state, batch):
        t0 = time.monotonic()
        t0_wall = time.time()
        out = step_fn(state, batch)
        dt = max(time.monotonic() - t0, 1e-9)
        TRAIN_STEPS.inc()
        tokens = getattr(batch.get("tokens"), "size", 0) \
            if hasattr(batch, "get") else 0
        if tokens:
            TRAIN_TOKENS.inc(tokens)
        if not ema["warm"]:
            # The first call pays the XLA compile (tens of seconds at
            # scale); seeding the EMA or the histogram with it would
            # poison both for dozens of steps.
            ema["warm"] = True
            return out
        STEP_SECONDS.observe(dt)
        recent.append(dt)
        TRAIN_STEP_LAST.set(dt)
        TRAIN_STEP_MEDIAN.set(statistics.median(recent))
        # Per-step trace span (joins an ambient trace when the run was
        # launched with one; the compile step is skipped like above).
        tracing.record_span("train.step", t0_wall, t0_wall + dt,
                            attrs={"tokens": tokens} if tokens else None)
        if tokens:
            rate = tokens / dt
            ema["rate"] = (rate if ema["rate"] == 0.0
                           else 0.9 * ema["rate"] + 0.1 * rate)
            TRAIN_TOKENS_PER_S.set(ema["rate"])
        return out

    return wrapper


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    # Storage dtype for Adam's first moment ("bfloat16" halves that
    # buffer — how billion-param configs fit a 16 GB chip). None keeps
    # the params' dtype.
    mu_dtype: Optional[str] = None


def make_optimizer(tc: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=tc.learning_rate,
        warmup_steps=tc.warmup_steps,
        # optax requires decay_steps > warmup_steps (the cosine segment
        # length is the difference).
        decay_steps=max(tc.total_steps, tc.warmup_steps + 1, 1),
        end_value=tc.learning_rate * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(tc.grad_clip),
        optax.adamw(schedule, b1=tc.beta1, b2=tc.beta2,
                    weight_decay=tc.weight_decay,
                    mu_dtype=tc.mu_dtype),
    )


# Train state is a plain dict {"params", "opt_state", "step"}: already a
# pytree with no registration, and pickles trivially. (A dict *subclass*
# would silently become a pytree leaf — do not "upgrade" this.)
TrainState = Dict[str, Any]


def _train_state(params, opt_state, step) -> TrainState:
    return {"params": params, "opt_state": opt_state, "step": step}


def state_shardings(cfg: llama.LlamaConfig, mesh: Mesh,
                    rules: sh.Rules = sh.DEFAULT_RULES, model=llama):
    """Shardings for the full train state (opt state mirrors params)."""
    tc = TrainConfig()
    opt = make_optimizer(tc)
    p_shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0), cfg))
    opt_shapes = jax.eval_shape(opt.init, p_shapes)
    p_sh = sh.logical_to_sharding(model.param_logical_axes(cfg), mesh, rules,
                                  shapes=p_shapes)

    opt_sh = opt_state_shardings(p_sh, p_shapes, opt_shapes, mesh)
    return {"params": p_sh, "opt_state": opt_sh,
            "step": NamedSharding(mesh, P())}


def opt_state_shardings(param_sh, param_shapes, opt_shapes, mesh: Mesh):
    """Shardings for an optax state given the params' shardings.

    Adam moments have param shapes -> reuse the matching param sharding
    by shape lookup; scalars (step counts) replicate. Shared by the
    full trainer and the LoRA adapter trainer.
    """

    def opt_leaf_sharding(leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        for ps, pl in zip(jax.tree.leaves(param_sh),
                          jax.tree.leaves(param_shapes)):
            if pl.shape == leaf.shape:
                return ps
        return NamedSharding(mesh, P())

    # Walk opt_state structurally: moments subtree matches params treedef.
    return jax.tree.map(opt_leaf_sharding, opt_shapes,
                        is_leaf=lambda x: hasattr(x, "shape"))


def create_train_state(cfg: llama.LlamaConfig, tc: TrainConfig,
                       mesh: Optional[Mesh], seed: int = 0,
                       rules: sh.Rules = sh.DEFAULT_RULES,
                       model=llama) -> TrainState:
    opt = make_optimizer(tc)

    def init_fn(rng):
        params = model.init_params(rng, cfg)
        return _train_state(params, opt.init(params),
                            jnp.zeros((), jnp.int32))

    rng = jax.random.key(seed)
    # Start-up phase ``state``: to the state's arrival on the device(s),
    # not to its dispatch (docs/observability.md §Start-up).
    with flight.STARTUP.phase("state"):
        if mesh is None:
            build = jax.jit(init_fn)
        else:
            build = jax.jit(init_fn, out_shardings=state_shardings(
                cfg, mesh, rules, model))
        return jax.block_until_ready(build(rng))


def create_abstract_state(cfg: llama.LlamaConfig, tc: TrainConfig,
                          mesh: Optional[Mesh],
                          rules: sh.Rules = sh.DEFAULT_RULES,
                          model=llama) -> TrainState:
    """ShapeDtypeStruct pytree (with shardings) of the train state —
    the restore target for ``train.checkpoints`` without materializing
    anything."""
    opt = make_optimizer(tc)

    def init_fn(rng):
        params = model.init_params(rng, cfg)
        return _train_state(params, opt.init(params),
                            jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init_fn, jax.random.key(0))
    if mesh is None:
        return shapes
    shardings = state_shardings(cfg, mesh, rules, model)
    return jax.tree.map(
        lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shd),
        shapes, shardings)


def _batch_key_fn(args: tuple, kwargs: dict):
    """Shape-derived program identity for the trainer's compile watch:
    jit retraces on a new batch shape even under an unchanged entry
    point, and a mid-run shape change is exactly the silent retrace
    ``train.unexpected_compile`` exists to expose."""
    batch = args[1] if len(args) > 1 else kwargs.get("batch")
    parts = []
    if hasattr(batch, "items"):
        for k in sorted(batch):
            shape = getattr(batch[k], "shape", None)
            if shape is not None:
                parts.append((k, "x".join(str(d) for d in shape)))
    return parts


def make_train_step(cfg: llama.LlamaConfig, tc: TrainConfig,
                    mesh: Optional[Mesh],
                    rules: sh.Rules = sh.DEFAULT_RULES,
                    act_rules: sh.Rules = sh.ACT_RULES,
                    model=llama, watch=None) -> Callable:
    """Returns jitted step(state, batch) -> (state, metrics).

    ``watch`` is an optional ``flight.CompileWatch`` (the trainer's
    own, with ``event_name="train.unexpected_compile"``): the jitted
    step is wrapped so every distinct batch-shape identity registers
    as a program, and a post-warmup retrace emits the typed event the
    goodput ledger and SLO watchdog alarm on.
    """
    flight.COMPILES.install()    # the step's compile goes on the ledger
    opt = make_optimizer(tc)
    constrain = sh.make_constrain(mesh, act_rules)

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        def lossf(params):
            return model.loss_fn(params, batch, cfg, constrain, mesh,
                                 act_rules)

        (loss, metrics), grads = jax.value_and_grad(lossf, has_aux=True)(
            state["params"])
        with jax.named_scope("optimizer"):
            updates, new_opt = opt.update(grads, state["opt_state"],
                                          state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        new_state = _train_state(new_params, new_opt, state["step"] + 1)
        metrics = dict(metrics, grad_norm=gnorm)
        return new_state, metrics

    if mesh is None:
        jitted = jax.jit(step, donate_argnums=(0,))
    else:
        shardings = state_shardings(cfg, mesh, rules, model)
        batch_spec = NamedSharding(mesh, P(("dp", "fsdp")))
        jitted = jax.jit(
            step,
            donate_argnums=(0,),
            in_shardings=(shardings, batch_spec),
            out_shardings=(shardings, None),
        )
    if watch is not None:
        jitted = watch.wrap("train_step", jitted, key_fn=_batch_key_fn)
    return _instrument_step(jitted)


def synthetic_batch(cfg: llama.LlamaConfig, batch_size: int, seq_len: int,
                    seed: int = 0) -> Dict[str, jax.Array]:
    rng = jax.random.key(seed)
    tokens = jax.random.randint(rng, (batch_size, seq_len), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    return {"tokens": tokens}
