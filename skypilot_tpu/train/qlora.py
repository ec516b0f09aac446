"""QLoRA finetuning: LoRA adapters over a FROZEN int8 base.

This is how an 8B-class model finetunes on one 16 GB chip: the base
weights live in HBM as int8 (+ per-output-channel scales, ~8 GB for
8B), each matmul dequantizes its weight tile on the fly into the MXU's
bf16 input (XLA fuses convert+scale into the matmul read — the weights
never exist as a full bf16 tree), and LoRA adapters ride as separate
low-rank matmuls beside the frozen projections:

    y = x @ dequant(Wq) + (alpha/r) * (x @ A) @ B

Only A/B receive gradients; backprop flows through the dequantized
matmuls (linear in x — unlike the w8a8 serving path, whose activation
rounding would zero every upstream gradient).

Differences from train.lora (fp base): lora merges adapters into the
base tree per step, which requires the fp tree to exist; here the base
is int8-only, so deltas stay factored.

Reference parity: llm/llama-3_1-finetuning (torchtune LoRA recipe on
Llama-3.1, the reference's flagship finetune, external) +
examples/tpu/v6e/README.md §Train — the tok/s/chip benchmark class.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from skypilot_tpu.models import llama
from skypilot_tpu.observability import flight
from skypilot_tpu.ops import flash_attention as fa
from skypilot_tpu.train import trainer
from skypilot_tpu.train.lora import LoRAConfig

Params = Dict[str, Any]


def dequant_weight(qw: Dict[str, jax.Array], n_contract: int,
                   dtype) -> jax.Array:
    """int8 {w, s} -> dtype weight. ``s`` spans the output dims (w's
    trailing ndim - n_contract); broadcast across the contracted head.
    Linear in w and constant under grad — safe inside a differentiable
    forward."""
    s = qw["s"][(None,) * n_contract + (...,)]
    return (qw["w"].astype(jnp.float32) * s).astype(dtype)


@jax.named_scope("lora")
def _lora_in(h, ab, scale):
    """Delta for an embed->heads/kv projection. h: [B,S,D]."""
    u = jnp.einsum("bsd,dr->bsr", h, ab["a"].astype(h.dtype))
    return scale * jnp.einsum("bsr,r...->bs...", u,
                              ab["b"].astype(h.dtype))


@jax.named_scope("lora")
def _lora_out(o, ab, scale):
    """Delta for the heads->embed (wo) projection. o: [B,S,H,K]."""
    u = jnp.einsum("bshk,hkr->bsr", o, ab["a"].astype(o.dtype))
    return scale * jnp.einsum("bsr,rd->bsd", u, ab["b"].astype(o.dtype))


# What a kept layer holds for the backward pass instead of computing it
# a second time: the outputs of six frozen-base products (all but
# w_down's) and the flash forward's two residuals
# (ops/flash_attention._flash_fwd names those).
KEPT_NAMES = ("base_wq", "base_wk", "base_wv", "base_wo", "base_w_gate",
              "base_w_up", "flash_o", "flash_lse")


def _qdecoder_layer(cfg: llama.LlamaConfig, lc: LoRAConfig, x, qlayer,
                    norms, adapters, cos, sin, constrain, mesh, rules,
                    segment_ids):
    """One pre-norm decoder block off int8 weights + factored LoRA."""
    dt = cfg.dtype

    def proj(name, h, eq, n_contract):
        # The frozen weight dequantised and multiplied, under ONE name
        # on the device timeline (forward, and backward through it).
        with jax.named_scope("base_matmul"):
            w = dequant_weight(qlayer[name], n_contract, dt)
            y = jnp.einsum(eq, h, w)
        # A kept layer (forward_hidden) saves these by name; w_down's
        # product is no residual of anything, so it has none.
        return y if name == "w_down" else checkpoint_name(y, "base_" + name)

    h = llama.rms_norm(x, norms["ln1"], cfg.norm_eps)
    with jax.named_scope("attn"):
        q = proj("wq", h, "bsd,dhk->bshk", 1)
        k = proj("wk", h, "bsd,dhk->bshk", 1)
        v = proj("wv", h, "bsd,dhk->bshk", 1)
        sc = lc.scale
        if "wq" in adapters:
            q = q + _lora_in(h, adapters["wq"], sc)
        if "wk" in adapters:
            k = k + _lora_in(h, adapters["wk"], sc)
        if "wv" in adapters:
            v = v + _lora_in(h, adapters["wv"], sc)
        q = llama.apply_rope(q, cos, sin)
        k = llama.apply_rope(k, cos, sin)
        q = constrain(q, ("batch", "seq", "heads", "head_dim"))
        k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
        o = llama._attention(q, k, v, cfg, mesh, rules, segment_ids)
        y = proj("wo", o, "bshk,hkd->bsd", 2)
        if "wo" in adapters:
            y = y + _lora_out(o, adapters["wo"], sc)
        x = x + constrain(y, ("batch", "seq", "embed"))

    h = llama.rms_norm(x, norms["ln2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        g = proj("w_gate", h, "bsd,df->bsf", 1)
        u = proj("w_up", h, "bsd,df->bsf", 1)
        m = proj("w_down", jax.nn.silu(g) * u, "bsf,fd->bsd", 1)
        return x + constrain(m, ("batch", "seq", "embed"))


def forward_hidden(qweights: Params, fp_params: Params, adapters: Params,
                   tokens: jax.Array, cfg: llama.LlamaConfig,
                   lc: LoRAConfig, constrain=None, mesh=None, rules=None,
                   positions=None, segment_ids=None,
                   n_keep: int = 0) -> jax.Array:
    """Token ids [B, S] -> final-norm hidden states, int8 base.

    ``fp_params`` is the slim tree (embed + norms, kvcache.slim_params
    layout); ``qweights["blocks"]`` the stacked int8 block weights.
    The first ``n_keep`` layers keep ``KEPT_NAMES`` for the backward
    pass; the rest recompute them (``layers_kept`` says how many fit).
    """
    if constrain is None:
        constrain = lambda x, axes: x
    B, S = tokens.shape
    tokens = constrain(tokens, ("batch", "seq"))
    with jax.named_scope("embed"):
        table = constrain(fp_params["embed"].astype(cfg.dtype),
                          ("vocab", "embed"))
        x = table[tokens]
        x = constrain(x, ("batch", "seq", "embed"))
    if positions is None:
        positions = jnp.arange(S)
    from skypilot_tpu.parallel import ring_attention as ra
    (x, positions, segment_ids, layer_rules, use_zigzag,
     n_sp) = ra.apply_zigzag_layout(x, positions, segment_ids, mesh,
                                    rules)
    cos, sin = llama.rope_frequencies(cfg, positions)
    norms = {"ln1": fp_params["blocks"]["ln1"],
             "ln2": fp_params["blocks"]["ln2"]}

    blocks = qweights["blocks"]

    def run(x, lo, hi, policy):
        """Layers lo..hi under one policy. A part of the stack picks its
        frozen layers out of the whole by index: a scan over a SLICE of
        the int8 stack would first copy the slice."""
        if lo == hi:
            return x
        if (lo, hi) == (0, cfg.n_layers):
            pick, xs = (lambda qlayer: qlayer), (blocks, norms, adapters)
        else:
            pick = lambda i: jax.tree.map(lambda a: a[i], blocks)
            xs = (jnp.arange(lo, hi),) + jax.tree.map(
                lambda a: a[lo:hi], (norms, adapters))

        def body(carry, xs):
            which, norm, ab = xs
            y = _qdecoder_layer(cfg, lc, carry, pick(which), norm, ab,
                                cos, sin, constrain, mesh, layer_rules,
                                segment_ids)
            return y, None

        if cfg.remat:
            body = jax.checkpoint(body, policy=policy)
        return lax.scan(body, x, xs)[0]

    x = run(x, 0, n_keep,
            jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES))
    x = run(x, n_keep, cfg.n_layers, llama.remat_policy(cfg))
    if use_zigzag:
        x = ra.zigzag_unpermute(x, n_sp)
    return llama.rms_norm(x, fp_params["final_norm"], cfg.norm_eps)


def loss_fn(qweights: Params, fp_params: Params, adapters: Params,
            batch: Dict[str, jax.Array], cfg: llama.LlamaConfig,
            lc: LoRAConfig, constrain=None, mesh=None, rules=None,
            n_keep: int = 0):
    """Next-token cross-entropy off the int8 base + adapters."""
    if constrain is None:
        constrain = lambda x, axes: x
    tokens = batch["tokens"]
    h = forward_hidden(qweights, fp_params, adapters, tokens, cfg, lc,
                       constrain, mesh, rules,
                       positions=batch.get("positions"),
                       segment_ids=batch.get("segment_ids"),
                       n_keep=n_keep)
    # The head's dequantisation is a base matmul's too (the einsum that
    # consumes it sits in xent_metrics' chunk loop, under "xent").
    with jax.named_scope("base_matmul"):
        head = dequant_weight(qweights["head"], 1, cfg.dtype)
    loss, acc, denom = llama.xent_metrics(
        fp_params, h, tokens, llama.packed_loss_mask(batch), cfg,
        constrain, head=head)
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}


# ---------------------------------------------------------------------------
# How many layers keep their products: arithmetic on shapes and the
# device's stated limit. No flag sets it, and bytes_in_use (which moves
# from run to run) is never read: another n_keep is another program.
# ---------------------------------------------------------------------------

# Left free beside the step on the device, on top of a re-laid-stacks
# term that errs high (the Mistral-7B step at 2 x 2048 on a v5e: 13
# layers kept, 0.9 GB measured free; one layer more and the compiler
# starts to trade time for memory: 1010 -> 1091 ms a step).
MARGIN_BYTES = 512 * 2**20


def kept_layer_bytes(cfg: llama.LlamaConfig, batch: int, seq: int) -> int:
    """Bytes one kept layer holds for the backward pass (KEPT_NAMES):
    six product outputs and the flash output in the compute dtype, and
    the flash log-sum-exp as the kernel lays it out — float32,
    replicated over the 128 lanes."""
    tokens = batch * seq
    heads, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    wide = (heads + 2 * kv      # wq, wk, wv
            + heads             # flash_o
            + cfg.d_model       # wo
            + 2 * cfg.d_ff)     # w_gate, w_up
    lse = batch * cfg.n_heads * seq * fa.LANES * 4
    return tokens * wide * jnp.dtype(cfg.dtype).itemsize + lse


def step_transient_bytes(cfg: llama.LlamaConfig, batch: int,
                         seq: int) -> int:
    """What the step with NOTHING kept holds beside its arguments, from
    its shapes: every layer's input (the scan's residuals), the
    dequantised head, and the larger of one layer's backward working
    set (about two and a half kept layers' worth) and three float32
    copies of a cross-entropy chunk's logits, which are never live
    together. 2.39 GB where a v5e reserved 2.42 (Mistral-7B, 2 x 2048).
    """
    item = jnp.dtype(cfg.dtype).itemsize
    layer_inputs = cfg.n_layers * batch * seq * cfg.d_model * item
    head = cfg.d_model * cfg.vocab_size * item
    logits = 3 * batch * (cfg.xent_chunk or seq) * cfg.vocab_size * 4
    return layer_inputs + head + max(
        5 * kept_layer_bytes(cfg, batch, seq) // 2, logits)


def layers_kept(cfg: llama.LlamaConfig, batch: int, seq: int,
                argument_bytes: int, limit_bytes: int) -> int:
    """How many layers' KEPT_NAMES fit beside the step's arguments and
    its own transients under the device's memory limit, less
    MARGIN_BYTES. 0 where the device states no limit, where one layer
    does not fit, or where ``cfg`` asks for another rematerialisation
    than the default ("dots" already saves every product; ``remat``
    off saves everything)."""
    if not limit_bytes or not cfg.remat or cfg.remat_policy != "none":
        return 0
    free = (limit_bytes - MARGIN_BYTES - argument_bytes
            - step_transient_bytes(cfg, batch, seq))
    layer = kept_layer_bytes(cfg, batch, seq)
    if free >= cfg.n_layers * layer:
        return cfg.n_layers
    # A part of the stack picks its int8 layers by index, and the
    # compiler then re-lays the whole wq / wk / wv stacks once a step.
    relaid = (cfg.n_layers * cfg.d_model * cfg.head_dim
              * (cfg.n_heads + 2 * cfg.n_kv_heads))
    return max(0, (free - relaid) // layer)


def _tree_bytes(tree) -> int:
    return sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def _limit_bytes() -> int:
    """``bytes_limit`` of the device the step runs on (a constant of the
    device kind); 0 where it states none, as the CPU."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 0))


class QLoRAStep:
    """step(state, qweights, fp_params, batch) -> (state, metrics): one
    jitted program a batch shape, the first ``kept(...)["n_keep"]``
    layers of which keep KEPT_NAMES for the backward pass."""

    def __init__(self, build: Callable[[int], Callable],
                 cfg: llama.LlamaConfig, n_keep: Optional[int]):
        self._build, self._cfg, self._n_keep = build, cfg, n_keep
        self._programs: Dict[tuple, tuple] = {}

    def _program(self, *args):
        shape = tuple(args[-1]["tokens"].shape)
        if shape not in self._programs:
            cfg, n_keep = self._cfg, self._n_keep
            if n_keep is None:
                n_keep = layers_kept(cfg, *shape, _tree_bytes(args),
                                     _limit_bytes())
            kept = {"n_keep": n_keep, "n_layers": cfg.n_layers,
                    "kept_bytes": n_keep * kept_layer_bytes(cfg, *shape)}
            self._programs[shape] = (self._build(n_keep), kept)
        return self._programs[shape]

    def kept(self, *args) -> Dict[str, int]:
        """{"n_keep", "n_layers", "kept_bytes"} of the program the
        step's arguments run (arrays or their ShapeDtypeStructs)."""
        return dict(self._program(*args)[1])

    def lower(self, *args):
        return self._program(*args)[0].lower(*args)

    def __call__(self, *args):
        return self._program(*args)[0](*args)


def make_qlora_train_step(cfg: llama.LlamaConfig, lc: LoRAConfig,
                          tc: trainer.TrainConfig, mesh=None,
                          n_keep: Optional[int] = None) -> QLoRAStep:
    """step(state, qweights, fp_params, batch) -> (state, metrics).

    The int8 base + slim fp tree are frozen inputs (no gradient, no
    donation); optimizer state exists only for the adapters.
    Single-chip oriented: the 8B bench's whole point is one 16 GB chip
    (multi-chip finetunes shard the fp base via train.lora instead).

    As many layers as ``layers_kept`` finds room for keep their
    frozen-base products and flash residuals instead of computing them
    twice; under a mesh none does (the arithmetic is one chip's).
    ``n_keep`` overrides the count, for tests and ahead-of-time
    compiles; the step's ``kept(...)`` says what a program holds.
    """
    flight.COMPILES.install()    # the step's compile goes on the ledger
    opt = trainer.make_optimizer(tc)
    if mesh is not None and n_keep is None:
        n_keep = 0

    def build(n_keep):
        def step(state, qweights, fp_params, batch):
            def lossf(adapters):
                return loss_fn(qweights, fp_params, adapters, batch,
                               cfg, lc, mesh=mesh, n_keep=n_keep)

            (loss, metrics), grads = jax.value_and_grad(
                lossf, has_aux=True)(state["params"])
            with jax.named_scope("optimizer"):
                updates, new_opt = opt.update(grads, state["opt_state"],
                                              state["params"])
                new_params = optax.apply_updates(state["params"],
                                                 updates)
                metrics = dict(metrics,
                               grad_norm=optax.global_norm(grads))
            return {"params": new_params, "opt_state": new_opt,
                    "step": state["step"] + 1}, metrics

        return jax.jit(step, donate_argnums=(0,))

    return QLoRAStep(build, cfg, n_keep)


def create_qlora_state(cfg: llama.LlamaConfig, lc: LoRAConfig,
                       tc: trainer.TrainConfig, seed: int = 0):
    """The adapter train state IS lora's (params/opt_state/step over
    A/B) — one definition, so `--qlora --resume` restore targets can
    never diverge from fresh init (see lora._state_init_fn)."""
    from skypilot_tpu.train import lora as lora_lib
    return lora_lib.create_lora_state(cfg, lc, tc, mesh=None, seed=seed)
