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

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from skypilot_tpu.models import llama
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
            return jnp.einsum(eq, h, w)

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
                   positions=None, segment_ids=None) -> jax.Array:
    """Token ids [B, S] -> final-norm hidden states, int8 base.

    ``fp_params`` is the slim tree (embed + norms, kvcache.slim_params
    layout); ``qweights["blocks"]`` the stacked int8 block weights.
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

    def body(carry, xs):
        qlayer, norm, ab = xs
        y = _qdecoder_layer(cfg, lc, carry, qlayer, norm, ab, cos, sin,
                            constrain, mesh, layer_rules, segment_ids)
        return y, None

    if cfg.remat:
        body = jax.checkpoint(body, policy=llama.remat_policy(cfg))

    x, _ = lax.scan(body, x, (qweights["blocks"], norms, adapters))
    if use_zigzag:
        x = ra.zigzag_unpermute(x, n_sp)
    return llama.rms_norm(x, fp_params["final_norm"], cfg.norm_eps)


def loss_fn(qweights: Params, fp_params: Params, adapters: Params,
            batch: Dict[str, jax.Array], cfg: llama.LlamaConfig,
            lc: LoRAConfig, constrain=None, mesh=None, rules=None):
    """Next-token cross-entropy off the int8 base + adapters."""
    if constrain is None:
        constrain = lambda x, axes: x
    tokens = batch["tokens"]
    h = forward_hidden(qweights, fp_params, adapters, tokens, cfg, lc,
                       constrain, mesh, rules,
                       positions=batch.get("positions"),
                       segment_ids=batch.get("segment_ids"))
    # The head's dequantisation is a base matmul's too (the einsum that
    # consumes it sits in xent_metrics' chunk loop, under "xent").
    with jax.named_scope("base_matmul"):
        head = dequant_weight(qweights["head"], 1, cfg.dtype)
    loss, acc, denom = llama.xent_metrics(
        fp_params, h, tokens, llama.packed_loss_mask(batch), cfg,
        constrain, head=head)
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}


def make_qlora_train_step(cfg: llama.LlamaConfig, lc: LoRAConfig,
                          tc: trainer.TrainConfig,
                          mesh=None) -> Callable:
    """step(state, qweights, fp_params, batch) -> (state, metrics).

    The int8 base + slim fp tree are frozen inputs (no gradient, no
    donation); optimizer state exists only for the adapters.
    Single-chip oriented: the 8B bench's whole point is one 16 GB chip
    (multi-chip finetunes shard the fp base via train.lora instead).
    """
    opt = trainer.make_optimizer(tc)

    def step(state, qweights, fp_params, batch):
        def lossf(adapters):
            return loss_fn(qweights, fp_params, adapters, batch, cfg,
                           lc, mesh=mesh)

        (loss, metrics), grads = jax.value_and_grad(
            lossf, has_aux=True)(state["params"])
        with jax.named_scope("optimizer"):
            updates, new_opt = opt.update(grads, state["opt_state"],
                                          state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            metrics = dict(metrics,
                           grad_norm=optax.global_norm(grads))
        return {"params": new_params, "opt_state": new_opt,
                "step": state["step"] + 1}, metrics

    return jax.jit(step, donate_argnums=(0,))


def create_qlora_state(cfg: llama.LlamaConfig, lc: LoRAConfig,
                       tc: trainer.TrainConfig, seed: int = 0):
    """The adapter train state IS lora's (params/opt_state/step over
    A/B) — one definition, so `--qlora --resume` restore targets can
    never diverge from fresh init (see lora._state_init_fn)."""
    from skypilot_tpu.train import lora as lora_lib
    return lora_lib.create_lora_state(cfg, lc, tc, mesh=None, seed=seed)
