"""QLoRA: LoRA adapters over a frozen int8 base (the 8B-on-one-chip
finetune path). Oracles against the fp model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.infer import kvcache
from skypilot_tpu.models import llama
from skypilot_tpu.train import qlora, trainer
from skypilot_tpu.train.lora import LoRAConfig, init_lora_params


@pytest.fixture(scope="module")
def cfg():
    return llama.CONFIGS["llama3-tiny"]


@pytest.fixture(scope="module")
def quantized(cfg):
    params = llama.init_params(jax.random.key(0), cfg)
    qw = {"blocks": kvcache.quantize_block_weights(params),
          "head": kvcache.quantize_head(params, cfg)}
    return params, qw, kvcache.slim_params(params)


@pytest.fixture(scope="module")
def batch(cfg):
    tokens = jax.random.randint(jax.random.key(2), (2, 32), 1,
                                cfg.vocab_size, dtype=jnp.int32)
    return {"tokens": tokens}


def test_zero_adapters_match_fp_model(cfg, quantized, batch):
    """With B=0 adapters the int8 forward is the base model up to
    quantization error (measured ~0.04% on the loss)."""
    params, qw, fp = quantized
    lc = LoRAConfig(rank=4)
    adapters = init_lora_params(jax.random.key(1), cfg, lc)
    loss_q, metrics = jax.jit(
        lambda a: qlora.loss_fn(qw, fp, a, batch, cfg, lc))(adapters)
    loss_fp, _ = jax.jit(lambda p: llama.loss_fn(p, batch, cfg))(params)
    np.testing.assert_allclose(float(loss_q), float(loss_fp), rtol=5e-3)
    assert np.isfinite(float(metrics["accuracy"]))


def test_qlora_adapters_learn(cfg, quantized, batch):
    """Gradients flow through the dequantized matmuls into the
    adapters: loss drops on a fixed batch with the base frozen."""
    _, qw, fp = quantized
    lc = LoRAConfig(rank=8)
    tc = trainer.TrainConfig(learning_rate=1e-2, warmup_steps=1)
    step = qlora.make_qlora_train_step(cfg, lc, tc)
    state = qlora.create_qlora_state(cfg, lc, tc)
    first = last = None
    for _ in range(8):
        state, metrics = step(state, qw, fp, batch)
        loss = float(metrics["loss"])
        first = loss if first is None else first
        last = loss
    assert last < first - 0.5, (first, last)
    assert float(metrics["grad_norm"]) > 0


def test_qlora_grads_only_adapters(cfg, quantized, batch):
    """value_and_grad wrt adapters only — every adapter leaf gets a
    finite gradient, and wq's B-grad is nonzero (B=0 start still gets
    gradient through A)."""
    _, qw, fp = quantized
    lc = LoRAConfig(rank=4)
    adapters = init_lora_params(jax.random.key(3), cfg, lc)
    grads = jax.jit(jax.grad(
        lambda a: qlora.loss_fn(qw, fp, a, batch, cfg, lc)[0]))(adapters)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()
    assert float(jnp.abs(grads["wq"]["b"]).sum()) > 0


def test_random_quantized_params_device_side(cfg):
    """The 8B bench's weight builder: no host numpy arrays, leaves live
    on device, engine-compatible structure."""
    fp, qw = kvcache.random_quantized_params(cfg, seed=1)
    assert qw["blocks"]["wq"]["w"].dtype == jnp.int8
    assert fp["embed"].dtype == jnp.bfloat16
    lc = LoRAConfig(rank=4)
    adapters = init_lora_params(jax.random.key(1), cfg, lc)
    loss, _ = jax.jit(lambda a: qlora.loss_fn(
        qw, fp, a, {"tokens": jnp.ones((1, 16), jnp.int32)}, cfg,
        lc))(adapters)
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# Kept layers: the first n_keep layers hold their frozen-base products
# and flash residuals for the backward pass; the rest compute them twice.
# ---------------------------------------------------------------------------

def _two_steps(step, cfg, lc, tc, qw, fp, batch):
    state = qlora.create_qlora_state(cfg, lc, tc, seed=3)
    out = []
    for _ in range(2):
        state, metrics = step(state, qw, fp, batch)
        out.append(jax.tree.map(np.asarray, (metrics, state)))
    return out


@pytest.mark.parametrize("n_keep", [0, 1, 2], ids=["none", "some", "all"])
def test_kept_layers_change_no_value(cfg, quantized, batch, n_keep):
    """A kept tensor IS the value the second forward would have made:
    loss, the adapters' gradient and two steps of the state are those
    of the step that keeps nothing, bit for bit on the CPU — which
    states no memory limit, so the default path is that step."""
    _, qw, fp = quantized
    lc = LoRAConfig(rank=4)
    tc = trainer.TrainConfig(learning_rate=1e-2, warmup_steps=1)
    adapters = jax.tree.map(
        lambda a: a + 0.01, init_lora_params(jax.random.key(3), cfg, lc))

    def loss_and_grad(k):
        return jax.jit(jax.value_and_grad(lambda a: qlora.loss_fn(
            qw, fp, a, batch, cfg, lc, n_keep=k)[0]))(adapters)

    (loss, grads), (loss0, grads0) = loss_and_grad(n_keep), loss_and_grad(0)
    assert float(loss) == float(loss0)
    assert jax.tree.structure(grads) == jax.tree.structure(adapters)
    for g, g0 in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g0))
        assert np.abs(np.asarray(g)).sum() > 0

    default = qlora.make_qlora_train_step(cfg, lc, tc)
    step = qlora.make_qlora_train_step(cfg, lc, tc, n_keep=n_keep)
    want = _two_steps(default, cfg, lc, tc, qw, fp, batch)
    got = _two_steps(step, cfg, lc, tc, qw, fp, batch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    state = qlora.create_qlora_state(cfg, lc, tc)
    assert default.kept(state, qw, fp, batch)["n_keep"] == 0
    assert step.kept(state, qw, fp, batch) == {
        "n_keep": n_keep, "n_layers": 2,
        "kept_bytes": n_keep * qlora.kept_layer_bytes(cfg, 2, 32)}


def _count(jaxpr, pred, times=1):
    """Equations ``pred`` holds for, a scan's body counted once a turn."""
    n = 0
    for eqn in jaxpr.eqns:
        n += times * bool(pred(eqn))
        inner = times * (eqn.params.get("length", 1)
                         if eqn.primitive.name == "scan" else 1)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, pred, inner)
    return n


@pytest.mark.parametrize("n_keep", [0, 1, 3], ids=["none", "some", "all"])
def test_a_kept_layer_runs_its_products_and_flash_forward_once(
        monkeypatch, n_keep):
    """The gradient's jaxpr, at a shape that takes the flash kernels: a
    layer multiplies its seven frozen weights forward and backward, and
    six of them and the flash forward a second time — unless kept."""
    from skypilot_tpu.ops import attention as attn_ops
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(llama.CONFIGS["llama3-tiny"], d_model=256,
                              n_heads=2, n_kv_heads=1, n_layers=3,
                              max_seq_len=1024)
    lc = LoRAConfig(rank=4)
    fp, qw = jax.eval_shape(lambda: kvcache.random_quantized_params(cfg))
    adapters = jax.eval_shape(
        lambda: init_lora_params(jax.random.key(1), cfg, lc))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1024), jnp.int32)}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda a, qw, fp, batch: qlora.loss_fn(
            qw, fp, a, batch, cfg, lc, n_keep=n_keep)[0]))(
                adapters, qw, fp, batch).jaxpr
    base_dots = _count(jaxpr, lambda e: (
        e.primitive.name == "dot_general"
        and "base_matmul" in str(e.source_info.name_stack)))
    flash_fwd = _count(jaxpr, lambda e: (
        e.primitive.name == "pallas_call" and "flash_fwd" in str(
            e.params.get("name_and_src_info", e.params.get("name")))))
    assert base_dots == 3 * (7 + 6 + 7) - 6 * n_keep
    assert flash_fwd == 2 * 3 - n_keep


MISTRAL_7B = llama.LlamaConfig(
    vocab_size=32768, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    d_ff=14336, max_seq_len=32768, xent_chunk=512)
V5E_LIMIT = 16_909_336_064      # memory_stats()["bytes_limit"] of a v5e
QLORA_ARGS = 7_552_271_872      # the cell's int8 base, embedding, adapters


def test_kept_layer_bytes_at_mistral_7b():
    """Batch 2 x 2048 in bf16: q 32 MiB, k 8, v 8, flash_o 32, the wo
    product 32, gate 112, up 112 — 336 MiB — and the kernel's
    lane-replicated float32 log-sum-exp, 64 MiB (0.5 if it were
    [B, H, S])."""
    assert qlora.kept_layer_bytes(MISTRAL_7B, 2, 2048) == 400 * 2**20
    assert qlora.kept_layer_bytes(MISTRAL_7B, 4, 2048) == 800 * 2**20


def test_layers_kept_is_arithmetic_on_shapes_and_the_limit():
    kept = lambda **kw: qlora.layers_kept(**{
        "cfg": MISTRAL_7B, "batch": 2, "seq": 2048,
        "argument_bytes": QLORA_ARGS, "limit_bytes": V5E_LIMIT, **kw})
    # The benchmark's cell on a v5e, and the same on every call.
    assert [kept() for _ in range(3)] == [13, 13, 13]
    # Monotone in the limit, none when a layer does not fit, all at most.
    counts = [kept(limit_bytes=gb * 10**9) for gb in range(8, 40)]
    assert counts == sorted(counts)
    assert counts[0] == 0 and counts[-1] == MISTRAL_7B.n_layers
    assert 0 < counts[10] < MISTRAL_7B.n_layers
    # Larger arguments or batches leave less room.
    assert kept(argument_bytes=QLORA_ARGS + 2**30) < kept()
    assert kept(batch=4) < kept() < kept(batch=1)
    # A device that states no limit (the CPU) keeps nothing; nor does a
    # configuration that asks for another rematerialisation.
    assert kept(limit_bytes=0) == 0
    for other in ({"remat": False}, {"remat_policy": "dots"}):
        assert kept(cfg=dataclasses.replace(MISTRAL_7B, **other)) == 0

