"""The plain reference against the program at a tiny size on the CPU,
and the control: the reference computed one precision lower, put in the
program's place, must land outside the tolerance a sound run keeps.

Tolerances here are for the tiny stand-in (2 layers of 128); the cells'
own limits are set from chip readings at the published widths (PERF.md).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, weights
from benchmarks.children import common
from benchmarks.reference import decoder
from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import kvcache

N_REQ, NEW, S = 8, 24, 128


def _tiny(name):
    config = manifest.load_config(name)
    config.update(config["rehearse"])
    dims = manifest.model_dims(config)
    cfg = common.register_llama_config("tiny-" + name, dims)
    int8 = config["precision"]["weights"] == "int8"
    return config, dims, cfg, int8


def _prompts(seed, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(n)).tolist()
            for n in rng.integers(8, 100, N_REQ)]


def _served(cfg, int8, seed, prompts):
    """Tokens through the engine's own programs: wave and chunked
    prefill, burst decode through the (int8) cache, speculation on."""
    params, qw = weights.build_serving(seed, cfg, "int8" if int8 else "float")
    e = eng.InferenceEngine(
        params, cfg, n_slots=4, max_len=S, prompt_buckets=(32, 64, S),
        kv_int8=int8, qweights=qw, max_wave=4, pad_waves=True,
        prefix_pool=0, spec_k=4, prefill_chunk=32)
    return e.generate(prompts, max_new_tokens=NEW)


def _positions(prompts, outs):
    toks = np.zeros((len(prompts), S), np.int32)
    rows, cols, served = [], [], []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq = p + o[:-1]
        toks[i, :len(seq)] = seq
        for j, t in enumerate(o):
            rows.append(i)
            cols.append(len(p) - 1 + j)
            served.append(t)
    return (jnp.asarray(toks), np.asarray(rows), np.asarray(cols),
            np.asarray(served))


def _gaps(logits, picks):
    return logits.max(-1) - logits[np.arange(len(picks)), picks]


# Sound runs over seeds 1-6 read a mean gap of at most 0.0004 (bf16) and
# 0.003 (w8a8: bf16 rounding between matmuls flips int8 roundings); the
# control's smallest was 0.0009 (int8 for bf16) and 0.35 (int4 for int8).
@pytest.mark.parametrize("name,mean_limit", [
    ("internlm2-1.8b-bf16", 0.0006), ("mistral-7b-w8a8", 0.02)])
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 2, 3])
def test_served_tokens_lie_at_the_reference_best_and_the_control_does_not(
        name, mean_limit, seed):
    config, dims, cfg, int8 = _tiny(name)
    prompts = _prompts(seed, dims.vocab_size)
    outs = _served(cfg, int8, seed, prompts)
    assert all(len(o) == NEW for o in outs)
    toks, rows, cols, served = _positions(prompts, outs)
    key = jnp.asarray(weights.seed_key(seed))
    kind = "int8" if int8 else "float"
    stated = decoder.stated_precision(config)
    ref = np.asarray(decoder.Reference(dims, kind, stated).logits_at(
        key, toks, rows, cols))
    sound = _gaps(ref, served)
    assert sound.mean() <= mean_limit, sound.mean()

    low = decoder.control_precision(config)
    assert low.below(stated) or low.weight_bits
    low_logits = np.asarray(decoder.Reference(dims, kind, low).logits_at(
        key, toks, rows, cols))
    control = _gaps(ref, low_logits.argmax(-1))
    assert control.mean() > mean_limit, control.mean()
    assert control.mean() > 2 * sound.mean()


@pytest.mark.parametrize("name,tol", [
    # RMS error as a share of the logits' own spread (std ~1), read over
    # seeds 11-13: bf16 against float32 0.010-0.013, its int8 control
    # 0.030-0.034; the w8a8 program (it rounds a different float to int8
    # here and there) 0.032-0.038, its int4 control 0.46-0.50
    ("internlm2-1.8b-bf16", 0.02), ("mistral-7b-w8a8", 0.1)])
def test_prefill_and_decode_logits_match_the_full_forward(name, tol):
    """The two serve programs that return logits — ``prefill_batch`` and
    ``decode_step`` through the cache — against the reference's full
    forward pass."""
    config, dims, cfg, int8 = _tiny(name)
    seed = 11
    kind = "int8" if int8 else "float"
    params, qw = weights.build_serving(seed, cfg, kind)
    rng = np.random.default_rng(seed)
    lens = np.array([17, 40, 64, 9])
    toks = np.zeros((4, 64), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(1, dims.vocab_size, n)
    prefix, logits = kvcache.prefill_batch(
        params, jnp.asarray(toks), jnp.asarray(lens), cfg, qweights=qw)
    key = jnp.asarray(weights.seed_key(seed))
    ref = decoder.Reference(dims, kind, decoder.stated_precision(config))
    want = np.asarray(ref.logits_at(key, jnp.asarray(toks), np.arange(4),
                                    lens - 1))
    spread = want.std()

    def rms(a):
        return float(np.sqrt(np.mean(np.square(a))))

    assert rms(np.asarray(logits) - want) <= tol * spread

    # one decode step through the cache, from the prefilled rows
    first = np.asarray(logits).argmax(-1)
    cache = kvcache.init_cache(cfg, 4, 128, kv_int8=int8)
    for i in range(4):
        cache = kvcache.insert(
            cache, {"k": prefix["k"][:, i], "v": prefix["v"][:, i]},
            jnp.asarray(i), jnp.asarray(lens[i]), jnp.asarray(first[i]))
    _, step_logits = kvcache.decode_step(params, cache, cfg, qweights=qw)
    longer = np.concatenate([toks, np.zeros((4, 16), np.int32)], axis=1)
    for i, n in enumerate(lens):
        longer[i, n] = first[i]
    want2 = np.asarray(ref.logits_at(key, jnp.asarray(longer), np.arange(4),
                                     lens))
    assert rms(np.asarray(step_logits) - want2) <= tol * spread

    # the control in the program's place is outside that tolerance
    low = decoder.Reference(dims, kind, decoder.control_precision(config))
    got = np.asarray(low.logits_at(key, jnp.asarray(toks), np.arange(4),
                                   lens - 1))
    assert rms(got - want) > tol * spread


def test_training_reference_follows_adamw_by_hand():
    """The written-out AdamW against optax on a toy tree (the reference
    uses no optimizer library; this pins its arithmetic)."""
    import optax
    from skypilot_tpu.train import trainer
    opt = {"learning_rate": 3e-4, "weight_decay": 0.1, "beta1": 0.9,
           "beta2": 0.95, "grad_clip": 1.0, "warmup_steps": 100,
           "total_steps": 10000}
    tx = trainer.make_optimizer(trainer.TrainConfig(**opt))
    rng = np.random.default_rng(0)
    p = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    state = tx.init(p)
    q, mu, nu = p, jax.tree.map(jnp.zeros_like, p), \
        jax.tree.map(jnp.zeros_like, p)
    for step in range(4):
        g = jax.tree.map(lambda a: jnp.asarray(
            rng.normal(size=a.shape) * 3, jnp.float32), p)
        upd, state = tx.update(g, state, p)
        p = optax.apply_updates(p, upd)
        q, mu, nu = decoder.adamw_update(
            q, decoder.clip_by_global_norm(g, 1.0), mu, nu, step, opt)
        for k in p:
            np.testing.assert_allclose(np.asarray(q[k]), np.asarray(p[k]),
                                       rtol=1e-6, atol=1e-9)
    assert decoder.warmup_cosine(0, 3e-4, 100, 10000) == 0.0
    assert decoder.warmup_cosine(50, 3e-4, 100, 10000) == pytest.approx(1.5e-4)


def test_weights_are_a_function_of_the_seed_alone():
    _, dims, cfg, _ = _tiny("mistral-7b-w8a8")
    big = 2 ** 32 + 12345                       # more than 32 bits
    a = weights.build_serving(big, cfg, "int8")
    b = weights.build_serving(big, cfg, "int8")
    c = weights.build_serving(big - 2 ** 32, cfg, "int8")
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert any((x != y).any() for x, y in zip(la, lc))
    # one layer regenerated alone equals its slice of the stacked tree
    key = jnp.asarray(weights.seed_key(big))
    one = weights.block_tensor(key, cfg, "w_down", np.uint32(1), "int8")
    assert (one["w"] == a[1]["blocks"]["w_down"]["w"][1]).all()
    assert (one["s"] == a[1]["blocks"]["w_down"]["s"][1]).all()
    w = np.asarray(a[1]["blocks"]["wq"]["w"], np.float32)
    assert w.min() == -127 and w.max() == 127 and abs(w.mean()) < 1.0
