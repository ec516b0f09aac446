"""What a chip run would hit, checked without a chip.

Part 1 asks the TPU's own compiler (installed here; the chip is
*described*, not attached — the `on-chip-measurement` guide §2.3) to
compile the Pallas kernels and two engine programs at llama3-8b
widths. Interpret-mode tests cannot see what it refuses: a block that
breaks the (8, 128) tiling rule, a kernel that outgrows scoped VMEM, a
Mosaic call the SPMD partitioner cannot split. Nothing runs, so these
say nothing about results or times.

Part 2 holds the rest of the bring-up contract on the CPU: the peaks
table, the compile-cache helper, the weight builder's start-up error,
and chip_smoke.py's control flow at tiny configs — including that a CPU
never gets a passing last line.
"""

import dataclasses
import json
import math
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import kvcache
from skypilot_tpu.models import llama
from skypilot_tpu.observability import attribution
from skypilot_tpu.ops import attention as attn_ops
from skypilot_tpu.ops import flash_attention as fa
from skypilot_tpu.ops import grouped_ffn
from skypilot_tpu.ops import paged_attention as pa
from skypilot_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

# ---------------------------------------------------------------------------
# Part 1: ahead-of-time compiles for a described v5e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on the first device of a described v5e:2x2 host. The
    persistent compile cache is off around these compiles: an
    executable for a described device is written to it but can never
    be read back, so it would only warn and pile up."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _compiled_kernels(fn, *args, **kw) -> int:
    """Compile for the described chip; count the Mosaic kernels in it."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **kw).compile()
    return compiled.as_text().count("tpu_custom_call")


# llama3-8b: 32 layers, 8 kv-heads of 128, 4 q-heads each; the serve
# recipe's pool: 33 slots x 5 blocks of 256 rows.
_L, _NB, _BL, _G, _HD, _REP = 32, 165, 256, 8, 128, 4


@pytest.mark.parametrize("rows", [_REP, 512 * _REP],
                         ids=["decode", "chunk"])
@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
def test_paged_kernel_compiles_for_v5e(one_chip, quant, rows):
    """The paged-attention kernel lowers at the llama3-8b pool
    [32, n_blocks, 256, 8, 128] — decode rows and a 512-token chunk's
    rows (which need the row tiling to stay inside scoped VMEM)."""
    S = _sds(one_chip)
    slots = 33 if rows == _REP else 1
    pool = S((_L, _NB, _BL, _G, _HD), jnp.int8 if quant else jnp.bfloat16)
    scale = S((_L, _NB, _G, _BL), jnp.bfloat16) if quant else None

    def f(q, kp, vp, ks, vs, table, lengths, layer):
        return pa.paged_attention(q, kp, vp, ks, vs, table, lengths,
                                  layer, span_blocks=5, interpret=False)

    assert _compiled_kernels(
        f, S((slots, _G, rows, _HD), jnp.bfloat16), pool, pool, scale,
        scale, S((slots, 6), jnp.int32), S((slots,), jnp.int32),
        S((), jnp.int32)) == 1


def _flash_loss(q, k, v, seg=None):
    return fa.flash_attention(q, k, v, causal=True,
                              segment_ids=seg).astype(jnp.float32).sum()


@pytest.mark.parametrize("which", ["forward", "backward",
                                   "segment_backward"])
def test_flash_kernels_compile_for_v5e(one_chip, which):
    """Flash forward, backward and segment-masked backward at the
    trainer's shapes: [6, 2048, 16, 128] bf16 (llama3-1b)."""
    S = _sds(one_chip)
    x = S((6, 2048, 16, 128), jnp.bfloat16)
    if which == "forward":
        n = _compiled_kernels(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
            x, x, x)
        assert n == 1
    elif which == "backward":
        n = _compiled_kernels(
            jax.grad(_flash_loss, argnums=(0, 1, 2)), x, x, x)
        assert n == 3          # forward + dKV + dQ
    else:
        n = _compiled_kernels(
            jax.grad(_flash_loss, argnums=(0, 1, 2)), x, x, x,
            S((6, 2048), jnp.int32))
        assert n == 3


@pytest.mark.parametrize("rows, d, f, groups, experts", [
    (2048, 2048, 1536, 6 * 64, 64),      # a GLM-4.7-Flash chunk: 512 x top-4
    (4096, 2048, 1024, 128, 128),        # a Trinity-Mini chunk: 512 x top-8
    (8192, 2048, 1536, 6 * 64, 64)],     # GLM's widest wave: 4 x 512 x top-4
    ids=["glm-chunk", "trinity-chunk", "glm-widest-wave"])
def test_grouped_swiglu_compiles_for_v5e(one_chip, rows, d, f, groups,
                                         experts):
    """The grouped-SwiGLU kernel lowers at the two published widths with
    the tiles the shapes give, over the WHOLE stack of experts and a
    traced ``expert_base``: a tiling the Mosaic lowering refuses, or
    blocks beyond the VMEM limit the call states, would show here."""
    S = _sds(one_chip)
    bf = jnp.bfloat16
    assert grouped_ffn.tiles_for(rows, d, f) is not None

    def ffn(xs, w_gate, w_up, w_down, offsets, base):
        return grouped_ffn.grouped_swiglu(xs, w_gate, w_up, w_down,
                                          offsets, base, interpret=False)

    assert _compiled_kernels(
        ffn, S((rows, d), bf), S((groups, d, f), bf), S((groups, d, f), bf),
        S((groups, f, d), bf), S((experts + 1,), jnp.int32),
        S((), jnp.int32)) == 1


# memory_stats()["bytes_limit"] of a v5e chip: 15.75 GiB, which is also
# what its compiler refuses a program over (my chip runs, PR 39).
V5E_LIMIT_BYTES = 16_909_336_064


@pytest.mark.parametrize("more", ["as_kept", "a_margin_more"])
def test_qlora_step_fits_a_v5e_at_mistral_7b(one_chip, monkeypatch, more):
    """The whole QLoRA step — 32 layers at Mistral-7B widths, batch
    2 x 2048, abstract arguments — with as many layers kept as
    ``layers_kept`` finds room for under a v5e's limit: the compiler
    accepts it, and accepts it with the stated margin's worth of layers
    MORE (so an out-of-memory is that far away by the compiler's own
    count of arguments and temporaries, which is what the chip reserves;
    ``memory_analysis()`` reads ~1.7 GB over that)."""
    from skypilot_tpu.train import lora as lora_lib
    from skypilot_tpu.train import qlora, trainer
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    cfg = llama.LlamaConfig(
        vocab_size=32768, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, rope_theta=1e6, max_seq_len=32768,
        xent_chunk=512)
    lc = lora_lib.LoRAConfig(rank=16, alpha=32.0)
    tc = trainer.TrainConfig()
    batch, seq = 2, 2048
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    fp, qw = jax.eval_shape(lambda: kvcache.random_quantized_params(cfg))
    state = jax.eval_shape(lambda: qlora.create_qlora_state(cfg, lc, tc))
    args = place((state, qw, fp,
                  {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}))
    n_keep = qlora.layers_kept(cfg, batch, seq, qlora._tree_bytes(args),
                               V5E_LIMIT_BYTES)
    assert n_keep == 13
    layer = qlora.kept_layer_bytes(cfg, batch, seq)
    if more == "a_margin_more":
        n_keep += -(-qlora.MARGIN_BYTES // layer)
    step = qlora.make_qlora_train_step(cfg, lc, tc, n_keep=n_keep)
    assert step.kept(*args)["kept_bytes"] == n_keep * layer
    compiled = step.lower(*args).compile()
    # flash forward of the kept layers, of the rest and of their second
    # forward; two backward kernels in each of the two backward scans.
    assert compiled.as_text().count("tpu_custom_call") == 7
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes
               - qlora._tree_bytes(args)) < 2**20      # scalars pad
    assert mem.temp_size_in_bytes > n_keep * layer


@pytest.fixture(scope="module")
def engine_8b_2layers():
    """The serve recipe's engine (w8a8, int8 KV, 32 slots, 1280) at
    every llama3-8b width, depth cut to 2 layers so a compile stays a
    few seconds. Weights are shapes only; the 2-layer cache is real
    (~0.2 GB of host memory)."""
    cfg = dataclasses.replace(llama.CONFIGS["llama3-8b"], n_layers=2)
    params, qweights = jax.eval_shape(
        lambda: kvcache.random_quantized_params(cfg))
    return eng.InferenceEngine(
        params, cfg, qweights=qweights, n_slots=32, max_len=1280,
        prompt_buckets=(128, 512, 1280), kv_int8=True, max_wave=4,
        pad_waves=True, prefix_pool=8, spec_k=4)


def _engine_args(e, sharding):
    abstract = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), tree)
    S = _sds(sharding)
    return (abstract(e.params), abstract(e.qweights), abstract(e.cache),
            abstract(e.rng), S(e.block_table.shape, jnp.int32), S)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_decode_burst_compiles_for_v5e(one_chip, engine_8b_2layers,
                                       kernel, monkeypatch):
    """The engine's own burst program (its jit, its donation), with the
    gather read and with the paged kernel inside the layer scan —
    compiled, so the suite's interpreter switch goes off here."""
    monkeypatch.setattr(pa, "INTERPRET", False)
    e = engine_8b_2layers
    params, qw, cache, rng, table, S = _engine_args(e, one_chip)
    n = _compiled_kernels(
        e._decode_burst_fn.__wrapped__, params, cache, rng,
        S((e.n_slots + 1,), jnp.bool_), table, k=4, qweights=qw,
        span=None, kernel=kernel)
    assert n == (1 if kernel else 0)


def _largest_under(text, *scopes):
    """Bytes of the largest array an op under one of ``scopes`` makes
    (a tuple's first element), read from the compiled text."""
    sizes = {"s8": 1, "pred": 1, "bf16": 2, "f32": 4, "s32": 4, "u32": 4}
    found = re.findall(
        r"= \(?(\w+)\[([\d,]+)\].*op_name=\"[^\"]*/(?:%s)/"
        % "|".join(scopes), text)
    return max(sizes[dt] * math.prod(int(d) for d in dims.split(","))
               for dt, dims in found)


@pytest.mark.parametrize("span", [640, None], ids=["span640", "full"])
def test_decode_burst_reads_live_tiles_out_of_the_flat_pool(
        one_chip, engine_8b_2layers, span):
    """A staged step gathers its live slots' blocks, ``kvcache.TILE``
    slots a turn, straight out of the pool seen as ``[L * blocks, ...]``:
    the compiled burst holds no copy of a layer's whole K or V pool
    (``s8[165,256,8,128]``, 43 MB each, sliced out before the gather every
    layer of every step until PR 31), no gather of all 33 rows — the
    gather's leading dim is the tile —, the largest array the read and
    the attention make is the tile's view of K or V (a few MB; 43 MB
    with the slices and the 33-row view — the program's WHOLE
    temporaries say nothing here: with two layers the compiler spends
    the memory it got back on keeping an FFN weight stack near), and
    the turns are one more loop inside the layer scan."""
    e = engine_8b_2layers
    params, qw, cache, rng, table, S = _engine_args(e, one_chip)
    compiled = e._decode_burst_fn.__wrapped__.lower(
        params, cache, rng, S((e.n_slots + 1,), jnp.bool_), table, k=4,
        qweights=qw, span=span, kernel=False).compile()
    text = compiled.as_text()
    rows = e.n_slots + 1
    layer_pool = ",".join(str(n) for n in e.cache["k"].shape[1:])
    assert layer_pool == "165,256,8,128"
    assert not re.search(
        rf"= s8\[{layer_pool}\]\S* (copy|dynamic-slice|fusion)\(", text), \
        "a layer of the K/V pool is sliced out or copied"
    assert not re.search(rf"= s8\[{rows},\d+,256,8,128\]\S* gather\(", text)
    assert len(re.findall(
        rf"= s8\[{kvcache.TILE},\d+,256,8,128\]\S* gather\(", text)) == 2
    view = kvcache.TILE * -(-(span or 1280) // 256) * 256 * 8 * 128
    assert _largest_under(text, "kv_gather", "attn_core") == view
    assert text.count(" while(") == 3       # steps, layers, live tiles


def test_prefill_chunk_compiles_for_v5e(one_chip, engine_8b_2layers):
    e = engine_8b_2layers
    params, qw, cache, rng, table, S = _engine_args(e, one_chip)
    i32 = S((), jnp.int32)
    _compiled_kernels(
        e._prefill_chunk_fn.__wrapped__, params, cache,
        S((512,), jnp.int32), i32, i32, i32, i32, rng, table, final=True,
        qweights=qw, span=640, kernel=False)


def test_top_bucket_prefill_takes_flash_at_1280(one_chip,
                                                engine_8b_2layers,
                                                monkeypatch):
    """The server's top prompt bucket is max_len (1280 in the recipe):
    not a multiple of 512, so flash shrinks its block to 256 instead
    of raising — and the wave program really holds the kernel. The
    compile happens on the CPU backend, so the test steers the
    backend check there (never a program option)."""
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    e = engine_8b_2layers
    params, qw, cache, rng, table, S = _engine_args(e, one_chip)
    n = _compiled_kernels(
        e._admit_wave_fn.__wrapped__, params, cache,
        S((4, 1280), jnp.int32), S((4,), jnp.int32), S((4,), jnp.int32),
        rng, table, bucket=1280, qweights=qw)
    assert n == 1


@pytest.fixture(scope="module")
def latent_engine_2layers():
    """The latent-cache family at every published GLM-4.7-Flash width,
    depth cut to one dense + one expert layer (weights are shapes only;
    the pool is real: 16 slots — several tiles — of 8704 rows, ~0.3 GB
    of host memory)."""
    from skypilot_tpu.models import glm_moe
    cfg = dataclasses.replace(glm_moe.CONFIGS["glm-4.7-flash"], n_layers=2)
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(cfg.dtype),
        glm_moe.init_params(jax.random.key(0), cfg)))
    return eng.InferenceEngine(
        params, cfg, n_slots=15, max_len=8704,
        prompt_buckets=(128, 512, 8704), max_wave=4, pad_waves=True,
        prefix_pool=8, spec_k=0)


@pytest.mark.parametrize("program", ["decode_burst", "prefill_chunk",
                                     "admit_wave"])
def test_latent_programs_compile_for_v5e(one_chip, latent_engine_2layers,
                                         program, monkeypatch):
    """The MLA / expert programs lower for the chip, the grouped
    expert products of a chunk or wave are ONE Mosaic kernel
    (``grouped_swiglu``; a decode step's few rows take none), and the donated
    latent pool is written IN PLACE: a scatter with the layer as a
    window dim made the compiler transpose the whole pool into another
    layout and back (temporaries of the pool's own size)."""
    e = latent_engine_2layers
    # The forms a chip's trace takes (the backend here is the CPU).
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(grouped_ffn, "INTERPRET", False)
    params, _, cache, rng, table, S = _engine_args(e, one_chip)
    i32 = S((), jnp.int32)
    if program == "decode_burst":
        lowered = e._decode_burst_fn.__wrapped__.lower(
            params, cache, rng, S((e.n_slots + 1,), jnp.bool_), table,
            k=4, qweights=None, span=None, kernel=False)
        kernels = 0
    elif program == "prefill_chunk":
        lowered = e._prefill_chunk_fn.__wrapped__.lower(
            params, cache, S((512,), jnp.int32), i32, i32, i32, i32, rng,
            table, final=True, qweights=None, span=4352, kernel=False)
        kernels = 1          # the expert layer's grouped SwiGLU
    else:
        lowered = e._admit_wave_fn.__wrapped__.lower(
            params, cache, S((4, 512), jnp.int32), S((4,), jnp.int32),
            S((4,), jnp.int32), rng, table, bucket=512, qweights=None)
        kernels = 1
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == kernels
    pool = sum(e.cache[n].nbytes for n in ("c_kv", "k_pe"))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool
    # (``k_pe``, an eighth of the bytes, IS still copied: its 64 values
    # a row are half a lane tile and the compiler re-lays it out before
    # the gather — ROADMAP M3.)
    shape = ",".join(str(n) for n in e.cache["c_kv"].shape)
    assert not re.search(rf"bf16\[{shape}\]\S* copy\(", text), \
        "the c_kv pool is copied"
    # No layer's experts (1.2 GB) are sliced out of the stack and copied
    # for the kernel: transients stay well under one layer's.
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_latent_decode_reads_the_expert_stack_in_place(
        one_chip, latent_engine_2layers):
    """A decode step visits the experts its live rows chose, a turn of
    a loop each, and takes the expert's three matrices where they lie
    in the stack: the compiled burst holds no temporary the size of a
    layer's experts (1.2 GB) nor of one tensor's layer slice (403 MB) —
    the whole of its temporaries is less than one such slice — and no
    expert tensor is copied or sliced out by the layer."""
    e = latent_engine_2layers
    params, _, cache, rng, table, S = _engine_args(e, one_chip)
    compiled = e._decode_burst_fn.__wrapped__.lower(
        params, cache, rng, S((e.n_slots + 1,), jnp.bool_), table,
        k=4, qweights=None, span=None, kernel=False).compile()
    gate = e.params["moe"]["we_gate"]
    one_slice = gate.size // gate.shape[0] * gate.dtype.itemsize
    assert one_slice == 64 * 2048 * 1536 * 2           # 403 MB
    assert compiled.memory_analysis().temp_size_in_bytes < one_slice
    text = compiled.as_text()
    assert text.count(" while(") >= 3      # steps, layers, expert turns
    for rows, cols in ((2048, 1536), (1536, 2048)):
        assert not re.search(
            rf"= bf16\[64,{rows},{cols}\]\S* (copy|dynamic-slice)\(", text), \
            "a layer's slice of an expert tensor is materialised"


def test_latent_decode_gathers_a_tile_of_slots(one_chip,
                                               latent_engine_2layers):
    """The latent burst reads its live slots' latent rows a tile a turn:
    no gather of every row's blocks (``bf16[16,17,256,512]`` here,
    ``[33,17,256,512]`` in the cell), the tile's instead, in both layer
    groups — each with its own loop of turns."""
    e = latent_engine_2layers
    params, _, cache, rng, table, S = _engine_args(e, one_chip)
    text = e._decode_burst_fn.__wrapped__.lower(
        params, cache, rng, S((e.n_slots + 1,), jnp.bool_), table,
        k=4, qweights=None, span=4352, kernel=False).compile().as_text()
    rows = e.n_slots + 1
    assert rows > kvcache.TILE
    assert not re.search(rf"= bf16\[{rows},\d+,256,512\]\S* gather\(", text)
    assert len(re.findall(
        rf"= bf16\[{kvcache.TILE},17,256,512\]\S* gather\(", text)) == 2
    # steps; dense layers, expert layers, each with its tile turns; the
    # expert visit.
    assert text.count(" while(") >= 5


# ---------------------------------------------------------------------------
# Part 2: the bring-up contract on the CPU
# ---------------------------------------------------------------------------


class _Device:
    def __init__(self, platform, kind, stats=None):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        return self._stats


@pytest.fixture(scope="module")
def hybrid_engine_1period():
    """The hybrid family at every published Olmo-Hybrid-7B width, depth
    cut to one period (three linear layers + one full; weights are
    shapes only; the cache is real: 15 slots — several tiles — of 8704
    rows over a pool of 40 blocks, ~0.3 GB of host memory)."""
    from skypilot_tpu.models import olmo_hybrid
    cfg = dataclasses.replace(olmo_hybrid.CONFIGS["olmo-hybrid-7b"],
                              n_layers=4)
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(cfg.dtype),
        olmo_hybrid.init_params(jax.random.key(0), cfg)))
    return eng.InferenceEngine(
        params, cfg, n_slots=15, max_len=8704,
        prompt_buckets=(128, 512, 8704), max_wave=4, pad_waves=True,
        prefix_pool=0, spec_k=0, kv_blocks=40)


@pytest.mark.parametrize("program", ["decode_burst", "prefill_chunk",
                                     "admit_wave"])
def test_hybrid_programs_compile_for_v5e(one_chip, hybrid_engine_1period,
                                         program):
    """The gated-delta-rule programs lower for the chip in plain XLA (no
    Mosaic kernel), and what a slot holds is written IN PLACE: the K/V
    pool of 32-head rows is not re-laid nor copied (with 30 heads
    second-minor the compiler copied it whole, twice a tensor, in every
    program), and the recurrent state is sliced and updated slot by slot
    (a gather by slot id made the compiler slice the whole state —
    every layer, every slot — before each read)."""
    e = hybrid_engine_1period
    params, _, cache, rng, table, S = _engine_args(e, one_chip)
    i32 = S((), jnp.int32)
    if program == "decode_burst":
        lowered = e._decode_burst_fn.__wrapped__.lower(
            params, cache, rng, S((e.n_slots + 1,), jnp.bool_), table,
            k=4, qweights=None, span=None, kernel=False)
    elif program == "prefill_chunk":
        lowered = e._prefill_chunk_fn.__wrapped__.lower(
            params, cache, S((512,), jnp.int32), i32, i32, i32, i32, rng,
            table, final=True, qweights=None, span=4352, kernel=False)
    else:
        lowered = e._admit_wave_fn.__wrapped__.lower(
            params, cache, S((4, 512), jnp.int32), S((4,), jnp.int32),
            S((4,), jnp.int32), rng, table, bucket=512, qweights=None)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 0
    held = sum(e.cache[n].nbytes for n in ("k", "v", "state", "conv"))
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    assert e.cache["k"].shape[3] == 32          # 30 heads in whole tiles
    for name, dtype in (("k", "bf16"), ("state", "f32")):
        shape = ",".join(str(n) for n in e.cache[name].shape)
        assert not re.search(rf"{dtype}\[{shape}\]\S* copy\(", text), \
            f"the {name} tensor is copied"
    # (the K/V gather's own slices, inside its fusion, are bf16)
    assert not re.search(r"mini-gather-slice\S* = f32\[", text), \
        "the state is gathered by slot id"
    # Transients stay a fraction of what is resident at 16 layers (8.2
    # GB of weights): the largest here is the head's 0.77 GB.
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < 2.2e9
    if program != "decode_burst":
        return
    # The decode program reads the pool in place, a block a turn of a
    # loop whose trip count is the blocks a slot HOLDS (here at the top
    # rung, 34 blocks a slot): nothing the size of a pool tensor —
    # whole, or in the halves a gather of a window over 1 MiB is lowered
    # to — is sliced, copied, gathered or selected; what makes such an
    # array is the argument, its views, and the flush's two in-place
    # scatters.
    pool = math.prod(e.cache["k"].shape)
    made = {}
    for dims, kind in re.findall(
            r"= bf16\[([\d,]+),32,128\]\S* ([\w\-]+)\(", text):
        if 32 * 128 * math.prod(int(d) for d in dims.split(",")) \
                >= pool // 2:
            made[kind] = made.get(kind, 0) + 1
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast",
                         "fusion", "scatter"}, made
    assert made["fusion"] == made["scatter"] == 2, made
    # The loop over a slot's blocks nests in the loop over the tile's
    # slots, for the scores and again for the values: two loops more
    # than the parent's ten.
    assert text.count(" while(") == 12
    # ... and its temporaries are the parent's (PR 40: 64 332 800 B with
    # one flat loop over every tile slot and span block), to the 0.4 MB
    # by which the scores buffer's layout now pads 30 heads to 32
    # sublanes; a copy of one pool tensor would be 84 MB.
    assert temps < 64_332_800 * 1.01


@pytest.fixture(scope="module")
def windowed_engine_cut():
    """The windowed family at every published Trinity-Mini width, cut as
    the cell cuts it (the leading dense layer + one whole period: three
    window layers and a global one, all 128 experts; weights are shapes
    only; the cache is real: 7 slots — two tiles — of 33 280 rows over a
    pool of 70 blocks of 512, and 8 rings a window layer, ~0.2 GB of
    host memory)."""
    from skypilot_tpu.models import afmoe
    whole = afmoe.CONFIGS["trinity-mini"]
    cfg = dataclasses.replace(whole, n_layers=5, n_dense_layers=1,
                              layer_types=whole.layer_types[:5])
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(cfg.dtype),
        afmoe.init_params(jax.random.key(0), cfg)))
    return eng.InferenceEngine(
        params, cfg, n_slots=7, max_len=33280,
        prompt_buckets=(128, 512, 33280), max_wave=4, pad_waves=True,
        prefix_pool=0, spec_k=0, kv_block=512, kv_blocks=70)


@pytest.mark.parametrize("program", ["decode_burst", "prefill_chunk",
                                     "admit_wave"])
def test_windowed_programs_compile_for_v5e(one_chip, windowed_engine_cut,
                                           program, monkeypatch):
    """The ring-and-pool programs lower for the chip at the TOP rung (33
    280 rows): a token is 2048 B a layer in pool and ring alike (4
    key/value heads side by side on the minor axis; a heads axis of 4
    would be padded to a tile's 16 sublanes), what a slot holds is
    written IN PLACE — no program copies or re-lays a whole pool or ring
    tensor, and none builds an array of either's size with the 4 heads
    on an axis of their own — and the decode program holds no Mosaic
    kernel (the prefill programs hold the grouped expert products: one
    ``grouped_swiglu`` call an expert layer)."""
    e = windowed_engine_cut
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(grouped_ffn, "INTERPRET", False)
    params, _, cache, rng, table, S = _engine_args(e, one_chip)
    i32 = S((), jnp.int32)
    if program == "decode_burst":
        lowered = e._decode_burst_fn.__wrapped__.lower(
            params, cache, rng, S((e.n_slots + 1,), jnp.bool_), table,
            k=4, qweights=None, span=None, kernel=False)
        kernels = 0
    elif program == "prefill_chunk":
        lowered = e._prefill_chunk_fn.__wrapped__.lower(
            params, cache, S((512,), jnp.int32), i32, i32, i32, i32, rng,
            table, final=True, qweights=None, span=None, kernel=False)
        kernels = 4
    else:
        lowered = e._admit_wave_fn.__wrapped__.lower(
            params, cache, S((4, 512), jnp.int32), S((4,), jnp.int32),
            S((4,), jnp.int32), rng, table, bucket=512, qweights=None)
        kernels = 4
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == kernels
    assert e.cache["k"].shape == (1, 70, 512, 512)
    assert e.cache["win_k"].shape == (4, 8, 2048, 512)
    for name in ("k", "win_k"):
        row = e.cache[name].shape[-1] * e.cache[name].dtype.itemsize
        assert 2 * row == 2048                   # K and V, a token, a layer
    held = sum(e.cache[n].nbytes for n in ("k", "v", "win_k", "win_v"))
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    for name in ("k", "win_k"):
        shape = ",".join(str(n) for n in e.cache[name].shape)
        assert not re.search(rf"bf16\[{shape}\]\S* copy\(", text), \
            f"the {name} tensor is copied"
    smallest = min(math.prod(e.cache[n].shape) for n in ("k", "win_k"))
    for dims in re.findall(r"= bf16\[([\d,]+),4,128\]", text):
        assert 512 * math.prod(int(d) for d in dims.split(",")) \
            < smallest // 2, f"bf16[{dims},4,128]: heads on their own axis"
    # Transients stay a fraction of what is resident (8.5 GB of weights):
    # the largest is a chunk's key tile of float32 scores.
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    if program == "decode_burst":
        # steps; per unrolled layer its tile turns, slot and block loops
        # (scores, values), and the expert visits.
        assert text.count(" while(") >= 12


@pytest.fixture(scope="module")
def shortconv_engine_cut():
    """The short-convolution family at every published LFM2-8B-A1B
    width, cut as the cell cuts it (the leading dense layer + 12 expert
    layers: 10 conv, 3 attention, all 32 experts; weights are shapes
    only; the cache is real: 7 slots — two tiles — of 1280 rows over a
    pool of 40 blocks of 256, and 8 tails a conv layer, a few MB of host
    memory)."""
    from skypilot_tpu.models import lfm2_moe
    whole = lfm2_moe.CONFIGS["lfm2-8b-a1b"]
    cfg = dataclasses.replace(whole, n_layers=13, n_dense_layers=1,
                              layer_types=whole.layer_types[:13])
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(cfg.dtype),
        lfm2_moe.init_params(jax.random.key(0), cfg)))
    return eng.InferenceEngine(
        params, cfg, n_slots=7, max_len=1280, max_wave=4, pad_waves=True,
        prefix_pool=0, spec_k=0, kv_blocks=40)


@pytest.mark.parametrize("program", ["decode_burst", "prefill_chunk",
                                     "admit_wave"])
def test_shortconv_programs_compile_for_v5e(one_chip, shortconv_engine_cut,
                                            program, monkeypatch):
    """The tail-and-pool programs lower for the chip with HEADS OF 64
    (every other served family has 128): a token is 2048 B an attention
    layer in the pool (8 key/value heads of 64 side by side on the minor
    axis, 512 values; laid ``[..., 8, 64]`` the minor dim would pad to
    128 lanes), what a slot holds — rows and the two-row tails — is
    written IN PLACE (the family's scatters: the pool flush a layer and
    512 rows a turn, the tails by slot), a slot's tail is read by one
    small gather (8 KB, far under the 1 MiB a window may hold), no
    program copies or re-lays a pool tensor, an expert stack or the
    embedding, or builds a pool-sized array with the heads on an axis of
    their own, and the decode program
    holds no Mosaic kernel (the prefill programs hold the grouped expert
    products: one ``grouped_swiglu`` call an expert layer, whose tensors
    are arrays of their own — no stack is indexed in a loop body)."""
    e = shortconv_engine_cut
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(grouped_ffn, "INTERPRET", False)
    params, _, cache, rng, table, S = _engine_args(e, one_chip)
    i32 = S((), jnp.int32)
    if program == "decode_burst":
        lowered = e._decode_burst_fn.__wrapped__.lower(
            params, cache, rng, S((e.n_slots + 1,), jnp.bool_), table,
            k=4, qweights=None, span=None, kernel=False)
        kernels = 0
    elif program == "prefill_chunk":
        lowered = e._prefill_chunk_fn.__wrapped__.lower(
            params, cache, S((512,), jnp.int32), i32, i32, i32, i32, rng,
            table, final=True, qweights=None, span=None, kernel=False)
        kernels = 12
    else:
        lowered = e._admit_wave_fn.__wrapped__.lower(
            params, cache, S((4, 512), jnp.int32), S((4,), jnp.int32),
            S((4,), jnp.int32), rng, table, bucket=512, qweights=None)
        kernels = 12
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == kernels
    assert e.cache["k"].shape == (3, 40, 256, 512)
    assert e.cache["conv"].shape == (10, 8, 2, 2048)
    assert 2 * e.cache["k"].shape[-1] * e.cache["k"].dtype.itemsize == 2048
    held = sum(e.cache[n].nbytes for n in ("k", "v", "conv"))
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    # (The tails, 0.3 MB here and 2.7 MB at 33 slots, the compiler MOVES
    # to its fast memory for the steps of a burst: a copy worth having.)
    for shape in ("3,40,256,512", "32,2048,1792", "32,1792,2048",
                  "65536,2048"):
        assert not re.search(rf"bf16\[{shape}\]\S* copy\(", text), \
            f"a bf16[{shape}] tensor is copied"
    pool = math.prod(e.cache["k"].shape)
    for dims in re.findall(r"= bf16\[([\d,]+),8,64\]", text):
        assert 512 * math.prod(int(d) for d in dims.split(",")) \
            < pool // 2, f"bf16[{dims},8,64]: heads on their own axis"
    # Transients stay a fraction of what is resident (9.2 GB of weights):
    # the largest is a chunk's key tile of float32 scores.
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    if program == "decode_burst":
        # steps; per attention layer its tile turns, slot and block loops
        # (scores, values); per expert layer its visit.
        assert text.count(" while(") >= 1 + 3 * 5 + 12


@pytest.mark.parametrize("engine", [
    "engine_8b_2layers", "hybrid_engine_1period", "windowed_engine_cut"],
    ids=["w8a8", "hybrid", "windowed"])
def test_one_row_wave_compiles_with_smaller_temporaries(
        one_chip, engine, request, monkeypatch):
    """Under ``pad_waves`` a lone arrival's wave is ONE row of its
    bucket (the ladder's other rung beside ``max_wave``): the program
    lowers for the chip at published widths beside the four-row one,
    and, since the runtime reserves a program's temporaries for as long
    as the executable lives, holds fewer of them than the four-row
    program whose place beside the cache was already paid for. Its
    expert layers keep the grouped kernel (512 tokens are whole tiles)."""
    e = request.getfixturevalue(engine)
    assert e.wave_rungs == (1, 4)
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(grouped_ffn, "INTERPRET", False)
    params, qw, cache, rng, table, S = _engine_args(e, one_chip)
    temps, kernels = {}, {}
    for rows in e.wave_rungs:
        compiled = e._admit_wave_fn.__wrapped__.lower(
            params, cache, S((rows, 512), jnp.int32), S((rows,), jnp.int32),
            S((rows,), jnp.int32), rng, table, bucket=512,
            qweights=qw).compile()
        temps[rows] = compiled.memory_analysis().temp_size_in_bytes
        kernels[rows] = compiled.as_text().count("tpu_custom_call")
    assert temps[1] < temps[4], temps
    assert kernels[1] == kernels[4], kernels


def test_peaks_table_is_keyed_by_device_kind():
    v5e = _Device("tpu", "TPU v5 lite")
    row = attribution.peaks_for(v5e)
    assert (row.bf16_flops, row.int8_ops, row.hbm_bytes_per_s) == \
        (197e12, 393e12, 819e9)
    assert "v5e" in row.source
    assert attribution.device_peaks(v5e) == (197e12, 819e9)
    with pytest.raises(attribution.UnknownDeviceError, match="TPU v9"):
        attribution.device_peaks(_Device("tpu", "TPU v9 mega"))
    # The placeholder row is for platform == "cpu" only.
    assert attribution.peaks_for(_Device("cpu", "cpu")).source.startswith(
        "placeholder")
    with pytest.raises(attribution.UnknownDeviceError):
        attribution.peaks_for(_Device("tpu", "cpu"))


def test_compile_cache_helper(monkeypatch, tmp_path):
    # JAX_COMPILATION_CACHE_DIR set: it wins, untouched.
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert os.environ[compile_cache.ENV_VAR] == str(tmp_path)
    # Not set: the fixed in-checkout path. jax is imported in this
    # process, so configure() also tells its config — put that back.
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR)
    try:
        assert compile_cache.configure() == os.path.join(REPO,
                                                         ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert compile_cache.is_warm(str(tmp_path)) is False
    (tmp_path / "entry").write_text("x")
    assert compile_cache.is_warm(str(tmp_path)) is True


def test_flash_block_shrinks_to_a_divisor():
    assert fa.fit_block(2048, 512) == 512
    assert fa.fit_block(1280, 512) == 256      # the recipe's top bucket
    assert fa.fit_block(100, 64) is None
    assert fa.fit_block(256, 64, lanes_only=True) is None
    assert attn_ops.flash_eligible(1280, 128)
    assert not attn_ops.flash_eligible(1280, 64)     # head_dim
    assert not attn_ops.flash_eligible(1000, 128)    # no 128-row block
    assert not attn_ops.flash_eligible(512, 128)     # short: einsum wins


def test_serving_weights_that_cannot_fit_are_a_typed_error(monkeypatch):
    cfg = llama.CONFIGS["llama3-tiny"]
    params, qweights = eng.random_serving_weights(cfg)
    assert qweights is None and params["embed"].dtype == cfg.dtype
    params, qweights = eng.random_serving_weights(cfg, weights_int8=True)
    assert sorted(params["blocks"]) == ["ln1", "ln2"]
    assert qweights["blocks"]["wq"]["w"].dtype == jnp.int8
    small = _Device("tpu", "TPU v5 lite", {"bytes_limit": 100_000})
    monkeypatch.setattr(jax, "devices", lambda: [small])
    with pytest.raises(eng.WeightsDoNotFitError) as err:
        eng.random_serving_weights(cfg)
    assert err.value.typed_error["type"] == "weights_do_not_fit"
    assert err.value.typed_error["limit_bytes"] == 100_000
    assert err.value.typed_error["need_bytes"] == \
        cfg.num_params() * jnp.dtype(cfg.dtype).itemsize


def test_tp_engine_builds_its_cache_sharded():
    """Under a mesh the KV cache is created sharded (kv-heads over tp),
    never whole on one device and resharded afterwards."""
    from jax.sharding import Mesh
    cfg = llama.CONFIGS["llama3-tiny"]
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    params, _ = eng.random_serving_weights(cfg, mesh=mesh)
    e = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                            prompt_buckets=(16,), mesh=mesh)
    k = e.cache["k"]
    assert k.sharding.spec[3] == "tp"
    assert k.addressable_shards[0].data.shape[3] == cfg.n_kv_heads // 2
    assert params["blocks"]["wq"].sharding.spec[2] == "tp"


# chip_smoke.py's control flow, at tiny configs on the CPU.
_TINY_TRAIN = ["--config", "llama3-tiny", "--seq", "64", "--batch", "2",
               "--log-every", "1"]
_TINY_PLAN = {
    "serve": {"args": ["--config", "llama3-tiny", "--weights-int8",
                       "--kv-int8", "--slots", "4", "--max-len", "128",
                       "--max-burst", "8", "--open-burst", "4",
                       "--admit-wave", "2", "--prefill-chunk", "32"],
              "vocab": 512, "short": (24, 32), "long": (48, 64),
              "new_tokens": 8},
    "train": [
        {"name": "qlora-tiny",
         "args": _TINY_TRAIN + ["--steps", "3", "--qlora", "4",
                                "--qlora-random-base"]},
        {"name": "full-tiny", "args": _TINY_TRAIN + ["--steps", "4"]},
    ],
    "launch": {"args": _TINY_TRAIN + ["--steps", "2"]},
}


@pytest.fixture()
def in_pytest(monkeypatch):
    """pytest's process has imported JAX (on the CPU, where it holds
    nothing against a child); the script's parent never does. The
    children get one CPU device, not the suite's eight."""
    monkeypatch.setattr(chip_smoke, "_parent_is_off_jax", lambda: True)
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")


def _smoke(phases, tmp_path, **kw):
    lines = []
    code = chip_smoke.run(phases, _TINY_PLAN, str(tmp_path), emit=lines.append,
                          **kw)
    return code, [json.loads(line) for line in lines]


def test_chip_smoke_serve_and_train_rehearsal(tmp_path, in_pytest):
    """The platform check stubbed to "cpu": both phases run their real
    children and every check of the script holds at tiny size."""
    code, lines = _smoke([chip_smoke.phase_serve, chip_smoke.phase_train],
                         tmp_path, platform="cpu")
    assert [r.get("phase") for r in lines[:-1]] == [
        "serve", "train:qlora-tiny", "train:full-tiny"]
    assert all(r["ok"] for r in lines), lines
    serve = lines[0]
    assert serve["generate_codes"] == {"200": 6}
    assert [r["stream"] for r in serve["requests"]].count(True) == 3
    assert serve["requests"][-1]["cache_hit"] is True
    assert serve["repeat_diverged_at"] is None
    assert any(p.startswith("prefill_chunk") for p in serve["programs"])
    assert any(p.startswith("admit_wave") for p in serve["programs"])
    assert lines[1]["losses"][-1] <= lines[1]["losses"][0]
    assert code == 0
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


def test_chip_smoke_launch_rehearsal(tmp_path, in_pytest):
    code, lines = _smoke([chip_smoke.phase_launch], tmp_path,
                         platform="cpu")
    assert code == 0, lines
    assert lines[0]["phase"] == "launch" and lines[0]["down_exit"] == 0
    assert "(cpu: cpu)" in lines[0]["job_log_device"]


def test_chip_smoke_refuses_a_cpu(tmp_path, in_pytest):
    """With the real platform requirement a CPU child is killed, the
    phase fails, no later phase starts, and the last line says so."""
    code, lines = _smoke([chip_smoke.phase_serve, chip_smoke.phase_train],
                         tmp_path)
    assert code == 1
    assert [r.get("phase") for r in lines[:-1]] == ["serve"]
    assert lines[0]["ok"] is False and "'cpu'" in lines[0]["error"]
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


def test_chip_smoke_command_never_passes_without_a_chip(tmp_path):
    """The command itself, as the driver runs it, on this CPU-only
    host: non-zero, last line "ok": false. (The deadline keeps it from
    building 8B weights on the CPU first; the refusal of a CPU child
    is the test above.) And in a directory that holds the script and
    nothing else of the repo it prints no result at all."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--deadline",
         "0.01", "--out", str(tmp_path / "out")],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and set(last) == {"ok", "device"}

    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=alone, env=env,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and p.stdout.strip() == ""
