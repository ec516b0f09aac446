"""Hybrid decoder: gated-delta-rule linear-attention layers with a full
softmax-attention layer every few (``olmo_hybrid``: Olmo-Hybrid-7B),
pure functional JAX.

The block (``x`` the residual stream; the OLMo 2 / 3 placement: each
sub-layer's OUTPUT is normed, then added; untied embedding and head)::

    x = x + RMSNorm(mixer(x));   x = x + RMSNorm(SwiGLU(x))

* **Linear mixer** (``layer_types`` ``linear_attention``). ``q~, k~, v~ =
  W_q x, W_k x, W_v x``; the three pass ONE depthwise causal convolution
  of width ``conv_kernel`` over time (channels ``q | k | v``), then SiLU;
  per head ``q = q~ / |q~| * d_k^-1/2``, ``k = k~ / |k~|``; ``beta = 2
  sigmoid(w_b . x)`` (``allow_neg_eigval``: the transition's eigenvalues
  reach -1), ``log a = -exp(A_log) softplus(w_a . x + dt_bias)``; the
  gated delta rule (``ops/gated_delta.py``) over a float32 state ``S``
  [d_v, d_k] per head; ``y = W_o(RMSNorm_{d_v}(o) * SiLU(W_g x))``. What a
  sequence carries from one call to the next is ``S`` and the last
  ``conv_kernel - 1`` inputs of the convolution
  (:func:`zero_mixer_state`).
* **Full mixer** (``full_attention``). Causal softmax attention over
  ``n_heads`` heads of ``head_dim`` (``n_kv_heads`` key/value heads), no
  bias, RMSNorm over the whole projected ``q`` and ``k`` before the heads
  are split, and NO rotation when the published ``rope_theta`` is null.

The stack is periodic — ``lin_per_period`` linear layers, then one full
layer — and is ONE ``lax.scan`` over the periods (:func:`scan_periods`):
the body unrolls a period, so a 32-layer model compiles four layer
bodies, not 32. Parameters are ``lin``, a LIST with one entry a place
in the period, each stacked ``[periods, ...]``, and ``full`` stacked
``[periods, ...]``: every stack is a tensor of its own that the scan
slices a period a turn, as ``llama``'s scan slices its layers. (Stacked
together as ``[periods, places, ...]`` or ``[places, periods, ...]`` and
indexed by place inside the program, the TPU compiler copies the
weights out of the stack before use: every step, or once a program at
the price of a second copy of the weights in memory.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.models import llama
from skypilot_tpu.ops import gated_delta as gd
from skypilot_tpu.parallel import ring_attention as ra

Params = Dict[str, Any]

# The serve programs of this family (``infer.kvcache.programs_for``).
SERVE_PROGRAMS = "skypilot_tpu.infer.hybrid"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Hyperparameters under the names the repo's other models use;
    :func:`from_published` maps a ``config.json``'s own key names."""

    vocab_size: int = 100_352
    d_model: int = 3840
    n_layers: int = 32
    lin_per_period: int = 3          # linear layers before each full one
    n_heads: int = 30                # full attention
    n_kv_heads: int = 30
    head_dim: int = 128
    lin_heads: int = 30              # key heads == value heads
    lin_k_dim: int = 96
    lin_v_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    d_ff: int = 11_008
    rope_theta: Optional[float] = None      # None: no rotation
    norm_eps: float = 1e-6
    max_seq_len: int = 65_536
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16        # activation / compute dtype
    param_dtype: Any = jnp.float32   # storage dtype for parameters

    @property
    def period(self) -> int:
        return self.lin_per_period + 1

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    @property
    def n_lin_layers(self) -> int:
        return self.n_periods * self.lin_per_period

    @property
    def n_full_layers(self) -> int:
        return self.n_periods

    @property
    def conv_channels(self) -> int:
        """Channels of the short convolution: ``q | k | v``."""
        return self.lin_heads * (2 * self.lin_k_dim + self.lin_v_dim)

    def lin_mixer_params(self) -> int:
        d, h = self.d_model, self.lin_heads
        return (d * self.conv_channels                   # W_q, W_k, W_v
                + self.conv_kernel * self.conv_channels
                + 2 * d * h + 2 * h                      # w_a, w_b, A_log, dt
                + 2 * d * h * self.lin_v_dim             # W_g, W_o
                + self.lin_v_dim)                        # the output norm

    def full_mixer_params(self) -> int:
        d = self.d_model
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        return 2 * d * q + 2 * d * kv + q + kv

    def num_params(self) -> int:
        d = self.d_model
        rest = 3 * d * self.d_ff + 2 * d                 # SwiGLU + 2 norms
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return (self.n_lin_layers * (self.lin_mixer_params() + rest)
                + self.n_full_layers * (self.full_mixer_params() + rest)
                + emb + d)


def period_of(layer_types) -> int:
    """Linear layers before each full one in a published ``layer_types``
    (which must repeat ``linear x n, full`` whole)."""
    types = list(layer_types)
    if "full_attention" not in types:
        raise ValueError("layer_types names no full_attention layer")
    n = types.index("full_attention")
    want = (["linear_attention"] * n + ["full_attention"]) \
        * (len(types) // (n + 1))
    if n < 1 or types != want:
        raise ValueError(
            "layer_types must repeat (linear_attention x n, "
            f"full_attention) whole; got {types}")
    return n


def from_published(config: Dict[str, Any], **overrides) -> OlmoHybridConfig:
    """An ``olmo_hybrid`` ``config.json`` (its own key names) as an
    :class:`OlmoHybridConfig`."""
    types = list(config["layer_types"])[:int(config["num_hidden_layers"])]
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types is shorter than num_hidden_layers")
    if int(config["linear_num_key_heads"]) \
            != int(config["linear_num_value_heads"]):
        raise ValueError("grouped linear-attention heads are not built")
    if config.get("attention_bias"):
        raise ValueError("attention_bias is not built")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    theta = (config.get("rope_parameters") or {}).get("rope_theta")
    fields = dict(
        vocab_size=int(config["vocab_size"]), d_model=d,
        n_layers=len(types), lin_per_period=period_of(types),
        n_heads=h, n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // h),
        lin_heads=int(config["linear_num_key_heads"]),
        lin_k_dim=int(config["linear_key_head_dim"]),
        lin_v_dim=int(config["linear_value_head_dim"]),
        conv_kernel=int(config["linear_conv_kernel_dim"]),
        allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        d_ff=int(config["intermediate_size"]),
        rope_theta=None if theta is None else float(theta),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)))
    fields.update(overrides)
    return OlmoHybridConfig(**fields)


CONFIGS: Dict[str, OlmoHybridConfig] = {
    # The published model (7.43 B parameters, 14.9 GB in bf16).
    "olmo-hybrid-7b": OlmoHybridConfig(),
    # Every mechanism at a size the CPU tests run: two periods of two
    # linear layers and a full one, grouped key/value heads.
    "olmo-hybrid-tiny": OlmoHybridConfig(
        vocab_size=512, d_model=64, n_layers=6, lin_per_period=2,
        n_heads=4, n_kv_heads=2, head_dim=16, lin_heads=4, lin_k_dim=8,
        lin_v_dim=16, d_ff=128, max_seq_len=512),
}


# ---------------------------------------------------------------------------
# Parameter init + logical sharding axes
# ---------------------------------------------------------------------------

def group_shapes(cfg: OlmoHybridConfig
                 ) -> Dict[str, Dict[str, Tuple[Tuple[int, ...], int]]]:
    """``{group: {name: (per-layer shape, fan_in)}}``; fan_in 0 marks a
    norm scale, -1 the rule's ``A_log``, -2 its ``dt_bias``."""
    d, ff = cfg.d_model, cfg.d_ff
    h, dk, dv = cfg.lin_heads, cfg.lin_k_dim, cfg.lin_v_dim
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rest = {"mixer_norm": ((d,), 0), "ffn_norm": ((d,), 0),
            "w_gate": ((d, ff), d), "w_up": ((d, ff), d),
            "w_down": ((ff, d), ff)}
    return {
        "lin": dict(rest, wq=((d, h, dk), d), wk=((d, h, dk), d),
                    wv=((d, h, dv), d),
                    conv=((cfg.conv_kernel, cfg.conv_channels),
                          cfg.conv_kernel),
                    w_a=((d, h), d), w_b=((d, h), d),
                    A_log=((h,), -1), dt_bias=((h,), -2),
                    wg=((d, h, dv), d), o_norm=((dv,), 0),
                    wo=((h, dv, d), h * dv)),
        "full": dict(rest, wq=((d, nh, hd), d), wk=((d, nkv, hd), d),
                     wv=((d, nkv, hd), d), q_norm=((nh * hd,), 0),
                     k_norm=((nkv * hd,), 0), wo=((nh, hd, d), nh * hd))}


def init_params(rng: jax.Array, cfg: OlmoHybridConfig) -> Params:
    """Random parameters. ``A_log`` and ``dt_bias`` take the Mamba-2 /
    Gated DeltaNet initialisation: ``A`` uniform in 1..16, ``dt``
    log-uniform in 1e-3..1e-1 stored through softplus' inverse."""
    d, v = cfg.d_model, cfg.vocab_size
    keys = iter(jax.random.split(rng, 64))

    def draw(shape, fan_in):
        if fan_in == 0:
            return jnp.ones(shape, cfg.param_dtype)
        if fan_in == -1:
            return jnp.log(jax.random.uniform(
                next(keys), shape, cfg.param_dtype, 1.0, 16.0))
        if fan_in == -2:
            dt = jnp.exp(jax.random.uniform(
                next(keys), shape, cfg.param_dtype,
                jnp.log(1e-3), jnp.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        return jax.random.normal(next(keys), shape,
                                 cfg.param_dtype) * fan_in ** -0.5

    params: Params = {
        "embed": jax.random.normal(next(keys), (v, d),
                                   cfg.param_dtype) * 0.02,
        "final_norm": jnp.ones((d,), cfg.param_dtype)}

    def stack(group):
        return {name: draw((cfg.n_periods,) + shape, fan_in)
                for name, (shape, fan_in)
                in group_shapes(cfg)[group].items()}

    params["lin"] = [stack("lin") for _ in range(cfg.lin_per_period)]
    params["full"] = stack("full")
    if not cfg.tie_embeddings:
        params["lm_head"] = draw((d, v), d)
    return params


def param_logical_axes(cfg: OlmoHybridConfig) -> Params:
    """Logical axis names per parameter (``parallel.sharding`` rules)."""
    rest = {"mixer_norm": ("embed",), "ffn_norm": ("embed",),
            "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
            "w_down": ("mlp", "embed")}
    per_layer = {
        "lin": dict(rest, wq=("embed", "heads", "head_dim"),
                    wk=("embed", "heads", "head_dim"),
                    wv=("embed", "heads", "head_dim"), conv=(None, None),
                    w_a=("embed", "heads"), w_b=("embed", "heads"),
                    A_log=("heads",), dt_bias=("heads",),
                    wg=("embed", "heads", "head_dim"), o_norm=(None,),
                    wo=("heads", "head_dim", "embed")),
        "full": dict(rest, wq=("embed", "heads", "head_dim"),
                     wk=("embed", "kv_heads", "head_dim"),
                     wv=("embed", "kv_heads", "head_dim"),
                     q_norm=(None,), k_norm=(None,),
                     wo=("heads", "head_dim", "embed"))}
    stacked = {group: {name: ("layer",) + ax for name, ax in named.items()}
               for group, named in per_layer.items()}
    axes: Params = {"embed": ("vocab", "embed"), "final_norm": ("embed",),
                    "lin": [stacked["lin"]] * cfg.lin_per_period,
                    "full": stacked["full"]}
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# The linear mixer, in the pieces the serve programs share
# ---------------------------------------------------------------------------

def lin_project(cfg: OlmoHybridConfig, layer: Params, x: jax.Array):
    """Everything the linear mixer takes from ``x`` [B, T, D] before
    time enters: the convolution's input ``qkv`` [B, T, conv_channels]
    (compute dtype), the rule's ``g`` (log decay, <= 0) and ``beta`` [B,
    T, H] float32, and the output gate's input [B, T, H, d_v]."""
    dt, f32 = cfg.dtype, jnp.float32
    B, T, _ = x.shape
    qkv = jnp.concatenate(
        [jnp.einsum("btd,dhk->bthk", x, layer[n].astype(dt)).reshape(B, T, -1)
         for n in ("wq", "wk", "wv")], axis=-1)
    xf = x.astype(f32)
    a = jnp.einsum("btd,dh->bth", xf, layer["w_a"].astype(f32))
    b = jnp.einsum("btd,dh->bth", xf, layer["w_b"].astype(f32))
    g = -jnp.exp(layer["A_log"].astype(f32)) \
        * jax.nn.softplus(a + layer["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.allow_neg_eigval else 1.0)
    gate = jnp.einsum("btd,dhv->bthv", x, layer["wg"].astype(dt))
    return qkv, g, beta, gate


def lin_heads_of(cfg: OlmoHybridConfig, qkv: jax.Array):
    """The convolved ``qkv`` [..., conv_channels] as the rule's
    operands: ``q`` (L2-normalised, scaled ``d_k^-1/2``) and ``k``
    (L2-normalised) [..., H, d_k] float32, ``v`` [..., H, d_v]."""
    h, dk, dv = cfg.lin_heads, cfg.lin_k_dim, cfg.lin_v_dim
    lead = qkv.shape[:-1]
    q = gd.l2_normalize(qkv[..., :h * dk].reshape(lead + (h, dk))) \
        * dk ** -0.5
    k = gd.l2_normalize(qkv[..., h * dk:2 * h * dk].reshape(lead + (h, dk)))
    return q, k, qkv[..., 2 * h * dk:].reshape(lead + (h, dv))


def lin_output(cfg: OlmoHybridConfig, layer: Params, o: jax.Array,
               gate: jax.Array) -> jax.Array:
    """``W_o(RMSNorm_{d_v}(o) * SiLU(gate))``: ``o`` [B, T, H, d_v]
    float32 -> [B, T, D]."""
    dt = cfg.dtype
    o = llama.rms_norm(o, layer["o_norm"], cfg.norm_eps).astype(dt)
    return jnp.einsum("bthv,hvd->btd", o * jax.nn.silu(gate),
                      layer["wo"].astype(dt))


@jax.named_scope("linear_mixer")
def linear_mixer(cfg: OlmoHybridConfig, layer: Params, x: jax.Array,
                 state: jax.Array, tail: jax.Array, n_valid: jax.Array):
    """The linear mixer over a run of tokens ``x`` [B, T, D] that
    continues ``state`` [B, H, d_v, d_k] float32 and ``tail`` [B, K - 1,
    conv_channels] (zeros: a sequence's start). Tokens at or past
    ``n_valid`` [B] are padding: no decay, no write, and the returned
    tail is the one after each row's last REAL token. Returns (``y`` [B,
    T, D], state', tail')."""
    qkv, g, beta, gate = lin_project(cfg, layer, x)
    qkv, tail = gd.causal_conv(qkv, layer["conv"], tail, n_valid)
    q, k, v = lin_heads_of(cfg, qkv)
    g, beta = gd.mask_pad(
        g, beta, jnp.arange(x.shape[1])[None, :] < n_valid[:, None])
    o, state = gd.chunk_rule(q, k, v, g, beta, state)
    return lin_output(cfg, layer, o, gate), state, tail


def zero_mixer_state(cfg: OlmoHybridConfig, batch: int):
    """(state, tail) of ``batch`` sequences that have seen nothing."""
    return (jnp.zeros((batch, cfg.lin_heads, cfg.lin_v_dim, cfg.lin_k_dim),
                      jnp.float32),
            jnp.zeros((batch, cfg.conv_kernel - 1, cfg.conv_channels),
                      cfg.dtype))


# ---------------------------------------------------------------------------
# The full mixer's projections, the feed-forward, the head
# ---------------------------------------------------------------------------

def rope_tables(cfg: OlmoHybridConfig, positions: jax.Array):
    """cos/sin for ``positions``, or ``None`` without a ``rope_theta``
    (the published file's is null: no rotation)."""
    if cfg.rope_theta is None:
        return None
    hd = cfg.head_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


@jax.named_scope("qkv_proj")
def full_project(cfg: OlmoHybridConfig, layer: Params, x: jax.Array,
                 rope=None):
    """``x`` [B, T, D] -> ``q`` [B, T, n_heads, hd], ``k``, ``v`` [B, T,
    n_kv_heads, hd]: projections, RMSNorm over the whole projected ``q``
    and ``k``, heads split, rotation where the config has one."""
    dt = cfg.dtype
    B, T, _ = x.shape
    q = jnp.einsum("btd,dhk->bthk", x, layer["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", x, layer["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", x, layer["wv"].astype(dt))
    q = llama.rms_norm(q.reshape(B, T, -1), layer["q_norm"],
                       cfg.norm_eps).reshape(q.shape)
    k = llama.rms_norm(k.reshape(B, T, -1), layer["k_norm"],
                       cfg.norm_eps).reshape(k.shape)
    if rope is not None:
        q = llama.apply_rope(q, *rope)
        k = llama.apply_rope(k, *rope)
    return q, k, v


@jax.named_scope("out_ffn")
def out_ffn(cfg: OlmoHybridConfig, layer: Params, x: jax.Array,
            mixed: jax.Array) -> jax.Array:
    """The back half of either layer: the mixer's output ``mixed`` [B,
    T, D] normed and added, then the SwiGLU's output normed and added."""
    dt = cfg.dtype
    x = x + llama.rms_norm(mixed.astype(dt), layer["mixer_norm"],
                           cfg.norm_eps)
    gate = jnp.einsum("btd,df->btf", x, layer["w_gate"].astype(dt))
    up = jnp.einsum("btd,df->btf", x, layer["w_up"].astype(dt))
    y = jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up,
                   layer["w_down"].astype(dt))
    return x + llama.rms_norm(y, layer["ffn_norm"], cfg.norm_eps)


def full_output(cfg: OlmoHybridConfig, layer: Params, o: jax.Array):
    """``o`` [B, T, n_heads, hd] -> [B, T, D]."""
    return jnp.einsum("bthk,hkd->btd", o.astype(cfg.dtype),
                      layer["wo"].astype(cfg.dtype))


@jax.named_scope("lm_head")
def head_logits(cfg: OlmoHybridConfig, params: Params, x: jax.Array):
    """Final norm + head over rows x [..., D] -> float32 logits."""
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...d,dv->...v", x,
                      head.astype(cfg.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

def scan_periods(cfg: OlmoHybridConfig, params: Params, carry,
                 lin_fn: Callable, full_fn: Callable):
    """One ``lax.scan`` over the periods. A turn runs ``lin_fn(carry,
    layer, li) -> (carry, ys)`` for each linear layer of the period
    (``li`` its index among ALL linear layers: a state's layer axis) and
    then ``full_fn(carry, layer, fi) -> (carry, ys)`` (``fi`` among the
    full layers: a KV cache's layer axis). Returns (carry, linear ys
    stacked ``[n_lin_layers, ...]``, full ys stacked ``[n_full_layers,
    ...]``)."""
    n = cfg.lin_per_period

    def body(c, xs):
        lin_p, full_p, p = xs
        lin_ys = []
        for j in range(n):
            c, ys = lin_fn(c, lin_p[j], p * n + j)
            lin_ys.append(ys)
        c, full_ys = full_fn(c, full_p, p)
        return c, (jax.tree.map(lambda *a: jnp.stack(a), *lin_ys), full_ys)

    carry, (lin_ys, full_ys) = lax.scan(
        body, carry, (list(params["lin"]), params["full"],
                      jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    lin_ys = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), lin_ys)
    return carry, lin_ys, full_ys


def forward_hidden(params: Params, tokens: jax.Array, cfg: OlmoHybridConfig,
                   true_lens: Optional[jax.Array] = None, mesh=None,
                   heads_axis=None):
    """Token ids [B, S] (right-padded to ``true_lens`` [B]; absent: all
    real) -> (hidden [B, S, D] before the final norm, what a cache keeps:
    ``{"k", "v": [L_full, B, S, n_kv_heads, hd], "state": [L_lin, B, H,
    d_v, d_k], "conv": [L_lin, B, K - 1, conv_channels]}`` — the state
    and tail after each row's last real token)."""
    B, S = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((B,), S, jnp.int32)
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    rope = rope_tables(cfg, jnp.arange(S))
    state0, tail0 = zero_mixer_state(cfg, B)

    def lin_fn(x, layer, li):
        y, state, tail = linear_mixer(cfg, layer, x, state0, tail0,
                                      true_lens)
        return out_ffn(cfg, layer, x, y), (state, tail)

    def full_fn(x, layer, fi):
        q, k, v = full_project(cfg, layer, x, rope)
        with jax.named_scope("attn_core"):
            o = ra.local_attention(q, k, v, mesh, causal=True,
                                   batch_axes=None, heads_axis=heads_axis)
        return out_ffn(cfg, layer, x, full_output(cfg, layer, o)), (k, v)

    x, (state, conv), (k, v) = scan_periods(cfg, params, x, lin_fn, full_fn)
    return x, {"k": k, "v": v, "state": state, "conv": conv}


def forward(params: Params, tokens: jax.Array, cfg: OlmoHybridConfig
            ) -> jax.Array:
    """Token ids [B, S] -> logits [B, S, vocab] float32."""
    x, _ = forward_hidden(params, tokens, cfg)
    return head_logits(cfg, params, x)
