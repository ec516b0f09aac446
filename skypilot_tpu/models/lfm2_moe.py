"""Decoder whose layers are GATED SHORT CONVOLUTIONS with a grouped-query
attention layer every few, routed experts (no shared one) after the
leading dense layers, and a tied head (``lfm2_moe``: LFM2-8B-A1B), pure
functional JAX.

The block (``x`` the residual stream; two RMSNorms a layer)::

    h = x + Op(N1(x));   y = h + FFN(N2(h))

* **conv operator** (``layer_types[i] == "conv"``). ``[B, C, x~] = W_in
  u`` (``d_model -> 3 d_model``, split in that order), ``z = B * x~``,
  ``c_t = sum_j w_j * z_{t - (K - 1) + j}`` (depthwise, causal, ``K =
  conv_L_cache`` taps, no bias, NO activation), output ``W_out (C *
  c)``. All it needs of the past is the last ``K - 1`` values of ``z``:
  the TAIL a serving slot carries (two rows of ``d_model`` at ``K = 3``).
* **attention** (``"full_attention"``). ``q`` as ``n_heads x head_dim``,
  ``k``, ``v`` as ``n_kv_heads x head_dim``; RMSNorm over each head's
  values of ``q`` and of ``k`` (a learned scale of ``head_dim``);
  rotate-half RoPE over the whole head; causal softmax at ``head_dim **
  -0.5``; ``W_o``. No bias anywhere.
* **FFN.** The first ``n_dense_layers`` layers: a SwiGLU. Every later
  layer: ``models/glm_moe.py``'s expert layer as it stands
  (:func:`glm_moe.moe_ffn`: float32 sigmoid router, a selection-only
  bias, top-k weights normalised and scaled, dropless) with
  ``n_shared_experts`` 0 — no ``ws_*`` tensor exists and no shared
  product runs. This config object answers to the names that layer
  reads.
* **head.** A last RMSNorm, then logits against the embedding (tied).

``layer_types`` is a LIST and need repeat with no period (the published
one ends ``... c c F c c``): the stack is walked layer by layer in the
order the list gives (:func:`walk_layers`), ``params["layers"]`` a list
of per-layer trees, and what the two KINDS of layer leave behind — a
tail per conv layer, K/V rows per attention layer — is stacked by kind,
each kind's layers counted in stack order. A layer's tensors are arrays
of their own: none is a slice of a stack, so the expert kernels read
each where it lies.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import afmoe, glm_moe, llama
from skypilot_tpu.ops import gated_delta as gd

Params = Dict[str, Any]

# The serve programs of this family (``infer.kvcache.programs_for``).
SERVE_PROGRAMS = "skypilot_tpu.infer.shortconv"

CONV, FULL = "conv", "full_attention"

_PUBLISHED_TYPES = tuple(
    FULL if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """Hyperparameters under the names the repo's other models use
    (and those :mod:`glm_moe`'s expert layer reads);
    :func:`from_published` maps a ``config.json``'s own key names."""

    vocab_size: int = 65_536
    d_model: int = 2048
    n_layers: int = 24
    layer_types: Tuple[str, ...] = _PUBLISHED_TYPES
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    conv_kernel: int = 3             # taps: a slot carries conv_kernel - 1
    n_dense_layers: int = 2
    d_ff: int = 7168                 # the dense layers' SwiGLU width
    moe_d_ff: int = 1792             # each routed expert's width
    n_routed_experts: int = 32
    n_shared_experts: int = 0
    experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 128_000
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16        # activation / compute dtype
    param_dtype: Any = jnp.float32   # storage dtype for parameters

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError("layer_types must name every layer")
        bad = [t for t in self.layer_types if t not in (CONV, FULL)]
        if bad:
            raise ValueError(f"unknown layer type {bad[0]!r}")
        if self.n_shared_experts:
            raise ValueError("the family has no shared expert")
        if not self.tie_embeddings:
            raise ValueError("the family's head is its embedding")

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == CONV)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    @property
    def n_conv_layers(self) -> int:
        return len(self.conv_layers)

    @property
    def n_full_layers(self) -> int:
        return len(self.full_layers)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kv_width(self) -> int:
        """Values of one token's K (or V) row in one attention layer."""
        return self.n_kv_heads * self.head_dim

    def conv_params(self) -> int:
        d = self.d_model
        return 3 * d * d + self.conv_kernel * d + d * d

    def attn_params(self) -> int:
        d, q = self.d_model, self.n_heads * self.head_dim
        return 2 * d * q + 2 * d * self.kv_width + 2 * self.head_dim

    def expert_params(self) -> int:
        """One layer's routed experts."""
        return self.n_routed_experts * 3 * self.d_model * self.moe_d_ff

    def num_params(self) -> int:
        d, e = self.d_model, self.n_routed_experts
        ops = (self.n_conv_layers * self.conv_params()
               + self.n_full_layers * self.attn_params())
        ffn = (self.n_dense_layers * 3 * d * self.d_ff
               + self.n_moe_layers * (d * e + e + self.expert_params()))
        return ops + ffn + self.n_layers * 2 * d + self.vocab_size * d + d

    def active_params(self) -> int:
        """Parameters one token multiplies with (its chosen experts
        only; the tied embedding once, as the head)."""
        idle = (self.n_routed_experts - self.experts_per_tok) \
            * 3 * self.d_model * self.moe_d_ff
        return self.num_params() - self.n_moe_layers * idle


def from_published(config: Dict[str, Any], **overrides) -> Lfm2MoeConfig:
    """An ``lfm2_moe`` ``config.json`` (its own key names) as an
    :class:`Lfm2MoeConfig`; the first ``num_hidden_layers`` entries of
    its ``layer_types`` run."""
    if config.get("conv_bias", False):
        raise ValueError("a convolution bias is not built")
    if not config.get("use_expert_bias", True):
        raise ValueError("a router without its selection bias is not built")
    if config.get("rope_scaling"):
        raise ValueError("rope_scaling is not built")
    n = int(config["num_hidden_layers"])
    types = tuple(config["layer_types"])[:n]
    if len(types) != n:
        raise ValueError("layer_types is shorter than num_hidden_layers")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    fields = dict(
        vocab_size=int(config["vocab_size"]), d_model=d, n_layers=n,
        layer_types=types, n_heads=h,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // h),
        conv_kernel=int(config["conv_L_cache"]),
        n_dense_layers=int(config["num_dense_layers"]),
        d_ff=int(config["intermediate_size"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        n_routed_experts=int(config["num_experts"]),
        n_shared_experts=int(config.get("num_shared_experts", 0)),
        experts_per_tok=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config.get("norm_topk_prob", True)),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", True)))
    fields.update(overrides)
    return Lfm2MoeConfig(**fields)


CONFIGS: Dict[str, Lfm2MoeConfig] = {
    # The published model (8.34 B parameters: 16.7 GB in bf16, more than
    # one 16 GB chip holds).
    "lfm2-8b-a1b": Lfm2MoeConfig(),
    # Every mechanism at a size the CPU tests run: a leading dense layer,
    # a layer list with NO period (c | F c c F c F c: the attention
    # layers 3, 2 and 2 apart), heads of 16, 8 experts top-2.
    "lfm2-moe-tiny": Lfm2MoeConfig(
        vocab_size=512, d_model=64, n_layers=8,
        layer_types=(CONV, FULL, CONV, CONV, FULL, CONV, FULL, CONV),
        n_heads=4, n_kv_heads=2, head_dim=16, n_dense_layers=1, d_ff=128,
        moe_d_ff=32, n_routed_experts=8, experts_per_tok=2,
        max_seq_len=512),
}


# ---------------------------------------------------------------------------
# Parameter init + logical sharding axes
# ---------------------------------------------------------------------------

def layer_shapes(cfg: Lfm2MoeConfig, i: int
                 ) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """``{name: (shape, fan_in)}`` of layer ``i``'s tensors (fan_in 0
    marks a norm scale, -1 the router's selection bias)."""
    d, nh, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = {"ln1": ((d,), 0), "ln2": ((d,), 0)}
    if cfg.layer_types[i] == CONV:
        out.update(w_in=((d, 3 * d), d), conv=((cfg.conv_kernel, d),
                                               cfg.conv_kernel),
                   w_out=((d, d), d))
    else:
        out.update(q_norm=((hd,), 0), k_norm=((hd,), 0),
                   wq=((d, nh, hd), d), wk=((d, g, hd), d),
                   wv=((d, g, hd), d), wo=((nh, hd, d), nh * hd))
    if i < cfg.n_dense_layers:
        ff = cfg.d_ff
        return dict(out, w_gate=((d, ff), d), w_up=((d, ff), d),
                    w_down=((ff, d), ff))
    e, f = cfg.n_routed_experts, cfg.moe_d_ff
    return dict(out, router=((d, e), d), router_bias=((e,), -1),
                we_gate=((e, d, f), d), we_up=((e, d, f), d),
                we_down=((e, f, d), f))


def init_params(rng: jax.Array, cfg: Lfm2MoeConfig) -> Params:
    """Random parameters: ``embed``, ``final_norm`` and ``layers``, a
    list of per-layer trees in stack order. The selection bias is a
    trained buffer in a checkpoint; here it is drawn at a scale that
    changes some choices."""
    keys = iter(jax.random.split(rng, 16 * (cfg.n_layers + 2)))

    def draw(shape, fan_in):
        if fan_in == 0:
            return jnp.ones(shape, cfg.param_dtype)
        std = 0.05 if fan_in < 0 else fan_in ** -0.5
        return jax.random.normal(next(keys), shape, cfg.param_dtype) * std

    return {
        "embed": jax.random.normal(
            next(keys), (cfg.vocab_size, cfg.d_model),
            cfg.param_dtype) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "layers": [{name: draw(shape, fan_in) for name, (shape, fan_in)
                    in layer_shapes(cfg, i).items()}
                   for i in range(cfg.n_layers)]}


def param_logical_axes(cfg: Lfm2MoeConfig) -> Params:
    """Logical axis names per parameter (``parallel.sharding`` rules)."""
    per_layer = {
        "ln1": ("embed",), "ln2": ("embed",), "q_norm": (None,),
        "k_norm": (None,), "w_in": ("embed", "mlp"), "conv": (None, "embed"),
        "w_out": ("mlp", "embed"),
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
        "w_down": ("mlp", "embed"), "router": ("embed", None),
        "router_bias": (None,), "we_gate": ("expert", "embed", "mlp"),
        "we_up": ("expert", "embed", "mlp"),
        "we_down": ("expert", "mlp", "embed")}
    return {"embed": ("vocab", "embed"), "final_norm": ("embed",),
            "layers": [{name: per_layer[name]
                        for name in layer_shapes(cfg, i)}
                       for i in range(cfg.n_layers)]}


# ---------------------------------------------------------------------------
# The layer, in the pieces the serve programs share
# ---------------------------------------------------------------------------

# cos/sin of positions, final norm + tied head, plain masked attention
# and its causal mask: the windowed family's, which read of a config the
# names this one has too.
rope_tables = afmoe.rope_tables
head_logits = afmoe.head_logits
attend = afmoe.attend


def embed(cfg: Lfm2MoeConfig, params: Params, tokens: jax.Array
          ) -> jax.Array:
    """Token ids [...] -> rows [..., D]."""
    return params["embed"].astype(cfg.dtype)[tokens]


@jax.named_scope("short_conv")
def short_conv(cfg: Lfm2MoeConfig, layer: Params, x: jax.Array,
               tail: jax.Array, n_valid: jax.Array):
    """The conv operator over ``x`` [B, T, D] continuing ``tail`` [B, K -
    1, D] (the ``z`` of the K - 1 tokens before ``x_0``; zeros at a
    sequence's start), ``n_valid`` [B] the real tokens of each row: the
    first norm, the in-projection, both gates, the convolution
    (``ops.gated_delta.carried_conv``: float32 taps and sum) and the
    out-projection. Returns (the operator's output [B, T, D], the tail
    after each row's LAST REAL token)."""
    dt = cfg.dtype
    u = llama.rms_norm(x, layer["ln1"], cfg.norm_eps)
    b, c, xt = jnp.split(
        jnp.einsum("btd,de->bte", u, layer["w_in"].astype(dt)), 3, axis=-1)
    acc, tail = gd.carried_conv(b * xt, layer["conv"], tail, n_valid)
    return jnp.einsum("btd,de->bte", c * acc.astype(dt),
                      layer["w_out"].astype(dt)), tail


@jax.named_scope("qkv_proj")
def attn_project(cfg: Lfm2MoeConfig, layer: Params, x: jax.Array, rope):
    """``x`` [B, T, D] -> ``q`` [B, T, n_heads, hd], ``k``, ``v`` [B, T,
    n_kv_heads, hd]: the first norm, the projections, RMSNorm over each
    head of ``q`` and ``k``, the rotation by ``rope`` (cos, sin)."""
    dt = cfg.dtype
    u = llama.rms_norm(x, layer["ln1"], cfg.norm_eps)
    q = jnp.einsum("btd,dhk->bthk", u, layer["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", u, layer["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", u, layer["wv"].astype(dt))
    q = llama.rms_norm(q, layer["q_norm"], cfg.norm_eps)
    k = llama.rms_norm(k, layer["k_norm"], cfg.norm_eps)
    return llama.apply_rope(q, *rope), llama.apply_rope(k, *rope), v


@jax.named_scope("out_ffn")
def attn_output(cfg: Lfm2MoeConfig, layer: Params, o: jax.Array
                ) -> jax.Array:
    """The attention result ``o`` [B, T, n_heads, hd] through ``W_o``."""
    return jnp.einsum("bthk,hkd->btd", o.astype(cfg.dtype),
                      layer["wo"].astype(cfg.dtype))


@jax.named_scope("out_ffn")
def out_ffn(cfg: Lfm2MoeConfig, layer: Params, x: jax.Array, y: jax.Array,
            moe: bool, live=None):
    """The back half of a layer: the operator's output ``y`` added, then
    the feed-forward after its norm, added. Returns ``(x', routed
    experts read)`` — :func:`glm_moe.moe_ffn`'s count, zero in a dense
    layer."""
    x = x + y
    h = llama.rms_norm(x, layer["ln2"], cfg.norm_eps)
    if moe:
        f, n = glm_moe.moe_ffn(cfg, h, layer, live)
    else:
        f = glm_moe._swiglu(h, layer["w_gate"], layer["w_up"],
                            layer["w_down"], cfg.dtype)
        n = jnp.zeros((), jnp.int32)
    return x + f.astype(cfg.dtype), n


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

def walk_layers(cfg: Lfm2MoeConfig, params: Params, carry,
                conv_fn: Callable, full_fn: Callable):
    """The stack in the order ``layer_types`` gives: ``conv_fn(carry,
    layer, ci, moe) -> (carry, ys)`` for a conv layer, ``full_fn(carry,
    layer, fi, moe) -> (carry, ys)`` for an attention layer — ``ci`` /
    ``fi`` the layer's index among the layers of ITS kind (a tail
    cache's or a K/V pool's layer axis), ``moe`` whether its FFN is the
    expert layer; all three static. Returns ``(carry, the conv layers'
    ys stacked in stack order, the attention layers')``; a kind without
    layers, or whose function returns ``None``, gives ``None``."""
    ys: Dict[str, list] = {CONV: [], FULL: []}
    for i, (kind, layer) in enumerate(zip(cfg.layer_types,
                                          params["layers"])):
        fn = conv_fn if kind == CONV else full_fn
        carry, y = fn(carry, layer, len(ys[kind]), i >= cfg.n_dense_layers)
        ys[kind].append(y)

    def stack(items):
        if not items or items[0] is None:
            return None
        return jax.tree.map(lambda *a: jnp.stack(a), *items)

    return carry, stack(ys[CONV]), stack(ys[FULL])


def forward_hidden(params: Params, tokens: jax.Array, cfg: Lfm2MoeConfig,
                   true_lens=None):
    """Token ids [B, S] -> (hidden [B, S, D] before the final norm, what
    a cache would keep: ``{"k", "v": [L_full, B, S, n_kv_heads, hd],
    "conv": [L_conv, B, K - 1, D]}`` — each row's tail after ITS last
    real token, ``true_lens`` [B]; absent: every token is real). Plain
    masked attention over the whole sequence: what a prefill wave (at
    most a chunk long) and the tests run."""
    B, S = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((B,), S, jnp.int32)
    with jax.named_scope("embed"):
        x = embed(cfg, params, tokens)
    rope = rope_tables(cfg, jnp.arange(S))
    mask = afmoe.sequence_mask(cfg, S, False)
    zero_tail = jnp.zeros((B, cfg.conv_kernel - 1, cfg.d_model), cfg.dtype)

    def conv_fn(x, layer, ci, moe):
        y, tail = short_conv(cfg, layer, x, zero_tail, true_lens)
        return out_ffn(cfg, layer, x, y, moe)[0], tail

    def full_fn(x, layer, fi, moe):
        q, k, v = attn_project(cfg, layer, x, rope)
        with jax.named_scope("attn_core"):
            o = attend(cfg, q, k, v, mask)
        return out_ffn(cfg, layer, x, attn_output(cfg, layer, o),
                       moe)[0], (k, v)

    x, tails, kv = walk_layers(cfg, params, x, conv_fn, full_fn)
    k, v = kv if kv is not None else (None, None)
    return x, {"k": k, "v": v, "conv": tails}


def forward(params: Params, tokens: jax.Array, cfg: Lfm2MoeConfig
            ) -> jax.Array:
    """Token ids [B, S] -> logits [B, S, vocab] float32."""
    x, _ = forward_hidden(params, tokens, cfg)
    return head_logits(cfg, params, x)
