"""Decoder whose attention layers are SLIDING-WINDOW ones with a global
(full causal) layer every few, a sigmoid output gate on the attention,
norms before AND after each sub-layer, and shared + routed experts
after the leading dense layers (``afmoe``: Trinity-Mini), pure
functional JAX.

The block (``x`` the residual stream; four RMSNorms a layer; untied
embedding and head; the embedding scaled by ``sqrt(d_model)``)::

    x = x + N2(Attn(N1(x)));   x = x + N4(FFN(N3(x)))

* **Attention, every layer.** ``q, k, v, g = W_q u, W_k u, W_v u, W_g u``
  (no bias; ``n_heads`` query heads over ``n_kv_heads`` key/value heads
  of ``head_dim``; the gate as wide as ``q``); ``q`` and ``k`` pass an
  RMSNorm over each head's ``head_dim`` values. A
  ``sliding_attention`` layer rotates ``q`` and ``k`` (rotate-half) and
  query ``i`` sees keys ``i - window < j <= i``; a ``full_attention``
  layer applies NO rotation and query ``i`` sees every ``j <= i``.
  Float32 softmax over ``q . k / sqrt(head_dim)``; output ``W_o(o *
  sigmoid(g))``.
* **FFN.** The first ``n_dense_layers`` layers: a SwiGLU. Every later
  layer: ``models/glm_moe.py``'s expert layer as it stands
  (:func:`glm_moe.moe_ffn`: float32 sigmoid router, a selection-only
  bias, top-k weights normalised and scaled, dropless, one shared
  expert) — this config object answers to the names that layer reads.

The stack is run BY KIND from the published ``layer_types``
(:func:`plan`, :func:`scan_layers`): the leading dense layers one by
one, then whole periods of the pattern as ONE ``lax.scan`` whose body
unrolls a period, then what is left of a period one by one. Parameters
follow the plan: ``lead`` and ``tail`` lists of per-layer trees,
``period`` a list with one entry a place in the period, each stacked
``[periods, ...]`` (``models/olmo_hybrid.py`` says why each stack is a
tensor of its own).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.models import glm_moe, llama

Params = Dict[str, Any]

# The serve programs of this family (``infer.kvcache.programs_for``).
SERVE_PROGRAMS = "skypilot_tpu.infer.windowed"

WINDOW, FULL = "sliding_attention", "full_attention"


def _types(n_layers: int, every: int) -> Tuple[str, ...]:
    return tuple(FULL if (i + 1) % every == 0 else WINDOW
                 for i in range(n_layers))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Hyperparameters under the names the repo's other models use
    (and the five :mod:`glm_moe`'s expert layer reads);
    :func:`from_published` maps a ``config.json``'s own key names."""

    vocab_size: int = 200_192
    d_model: int = 2048
    n_layers: int = 32
    layer_types: Tuple[str, ...] = _types(32, 4)
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    window: int = 2048               # rows a sliding layer sees and keeps
    n_dense_layers: int = 2
    d_ff: int = 6144                 # the dense layers' SwiGLU width
    moe_d_ff: int = 1024             # each routed / shared expert's width
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    experts_per_tok: int = 8
    routed_scaling_factor: float = 2.826
    norm_topk_prob: bool = True
    mup_enabled: bool = True         # the embedding times sqrt(d_model)
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131_072
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16        # activation / compute dtype
    param_dtype: Any = jnp.float32   # storage dtype for parameters

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError("layer_types must name every layer")
        plan(self)                   # refuses a pattern that is no period

    @property
    def win_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == WINDOW)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    @property
    def n_win_layers(self) -> int:
        return len(self.win_layers)

    @property
    def n_full_layers(self) -> int:
        return len(self.full_layers)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kv_width(self) -> int:
        """Values of one token's K (or V) row in one layer."""
        return self.n_kv_heads * self.head_dim

    def attn_params(self) -> int:
        d, q = self.d_model, self.n_heads * self.head_dim
        return 3 * d * q + 2 * d * self.kv_width + 2 * self.head_dim

    def expert_params(self) -> int:
        """One layer's routed experts."""
        return self.n_routed_experts * 3 * self.d_model * self.moe_d_ff

    def num_params(self) -> int:
        d = self.d_model
        dense = self.attn_params() + 4 * d + 3 * d * self.d_ff
        moe = (self.attn_params() + 4 * d + d * self.n_routed_experts
               + self.n_routed_experts + self.expert_params()
               + self.n_shared_experts * 3 * d * self.moe_d_ff)
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return (self.n_dense_layers * dense + self.n_moe_layers * moe
                + emb + d)

    def active_params(self) -> int:
        """Parameters one token multiplies with (its chosen experts
        only, no embedding row lookup)."""
        idle = (self.n_routed_experts - self.experts_per_tok) \
            * 3 * self.d_model * self.moe_d_ff
        return self.num_params() - self.n_moe_layers * idle \
            - self.vocab_size * self.d_model


def plan(cfg: AfmoeConfig) -> Tuple[Tuple[int, ...], int, int,
                                    Tuple[int, ...]]:
    """How the stack is run: ``(lead, period, n_periods, tail)`` — the
    leading dense layers' indices (unrolled), the length of the pattern's
    period, how many WHOLE periods follow them (scanned), and the indices
    of the layers left over (unrolled). The pattern from the first expert
    layer on must repeat with that period."""
    types = cfg.layer_types
    bad = [t for t in types if t not in (WINDOW, FULL)]
    if bad:
        raise ValueError(f"unknown layer type {bad[0]!r}")
    n, lead = cfg.n_layers, min(cfg.n_dense_layers, cfg.n_layers)
    period = types.index(FULL) + 1 if FULL in types else 1
    if any(types[i] != types[i + period]
           for i in range(lead, n - period)):
        raise ValueError(
            f"layer_types must repeat with period {period} after the "
            f"leading dense layers; got {types}")
    n_periods = (n - lead) // period
    return (tuple(range(lead)), period, n_periods,
            tuple(range(lead + n_periods * period, n)))


def from_published(config: Dict[str, Any], **overrides) -> AfmoeConfig:
    """An ``afmoe`` ``config.json`` (its own key names) as an
    :class:`AfmoeConfig`; the first ``num_hidden_layers`` entries of its
    ``layer_types`` run."""
    for key in ("n_group", "topk_group", "num_expert_groups",
                "num_limited_groups"):
        if int(config.get(key, 1)) != 1:
            raise ValueError(f"group-limited routing ({key} > 1) is not built")
    if config.get("rope_scaling"):
        raise ValueError("rope_scaling is not built")
    if config.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("only the sigmoid router is built")
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
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)))
    fields.update(overrides)
    return AfmoeConfig(**fields)


CONFIGS: Dict[str, AfmoeConfig] = {
    # The published model (26.12 B parameters: 52 GB in bf16, more than
    # any single host here holds).
    "trinity-mini": AfmoeConfig(),
    # Every mechanism at a size the CPU tests run: a leading dense
    # layer, then (window, full, window, window), (window, full, ...)
    # — two whole periods of three and one layer left over —, a window
    # of 32 rows, 8 experts top-2 with one shared.
    "afmoe-tiny": AfmoeConfig(
        vocab_size=512, d_model=64, n_layers=8, layer_types=_types(8, 3),
        n_heads=4, n_kv_heads=2, head_dim=16, window=32, n_dense_layers=1,
        d_ff=128, moe_d_ff=32, n_routed_experts=8, experts_per_tok=2,
        max_seq_len=512),
}


# ---------------------------------------------------------------------------
# Parameter init + logical sharding axes
# ---------------------------------------------------------------------------

def layer_shapes(cfg: AfmoeConfig, moe: bool
                 ) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """``{name: (shape, fan_in)}`` of one layer's tensors (fan_in 0
    marks a norm scale, -1 the router's selection bias)."""
    d, nh, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = {"ln1": ((d,), 0), "ln2": ((d,), 0), "ln3": ((d,), 0),
           "ln4": ((d,), 0), "q_norm": ((hd,), 0), "k_norm": ((hd,), 0),
           "wq": ((d, nh, hd), d), "wk": ((d, g, hd), d),
           "wv": ((d, g, hd), d), "wg": ((d, nh, hd), d),
           "wo": ((nh, hd, d), nh * hd)}
    if not moe:
        ff = cfg.d_ff
        return dict(out, w_gate=((d, ff), d), w_up=((d, ff), d),
                    w_down=((ff, d), ff))
    e, f = cfg.n_routed_experts, cfg.moe_d_ff
    fs = cfg.n_shared_experts * f
    return dict(out, router=((d, e), d), router_bias=((e,), -1),
                we_gate=((e, d, f), d), we_up=((e, d, f), d),
                we_down=((e, f, d), f), ws_gate=((d, fs), d),
                ws_up=((d, fs), d), ws_down=((fs, d), fs))


def init_params(rng: jax.Array, cfg: AfmoeConfig) -> Params:
    """Random parameters in :func:`plan`'s layout. The selection bias is
    a trained buffer in a checkpoint; here it is drawn at a scale that
    changes some choices."""
    d, v = cfg.d_model, cfg.vocab_size
    lead, period, n_periods, tail = plan(cfg)
    keys = iter(jax.random.split(rng, 16 * (cfg.n_layers + 2)))

    def draw(shape, fan_in):
        if fan_in == 0:
            return jnp.ones(shape, cfg.param_dtype)
        std = 0.05 if fan_in < 0 else fan_in ** -0.5
        return jax.random.normal(next(keys), shape, cfg.param_dtype) * std

    def layer(i, stack=()):
        return {name: draw(stack + shape, fan_in) for name, (shape, fan_in)
                in layer_shapes(cfg, i >= cfg.n_dense_layers).items()}

    params: Params = {
        "embed": jax.random.normal(next(keys), (v, d),
                                   cfg.param_dtype) * 0.02,
        "final_norm": jnp.ones((d,), cfg.param_dtype),
        "lead": [layer(i) for i in lead],
        "period": [layer(len(lead) + j, (n_periods,))
                   for j in range(period)] if n_periods else [],
        "tail": [layer(i) for i in tail]}
    if not cfg.tie_embeddings:
        params["lm_head"] = draw((d, v), d)
    return params


def param_logical_axes(cfg: AfmoeConfig) -> Params:
    """Logical axis names per parameter (``parallel.sharding`` rules)."""
    per_layer = {
        "ln1": ("embed",), "ln2": ("embed",), "ln3": ("embed",),
        "ln4": ("embed",), "q_norm": (None,), "k_norm": (None,),
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wg": ("embed", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
        "w_down": ("mlp", "embed"), "router": ("embed", None),
        "router_bias": (None,), "we_gate": ("expert", "embed", "mlp"),
        "we_up": ("expert", "embed", "mlp"),
        "we_down": ("expert", "mlp", "embed"), "ws_gate": ("embed", "mlp"),
        "ws_up": ("embed", "mlp"), "ws_down": ("mlp", "embed")}
    lead, period, n_periods, tail = plan(cfg)

    def layer(i, stack=()):
        return {name: stack + per_layer[name]
                for name in layer_shapes(cfg, i >= cfg.n_dense_layers)}

    axes: Params = {
        "embed": ("vocab", "embed"), "final_norm": ("embed",),
        "lead": [layer(i) for i in lead],
        "period": [layer(len(lead) + j, ("layer",))
                   for j in range(period)] if n_periods else [],
        "tail": [layer(i) for i in tail]}
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# The layer, in the pieces the serve programs share
# ---------------------------------------------------------------------------

def rope_tables(cfg: AfmoeConfig, positions: jax.Array):
    """cos/sin for ``positions`` (the window layers' rotation)."""
    hd = cfg.head_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def embed(cfg: AfmoeConfig, params: Params, tokens: jax.Array) -> jax.Array:
    """Token ids [...] -> rows [..., D], scaled by ``sqrt(d_model)``
    where the config says so."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.mup_enabled:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    return x


@jax.named_scope("qkv_proj")
def project(cfg: AfmoeConfig, layer: Params, x: jax.Array, rope):
    """``x`` [B, T, D] -> ``q`` [B, T, n_heads, hd], ``k``, ``v`` [B, T,
    n_kv_heads, hd] and the output gate's input [B, T, n_heads, hd]:
    the first norm, the projections, RMSNorm over each head of ``q``
    and ``k``, and the rotation where ``rope`` (cos, sin) is given — a
    window layer; ``None`` in a global one."""
    dt = cfg.dtype
    u = llama.rms_norm(x, layer["ln1"], cfg.norm_eps)
    q = jnp.einsum("btd,dhk->bthk", u, layer["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", u, layer["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", u, layer["wv"].astype(dt))
    gate = jnp.einsum("btd,dhk->bthk", u, layer["wg"].astype(dt))
    q = llama.rms_norm(q, layer["q_norm"], cfg.norm_eps)
    k = llama.rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if rope is not None:
        q = llama.apply_rope(q, *rope)
        k = llama.apply_rope(k, *rope)
    return q, k, v, gate


@jax.named_scope("out_ffn")
def out_ffn(cfg: AfmoeConfig, layer: Params, x: jax.Array, o: jax.Array,
            gate: jax.Array, moe: bool, live=None):
    """The back half of a layer: the attention result ``o`` [B, T,
    n_heads, hd] gated, projected, normed and added; then the
    feed-forward between its two norms, added. Returns ``(x', routed
    experts read)`` — :func:`glm_moe.moe_ffn`'s count, zero in a dense
    layer."""
    dt = cfg.dtype
    o = o.astype(dt) * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
    a = jnp.einsum("bthk,hkd->btd", o, layer["wo"].astype(dt))
    x = x + llama.rms_norm(a, layer["ln2"], cfg.norm_eps)
    h = llama.rms_norm(x, layer["ln3"], cfg.norm_eps)
    if moe:
        y, n = glm_moe.moe_ffn(cfg, h, layer, live)
    else:
        y = glm_moe._swiglu(h, layer["w_gate"], layer["w_up"],
                            layer["w_down"], dt)
        n = jnp.zeros((), jnp.int32)
    return x + llama.rms_norm(y.astype(dt), layer["ln4"], cfg.norm_eps), n


@jax.named_scope("lm_head")
def head_logits(cfg: AfmoeConfig, params: Params, x: jax.Array) -> jax.Array:
    """Final norm + head over rows x [..., D] -> float32 logits."""
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...d,dv->...v", x,
                      head.astype(cfg.dtype)).astype(jnp.float32)


def attend(cfg: AfmoeConfig, q: jax.Array, k: jax.Array, v: jax.Array,
           mask: jax.Array) -> jax.Array:
    """Plain masked attention: ``q`` [B, Q, n_heads, hd] over ``k``, ``v``
    [B, M, n_kv_heads, hd] under ``mask`` [B|1, Q, M] -> [B, Q, n_heads,
    hd] float32. Operands in the compute dtype, float32 scores, softmax
    and accumulation."""
    B, Q, nh, hd = q.shape
    G, dt = cfg.n_kv_heads, cfg.dtype
    qh = q.reshape(B, Q, G, nh // G, hd).astype(dt)
    s = jnp.einsum("bqgrk,bmgk->bqgrm", qh, k.astype(dt),
                   preferred_element_type=jnp.float32) * hd ** -0.5
    s = jnp.where(mask[:, :, None, None, :], s,
                  jnp.asarray(-1e30, jnp.float32))
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqgrm,bmgk->bqgrk", w.astype(dt), v.astype(dt),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Q, nh, hd)


def sequence_mask(cfg: AfmoeConfig, n: int, window: bool) -> jax.Array:
    """[1, n, n] bool: what query ``i`` of a whole sequence sees —
    ``j <= i``, and in a window layer ``i - window < j``."""
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    mask = j <= i
    if window:
        mask = mask & (i - j < cfg.window)
    return mask[None]


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

def _whole_experts(stacked: Params):
    """A period place's stacked tensors split for the scan: the routed
    experts WHOLE, seen ``[periods * E, ...]`` (an invariant the loop
    indexes by ``expert_base``: ``glm_moe.experts_grouped``'s kernel —
    ``ops.grouped_ffn.grouped_swiglu`` on a TPU — reads this layer's E
    experts where they lie in it; a layer's slice handed to a kernel
    would first be copied), the rest sliced a period a turn."""
    whole = {name: w.reshape((-1,) + w.shape[2:])
             for name, w in stacked.items()
             if name in glm_moe.EXPERT_TENSORS}
    return whole, {n: w for n, w in stacked.items() if n not in whole}


def scan_layers(cfg: AfmoeConfig, params: Params, carry,
                layer_fn: Callable):
    """``layer_fn(carry, layer, i, ci, window, moe) -> (carry, ys)``
    over the stack as :func:`plan` lays it out. ``i`` is the layer's
    index in the whole stack and ``ci`` its index among the layers of
    ITS kind (a window cache's or a global cache's layer axis) — Python
    ints in an unrolled layer, traced in a scanned one; ``window`` and
    ``moe`` are static. Returns ``(carry, ys stacked over all layers,
    in stack order)``."""
    lead, period, n_periods, tail = plan(cfg)
    types = cfg.layer_types
    win_before = [sum(t == WINDOW for t in types[:i])
                  for i in range(cfg.n_layers + 1)]

    def kind_index(i):
        return win_before[i] if types[i] == WINDOW else i - win_before[i]

    def one(carry, layer, i):
        return layer_fn(carry, layer, i, kind_index(i), types[i] == WINDOW,
                        i >= cfg.n_dense_layers)

    outs: List[Any] = []
    for i, layer in zip(lead, params["lead"]):
        carry, ys = one(carry, layer, i)
        outs.append(jax.tree.map(lambda a: a[None], ys))
    if n_periods:
        first = len(lead)
        split = [_whole_experts(p) for p in params["period"]]
        places = range(period)
        win_pp = sum(types[first + j] == WINDOW for j in places)

        def body(c, xs):
            sliced, p = xs
            ys_all = []
            for j in places:
                i0 = first + j
                is_win = types[i0] == WINDOW
                layer = dict(sliced[j], **split[j][0])
                if split[j][0]:
                    layer["expert_base"] = p * cfg.n_routed_experts
                step = win_pp if is_win else period - win_pp
                c, ys = layer_fn(c, layer, i0 + p * period,
                                 kind_index(i0) + p * step, is_win,
                                 i0 >= cfg.n_dense_layers)
                ys_all.append(ys)
            return c, jax.tree.map(lambda *a: jnp.stack(a), *ys_all)

        carry, ys = lax.scan(
            body, carry, ([s[1] for s in split],
                          jnp.arange(n_periods, dtype=jnp.int32)))
        # [periods, places, ...] -> [periods * places, ...]: stack order.
        outs.append(jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), ys))
    for i, layer in zip(tail, params["tail"]):
        carry, ys = one(carry, layer, i)
        outs.append(jax.tree.map(lambda a: a[None], ys))
    if len(outs) == 1:
        return carry, outs[0]
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs)


def forward_hidden(params: Params, tokens: jax.Array, cfg: AfmoeConfig):
    """Token ids [B, S] -> (hidden [B, S, D] before the final norm, every
    layer's ``k`` and ``v`` rows [L, B, S, n_kv_heads, hd], in stack
    order). Plain masked attention over the whole sequence: what a
    prefill wave (at most a chunk long) and the tests run."""
    S = tokens.shape[1]
    with jax.named_scope("embed"):
        x = embed(cfg, params, tokens)
    rope = rope_tables(cfg, jnp.arange(S))
    masks = {w: sequence_mask(cfg, S, w) for w in (False, True)}

    def layer_fn(x, layer, i, ci, window, moe):
        q, k, v, gate = project(cfg, layer, x, rope if window else None)
        with jax.named_scope("window_attn" if window else "attn_core"):
            o = attend(cfg, q, k, v, masks[window])
        return out_ffn(cfg, layer, x, o, gate, moe)[0], (k, v)

    x, (k, v) = scan_layers(cfg, params, x, layer_fn)
    return x, {"k": k, "v": v}


def forward(params: Params, tokens: jax.Array, cfg: AfmoeConfig
            ) -> jax.Array:
    """Token ids [B, S] -> logits [B, S, vocab] float32."""
    x, _ = forward_hidden(params, tokens, cfg)
    return head_logits(cfg, params, x)
