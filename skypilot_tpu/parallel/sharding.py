"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Model code annotates tensors with *logical* axis names ("embed", "heads",
"batch", ...). A rule table maps each logical name to a mesh axis (or
None = replicated). Swapping the table reconfigures the whole model
between FSDP / TP / DP / hybrid without touching model code.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = Union[str, Tuple[str, ...], None]
Rules = Dict[str, MeshAxis]

# Default hybrid FSDP x TP rules:
#  - params' "embed" dim sharded over fsdp (ZeRO-3 style),
#  - heads / mlp / vocab dims over tp (Megatron style),
#  - activations' batch dim over (dp, fsdp) jointly.
DEFAULT_RULES: Rules = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "vocab": "tp",
    "heads": "tp",
    "kv_heads": "tp",
    "mlp": "tp",
    "head_dim": None,
    "layer": None,
    # MoE: the expert dim shards over ep; XLA turns the dispatch/combine
    # einsums into all-to-alls over the ep axis. Capacity stays local.
    "expert": "ep",
    "capacity": None,
    # Pipeline: the stage dim of stage-stacked weights / activation
    # buffers shards over pp; the tick shift compiles to collective
    # permutes between neighbor stages.
    "stage": "pp",
    "micro": None,
}

# Activation-side overrides: activations' "embed" stays unsharded (it is
# the contracting dim of every matmul); sharding it would force XLA into
# all-to-alls mid-layer.
ACT_RULES: Rules = dict(DEFAULT_RULES, embed=None, vocab="tp")


def _axis_size(mesh: Mesh, axis: MeshAxis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def spec_for(logical_axes: Sequence[Optional[str]], rules: Rules,
             mesh: Optional[Mesh] = None,
             shape: Optional[Sequence[int]] = None) -> P:
    """Resolve logical axis names to a PartitionSpec via the rule table.

    When ``mesh`` and ``shape`` are given, a dim that the mapped mesh axis
    does not divide evenly is left replicated instead of erroring — so the
    same rules work for any slice topology (a 3-way fsdp axis simply won't
    shard a 128-wide dim).
    """
    resolved = []
    for i, a in enumerate(logical_axes):
        axis = rules.get(a) if a is not None else None
        if (axis is not None and mesh is not None and shape is not None
                and shape[i] % _axis_size(mesh, axis) != 0):
            axis = None
        resolved.append(axis)
    return P(*resolved)


def logical_to_sharding(logical_tree, mesh: Mesh, rules: Rules = DEFAULT_RULES,
                        shapes=None):
    """Map a pytree of logical-axis tuples to NamedShardings.

    ``shapes``: optional matching pytree of array shapes (or objects with
    ``.shape``) enabling the divisibility guard in ``spec_for``.
    """
    is_leaf = lambda x: isinstance(x, tuple)
    if shapes is None:
        return jax.tree.map(
            lambda axes: NamedSharding(mesh, spec_for(axes, rules, mesh)),
            logical_tree, is_leaf=is_leaf)
    return jax.tree.map(
        lambda axes, s: NamedSharding(
            mesh, spec_for(axes, rules, mesh, getattr(s, "shape", s))),
        logical_tree, shapes, is_leaf=is_leaf)


def subset_shardings(tree, logical_tree, mesh: Mesh, rules: Rules):
    """NamedShardings for every leaf of ``tree`` (arrays or
    ShapeDtypeStructs) per its axes in ``logical_tree``, walking by
    DICT KEY so ``tree`` may be a subset of the axes tree (e.g. w8a8
    serving's slimmed params: embed + norms only — a plain tree.map
    would fail on the structure mismatch). Leaves without an axes
    entry are replicated."""
    if isinstance(tree, dict):
        sub = logical_tree if isinstance(logical_tree, dict) else {}
        return {k: subset_shardings(v, sub.get(k), mesh, rules)
                for k, v in tree.items()}
    if not isinstance(logical_tree, tuple):
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, spec_for(logical_tree, rules, mesh,
                                        tree.shape))


def shard_tree_subset(tree, logical_tree, mesh: Mesh, rules: Rules):
    """device_put ``tree`` per :func:`subset_shardings`."""
    return jax.device_put(
        tree, subset_shardings(tree, logical_tree, mesh, rules))


def init_sharded(build, axes_of, mesh: Mesh, rules: Rules):
    """Run the zero-argument ``build`` under jit with out_shardings:
    every device materializes only its own shards, so a tree bigger
    than one chip's HBM (8B weights in bf16, a 32-slot KV cache) is
    never built whole on device 0 and resharded afterwards.
    ``axes_of(abstract_tree)`` returns the logical axes (by dict key,
    as in :func:`subset_shardings`)."""
    abstract = jax.eval_shape(build)
    return jax.jit(build, out_shardings=subset_shardings(
        abstract, axes_of(abstract), mesh, rules))()


# Inference TP rules: Megatron-style heads/mlp/vocab over tp; no data/
# fsdp axes (serving replicates activations' batch). The "embed"
# logical axis has no rule, so NORMS replicate — but the embedding
# table and LM head shard their "vocab" dim (the token gather and the
# logits matmul run vocab-split, with XLA inserting the collectives).
INFER_TP_RULES: Rules = {"heads": "tp", "kv_heads": "tp",
                         "mlp": "tp", "vocab": "tp"}


def make_constrain(mesh: Optional[Mesh], rules: Rules = ACT_RULES):
    """Return fn(x, logical_axes) applying with_sharding_constraint.

    With mesh=None returns identity (single-device path compiles to the
    same HLO with zero overhead).
    """
    if mesh is None:
        return lambda x, axes: x

    def constrain(x, axes):
        if len(axes) != x.ndim:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec_for(axes, rules, mesh, x.shape)))

    return constrain
