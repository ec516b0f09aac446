"""retrace-safety: trace-incompatible Python inside jit-reachable code.

The serving invariant since PR 5 is "one compiled program per bucket,
no retracing": every device program is traced once per static shape
and replayed forever. Code that runs *under trace* must therefore
never concretize a traced value (``int(x)``, ``.item()``), branch on
one in Python (``if (x > 0).any():``), pull it to the host
(``np.asarray``, ``.block_until_ready``), or build an array whose
shape depends on one — each of those either throws at trace time or,
worse, silently bakes a value in and recompiles per request.

Detection is reachability-based: roots are functions jitted in
``infer/`` and ``train/`` (``@jax.jit`` / ``functools.partial(jax.jit,
...)`` decorators, ``jax.jit(f)`` / ``shard_map(f)`` call sites,
jitted lambdas), and the call graph is followed through module aliases
into ``models/``, ``ops/`` and ``parallel/``. Python branches on
*static* values (config attrs, ``static_argnames``, ``is None``
checks) are trace-time constants and are not flagged; the branch rule
only fires on tests that contain array-API calls, which are traced by
construction.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from skypilot_tpu.analysis.checkers import _util
from skypilot_tpu.analysis.core import Checker, FileContext, register
from skypilot_tpu.analysis.findings import Finding

_ROOT_DIRS = ("skypilot_tpu/infer/", "skypilot_tpu/train/")

# The serve programs of a model family are reached through a HANDLE,
# not a module alias: ``progs = kvcache.programs_for(cfg)`` and then
# ``progs.prefill_batch(...)`` inside the engine's jitted entry points.
# A call on such a handle may land in any of these modules (and, so
# that a fixture can stand alone, in the calling module itself): the
# call graph follows it into all of them.
_FAMILY_SELECTOR = "programs_for"
_FAMILY_MODULES = ("skypilot_tpu.infer.kvcache",
                   "skypilot_tpu.infer.latent",
                   "skypilot_tpu.infer.hybrid")

# jnp constructors whose first argument is a shape.
_SHAPE_CTORS = {"zeros", "ones", "full", "empty", "eye"}
_RANGE_CTORS = {"arange", "linspace"}
_ARRAY_MODULES = {"jnp", "lax", "jax"}
_TRACED_METHODS = {"any", "all", "item", "sum", "min", "max", "mean"}


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``pjit`` / ``shard_map`` (optionally dotted)."""
    name = _util.dotted(node)
    if name is None:
        return False
    leaf = name.split(".")[-1]
    return leaf in {"jit", "pjit", "shard_map"}


def _jit_wrapper_target(call: ast.Call) -> Optional[ast.AST]:
    """For ``jax.jit(f, ...)`` / ``shard_map(f, ...)``: the wrapped
    function expression (Name or Lambda); for ``functools.partial(
    jax.jit, ...)`` there is no target (it's used as a decorator)."""
    if _is_jit_expr(call.func) and call.args:
        return call.args[0]
    return None


def _has_jit_decorator(func: ast.AST) -> bool:
    for dec in getattr(func, "decorator_list", []):
        if _is_jit_expr(dec):
            return True
        if isinstance(dec, ast.Call):
            if _is_jit_expr(dec.func):
                return True
            # functools.partial(jax.jit, ...)
            name = _util.dotted(dec.func) or ""
            if name.split(".")[-1] == "partial" and dec.args \
                    and _is_jit_expr(dec.args[0]):
                return True
    return False


def _family_handles(tree: ast.AST) -> Set[str]:
    """Names bound to ``<module>.programs_for(...)`` anywhere in a
    file (``self._progs = progs = kvcache.programs_for(cfg)``)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and (_util.dotted(node.value.func) or "").split(
                    ".")[-1] == _FAMILY_SELECTOR:
            out.update(t.id for t in node.targets
                       if isinstance(t, ast.Name))
    return out


def _module_key(rel: str) -> str:
    return rel[:-3].replace("/", ".")


class _FuncInfo:
    def __init__(self, ctx: FileContext, qual: str, node: ast.AST):
        self.ctx = ctx
        self.qual = qual
        self.node = node


@register
class RetraceSafetyChecker(Checker):
    name = "retrace-safety"
    description = ("Python that breaks tracing (concretization, "
                   "host transfer, traced branches, dynamic shapes) "
                   "reachable from jax.jit/shard_map entry points")
    scope = "project"
    # v2: span-parameterized attention programs (static span args)
    # joined the guarded surface — golden fixtures cover the
    # span-gather shape; the bump invalidates warm caches so the new
    # fixtures and the edited kvcache/engine hot path rescan cold.
    # v3: Pallas kernel bodies joined the guarded surface — the BFS
    # follows ``functools.partial(kernel_fn, ...)`` targets (the
    # pallas_call idiom wraps the kernel in a partial, which hid its
    # body from reachability), covering ops/paged_attention.py's
    # kernel + wrapper and the kvcache dispatch seam; the bump
    # rescans the edited hot path and the new fixtures cold.
    # v4: multi-LoRA adapter gathers (PR 13) — the per-slot (A, B)
    # delta helpers and the adapter-pool install program joined the
    # jit-reachable surface (kvcache lora plumbing + engine
    # _adapter_install); the bump rescans the edited programs and the
    # new adapter fixtures cold.
    # v5: draft-model speculation (PR 14) — infer/draft.py's jitted
    # rollout/ingest/sync programs are new roots in the infer/ root
    # dir and kvcache.sync_slots joined the reachable surface; the
    # bump rescans the edited spec programs and the new draft
    # fixtures cold.
    # v6: a second family of serve programs (infer/latent.py: the MLA
    # latent cache and the dropless expert layer of models/glm_moe.py)
    # is reached through ``kvcache.programs_for(cfg)``; calls on that
    # handle are followed into every family module, which keeps
    # kvcache's own programs reachable from the engine's roots too.
    # v7: a third family (infer/hybrid.py: K/V rows beside a per-slot
    # recurrent state, models/olmo_hybrid.py) joins the family modules.
    version = 7

    def check_project(self, ctxs: Sequence[FileContext],
                      root: str) -> List[Finding]:
        # Symbol table: dotted module -> {func name -> _FuncInfo}.
        by_module: Dict[str, Dict[str, _FuncInfo]] = {}
        aliases: Dict[str, Dict[str, str]] = {}
        handles: Dict[str, Set[str]] = {}
        for ctx in ctxs:
            mod = _module_key(ctx.rel)
            funcs: Dict[str, _FuncInfo] = {}
            for qual, _cls, node in ctx.functions:
                # Module-level name wins over same-named nested defs.
                leaf = qual.split(".")[-1]
                if leaf not in funcs or "." not in qual:
                    funcs[leaf] = _FuncInfo(ctx, qual, node)
            by_module[mod] = funcs
            aliases[ctx.rel] = ctx.import_aliases
            handles[ctx.rel] = _family_handles(ctx.tree)

        # Roots: jitted functions/lambdas in infer/ and train/.
        roots: List[Tuple[FileContext, str, ast.AST]] = []
        for ctx in ctxs:
            if not ctx.rel.startswith(_ROOT_DIRS):
                continue
            for qual, _cls, node in ctx.functions:
                if _has_jit_decorator(node):
                    roots.append((ctx, qual, node))
            for node in ctx.nodes:
                if not isinstance(node, ast.Call):
                    continue
                target = _jit_wrapper_target(node)
                if target is None:
                    continue
                if isinstance(target, ast.Lambda):
                    roots.append((ctx, f"<lambda@L{target.lineno}>",
                                  target))
                elif isinstance(target, ast.Name):
                    info = by_module.get(
                        _module_key(ctx.rel), {}).get(target.id)
                    if info is not None:
                        roots.append((info.ctx, info.qual, info.node))

        # BFS the call graph through module aliases.
        seen: Set[int] = set()
        queue: List[Tuple[FileContext, str, ast.AST]] = []
        for ctx, qual, node in roots:
            if id(node) not in seen:
                seen.add(id(node))
                queue.append((ctx, qual, node))
        reached: List[Tuple[FileContext, str, ast.AST]] = []
        while queue:
            ctx, qual, node = queue.pop()
            reached.append((ctx, qual, node))
            mod = _module_key(ctx.rel)
            file_aliases = aliases.get(ctx.rel, {})
            for sub in _util.body_walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                target = sub.func
                # ``functools.partial(f, ...)``: the partial runs
                # f's body wherever the partial is called (the
                # pallas_call kernel idiom) — follow f itself.
                name = _util.dotted(sub.func) or ""
                if name.split(".")[-1] == "partial" and sub.args \
                        and not _is_jit_expr(sub.args[0]):
                    target = sub.args[0]
                found = [self._resolve(target, mod, file_aliases,
                                       by_module)]
                if isinstance(target, ast.Attribute) \
                        and isinstance(target.value, ast.Name) \
                        and target.value.id in handles.get(ctx.rel, ()):
                    found += [by_module.get(m, {}).get(target.attr)
                              for m in _FAMILY_MODULES + (mod,)]
                for info in found:
                    if info is not None and id(info.node) not in seen:
                        seen.add(id(info.node))
                        queue.append((info.ctx, info.qual, info.node))

        findings: List[Finding] = []
        for ctx, qual, node in reached:
            findings.extend(self._check_traced(ctx, qual, node))
        return findings

    def _resolve(self, func: ast.AST, mod: str,
                 file_aliases: Dict[str, str],
                 by_module: Dict[str, Dict[str, _FuncInfo]]
                 ) -> Optional[_FuncInfo]:
        if isinstance(func, ast.Name):
            local = by_module.get(mod, {}).get(func.id)
            if local is not None:
                return local
            # `from skypilot_tpu.infer.kvcache import prefill`: the
            # alias maps to a module *member*; resolve via its parent.
            dotted = file_aliases.get(func.id)
            if dotted and "." in dotted:
                parent, leaf = dotted.rsplit(".", 1)
                return by_module.get(parent, {}).get(leaf)
            return None
        if isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name):
            target_mod = file_aliases.get(func.value.id)
            if target_mod is None:
                return None
            funcs = by_module.get(target_mod)
            if funcs is None:
                return None
            return funcs.get(func.attr)
        return None

    # -- rules inside traced code -----------------------------------------

    def _check_traced(self, ctx: FileContext, qual: str,
                      func: ast.AST) -> List[Finding]:
        out: List[Finding] = []
        # One-step local dataflow: name -> the expression last assigned
        # to it. `cap = math.ceil(...)` then `int(cap)` is a static
        # cast; without this every helper computing host math from
        # config would false-positive.
        assigns: Dict[str, ast.AST] = {}
        for node in _util.body_walk(func):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                assigns[node.targets[0].id] = node.value

        def finding(node, rule, message, hint):
            out.append(Finding(
                checker=self.name, rule=rule, path=ctx.rel,
                line=node.lineno, col=node.col_offset,
                message=f"in traced `{qual}`: {message}",
                ident=f"{qual}:{rule}", hint=hint))

        for node in _util.body_walk(func):
            if isinstance(node, (ast.If, ast.While)):
                bad = self._traced_test(node.test)
                if bad is not None:
                    finding(node, "traced-branch",
                            f"Python `{type(node).__name__.lower()}` "
                            f"branches on a traced value "
                            f"(`{ast.unparse(bad)[:60]}`)",
                            "use jnp.where / lax.cond / lax.select — "
                            "a Python branch on a tracer throws at "
                            "trace time or bakes one path in")
            elif isinstance(node, ast.Call):
                name = _util.call_name(node) or ""
                leaf = name.split(".")[-1]
                attr = (node.func.attr
                        if isinstance(node.func, ast.Attribute)
                        else None)
                if leaf in {"int", "float", "bool"} and "." not in name \
                        and len(node.args) == 1 \
                        and not self._static_arg(node.args[0],
                                                 assigns):
                    finding(node, "concretize",
                            f"`{leaf}(...)` on a non-static value "
                            f"forces concretization",
                            "keep the value on device (jnp ops), or "
                            "hoist the cast out of the jitted code")
                elif attr in {"item", "tolist"} and not node.args:
                    finding(node, "concretize",
                            f"`.{attr}()` forces a device->host sync "
                            f"under trace",
                            "return the array and fetch it outside "
                            "the jitted function")
                elif attr == "block_until_ready":
                    finding(node, "host-transfer",
                            "`.block_until_ready()` under trace",
                            "sync outside jitted code")
                elif name in {"np.asarray", "np.array",
                              "numpy.asarray", "numpy.array",
                              "jax.device_get"} \
                        and node.args \
                        and not self._static_arg(node.args[0],
                                                 assigns):
                    finding(node, "host-transfer",
                            f"`{name}` on a traced value pulls it to "
                            f"the host",
                            "use jnp.asarray / keep the computation "
                            "in jax.numpy under trace")
                elif self._dynamic_shape_ctor(node, name, leaf):
                    finding(node, "dynamic-shape",
                            f"`{name}` built with a shape computed "
                            f"from traced values",
                            "shapes must be static under jit: derive "
                            "them from .shape / config, or pad to a "
                            "bucket")
        return out

    def _traced_test(self, test: ast.AST) -> Optional[ast.AST]:
        """The subexpression proving the test is traced, if any."""
        for node in ast.walk(test):
            if not isinstance(node, ast.Call):
                continue
            name = _util.call_name(node) or ""
            head = name.split(".")[0]
            if head in _ARRAY_MODULES and "." in name:
                return node
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _TRACED_METHODS \
                    and not node.args and not node.keywords:
                return node
        return None

    def _static_arg(self, arg: ast.AST,
                    assigns: Optional[Dict[str, ast.AST]] = None,
                    depth: int = 0) -> bool:
        """Casts/transfers of static quantities are fine: constants,
        ``.shape`` chains, ``len()``, ``.ndim``/``.size``, host
        ``math.*`` results — following one-step local assignments
        (bounded, so a self-referential rebind can't recurse)."""
        if _util.is_constant_expr(arg):
            return True
        for node in ast.walk(arg):
            if isinstance(node, ast.Attribute) \
                    and node.attr in {"shape", "ndim", "size"}:
                return True
            if isinstance(node, ast.Call):
                name = _util.call_name(node) or ""
                if name == "len" or name.startswith("math."):
                    return True
        if isinstance(arg, ast.Name) and assigns and depth < 3:
            src_expr = assigns.get(arg.id)
            if src_expr is not None and src_expr is not arg:
                return self._static_arg(src_expr, assigns, depth + 1)
        return False

    def _dynamic_shape_ctor(self, node: ast.Call, name: str,
                            leaf: str) -> bool:
        head = name.split(".")[0]
        if head not in _ARRAY_MODULES:
            return False
        if leaf in _SHAPE_CTORS and node.args:
            shape_args = [node.args[0]]
        elif leaf in _RANGE_CTORS:
            shape_args = list(node.args)
        else:
            return False
        for arg in shape_args:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Call):
                    sub_name = _util.call_name(sub) or ""
                    if sub_name.split(".")[0] in _ARRAY_MODULES \
                            and "." in sub_name:
                        return True
        return False
