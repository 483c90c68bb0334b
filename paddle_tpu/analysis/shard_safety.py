"""Shard-map collective discipline: axis names must be bound.

The TP runner relies on convention today: collectives (``psum``,
``ppermute``, ``all_gather``, ...) are only legal inside a function that
``jax.shard_map`` maps over the mesh, and only on axis names the mapping
actually binds (``axis_names=`` / the mesh's axis tuple).  An unbound
axis name is a runtime ``NameError``-equivalent deep inside jit; a
misspelled PartitionSpec axis shards nothing and silently replicates.

The analyzer resolves every ``shard_map`` call's target the same way
``jit_safety`` resolves jit targets (named fns, nested defs, factory
closures, ``functools.partial`` wrappers), collects the axis universe
each call binds (literal ``axis_names={...}`` or the literal axis tuple
of the ``Mesh`` the ``mesh=`` argument refers to), and marks those
bodies — plus same-module helpers they call — as mapped.  Only *string
literal* axis arguments are judged: the repo's helper convention passes
the axis as a parameter (``def _psum(part, axis): ... psum(part,
axis)``), which is deliberate indirection the caller owns, so
parameter/closure axes are never flagged.

Rules:

``collective-outside-shardmap``
    A collective with a literal axis name in a function no ``shard_map``
    in the module maps — under jit this raises "unbound axis name".

``collective-unknown-axis``
    A literal axis that the mapping ``shard_map`` provably does not
    bind, or a literal ``PartitionSpec`` axis that is not an axis of
    any literal ``Mesh`` in the module.
"""
from __future__ import annotations

import ast

from .core import Finding, SourceFile, call_name
from .jit_safety import _JitCall, _ModuleIndex

__all__ = ["analyze"]

RULES = {
    "collective-outside-shardmap": "collective on a literal axis name "
                                   "outside any shard_map-mapped "
                                   "function",
    "collective-unknown-axis": "literal axis name not bound by the "
                               "mapping shard_map / mesh",
}

_COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "ppermute",
                "all_gather", "all_to_all", "axis_index", "psum_scatter"}
# positional index of the axis-name argument
_AXIS_POS = {"axis_index": 0}
_DEFAULT_AXIS_POS = 1

_SHARD_MAP_NAMES = {"jax.shard_map", "shard_map",
                    "jax.experimental.shard_map.shard_map"}
_MESH_NAMES = {"Mesh", "jax.sharding.Mesh", "sharding.Mesh",
               "jax.make_mesh"}

_TOKENS = ("psum", "ppermute", "all_gather", "all_to_all", "pmean",
           "pmax", "pmin", "axis_index", "shard_map", "PartitionSpec")


def analyze(src: SourceFile) -> list[Finding]:
    text = src.text
    if not any(t in text for t in _TOKENS):
        return []
    findings: list[Finding] = []
    mod = _ModuleIndex(src)
    index = _ShardIndex(src, mod)
    for call, fn in index.collectives:
        axes = _literal_axes(call)
        if not axes:
            continue                # parameter/closure axis: caller owns
        cname = call_name(call)
        fn_name = fn.name if fn is not None else "<module>"
        universe = index.universe_of(fn)
        if fn is None or id(fn) not in index.mapped:
            findings.append(Finding(
                "collective-outside-shardmap", src.path, call.lineno,
                f"collective `{cname}` on axis "
                f"{_fmt_axes(axes)} in `{fn_name}` is not mapped by any "
                "shard_map in this module — under jit the axis name is "
                "unbound",
                hint="wrap the caller in jax.shard_map(..., axis_names="
                     "...) or take the axis as a parameter"))
            continue
        if universe:
            for ax in axes:
                if ax not in universe:
                    findings.append(Finding(
                        "collective-unknown-axis", src.path, call.lineno,
                        f"collective `{cname}` in `{fn_name}` uses axis "
                        f"'{ax}' but the mapping shard_map binds only "
                        f"{sorted(universe)}",
                        hint="use one of the bound axis names, or bind "
                             "the axis in axis_names=/the mesh"))
    findings.extend(_check_partition_specs(src, index))
    seen, unique = set(), []
    for f in findings:
        key = (f.rule, f.line, f.message)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    return src.filter(unique)


def _fmt_axes(axes) -> str:
    if len(axes) == 1:
        return f"'{axes[0]}'"
    return "(" + ", ".join(f"'{a}'" for a in axes) + ")"


def _is_collective(call) -> str | None:
    name = call_name(call)
    if name is None:
        return None
    base = name.split(".")[-1]
    if base not in _COLLECTIVES:
        return None
    prefix = name[: -len(base)].rstrip(".")
    if prefix in ("", "lax", "jax.lax"):
        return base
    return None


def _literal_axes(call) -> list:
    base = _is_collective(call)
    if base is None:
        return []
    axis = None
    for kw in call.keywords:
        if kw.arg == "axis_name":
            axis = kw.value
    if axis is None:
        pos = _AXIS_POS.get(base, _DEFAULT_AXIS_POS)
        if len(call.args) > pos:
            axis = call.args[pos]
    if axis is None:
        return []
    out = []
    elts = axis.elts if isinstance(axis, (ast.Tuple, ast.List)) \
        else [axis]
    for e in elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, str):
            out.append(e.value)
        else:
            return []               # any non-literal part: caller owns it
    return out


class _ShardIndex:
    """shard_map-mapped functions, their axis universes, and all
    collective call sites with their enclosing function."""

    def __init__(self, src, mod: _ModuleIndex):
        self.src = src
        self.mod = mod
        self.mapped: dict[int, ast.AST] = {}    # id(fn) -> fn
        self.universes: dict[int, set | None] = {}
        self.collectives: list = []             # (call, enclosing fn)
        self.mesh_axes: set = set()             # all literal mesh axes
        self.spec_aliases = {"PartitionSpec"}
        self._collect_imports(src.tree)
        self._walk(src.tree, None, None)
        self._expand_transitive()

    # ------------------------------------------------------------ walking
    def _collect_imports(self, tree):
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name == "PartitionSpec":
                        self.spec_aliases.add(alias.asname or alias.name)

    def _walk(self, node, fn, cls):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                self._walk(child, fn, child)
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                self._walk(child, child, cls)
            else:
                self._visit_exprs(child, fn, cls)
                self._walk(child, fn, cls)

    def _visit_exprs(self, node, fn, cls):
        if not isinstance(node, ast.Call):
            return
        name = call_name(node)
        if name in _SHARD_MAP_NAMES and node.args:
            jit = _JitCall(node, fn, cls)
            body = self.mod._resolve_expr(node.args[0], jit)
            universe = self._universe(node, fn)
            if universe:
                self.mesh_axes |= universe
            if body is not None:
                key = id(body.node)
                self.mapped[key] = body.node
                if key in self.universes and \
                        self.universes[key] != universe:
                    self.universes[key] = None      # conflicting: unknown
                else:
                    self.universes[key] = universe
        elif _is_collective(node):
            self.collectives.append((node, fn))
        else:
            self._note_mesh(node)

    def _note_mesh(self, call):
        if call_name(call) not in _MESH_NAMES:
            return
        axes = self._mesh_axes_from_call(call)
        if axes:
            self.mesh_axes |= axes

    # ------------------------------------------------------ axis universes
    def universe_of(self, fn):
        return self.universes.get(id(fn)) if fn is not None else None

    def _universe(self, call, enclosing_fn) -> set | None:
        for kw in call.keywords:
            if kw.arg == "axis_names":
                axes = _str_literals(kw.value)
                if axes is not None:
                    return axes
        for kw in call.keywords:
            if kw.arg == "mesh":
                return self._mesh_universe(kw.value, enclosing_fn)
        return None

    def _mesh_universe(self, expr, enclosing_fn) -> set | None:
        if isinstance(expr, ast.Call):
            return self._mesh_axes_from_call(expr)
        if isinstance(expr, ast.Name):
            scopes = [self.src.tree]
            if enclosing_fn is not None:
                scopes.insert(0, enclosing_fn)
            for scope in scopes:
                for node in ast.walk(scope):
                    if isinstance(node, ast.Assign) and \
                            len(node.targets) == 1 and \
                            isinstance(node.targets[0], ast.Name) and \
                            node.targets[0].id == expr.id and \
                            isinstance(node.value, ast.Call):
                        return self._mesh_axes_from_call(node.value)
        return None

    @staticmethod
    def _mesh_axes_from_call(call) -> set | None:
        if call_name(call) not in _MESH_NAMES:
            return None
        cand = None
        for kw in call.keywords:
            if kw.arg in ("axis_names", "axis_name"):
                cand = kw.value
        if cand is None and len(call.args) > 1:
            cand = call.args[1]
        if cand is None:
            return None
        return _str_literals(cand)

    # ----------------------------------------- transitive mapped expansion
    def _expand_transitive(self):
        for _ in range(2):          # depth-bounded closure
            for fn in list(self.mapped.values()):
                universe = self.universes.get(id(fn))
                for node in ast.walk(fn):
                    for callee in self._referenced_defs(node, fn):
                        if id(callee) in self.mapped:
                            continue
                        self.mapped[id(callee)] = callee
                        self.universes[id(callee)] = universe

    def _referenced_defs(self, node, fn):
        """Defs a mapped body hands control to: direct calls, plus bare
        function references (scan/fori_loop bodies run in the mapped
        context without ever being *called* by name)."""
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            callee = self.mod.nested.get(id(fn), {}).get(node.id) or \
                self.mod.defs.get((None, node.id))
            if callee is not None:
                yield callee
        elif isinstance(node, ast.Call):
            name = call_name(node) or ""
            base = name.split(".")[-1]
            if name.startswith("self."):
                for (cls, fname), d in self.mod.defs.items():
                    if cls is not None and fname == base:
                        yield d
                        return
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a def nested in a mapped body runs in the mapped context
            if id(node) not in self.mapped and node is not fn:
                yield node


def _str_literals(node) -> set | None:
    """The set of string constants a literal collection denotes."""
    if isinstance(node, ast.Call) and \
            call_name(node) in ("frozenset", "set") and node.args:
        return _str_literals(node.args[0])
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.add(e.value)
            else:
                return None
        return out
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    return None


def _check_partition_specs(src, index: _ShardIndex) -> list[Finding]:
    if not index.mesh_axes:
        return []                   # no provable universe: stay silent
    findings = []
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node) or ""
        if name.split(".")[-1] not in index.spec_aliases:
            continue
        for arg in node.args:
            elts = arg.elts if isinstance(arg, (ast.Tuple, ast.List)) \
                else [arg]
            for e in elts:
                if isinstance(e, ast.Constant) and \
                        isinstance(e.value, str) and \
                        e.value not in index.mesh_axes:
                    findings.append(Finding(
                        "collective-unknown-axis", src.path, node.lineno,
                        f"PartitionSpec axis '{e.value}' is not an axis "
                        "of any mesh in this module "
                        f"({sorted(index.mesh_axes)}) — the dimension "
                        "silently replicates",
                        hint="use a mesh axis name, or None for "
                             "replicated dimensions"))
    return findings
