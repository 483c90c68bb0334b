"""Tape-based reverse-mode autograd over jax ops.

The reference implements dygraph autograd as a C++ GradNode DAG built by
generated ``<op>_ad_func`` wrappers and walked by ``egr::Backward``
(paddle/fluid/eager/backward.cc:105,439).  On TPU we get every op's VJP from
jax (`jax.vjp`), so the tape only needs to (a) record a node per op linking
input/output tensors, (b) run a reverse-topological sweep accumulating
cotangents.  The tape records plain functions of jax arrays, so it works both
eagerly and inside a `jax.jit` trace (backward() under trace yields traced
grads — this is how the compiled training step is built).
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["GradNode", "no_grad", "enable_grad", "is_grad_enabled",
           "set_grad_enabled", "backward", "grad"]


class _TapeState(threading.local):
    def __init__(self):
        self.enabled = True
        self.next_id = 0


_state = _TapeState()


def is_grad_enabled() -> bool:
    return _state.enabled


def set_grad_enabled(mode: bool):
    _state.enabled = bool(mode)


class _GradModeGuard:
    def __init__(self, mode: bool):
        self._mode = mode

    def __enter__(self):
        self._prev = _state.enabled
        _state.enabled = self._mode
        return self

    def __exit__(self, *exc):
        _state.enabled = self._prev
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with _GradModeGuard(self._mode):
                return fn(*a, **k)

        return wrapper


def no_grad(func=None):
    """Context manager / decorator disabling tape recording (paddle.no_grad)."""
    g = _GradModeGuard(False)
    return g(func) if callable(func) else g


def enable_grad(func=None):
    g = _GradModeGuard(True)
    return g(func) if callable(func) else g


class GradNode:
    """One recorded op: maps output cotangents -> input cotangents.

    ``vjp_fn`` takes a tuple of output cotangents (one per output, zeros
    filled for unused outputs) and returns a tuple of input cotangents
    aligned with ``inputs``.

    Inputs are snapshotted as (tensor, producer_node, out_index) at record
    time: in-place APIs rebind tensor handles to new nodes, so the recorded
    graph must not chase the live ``_grad_node`` (it may point *forward*).
    """

    __slots__ = ("id", "name", "vjp_fn", "inputs", "out_avals",
                 "raw_vjp", "out_treedef", "fwd_closed")

    def __init__(self, name: str, vjp_fn: Callable, inputs: Sequence[Any],
                 out_avals: Sequence[Any]):
        self.id = _state.next_id
        _state.next_id += 1
        self.name = name
        self.vjp_fn = vjp_fn
        self.inputs = [(t, t._grad_node, t._out_index) for t in inputs]
        self.out_avals = list(out_avals)  # jax.ShapeDtypeStruct per output
        self.raw_vjp = None        # tree_util.Partial when fusable
        self.out_treedef = None
        self.fwd_closed = None     # re-runnable fwd for create_graph=True

    def __repr__(self):
        return f"<GradNode {self.name}#{self.id}>"


def _zeros_like_aval(aval):
    if aval.dtype == jax.dtypes.float0:
        import numpy as np
        return np.zeros(aval.shape, jax.dtypes.float0)
    return jnp.zeros(aval.shape, aval.dtype)


# ------------------------------------------------------- fused backward
# One dispatch per GradNode is the dygraph tax (a host cost each).
# For the common case — every node carries a cached-jit
# vjp Partial, no hooks, plain .grad accumulation — the WHOLE reverse
# sweep retraces into one jitted executable, cached by the tape's
# structural signature (the graph repeats every step in a training loop).
_FUSED_BW_CACHE: dict = {}
_FUSED_BW_MAX = 128
FUSED_BACKWARD = True


def _try_fused_backward(tensors, grad_tensors, retain_graph):
    """Returns True when the sweep ran fused; False -> caller runs the
    per-node path."""
    from jax.tree_util import tree_flatten, tree_unflatten

    # ---- plan: walk the graph symbolically (no vjp execution) --------
    plan_nodes = []            # GradNode, reverse-topo order
    nodes: dict[int, GradNode] = {}
    seeds = []                 # (node, out_index, seed_array)

    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            continue
        if isinstance(t._data, jax.core.Tracer):
            return False       # inside an outer trace: per-node path
        node = t._grad_node
        if node is None:
            return False       # direct-leaf seed: per-node path handles
        if g is None:
            if t.size != 1:
                return False   # error path: per-node code raises it
            g = jnp.ones(t._data.shape, t._data.dtype)
        else:
            g = g._data if hasattr(g, "_data") else jnp.asarray(g)
        seeds.append((node, t._out_index, g))
        nodes[node.id] = node

    if not seeds:
        return False
    order: list[int] = []
    walk = dict(nodes)
    while walk:
        nid = max(walk)
        node = walk.pop(nid)
        if node.raw_vjp is None or node.vjp_fn is _used_vjp:
            return False       # hooks / non-cached vjp / reused graph
        plan_nodes.append(node)
        order.append(nid)
        for (t, prod, _pi) in node.inputs:
            if t is not None and not t.stop_gradient and prod is not None:
                walk[prod.id] = prod

    # leaves in deterministic discovery order
    leaves = []                # Tensor objects
    leaf_slot: dict[int, int] = {}
    for node in plan_nodes:
        for (t, prod, _pi) in node.inputs:
            if t is not None and not t.stop_gradient and prod is None \
                    and id(t) not in leaf_slot:
                leaf_slot[id(t)] = len(leaves)
                leaves.append(t)

    id2pos = {nid: i for i, nid in enumerate(order)}

    # ---- signature + dynamic inputs ----------------------------------
    sig_parts = []
    res_leaves_all = []        # flat residual leaves, per node
    res_trees = []
    for node in plan_nodes:
        rl, rt = tree_flatten(node.raw_vjp)
        res_leaves_all.append(tuple(rl))
        res_trees.append(rt)
        links = tuple(
            ("x",) if t is None or t.stop_gradient else
            (("l", leaf_slot[id(t)]) if prod is None
             else ("n", id2pos[prod.id], pi))
            for (t, prod, pi) in node.inputs)
        sig_parts.append((
            node.name, rt, node.out_treedef,
            tuple((tuple(a.shape), str(a.dtype)) for a in node.out_avals),
            tuple((tuple(l.shape), str(l.dtype)) for l in rl),
            links))
    sig = (tuple(sig_parts),
           tuple((id2pos[n.id], oi, tuple(g.shape), str(g.dtype))
                 for n, oi, g in seeds),
           len(leaves))

    leaf_avals = tuple(
        (tuple(t._data.shape), str(t._data.dtype)) for t in leaves)
    sig = sig + (leaf_avals,)
    fn = _FUSED_BW_CACHE.get(sig)
    if fn is None:
        plan_meta = [(list(node.out_avals), tree, node.out_treedef,
                      links)
                     for node, tree, links in zip(
                         plan_nodes, res_trees,
                         [sp[-1] for sp in sig_parts])]
        seed_meta = [(id2pos[n.id], oi) for n, oi, _g in seeds]
        n_leaves = len(leaves)

        def fused(all_res, seed_vals):
            from ..ops.registry import _apply_cached_vjp

            pend = [[None] * len(m[0]) for m in plan_meta]
            leaf_out = [None] * n_leaves

            def add(slot, g):
                if g is None:
                    return
                kind = slot[0]
                if kind == "l":
                    i = slot[1]
                    leaf_out[i] = g if leaf_out[i] is None \
                        else leaf_out[i] + g
                elif kind == "n":
                    _, pos, oi = slot
                    pend[pos][oi] = g if pend[pos][oi] is None \
                        else pend[pos][oi] + g

            for (pos, oi), g in zip(seed_meta, seed_vals):
                pend[pos][oi] = g if pend[pos][oi] is None \
                    else pend[pos][oi] + g

            for pos, (avals, rtree, otree, links) in enumerate(plan_meta):
                cots = tuple(
                    c if c is not None else _zeros_like_aval(a)
                    for c, a in zip(pend[pos], avals))
                raw = tree_unflatten(rtree, list(all_res[pos]))
                in_cots = _apply_cached_vjp(
                    raw, tree_unflatten(otree, list(cots)))
                for slot, g in zip(links, in_cots):
                    if slot[0] != "x":
                        add(slot, g)
            return [g if g is not None else jnp.zeros(s, d)
                    for g, (s, d) in zip(leaf_out, leaf_avals)]

        fn = jax.jit(fused)
        if len(_FUSED_BW_CACHE) >= _FUSED_BW_MAX:
            _FUSED_BW_CACHE.pop(next(iter(_FUSED_BW_CACHE)))
        _FUSED_BW_CACHE[sig] = fn

    try:
        grads = fn(tuple(res_leaves_all), tuple(g for _n, _oi, g in seeds))
    except Exception:
        return False
    for t, g in zip(leaves, grads):
        t._grad = g if t._grad is None else t._grad + g
    if not retain_graph:
        for node in plan_nodes:
            node.vjp_fn = _used_vjp
            node.raw_vjp = None
            node.inputs = []
            node.fwd_closed = None
    return True


def backward(tensors, grad_tensors=None, retain_graph=False, _sink=None,
             _capture=frozenset()):
    """Reverse sweep from ``tensors`` accumulating into leaf ``.grad``.

    Mirrors ``egr::Backward`` semantics: seeds with ones for scalar outputs,
    walks nodes in reverse creation order (a valid reverse-topological order
    for a tape), accumulates into ``Tensor.grad`` on leaves
    (stop_gradient=False tensors with no grad node).

    When ``_sink`` (a dict) is given, leaf cotangents go into
    ``_sink[id(tensor)]`` instead of ``.grad`` — used by :func:`grad`.
    """
    from ..framework.tensor import Tensor

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor) or not isinstance(grad_tensors, (list, tuple)):
        grad_tensors = [grad_tensors]

    if (FUSED_BACKWARD and _sink is None
            and _try_fused_backward(tensors, grad_tensors, retain_graph)):
        return

    # node id -> list of output cotangents (lazily filled)
    pending: dict[int, list] = {}
    nodes: dict[int, GradNode] = {}

    def seed(t: Tensor, g):
        if t.stop_gradient:
            return
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {t.shape}")
            g = jnp.ones(t._data.shape, t._data.dtype)
        else:
            g = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        _accumulate(t, t._grad_node, t._out_index, g)

    def _accumulate(t: Tensor, node, out_index, g):
        if _sink is not None and (node is None or id(t) in _capture):
            prev = _sink.get(id(t))
            _sink[id(t)] = g if prev is None else prev + g
            if node is None:
                return
        elif node is None:
            # leaf: accumulate into .grad
            prev = t._grad
            t._grad = g if prev is None else prev + g
            return
        nodes[node.id] = node
        cots = pending.get(node.id)
        if cots is None:
            cots = [None] * len(node.out_avals)
            pending[node.id] = cots
        cots[out_index] = g if cots[out_index] is None \
            else cots[out_index] + g

    for t, g in zip(tensors, grad_tensors):
        seed(t, g)

    # Reverse creation order == reverse topological order on a tape.
    while nodes:
        nid = max(nodes)
        node = nodes.pop(nid)
        cots = pending.pop(nid)
        cots = tuple(
            c if c is not None else _zeros_like_aval(a)
            for c, a in zip(cots, node.out_avals))
        in_cots = node.vjp_fn(cots)
        for (t, prod_node, prod_idx), g in zip(node.inputs, in_cots):
            if t is None or g is None:
                continue
            if not t.stop_gradient:
                _accumulate(t, prod_node, prod_idx, g)
        if not retain_graph:
            node.vjp_fn = _used_vjp
            node.inputs = []
            node.fwd_closed = None


def _used_vjp(*_):
    raise RuntimeError(
        "Trying to backward through the graph a second time; "
        "pass retain_graph=True if you need to.")


# ------------------------------------------------- higher-order autograd
# The reference implements double/triple backward as dedicated
# *_double_grad / *_triple_grad ops (34 + 19 entries in
# paddle/phi/ops/yaml/backward.yaml:4) driven by grad(create_graph=True)
# (python/paddle/base/dygraph/base.py:656,690).  Here every registry op
# stores a re-runnable forward closure (registry._make_closed), so the
# create_graph sweep re-linearises each node with `jax.vjp` — the grad of
# the grad falls out of jax's own transpose rules, to arbitrary order
# (the replay node stores its OWN closure, so triple grad recurses).


def _replay_differentiable(node: GradNode, cot_ts: list):
    """Run one node's backward as a *recorded*, differentiable op.

    cot_ts: flat output-cotangent Tensors (one per out_aval).  Returns
    input-cotangent Tensors aligned with ``node.inputs``; when any diff
    input feeds them, they carry a new GradNode whose vjp comes from
    ``jax.vjp`` of the replay — so the result is differentiable w.r.t.
    both the op's original inputs (via residual recompute) and the
    incoming cotangents (the linear part).
    """
    from jax.tree_util import tree_flatten, tree_unflatten
    from ..framework.tensor import Tensor
    from ..ops.registry import _tangent_dtype

    if node.fwd_closed is None or node.out_treedef is None:
        raise NotImplementedError(
            f"grad(..., create_graph=True) through op '{node.name}' is not "
            "supported: the node has no re-differentiable forward closure "
            "(custom GradNodes — PyLayer / to_static / recompute / "
            "sparse-conv — and eager-RNG ops like dropout). Restructure the "
            "double-grad region to use framework ops, or compute it under "
            "jax.grad directly.")

    in_items = list(node.inputs)          # (tensor, producer, out_index)
    in_arrs0 = [t._data for (t, _p, _i) in in_items]
    # float0 cotangents (integer outputs) travel as raw numpy zeros, not
    # Tensors — they are never differentiable
    cot_arrs0 = [getattr(c, "_data", c) for c in cot_ts]
    fwd = node.fwd_closed
    otree = node.out_treedef

    def _inexact(a):
        return _tangent_dtype(a) != jax.dtypes.float0

    diff = [("i", k) for k, (t, _p, _ix) in enumerate(in_items)
            if not t.stop_gradient and _inexact(t._data)]
    diff += [("c", k) for k, c in enumerate(cot_ts)
             if isinstance(c, Tensor) and not c.stop_gradient
             and _inexact(c._data)]

    def gop(*darrs):
        ia, ca = list(in_arrs0), list(cot_arrs0)
        for (kind, k), a in zip(diff, darrs):
            (ia if kind == "i" else ca)[k] = a
        _out, vjp = jax.vjp(fwd, *ia)
        return tuple(vjp(tree_unflatten(otree, ca)))

    darrs = [(in_arrs0 if kind == "i" else cot_arrs0)[k]
             for (kind, k) in diff]
    if diff and is_grad_enabled():
        out, raw_vjp = jax.vjp(gop, *darrs)
    else:
        out, raw_vjp = gop(*darrs), None

    out_flat, out_tree2 = tree_flatten(out)
    nnode = None
    if raw_vjp is not None:
        out_avals = [jax.ShapeDtypeStruct(np.shape(a), _tangent_dtype(a))
                     for a in out_flat]

        def vjp_fn(flat_cots):
            return raw_vjp(tree_unflatten(out_tree2, list(flat_cots)))

        diff_ts = [in_items[k][0] if kind == "i" else cot_ts[k]
                   for (kind, k) in diff]
        nnode = GradNode(f"grad[{node.name}]", vjp_fn, diff_ts, out_avals)
        # the original inputs' producers were snapshotted at forward-record
        # time; the live _grad_node may have been rebound by in-place APIs
        # since — restore the snapshot
        for j, (kind, k) in enumerate(diff):
            if kind == "i":
                nnode.inputs[j] = in_items[k]
        nnode.fwd_closed = gop
        nnode.out_treedef = out_tree2

    res = []
    for i, a in enumerate(out_flat):
        diffable = nnode is not None and _tangent_dtype(a) != jax.dtypes.float0
        t = Tensor(a, stop_gradient=not diffable)
        if diffable:
            t._grad_node = nnode
            t._out_index = i
        res.append(t)
    return res


def _backward_create_graph(tensors, grad_tensors, _sink, _capture,
                           retain_graph):
    """The grad(create_graph=True) sweep: cotangents flow as *recorded*
    Tensors and every node replay is itself differentiable."""
    from ..framework.tensor import Tensor

    pending: dict[int, list] = {}
    nodes: dict[int, GradNode] = {}

    def _acc_pair(a, b):
        return b if a is None else a + b      # Tensor __add__: recorded

    def _accumulate(t, node, out_index, g):
        if node is None or id(t) in _capture:
            prev = _sink.get(id(t))
            _sink[id(t)] = _acc_pair(prev, g)
            if node is None:
                return
        nodes[node.id] = node
        cots = pending.get(node.id)
        if cots is None:
            cots = [None] * len(node.out_avals)
            pending[node.id] = cots
        cots[out_index] = _acc_pair(cots[out_index], g)

    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            continue
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {t.shape}")
            g = Tensor(jnp.ones(t._data.shape, t._data.dtype),
                       stop_gradient=True)
        elif not isinstance(g, Tensor):
            g = Tensor(jnp.asarray(g), stop_gradient=True)
        _accumulate(t, t._grad_node, t._out_index, g)

    while nodes:
        nid = max(nodes)
        node = nodes.pop(nid)
        cots = pending.pop(nid)
        def _zero_cot(a):
            z = _zeros_like_aval(a)
            return z if a.dtype == jax.dtypes.float0 \
                else Tensor(z, stop_gradient=True)

        cot_ts = [c if c is not None else _zero_cot(a)
                  for c, a in zip(cots, node.out_avals)]
        in_cots = _replay_differentiable(node, cot_ts)
        for (t, prod_node, prod_idx), g in zip(node.inputs, in_cots):
            if t is None or g is None:
                continue
            if not t.stop_gradient:
                _accumulate(t, prod_node, prod_idx, g)
        if not retain_graph:
            node.vjp_fn = _used_vjp
            node.inputs = []
            node.fwd_closed = None


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """paddle.grad: grads of outputs wrt inputs without touching .grad.

    Implemented as a tape sweep into a side accumulator (reference:
    general_grad.h selective subgraph; create_graph semantics from
    python/paddle/base/dygraph/base.py:656,690 — retain_graph defaults to
    the create_graph value, and with create_graph=True the returned grads
    are themselves recorded for higher-order differentiation).
    """
    from ..framework.tensor import Tensor

    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if not only_inputs:
        raise NotImplementedError("only_inputs=False is not supported "
                                  "(matches the reference deprecation)")
    if retain_graph is None:
        retain_graph = create_graph
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif isinstance(grad_outputs, Tensor) or not isinstance(
            grad_outputs, (list, tuple)):
        grad_outputs = [grad_outputs]

    ngv = []
    if no_grad_vars:
        if isinstance(no_grad_vars, Tensor):
            no_grad_vars = [no_grad_vars]
        for t in no_grad_vars:
            if not t.stop_gradient:
                ngv.append(t)
                t.stop_gradient = True
    sink: dict[int, Any] = {}
    try:
        if create_graph:
            with enable_grad():
                _backward_create_graph(
                    outputs, grad_outputs, sink,
                    frozenset(id(t) for t in inputs), retain_graph)
        else:
            backward(outputs, grad_outputs, retain_graph=retain_graph,
                     _sink=sink, _capture=frozenset(id(t) for t in inputs))
    finally:
        for t in ngv:
            t.stop_gradient = False
    results = []
    for t in inputs:
        g = sink.get(id(t))
        if g is None and not allow_unused:
            g = jnp.zeros(t._data.shape, t._data.dtype)
            g = Tensor(g, stop_gradient=True)
        elif g is not None and not isinstance(g, Tensor):
            g = Tensor(g, stop_gradient=True)
        results.append(g)
    return results


# ---------------------------------------------------- saved-tensor hooks
# (reference: python/paddle/autograd/saved_tensors_hooks.py — pack runs
# when an op saves residuals for backward, unpack when backward uses them.
# Here residuals live inside jax.vjp closures; the hooks are applied to
# the op's *input* tensors, which is the dominant residual class, by
# wrapping the recorded vjp.)

_saved_hooks_stack = []


def push_saved_tensors_hooks(pack_hook, unpack_hook):
    _saved_hooks_stack.append((pack_hook, unpack_hook))


def pop_saved_tensors_hooks():
    _saved_hooks_stack.pop()


def current_saved_tensors_hooks():
    return _saved_hooks_stack[-1] if _saved_hooks_stack else None
