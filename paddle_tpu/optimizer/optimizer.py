"""Optimizer base (reference: python/paddle/optimizer/optimizer.py, 2052 LoC).

State (accumulators, master weights) is a dict of jax arrays keyed by
parameter name — a pytree, so a whole optimizer.step can run inside one
jitted update when driven through jit/functional.py.  Updates compute in
fp32 (master weights for low-precision params, reference `_master_weights`
optimizer.py:317) and write back in the param dtype.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor
from ..autograd import no_grad

__all__ = ["Optimizer"]

# The learning rate a compiled step hands in as a traced scalar: get_lr()
# inside that trace reads it, so one program follows a scheduler (or
# set_lr) from call to call instead of keeping its trace's value.
_TRACED_LR = contextvars.ContextVar("traced_lr", default=None)


@contextlib.contextmanager
def traced_lr(lr):
    """``get_lr()`` of every optimizer returns ``lr`` inside the block."""
    token = _TRACED_LR.set(lr)
    try:
        yield
    finally:
        _TRACED_LR.reset(token)


class Optimizer:
    _creating_state = False     # create_state's pass: slots keep their init

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        from .lr import LRScheduler
        import paddle_tpu
        if parameters is None and not paddle_tpu.in_dynamic_mode():
            parameters = []       # static mode: filled by minimize()
        if parameters is None:
            raise ValueError(
                "parameters is required in dygraph mode (pass model.parameters())")
        self._parameter_list = list(parameters)
        if not self._parameter_list and paddle_tpu.in_dynamic_mode():
            raise ValueError("optimizer got an empty parameter list")
        self._lr = learning_rate
        self._lr_scheduler = learning_rate if isinstance(
            learning_rate, LRScheduler) else None
        self._l1_coeff = 0.0
        if isinstance(weight_decay, float):
            self._coeff = weight_decay
        elif weight_decay is None:
            self._coeff = 0.0
        else:  # L1Decay/L2Decay-like object with a coeff
            coeff = float(getattr(weight_decay, "_coeff",
                                  getattr(weight_decay, "coeff", 0.0)))
            if type(weight_decay).__name__ == "L1Decay":
                self._l1_coeff = coeff
                self._coeff = 0.0
            else:
                self._coeff = coeff
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: dict[str, dict[str, jnp.ndarray]] = {}
        self._master_weights: dict[str, jnp.ndarray] = {}
        self._step_count = 0
        self._name = name

    # ------------------------------------------------------------ lr
    def get_lr(self):
        lr = _TRACED_LR.get()
        if lr is not None:
            return lr
        if self._lr_scheduler is not None:
            return self._lr_scheduler()
        return float(self._lr)

    def set_lr(self, value):
        self._lr = float(value)
        self._lr_scheduler = None

    # ------------------------------------------------------------ state
    def _param_key(self, p):
        return p.name

    def _get_master(self, p):
        key = self._param_key(p)
        if not self._multi_precision or p._data.dtype == jnp.float32:
            return None
        if key not in self._master_weights:
            self._master_weights[key] = p._data.astype(jnp.float32)
        return self._master_weights[key]

    def _acc(self, p, name, init=None):
        key = self._param_key(p)
        slot = self._accumulators.setdefault(key, {})
        if name not in slot:
            slot[name] = init if init is not None else \
                jnp.zeros(p._data.shape, jnp.float32)
        return slot[name]

    def _set_acc(self, p, name, value):
        if self._creating_state:
            return
        self._accumulators[self._param_key(p)][name] = value

    def create_state(self):
        """Create every accumulator and master weight that the next update
        of the trainable parameters would create, with the values that
        update would give them (``_acc``'s init, ``_get_master``'s cast);
        state already held (loaded, resumed) is kept.

        One jitted pass runs ``_update_param`` on zero gradients with
        ``_set_acc`` and ``_write_back`` held off, so every slot keeps
        the value its creation gave it; the update arithmetic is traced,
        and dropped from the program (nothing reads it).  The pass is
        traced once an optimizer, and skipped where the state already has
        the layout it left.  A compiled step whose optimizer state is
        whole before its first trace is handed the same pytree on every
        call, and so holds one program."""
        import jax

        if type(self)._update_param is Optimizer._update_param:
            return              # wrappers and closure optimizers: no slots
        datas = {self._param_key(p): p._data for p in self._parameter_list
                 if not p.stop_gradient}
        held = (self._accumulators, self._master_weights)
        whole = self.__dict__.get("_whole_layout")
        if not datas or (whole is not None
                         and jax.tree.structure(held) == whole):
            return
        create = self.__dict__.get("_create_state_jit")
        if create is None:
            create = self._create_state_jit = jax.jit(self._created)
        accs, masters = create(datas, *held, np.float32(self.get_lr()))
        for k, slots in accs.items():
            for name, a in slots.items():
                self._accumulators.setdefault(k, {}).setdefault(name, a)
        for k, m in masters.items():
            self._master_weights.setdefault(k, m)
        self._whole_layout = jax.tree.structure(held)

    def _created(self, datas, accs, masters, lr):
        """``create_state``'s pass: the state after one update that
        changed nothing but what it created."""
        params = [p for p in self._parameter_list
                  if self._param_key(p) in datas]
        with self._replaying(params, [datas[self._param_key(p)]
                                      for p in params], accs, masters, 0):
            self._creating_state = True
            try:
                for p in params:
                    self._update_param(
                        p, jnp.zeros(p._data.shape, jnp.float32), lr)
            finally:
                self._creating_state = False
            return self._accumulators, self._master_weights

    @contextlib.contextmanager
    def _replaying(self, params, datas, accs, masters, steps):
        """Swap ``datas`` into ``params`` and the given state into the
        optimizer for a replay of ``_update_param`` under a trace; put
        back what was there after."""
        saved = ([p._data for p in params], self._accumulators,
                 self._master_weights, self._step_count)
        try:
            for p, d in zip(params, datas):
                p._data = d
            self._accumulators = {k: dict(v) for k, v in accs.items()}
            self._master_weights = dict(masters)
            self._step_count = steps
            yield
        finally:
            (kept, self._accumulators, self._master_weights,
             self._step_count) = saved
            for p, d in zip(params, kept):
                p._data = d

    # ------------------------------------------------------------ step
    @no_grad()
    def step(self):
        params_grads = []
        for p in self._parameter_list:
            if p.stop_gradient or p._grad is None:
                continue
            params_grads.append((p, p._grad))
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        lr = self.get_lr()
        gt = getattr(self, "_grad_transform", None)
        if gt is None and params_grads and self._try_fused_step(
                params_grads, lr):
            self._step_count += 1
            return
        from ..framework.selected_rows import RowSparseGrad
        for p, g in params_grads:
            if g is None:
                continue
            g32 = g.astype(jnp.float32)
            if isinstance(g32, RowSparseGrad) and gt is None \
                    and not self._l1_coeff:
                self._update_param_rowsparse(p, g32, lr)
                continue
            if isinstance(g32, RowSparseGrad):
                # sharded-grad transforms / L1 operate on dense math
                g32 = g32.to_dense()
            if gt is not None:
                # sharding-stage>=2: reduce-scatter semantics — the grad
                # becomes dp-sharded so update math runs on shards only
                g32 = gt(g32)
            if self._l1_coeff:  # L1 regularization: grad += c * sign(param)
                g32 = g32 + self._l1_coeff * jnp.sign(self._param_f32(p))
            self._update_param(p, g32, lr)
        self._step_count += 1

    # ----------------------------------------------------- fused eager step
    # Eager per-param updates dispatch 2-5 device ops per parameter; the
    # reference fuses them (phi multi_tensor_adam / fused kernels).  The
    # TPU analog: replay the subclass's _update_param math for ALL params
    # under one cached jit, with lr/step passed as traced scalars so
    # schedulers and Adam bias correction stay step-accurate.
    def _try_fused_step(self, params_grads, lr):
        import jax

        if getattr(self, "_fused_step_broken", False):
            return False
        if "_acc" in self.__dict__ or hasattr(self, "_shard_state_fn") \
                or getattr(self, "_param_restore", None) is not None:
            # sharded-state optimizers (shard_optimizer stages) place
            # accumulators with device_put; inside a jit that placement
            # becomes advisory and XLA replicates — keep the eager loop
            return False
        from ..framework.selected_rows import RowSparseGrad
        if any(g is None or isinstance(g, RowSparseGrad)
               for _, g in params_grads):
            return False
        ps = [p for p, _ in params_grads]
        gs = [g for _, g in params_grads]
        if any(isinstance(x._data if hasattr(x, "_data") else x,
                          jax.core.Tracer) for x in ps + gs):
            return False          # traced context (train_step): legacy path
        keys = [self._param_key(p) for p in ps]
        accs_in = {k: dict(self._accumulators.get(k, {})) for k in keys}
        masters_in = {k: self._master_weights[k] for k in keys
                      if k in self._master_weights}
        sig = (tuple((str(p._data.dtype), p._data.shape) for p in ps),
               tuple((str(g.dtype), g.shape) for g in gs),
               tuple((k, tuple(sorted(v))) for k, v in accs_in.items()),
               tuple(sorted(masters_in)))
        cache = self.__dict__.setdefault("_fused_step_cache", {})
        fn = cache.get(sig)
        if fn is None:
            opt = self

            def run(pvals, gvals, accs, masters, lr_arr, prev_steps):
                with opt._replaying(ps, pvals, accs, masters, prev_steps):
                    for p, g in zip(ps, gvals):
                        g32 = g.astype(jnp.float32)
                        if opt._l1_coeff:
                            g32 = g32 + opt._l1_coeff * jnp.sign(
                                opt._param_f32(p))
                        opt._update_param(p, g32, lr_arr)
                    new_p = [p._data for p in ps]
                    new_accs = {k: dict(opt._accumulators.get(k, {}))
                                for k in keys}
                    new_masters = {k: opt._master_weights[k] for k in keys
                                   if k in opt._master_weights}
                    return new_p, new_accs, new_masters

            fn = jax.jit(run)
        try:
            import numpy as _np

            # numpy scalars, NOT jnp: jnp.float32(lr) is an eager
            # device_put dispatch per step; as np scalars the transfer
            # rides the jitted call itself
            new_p, new_accs, new_masters = fn(
                [p._data for p in ps], gs, accs_in, masters_in,
                _np.float32(lr), _np.int32(self._step_count))
        except Exception:
            # subclass math not traceable (host-side control flow, e.g.
            # line searches): permanently take the legacy loop
            self._fused_step_broken = True
            return False
        cache[sig] = fn
        for p, nv in zip(ps, new_p):
            key = self._param_key(p)
            if key in new_masters:
                self._master_weights[key] = new_masters[key]
            p._data = nv
        for k, v in new_accs.items():
            if v:
                self._accumulators[k] = v
        return True

    def _update_param(self, p, grad_f32, lr):
        raise NotImplementedError

    def _update_param_rowsparse(self, p, g, lr):
        """Apply a RowSparseGrad.  Base behavior: densify (with a one-time
        note) — SGD and lazy Adam/AdamW override with true row updates
        (reference: sgd SelectedRows kernel + adam lazy_mode,
        paddle/phi/kernels/selected_rows/)."""
        if not getattr(type(self), "_rs_densify_warned", False):
            import logging
            logging.getLogger("paddle_tpu").info(
                "%s has no row-sparse update; densifying embedding grad "
                "(use SGD or Adam/AdamW(lazy_mode=True) for row updates)",
                type(self).__name__)
            type(self)._rs_densify_warned = True
        self._update_param(p, g.to_dense(), lr)

    def _write_back(self, p, new_f32):
        if self._creating_state:
            return
        key = self._param_key(p)
        if key in self._master_weights:
            self._master_weights[key] = new_f32
        out = new_f32.astype(p._data.dtype)
        restore = getattr(self, "_param_restore", None)
        if restore is not None:
            # sharding-stage 2: updated shards gather back to the param's
            # own layout (replicated); stage 3 params are sharded so this
            # is a no-op placement
            out = restore(p, out)
        p._data = out

    def _param_f32(self, p):
        master = self._get_master(p)
        return master if master is not None else p._data.astype(jnp.float32)

    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list:
            p.clear_grad(set_to_zero=False)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static import graph as _sgraph
        if isinstance(loss, _sgraph.Variable):
            # static build: record a train op; Executor.run computes the
            # grads and calls step() (reference: appended optimizer ops)
            prog = loss.program
            prog.train_ops.append((self, loss))
            prog.version += 1
            if not self._parameter_list:
                self._parameter_list = [
                    p for p in prog.all_parameters() if not p.stop_gradient]
            return None, None
        loss.backward()
        self.step()
        self.clear_grad()

    # ------------------------------------------------------------ ckpt
    def state_dict(self):
        state = {}
        for pkey, slots in self._accumulators.items():
            for sname, arr in slots.items():
                state[f"{pkey}.{sname}"] = Tensor(arr)
        for pkey, arr in self._master_weights.items():
            state[f"{pkey}.master_weight"] = Tensor(arr)
        if self._lr_scheduler is not None:
            state["LR_Scheduler"] = self._lr_scheduler.state_dict()
        state["@step"] = self._step_count
        return state

    def set_state_dict(self, state):
        for key, val in state.items():
            if key == "LR_Scheduler":
                if self._lr_scheduler is not None:
                    self._lr_scheduler.set_state_dict(val)
                continue
            if key == "@step":
                self._step_count = int(val)
                continue
            pkey, sname = key.rsplit(".", 1)
            arr = val._data if isinstance(val, Tensor) else jnp.asarray(val)
            if sname == "master_weight":
                self._master_weights[pkey] = arr
            else:
                self._accumulators.setdefault(pkey, {})[sname] = arr

    # ------------------------------------------------- functional bridge
    def opt_state(self):
        """All optimizer state as a pytree of jax arrays (for jit)."""
        return {"acc": {k: dict(v) for k, v in self._accumulators.items()},
                "master": dict(self._master_weights),
                "step": self._step_count}

    def load_opt_state(self, state):
        self._accumulators = {k: dict(v) for k, v in state["acc"].items()}
        self._master_weights = dict(state["master"])
        self._step_count = int(state["step"]) if not hasattr(
            state["step"], "dtype") else state["step"]
