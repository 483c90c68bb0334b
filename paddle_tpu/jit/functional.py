"""Compiled training step: the TPU hot path.

The reference's static-graph training (Executor over a PIR program with
fused kernels) maps to a single jitted function of
(params, opt_state, batch, key) -> (loss, params, opt_state): forward,
backward, and optimizer update fused into one XLA executable, parameters
donated so updates happen in-place in HBM.

`TrainStep` drives a stock `nn.Layer` + `optimizer.Optimizer` through this
path without the user rewriting anything: it re-runs the tape under trace
(all op bodies are pure jax) and captures the optimizer's state pytree.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..framework import random as _random
from ..autograd import tape

__all__ = ["TrainStep", "train_step"]


class TrainStep:
    def __init__(self, model, optimizer, loss_fn: Callable, donate=True):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self._compiled = None
        self._donate = donate

    def _build(self):
        return jax.jit(self._pure_step(), donate_argnums=(
            (0, 2) if self._donate else ()))

    def _pure_step(self):
        """The unjitted (params, bufs, opt_state, key, *batch) ->
        (loss, params, bufs, opt_state) function — scannable."""
        model, optimizer, loss_fn = self.model, self.optimizer, self.loss_fn

        def step(params, bufs, opt_state, key, *batch):
            with _random.trace_key_guard(key):
                # load traced state into the live objects
                saved = model.functional_state()
                model.load_functional_state({**params, **bufs})
                optimizer.load_opt_state(opt_state)
                param_objs = {name: p for name, p in model.named_parameters()}
                try:
                    inputs = jax.tree.map(
                        lambda a: Tensor(a, stop_gradient=True), list(batch))
                    with tape.enable_grad():
                        loss = loss_fn(model, *inputs)
                        loss.backward()
                    optimizer.step()
                    optimizer.clear_grad()
                    new_params = {k: param_objs[k]._data for k in params}
                    new_bufs = {k: v for k, v in model.functional_state().items()
                                if k in bufs}
                    new_opt = optimizer.opt_state()
                    return loss._data, new_params, new_bufs, new_opt
                finally:
                    model.load_functional_state(saved)

        return step

    def multi_step(self, n):
        """Compile an n-step training scan: ONE device dispatch runs n
        optimizer steps on the same batch argument (pass fresh batches
        per call for real epochs).  This amortizes per-dispatch host
        latency, mirroring how the reference's Executor replays a whole
        program per run call.

            many = paddle.jit.train_step(model, opt, loss_fn).multi_step(10)
            loss = many(x, y)     # 10 steps, one dispatch
        """
        pure = self._pure_step()

        def many(params, bufs, opt_state, key, *batch):
            keys = jax.random.split(key, n)
            # step 1 runs unrolled: it materializes lazily-created
            # optimizer accumulators so the scan carry is structure-stable
            loss0, p, b_, o = pure(params, bufs, opt_state, keys[0],
                                   *batch)
            if n == 1:
                return loss0, p, b_, o

            def body(carry, k):
                p, b_, o = carry
                loss, p2, b2, o2 = pure(p, b_, o, k, *batch)
                return (p2, b2, o2), loss

            (p, b_, o), losses = jax.lax.scan(body, (p, b_, o), keys[1:])
            return losses[-1], p, b_, o

        jitted = jax.jit(many, donate_argnums=(
            (0, 2) if self._donate else ()))
        outer = self

        def run(*batch):
            params = {k: p._data for k, p in
                      outer.model.named_parameters()}
            bufs = {"buffers." + k: b._data
                    for k, b in outer.model.named_buffers()}
            opt_state = outer.optimizer.opt_state()
            key = _random.split_key()
            loss, new_params, new_bufs, new_opt = jitted(
                params, bufs, opt_state, key, *_as_arrays(batch))
            outer.model.load_functional_state({**new_params, **new_bufs})
            outer.optimizer.load_opt_state(new_opt)
            return Tensor(loss, stop_gradient=True)

        return run

    def __call__(self, *batch):
        """Run one compiled step; returns the loss Tensor."""
        if self._compiled is None:
            self._compiled = self._build()
        model, optimizer = self.model, self.optimizer
        params = {}
        bufs = {}
        for name, p in model.named_parameters():
            params[name] = p._data
        for name, b in model.named_buffers():
            bufs["buffers." + name] = b._data
        opt_state = optimizer.opt_state()
        key = _random.split_key()
        # batch items may be arbitrary pytrees (tuples/dicts from a
        # DataLoader); Tensors become raw arrays at the leaves
        arrays = _as_arrays(batch)
        loss, new_params, new_bufs, new_opt = self._compiled(
            params, bufs, opt_state, key, *arrays)
        # write results back into the live objects
        model.load_functional_state({**new_params, **new_bufs})
        optimizer.load_opt_state(new_opt)
        if optimizer._lr_scheduler is not None:
            pass  # user steps the scheduler per paddle convention
        return Tensor(loss, stop_gradient=True)


def _as_arrays(batch):
    return jax.tree.map(
        lambda b: b._data if isinstance(b, Tensor) else jnp.asarray(b),
        list(batch), is_leaf=lambda b: isinstance(b, Tensor))


def train_step(model, optimizer, loss_fn):
    """Build a compiled train step:

        step = paddle_tpu.jit.train_step(model, opt,
                    lambda m, x, y: F.cross_entropy(m(x), y))
        loss = step(x_batch, y_batch)
    """
    return TrainStep(model, optimizer, loss_fn)
