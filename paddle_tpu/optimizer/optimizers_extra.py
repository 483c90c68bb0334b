"""Remaining reference optimizers: Adamax, ASGD, NAdam, RAdam, Rprop, LBFGS.

Math matches the reference phi kernels:
  Adamax  paddle/phi/kernels/impl/adamax_kernel_impl.h:61-69
  NAdam   paddle/phi/kernels/impl/nadam_kernel_impl.h:77-108
  RAdam   paddle/phi/kernels/impl/radam_kernel_impl.h:76-117
  Rprop   paddle/phi/kernels/cpu/rprop_kernel.cc:69-101
  ASGD    paddle/phi/kernels/cpu/asgd_kernel.cc:25-48 (+ python ring buffer
          python/paddle/optimizer/asgd.py:240-320)
  LBFGS   python/paddle/optimizer/lbfgs.py (two-loop recursion + strong Wolfe)
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .optimizer import Optimizer

__all__ = ["Adamax", "ASGD", "NAdam", "RAdam", "Rprop", "LBFGS"]


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _update_param(self, p, g, lr):
        x = self._param_f32(p)
        if self._coeff:
            g = g + self._coeff * x
        m = self._acc(p, "moment")
        u = self._acc(p, "inf_norm")
        t = self._step_count + 1
        m = self._beta1 * m + (1 - self._beta1) * g
        # reference: inf_norm = max(|g|, beta2*inf_norm + eps)
        u = jnp.maximum(jnp.abs(g), self._beta2 * u + self._epsilon)
        self._set_acc(p, "moment", m)
        self._set_acc(p, "inf_norm", u)
        lr_t = lr / (1 - self._beta1 ** t)
        self._write_back(p, x - lr_t * m / u)


class ASGD(Optimizer):
    """Stochastic Average Gradient (the reference calls it ASGD): keeps the
    last gradient seen at each of ``batch_num`` ring slots and steps with the
    running sum d/min(step, n).

    Memory note: the ring buffer costs ``batch_num`` fp32 copies of EVERY
    parameter on device (mirroring the reference design,
    python/paddle/optimizer/asgd.py:240) — with large ``batch_num`` this
    dwarfs the params themselves; a warning is emitted past 64."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True,
                 name=None):
        if batch_num is not None and batch_num > 64:
            import warnings
            warnings.warn(
                f"ASGD allocates batch_num={batch_num} fp32 copies of "
                "every parameter for its gradient ring buffer "
                f"(~{batch_num}x param memory)")
        if batch_num is None or batch_num <= 0:
            raise ValueError("batch_num should be greater than 0")
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._n = int(batch_num)

    def _update_param(self, p, g, lr):
        x = self._param_f32(p)
        if self._coeff:
            g = g + self._coeff * x
        d = self._acc(p, "d")
        ys = self._acc(p, "y",
                       jnp.zeros((self._n,) + tuple(p._data.shape),
                                 jnp.float32))
        idx = self._step_count % self._n
        d = d - ys[idx] + g
        ys = ys.at[idx].set(g)
        self._set_acc(p, "d", d)
        self._set_acc(p, "y", ys)
        n_eff = jnp.minimum(self._step_count + 1, self._n)
        self._write_back(p, x - (lr / n_eff) * d)


class NAdam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._psi = momentum_decay

    def _update_param(self, p, g, lr):
        x = self._param_f32(p)
        if self._coeff:
            g = g + self._coeff * x
        m = self._acc(p, "moment1")
        v = self._acc(p, "moment2")
        # mu_product carried per-param so each param's schedule is exact
        mu_prod = self._acc(p, "mu_product", jnp.ones((), jnp.float32))
        t = self._step_count + 1
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._psi))
        mu_t1 = self._beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        mu_prod = mu_prod * mu_t
        mu_prod_t1 = mu_prod * mu_t1
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * jnp.square(g)
        self._set_acc(p, "moment1", m)
        self._set_acc(p, "moment2", v)
        self._set_acc(p, "mu_product", mu_prod)
        m_hat = (mu_t1 * m / (1 - mu_prod_t1)
                 + (1 - mu_t) * g / (1 - mu_prod))
        v_hat = v / (1 - self._beta2 ** t)
        self._write_back(p, x - lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon))


class RAdam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _update_param(self, p, g, lr):
        x = self._param_f32(p)
        if self._coeff:
            g = g + self._coeff * x
        m = self._acc(p, "moment1")
        v = self._acc(p, "moment2")
        t = self._step_count + 1
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * jnp.square(g)
        self._set_acc(p, "moment1", m)
        self._set_acc(p, "moment2", v)
        rho_inf = 2.0 / (1.0 - self._beta2) - 1.0
        beta2_t = self._beta2 ** t
        rho_t = rho_inf - 2.0 * t * beta2_t / (1.0 - beta2_t)
        m_hat = m / (1 - self._beta1 ** t)
        # rectified update (reference radam_kernel_impl.h:100); jnp.where so
        # the step count may be a traced value under the jitted train step
        l_t = jnp.sqrt(1.0 - beta2_t) / (jnp.sqrt(v) + self._epsilon)
        safe_rho = jnp.maximum(rho_t, 5.0 + 1e-6)
        r_t = jnp.sqrt((safe_rho - 4) * (safe_rho - 2) * rho_inf /
                       ((rho_inf - 4) * (rho_inf - 2) * safe_rho))
        self._write_back(p, x - jnp.where(rho_t > 5.0,
                                          lr * m_hat * r_t * l_t,
                                          lr * m_hat))


class Rprop(Optimizer):
    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas

    def _update_param(self, p, g, lr):
        x = self._param_f32(p)
        prev = self._acc(p, "prev")
        lrs = self._acc(p, "learning_rate",
                        jnp.full(p._data.shape, lr, jnp.float32))
        sign = g * prev
        eta = jnp.where(sign > 0, self._eta_pos,
                        jnp.where(sign < 0, self._eta_neg, 1.0))
        g = jnp.where(sign < 0, 0.0, g)  # reference zeroes grad on sign flip
        lrs = jnp.clip(lrs * eta, self._lr_min, self._lr_max)
        self._set_acc(p, "prev", g)
        self._set_acc(p, "learning_rate", lrs)
        self._write_back(p, x - jnp.sign(g) * lrs)


class LBFGS(Optimizer):
    """Limited-memory BFGS with optional strong-Wolfe line search.

    Reference python/paddle/optimizer/lbfgs.py: single-tensor flattened
    history, two-loop recursion, ``step(closure)`` API where closure
    re-evaluates the loss (and grads) at trial points.
    """

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-07, tolerance_change=1e-09,
                 history_size=100, line_search_fn=None, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        if max_eval is None:
            max_eval = max_iter * 5 // 4
        self._max_iter = max_iter
        self._max_eval = max_eval
        self._tol_grad = tolerance_grad
        self._tol_change = tolerance_change
        self._history_size = history_size
        if line_search_fn not in (None, "strong_wolfe"):
            raise ValueError("only 'strong_wolfe' is supported")
        if grad_clip is not None:
            raise ValueError(
                "LBFGS does not support grad_clip: the line search needs raw "
                "closure gradients (reference lbfgs.py has no clip path)")
        self._line_search_fn = line_search_fn
        self._state = {"old_sks": [], "old_yks": [], "ro": [],
                       "H_diag": 1.0, "prev_flat_grad": None, "d": None,
                       "t": None, "n_iter": 0, "func_evals": 0}

    def state_dict(self):
        state = super().state_dict()
        st = self._state
        state["@lbfgs"] = {
            "old_sks": [np.asarray(a) for a in st["old_sks"]],
            "old_yks": [np.asarray(a) for a in st["old_yks"]],
            "ro": list(st["ro"]),
            "H_diag": st["H_diag"],
            "prev_flat_grad": None if st["prev_flat_grad"] is None
            else np.asarray(st["prev_flat_grad"]),
            "d": None if st["d"] is None else np.asarray(st["d"]),
            "t": st["t"], "n_iter": st["n_iter"],
            "func_evals": st["func_evals"]}
        return state

    def set_state_dict(self, state):
        state = dict(state)
        lb = state.pop("@lbfgs", None)
        super().set_state_dict(state)
        if lb is not None:
            self._state = {
                "old_sks": [jnp.asarray(a) for a in lb["old_sks"]],
                "old_yks": [jnp.asarray(a) for a in lb["old_yks"]],
                "ro": list(lb["ro"]),
                "H_diag": lb["H_diag"],
                "prev_flat_grad": None if lb["prev_flat_grad"] is None
                else jnp.asarray(lb["prev_flat_grad"]),
                "d": None if lb["d"] is None else jnp.asarray(lb["d"]),
                "t": lb["t"], "n_iter": lb["n_iter"],
                "func_evals": lb["func_evals"]}

    # ---- flat views over the parameter list
    def _gather_flat_grad(self):
        flat = []
        for p in self._parameter_list:
            g = p._grad
            if g is None:
                g = jnp.zeros(p._data.shape, p._data.dtype)
            elif hasattr(g, "_data"):
                g = g._data
            g = jnp.reshape(g, (-1,)).astype(jnp.float32)
            if self._coeff:  # L2 regularization folded into the grad
                g = g + self._coeff * jnp.reshape(
                    p._data, (-1,)).astype(jnp.float32)
            flat.append(g)
        return jnp.concatenate(flat)

    def _add_delta(self, step_size, direction):
        off = 0
        for p in self._parameter_list:
            n = int(np.prod(p._data.shape)) if p._data.shape else 1
            upd = direction[off:off + n].reshape(p._data.shape)
            p._data = (p._data.astype(jnp.float32)
                       + step_size * upd).astype(p._data.dtype)
            off += n

    def _clone_params(self):
        return [p._data for p in self._parameter_list]

    def _restore_params(self, snapshot):
        for p, d in zip(self._parameter_list, snapshot):
            p._data = d

    def step(self, closure):
        closure_fn = closure
        loss = float(closure_fn())
        self._state["func_evals"] += 1
        flat_grad = self._gather_flat_grad()
        if float(jnp.max(jnp.abs(flat_grad))) <= self._tol_grad:
            return loss

        st = self._state
        lr = self.get_lr()
        current_evals = 1
        n_iter = 0
        while n_iter < self._max_iter:
            n_iter += 1
            st["n_iter"] += 1
            # --- direction via two-loop recursion
            if st["n_iter"] == 1:
                d = -flat_grad
                st["old_sks"], st["old_yks"], st["ro"] = [], [], []
                st["H_diag"] = 1.0
            else:
                y = flat_grad - st["prev_flat_grad"]
                s = st["d"] * st["t"]
                ys = float(jnp.dot(y, s))
                if ys > 1e-10:
                    if len(st["old_sks"]) == self._history_size:
                        st["old_sks"].pop(0)
                        st["old_yks"].pop(0)
                        st["ro"].pop(0)
                    st["old_sks"].append(s)
                    st["old_yks"].append(y)
                    st["ro"].append(1.0 / ys)
                    st["H_diag"] = ys / float(jnp.dot(y, y))
                q = -flat_grad
                alphas = []
                for s_i, y_i, ro_i in zip(reversed(st["old_sks"]),
                                          reversed(st["old_yks"]),
                                          reversed(st["ro"])):
                    alpha = ro_i * float(jnp.dot(s_i, q))
                    alphas.append(alpha)
                    q = q - alpha * y_i
                d = q * st["H_diag"]
                for (s_i, y_i, ro_i), alpha in zip(
                        zip(st["old_sks"], st["old_yks"], st["ro"]),
                        reversed(alphas)):
                    beta = ro_i * float(jnp.dot(y_i, d))
                    d = d + s_i * (alpha - beta)
            st["prev_flat_grad"] = flat_grad
            prev_loss = loss

            # --- step size
            if st["n_iter"] == 1:
                t = min(1.0, 1.0 / float(jnp.sum(jnp.abs(flat_grad)))) * lr
            else:
                t = lr
            gtd = float(jnp.dot(flat_grad, d))
            if gtd > -self._tol_change:
                break

            if self._line_search_fn == "strong_wolfe":
                snapshot = self._clone_params()

                def obj(alpha):
                    self._restore_params(snapshot)
                    self._add_delta(alpha, d)
                    l = float(closure_fn())
                    g = self._gather_flat_grad()
                    return l, g

                loss, flat_grad, t, ls_evals = _strong_wolfe(
                    obj, t, d, loss, flat_grad, gtd)
                self._restore_params(snapshot)
                self._add_delta(t, d)
                current_evals += ls_evals
                st["func_evals"] += ls_evals
            else:
                self._add_delta(t, d)
                if n_iter != self._max_iter:
                    loss = float(closure_fn())
                    flat_grad = self._gather_flat_grad()
                    current_evals += 1
                    st["func_evals"] += 1
            st["d"], st["t"] = d, t

            if current_evals >= self._max_eval:
                break
            if float(jnp.max(jnp.abs(flat_grad))) <= self._tol_grad:
                break
            if float(jnp.max(jnp.abs(d * t))) <= self._tol_change:
                break
            if abs(loss - prev_loss) < self._tol_change:
                break
        self._step_count += 1
        return loss


def _hermite_min(a, fa, sa, b, fb, sb, lo, hi):
    """Minimizer of the cubic Hermite interpolant through (a, fa, sa) and
    (b, fb, sb), clamped to [lo, hi].

    Derivation: parametrize tau in [0, 1] over the (a, b) span h = b - a,
    p(tau) = c0 + c1*tau + c2*tau^2 + c3*tau^3 with
      c0 = fa, c1 = h*sa,
      c2 = 3*(fb - fa) - h*(2*sa + sb),
      c3 = h*(sa + sb) - 2*(fb - fa),
    and take the p'(tau) = 0 root with p'' > 0; bisect when the
    interpolant has no interior minimum."""
    h = b - a
    if h == 0.0:
        return max(lo, min(hi, a))
    df = fb - fa
    c1 = h * sa
    c2 = 3.0 * df - h * (2.0 * sa + sb)
    c3 = h * (sa + sb) - 2.0 * df
    cand = None
    if abs(c3) > 1e-20:
        disc = c2 * c2 - 3.0 * c3 * c1
        if disc >= 0.0:
            # root with positive curvature: p'' = 2 c2 + 6 c3 tau > 0
            r = disc ** 0.5
            tau = (-c2 + r) / (3.0 * c3)
            if 2.0 * c2 + 6.0 * c3 * tau < 0.0:
                tau = (-c2 - r) / (3.0 * c3)
            cand = a + tau * h
    elif abs(c2) > 1e-20 and c2 > 0.0:
        cand = a + (-c1 / (2.0 * c2)) * h
    if cand is None or not (lo <= cand <= hi):
        cand = 0.5 * (lo + hi)
    return cand


def _strong_wolfe(obj_func, t, d, f, g, gtd, c1=1e-4, c2=0.9,
                  tolerance_change=1e-9, max_ls=25):
    """Strong-Wolfe line search along direction d.

    Two phases (Nocedal & Wright, Alg. 3.5/3.6 shape): an expansion walk
    that either accepts the trial, brackets a minimum, or grows the step;
    then a zoom on the bracket using the Hermite-cubic candidate with a
    central-interval safeguard.  obj_func(step) -> (value, flat_grad).
    Returns (value, flat_grad, step, n_evals)."""
    scale = float(jnp.max(jnp.abs(d)))

    def probe(step):
        val, grad = obj_func(step)
        return val, grad, float(jnp.dot(grad, d))

    def armijo_ok(step, val):
        return val <= f + c1 * step * gtd

    def curvature_ok(slope):
        return abs(slope) <= -c2 * gtd

    evals = 0
    prev = (0.0, f, jnp.asarray(g), gtd)   # (step, value, grad, slope)
    cur_v, cur_g, cur_s = probe(t)
    evals += 1
    cur = (t, cur_v, cur_g, cur_s)

    span = None
    for k in range(max_ls):
        st, v, gr, sl = cur
        if not armijo_ok(st, v) or (k > 0 and v >= prev[1]):
            span = (prev, cur)        # overshot: minimum is inside
            break
        if curvature_ok(sl):
            return v, gr, st, evals   # Wolfe pair satisfied outright
        if sl >= 0.0:
            span = (cur, prev)        # slope flipped: bracketed
            break
        # still descending: extrapolate beyond the current step
        grow = _hermite_min(prev[0], prev[1], prev[3], st, v, sl,
                            st + 0.1 * (st - prev[0]), 4.0 * st)
        prev = cur
        nv, ng, ns = probe(grow)
        evals += 1
        cur = (grow, nv, ng, ns)
    if span is None:
        # expansion exhausted: fall back to the best endpoint seen
        span = ((0.0, f, jnp.asarray(g), gtd), cur)

    lo, hi = span if span[0][1] <= span[1][1] else (span[1], span[0])
    while evals < max_ls and not curvature_ok(lo[3]):
        width = abs(hi[0] - lo[0])
        if width * scale < tolerance_change:
            break
        a, b = (lo, hi) if lo[0] < hi[0] else (hi, lo)
        cand = _hermite_min(a[0], a[1], a[3], b[0], b[1], b[3],
                            a[0], b[0])
        # keep the trial inside the central 80% of the bracket so the
        # interval provably shrinks (bisect otherwise)
        margin = 0.1 * width
        if not (a[0] + margin <= cand <= b[0] - margin):
            cand = 0.5 * (a[0] + b[0])
        nv, ng, ns = probe(cand)
        evals += 1
        trial = (cand, nv, ng, ns)
        if not armijo_ok(cand, nv) or nv >= lo[1]:
            hi = trial                # sufficient-decrease side shrinks
        else:
            if curvature_ok(ns):
                lo = trial
                break
            if ns * (hi[0] - lo[0]) >= 0.0:
                hi = lo               # minimum is on the other side
            lo = trial
    return lo[1], lo[2], lo[0], evals
