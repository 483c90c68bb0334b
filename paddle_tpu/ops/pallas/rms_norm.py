"""Fused RMSNorm Pallas kernel.

Reference: paddle fused_rms_norm (paddle/phi/kernels/fusion/gpu, python
incubate/nn/functional/fused_rms_norm.py).  One pass over HBM: read x, write
normalized output; stats in fp32 on-chip.  Falls back to the XLA body on CPU
(XLA fuses it well there anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


# Scoped VMEM one kernel instance may use is 16 MB on the chip.  Each
# element of a (block, d) tile costs the double-buffered input and output
# blocks (4 x itemsize) plus one f32 working copy: the compiler counted
# 24.21 MB for 512 rows x 4096 in bf16, 11.5 bytes per element against the
# 12 this predicts.  Budget 14 MB: the weight row and compiler scratch
# take the rest.
_VMEM_BUDGET = 14 * 1024 * 1024


def _row_block(n, d, itemsize):
    """Largest power-of-two row block <= 512 that divides ``n`` and keeps
    a (block, d) tile inside the VMEM budget, so the block shrinks as the
    width grows (bf16: 512 rows up to 2048 wide, 256 at 4096, 128 at
    8192) where it used to be 512 whatever the width."""
    cap = _VMEM_BUDGET // ((4 * itemsize + 4) * d)
    block = 512
    while block > 1 and (block > cap or n % block):
        block //= 2
    return block


@functools.partial(jax.jit, static_argnames=("eps",))
def _pallas_rms(x2d, w, eps):
    from jax.experimental import pallas as pl

    n, d = x2d.shape
    block = _row_block(n, d, x2d.dtype.itemsize)
    with jax.enable_x64(False):   # see flash_attention._flash_fwd
        return pl.pallas_call(
            functools.partial(_rms_kernel, eps=eps),
            grid=(n // block,),
            in_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                      pl.BlockSpec((1, d), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
            name="rms_norm",
        )(x2d, w.reshape(1, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, eps=1e-6):
    """[..., d] fused rmsnorm; weight [d].  Differentiable: the forward
    runs the Pallas kernel on TPU, the backward is the closed-form
    jnp vjp (XLA fuses it into one pass)."""
    return _rms_fwd_impl(x, weight, eps)


def _rms_fwd_impl(x, weight, eps):
    if jax.default_backend() == "cpu" or x.shape[-1] % 128:
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return ((xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight)
    shape = x.shape
    out = _pallas_rms(x.reshape(-1, shape[-1]), weight, eps)
    return out.reshape(shape)


def _rms_vjp_fwd(x, weight, eps):
    return _rms_fwd_impl(x, weight, eps), (x, weight)


def _rms_vjp_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    d = xf.shape[-1]
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1,
                               keepdims=True) + eps)
    gw = gf * wf                                       # [..., d]
    dx = (gw * r - xf * (jnp.sum(gw * xf, axis=-1, keepdims=True)
                         * (r ** 3) / d)).astype(x.dtype)
    dw = jnp.sum((xf * r * gf).reshape(-1, d), axis=0).astype(w.dtype)
    return dx, dw


rms_norm.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)
