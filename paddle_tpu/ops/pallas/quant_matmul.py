"""Pallas weight-only quantized matmul for the memory-bound decode path.

Reference analog: paddle/phi/kernels/funcs/weight_only_gemv.cu +
weight_only_linear_kernel.h — the fused int8/int4-weight x half-activation
GEMV that wins decode by halving (int8) or quartering (int4) weight HBM
traffic, with dequantization fused into the matmul prologue.

TPU formulation: one `pallas_call` per matmul, grid over output-column
blocks.  Each program DMAs an int8 weight tile [K, bn] from HBM into
VMEM (this is the only HBM traffic that matters at decode's M<=8 row
counts), upconverts in-register, runs the MXU dot at bf16, and applies
the per-output-channel scale to the f32 accumulator before writing the
bf16 result.  int4 weights are stored nibble-packed [K/2, N] (row 2k in
the low nibble, row 2k+1 in the high nibble — the reference packs along
K the same way); the kernel splits the activation rows even/odd and
issues two half-K dots against the unpacked nibble planes, so no
interleave materializes.

The XLA fallback (`lax.dot_general` on the int8 weight + scale on the
result) is used off-TPU and for prefill-shaped calls (large M), where
the matmul is MXU-bound and streaming tricks buy nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["QuantizedWeight", "pack_int4", "unpack_int4",
           "weight_only_matmul"]

_INTERPRET = False
# decode-shaped calls (M rows at most this) take the Pallas kernel;
# larger M is compute-bound and runs the XLA dequant-into-matmul path
_GEMV_MAX_ROWS = 64


@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """A weight-only-quantized matmul weight: int8 values (nibble-packed
    for int4) + per-output-channel f32 scale.  Registered as a pytree so
    it flows through jit/scan state like the dense weight it replaces."""

    def __init__(self, q, scale, kind="int8", k=None):
        self.q = q
        self.scale = scale
        self.kind = kind                      # "int8" | "int4"
        self.k = int(k if k is not None else q.shape[0])   # logical K

    def tree_flatten(self):
        return (self.q, self.scale), (self.kind, self.k)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, kind=aux[0], k=aux[1])

    @property
    def shape(self):
        return (self.k, self.q.shape[1])

    def dequantize(self, dtype=jnp.bfloat16):
        q = unpack_int4(self.q, self.k) if self.kind == "int4" else self.q
        return (q.astype(jnp.float32) * self.scale.astype(
            jnp.float32)).astype(dtype)


def pack_int4(q):
    """[K, N] int8 values in [-8, 7] -> [K/2, N] int8, row 2k in the low
    nibble and row 2k+1 in the high nibble (reference weight_quantize's
    int4 layout packs along K)."""
    k = q.shape[0]
    if k % 2:
        raise ValueError(f"int4 packing needs even K, got {k}")
    lo = q[0::2].astype(jnp.uint8) & 0xF
    hi = (q[1::2].astype(jnp.uint8) & 0xF) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_int4(packed, k=None):
    """Inverse of :func:`pack_int4` -> [K, N] int8 (sign-extended)."""
    u = packed.astype(jnp.uint8)
    lo = _sext4(u & 0xF)
    hi = _sext4(u >> 4)
    out = jnp.stack([lo, hi], axis=1).reshape(-1, packed.shape[1])
    return out if k is None else out[:k]


def _sext4(nib):
    """uint8 nibble -> sign-extended int8."""
    nib = nib.astype(jnp.int8)
    return jnp.where(nib >= 8, nib - 16, nib)


# ------------------------------------------------------------ int8 kernel
def _int8_kernel(x_ref, q_ref, s_ref, o_ref):
    w = q_ref[...].astype(jnp.bfloat16)            # int8 -> bf16 in VMEM
    acc = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


# ------------------------------------------------------------ int4 kernel
def _int4_kernel(xe_ref, xo_ref, p_ref, s_ref, o_ref):
    """Packed tile p [K/2, bn]: low nibble = even K rows, high = odd.

    The unpack widens the byte to i32 FIRST and does the bit ops there:
    i32 shifts/masks are native VPU lanes, while i8 shift formulations
    lower through multi-pass emulation (measured 45 us vs 8.9 us per
    2048x5632 matmul — the difference between the int4 kernel beating
    the int8 one and losing to dense bf16)."""
    w = p_ref[...].astype(jnp.int32)
    hi = (w >> 4).astype(jnp.bfloat16)            # arithmetic: already sext
    lo = (((w & 15) ^ 8) - 8).astype(jnp.bfloat16)   # sext of low nibble
    dims = (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(xe_ref[...], lo, dims,
                              preferred_element_type=jnp.float32)
    acc += jax.lax.dot_general(xo_ref[...], hi, dims,
                               preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def _block_n(n, cap=2048):
    """Largest multiple of 128 that divides n, capped (tile VMEM)."""
    best = 0
    for m in range(128, cap + 1, 128):
        if n % m == 0:
            best = m
    return best


def _block_n_int4(n, kh):
    """int4 tile cap: the in-kernel i32 widen MATERIALIZES 4*kh*bn bytes
    of scoped VMEM (the int8 kernel's bf16 convert fuses into the dot
    and never does), so bn is budgeted to keep that under ~8 MB of the
    16 MB scoped limit."""
    cap = max(128, (8 * 2**20 // (4 * kh)) // 128 * 128)
    return _block_n(n, cap)


def _pallas_int8(x, q, scale, bn):
    from jax.experimental import pallas as pl

    m, k = x.shape
    n = q.shape[1]
    s2 = scale.reshape(1, n).astype(jnp.float32)
    with jax.enable_x64(False):
        return pl.pallas_call(
            _int8_kernel,
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((m, k), lambda i: (0, 0)),
                      pl.BlockSpec((k, bn), lambda i: (0, i)),
                      pl.BlockSpec((1, bn), lambda i: (0, i))],
            out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            interpret=_INTERPRET,
            name="quant_matmul_int8",
        )(x, q, s2)


def _pallas_int4(x, packed, scale, k, bn):
    from jax.experimental import pallas as pl

    m = x.shape[0]
    n = packed.shape[1]
    xe = x[:, 0::2]                                 # [M, K/2] even rows
    xo = x[:, 1::2]
    s2 = scale.reshape(1, n).astype(jnp.float32)
    kh = k // 2
    with jax.enable_x64(False):
        return pl.pallas_call(
            _int4_kernel,
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((m, kh), lambda i: (0, 0)),
                      pl.BlockSpec((m, kh), lambda i: (0, 0)),
                      pl.BlockSpec((kh, bn), lambda i: (0, i)),
                      pl.BlockSpec((1, bn), lambda i: (0, i))],
            out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            interpret=_INTERPRET,
            name="quant_matmul_int4",
        )(xe, xo, packed, s2)


def _xla_fallback(x, w: QuantizedWeight):
    if w.kind == "int4":
        q = unpack_int4(w.q, w.k)
    else:
        q = w.q
    out = jax.lax.dot_general(
        x, q, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (out * w.scale.astype(jnp.float32)).astype(x.dtype)


def weight_only_matmul(x, w: QuantizedWeight):
    """x [..., K] @ dequant(w) -> [..., N] — Pallas GEMV kernel at
    decode shapes on TPU, XLA dequant-matmul otherwise."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    if k != w.shape[0]:
        raise ValueError(f"matmul K mismatch: x has {k}, weight "
                         f"{w.shape[0]}")
    n = w.shape[1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bn = _block_n_int4(n, k // 2) if w.kind == "int4" else _block_n(n)
    use_pallas = (
        (bn > 0)
        and m <= _GEMV_MAX_ROWS
        and (w.kind == "int8" or k % 2 == 0)
        and (_INTERPRET or jax.default_backend() not in ("cpu",)))
    if use_pallas:      # a kernel failure raises: no XLA rescue
        if w.kind == "int4":
            out = _pallas_int4(x2, w.q, w.scale, k, bn)
        else:
            out = _pallas_int8(x2, w.q, w.scale, bn)
        return out.reshape(*lead, n)
    return _xla_fallback(x2, w).reshape(*lead, n)
