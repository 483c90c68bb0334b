"""Dropless grouped expert FFN (megablocks-style) Pallas kernel.

Reference: the fused/grouped expert GEMM the reference serves MoE with
(python/paddle/incubate/nn/functional/fused_moe.py:1, CUTLASS grouped
GEMM under paddle/phi/kernels/fusion/cutlass) — no capacity factor, no
dropped tokens.

TPU formulation: tokens are counting-sorted by expert into a
TILE-ALIGNED buffer (each expert's rows padded up to the 128-row tile,
so every row tile belongs to exactly ONE expert).  One kernel computes
``silu(x_t @ w1[e]) @ w2[e]`` per row tile with the expert chosen by a
scalar-prefetched tile->expert map — both GEMMs fused, the [tile, F]
intermediate never touches HBM.  The backward kernel recomputes the
intermediate and accumulates dw1/dw2/db into expert blocks
(same-expert tiles are CONTIGUOUS in the sorted order, so the
revisit-accumulation pattern is safe on the sequential TPU grid).

Padding waste is <= E*(tile-1) rows (~6% at the bench shape) versus
the capacity formulation's 25% — and zero drops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_INTERPRET = False
TILE = 128


def _silu_grad_parts(s):
    sig = jax.nn.sigmoid(s)
    return s * sig, sig * (1.0 + s * (1.0 - sig))


def _fwd_kernel(emap_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref,
                *, gated):
    x = x_ref[...]
    h = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    h = h + b1_ref[...].astype(jnp.float32)
    if gated:
        half = h.shape[-1] // 2
        h = jax.nn.silu(h[:, :half]) * h[:, half:]
    else:
        h = jax.nn.silu(h)
    out = jnp.dot(h.astype(x.dtype), w2_ref[...],
                  preferred_element_type=jnp.float32)
    o_ref[...] = (out + b2_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)


def _bwd_kernel(emap_ref, x_ref, dy_ref, w1_ref, b1_ref, w2_ref,
                dx_ref, dw1_ref, dw2_ref, db1_ref, db2_ref, *, gated):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    x = x_ref[...]
    dyf = dy_ref[...].astype(jnp.float32)
    dy = dy_ref[...]
    s = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32) \
        + b1_ref[...].astype(jnp.float32)
    dh = jnp.dot(dy, w2_ref[...].swapaxes(-1, -2),
                 preferred_element_type=jnp.float32)
    if gated:
        half = s.shape[-1] // 2
        u, g = s[:, :half], s[:, half:]
        su, du = _silu_grad_parts(u)
        h = su * g
        ds = jnp.concatenate([dh * g * du, dh * su], axis=-1)
    else:
        h, du = _silu_grad_parts(s)
        ds = dh * du

    dsx = ds.astype(x.dtype)
    dx_ref[...] = jnp.dot(dsx, w1_ref[...].swapaxes(-1, -2),
                          preferred_element_type=jnp.float32) \
        .astype(dx_ref.dtype)

    # expert-block accumulation: zero at each expert's first tile
    # (same-expert tiles are contiguous in the sorted order)
    @pl.when(jnp.logical_or(i == 0, emap_ref[i] != emap_ref[i - 1]))
    def _init():
        dw1_ref[...] = jnp.zeros_like(dw1_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)
        db1_ref[...] = jnp.zeros_like(db1_ref)
        db2_ref[...] = jnp.zeros_like(db2_ref)

    dw1_ref[...] += jnp.dot(x.swapaxes(-1, -2), dsx,
                            preferred_element_type=jnp.float32)
    dw2_ref[...] += jnp.dot(h.astype(x.dtype).swapaxes(-1, -2), dy,
                            preferred_element_type=jnp.float32)
    db1_ref[...] += jnp.sum(ds, axis=0)
    db2_ref[...] += jnp.sum(dyf, axis=0)


def _call_fwd(x_buf, w1, b1, w2, b2, emap, gated):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, d = x_buf.shape
    f2 = w1.shape[2]
    fin, dout = w2.shape[1], w2.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r // TILE,),
        in_specs=[
            pl.BlockSpec((TILE, d), lambda i, emap: (i, 0)),
            pl.BlockSpec((None, d, f2), lambda i, emap: (emap[i], 0, 0)),
            pl.BlockSpec((None, f2), lambda i, emap: (emap[i], 0)),
            pl.BlockSpec((None, fin, dout),
                         lambda i, emap: (emap[i], 0, 0)),
            pl.BlockSpec((None, dout), lambda i, emap: (emap[i], 0)),
        ],
        out_specs=pl.BlockSpec((TILE, dout), lambda i, emap: (i, 0)),
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, gated=gated),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((r, dout), x_buf.dtype),
            interpret=_INTERPRET,
            name="grouped_ffn_fwd",
        )(emap.astype(jnp.int32), x_buf, w1, b1, w2, b2)


def _call_bwd(x_buf, dy, w1, b1, w2, emap, gated):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, d = x_buf.shape
    e, _, f2 = w1.shape
    fin, dout = w2.shape[1], w2.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r // TILE,),
        in_specs=[
            pl.BlockSpec((TILE, d), lambda i, emap: (i, 0)),
            pl.BlockSpec((TILE, dout), lambda i, emap: (i, 0)),
            pl.BlockSpec((None, d, f2), lambda i, emap: (emap[i], 0, 0)),
            pl.BlockSpec((None, f2), lambda i, emap: (emap[i], 0)),
            pl.BlockSpec((None, fin, dout),
                         lambda i, emap: (emap[i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE, d), lambda i, emap: (i, 0)),
            pl.BlockSpec((None, d, f2), lambda i, emap: (emap[i], 0, 0)),
            pl.BlockSpec((None, fin, dout),
                         lambda i, emap: (emap[i], 0, 0)),
            pl.BlockSpec((None, f2), lambda i, emap: (emap[i], 0)),
            pl.BlockSpec((None, dout), lambda i, emap: (emap[i], 0)),
        ],
    )
    f32 = jnp.float32
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, gated=gated),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((r, d), x_buf.dtype),
                jax.ShapeDtypeStruct((e, d, f2), f32),
                jax.ShapeDtypeStruct((e, fin, dout), f32),
                jax.ShapeDtypeStruct((e, f2), f32),
                jax.ShapeDtypeStruct((e, dout), f32),
            ],
            interpret=_INTERPRET,
            name="grouped_ffn_bwd",
        )(emap.astype(jnp.int32), x_buf, dy, w1, b1, w2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def grouped_ffn(x_buf, w1, b1, w2, b2, emap, gated=False):
    """Tile-aligned grouped expert FFN: x_buf [R, D] (R % 128 == 0, the
    rows of row-tile t belong to expert emap[t]), w1 [E, D, F(*2)],
    b1 [E, F(*2)], w2 [E, F, D'], b2 [E, D'].  gated=True treats w1's
    output as [up | gate] halves (swiglu).  Returns [R, D']."""
    return _call_fwd(x_buf, w1, b1, w2, b2, emap, gated)


def _gffn_fwd(x_buf, w1, b1, w2, b2, emap, gated):
    out = _call_fwd(x_buf, w1, b1, w2, b2, emap, gated)
    # zero-width dtype carrier: residuals must be jax types
    return out, (x_buf, w1, b1, w2, jnp.zeros((0,), b2.dtype), emap)


def _gffn_bwd(gated, res, dy):
    x_buf, w1, b1, w2, b2_ref, emap = res
    dx, dw1, dw2, db1, db2 = _call_bwd(x_buf, dy, w1, b1, w2, emap,
                                       gated)
    # experts with zero tiles never ran: their accumulator blocks are
    # uninitialized memory — zero them by visited mask.  Cotangent
    # dtypes must match each PRIMAL's dtype (biases may be f32 while
    # weights are bf16).
    e = w1.shape[0]
    visited = jnp.zeros((e,), bool).at[emap].set(True)
    dw1 = jnp.where(visited[:, None, None], dw1, 0).astype(w1.dtype)
    dw2 = jnp.where(visited[:, None, None], dw2, 0).astype(w2.dtype)
    db1 = jnp.where(visited[:, None], db1, 0).astype(b1.dtype)
    db2 = jnp.where(visited[:, None], db2, 0).astype(b2_ref.dtype)
    return dx, dw1, db1, dw2, db2, None


grouped_ffn.defvjp(_gffn_fwd, _gffn_bwd)


def grouped_ffn_xla(x_buf, w1, b1, w2, b2, emap, gated=False):
    """Dense-gather XLA reference (identical numerics): materializes
    per-tile expert weights — parity tests and the off-TPU fallback."""
    r, d = x_buf.shape
    nt = r // TILE
    xt = x_buf.reshape(nt, TILE, d)
    h = jnp.einsum("tbd,tdf->tbf", xt, w1[emap],
                   preferred_element_type=jnp.float32)
    h = h + b1[emap][:, None, :].astype(jnp.float32)
    if gated:
        half = h.shape[-1] // 2
        h = jax.nn.silu(h[..., :half]) * h[..., half:]
    else:
        h = jax.nn.silu(h)
    out = jnp.einsum("tbf,tfd->tbd", h.astype(x_buf.dtype), w2[emap],
                     preferred_element_type=jnp.float32)
    out = out + b2[emap][:, None, :].astype(jnp.float32)
    return out.reshape(r, w2.shape[2]).astype(x_buf.dtype)
