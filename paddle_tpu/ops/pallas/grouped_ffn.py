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

Serving (``grouped_matmul``): the fused kernel above takes one expert's
whole ``[D, 2F]`` block a grid step, which at expert widths of
7168 x 2048 (58.7 MB in bf16) no VMEM double-buffers.  The serving path
runs each of an expert's three matrix products as one grouped matmul
tiled over the contraction and the output columns (``expert_blocks``),
over the same tile-aligned sorted rows.  The row tile is small at decode
(``tile_m`` 16: an expert sees 0-8 rows a step) and the kernel is told
how many row tiles are live: a tile past them computes nothing and asks
for no new block, so an expert that no row chose is never read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_INTERPRET = False
TILE = 128
# bytes of one [tk, tn] weight block of grouped_matmul: two of them are in
# flight, and a block this large takes ~9 us to arrive at 819 GB/s, thirty
# times a grid step's fixed cost
BLOCK_BYTES = 8 << 20


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


# ------------------------------------------------ serving: tiled grouped matmul
def expert_blocks(k: int, n: int, itemsize: int) -> tuple:
    """(tk, tn) of one weight block for a [k, n] expert matrix: the
    largest divisors in whole 128-lane tiles (or the whole dimension)
    whose block stays within ``BLOCK_BYTES``; of equal blocks the one
    with the longer rows (they are what is contiguous in HBM)."""
    def divisors(d):
        out = [d]
        if d % 128 == 0:
            out += [t for t in range(d - 128, 0, -128) if d % t == 0]
        return out

    fits = [(tk * tn, tn, tk) for tn in divisors(n) for tk in divisors(k)
            if tk * tn * itemsize <= BLOCK_BYTES]
    if not fits:
        return divisors(k)[-1], divisors(n)[-1]
    _, tn, tk = max(fits)
    return tk, tn


def _gmm_kernel(emap_ref, live_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    from jax.experimental import pallas as pl

    k = pl.program_id(2)

    @pl.when(pl.program_id(0) < live_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x_buf, w, emap, n_live, *, tile_m: int):
    """x_buf [R, K] (R % tile_m == 0; the rows of row tile t belong to
    expert emap[t]), w [E, K, N], ``n_live`` the number of leading row
    tiles that hold rows.  Returns [R, N]; rows of tiles past ``n_live``
    are not written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, k = x_buf.shape
    n = w.shape[2]
    tk, tn = expert_blocks(k, n, jnp.dtype(w.dtype).itemsize)
    nk, nn = k // tk, n // tn

    # a tile past the live ones keeps every index of the last live
    # step, so the pipeline has nothing new to fetch or write back
    def frozen(i, j, kk, live):
        on = i < live[0]
        last = jnp.maximum(live[0] - 1, 0)
        return (jnp.where(on, i, last), jnp.where(on, j, nn - 1),
                jnp.where(on, kk, nk - 1))

    def x_map(i, j, kk, emap, live):
        ii, _, k_ = frozen(i, j, kk, live)
        return ii, k_

    def w_map(i, j, kk, emap, live):
        ii, j_, k_ = frozen(i, j, kk, live)
        return emap[ii], k_, j_

    def o_map(i, j, kk, emap, live):
        ii, j_, _ = frozen(i, j, kk, live)
        return ii, j_

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r // tile_m, nn, nk),
        in_specs=[pl.BlockSpec((tile_m, tk), x_map),
                  pl.BlockSpec((None, tk, tn), w_map)],
        out_specs=pl.BlockSpec((tile_m, tn), o_map),
        scratch_shapes=[pltpu.VMEM((tile_m, tn), jnp.float32)],
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_gmm_kernel, nk=nk),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((r, n), x_buf.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=3 * BLOCK_BYTES + (16 << 20)),
            interpret=_INTERPRET,
            name="grouped_matmul",
        )(emap.astype(jnp.int32),
          jnp.asarray(n_live, jnp.int32).reshape(1), x_buf, w)


def grouped_matmul_xla(x_buf, w, emap, n_live, *, tile_m: int):
    """Dense-gather form of :func:`grouped_matmul` (rows of dead tiles
    come out as zeros): parity tests and the off-TPU path."""
    r, k = x_buf.shape
    nt = r // tile_m
    xt = x_buf.reshape(nt, tile_m, k)
    out = jnp.einsum("tbk,tkn->tbn", xt, w[emap],
                     preferred_element_type=jnp.float32)
    live = jnp.arange(nt) < jnp.asarray(n_live, jnp.int32).reshape(())
    out = jnp.where(live[:, None, None], out, 0.0)
    return out.reshape(r, w.shape[2]).astype(x_buf.dtype)


def select_grouped_matmul():
    """The kernel on a TPU (or under interpret mode), the dense gather
    on the CPU."""
    if jax.default_backend() not in ("cpu",) or _INTERPRET:
        return grouped_matmul
    return grouped_matmul_xla
