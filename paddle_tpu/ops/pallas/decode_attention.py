"""Pallas decode-step attention over the KV cache.

Reference analog: paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu + masked_multihead_attention — the
serving-path kernels that attend ONE query token against the cache
without materializing head-repeated K/V or an [T] softmax round-trip.

Two implementations, measured head-to-head on a v5e chip
(B=8, T=8192, 32 q / 8 kv heads, D=128, bf16):

  * the DEFAULT path is XLA: kv-head-major [B, kvh, T, D] caches with a
    head-repeat + batched-GEMV einsum — XLA fuses mask+softmax+PV into
    the matmul pipeline at full HBM bandwidth (6.8 ms/step; the old
    [B, T, kvh, D] layout cost 9.0 ms).  At decode's one-row-per-head
    shapes this fused path is the fastest formulation on current
    hardware.
  * the Pallas kernel (enable with PALLAS_DECODE=True): grid
    (batch, kv_head, T/block_t), online softmax in f32 scratch, blocks
    past `pos` skip compute.  Numerically verified on TPU, but the
    sequential grid's per-step overhead loses to the fused XLA path at
    these shapes (85 ms measured) — it exists as the foundation for
    paged/block-table attention, where the cache gather cannot be
    expressed as one dense einsum and a kernel is the only option.

Inference-only (no VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import NUM_LANES

__all__ = ["decode_attention"]

_INTERPRET = False
PALLAS_DECODE = False   # opt-in: see module docstring for the measured
                        # XLA-vs-kernel numbers behind this default


def _decode_kernel(q_ref, k_ref, v_ref, pos_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, block_t, sm_scale, nblk):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)
    q = q_ref[...]                                  # [rep, D]
    rep, d = q.shape
    pos = pos_ref[0, 0]                             # scalar int32

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * block_t <= pos)     # blocks past pos skip their compute
    def _compute():
        k = k_ref[...]                              # [block_t, D]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(sm_scale)
        t_ids = i * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (rep, block_t), 1)
        s = jnp.where(t_ids <= pos, s, -jnp.inf)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(i == nblk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[:, 0][:, None]).astype(
            o_ref.dtype)


def _pallas_decode(q, kcache, vcache, pos, block_t):
    """q [B, nh, D]; caches [B, kvh, T, D] (kv-head-major, so the
    [block_t, D] tiles are the trailing dims Mosaic can tile);
    pos [B] -> [B, nh, D]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, nh, d = q.shape
    kvh, t = kcache.shape[1], kcache.shape[2]
    rep = nh // kvh
    nblk = t // block_t
    qg = q.reshape(b, kvh, rep, d)
    # [B, 8, 128] so the pos block meets Mosaic's (8, 128) tiling
    pos_b = jnp.broadcast_to(
        pos.astype(jnp.int32)[:, None, None], (b, 8, NUM_LANES))

    with jax.enable_x64(False):   # see flash_attention._flash_fwd
        out = pl.pallas_call(
            functools.partial(_decode_kernel, block_t=block_t,
                              sm_scale=1.0 / np.sqrt(d), nblk=nblk),
            grid=(b, kvh, nblk),
            in_specs=[
                pl.BlockSpec((None, None, rep, d),
                             lambda b_, g, i: (b_, g, 0, 0)),
                pl.BlockSpec((None, None, block_t, d),
                             lambda b_, g, i: (b_, g, i, 0)),
                pl.BlockSpec((None, None, block_t, d),
                             lambda b_, g, i: (b_, g, i, 0)),
                pl.BlockSpec((None, 8, NUM_LANES),
                             lambda b_, g, i: (b_, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, rep, d),
                                   lambda b_, g, i: (b_, g, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((rep, d), jnp.float32),
                pltpu.VMEM((rep, NUM_LANES), jnp.float32),
                pltpu.VMEM((rep, NUM_LANES), jnp.float32),
            ],
            interpret=_INTERPRET,
            name="decode_attention",
        )(qg, kcache, vcache, pos_b)
    return out.reshape(b, nh, d)


def decode_attention(q, kcache, vcache, pos):
    """One-token cache attention: q [B, nh, D], caches [B, kvh, T, D]
    (kv-head-major serving layout),
    pos [B] (index of the CURRENT token; entries t <= pos attend).
    Returns [B, nh, D].  Pallas path when PALLAS_DECODE is on and
    shapes/backend allow, XLA einsum otherwise (identical numerics).
    The route is chosen on the input; a kernel that fails to trace or
    compile raises, it is never swapped for the XLA result."""
    b, nh, d = q.shape
    kvh, t = kcache.shape[1], kcache.shape[2]
    block_t = 256 if t % 256 == 0 else (128 if t % 128 == 0 else None)
    use_pallas = (
        (PALLAS_DECODE or _INTERPRET)
        and block_t is not None
        and d in (64, 128, 256)
        and nh % kvh == 0
        and q.dtype == kcache.dtype == vcache.dtype
        and (jax.default_backend() not in ("cpu",) or _INTERPRET))
    if use_pallas:
        return _pallas_decode(q, kcache, vcache, pos, block_t)
    return _xla_decode(q, kcache, vcache, pos)


def _xla_decode(q, kcache, vcache, pos):
    b, nh, d = q.shape
    kvh = kcache.shape[1]
    rep = nh // kvh
    kq = jnp.repeat(kcache, rep, axis=1)            # [B, nh, T, D]
    vq = jnp.repeat(vcache, rep, axis=1)
    logits = jnp.einsum("bhd,bhtd->bht", q, kq,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    tpos = jnp.arange(kcache.shape[2])
    valid = tpos[None, None, :] <= pos[:, None, None]
    logits = jnp.where(valid, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,bhtd->bhd", probs, vq)
