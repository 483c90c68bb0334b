"""Masked flash attention variants — flashmask intervals + dense bias.

Reference analog: paddle's flashmask_attention
(python/paddle/nn/functional/flash_attention.py, kernel surface
paddle/phi/kernels/gpu/flash_attn_kernel.cu) — an O(S) column-interval
encoding of attention masks (padding, sliding window, packed documents,
causal documents) so masked training never materializes the [S, S]
score matrix; plus a dense additive-bias path for ALiBi/relative-pos
biases.

TPU formulation (kernels in flash_attention.py style):
  * flashmask: the reference's column-interval encoding — for kv column
    j, query rows in [lts[j], lte[j]) are MASKED (and, non-causal, also
    [uts[j], ute[j])).  Passed as ONE stacked int32 array
    mask_vecs [B|1, H|1, nvec, Sk] with nvec = 2 (one interval) or
    4 (two intervals) — O(S) memory.  Fully-masked rows produce zero
    output and lse = -inf, and the backward treats them as zero-grad.
  * bias: an additive [B|1, H|1, Sq, Sk] term streamed blockwise into
    the logits; dbias is produced by a separate kernel pass so XLA can
    DCE it when the bias is a constant (ALiBi).

Both compose with `causal`.  See `sdpa` in flash_attention.py for the
dispatch rules and the bool-mask -> flashmask auto-conversion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import (_ab, _ab_t, _at_b, _visible,
                              _q_trip_count, _k_trip_bounds, NUM_LANES,
                              MASK_VAL, LSE_INVALID, _stream_wanted,
                              causal_kv_clamp, causal_q_clamp)

__all__ = ["flash_mha_masked", "flash_mha_biased", "padding_mask_to_intervals",
           "sliding_window_intervals", "segment_intervals", "pad_intervals"]


# ------------------------------------------------------------ mask helpers
def padding_mask_to_intervals(key_mask, sq):
    """[B, Sk] or [B, H, Sk] bool key-padding mask -> mask_vecs
    [B, 1|H, 2, Sk]: masked columns exclude every query row ([0, sq)),
    valid columns none ([sq, sq))."""
    key_mask = jnp.asarray(key_mask)
    if key_mask.ndim == 2:
        key_mask = key_mask[:, None, :]
    lts = jnp.where(key_mask, jnp.int32(sq), jnp.int32(0))
    lte = jnp.full_like(lts, sq)
    return jnp.stack([lts, lte], axis=2)


def sliding_window_intervals(sk, window, batch=1):
    """Causal sliding-window attention (combine with causal=True): row r
    attends keys [r - window, r] — paddle's window convention (window+1
    keys incl. the diagonal), so column j masks rows > j + window."""
    j = jnp.arange(sk, dtype=jnp.int32)
    lts = jnp.broadcast_to(j + jnp.int32(window) + 1, (batch, 1, sk))
    lte = jnp.full_like(lts, sk)
    return jnp.stack([lts, lte], axis=2)


def segment_intervals(segment_ids, causal=True):
    """[B, S] int segment ids (contiguous packing) -> mask_vecs keeping
    attention within each segment (reference flashmask 'document mask').
    causal=True yields nvec=2 (rows past the segment are already masked
    by the triangle); causal=False yields nvec=4."""
    seg = jnp.asarray(segment_ids)
    b, s = seg.shape
    pos = jnp.arange(s, dtype=jnp.int32)
    same = seg[:, :, None] == seg[:, None, :]          # [B, S, S] bool
    # per-column segment bounds — the O(S^2) bool is a transient XLA
    # fusion; the kernel inputs stay O(S)
    first = jnp.min(jnp.where(same, pos[None, :, None], s), axis=1)
    last1 = jnp.max(jnp.where(same, pos[None, :, None], -1), axis=1) + 1
    lts = last1.astype(jnp.int32)          # mask rows at/after seg end
    lte = jnp.full_like(lts, s)
    if causal:
        vec = jnp.stack([lts, lte], axis=1)
    else:
        uts = jnp.zeros_like(lts)          # mask rows before seg start
        ute = first.astype(jnp.int32)
        vec = jnp.stack([lts, lte, uts, ute], axis=1)
    return vec[:, None]


def pad_intervals(mask_vecs, sk_padded):
    """Extend mask_vecs [B|1, H|1, nvec, Sk] to a padded key length.
    Tail values are irrelevant — every kernel masks k_ids >= sk_real
    itself — only the padded SHAPE matters for the BlockSpecs."""
    vec = jnp.asarray(mask_vecs)
    pad = sk_padded - vec.shape[-1]
    if pad <= 0:
        return vec
    return jnp.pad(vec, ((0, 0), (0, 0), (0, 0), (0, pad)))


def _mask_spec(mask_vecs, sk):
    """BlockSpec for [B|1, H|1, nvec, Sk] mask arrays (broadcast-aware)."""
    from jax.experimental import pallas as pl
    bb, hb, nvec = mask_vecs.shape[:3]

    def imap(b_, h_, i):
        return (b_ if bb > 1 else 0, h_ if hb > 1 else 0, 0, 0)

    return pl.BlockSpec((None, None, nvec, sk), imap)


def _bias_spec(bias, block_q, sk, blocked=True):
    from jax.experimental import pallas as pl
    bb, hb = bias.shape[0], bias.shape[1]

    def imap(b_, h_, i):
        return (b_ if bb > 1 else 0, h_ if hb > 1 else 0,
                i if blocked else 0, 0)

    return pl.BlockSpec((None, None, block_q if blocked else bias.shape[2],
                         sk), imap)


def _safe(m):
    return jnp.where(jnp.isfinite(m), m, jnp.zeros_like(m))


def _mask_block(s, mask_ref, q_ids, col0, ncols, nvec):
    """Apply the [lts,lte(,uts,ute)) masked-intervals for columns
    [col0, col0+ncols) to the score block s."""
    from jax.experimental import pallas as pl
    for i in range(nvec // 2):
        start = mask_ref[2 * i, pl.dslice(col0, ncols)]
        end = mask_ref[2 * i + 1, pl.dslice(col0, ncols)]
        hit = jnp.logical_and(q_ids >= start[None, :],
                              q_ids < end[None, :])
        s = jnp.where(hit, -jnp.inf, s)
    return s


# ---------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, causal, block_k, sm_scale,
                nvec, has_bias, need_lse, sq_real, sk_real):
    from jax.experimental import pallas as pl

    it = iter(rest)
    mask_ref = next(it) if nvec else None
    bias_ref = next(it) if has_bias else None
    o_ref = next(it)
    lse_ref = next(it) if need_lse else None

    q = q_ref[...]                                         # [bq, d]
    bq, d = q.shape
    ko = sk_real - sq_real              # bottom-right causal alignment
    q_blk = pl.program_id(2)

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.dslice(i * block_k, block_k), :]
        v = v_ref[pl.dslice(i * block_k, block_k), :]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        if has_bias:
            s = s + bias_ref[:, pl.dslice(i * block_k, block_k)].astype(
                jnp.float32)
        q_ids = q_blk * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_ids = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, -jnp.inf)
        if nvec:
            s = _mask_block(s, mask_ref, q_ids, i * block_k, block_k, nvec)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # fully-masked-so-far rows: keep the exp argument finite
        alpha = jnp.where(jnp.isfinite(m_cur),
                          jnp.exp(m_prev - m_cur), 1.0)
        p = jnp.exp(s - _safe(m_cur)[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + _ab(p.astype(v.dtype), v)
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    upper = _q_trip_count(q_blk, bq, block_k, causal, sq_real, sk_real)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    if lse_ref is not None:
        lse = jnp.where(l == 0.0, -jnp.inf, m + jnp.log(l_safe))
        lse_ref[...] = jnp.broadcast_to(lse[:, None], (bq, NUM_LANES))


def _masked_fwd(q, k, v, mask_vecs, bias, causal, sm_scale, block_q,
                block_k, sq_real, sk_real, need_lse=True, interpret=False):
    from jax.experimental import pallas as pl

    if _stream_wanted(max(q.shape[2], k.shape[2])):
        # whole-K/V VMEM residency exceeds scoped VMEM past ~4k: stream
        # the key blocks through the grid (VERDICT r3 #2 — masked
        # long-context training stays in Pallas)
        return _masked_fwd_stream(q, k, v, mask_vecs, bias, causal,
                                  sm_scale, block_q, block_k, sq_real,
                                  sk_real, need_lse, interpret)

    b, h, sq, d = q.shape
    g = h // k.shape[1]                  # q heads per kv head (GQA)
    sk = k.shape[2]
    nvec = mask_vecs.shape[2] if mask_vecs is not None else 0
    has_bias = bias is not None
    blk = pl.BlockSpec((None, None, block_q, d),
                       lambda b_, h_, i: (b_, h_, i, 0))
    kv = pl.BlockSpec((None, None, sk, d),
                      lambda b_, h_, i: (b_, h_ // g, 0, 0))
    in_specs = [blk, kv, kv]
    args = [q, k, v]
    if nvec:
        in_specs.append(_mask_spec(mask_vecs, sk))
        args.append(mask_vecs)
    if has_bias:
        in_specs.append(_bias_spec(bias, block_q, sk))
        args.append(bias)
    out_specs = [blk]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((None, None, block_q, NUM_LANES),
                                      lambda b_, h_, i: (b_, h_, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, h, sq, NUM_LANES), jnp.float32))
    kernel = functools.partial(_fwd_kernel, causal=causal, block_k=block_k,
                               sm_scale=sm_scale, nvec=nvec,
                               has_bias=has_bias, need_lse=need_lse,
                               sq_real=sq_real, sk_real=sk_real)
    with jax.enable_x64(False):   # see flash_attention._flash_fwd
        res = pl.pallas_call(
            kernel, grid=(b, h, sq // block_q),
            in_specs=in_specs,
            out_specs=out_specs if need_lse else out_specs[0],
            out_shape=out_shape if need_lse else out_shape[0],
            interpret=interpret,
            name="flash_mask_fwd",
        )(*args)
    return res if need_lse else (res, None)


# -------------------------------------------- streamed (long-seq) variants
# Same design as flash_attention's streamed kernels: the K/V (fwd+dq) or
# Q/dO (dkv) operand iterates through an inner GRID dimension with the
# online-softmax / gradient state carried in f32 VMEM scratch, so VMEM
# use is independent of sequence length.  Mask intervals ride along as
# [nvec, block_k] column blocks; bias as [block_q, block_k] tiles.
# Conventions follow the plain streamed kernels (MASK_VAL finite -inf,
# LSE_INVALID for empty rows) rather than the legacy masked kernels'
# -inf arithmetic — @pl.when branches must not poison scratch carries.


def _mask_block_stream(s, mask_ref, q_ids, nvec):
    """Interval mask for a streamed step: mask_ref holds THIS k block's
    columns [nvec, bk]; masked cells get MASK_VAL (finite)."""
    for i in range(nvec // 2):
        start = mask_ref[2 * i, :]
        end = mask_ref[2 * i + 1, :]
        hit = jnp.logical_and(q_ids >= start[None, :],
                              q_ids < end[None, :])
        s = jnp.where(hit, MASK_VAL, s)
    return s


def _fwd_kernel_stream(q_ref, k_ref, v_ref, *rest, causal, sm_scale,
                       nvec, has_bias, need_lse, sq_real, sk_real, nk):
    from jax.experimental import pallas as pl

    it = iter(rest)
    mask_ref = next(it) if nvec else None
    bias_ref = next(it) if has_bias else None
    o_ref = next(it)
    lse_ref = next(it) if need_lse else None
    acc_ref, m_ref, l_ref = it

    i = pl.program_id(2)
    j = pl.program_id(3)
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    ko = sk_real - sq_real

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VAL)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = i * bq
    k_lo = j * bk
    vis = (q_lo < sq_real) & (k_lo < sk_real)
    if causal:
        vis = vis & (q_lo + bq - 1 + ko >= k_lo)

    @pl.when(vis)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        if has_bias:
            s = s + bias_ref[...].astype(jnp.float32)
        q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
        if nvec:
            s = _mask_block_stream(s, mask_ref, q_ids, nvec)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] \
            + _ab(p.astype(v.dtype), v).astype(jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        row_ok = (m > MASK_VAL * 0.5) & (l > 0.0)
        o_ref[...] = jnp.where(
            row_ok[:, None],
            acc_ref[...] / jnp.where(row_ok, l, 1.0)[:, None],
            0.0).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = jnp.where(row_ok, m + jnp.log(jnp.where(row_ok, l, 1.0)),
                            LSE_INVALID)
            lse_ref[...] = jnp.broadcast_to(lse[:, None], lse_ref.shape)


def _bwd_dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                          *rest, causal, sm_scale, nvec, has_bias,
                          sq_real, sk_real, nk):
    from jax.experimental import pallas as pl

    it = iter(rest)
    mask_ref = next(it) if nvec else None
    bias_ref = next(it) if has_bias else None
    dq_ref = next(it)
    acc_ref, delta_ref = it

    i = pl.program_id(2)
    j = pl.program_id(3)
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    ko = sk_real - sq_real

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        delta = jnp.sum(o_ref[...].astype(jnp.float32)
                        * do_ref[...].astype(jnp.float32), axis=1)
        delta_ref[...] = jnp.broadcast_to(delta[:, None], delta_ref.shape)

    q_lo = i * bq
    k_lo = j * bk
    vis = (q_lo < sq_real) & (k_lo < sk_real)
    if causal:
        vis = vis & (q_lo + bq - 1 + ko >= k_lo)

    @pl.when(vis)
    def _compute():
        q = q_ref[...]
        do = do_ref[...]
        lse = lse_ref[:, 0]
        delta = delta_ref[:, 0]
        k = k_ref[...]
        v = v_ref[...]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        if has_bias:
            s = s + bias_ref[...].astype(jnp.float32)
        q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
        if nvec:
            s = _mask_block_stream(s, mask_ref, q_ids, nvec)
        p = jnp.exp(s - lse[:, None])      # empty rows: lse=LSE_INVALID->0
        dp = _ab_t(do, v)
        ds = p * (dp - delta[:, None]) * jnp.float32(sm_scale)
        acc_ref[...] = acc_ref[...] + \
            _ab(ds.astype(k.dtype), k).astype(jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_stream(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                           *rest, causal, sm_scale, nvec, has_bias,
                           sq_real, sk_real, nq):
    from jax.experimental import pallas as pl

    it = iter(rest)
    mask_ref = next(it) if nvec else None
    bias_ref = next(it) if has_bias else None
    dk_ref = next(it)
    dv_ref = next(it)
    dk_acc, dv_acc = it

    i = pl.program_id(2)   # k block
    j = pl.program_id(3)   # q block
    bk, d = k_ref.shape
    bq = q_ref.shape[0]
    ko = sk_real - sq_real

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_lo = j * bq
    k_lo = i * bk
    vis = (q_lo < sq_real) & (k_lo < sk_real)
    if causal:
        vis = vis & (q_lo + bq - 1 + ko >= k_lo)

    @pl.when(vis)
    def _compute():
        k = k_ref[...]
        v = v_ref[...]
        q = q_ref[...]
        do = do_ref[...]
        lse = lse_ref[:, 0]
        delta = jnp.sum(o_ref[...].astype(jnp.float32)
                        * do.astype(jnp.float32), axis=1)
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        if has_bias:
            s = s + bias_ref[...].astype(jnp.float32)
        q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
        if nvec:
            s = _mask_block_stream(s, mask_ref, q_ids, nvec)
        p = jnp.exp(s - lse[:, None])
        dv_acc[...] = dv_acc[...] + \
            _at_b(p.astype(do.dtype), do).astype(jnp.float32)
        dp = _ab_t(do, v)
        ds = p * (dp - delta[:, None]) * jnp.float32(sm_scale)
        dk_acc[...] = dk_acc[...] + \
            _at_b(ds.astype(q.dtype), q).astype(jnp.float32)

    @pl.when(j == nq - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dbias_kernel_stream(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                             *rest, causal, sm_scale, nvec, sq_real,
                             sk_real, bb, hb, nb, nh):
    """dbias at the bias's OWN broadcast shape: grid (nq, nk, B, H) with
    b/h INNERMOST, so each (i, j) tile's reduction group is consecutive
    — broadcast dims (bb/hb == 1) accumulate into VMEM scratch and write
    once, instead of materializing a full [B, H, Sq, Sk] then summing
    (4.3 GB f32 at seq 8k — the review-caught regression)."""
    from jax.experimental import pallas as pl

    it = iter(rest)
    mask_ref = next(it) if nvec else None
    bias_ref = next(it)
    dbias_ref = next(it)
    (acc_ref,) = it

    i = pl.program_id(0)
    j = pl.program_id(1)
    # reduced (broadcast) dims sit INNERMOST so each (i, j) tile's
    # accumulation group is consecutive; when only b reduces, the grid
    # is (nq, nk, h, b) — see the swap_bh flag in the caller
    if bb == 1 and hb > 1:
        h_ = pl.program_id(2)
        b_ = pl.program_id(3)
    else:
        b_ = pl.program_id(2)
        h_ = pl.program_id(3)
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    ko = sk_real - sq_real
    q_lo = i * bq
    k_lo = j * bk
    vis = (q_lo < sq_real) & (k_lo < sk_real)
    if causal:
        vis = vis & (q_lo + bq - 1 + ko >= k_lo)

    first = jnp.bool_(True)
    last = jnp.bool_(True)
    if bb == 1:                 # b is a reduced (broadcast) dim
        first = first & (b_ == 0)
        last = last & (b_ == nb - 1)
    if hb == 1:
        first = first & (h_ == 0)
        last = last & (h_ == nh - 1)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(vis)
    def _compute():
        q = q_ref[...]
        do = do_ref[...]
        lse = lse_ref[:, 0]
        delta = jnp.sum(o_ref[...].astype(jnp.float32)
                        * do.astype(jnp.float32), axis=1)
        k = k_ref[...]
        v = v_ref[...]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        s = s + bias_ref[...].astype(jnp.float32)
        q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
        if nvec:
            s = _mask_block_stream(s, mask_ref, q_ids, nvec)
        p = jnp.exp(s - lse[:, None])
        dp = _ab_t(do, v)
        acc_ref[...] = acc_ref[...] + p * (dp - delta[:, None])

    @pl.when(last)
    def _finalize():
        dbias_ref[...] = acc_ref[...].astype(dbias_ref.dtype)


def _stream_specs(mask_vecs, bias, block_q, block_k, nq, nk, causal,
                  ko, transposed=False):
    """Streamed-grid BlockSpecs for mask/bias (broadcast-aware).
    transposed=True builds specs for the dkv grid (b, h, k_blk, q_blk)."""
    from jax.experimental import pallas as pl

    specs = []
    _jclamp = causal_kv_clamp(block_q, block_k, ko, nk,
                              causal and not transposed)
    _qclamp = causal_q_clamp(block_q, block_k, ko, nq,
                             causal and transposed)
    if mask_vecs is not None:
        bb, hb, nvec = mask_vecs.shape[:3]
        if transposed:
            def imap_m(b_, h_, i, j, _bb=bb, _hb=hb):
                return (b_ if _bb > 1 else 0, h_ if _hb > 1 else 0, 0, i)
        else:
            def imap_m(b_, h_, i, j, _bb=bb, _hb=hb):
                return (b_ if _bb > 1 else 0, h_ if _hb > 1 else 0, 0,
                        _jclamp(i, j))
        specs.append(pl.BlockSpec((None, None, nvec, block_k), imap_m))
    if bias is not None:
        bb, hb = bias.shape[0], bias.shape[1]
        if transposed:
            def imap_b(b_, h_, i, j, _bb=bb, _hb=hb):
                return (b_ if _bb > 1 else 0, h_ if _hb > 1 else 0,
                        _qclamp(i, j), i)
        else:
            def imap_b(b_, h_, i, j, _bb=bb, _hb=hb):
                return (b_ if _bb > 1 else 0, h_ if _hb > 1 else 0, i,
                        _jclamp(i, j))
        specs.append(pl.BlockSpec((None, None, block_q, block_k), imap_b))
    return specs


def _masked_fwd_stream(q, k, v, mask_vecs, bias, causal, sm_scale,
                       block_q, block_k, sq_real, sk_real, need_lse,
                       interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    g = h // k.shape[1]
    sk = k.shape[2]
    nk = sk // block_k
    nq = sq // block_q
    nvec = mask_vecs.shape[2] if mask_vecs is not None else 0
    has_bias = bias is not None
    ko = sk_real - sq_real

    jc = causal_kv_clamp(block_q, block_k, ko, nk, causal)
    blk = pl.BlockSpec((None, None, block_q, d),
                       lambda b_, h_, i, j: (b_, h_, i, 0))
    kv = pl.BlockSpec((None, None, block_k, d),
                      lambda b_, h_, i, j: (b_, h_ // g, jc(i, j), 0))
    in_specs = [blk, kv, kv] + _stream_specs(
        mask_vecs, bias, block_q, block_k, nq, nk, causal, ko)
    args = [q, k, v] + [a for a in (mask_vecs, bias) if a is not None]
    out_specs = [blk]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((None, None, block_q, NUM_LANES),
                                      lambda b_, h_, i, j: (b_, h_, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, h, sq, NUM_LANES), jnp.float32))
    kernel = functools.partial(_fwd_kernel_stream, causal=causal,
                               sm_scale=sm_scale, nvec=nvec,
                               has_bias=has_bias, need_lse=need_lse,
                               sq_real=sq_real, sk_real=sk_real, nk=nk)
    with jax.enable_x64(False):
        res = pl.pallas_call(
            kernel, grid=(b, h, nq, nk),
            in_specs=in_specs,
            out_specs=out_specs if need_lse else out_specs[0],
            out_shape=out_shape if need_lse else out_shape[0],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                            pltpu.VMEM((block_q, NUM_LANES), jnp.float32)],
            interpret=interpret,
            name="flash_mask_fwd_stream",
        )(*args)
    return res if need_lse else (res, None)


def _masked_bwd_stream(q, k, v, out, lse, g, mask_vecs, bias, causal,
                       sm_scale, block_q, block_k, sq_real, sk_real,
                       need_dbias, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    hk = k.shape[1]
    grp = h // hk
    sk = k.shape[2]
    nk = sk // block_k
    nq = sq // block_q
    nvec = mask_vecs.shape[2] if mask_vecs is not None else 0
    has_bias = bias is not None
    ko = sk_real - sq_real
    lse_b = jnp.broadcast_to(lse[..., None], (b, h, sq, NUM_LANES))

    jc = causal_kv_clamp(block_q, block_k, ko, nk, causal)
    blk_q4 = pl.BlockSpec((None, None, block_q, d),
                          lambda b_, h_, i, j: (b_, h_, i, 0))
    blk_l4 = pl.BlockSpec((None, None, block_q, NUM_LANES),
                          lambda b_, h_, i, j: (b_, h_, i, 0))
    kv4 = pl.BlockSpec((None, None, block_k, d),
                       lambda b_, h_, i, j: (b_, h_ // grp, jc(i, j), 0))
    mb_specs = _stream_specs(mask_vecs, bias, block_q, block_k,
                             nq, nk, causal, ko)
    mb_args = [a for a in (mask_vecs, bias) if a is not None]

    with jax.enable_x64(False):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel_stream, causal=causal,
                              sm_scale=sm_scale, nvec=nvec,
                              has_bias=has_bias, sq_real=sq_real,
                              sk_real=sk_real, nk=nk),
            grid=(b, h, nq, nk),
            in_specs=[blk_q4, kv4, kv4, blk_q4, blk_q4, blk_l4] + mb_specs,
            out_specs=blk_q4,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((block_q, NUM_LANES), jnp.float32)],
            interpret=interpret,
            name="flash_mask_bwd_dq_stream",
        )(q, k, v, g, out, lse_b, *mb_args)

        blk_k4 = pl.BlockSpec((None, None, block_k, d),
                              lambda b_, h_, i, j: (b_, h_, i, 0))
        kv_i4 = pl.BlockSpec((None, None, block_k, d),
                             lambda b_, h_, i, j: (b_, h_ // grp, i, 0))
        qc = causal_q_clamp(block_q, block_k, ko, nq, causal)
        q_j4 = pl.BlockSpec(
            (None, None, block_q, d),
            lambda b_, h_, i, j: (b_, h_, qc(i, j), 0))
        l_j4 = pl.BlockSpec(
            (None, None, block_q, NUM_LANES),
            lambda b_, h_, i, j: (b_, h_, qc(i, j), 0))
        mb_specs_t = _stream_specs(mask_vecs, bias, block_q, block_k,
                                   nq, nk, causal, ko,
                                   transposed=True)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel_stream, causal=causal,
                              sm_scale=sm_scale, nvec=nvec,
                              has_bias=has_bias, sq_real=sq_real,
                              sk_real=sk_real, nq=nq),
            grid=(b, h, nk, nq),
            in_specs=[q_j4, kv_i4, kv_i4, q_j4, q_j4, l_j4] + mb_specs_t,
            out_specs=[blk_k4, blk_k4],
            out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
                       jax.ShapeDtypeStruct((b, h, sk, d), v.dtype)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            interpret=interpret,
            name="flash_mask_bwd_dkv_stream",
        )(q, k, v, g, out, lse_b, *mb_args)
        if grp > 1:
            dk = dk.reshape(b, hk, grp, sk, d).sum(axis=2)
            dv = dv.reshape(b, hk, grp, sk, d).sum(axis=2)

        dbias = None
        if need_dbias:
            # grid (nq, nk, ·, ·) with the REDUCED broadcast dims
            # innermost, so each (i, j) tile's accumulation group is
            # consecutive; dbias comes out at the bias's own shape
            bb, hb = bias.shape[0], bias.shape[1]
            swap_bh = bb == 1 and hb > 1      # only-b reduces: b inner

            def _bh(g2, g3):
                return (g3, g2) if swap_bh else (g2, g3)

            jcd = causal_kv_clamp(block_q, block_k, ko, nk, causal)

            def spec(shape, f):
                return pl.BlockSpec(shape, lambda i, j, g2, g3: f(
                    i, j, *_bh(g2, g3)))

            qd = spec((None, None, block_q, d),
                      lambda i, j, b_, h_: (b_, h_, i, 0))
            ld = spec((None, None, block_q, NUM_LANES),
                      lambda i, j, b_, h_: (b_, h_, i, 0))
            kvd = spec((None, None, block_k, d),
                       lambda i, j, b_, h_: (b_, h_ // grp, jcd(i, j), 0))
            d_specs = [qd, kvd, kvd, qd, qd, ld]
            d_args = [q, k, v, g, out, lse_b]
            if nvec:
                mb_, mh_ = mask_vecs.shape[0], mask_vecs.shape[1]
                d_specs.append(spec(
                    (None, None, nvec, block_k),
                    lambda i, j, b_, h_, _mb=mb_, _mh=mh_:
                    (b_ if _mb > 1 else 0, h_ if _mh > 1 else 0, 0,
                     jcd(i, j))))
                d_args.append(mask_vecs)
            d_specs.append(spec(
                (None, None, block_q, block_k),
                lambda i, j, b_, h_, _bb=bb, _hb=hb:
                (b_ if _bb > 1 else 0, h_ if _hb > 1 else 0, i,
                 jcd(i, j))))
            d_args.append(bias)
            dbias = pl.pallas_call(
                functools.partial(_bwd_dbias_kernel_stream, causal=causal,
                                  sm_scale=sm_scale, nvec=nvec,
                                  sq_real=sq_real, sk_real=sk_real,
                                  bb=bb, hb=hb, nb=b, nh=h),
                grid=(nq, nk, h, b) if swap_bh else (nq, nk, b, h),
                in_specs=d_specs,
                out_specs=spec(
                    (None, None, block_q, block_k),
                    lambda i, j, b_, h_, _bb=bb, _hb=hb:
                    (b_ if _bb > 1 else 0, h_ if _hb > 1 else 0, i, j)),
                out_shape=jax.ShapeDtypeStruct((bb, hb, sq, sk),
                                               jnp.float32),
                scratch_shapes=[pltpu.VMEM((block_q, block_k),
                                           jnp.float32)],
                interpret=interpret,
                name="flash_mask_bwd_dbias_stream",
            )(*d_args).astype(bias.dtype)
    return dq, dk, dv, dbias


# --------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
                   causal, block_k, sm_scale, nvec, has_bias, sq_real,
                   sk_real):
    from jax.experimental import pallas as pl

    it = iter(rest)
    mask_ref = next(it) if nvec else None
    bias_ref = next(it) if has_bias else None
    dq_ref = next(it)

    q = q_ref[...]
    do = do_ref[...]
    lse = _safe(lse_ref[:, 0])
    delta = dl_ref[:, 0]
    bq, d = q.shape
    ko = sk_real - sq_real
    q_blk = pl.program_id(2)

    def body(i, dq):
        k = k_ref[pl.dslice(i * block_k, block_k), :]
        v = v_ref[pl.dslice(i * block_k, block_k), :]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        if has_bias:
            s = s + bias_ref[:, pl.dslice(i * block_k, block_k)].astype(
                jnp.float32)
        q_ids = q_blk * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_ids = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, -jnp.inf)
        if nvec:
            s = _mask_block(s, mask_ref, q_ids, i * block_k, block_k, nvec)
        p = jnp.exp(s - lse[:, None])                       # masked -> 0
        dp = _ab_t(do, v)
        ds = p * (dp - delta[:, None]) * jnp.float32(sm_scale)
        return dq + _ab(ds.astype(k.dtype), k)

    upper = _q_trip_count(q_blk, bq, block_k, causal, sq_real, sk_real)
    dq = jax.lax.fori_loop(0, upper, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
                    causal, block_q, sm_scale, nvec, has_bias, sq_real,
                    sk_real):
    from jax.experimental import pallas as pl

    it = iter(rest)
    mask_ref = next(it) if nvec else None
    bias_ref = next(it) if has_bias else None
    dk_ref = next(it)
    dv_ref = next(it)

    k = k_ref[...]
    v = v_ref[...]
    bk, d = k.shape
    ko = sk_real - sq_real
    k_blk = pl.program_id(2)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.dslice(i * block_q, block_q), :]
        do = do_ref[pl.dslice(i * block_q, block_q), :]
        lse = _safe(lse_ref[pl.dslice(i * block_q, block_q), 0])
        delta = dl_ref[pl.dslice(i * block_q, block_q), 0]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        if has_bias:
            s = s + bias_ref[pl.dslice(i * block_q, block_q),
                             pl.dslice(k_blk * bk, bk)].astype(jnp.float32)
        q_ids = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        k_ids = k_blk * bk + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, -jnp.inf)
        if nvec:
            # this kernel's block covers k columns [k_blk*bk, k_blk*bk+bk)
            s = _mask_block(s, mask_ref, q_ids, 0, bk, nvec)
        p = jnp.exp(s - lse[:, None])
        dv = dv + _at_b(p.astype(do.dtype), do)
        dp = _ab_t(do, v)
        ds = p * (dp - delta[:, None]) * jnp.float32(sm_scale)
        dk = dk + _at_b(ds.astype(q.dtype), q)
        return dk, dv

    lower, nblk = _k_trip_bounds(k_blk, bk, block_q, causal, sq_real,
                                 sk_real)
    dk, dv = jax.lax.fori_loop(
        lower, nblk, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_dbias_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
                      causal, block_k, sm_scale, nvec, sq_real, sk_real):
    """ds per q block, written to a [block_q, Sk] dbias row; its own
    pallas_call so constant-bias training DCEs the whole pass."""
    from jax.experimental import pallas as pl

    it = iter(rest)
    mask_ref = next(it) if nvec else None
    bias_ref = next(it)
    dbias_ref = next(it)

    q = q_ref[...]
    do = do_ref[...]
    lse = _safe(lse_ref[:, 0])
    delta = dl_ref[:, 0]
    bq, d = q.shape
    ko = sk_real - sq_real
    q_blk = pl.program_id(2)
    dbias_ref[...] = jnp.zeros_like(dbias_ref)

    def body(i, _):
        k = k_ref[pl.dslice(i * block_k, block_k), :]
        v = v_ref[pl.dslice(i * block_k, block_k), :]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        s = s + bias_ref[:, pl.dslice(i * block_k, block_k)].astype(
            jnp.float32)
        q_ids = q_blk * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_ids = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, -jnp.inf)
        if nvec:
            s = _mask_block(s, mask_ref, q_ids, i * block_k, block_k, nvec)
        p = jnp.exp(s - lse[:, None])
        dp = _ab_t(do, v)
        ds = p * (dp - delta[:, None])
        dbias_ref[:, pl.dslice(i * block_k, block_k)] = \
            ds.astype(dbias_ref.dtype)
        return 0

    upper = _q_trip_count(q_blk, bq, block_k, causal, sq_real, sk_real)
    jax.lax.fori_loop(0, upper, body, 0)


def _masked_bwd(q, k, v, out, lse, g, mask_vecs, bias, causal, sm_scale,
                block_q, block_k, sq_real, sk_real, need_dbias,
                interpret=False):
    from jax.experimental import pallas as pl

    if _stream_wanted(max(q.shape[2], k.shape[2])):
        return _masked_bwd_stream(q, k, v, out, lse, g, mask_vecs, bias,
                                  causal, sm_scale, block_q, block_k,
                                  sq_real, sk_real, need_dbias, interpret)

    b, h, sq, d = q.shape
    hk = k.shape[1]
    grp = h // hk
    sk = k.shape[2]
    nvec = mask_vecs.shape[2] if mask_vecs is not None else 0
    has_bias = bias is not None
    lse_b = jnp.broadcast_to(lse[..., None], (b, h, sq, NUM_LANES))
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (b, h, sq, NUM_LANES))

    full = lambda s: pl.BlockSpec((None, None, s, d),          # noqa: E731
                                  lambda b_, h_, i: (b_, h_, 0, 0))
    full_kv = pl.BlockSpec((None, None, sk, d),
                           lambda b_, h_, i: (b_, h_ // grp, 0, 0))
    full_l = pl.BlockSpec((None, None, sq, NUM_LANES),
                          lambda b_, h_, i: (b_, h_, 0, 0))
    blk_q = pl.BlockSpec((None, None, block_q, d),
                         lambda b_, h_, i: (b_, h_, i, 0))
    blk_l = pl.BlockSpec((None, None, block_q, NUM_LANES),
                         lambda b_, h_, i: (b_, h_, i, 0))

    tail_specs = []
    tail_args = []
    if nvec:
        tail_specs.append(_mask_spec(mask_vecs, sk))
        tail_args.append(mask_vecs)

    with jax.enable_x64(False):
        dq = pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel, causal=causal, block_k=block_k,
                sm_scale=sm_scale, nvec=nvec, has_bias=has_bias,
                sq_real=sq_real, sk_real=sk_real),
            grid=(b, h, sq // block_q),
            in_specs=[blk_q, full_kv, full_kv, blk_q, blk_l, blk_l]
            + tail_specs
            + ([_bias_spec(bias, block_q, sk)] if has_bias else []),
            out_specs=blk_q,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=interpret,
            name="flash_mask_bwd_dq",
        )(q, k, v, g, lse_b, delta,
          *(tail_args + ([bias] if has_bias else [])))

        blk_k = pl.BlockSpec((None, None, block_k, d),
                             lambda b_, h_, i: (b_, h_, i, 0))
        kv_blk = pl.BlockSpec((None, None, block_k, d),
                              lambda b_, h_, i: (b_, h_ // grp, i, 0))
        kv_tail_specs = []
        if nvec:
            bb, hb = mask_vecs.shape[0], mask_vecs.shape[1]
            kv_tail_specs.append(pl.BlockSpec(
                (None, None, nvec, block_k),
                lambda b_, h_, i, _bb=bb, _hb=hb:
                (b_ if _bb > 1 else 0, h_ if _hb > 1 else 0, 0, i)))
        # dK/dV emitted per Q head (grid over h), group-summed below
        dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_dkv_kernel, causal=causal, block_q=block_q,
                sm_scale=sm_scale, nvec=nvec, has_bias=has_bias,
                sq_real=sq_real, sk_real=sk_real),
            grid=(b, h, sk // block_k),
            in_specs=[full(sq), kv_blk, kv_blk, full(sq), full_l, full_l]
            + kv_tail_specs
            + ([_bias_spec(bias, block_q, sk, blocked=False)]
               if has_bias else []),
            out_specs=[blk_k, blk_k],
            out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
                       jax.ShapeDtypeStruct((b, h, sk, d), v.dtype)],
            interpret=interpret,
            name="flash_mask_bwd_dkv",
        )(q, k, v, g, lse_b, delta,
          *(tail_args + ([bias] if has_bias else [])))
        if grp > 1:
            dk = dk.reshape(b, hk, grp, sk, d).sum(axis=2)
            dv = dv.reshape(b, hk, grp, sk, d).sum(axis=2)

        dbias = None
        if need_dbias:
            dbias_full = pl.pallas_call(
                functools.partial(
                    _bwd_dbias_kernel, causal=causal, block_k=block_k,
                    sm_scale=sm_scale, nvec=nvec,
                    sq_real=sq_real, sk_real=sk_real),
                grid=(b, h, sq // block_q),
                in_specs=[blk_q, full_kv, full_kv, blk_q, blk_l, blk_l]
                + tail_specs + [_bias_spec(bias, block_q, sk)],
                out_specs=pl.BlockSpec((None, None, block_q, sk),
                                       lambda b_, h_, i: (b_, h_, i, 0)),
                out_shape=jax.ShapeDtypeStruct((b, h, sq, sk),
                                               jnp.float32),
                interpret=interpret,
                name="flash_mask_bwd_dbias",
            )(q, k, v, g, lse_b, delta, *(tail_args + [bias]))
            # reduce over broadcast dims back to the bias shape
            red = []
            if bias.shape[0] == 1 and b > 1:
                red.append(0)
            if bias.shape[1] == 1 and h > 1:
                red.append(1)
            dbias = (jnp.sum(dbias_full, axis=tuple(red), keepdims=True)
                     if red else dbias_full).astype(bias.dtype)
    return dq, dk, dv, dbias


# ------------------------------------------------------------- custom_vjp
_INTERPRET = False   # set True in tests to run the kernels anywhere


def _blocks(sq, sk):
    from .flash_attention import _block_sizes
    return _block_sizes(sq, sk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_mha_masked(q, k, v, mask_vecs, causal, sm_scale, sq_real=None,
                     sk_real=None):
    """[B, H, S, D] flash attention with the flashmask column-interval
    encoding (mask_vecs [B|1, H|1, 2 or 4, Sk] int32); differentiable,
    O(S) mask memory.  S dims must be block multiples (the sdpa wrapper
    pads and extends mask_vecs via pad_intervals); sq_real/sk_real are
    the true lengths.  K/V may carry fewer heads than Q (GQA)."""
    sq_real = sq_real if sq_real is not None else q.shape[2]
    sk_real = sk_real if sk_real is not None else k.shape[2]
    out, _ = _masked_fwd(q, k, v, mask_vecs, None, causal, sm_scale,
                         *_blocks(q.shape[2], k.shape[2]),
                         sq_real, sk_real, need_lse=False,
                         interpret=_INTERPRET)
    return out


def _masked_vjp_fwd(q, k, v, mask_vecs, causal, sm_scale, sq_real,
                    sk_real):
    sq_real = sq_real if sq_real is not None else q.shape[2]
    sk_real = sk_real if sk_real is not None else k.shape[2]
    out, lse = _masked_fwd(q, k, v, mask_vecs, None, causal, sm_scale,
                           *_blocks(q.shape[2], k.shape[2]),
                           sq_real, sk_real, interpret=_INTERPRET)
    return out, (q, k, v, mask_vecs, out, lse[..., 0])


def _masked_vjp_bwd(causal, sm_scale, sq_real, sk_real, res, g):
    q, k, v, mask_vecs, out, lse = res
    sq_real = sq_real if sq_real is not None else q.shape[2]
    sk_real = sk_real if sk_real is not None else k.shape[2]
    dq, dk, dv, _ = _masked_bwd(q, k, v, out, lse, g, mask_vecs, None,
                                causal, sm_scale,
                                *_blocks(q.shape[2], k.shape[2]),
                                sq_real, sk_real,
                                need_dbias=False, interpret=_INTERPRET)
    return dq, dk, dv, None


flash_mha_masked.defvjp(_masked_vjp_fwd, _masked_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_mha_biased(q, k, v, bias, causal, sm_scale, sq_real=None,
                     sk_real=None):
    """[B, H, S, D] flash attention with a dense additive bias
    [B|1, H|1, Sq, Sk]; differentiable (dbias materializes a
    [B,H,Sq,Sk] f32 transient only when the bias needs a gradient).
    S dims must be block multiples (the sdpa wrapper pads the bias with
    -1e9 on the key tail); sq_real/sk_real are the true lengths."""
    sq_real = sq_real if sq_real is not None else q.shape[2]
    sk_real = sk_real if sk_real is not None else k.shape[2]
    out, _ = _masked_fwd(q, k, v, None, bias, causal, sm_scale,
                         *_blocks(q.shape[2], k.shape[2]),
                         sq_real, sk_real, need_lse=False,
                         interpret=_INTERPRET)
    return out


def _biased_vjp_fwd(q, k, v, bias, causal, sm_scale, sq_real, sk_real):
    sq_real = sq_real if sq_real is not None else q.shape[2]
    sk_real = sk_real if sk_real is not None else k.shape[2]
    out, lse = _masked_fwd(q, k, v, None, bias, causal, sm_scale,
                           *_blocks(q.shape[2], k.shape[2]),
                           sq_real, sk_real, interpret=_INTERPRET)
    return out, (q, k, v, bias, out, lse[..., 0])


def _biased_vjp_bwd(causal, sm_scale, sq_real, sk_real, res, g):
    q, k, v, bias, out, lse = res
    sq_real = sq_real if sq_real is not None else q.shape[2]
    sk_real = sk_real if sk_real is not None else k.shape[2]
    dq, dk, dv, dbias = _masked_bwd(q, k, v, out, lse, g, None, bias,
                                    causal, sm_scale,
                                    *_blocks(q.shape[2], k.shape[2]),
                                    sq_real, sk_real,
                                    need_dbias=True, interpret=_INTERPRET)
    return dq, dk, dv, dbias


flash_mha_biased.defvjp(_biased_vjp_fwd, _biased_vjp_bwd)


def dense_mask_from_intervals(mask_vecs, sq, sk):
    """Dense bool mask (True = attend) equivalent to mask_vecs — the
    O(S^2) fallback used when the Pallas path is unavailable."""
    vec = jnp.asarray(mask_vecs)
    b, h, nvec, _ = vec.shape
    r = jnp.arange(sq)[:, None]
    allowed = jnp.ones((b, h, sq, sk), bool)
    for i in range(nvec // 2):
        start = vec[:, :, 2 * i][:, :, None, :]
        end = vec[:, :, 2 * i + 1][:, :, None, :]
        allowed = jnp.logical_and(
            allowed, ~jnp.logical_and(r[None, None] >= start,
                                      r[None, None] < end))
    return allowed
