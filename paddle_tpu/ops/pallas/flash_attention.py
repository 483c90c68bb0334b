"""Flash attention for TPU — Pallas forward AND backward kernels.

Reference analog: paddle/phi/kernels/gpu/flash_attn_kernel.cu +
flash_attn_grad_kernel.cu (dynloaded CUDA flashattn library); layout
[batch, seqlen, num_heads, head_dim], causal flag, optional dense mask.

TPU formulation: a blockwise streaming kernel pair.
  * forward: online-softmax over K/V blocks; emits out + per-row
    log-sum-exp (lse, lane-broadcast to [B,H,S,128] per Mosaic tiling).
  * backward: flash-style recompute — a dQ kernel streaming K/V blocks
    and a dK/dV kernel streaming Q blocks, both re-deriving the softmax
    from the saved lse instead of storing [S,S] probabilities.
  * wired together with jax.custom_vjp so jax.grad never materializes
    the quadratic score matrix (the OOM the naive path hits at 2k+ seq).

Arbitrary sequence lengths: the wrapper pads Sq/Sk up to block multiples
and bakes the REAL lengths into the kernels as static constants; tail
K columns are masked in-kernel, padded Q rows produce finite garbage
that is sliced off (their cotangents are zero in backward, so they
contribute nothing to dK/dV).  Sq != Sk causal uses the reference's
bottom-right alignment (row i sees keys <= i + Sk - Sq); rows with no
visible key (Sq > Sk) emit zeros, matching the flash contract.

Grouped-query attention runs in-kernel: the K/V BlockSpec index map
sends q-head h to kv-head h // group, so K/V are never materialized at
q-head width.  dK/dV are emitted per q-head and group-summed outside.

The XLA path (`_xla_sdpa`) keeps full semantics (arbitrary masks,
dropout) and is numerically the flash reference: fp32 softmax, input
dtype matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NUM_LANES = 128
# Finite stand-in for -inf so blockwise max/exp arithmetic never forms
# (-inf) - (-inf): masked logits underflow exp() to exactly 0.
MASK_VAL = -0.7 * float(np.finfo(np.float32).max)
# lse sentinel for rows with no visible key: exp(s - BIG) == 0 for any
# representable s, so backward treats the whole row as zero-probability.
LSE_INVALID = float(np.finfo(np.float32).max) * 0.5


def _ab_t(a, b):
    """a @ b.T with f32 accumulation (operands keep their dtype so bf16
    runs the MXU at full rate)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _ab(a, b):
    """a @ b with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _at_b(a, b):
    """a.T @ b with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _xla_sdpa(q, k, v, attn_mask=None, is_causal=False, dropout_p=0.0,
              training=True, key=None):
    # [B, S, H, D] -> [B, H, S, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    # grouped-query attention: broadcast kv heads if fewer than q heads
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    masked = None
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cmask, logits, -jnp.inf)
        masked = jnp.broadcast_to(cmask, logits.shape)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -jnp.inf)
            am = jnp.broadcast_to(attn_mask, logits.shape)
            masked = am if masked is None else masked & am
        else:
            logits = logits + attn_mask.astype(logits.dtype)
    if masked is not None:
        # rows with no visible key softmax over all -inf -> NaN in BOTH
        # directions (the softmax VJP turns NaN*0 cotangents into NaN);
        # rewrite those rows to finite logits first, then zero the probs,
        # so forward AND backward match the flash kernels' zero-row
        # convention
        row_ok = jnp.any(masked, axis=-1, keepdims=True)
        logits = jnp.where(row_ok, logits, jnp.zeros((), logits.dtype))
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        probs = jnp.where(row_ok, probs, jnp.zeros((), probs.dtype))
    else:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        from ...framework import random as _random
        keep = jax.random.bernoulli(
            key if key is not None else _random.split_key(),
            1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros((), probs.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


# tests: run the kernels anywhere via interpret mode.  Off in every
# program a user starts (chip_smoke.py asserts it).
_INTERPRET = False


def _pad_len(s, mult=128):
    """Pad to a lane-tileable length: 128-multiples suffice for Mosaic
    (block sizes need not be powers of two — seq 384 runs unpadded with
    384-wide blocks instead of paying 33% padding to reach 512)."""
    return max(mult, -(-s // mult) * mult)


def _pad_seq(x, target):
    s = x.shape[1]
    if s == target:
        return x
    return jnp.pad(x, ((0, 0), (0, target - s), (0, 0), (0, 0)))


def kernel_route(q, k, v, dropout_p=0.0) -> bool:
    """Whether :func:`sdpa` sends these operands to the Pallas kernels
    (given a mask form they can express): decided on what is visible in
    the input.  Callers that must treat a kernel call differently from an
    XLA one (a Mosaic call cannot be partitioned automatically) ask here
    instead of repeating the rule."""
    return (
        dropout_p == 0.0
        and q.dtype == k.dtype == v.dtype   # kernels matmul in input dtype
        and q.shape[-1] in (64, 128, 192, 256)
        and q.shape[1] >= 128 and k.shape[1] >= 128
        and jax.default_backend() not in ("cpu",))


def sdpa(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
         training=True, flashmask=None):
    """Paddle-layout scaled-dot-product attention: [B, S, H, D] in/out.

    Masked inputs route to the Pallas kernels where the mask is
    expressible without the [S, S] score matrix:
      * flashmask: column-interval mask_vecs [B|1, H|1, 2|4, Sk] int32
        (see ops.pallas.flash_mask) — O(S) memory;
      * a bool key-padding attn_mask [B, 1|H, 1, Sk] auto-converts to
        flashmask;
      * a floating attn_mask [B|1, H|1, Sq, Sk] becomes the dense-bias
        kernel (streamed blockwise, no softmax residuals).
    Sequence lengths are arbitrary (>= 128): inputs are padded to block
    multiples and the tails masked in-kernel.  Anything else (dropout,
    arbitrary bool masks, fewer than 128 positions, the CPU backend)
    routes to the XLA path: a choice made on the input, never a rescue
    from a kernel that failed."""
    shapes_ok = kernel_route(q, k, v, dropout_p)

    mask_vecs = flashmask
    bias = None
    if attn_mask is not None and mask_vecs is None and shapes_ok:
        am = jnp.asarray(attn_mask)
        if (am.dtype == jnp.bool_ and am.ndim == 4 and am.shape[2] == 1
                and am.shape[-1] == k.shape[1]):
            # key-padding mask (per-batch or per-head): columns allowed
            # for all rows or none
            from .flash_mask import padding_mask_to_intervals
            mask_vecs = padding_mask_to_intervals(am[:, :, 0, :],
                                                  q.shape[1])
        elif (jnp.issubdtype(am.dtype, jnp.floating) and am.ndim == 4
                and am.shape[-2:] == (q.shape[1], k.shape[1])):
            bias = am

    if shapes_ok and (attn_mask is None or mask_vecs is not None
                      or bias is not None):
        # No probe and no fallback.  What routes a call here is visible
        # in its input (backend, dtypes, head dim, lengths, mask form);
        # a kernel that then fails to trace or compile raises with the
        # compiler's message — the XLA path would hide a chip that is
        # running without its kernels.  Past _STREAM_SEQ the kernels
        # switch to their streamed variants on their own.
        if mask_vecs is not None:
            return _pallas_sdpa_masked(q, k, v, mask_vecs, is_causal)
        if bias is not None:
            return _pallas_sdpa_biased(q, k, v, bias, is_causal)
        return _pallas_sdpa(q, k, v, is_causal)
    if attn_mask is None and flashmask is not None:
        # keep flashmask semantics on the XLA path (dense, O(S^2)).
        # Additive -1e9 (not bool -inf) keeps fully-masked rows finite;
        # zeroing them afterwards matches the kernel's convention.
        from .flash_mask import dense_mask_from_intervals
        allowed = dense_mask_from_intervals(flashmask, q.shape[1],
                                            k.shape[1])
        bias = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)
        out = _xla_sdpa(q, k, v, attn_mask=bias, is_causal=is_causal,
                        dropout_p=dropout_p, training=training)
        row_ok = jnp.any(allowed, axis=-1)            # [B|1, H|1, Sq]
        row_ok = jnp.swapaxes(row_ok, 1, 2)[..., None]  # [B,Sq,H|1,1]
        return jnp.where(row_ok, out, jnp.zeros((), out.dtype))
    return _xla_sdpa(q, k, v, attn_mask=attn_mask, is_causal=is_causal,
                     dropout_p=dropout_p, training=training)


def _xla_sdpa_streamed(q, k, v, is_causal, bias=None, mask_vecs=None,
                       chunk=512):
    """O(S)-memory masked attention in plain XLA: lax.scan over key
    chunks with the online-softmax recurrence.  No dispatch reaches
    it: it is the reference the streamed masked kernels are tested
    against at lengths where the dense [Sq, Sk] reference is too big.
    Supports float bias [B|1, H|1, Sq, Sk] and flashmask interval vecs
    [B|1, H|1, 2|4, Sk]; per-chunk slices keep every transient at
    [B, H, Sq, chunk].  The step is jax.checkpoint-ed: without it the
    scan saves per-chunk s/p residuals for backward — O(Sq*Sk) total,
    the very blowup this path exists to avoid (advisor r3)."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)   # [B, H, Sq, D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    b, hq, sq, d = qh.shape
    hk = kh.shape[1]
    if hq != hk:                                      # GQA
        rep = hq // hk
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    sk = kh.shape[2]
    scale = 1.0 / np.sqrt(d)
    pad = (-sk) % chunk
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pad)))
        if mask_vecs is not None:
            mask_vecs = jnp.pad(mask_vecs,
                                ((0, 0), (0, 0), (0, 0), (0, pad)))
    nc = kh.shape[2] // chunk
    ko = sk - sq
    q_ids = jnp.arange(sq)[:, None]                  # [Sq, 1]

    def step(carry, c):
        m_prev, l_prev, acc = carry
        c0 = c * chunk
        kc = jax.lax.dynamic_slice_in_dim(kh, c0, chunk, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(vh, c0, chunk, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh,
                       kc.astype(jnp.float32)) * scale
        k_ids = c0 + jnp.arange(chunk)[None, :]      # [1, chunk]
        ok = k_ids < sk                               # padded tail
        if is_causal:
            ok = ok & (k_ids <= q_ids + ko)
        if bias is not None:
            s = s + jax.lax.dynamic_slice_in_dim(
                bias, c0, chunk, axis=3).astype(jnp.float32)
        if mask_vecs is not None:
            from .flash_mask import dense_mask_from_intervals
            vec_c = jax.lax.dynamic_slice_in_dim(mask_vecs, c0, chunk,
                                                 axis=3)
            # interval semantics are per-COLUMN (row bounds in the vec
            # entries), so column slicing composes exactly
            allowed = dense_mask_from_intervals(vec_c, sq, chunk)
            s = jnp.where(allowed, s, MASK_VAL)
        s = jnp.where(ok[None, None], s, MASK_VAL)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
        # pin carry dtypes: the framework's global x64 mode promotes
        # somewhere in the reductions
        return (m_cur.astype(jnp.float32), l_cur.astype(jnp.float32),
                acc.astype(jnp.float32)), None

    m0 = jnp.full((b, hq, sq), MASK_VAL, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, acc0),
                                  jnp.arange(nc))
    row_ok = (m > MASK_VAL * 0.5) & (l > 0.0)
    out = jnp.where(row_ok[..., None],
                    acc / jnp.where(row_ok, l, 1.0)[..., None], 0.0)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _pallas_sdpa(q, k, v, causal):
    """[B, S, H, D] wrapper: pads seqlens to block multiples and
    transposes to [B, H, S, D]; the pad/slice VJPs (zero-pad the
    cotangent / slice the grad) are handled by jax outside custom_vjp."""
    sq, sk = q.shape[1], k.shape[1]
    sq_p, sk_p = _pad_len(sq), _pad_len(sk)
    qt = jnp.swapaxes(_pad_seq(q, sq_p), 1, 2)
    kt = jnp.swapaxes(_pad_seq(k, sk_p), 1, 2)
    vt = jnp.swapaxes(_pad_seq(v, sk_p), 1, 2)
    out = flash_mha(qt, kt, vt, causal, 1.0 / np.sqrt(q.shape[-1]), sq, sk)
    return jnp.swapaxes(out, 1, 2)[:, :sq]


def _pallas_sdpa_masked(q, k, v, mask_vecs, causal):
    from .flash_mask import flash_mha_masked, pad_intervals
    sq, sk = q.shape[1], k.shape[1]
    sq_p, sk_p = _pad_len(sq), _pad_len(sk)
    h, hm = q.shape[2], mask_vecs.shape[1]
    if hm not in (1, h):                 # per-kv-head mask under GQA
        mask_vecs = jnp.repeat(mask_vecs, h // hm, axis=1)
    mask_vecs = pad_intervals(mask_vecs, sk_p)
    qt = jnp.swapaxes(_pad_seq(q, sq_p), 1, 2)
    kt = jnp.swapaxes(_pad_seq(k, sk_p), 1, 2)
    vt = jnp.swapaxes(_pad_seq(v, sk_p), 1, 2)
    out = flash_mha_masked(qt, kt, vt, mask_vecs, causal,
                           1.0 / np.sqrt(q.shape[-1]), sq, sk)
    return jnp.swapaxes(out, 1, 2)[:, :sq]


def _pallas_sdpa_biased(q, k, v, bias, causal):
    from .flash_mask import flash_mha_biased
    sq, sk = q.shape[1], k.shape[1]
    sq_p, sk_p = _pad_len(sq), _pad_len(sk)
    h, hb = q.shape[2], bias.shape[1]
    if hb not in (1, h):
        bias = jnp.repeat(bias, h // hb, axis=1)
    if (sq_p, sk_p) != (sq, sk):
        # padded K columns masked via the bias itself (finite large-neg)
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, sq_p - sq),
                              (0, sk_p - sk)), constant_values=-1e9)
    qt = jnp.swapaxes(_pad_seq(q, sq_p), 1, 2)
    kt = jnp.swapaxes(_pad_seq(k, sk_p), 1, 2)
    vt = jnp.swapaxes(_pad_seq(v, sk_p), 1, 2)
    out = flash_mha_biased(qt, kt, vt, bias, causal,
                           1.0 / np.sqrt(q.shape[-1]), sq, sk)
    return jnp.swapaxes(out, 1, 2)[:, :sq]


def _visible(q_ids, k_ids, causal, sk_real, ko):
    """The mask every kernel shares: tail K columns are invisible, and
    causal visibility is bottom-right aligned (offset ko = sk - sq)."""
    vis = k_ids < sk_real
    if causal:
        vis &= k_ids <= q_ids + ko
    return vis


def _q_trip_count(q_blk, bq, block_k, causal, sq_real, sk_real):
    """K-block trip count for a Q-block program (fwd/dq/dbias grids):
    skips the padded K tail, the causal upper triangle, and — when the
    whole Q block is padding — everything."""
    nblk = -(-sk_real // block_k)
    if causal:
        ko = sk_real - sq_real
        upper = jnp.clip(
            (q_blk * bq + bq + ko + block_k - 1) // block_k, 0, nblk)
    else:
        upper = nblk
    return jnp.where(q_blk * bq >= sq_real, 0, upper)


def _k_trip_bounds(k_blk, bk, block_q, causal, sq_real, sk_real):
    """(lower, upper) Q-block bounds for a K-block program (dkv grid):
    skips the causal lower triangle, the padded Q tail (zero cotangent),
    and fully-padded K blocks."""
    nblk = -(-sq_real // block_q)
    if causal:
        ko = sk_real - sq_real
        lower = jnp.clip((k_blk * bk - ko) // block_q, 0, nblk)
    else:
        lower = 0
    return jnp.where(k_blk * bk >= sk_real, nblk, lower), nblk


# ---------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k,
                sm_scale, sq_real, sk_real):
    # lse_ref is None for the inference-only variant (no residual needed)
    from jax.experimental import pallas as pl

    q = q_ref[...]                                         # [bq, d]
    bq, d = q.shape
    ko = sk_real - sq_real
    q_blk = pl.program_id(2)

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.dslice(i * block_k, block_k), :]
        v = v_ref[pl.dslice(i * block_k, block_k), :]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        q_ids = q_blk * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_ids = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + _ab(p.astype(v.dtype), v)
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), MASK_VAL, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    upper = _q_trip_count(q_blk, bq, block_k, causal, sq_real, sk_real)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    # rows with no visible key (causal with sq > sk, or padded rows when
    # upper == 0): m stayed at MASK_VAL -> emit zeros, poison-free
    row_ok = (m > MASK_VAL * 0.5) & (l > 0.0)
    o_ref[...] = jnp.where(row_ok[:, None], acc / jnp.where(
        row_ok, l, 1.0)[:, None], 0.0).astype(o_ref.dtype)
    if lse_ref is not None:
        lse = jnp.where(row_ok, m + jnp.log(jnp.where(row_ok, l, 1.0)),
                        LSE_INVALID)
        lse_ref[...] = jnp.broadcast_to(lse[:, None], (bq, NUM_LANES))


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, sq_real,
               sk_real, need_lse=True):
    # jax 0.9.0: Mosaic lowering infinitely recurses under jax_enable_x64
    # (the framework's global default); trace the kernel in 32-bit mode.
    with jax.enable_x64(False):
        return _flash_fwd_x32(q, k, v, causal, sm_scale, block_q, block_k,
                              sq_real, sk_real, need_lse)


def _flash_fwd_x32(q, k, v, causal, sm_scale, block_q, block_k, sq_real,
                   sk_real, need_lse):
    from jax.experimental import pallas as pl

    if _stream_wanted(k.shape[2]):
        # whole-K/V VMEM residency would exceed scoped VMEM: stream the
        # key blocks through the grid instead
        return _flash_fwd_stream(q, k, v, causal, sm_scale, block_q,
                                 block_k, sq_real, sk_real, need_lse)

    b, h, sq, d = q.shape
    hk = k.shape[1]
    g = h // hk                           # q heads per kv head (GQA)
    sk = k.shape[2]
    blk = pl.BlockSpec((None, None, block_q, d),
                       lambda b_, h_, i: (b_, h_, i, 0))
    kv = pl.BlockSpec((None, None, sk, d),
                      lambda b_, h_, i: (b_, h_ // g, 0, 0))
    out_specs = [blk]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((None, None, block_q, NUM_LANES),
                                      lambda b_, h_, i: (b_, h_, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, h, sq, NUM_LANES), jnp.float32))
    kernel = functools.partial(_fwd_kernel, causal=causal, block_k=block_k,
                               sm_scale=sm_scale, sq_real=sq_real,
                               sk_real=sk_real)
    res = pl.pallas_call(
        kernel if need_lse else
        (lambda q_ref, k_ref, v_ref, o_ref: kernel(q_ref, k_ref, v_ref,
                                                   o_ref, None)),
        grid=(b, h, sq // block_q),
        in_specs=[blk, kv, kv],
        out_specs=out_specs if need_lse else out_specs[0],
        out_shape=out_shape if need_lse else out_shape[0],
        interpret=_INTERPRET,
        name="flash_fwd",
    )(q, k, v)
    return res if need_lse else (res, None)


# -------------------------------------------- streamed (long-seq) variants
# The block kernels above hold one full non-blocked operand in VMEM (K/V
# for fwd+dq, Q/dO/O for dkv) — ideal below ~4k tokens, beyond Mosaic's
# scoped-VMEM limit past it (measured: seq 8192 bwd needs 20.75M of the
# 16M budget).  The streamed variants below iterate that operand through
# an inner GRID dimension instead, carrying the online-softmax state /
# gradient accumulators across grid steps in f32 VMEM scratch, so VMEM
# use is independent of sequence length — the flash recurrence proper.
_STREAM_SEQ = 4096     # switch point (full-VMEM path is faster below it)
_FORCE_STREAM = False  # tests: exercise the streamed path at tiny shapes


def _stream_wanted(s):
    return _FORCE_STREAM or s > _STREAM_SEQ


def causal_kv_clamp(block_q, block_k, ko, nk, causal):
    """Clamp the kv-block grid index j for a q-block program: causally
    invisible cells re-request the PREVIOUS block so Mosaic elides the
    repeated DMA (pl.when skips compute, but NOT the fetch — without
    the clamp the upper triangle costs ~2x K/V HBM traffic).  Shared by
    every streamed-grid BlockSpec (plain/masked/biased, fwd/dq)."""
    if not causal:
        return lambda i, j: j

    def f(i, j):
        jmax = jnp.clip((i * block_q + block_q - 1 + ko) // block_k,
                        0, nk - 1)
        return jnp.minimum(j, jmax)
    return f


def causal_q_clamp(block_q, block_k, ko, nq, causal):
    """Mirror clamp for a k-block program's q-side fetches (dkv grid):
    cells below the k block's first visible q block re-request the
    previous q/do/o/lse blocks."""
    if not causal:
        return lambda i, j: j

    def f(i, j):
        jmin = jnp.clip((i * block_k - ko) // block_q, 0, nq - 1)
        return jnp.maximum(j, jmin)
    return f


def _fwd_kernel_stream(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                       m_ref, l_ref, *, causal, sm_scale, sq_real,
                       sk_real, nk):
    from jax.experimental import pallas as pl

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
        q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
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


def _flash_fwd_stream(q, k, v, causal, sm_scale, block_q, block_k,
                      sq_real, sk_real, need_lse):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    hk = k.shape[1]
    g = h // hk
    sk = k.shape[2]
    nk = sk // block_k
    jc = causal_kv_clamp(block_q, block_k, sk_real - sq_real, nk, causal)
    blk = pl.BlockSpec((None, None, block_q, d),
                       lambda b_, h_, i, j: (b_, h_, i, 0))
    kv = pl.BlockSpec((None, None, block_k, d),
                      lambda b_, h_, i, j: (b_, h_ // g, jc(i, j), 0))
    out_specs = [blk]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec(
            (None, None, block_q, NUM_LANES),
            lambda b_, h_, i, j: (b_, h_, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, h, sq, NUM_LANES), jnp.float32))
    kernel = functools.partial(_fwd_kernel_stream, causal=causal,
                               sm_scale=sm_scale, sq_real=sq_real,
                               sk_real=sk_real, nk=nk)
    res = pl.pallas_call(
        kernel if need_lse else
        (lambda q_ref, k_ref, v_ref, o_ref, acc, m, l:
         kernel(q_ref, k_ref, v_ref, o_ref, None, acc, m, l)),
        grid=(b, h, sq // block_q, nk),
        in_specs=[blk, kv, kv],
        out_specs=out_specs if need_lse else out_specs[0],
        out_shape=out_shape if need_lse else out_shape[0],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                        pltpu.VMEM((block_q, NUM_LANES), jnp.float32)],
        interpret=_INTERPRET,
        name="flash_fwd_stream",
    )(q, k, v)
    return res if need_lse else (res, None)


def _bwd_dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                          dq_ref, acc_ref, delta_ref, *, causal, sm_scale,
                          sq_real, sk_real, nk):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)
    j = pl.program_id(3)
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    ko = sk_real - sq_real

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # delta depends only on the q block: compute once, not nk times
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
        q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
        p = jnp.exp(s - lse[:, None])
        dp = _ab_t(do, v)
        ds = p * (dp - delta[:, None]) * jnp.float32(sm_scale)
        acc_ref[...] = acc_ref[...] + \
            _ab(ds.astype(k.dtype), k).astype(jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_stream(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, causal,
                           sm_scale, sq_real, sk_real, nq):
    from jax.experimental import pallas as pl

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
        q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
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


def _flash_bwd_stream(q, k, v, out, lse, g, causal, sm_scale, block_q,
                      block_k, sq_real, sk_real):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    hk = k.shape[1]
    grp = h // hk
    sk = k.shape[2]
    nk = sk // block_k
    nq = sq // block_q
    lse = jnp.broadcast_to(lse[..., None], (b, h, sq, NUM_LANES))

    ko = sk_real - sq_real
    jc = causal_kv_clamp(block_q, block_k, ko, nk, causal)
    blk_q4 = pl.BlockSpec((None, None, block_q, d),
                          lambda b_, h_, i, j: (b_, h_, i, 0))
    blk_l4 = pl.BlockSpec((None, None, block_q, NUM_LANES),
                          lambda b_, h_, i, j: (b_, h_, i, 0))
    kv4 = pl.BlockSpec((None, None, block_k, d),
                       lambda b_, h_, i, j: (b_, h_ // grp, jc(i, j), 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_stream, causal=causal,
                          sm_scale=sm_scale, sq_real=sq_real,
                          sk_real=sk_real, nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[blk_q4, kv4, kv4, blk_q4, blk_q4, blk_l4],
        out_specs=blk_q4,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, NUM_LANES), jnp.float32)],
        interpret=_INTERPRET,
        name="flash_bwd_dq_stream",
    )(q, k, v, g, out, lse)

    blk_k4 = pl.BlockSpec((None, None, block_k, d),
                          lambda b_, h_, i, j: (b_, h_, i, 0))
    kv_i4 = pl.BlockSpec((None, None, block_k, d),
                         lambda b_, h_, i, j: (b_, h_ // grp, i, 0))
    qc = causal_q_clamp(block_q, block_k, ko, nq, causal)
    q_j4 = pl.BlockSpec((None, None, block_q, d),
                        lambda b_, h_, i, j: (b_, h_, qc(i, j), 0))
    l_j4 = pl.BlockSpec((None, None, block_q, NUM_LANES),
                        lambda b_, h_, i, j: (b_, h_, qc(i, j), 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_stream, causal=causal,
                          sm_scale=sm_scale, sq_real=sq_real,
                          sk_real=sk_real, nq=nq),
        grid=(b, h, sk // block_k, nq),
        in_specs=[q_j4, kv_i4, kv_i4, q_j4, q_j4, l_j4],
        out_specs=[blk_k4, blk_k4],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_INTERPRET,
        name="flash_bwd_dkv_stream",
    )(q, k, v, g, out, lse)
    if grp > 1:
        dk = dk.reshape(b, hk, grp, sk, d).sum(axis=2)
        dv = dv.reshape(b, hk, grp, sk, d).sum(axis=2)
    return dq, dk, dv


# --------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, *,
                   causal, block_k, sm_scale, sq_real, sk_real):
    from jax.experimental import pallas as pl

    q = q_ref[...]                                          # [bq, d]
    do = do_ref[...]
    lse = lse_ref[:, 0]                                     # [bq]
    # delta = rowsum(out * dout), derived in-kernel from the streamed
    # blocks instead of a separate materialized [B,H,S,128] pass
    delta = jnp.sum(o_ref[...].astype(jnp.float32)
                    * do.astype(jnp.float32), axis=1)
    bq, d = q.shape
    ko = sk_real - sq_real
    q_blk = pl.program_id(2)

    def body(i, dq):
        k = k_ref[pl.dslice(i * block_k, block_k), :]
        v = v_ref[pl.dslice(i * block_k, block_k), :]
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        q_ids = q_blk * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_ids = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
        p = jnp.exp(s - lse[:, None])                       # masked -> 0
        dp = _ab_t(do, v)
        ds = p * (dp - delta[:, None]) * jnp.float32(sm_scale)
        return dq + _ab(ds.astype(k.dtype), k)

    upper = _q_trip_count(q_blk, bq, block_k, causal, sq_real, sk_real)
    dq = jax.lax.fori_loop(0, upper, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref,
                    dv_ref, *, causal, block_q, sm_scale, sq_real, sk_real):
    from jax.experimental import pallas as pl

    k = k_ref[...]                                          # [bk, d]
    v = v_ref[...]
    bk, d = k.shape
    q_len = q_ref.shape[0]
    ko = sk_real - sq_real
    k_blk = pl.program_id(2)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.dslice(i * block_q, block_q), :]
        do = do_ref[pl.dslice(i * block_q, block_q), :]
        lse = lse_ref[pl.dslice(i * block_q, block_q), 0]
        delta = jnp.sum(
            o_ref[pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
            * do.astype(jnp.float32), axis=1)
        s = _ab_t(q, k) * jnp.float32(sm_scale)
        q_ids = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        k_ids = k_blk * bk + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 1)
        s = jnp.where(_visible(q_ids, k_ids, causal, sk_real, ko),
                      s, MASK_VAL)
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


def _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
               sq_real, sk_real):
    with jax.enable_x64(False):   # see _flash_fwd
        return _flash_bwd_x32(q, k, v, out, lse, g, causal, sm_scale,
                              block_q, block_k, sq_real, sk_real)


def _flash_bwd_x32(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
                   sq_real, sk_real):
    from jax.experimental import pallas as pl

    if _stream_wanted(max(q.shape[2], k.shape[2])):
        return _flash_bwd_stream(q, k, v, out, lse, g, causal, sm_scale,
                                 block_q, block_k, sq_real, sk_real)

    b, h, sq, d = q.shape
    hk = k.shape[1]
    grp = h // hk
    sk = k.shape[2]
    # restore the kernels' lane tiling (transient, freed per layer);
    # delta is derived in-kernel from the out/dout streams
    lse = jnp.broadcast_to(lse[..., None], (b, h, sq, NUM_LANES))

    full = lambda s: pl.BlockSpec((None, None, s, d),
                                  lambda b_, h_, i: (b_, h_, 0, 0))
    full_kv = pl.BlockSpec((None, None, sk, d),
                           lambda b_, h_, i: (b_, h_ // grp, 0, 0))
    full_l = pl.BlockSpec((None, None, sq, NUM_LANES),
                          lambda b_, h_, i: (b_, h_, 0, 0))
    blk_q = lambda: pl.BlockSpec((None, None, block_q, d),
                                 lambda b_, h_, i: (b_, h_, i, 0))
    blk_l = pl.BlockSpec((None, None, block_q, NUM_LANES),
                         lambda b_, h_, i: (b_, h_, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, block_k=block_k,
                          sm_scale=sm_scale, sq_real=sq_real,
                          sk_real=sk_real),
        grid=(b, h, sq // block_q),
        in_specs=[blk_q(), full_kv, full_kv, blk_q(), blk_q(), blk_l],
        out_specs=blk_q(),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_INTERPRET,
        name="flash_bwd_dq",
    )(q, k, v, g, out, lse)

    blk_k = lambda: pl.BlockSpec((None, None, block_k, d),
                                 lambda b_, h_, i: (b_, h_, i, 0))
    kv_blk = pl.BlockSpec((None, None, block_k, d),
                          lambda b_, h_, i: (b_, h_ // grp, i, 0))
    # dK/dV are emitted per Q head (grid over h) and group-summed below;
    # K/V themselves are read at kv-head width via the index map
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, block_q=block_q,
                          sm_scale=sm_scale, sq_real=sq_real,
                          sk_real=sk_real),
        grid=(b, h, sk // block_k),
        in_specs=[full(sq), kv_blk, kv_blk, full(sq), full(sq), full_l],
        out_specs=[blk_k(), blk_k()],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), v.dtype)],
        interpret=_INTERPRET,
        name="flash_bwd_dkv",
    )(q, k, v, g, out, lse)
    if grp > 1:
        dk = dk.reshape(b, hk, grp, sk, d).sum(axis=2)
        dv = dv.reshape(b, hk, grp, sk, d).sum(axis=2)
    return dq, dk, dv


# ------------------------------------------------------------- custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_mha(q, k, v, causal, sm_scale, sq_real, sk_real):
    """[B, H, S, D] flash attention; differentiable, O(S) memory.
    S dims must be block multiples (the sdpa wrapper pads); sq_real /
    sk_real are the true lengths baked into the kernels for masking.
    K/V may carry fewer heads than Q (GQA) — no repeat happens."""
    out, _ = _flash_fwd(q, k, v, causal, sm_scale,
                        *_block_sizes(q.shape[2], k.shape[2]),
                        sq_real, sk_real,
                        need_lse=False)   # no-grad path: skip the residual
    return out


def _block_sizes(sq, sk):
    """Largest 128-multiple divisor <= 512 per axis (the padded lengths
    are 128-multiples, so 128 always divides)."""
    def pick(n):
        for b in (512, 384, 256, 128):
            if n % b == 0:
                return b
        return 128
    return min(pick(sq), sq), min(pick(sk), sk)


def _flash_mha_fwd(q, k, v, causal, sm_scale, sq_real, sk_real):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale,
                          *_block_sizes(q.shape[2], k.shape[2]),
                          sq_real, sk_real)
    # the lane broadcast is a Mosaic tiling artifact; keep 1/128 of it
    # as the residual (holding it whole would pin 128x fp32 activation
    # memory per layer) and re-broadcast transiently in the backward
    return out, (q, k, v, out, lse[..., 0])


def _flash_mha_bwd(causal, sm_scale, sq_real, sk_real, res, g):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, causal, sm_scale,
                            *_block_sizes(q.shape[2], k.shape[2]),
                            sq_real, sk_real)
    return dq, dk, dv


flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)
