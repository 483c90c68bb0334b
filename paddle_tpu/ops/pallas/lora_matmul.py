"""Pallas batched gather-LoRA matmul for multi-adapter decode.

Reference analog: the grouped per-request adapter GEMVs of multi-LoRA
serving stacks (Punica's BGMV / vLLM's multi-LoRA shrink+expand) — one
base-model matmul plus a rank-r delta per request, where each request
may point at a *different* adapter:

    delta[s] = (x[s] @ A[idx[s]].T) @ B[idx[s]] * scale[idx[s]]

The adapter bank is packed ``A [N, r, K]`` / ``B [N, r, M]`` with bank
row 0 zeroed (the "no adapter" row), so mixed batches — including
slots with no adapter at all — run through ONE jitted program with the
per-slot index vector as plain data.

TPU formulation: one ``pallas_call`` gridded over slots with the index
vector as a scalar-prefetch argument; the BlockSpec index maps use
``idx_ref[s]`` to DMA exactly the two rank-r adapter tiles this slot
needs from the bank in HBM — the gather never materializes ``[S, r, K]``.
Decode row counts are tiny (S = max_slots), so the kernel is gather-
latency bound, which is precisely what the block-level DMA hides.

The XLA fallback (``take`` + two einsums) runs off-TPU and for
prefill-shaped calls, and is the reference semantics the kernel is
tested against.  Math accumulates in f32 regardless of bank dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["lora_delta", "lora_gather_matmul"]

_INTERPRET = False
# decode-shaped calls (at most this many slot rows) take the Pallas
# kernel; larger row counts are prefill-shaped and MXU-bound, where the
# plain XLA gather-einsum is already optimal
_GATHER_MAX_ROWS = 64


def _xla_gather_matmul(x, a, b, scale, idx):
    """take + einsum reference path: [S, K] x banks -> [S, M]."""
    xf = x.astype(jnp.float32)
    aw = jnp.take(a, idx, axis=0).astype(jnp.float32)   # [S, r, K]
    bw = jnp.take(b, idx, axis=0).astype(jnp.float32)   # [S, r, M]
    h = jnp.einsum("sk,srk->sr", xf, aw)
    out = jnp.einsum("sr,srm->sm", h, bw)
    return (out * scale[idx].astype(jnp.float32)[:, None]).astype(x.dtype)


def _lora_kernel(idx_ref, x_ref, a_ref, b_ref, s_ref, o_ref):
    """One slot per program: both rank-r tiles arrive via the
    idx-indexed BlockSpecs, so the body is two tiny dots + a scale."""
    del idx_ref                       # consumed by the index maps
    a = a_ref[0]                                        # [r, K]
    b = b_ref[0]                                        # [r, M]
    h = jax.lax.dot_general(
        x_ref[...], a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # [1, r]
    acc = jax.lax.dot_general(
        h.astype(b.dtype), b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # [1, M]
    o_ref[...] = (acc * s_ref[0, 0]).astype(o_ref.dtype)


def _pallas_gather_matmul(x, a, b, scale, idx):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, k = x.shape
    _, r, m = b.shape
    svec = scale[idx].astype(jnp.float32).reshape(s, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, k), lambda i, idx_ref: (i, 0)),
            pl.BlockSpec((1, r, k), lambda i, idx_ref: (idx_ref[i], 0, 0)),
            pl.BlockSpec((1, r, m), lambda i, idx_ref: (idx_ref[i], 0, 0)),
            pl.BlockSpec((1, 1), lambda i, idx_ref: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, m), lambda i, idx_ref: (i, 0)),
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            _lora_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s, m), x.dtype),
            interpret=_INTERPRET,
            name="lora_matmul",
        )(idx.astype(jnp.int32), x, a, b, svec)


def lora_gather_matmul(x, a, b, scale, idx):
    """Per-row adapter delta: ``x [S, K]`` against banks ``a [N, r, K]``
    / ``b [N, r, M]`` with per-bank-row ``scale [N]`` (alpha / r) and
    per-slot bank indices ``idx [S]`` -> ``[S, M]`` in ``x.dtype``.

    Bank row 0 is the zeroed no-adapter row by convention, so a mixed
    batch (some slots dense, some adapterized) is one program."""
    if x.ndim != 2:
        raise ValueError(f"x must be [S, K], got {x.shape}")
    if a.shape[0] != b.shape[0] or a.shape[1] != b.shape[1]:
        raise ValueError(f"bank mismatch: a {a.shape} vs b {b.shape}")
    if x.shape[1] != a.shape[2]:
        raise ValueError(f"matmul K mismatch: x has {x.shape[1]}, "
                         f"bank A {a.shape[2]}")
    use_pallas = (
        x.shape[0] <= _GATHER_MAX_ROWS
        and (_INTERPRET or jax.default_backend() not in ("cpu",)))
    if use_pallas:      # a kernel failure raises: no XLA rescue
        return _pallas_gather_matmul(x, a, b, scale, idx)
    return _xla_gather_matmul(x, a, b, scale, idx)


def lora_delta(lora, key, li, x, idx):
    """Adapter delta for projection ``key`` at layer ``li`` of a packed
    LoRA bank (``serving.lora`` layout: ``lora["a"][key] [L, N, r, K]``,
    ``lora["b"][key] [L, N, r, M]``, ``lora["scale"] [N]``).

    ``x`` is ``[..., K]``; ``idx`` is an int32 per-row bank-index vector
    aligned with ``x``'s flattened leading dims, or a scalar (whole call
    under one adapter — the per-sequence prefill shape)."""
    a = lora["a"][key][li]                              # [N, r, K]
    b = lora["b"][key][li]                              # [N, r, M]
    scale = lora["scale"]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    idx = jnp.asarray(idx, jnp.int32)
    if idx.ndim == 0:
        # single-adapter call: one dynamic bank row, plain dense matmuls
        aw = a[idx].astype(jnp.float32)                 # [r, K]
        bw = b[idx].astype(jnp.float32)                 # [r, M]
        h = jax.lax.dot_general(
            x2.astype(jnp.float32), aw, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = jax.lax.dot_general(
            h, bw, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = (out * scale[idx].astype(jnp.float32)).astype(x.dtype)
    else:
        out = lora_gather_matmul(x2, a, b, scale, idx)
    return out.reshape(*lead, out.shape[-1])
