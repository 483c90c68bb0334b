"""Tensor-parallel transformer layers for the serving runner.

Per-shard mirrors of ``models/generation._decode_layer_paged``,
``_prefill_layer``, and ``serving/engine._prefill_layer_cached``,
written to run inside a ``shard_map`` over the ``tp`` mesh axis:

  * q/k/v, gate, and up are column-sharded — each device projects its
    own ``nh/tp`` query heads, ``kvh/tp`` KV heads, and ``I/tp`` FFN
    columns, so local head counts come from the weight shard shapes;
  * attention over the paged pool is head-parallel (each head's softmax
    sees its full sequence locally — the pool is sharded on the head
    axis, not the token axis), so no collective runs inside attention;
  * o and down are row-sharded; their partial products are the ONLY two
    all-reduce points per layer (``psum`` over ``tp``), exactly where
    Megatron-style TP places them.

FUSED weight paths are intentionally absent (the runner rejects fused
states for ``tp>1`` up front), but every matmul routes through
``models.generation._mm``: per-projection ``QuantizedWeight`` shards
(int8/int4 + per-output-channel scale) take the weight-only matmul
path, and plain arrays lower to the identical ``@`` the bodies always
used — the dense jaxpr is unchanged.

Pool shapes.  The decode bodies (``decode_layer_paged_tp``,
``decode_layer_paged_quant``) take every layer's pools whole,
``[L, P, kvH/tp, ps, D]`` (scales ``[L, P, kvH/tp, ps]``), with their
layer ``li``: the row scatter and the attention index the layer, and the
bodies return the same whole arrays, so a donated pool is updated where
it lies.  The cached-prefill bodies only read the resident prefix and
take one layer's ``[P, kvH/tp, ps, D]``; their program writes the pool.

The ``*_quant`` bodies are the int8-KV-page mirrors: pools are int8
with per-(page-row, head) f32 scale arrays, new KV quantizes on write
inside the same traced step, and attention dequantizes fused into the
page gather.  They serve BOTH construction modes (``axis=None`` is the
single-chip runner; an axis name marks the shard_map context), so the
dense bodies stay byte-identical when quantization is off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...models.generation import _ffn, _mm, _qkv_proj
from ...models.llama import _rotate_half
from ...models.llama_hybrid import _rms
from ...ops.pallas.lora_matmul import lora_delta
from ...ops.pallas.paged_attention import (gather_kv_pages,
                                           gather_kv_pages_quant,
                                           paged_attention_quant,
                                           quantize_kv_rows,
                                           select_paged_attention)

__all__ = ["decode_layer_paged_tp", "prefill_layer_tp",
           "prefill_layer_cached_tp", "decode_layer_paged_quant",
           "prefill_layer_cached_quant"]


def _local_qkv(w, h, hd, lora=(), aidx=None, li=0):
    """Project with the local weight shards; head counts are derived
    from the shard widths (``nh_local = nh / tp`` etc.).  LoRA bank B
    tensors for q/k/v are column-sharded exactly like the base
    weights, so the deltas land on this shard's own heads."""
    q, k, v = _mm(h, w["q"]), _mm(h, w["k"]), _mm(h, w["v"])
    if lora:
        q = q + lora_delta(lora, "q", li, h, aidx)
        k = k + lora_delta(lora, "k", li, h, aidx)
        v = v + lora_delta(lora, "v", li, h, aidx)
    return q, k, v, q.shape[-1] // hd, k.shape[-1] // hd


def _ffn_tp(w, h, axis, lora=(), aidx=None, li=0):
    """Column-sharded gate/up, row-sharded down: the partial down
    product is one of the layer's two all-reduces.  The down adapter's
    A is row-sharded like the base weight, so its partial delta joins
    the SAME psum (contraction splits linearly) — LoRA adds zero
    collectives."""
    g, u = _mm(h, w["gate"]), _mm(h, w["up"])
    if lora:
        g = g + lora_delta(lora, "gate", li, h, aidx)
        u = u + lora_delta(lora, "up", li, h, aidx)
    act = jax.nn.silu(g) * u
    part = _mm(act, w["down"])
    if lora:
        part = part + lora_delta(lora, "down", li, act, aidx)
    return jax.lax.psum(part, axis)


def decode_layer_paged_tp(w, x, kpool, vpool, table, cos1, sin1, pos,
                          cfg, axis, lora=(), aidx=None, *, li):
    """Per-shard paged decode layer ``li``: ``x`` [B, H] replicated,
    pools [L, P, kvH/tp, ps, D] local and whole, ``table``/``pos``
    replicated.  Returns (out replicated, kpool, vpool local, whole) —
    mirror of ``_decode_layer_paged`` with the o/down all-reduces."""
    b = x.shape[0]
    hd = cfg.head_dim
    ps = kpool.shape[3]
    with jax.named_scope("attn.qkv"):
        h = _rms(x[:, None], w["ln1"], cfg.rms_norm_eps)[:, 0]
        qp, kp, vp, nh_l, kvh_l = _local_qkv(w, h, hd, lora, aidx, li)
        q = qp.reshape(b, nh_l, hd)
        k = kp.reshape(b, kvh_l, hd)
        v = vp.reshape(b, kvh_l, hd)
        cos_c = cos1[:, None, :].astype(q.dtype)
        sin_c = sin1[:, None, :].astype(q.dtype)
        q = q * cos_c + _rotate_half(q) * sin_c
        k = k * cos_c + _rotate_half(k) * sin_c

    with jax.named_scope("kv.write"):
        page = jnp.take_along_axis(table, (pos // ps)[:, None], axis=1)[:, 0]
        off = pos % ps
        idx = (li, page[:, None], jnp.arange(kvh_l)[None, :], off[:, None])
        kpool = kpool.at[idx].set(k)
        vpool = vpool.at[idx].set(v)

    with jax.named_scope("attn.decode"):
        attn = select_paged_attention(tp_axis=axis)(
            q, kpool, vpool, li, table, pos + 1).reshape(b, nh_l * hd)
    with jax.named_scope("attn.out"):
        part = _mm(attn, w["o"])
        if lora:          # o's A is row-sharded: partial delta, same psum
            part = part + lora_delta(lora, "o", li, attn, aidx)
        x = x + jax.lax.psum(part, axis)
    with jax.named_scope("mlp"):
        h = _rms(x[:, None], w["ln2"], cfg.rms_norm_eps)[:, 0]
        return x + _ffn_tp(w, h, axis, lora, aidx, li), kpool, vpool


def prefill_layer_tp(w, x, cos, sin, mask, cfg, axis, lora=(),
                     aidx=None, li=0):
    """Per-shard prefill layer: ``x`` [B, S, H] replicated; returns
    (out replicated, k/v caches [B, S, kvH/tp, D] local)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("attn.qkv"):
        h = _rms(x, w["ln1"], cfg.rms_norm_eps)
        qp, kp, vp, nh_l, kvh_l = _local_qkv(w, h, hd, lora, aidx, li)
        q = qp.reshape(b, s, nh_l, hd)
        k = kp.reshape(b, s, kvh_l, hd)
        v = vp.reshape(b, s, kvh_l, hd)
        cos_c = cos[None, :, None, :].astype(q.dtype)
        sin_c = sin[None, :, None, :].astype(q.dtype)
        q = q * cos_c + _rotate_half(q) * sin_c
        k = k * cos_c + _rotate_half(k) * sin_c

    with jax.named_scope("attn.prefill"):
        from ...ops.pallas.flash_attention import sdpa
        attn = sdpa(q, k, v, attn_mask=mask[:, None, None, :],
                    is_causal=True).reshape(b, s, nh_l * hd)
    with jax.named_scope("attn.out"):
        part = _mm(attn, w["o"])
        if lora:
            part = part + lora_delta(lora, "o", li, attn, aidx)
        x = x + jax.lax.psum(part, axis)
    with jax.named_scope("mlp"):
        h = _rms(x, w["ln2"], cfg.rms_norm_eps)
        return x + _ffn_tp(w, h, axis, lora, aidx, li), k, v


def prefill_layer_cached_tp(w, x, kpool, vpool, row, cos_s, sin_s, mask,
                            cfg, axis, lora=(), aidx=None, li=0):
    """Per-shard cached-suffix prefill layer: suffix queries attend the
    resident prefix gathered from the LOCAL pool shard (prefix keys for
    this device's heads live on this device) concatenated with the
    suffix's own k/v.  Mirror of ``engine._prefill_layer_cached`` plus
    the o/down all-reduces; returns (out, k_suffix, v_suffix local)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("attn.qkv"):
        h = _rms(x, w["ln1"], cfg.rms_norm_eps)
        qp, kp, vp, nh_l, kvh_l = _local_qkv(w, h, hd, lora, aidx, li)
        q = qp.reshape(b, s, nh_l, hd)
        k = kp.reshape(b, s, kvh_l, hd)
        v = vp.reshape(b, s, kvh_l, hd)
        cos_c = cos_s[None, :, None, :].astype(q.dtype)
        sin_c = sin_s[None, :, None, :].astype(q.dtype)
        q = q * cos_c + _rotate_half(q) * sin_c
        k = k * cos_c + _rotate_half(k) * sin_c

    with jax.named_scope("attn.prefill"):
        kpre = gather_kv_pages(kpool, row)[None]
        vpre = gather_kv_pages(vpool, row)[None]
        from ...ops.pallas.flash_attention import sdpa
        kcat = jnp.concatenate([kpre.astype(k.dtype), k], axis=1)
        vcat = jnp.concatenate([vpre.astype(v.dtype), v], axis=1)
        attn = sdpa(q, kcat, vcat, attn_mask=mask,
                    is_causal=False).reshape(b, s, nh_l * hd)
    with jax.named_scope("attn.out"):
        part = _mm(attn, w["o"])
        if lora:
            part = part + lora_delta(lora, "o", li, attn, aidx)
        x = x + jax.lax.psum(part, axis)
    with jax.named_scope("mlp"):
        h = _rms(x, w["ln2"], cfg.rms_norm_eps)
        return x + _ffn_tp(w, h, axis, lora, aidx, li), k, v


# ------------------------------------------------- int8 KV page bodies
def _proj_qkv(w, h, cfg, axis, lora=(), aidx=None, li=0):
    """(q, k, v, nh_local, kvh_local) for either construction mode:
    single-chip (``axis=None``) goes through ``_qkv_proj`` so fused
    quantized states keep their one-GEMV path; per-shard derives local
    head counts from the shard widths like ``_local_qkv``.  LoRA
    deltas stay f32/bf16 ON TOP of the weight-only matmuls — quantized
    base weights compose with any adapter."""
    hd = cfg.head_dim
    if axis is None:
        qp, kp, vp = _qkv_proj(w, h, cfg.num_attention_heads,
                               cfg.num_key_value_heads, hd, lora, aidx,
                               li)
    else:
        qp, kp, vp = _mm(h, w["q"]), _mm(h, w["k"]), _mm(h, w["v"])
        if lora:
            qp = qp + lora_delta(lora, "q", li, h, aidx)
            kp = kp + lora_delta(lora, "k", li, h, aidx)
            vp = vp + lora_delta(lora, "v", li, h, aidx)
    return qp, kp, vp, qp.shape[-1] // hd, kp.shape[-1] // hd


def _out_reduce(part, axis):
    """Row-sharded output projection: psum inside a shard_map, identity
    on the single-chip path."""
    return part if axis is None else jax.lax.psum(part, axis)


def _ffn_quant(w, h, axis, lora=(), aidx=None, li=0):
    if axis is None:
        return _ffn(w, h, lora, aidx, li)
    return _ffn_tp(w, h, axis, lora, aidx, li)


def decode_layer_paged_quant(w, x, kpool, vpool, kscale, vscale, table,
                             cos1, sin1, pos, cfg, axis=None, lora=(),
                             aidx=None, *, li):
    """Paged decode layer ``li`` over int8 KV pools [L, P, kvH, ps, D]
    and their scale pools [L, P, kvH, ps], all whole: quantize this
    token's k/v rows on write (per-(token, head) scale into the scale
    pools — same traced step, no extra host sync), attend through the
    dequantizing gather.  ``axis=None`` is the tp=1 runner; an axis
    name runs the same body per-shard with the o/down all-reduces.
    Returns (out, kpool, vpool, kscale, vscale), the pools whole."""
    b = x.shape[0]
    hd = cfg.head_dim
    ps = kpool.shape[3]
    with jax.named_scope("attn.qkv"):
        h = _rms(x[:, None], w["ln1"], cfg.rms_norm_eps)[:, 0]
        qp, kp, vp, nh_l, kvh_l = _proj_qkv(w, h, cfg, axis, lora, aidx, li)
        q = qp.reshape(b, nh_l, hd)
        k = kp.reshape(b, kvh_l, hd)
        v = vp.reshape(b, kvh_l, hd)
        cos_c = cos1[:, None, :].astype(q.dtype)
        sin_c = sin1[:, None, :].astype(q.dtype)
        q = q * cos_c + _rotate_half(q) * sin_c
        k = k * cos_c + _rotate_half(k) * sin_c

    with jax.named_scope("kv.write"):
        page = jnp.take_along_axis(table, (pos // ps)[:, None], axis=1)[:, 0]
        off = pos % ps
        qk, sk = quantize_kv_rows(k)
        qv, sv = quantize_kv_rows(v)
        idx = (li, page[:, None], jnp.arange(kvh_l)[None, :], off[:, None])
        kpool = kpool.at[idx].set(qk)
        vpool = vpool.at[idx].set(qv)
        kscale = kscale.at[idx].set(sk)
        vscale = vscale.at[idx].set(sv)

    with jax.named_scope("attn.decode"):
        attn = paged_attention_quant(
            q, kpool, vpool, kscale, vscale, li, table, pos + 1,
            tp_axis=axis).reshape(b, nh_l * hd)
    with jax.named_scope("attn.out"):
        part = _mm(attn, w["o"])
        if lora:
            part = part + lora_delta(lora, "o", li, attn, aidx)
        x = x + _out_reduce(part, axis)
    with jax.named_scope("mlp"):
        h = _rms(x[:, None], w["ln2"], cfg.rms_norm_eps)[:, 0]
        return (x + _ffn_quant(w, h, axis, lora, aidx, li), kpool, vpool,
                kscale, vscale)


def prefill_layer_cached_quant(w, x, kpool, vpool, kscale, vscale, row,
                               cos_s, sin_s, mask, cfg, axis=None,
                               lora=(), aidx=None, li=0):
    """Cached-suffix prefill layer over int8 KV pools: the resident
    prefix dequantizes through the scale-aware gather; the suffix's own
    k/v stay float here (the runner quantizes them at the pool write).
    Returns (out, k_suffix, v_suffix) like the dense mirrors."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("attn.qkv"):
        h = _rms(x, w["ln1"], cfg.rms_norm_eps)
        qp, kp, vp, nh_l, kvh_l = _proj_qkv(w, h, cfg, axis, lora, aidx, li)
        q = qp.reshape(b, s, nh_l, hd)
        k = kp.reshape(b, s, kvh_l, hd)
        v = vp.reshape(b, s, kvh_l, hd)
        cos_c = cos_s[None, :, None, :].astype(q.dtype)
        sin_c = sin_s[None, :, None, :].astype(q.dtype)
        q = q * cos_c + _rotate_half(q) * sin_c
        k = k * cos_c + _rotate_half(k) * sin_c

    with jax.named_scope("attn.prefill"):
        kpre = gather_kv_pages_quant(kpool, kscale, row, k.dtype)[None]
        vpre = gather_kv_pages_quant(vpool, vscale, row, v.dtype)[None]
        from ...ops.pallas.flash_attention import sdpa
        kcat = jnp.concatenate([kpre, k], axis=1)
        vcat = jnp.concatenate([vpre, v], axis=1)
        attn = sdpa(q, kcat, vcat, attn_mask=mask,
                    is_causal=False).reshape(b, s, nh_l * hd)
    with jax.named_scope("attn.out"):
        part = _mm(attn, w["o"])
        if lora:
            part = part + lora_delta(lora, "o", li, attn, aidx)
        x = x + _out_reduce(part, axis)
    with jax.named_scope("mlp"):
        h = _rms(x, w["ln2"], cfg.rms_norm_eps)
        return x + _ffn_quant(w, h, axis, lora, aidx, li), k, v
