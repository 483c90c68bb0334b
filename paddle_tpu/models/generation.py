"""Autoregressive generation with a static-shape KV cache.

Reference analog: the decoding stack the reference exposes through
fused inference ops (paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu, masked_multihead_attention) and
PaddleNLP's generate() loop.

TPU formulation: the whole decode is ONE jitted program —
  * prefill: full-sequence forward over the (right-padded) prompt fills
    a kv-head-major [L, B, kvH, T, D] cache; prompt lengths are data,
    shapes are static.
  * decode: `lax.scan` over max_new_tokens, each step one-token
    attention against the cache (dot-products on the MXU, no [S,S]
    materialization); the per-batch cache write is a positional
    compare-and-select (positions differ per row, so a plain
    dynamic_update_slice does not apply).
  * sampling: greedy / temperature / top-k / top-p, all shape-static
    (top-p via sorted-cumsum masking).
No Python-loop-per-token, no retrace per step, no dynamic shapes.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.lora_matmul import lora_delta
from ..ops.pallas.paged_attention import PagedKV
from .llama import LlamaConfig, _rope_tables, _rotate_half
from .llama_hybrid import _rms

__all__ = ["GenerationConfig", "generate", "build_generate_fn",
           "quantize_state"]

_FN_CACHE: dict = {}   # (config fields, prompt_len, gen fields) -> jitted fn
_FN_CACHE_MAX = 16


def astuple_cfg(cfg):
    """Value-based cache key: id(cfg) can be reused after GC."""
    import dataclasses
    return tuple(sorted(dataclasses.asdict(cfg).items()))


@dataclass
class GenerationConfig:
    max_new_tokens: int = 64
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: int | None = None
    pad_token_id: int = 0
    seed: int = 0


# ------------------------------------------------------------- weight view
def _mm(h, w):
    """Matmul against a raw weight, a legacy ``(int8, scale)`` pair, or
    a :class:`~paddle_tpu.ops.pallas.quant_matmul.QuantizedWeight`.

    The quantized path runs the Pallas weight-only GEMV kernel at
    decode shapes (int8 tiles stream HBM->VMEM, dequant in-register,
    per-channel scale fused on the f32 accumulator — the reference
    weight_only_gemv.cu role); prefill-shaped calls and off-TPU
    backends take the XLA dequant-into-matmul path inside
    weight_only_matmul."""
    from ..ops.pallas.quant_matmul import QuantizedWeight, weight_only_matmul
    if isinstance(w, tuple):        # legacy (int8, scale) pair
        w = QuantizedWeight(w[0], w[1], kind="int8")
    if isinstance(w, QuantizedWeight):
        return weight_only_matmul(h, w)
    return h @ w


_QUANT_KEYS = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
               "self_attn.v_proj.weight", "self_attn.o_proj.weight",
               "mlp.gate_proj.weight", "mlp.up_proj.weight",
               "mlp.down_proj.weight")


def quantize_state(state, algo="weight_only_int8"):
    """Replace every matmul weight in a generation state dict with a
    :class:`QuantizedWeight` (embeddings stay dense: they are gathers,
    not matmuls).  int4 weights are nibble-packed [K/2, N] — a quarter
    of the bf16 HBM footprint.

    q/k/v and gate/up are quantized FUSED (columns concatenated before
    per-output-channel quantization — bit-identical to separate, since
    the scale is per column) so the decode loop issues one GEMV kernel
    where it issued three: at B=8 decode shapes the launch count, not
    the flops, is the cost.  Contract: the per-projection q/k/v and
    gate/up keys are ALSO quantized individually, so every matmul key
    in the returned dict is a QuantizedWeight — consumers reading the
    per-projection keys directly (instead of the *_fused entries the
    decode loop prefers) still get the quantized path.  The reference
    analog is converting a deploy model through weight_quantize before
    serving (python/paddle/nn/quant)."""
    from ..nn.quant import weight_quantize
    from ..ops.pallas.quant_matmul import QuantizedWeight

    kind = "int4" if algo.endswith("int4") else "int8"

    def quant(arr):
        q, scale = weight_quantize.__op_body__(arr, algo)
        return QuantizedWeight(q, scale, kind=kind, k=arr.shape[0])

    out = dict(state)
    for name in state:
        p, _, leaf = name.rpartition(".self_attn.q_proj.weight")
        if leaf == "" and p:
            pre = p + ".self_attn."
            out[pre + "qkv_fused.weight"] = quant(jnp.concatenate(
                [state[pre + "q_proj.weight"],
                 state[pre + "k_proj.weight"],
                 state[pre + "v_proj.weight"]], axis=1))
        p, _, leaf = name.rpartition(".mlp.gate_proj.weight")
        if leaf == "" and p:
            pre = p + ".mlp."
            out[pre + "gateup_fused.weight"] = quant(jnp.concatenate(
                [state[pre + "gate_proj.weight"],
                 state[pre + "up_proj.weight"]], axis=1))
    for name, arr in state.items():
        if name.endswith(_QUANT_KEYS) or name == "lm_head.weight":
            # fused members included: the returned state is UNIFORMLY
            # quantized (r4 advisor: a consumer reading q_proj.weight
            # directly must not silently run dense)
            out[name] = quant(arr)
    return out


def _layer_weights(state, i):
    p = f"llama.layers.{i}."
    w = {
        "ln1": state[p + "input_layernorm.weight"],
        "q": state[p + "self_attn.q_proj.weight"],
        "k": state[p + "self_attn.k_proj.weight"],
        "v": state[p + "self_attn.v_proj.weight"],
        "o": state[p + "self_attn.o_proj.weight"],
        "ln2": state[p + "post_attention_layernorm.weight"],
        "gate": state[p + "mlp.gate_proj.weight"],
        "up": state[p + "mlp.up_proj.weight"],
        "down": state[p + "mlp.down_proj.weight"],
    }
    if p + "self_attn.qkv_fused.weight" in state:   # quantized serving
        w["qkv"] = state[p + "self_attn.qkv_fused.weight"]
    if p + "mlp.gateup_fused.weight" in state:
        w["gateup"] = state[p + "mlp.gateup_fused.weight"]
    return w


def _qkv_proj(w, h, cfg, lora=(), aidx=None, li=0):
    """(q, k, v) projections, flat — one fused GEMV when the quantized
    state provides it (split by the config's head counts: one chip
    only, the mesh runner refuses fused states), three matmuls
    otherwise, whose widths are this shard's own (callers read their
    head counts off them: ``q.shape[-1] // head_dim``).  A non-empty
    ``lora`` bank adds each slot's rank-r adapter delta on top (``aidx``
    indexes the bank per row; ``lora=()`` is the dense path,
    byte-identical jaxpr — zero extra pytree leaves, no traced ops); the
    bank's B for q/k/v is column-sharded like the base weights, so the
    deltas land on this shard's own heads."""
    if "qkv" in w:
        nq = cfg.num_attention_heads * cfg.head_dim
        nkv = cfg.num_key_value_heads * cfg.head_dim
        qkv = _mm(h, w["qkv"])
        q, k, v = qkv[..., :nq], qkv[..., nq:nq + nkv], qkv[..., nq + nkv:]
    else:
        q, k, v = _mm(h, w["q"]), _mm(h, w["k"]), _mm(h, w["v"])
    if lora:
        q = q + lora_delta(lora, "q", li, h, aidx)
        k = k + lora_delta(lora, "k", li, h, aidx)
        v = v + lora_delta(lora, "v", li, h, aidx)
    return q, k, v


def _psum(part, axis):
    """A row-sharded projection's partial product, summed over the mesh
    axis ``axis``; the whole product itself on one chip (``None``)."""
    return part if axis is None else jax.lax.psum(part, axis)


def _out_proj(w, attn, axis, lora, aidx, li):
    """``o`` on the heads' outputs.  The adapter's A for ``o`` is
    row-sharded like the base weight, so its partial delta joins the
    same all-reduce."""
    o = _mm(attn, w["o"])
    if lora:
        o = o + lora_delta(lora, "o", li, attn, aidx)
    return _psum(o, axis)


def _ffn(w, h, axis=None, lora=(), aidx=None, li=0):
    """SwiGLU: column-sharded gate/up (one fused product where the
    state has it), row-sharded down.  The down adapter's A is
    row-sharded like the base weight, so its partial delta joins the
    SAME all-reduce (contraction splits linearly) — LoRA adds zero
    collectives."""
    if "gateup" in w:
        gu = _mm(h, w["gateup"])
        half = gu.shape[-1] // 2
        g, u = gu[..., :half], gu[..., half:]
    else:
        g, u = _mm(h, w["gate"]), _mm(h, w["up"])
    if lora:
        g = g + lora_delta(lora, "gate", li, h, aidx)
        u = u + lora_delta(lora, "up", li, h, aidx)
    act = jax.nn.silu(g) * u
    out = _mm(act, w["down"])
    if lora:
        out = out + lora_delta(lora, "down", li, act, aidx)
    return _psum(out, axis)


def _rope_at(cos, sin, pos):
    """cos/sin: [max_len, D]; pos: [...] -> [..., D]"""
    return jnp.take(cos, pos, axis=0), jnp.take(sin, pos, axis=0)


def residual_add(x, y, cfg):
    """``x + y``, or ``x + residual_multiplier * y`` where the model
    description has one (no attribute: the plain sum, the same
    program)."""
    rm = getattr(cfg, "residual_multiplier", None)
    return x + y if rm is None else x + y * jnp.asarray(rm, y.dtype)


def _position_and_scale(q, k, cos, sin, cfg, width):
    """What a description says of q and k before attention: the rotary
    encoding unless ``position_embedding_type`` is "nope", and, where it
    names an ``attention_multiplier``, that softmax scale in place of
    the kernels' own ``1 / sqrt(width)`` (``width`` the head dim the
    kernel will see: several heads' where a pool row packs them, and
    then ``1 / sqrt(head dim)`` is restored), folded into q."""
    if getattr(cfg, "position_embedding_type", "rope") != "nope":
        q = q * cos + _rotate_half(q) * sin
        k = k * cos + _rotate_half(k) * sin
    mult = getattr(cfg, "attention_multiplier", None)
    if mult is not None:
        q = q * jnp.asarray(mult * np.sqrt(width), q.dtype)
    elif width != q.shape[-1]:      # packed rows: the head's own width
        q = q * jnp.asarray(np.sqrt(width / q.shape[-1]), q.dtype)
    return q, k


def mlp_block(w, x, cfg, *, li, axis=None, lora=(), aidx=None):
    """A layer's second half, on one token a row (x [B, H]) or a prompt
    (x [B, S, H]): ``x + MLP(RMSNorm(x))``.  Every layer of every family
    the runner serves through ``_ffn`` ends here."""
    with jax.named_scope("mlp"):
        if x.ndim == 2:
            h = _rms(x[:, None], w["ln2"], cfg.rms_norm_eps)[:, 0]
        else:
            h = _rms(x, w["ln2"], cfg.rms_norm_eps)
        return residual_add(x, _ffn(w, h, axis, lora, aidx, li), cfg)


def _attend_packed(cache, q, group: int, pack: int, li, table, lens, axis):
    """``cache.attend`` over pools that hold ``pack`` KV heads side by
    side in one row (a head dim below the TPU's 128 lanes: the page
    copies stay whole lane rows and no lane is padding).  Each query
    head's values go to its own KV head's part of the row and zeros to
    the rest, so its scores are its head's alone; of the output row it
    keeps that part.  ``group`` query heads share a KV head."""
    b, nh, d = q.shape
    # [nh, pack] one-hot: the part of the row that is head h's KV head
    part = (jnp.arange(nh) // group) % pack
    parts = (part[:, None] == jnp.arange(pack)[None, :]).astype(q.dtype)
    wide = (q[:, :, None, :] * parts[None, :, :, None]).reshape(
        b, nh, pack * d)
    out = cache.attend(wide, li, table, lens, axis).reshape(b, nh, pack, d)
    return (out * parts[None, :, :, None]).sum(axis=2)


# ------------------------------------------------------- the layer bodies
# One Llama-family decoder layer, written twice: over a prompt
# (``prefill_layer``) and over one token a slot against the paged cache
# (``decode_layer``).  Both serve one chip (``axis=None``) and a shard
# of a tensor-parallel mesh (``axis`` the mesh axis name, inside a
# ``shard_map``):
#
#   * q/k/v, gate and up are column-sharded — each device projects its
#     own ``nh/tp`` query heads, ``kvh/tp`` KV heads and ``I/tp`` FFN
#     columns, so local head counts come from the projected widths;
#   * attention is head-parallel (each head's softmax sees its whole
#     sequence locally — the pools are sharded on the head axis, not
#     the token axis), so no collective runs inside attention;
#   * o and down are row-sharded; their partial products are the ONLY
#     two all-reduce points per layer (``psum`` over ``axis``), exactly
#     where Megatron-style TP places them.
#
# Every matmul routes through ``_mm``: ``QuantizedWeight`` leaves (int8 /
# int4 + per-output-channel scale) take the weight-only matmul, plain
# arrays the ``@`` they always did.  The scopes (``attn.qkv``,
# ``kv.write``, ``attn.decode`` / ``attn.prefill``, ``attn.out``,
# ``mlp``) are what the benchmark's breakdown names device time by.
def prefill_attention(w, x, cos, sin, mask, cfg: LlamaConfig, *, li,
                      axis=None, lora=(), aidx=None, prefix=None):
    """A layer's first half over a prompt: ``x + Attention(RMSNorm(x))``
    (a family whose block is the attention alone ends here).  x: [B, S,
    H]; cos/sin [S, D] at the rows' positions.  Returns
    (out, k, v [B, S, kvH, D]) — the caller owns the cache and writes
    k/v (keys already rotary-encoded, as every reader expects them).

    Without ``prefix`` the rows are a whole prompt: ``mask`` [B, S] is
    its key padding and attention is causal.  ``prefix=(kpre, vpre)``
    [B, Tpre, kvH, D] is a resident prefix already gathered from the
    cache for this layer: the rows attend it ahead of their own keys and
    ``mask`` [1, 1, S, Tpre + S] says everything (no causal flag)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("attn.qkv"):
        h = _rms(x, w["ln1"], cfg.rms_norm_eps)
        qp, kp, vp = _qkv_proj(w, h, cfg, lora, aidx, li)
        q = qp.reshape(b, s, -1, hd)
        k = kp.reshape(b, s, -1, hd)
        v = vp.reshape(b, s, -1, hd)
        cos_c = None if cos is None else cos[None, :, None, :].astype(q.dtype)
        sin_c = None if sin is None else sin[None, :, None, :].astype(q.dtype)
        q, k = _position_and_scale(q, k, cos_c, sin_c, cfg, hd)

    with jax.named_scope("attn.prefill"):
        # flash path: causal + key-padding mask, GQA in-kernel, O(S) memory
        # (the naive [B,H,S,S] fp32 logits OOM long-prompt prefill)
        from ..ops.pallas.flash_attention import sdpa
        if prefix is None:
            attn = sdpa(q, k, v, attn_mask=mask[:, None, None, :],
                        is_causal=True)
        else:
            kcat = jnp.concatenate([prefix[0].astype(k.dtype), k], axis=1)
            vcat = jnp.concatenate([prefix[1].astype(v.dtype), v], axis=1)
            attn = sdpa(q, kcat, vcat, attn_mask=mask, is_causal=False)
        attn = attn.reshape(b, s, qp.shape[-1])
    with jax.named_scope("attn.out"):
        x = residual_add(x, _out_proj(w, attn, axis, lora, aidx, li), cfg)
    return x, k, v


def prefill_layer(w, x, cos, sin, mask, cfg: LlamaConfig, *, li,
                  axis=None, lora=(), aidx=None, prefix=None):
    """:func:`prefill_attention`, then the MLP half."""
    x, k, v = prefill_attention(w, x, cos, sin, mask, cfg, li=li, axis=axis,
                                lora=lora, aidx=aidx, prefix=prefix)
    return mlp_block(w, x, cfg, li=li, axis=axis, lora=lora, aidx=aidx), k, v


def decode_attention(w, x, cache, table, cos1, sin1, pos, cfg: LlamaConfig,
                     *, li, axis=None, lora=(), aidx=None):
    """A layer's first half against the paged cache, ``x +
    Attention(RMSNorm(x))``, layer ``li``: x [B, H] one token a row;
    ``cache`` a :class:`~paddle_tpu.ops.pallas.paged_attention.PagedKV`,
    every layer's pools, passed whole and returned whole — the row
    write and the attention both take the layer as an index, so donated
    pools are updated where they lie.  table [B, max_pages]; pos [B] is
    the CURRENT token's position.  The write targets page
    table[b, pos // ps] slot pos % ps — always a real reserved page;
    reads go through the cache (reference
    block_multi_head_attention_kernel.cu).  Returns (out, cache)."""
    b = x.shape[0]
    hd = cfg.head_dim
    ps = cache.k.shape[3]
    # KV heads a pool row holds: 1, but for a head dim under 128 lanes
    pack = cache.k.shape[4] // hd
    with jax.named_scope("attn.qkv"):
        h = _rms(x[:, None], w["ln1"], cfg.rms_norm_eps)[:, 0]
        qp, kp, vp = _qkv_proj(w, h, cfg, lora, aidx, li)
        q = qp.reshape(b, -1, hd)
        k = kp.reshape(b, -1, hd)
        v = vp.reshape(b, -1, hd)
        group = q.shape[1] // k.shape[1]    # query heads a KV head
        cos_c = None if cos1 is None else cos1[:, None, :].astype(q.dtype)
        sin_c = None if sin1 is None else sin1[:, None, :].astype(q.dtype)
        q, k = _position_and_scale(q, k, cos_c, sin_c, cfg, hd * pack)

    with jax.named_scope("kv.write"):
        page = jnp.take_along_axis(table, (pos // ps)[:, None], axis=1)[:, 0]
        if pack > 1:
            k = k.reshape(b, -1, hd * pack)
            v = v.reshape(b, -1, hd * pack)
        cache = cache.write(li, page, pos % ps, k, v)

    with jax.named_scope("attn.decode"):
        if pack > 1:
            attn = _attend_packed(cache, q, group, pack, li, table, pos + 1,
                                  axis)
        else:
            attn = cache.attend(q, li, table, pos + 1, axis)
        attn = attn.reshape(b, qp.shape[-1])
    with jax.named_scope("attn.out"):
        x = residual_add(x, _out_proj(w, attn, axis, lora, aidx, li), cfg)
    return x, cache


def decode_layer(w, x, cache, table, cos1, sin1, pos, cfg: LlamaConfig, *,
                 li, axis=None, lora=(), aidx=None):
    """:func:`decode_attention`, then the MLP half."""
    x, cache = decode_attention(w, x, cache, table, cos1, sin1, pos, cfg,
                                li=li, axis=axis, lora=lora, aidx=aidx)
    return (mlp_block(w, x, cfg, li=li, axis=axis, lora=lora, aidx=aidx),
            cache)


# ------------------------------------------- decode step, contiguous cache
def _decode_layer(w, x, kcache, vcache, cos1, sin1, pos, cfg: LlamaConfig):
    """x: [B, H] one token; kcache/vcache: [B, kvH, T, D] (kv-head-major,
    the decode kernel's tiling-friendly layout); pos: [B].  The cache of
    ``generate``'s dense and beam programs, and the reference the paged
    path is compared with."""
    b = x.shape[0]
    hd = cfg.head_dim
    h = _rms(x[:, None], w["ln1"], cfg.rms_norm_eps)[:, 0]
    qp, kp, vp = _qkv_proj(w, h, cfg)
    q = qp.reshape(b, -1, hd)
    k = kp.reshape(b, -1, hd)
    v = vp.reshape(b, -1, hd)
    cos_c = cos1[:, None, :].astype(q.dtype)
    sin_c = sin1[:, None, :].astype(q.dtype)
    q = q * cos_c + _rotate_half(q) * sin_c
    k = k * cos_c + _rotate_half(k) * sin_c

    # write this token's k/v at pos (per-batch positions).  A scatter —
    # NOT a compare-select over the whole cache: jnp.where materializes
    # a full cache copy per layer per step (~268 MB of HBM traffic at
    # the bench shapes), while .at[].set lowers to an in-place update
    # of one token row on the donated scan carry
    b_ids = jnp.arange(b)
    kcache = kcache.at[b_ids, :, pos, :].set(k, mode="drop")
    vcache = vcache.at[b_ids, :, pos, :].set(v, mode="drop")

    # blockwise cache attention kernel (ops/pallas/decode_attention.py);
    # transparently falls back to the einsum path off-TPU
    from ..ops.pallas.decode_attention import decode_attention
    attn = decode_attention(q, kcache, vcache, pos).reshape(b, qp.shape[-1])
    x = x + _mm(attn, w["o"])
    h = _rms(x[:, None], w["ln2"], cfg.rms_norm_eps)[:, 0]
    return (x + _ffn(w, h), kcache, vcache)


# --------------------------------------------------------------- sampling
def _sample(logits, key, gen: GenerationConfig):
    logits = logits.astype(jnp.float32)
    if not gen.do_sample:
        return jnp.argmax(logits, axis=-1)
    if gen.temperature != 1.0:
        logits = logits / jnp.float32(max(gen.temperature, 1e-6))
    if gen.top_k and gen.top_k > 0:
        k = min(gen.top_k, logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if gen.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p
        cutoff_idx = jnp.sum(cum < gen.top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


# ------------------------------------------------------------ paged main
def build_generate_fn_paged(config: LlamaConfig, gen: GenerationConfig,
                            prompt_len: int, page_size: int,
                            num_pages: int, max_pages: int):
    """Paged-cache generate: jitted (state, ids, lengths, key, table) ->
    tokens.  Pools are allocated inside (zeros) with static shapes from
    the PagedPool reservation; HBM scales with sum(len+new), not
    B * max_len (reference block_multi_head_attention serving path)."""
    L = config.num_hidden_layers
    kvh, hd = config.num_key_value_heads, config.head_dim
    T = prompt_len + gen.max_new_tokens
    assert T <= config.max_position_embeddings
    ps = page_size
    prompt_pages = -(-prompt_len // ps)

    def run(state, ids, lengths, key, table):
        b = ids.shape[0]
        dtype = state["llama.embed_tokens.weight"].dtype
        rope_len = max(T, prompt_pages * ps)
        cos, sin = _rope_tables(rope_len, config.head_dim,
                                config.rope_theta)
        cos = cos.astype(jnp.float32)
        sin = sin.astype(jnp.float32)

        kpool = jnp.zeros((L, num_pages, kvh, ps, hd), dtype)
        vpool = jnp.zeros((L, num_pages, kvh, ps, hd), dtype)

        # ---- prefill over the padded prompt, paging k/v into the pool
        x = jnp.take(state["llama.embed_tokens.weight"], ids, axis=0)
        pmask = jnp.arange(prompt_len)[None, :] < lengths[:, None]
        spad = prompt_pages * ps - prompt_len
        for i in range(L):
            w = _layer_weights(state, i)
            x, k, v = prefill_layer(w, x, cos[:prompt_len],
                                    sin[:prompt_len], pmask, config, li=i)
            kp = jnp.pad(k, ((0, 0), (0, spad), (0, 0), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (0, spad), (0, 0), (0, 0)))
            for p in range(prompt_pages):
                rows_k = kp[:, p * ps:(p + 1) * ps].swapaxes(1, 2)
                rows_v = vp[:, p * ps:(p + 1) * ps].swapaxes(1, 2)
                kpool = kpool.at[i, table[:, p]].set(rows_k)
                vpool = vpool.at[i, table[:, p]].set(rows_v)

        x = _rms(x, state["llama.norm.weight"], config.rms_norm_eps)
        head = state.get("lm_head.weight")

        def logits_of(h):
            if head is not None:
                return _mm(h, head)
            return h @ state["llama.embed_tokens.weight"].T

        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        key, sub = jax.random.split(key)
        tok = _sample(logits_of(last), sub, gen)

        done = jnp.zeros((b,), bool)
        if gen.eos_token_id is not None:
            done = done | (tok == gen.eos_token_id)

        def step(carry, key_t):
            tok, pos, cache, done = carry
            emb = jnp.take(state["llama.embed_tokens.weight"], tok,
                           axis=0)
            cos1, sin1 = _rope_at(cos, sin, pos)
            h = emb
            for i in range(L):
                w = _layer_weights(state, i)
                h, cache = decode_layer(w, h, cache, table, cos1, sin1,
                                        pos, config, li=i)
            h = _rms(h[:, None], state["llama.norm.weight"],
                     config.rms_norm_eps)[:, 0]
            nxt = _sample(logits_of(h), key_t, gen)
            if gen.eos_token_id is not None:
                nxt = jnp.where(done, gen.pad_token_id, nxt)
                done = done | (nxt == gen.eos_token_id)
            return (nxt, pos + 1, cache, done), tok

        keys = jax.random.split(key, gen.max_new_tokens)
        (tok, _, _, _), toks = jax.lax.scan(
            step, (tok.astype(ids.dtype), lengths.astype(jnp.int32),
                   PagedKV(kpool, vpool), done), keys)
        return jnp.concatenate([ids, toks.T.astype(ids.dtype)], axis=1)

    return jax.jit(run)


# ------------------------------------------------------------------ main
def _cache_len(prompt_len, max_new_tokens):
    """Padded cache length: the block-cache kernel needs 128 alignment
    (rope rows past max_position_embeddings exist but are never
    addressed); the XLA path skips it so tiny caches stay tiny."""
    from ..ops.pallas import decode_attention as _DA
    T = prompt_len + max_new_tokens
    if _DA.PALLAS_DECODE or _DA._INTERPRET:
        T = -(-T // 128) * 128
    return T


def _prefill_prompt(state, ids, lengths, cos, sin, config, prompt_len, T):
    """Shared prompt prefill (greedy + beam paths): returns
    (last [B, D] hidden of each prompt's final real token, logits_of,
    kcache [L, B, kvH, T, D], vcache)."""
    L = config.num_hidden_layers
    x = jnp.take(state["llama.embed_tokens.weight"], ids, axis=0)
    pmask = jnp.arange(prompt_len)[None, :] < lengths[:, None]
    kcaches, vcaches = [], []
    for i in range(L):
        w = _layer_weights(state, i)
        x, k, v = prefill_layer(w, x, cos[:prompt_len],
                                sin[:prompt_len], pmask, config, li=i)
        # kv-head-major cache layout [B, kvH, T, D]
        pad = ((0, 0), (0, 0), (0, T - prompt_len), (0, 0))
        kcaches.append(jnp.pad(k.swapaxes(1, 2), pad))
        vcaches.append(jnp.pad(v.swapaxes(1, 2), pad))
    kcache = jnp.stack(kcaches)
    vcache = jnp.stack(vcaches)

    x = _rms(x, state["llama.norm.weight"], config.rms_norm_eps)
    head = state.get("lm_head.weight")

    def logits_of(h):
        if head is not None:
            return _mm(h, head)
        return h @ state["llama.embed_tokens.weight"].T

    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return last, logits_of, kcache, vcache


def build_generate_fn(config: LlamaConfig, gen: GenerationConfig,
                      prompt_len: int):
    """Returns jitted (state, ids[B, prompt_len], lengths[B], key) ->
    tokens [B, prompt_len + max_new_tokens]."""
    L = config.num_hidden_layers
    T = _cache_len(prompt_len, gen.max_new_tokens)
    assert prompt_len + gen.max_new_tokens \
        <= config.max_position_embeddings

    def run(state, ids, lengths, key):
        b = ids.shape[0]
        cos, sin = _rope_tables(T, config.head_dim, config.rope_theta)
        cos = cos.astype(jnp.float32)
        sin = sin.astype(jnp.float32)

        last, logits_of, kcache, vcache = _prefill_prompt(
            state, ids, lengths, cos, sin, config, prompt_len, T)
        key, sub = jax.random.split(key)
        tok = _sample(logits_of(last), sub, gen)

        done = jnp.zeros((b,), bool)
        if gen.eos_token_id is not None:
            done = done | (tok == gen.eos_token_id)

        def step(carry, key_t):
            tok, pos, kcache, vcache, done = carry
            emb = jnp.take(state["llama.embed_tokens.weight"], tok, axis=0)
            cos1, sin1 = _rope_at(cos, sin, pos)
            h = emb
            newk, newv = [], []
            for i in range(L):
                w = _layer_weights(state, i)
                h, kc, vc = _decode_layer(w, h, kcache[i], vcache[i],
                                          cos1, sin1, pos, config)
                newk.append(kc)
                newv.append(vc)
            kcache = jnp.stack(newk)
            vcache = jnp.stack(newv)
            h = _rms(h[:, None], state["llama.norm.weight"],
                     config.rms_norm_eps)[:, 0]
            nxt = _sample(logits_of(h), key_t, gen)
            if gen.eos_token_id is not None:
                nxt = jnp.where(done, gen.pad_token_id, nxt)
                done = done | (nxt == gen.eos_token_id)
            return (nxt, pos + 1, kcache, vcache, done), tok

        keys = jax.random.split(key, gen.max_new_tokens)
        (tok, _, _, _, _), toks = jax.lax.scan(
            step, (tok.astype(ids.dtype), lengths.astype(jnp.int32),
                   kcache, vcache, done), keys)
        # toks[t] is the token sampled after t decode steps: exactly
        # max_new_tokens new tokens (the final carry is one beyond)
        return jnp.concatenate([ids, toks.T.astype(ids.dtype)], axis=1)

    return jax.jit(run)


def build_generate_fn_beam(config: LlamaConfig, gen: GenerationConfig,
                           prompt_len: int, num_beams: int):
    """Beam-search decoding with the KV cache (reference
    nn/decode.py BeamSearchDecoder semantics over the serving engine):
    fixed-shape [B, K, V] top-k merge per step under jax.lax.scan, beam
    ancestry resolved by a gather_tree backtrace — no ragged hypothesis
    sets, everything jits.  Finished beams emit only eos with log-prob 0
    (score freezes), matching the reference's noend mask."""
    L = config.num_hidden_layers
    K = num_beams
    T = _cache_len(prompt_len, gen.max_new_tokens)
    assert prompt_len + gen.max_new_tokens \
        <= config.max_position_embeddings
    eos = gen.eos_token_id

    def run(state, ids, lengths, key):
        b = ids.shape[0]
        cos, sin = _rope_tables(T, config.head_dim, config.rope_theta)
        cos = cos.astype(jnp.float32)
        sin = sin.astype(jnp.float32)

        last, logits_of, kcache, vcache = _prefill_prompt(
            state, ids, lengths, cos, sin, config, prompt_len, T)
        lp0 = jax.nn.log_softmax(
            logits_of(last).astype(jnp.float32), axis=-1)   # [B, V]
        V = lp0.shape[-1]
        # first step: top-K over the vocab seeds the beams
        log_probs, tok = jax.lax.top_k(lp0, K)              # [B, K]
        done = jnp.zeros((b, K), bool)
        if eos is not None:
            done = done | (tok == eos)

        # beams share the prefill cache: expand to [L, B*K, kvh, T, D]
        def expand(c):
            return jnp.repeat(c, K, axis=1)

        kcache, vcache = expand(kcache), expand(vcache)
        noend = jnp.full((V,), -1e9, jnp.float32)
        if eos is not None:
            noend = noend.at[eos].set(0.0)

        def step(carry, _):
            tok, pos, kcache, vcache, log_probs, done = carry
            flat_tok = tok.reshape(b * K)
            emb = jnp.take(state["llama.embed_tokens.weight"], flat_tok,
                           axis=0)
            posf = jnp.repeat(pos, K)
            cos1, sin1 = _rope_at(cos, sin, posf)
            h = emb
            newk, newv = [], []
            for i in range(L):
                w = _layer_weights(state, i)
                h, kc, vc = _decode_layer(w, h, kcache[i], vcache[i],
                                          cos1, sin1, posf, config)
                newk.append(kc)
                newv.append(vc)
            kcache = jnp.stack(newk)
            vcache = jnp.stack(newv)
            h = _rms(h[:, None], state["llama.norm.weight"],
                     config.rms_norm_eps)[:, 0]
            step_lp = jax.nn.log_softmax(
                logits_of(h).astype(jnp.float32), axis=-1) \
                .reshape(b, K, V)
            # finished beams: only eos continues, at zero cost
            step_lp = jnp.where(done[:, :, None], noend[None, None, :],
                                step_lp)
            cand = (log_probs[:, :, None] + step_lp).reshape(b, K * V)
            log_probs, flat_idx = jax.lax.top_k(cand, K)     # [B, K]
            parent = flat_idx // V
            nxt = flat_idx % V

            # reorder beam state by ancestry
            gidx = (jnp.arange(b)[:, None] * K + parent).reshape(-1)
            kcache = kcache[:, gidx]
            vcache = vcache[:, gidx]
            done = jnp.take_along_axis(done, parent, axis=1)
            if eos is not None:
                nxt = jnp.where(done, gen.pad_token_id, nxt)
                done = done | (nxt == eos)
            return ((nxt, pos + 1, kcache, vcache, log_probs, done),
                    (tok, parent))

        init = (tok.astype(jnp.int32), lengths.astype(jnp.int32),
                kcache, vcache, log_probs, done)
        (tok, _, _, _, log_probs, _), (toks, parents) = jax.lax.scan(
            step, init, None, length=gen.max_new_tokens - 1)
        # toks[t]: tokens in time-t beam order; scan's parent_j maps
        # time-(j+1) beams to time-j beams, so toks[t] pairs with
        # parents[t-1] — the seed row (t=0) has identity ancestry
        toks = jnp.concatenate([toks, tok[None]], axis=0)   # [N, B, K]
        parents = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(K), (1, b, K)), parents], axis=0)

        # backtrace ancestry (nn.functional gather_tree semantics)
        def bt(carry, inp):
            beam = carry
            t_tok, t_par = inp
            out = jnp.take_along_axis(t_tok, beam, axis=-1)
            beam = jnp.take_along_axis(t_par, beam, axis=-1)
            return beam, out

        init_beam = jnp.broadcast_to(jnp.arange(K), (b, K))
        _, seq_rev = jax.lax.scan(bt, init_beam,
                                  (toks[::-1], parents[::-1]))
        seqs = seq_rev[::-1]                                # [N, B, K]
        best = jnp.argmax(log_probs, axis=-1)               # [B]
        best_seq = jnp.take_along_axis(
            seqs, best[None, :, None], axis=2)[:, :, 0].T   # [B, N]
        return jnp.concatenate([ids, best_seq.astype(ids.dtype)], axis=1)

    return jax.jit(run)


def generate(model, input_ids, max_new_tokens=64, do_sample=False,
             temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
             pad_token_id=0, seed=0, lengths=None, cache="dense",
             page_size=128, weight_quant=None, num_beams=1):
    """User entry: model is a LlamaForCausalLM; input_ids [B, S] (right-
    padded if lengths given; new tokens overwrite the padded slots in the
    cache). Returns [B, S + max_new_tokens] ids.

    cache="paged" serves from a block-table pool (reference
    block_multi_head_attention): HBM and attention reads scale with each
    sequence's OWN length instead of the batch max — the win on ragged
    batches.

    num_beams > 1 runs beam search (reference nn/decode.py semantics)
    with the dense KV cache — a fixed-shape [B, K, V] top-k merge per
    scanned step."""
    from ..framework.tensor import Tensor

    ids = input_ids._data if isinstance(input_ids, Tensor) else \
        jnp.asarray(input_ids)
    b, s = ids.shape
    if lengths is None:
        lengths_np = np.full((b,), s, np.int32)
    else:
        lengths_np = np.asarray(
            lengths._data if isinstance(lengths, Tensor) else lengths,
            np.int32)
    lengths_arr = jnp.asarray(lengths_np)
    gen = GenerationConfig(
        max_new_tokens=max_new_tokens, do_sample=do_sample,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id, seed=seed)
    state = {k: (v._data if isinstance(v, Tensor) else v)
             for k, v in model.functional_state().items()}
    if weight_quant is not None:
        if weight_quant not in ("int8", "int4"):
            raise ValueError(f"weight_quant must be int8|int4, "
                             f"got {weight_quant!r}")
        # quantize once per (model weights, algo): serving loops call
        # generate() per request and must not re-quantize every call.
        # Keyed by identity of the source arrays (held strongly in the
        # cache, so ids cannot be reused); rebinding any weight (a
        # training step) misses and re-quantizes.
        wq_cache = getattr(model, "_wq_cache", None)
        src = {k: v for k, v in state.items()
               if k.endswith(_QUANT_KEYS) or k == "lm_head.weight"}
        if (wq_cache is not None and wq_cache["algo"] == weight_quant
                and wq_cache["src"].keys() == src.keys()
                and all(wq_cache["src"][k] is v for k, v in src.items())):
            qstate = wq_cache["state"]
        else:
            qstate = quantize_state(state, f"weight_only_{weight_quant}")
            model._wq_cache = {"algo": weight_quant, "src": src,
                               "state": qstate}
        # carry the quantized leaves AND the fused qkv/gateup entries
        state = dict(state, **{k: v for k, v in qstate.items()
                               if k in src
                               or k.endswith(("qkv_fused.weight",
                                              "gateup_fused.weight"))})
    from ..ops.pallas import decode_attention as _DA

    if num_beams > 1:
        if do_sample:
            raise ValueError("num_beams > 1 requires do_sample=False "
                             "(beam search is deterministic)")
        if cache == "paged":
            raise NotImplementedError(
                "beam search currently uses the dense cache "
                "(paged-beam reordering needs per-beam block tables)")
        cache_key = ("beam", astuple_cfg(model.config), s,
                     gen.max_new_tokens, num_beams, gen.eos_token_id,
                     gen.pad_token_id,
                     _DA.PALLAS_DECODE or _DA._INTERPRET, weight_quant)
        fn = _FN_CACHE.get(cache_key)
        if fn is None:
            if len(_FN_CACHE) >= _FN_CACHE_MAX:
                _FN_CACHE.pop(next(iter(_FN_CACHE)))
            fn = _FN_CACHE[cache_key] = build_generate_fn_beam(
                model.config, gen, s, num_beams)
        out = fn(state, ids, lengths_arr, jax.random.key(seed))
        return Tensor(out, stop_gradient=True)

    if cache == "paged":
        from ..ops.pallas.paged_attention import PagedPool
        pool = PagedPool(lengths_np, gen.max_new_tokens,
                         page_size=page_size,
                         min_table_width=-(-s // page_size))
        cache_key = ("paged", astuple_cfg(model.config), s,
                     gen.max_new_tokens, gen.do_sample, gen.temperature,
                     gen.top_k, gen.top_p, gen.eos_token_id,
                     gen.pad_token_id, pool.page_size, pool.num_pages,
                     pool.max_pages, weight_quant)
        fn = _FN_CACHE.get(cache_key)
        if fn is None:
            if len(_FN_CACHE) >= _FN_CACHE_MAX:
                _FN_CACHE.pop(next(iter(_FN_CACHE)))
            fn = _FN_CACHE[cache_key] = build_generate_fn_paged(
                model.config, gen, s, pool.page_size, pool.num_pages,
                pool.max_pages)
        out = fn(state, ids, lengths_arr, jax.random.key(seed),
                 jnp.asarray(pool.table))
        return Tensor(out, stop_gradient=True)

    cache_key = (astuple_cfg(model.config), s,
                 gen.max_new_tokens, gen.do_sample, gen.temperature,
                 gen.top_k, gen.top_p, gen.eos_token_id, gen.pad_token_id,
                 _DA.PALLAS_DECODE or _DA._INTERPRET, weight_quant)
    fn = _FN_CACHE.get(cache_key)
    if fn is None:
        if len(_FN_CACHE) >= _FN_CACHE_MAX:   # bound compiled programs
            _FN_CACHE.pop(next(iter(_FN_CACHE)))
        fn = _FN_CACHE[cache_key] = build_generate_fn(
            model.config, gen, s)
    out = fn(state, ids, lengths_arr, jax.random.key(seed))
    return Tensor(out, stop_gradient=True)
