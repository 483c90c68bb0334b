"""A ``deepseek_v3`` decoder LM (DeepSeek-V3, arXiv:2412.19437, as the
published ``modeling_deepseek_v3`` computes it): multi-head latent
attention (DeepSeek-V2, arXiv:2405.04434), a bias-corrected sigmoid
router over grouped experts beside a shared expert, YaRN rotary
positions (arXiv:2309.00071).  The plain reference.

``jax.numpy``, float32 with matmul precision ``highest``, one causal
forward over the whole sequence; attention in its expanded form only
(keys and values per head from the latent); no cache, no paging, no
kernel, nothing imported from ``paddle_tpu``.  Weights are a flat dict
under the published parameter names, ``[in, out]``, in the served dtype,
the held experts stacked (``mlp.experts.gate_proj.weight``
``[held, hidden, moe_intermediate]``); each matrix is upcast inside the
call that uses it, attention runs in blocks of query rows and the
experts one after another, so that 4,096 positions fit beside the
served weights.

``model`` is the configuration's ``model`` object with two keys as the
deployment has them: ``n_routed_experts`` the router's published width,
and ``local_experts`` ``[first, count]``, the experts this chip holds.
The routed sum runs over the chosen experts among those, by a plain
loop; the others' terms belong to other chips and are left out, as in
the program.  The vocabulary is the slice the weights hold.

Departures from the published code, each on purpose:
  * experts outside the kept groups are masked with -inf before the
    top-k, where the published code fills 0.0: the same choice whenever
    a kept expert's corrected score is positive, and "the top k among
    the kept groups" where it is not;
  * weights are ``[in, out]`` (the published tensors transposed);
  * ``num_nextn_predict_layers`` is not run: the module is a further
    head on which these logits do not depend.

``int8=True`` is the control of ``correct``: every projection matrix of
every layer (attention's five, the dense MLP's or the shared expert's
three, each held expert's three) is rounded to int8 with one float32
scale per output channel; embedding, norms, router and head stay as
served.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
ROWS = 512                              # query rows of one attention block


def _int8(w):
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _w(w, int8):
    w = w.astype(jnp.float32)
    return _int8(w) if int8 else w


def _mm(a, w, int8=False):
    return jnp.matmul(a, _w(w, int8), precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def inv_freq(model: dict):
    """[rope/2] rotary frequencies, YaRN-blended where the model says."""
    d, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    plain = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    rs = model.get("rope_scaling")
    if not rs:
        return plain
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(float(rs.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction(float(rs.get("beta_slow", 1)))), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                   # 1 where the plain frequency stays
    return plain / factor * (1.0 - keep) + plain * keep


def attention_constants(model: dict) -> tuple:
    """(cos/sin factor, softmax scale)."""
    rs = model.get("rope_scaling")
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    if not rs:
        return 1.0, scale
    factor = float(rs["factor"])
    if rs.get("mscale") and rs.get("mscale_all_dim"):
        trig = (_mscale(factor, float(rs["mscale"]))
                / _mscale(factor, float(rs["mscale_all_dim"])))
    else:
        trig = _mscale(factor, 1.0)
    if rs.get("mscale_all_dim"):
        m = _mscale(factor, float(rs["mscale_all_dim"]))
        scale *= m * m
    return trig, scale


def _rope(x, inv, trig):
    """x [S, heads, rope], positions 0..S-1, pairs (2i, 2i+1): the
    published de-interleave, then the half rotation."""
    s = x.shape[0]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None] * trig
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None] * trig
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vdim", "rank", "eps", "trig", "scale",
    "int8"))
def attention(x, w, inv, *, heads, nope, rope, vdim, rank, eps, trig,
              scale, int8):
    """x + MLA(rms(x)) for x [S, H], S a multiple of ROWS or under it."""
    s = x.shape[0]
    h = _rms(x, w["input_layernorm.weight"], eps)
    cq = _rms(_mm(h, w["q_a_proj.weight"], int8),
              w["q_a_layernorm.weight"], eps)
    q = _mm(cq, w["q_b_proj.weight"], int8).reshape(s, heads, nope + rope)
    ckv = _mm(h, w["kv_a_proj_with_mqa.weight"], int8)
    c = _rms(ckv[:, :rank], w["kv_a_layernorm.weight"], eps)
    k_r = _rope(ckv[:, None, rank:], inv, trig)             # [S, 1, rope]
    kv = _mm(c, w["kv_b_proj.weight"], int8).reshape(s, heads, nope + vdim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (s, heads, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, trig)], -1)

    rows = min(ROWS, s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        sc = jnp.einsum("qhd,thd->hqt", qb, k, precision=HI) * scale
        seen = (jnp.arange(s)[None, :]
                <= (start + jnp.arange(rows))[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", p, v, precision=HI)

    ctx = jax.lax.map(block, jnp.arange(0, s, rows)).reshape(
        s, heads * vdim)
    return x + _mm(ctx, w["o_proj.weight"], int8)


def _swiglu(h, gate, up, down, int8):
    return _mm(jax.nn.silu(_mm(h, gate, int8)) * _mm(h, up, int8), down,
               int8)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def dense_ffn(x, w, *, eps, int8):
    h = _rms(x, w["post_attention_layernorm.weight"], eps)
    return x + _swiglu(h, w["mlp.gate_proj.weight"],
                       w["mlp.up_proj.weight"],
                       w["mlp.down_proj.weight"], int8)


def route(h, w_gate, bias, *, groups, keep_groups, top_k, norm, factor):
    """h [T, H] float32 -> (experts [T, K], weights [T, K])."""
    t, e = h.shape[0], w_gate.shape[1]
    sc = jax.nn.sigmoid(jnp.matmul(h, w_gate.astype(jnp.float32),
                                   precision=HI))
    ch = sc + bias.astype(jnp.float32)[None, :]
    per = ch.reshape(t, groups, e // groups)
    score = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)
    kept = jax.lax.top_k(score, keep_groups)[1]
    ok = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    among = jnp.where(jnp.repeat(ok, e // groups, axis=1), ch, -jnp.inf)
    idx = jax.lax.top_k(among, top_k)[1]
    wt = jnp.take_along_axis(sc, idx, axis=1)
    if norm:
        wt = wt / (jnp.sum(wt, axis=1, keepdims=True) + 1e-20)
    return idx, wt * factor


@functools.partial(jax.jit, static_argnames=(
    "eps", "groups", "keep_groups", "top_k", "norm", "factor", "int8"))
def expert_ffn_shared(x, w, *, eps, groups, keep_groups, top_k, norm,
                      factor, int8):
    """(normed input, x + shared expert, routing) of an expert layer."""
    h = _rms(x, w["post_attention_layernorm.weight"], eps)
    idx, wt = route(h, w["mlp.gate.weight"],
                    w["mlp.gate.e_score_correction_bias"], groups=groups,
                    keep_groups=keep_groups, top_k=top_k, norm=norm,
                    factor=factor)
    y = x + _swiglu(h, w["mlp.shared_experts.gate_proj.weight"],
                    w["mlp.shared_experts.up_proj.weight"],
                    w["mlp.shared_experts.down_proj.weight"], int8)
    return h, y, idx, wt


@functools.partial(jax.jit, static_argnames=("int8",))
def add_expert(y, h, idx, wt, expert, gate, up, down, *, int8):
    """y + (the tokens' weight for ``expert``) * expert(h)."""
    mine = jnp.sum(jnp.where(idx == expert, wt, 0.0), axis=1)
    return y + mine[:, None] * _swiglu(h, gate, up, down, int8)


def logits_at(state: dict, model: dict, ids, rows, *, int8: bool = False):
    """Float32 logits [len(rows), V] at positions ``rows`` of one causal
    forward over ``ids`` [S] (right padding is invisible to the rows
    before it)."""
    eps = float(model["rms_norm_eps"])
    trig, scale = attention_constants(model)
    inv = inv_freq(model)
    first, held = model.get("local_experts") or (
        0, int(model["n_routed_experts"]))
    x = jnp.take(state["model.embed_tokens.weight"], jnp.asarray(ids),
                 axis=0).astype(jnp.float32)
    for n in range(model["num_hidden_layers"]):
        p = f"model.layers.{n}."
        w = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
        x = attention(
            x, {k[len("self_attn."):] if k.startswith("self_attn.") else k:
                v for k, v in w.items() if not k.startswith("mlp.")},
            inv, heads=model["num_attention_heads"],
            nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
            vdim=model["v_head_dim"], rank=model["kv_lora_rank"], eps=eps,
            trig=float(trig), scale=float(scale), int8=bool(int8))
        ffn = {k: v for k, v in w.items()
               if k.startswith("mlp.") or k.startswith("post_")}
        if n < model["first_k_dense_replace"]:
            x = dense_ffn(x, ffn, eps=eps, int8=bool(int8))
            continue
        experts = {k: ffn.pop("mlp.experts." + k + "_proj.weight")
                   for k in ("gate", "up", "down")}
        h, x, idx, wt = expert_ffn_shared(
            x, ffn, eps=eps, groups=int(model["n_group"]),
            keep_groups=int(model["topk_group"]),
            top_k=int(model["num_experts_per_tok"]),
            norm=bool(model["norm_topk_prob"]),
            factor=float(model["routed_scaling_factor"]), int8=bool(int8))
        for e in range(int(held)):
            x = add_expert(x, h, idx, wt, first + e, experts["gate"][e],
                           experts["up"][e], experts["down"][e],
                           int8=bool(int8))
    x = _rms(x[jnp.asarray(rows)], state["model.norm.weight"], eps)
    return jnp.matmul(x, state["lm_head.weight"].astype(jnp.float32),
                      precision=HI)


def served_gaps(state: dict, model: dict, prompt, served, *, pad_to: int,
                pad_rows: int = 0, int8: bool = False) -> dict:
    """Teacher-forced reading of one finished request: the contract of
    ``decoder_lm.served_gaps``."""
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    rows = np.arange(n_p - 1, n_p + n_s - 1)
    rows = np.concatenate([rows, np.full(max(0, pad_rows - n_s), rows[-1])])
    ref = logits_at(state, model, ids, rows)[:n_s]
    if int8:
        judged = jnp.argmax(
            logits_at(state, model, ids, rows, int8=True)[:n_s], axis=-1)
    else:
        judged = jnp.asarray(np.asarray(served, np.int32))
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, judged[:, None], axis=1)[:, 0]
    if not bool(jnp.all(jnp.isfinite(ref))):
        raise RuntimeError("reference logits are not finite")
    return {"gaps": np.asarray(gap), "best": np.asarray(best),
            "std": float(jnp.std(ref))}
