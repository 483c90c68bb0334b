"""A pre-norm decoder LM with rotary GQA attention and a SwiGLU MLP (the
Mistral-7B layer: Jiang et al., arXiv:2310.06825; RoPE arXiv:2104.09864,
in the half-rotation layout of the published checkpoints): the plain
reference.

``jax.numpy``, float32 with matmul precision ``highest``, one causal
forward over the whole sequence; no cache, no paging, no kernel, nothing
imported from ``paddle_tpu``.  Weights are a flat dict under the names of
``benchmarks/lib/state.decoder_shapes``, [in, out], in the served dtype;
each layer's are upcast inside that layer's call, and layers run one
jitted call after another, so the reference holds one layer in float32
at a time.

``int8=True`` is the control of ``correct``: the seven projection
matrices of every layer are rounded to int8 with one float32 scale per
output channel (embedding, norms and head stay as served), the step
below bf16 that would tempt a later PR (the program has it as
``quant="int8"``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
PROJECTIONS = ("self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj",
               "self_attn.o_proj", "mlp.gate_proj", "mlp.up_proj",
               "mlp.down_proj")


def _int8(w):
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: [S, heads, D]; positions 0..S-1; pairs (i, i + D/2) rotate."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "int8"))
def layer(x, w, *, heads, kv_heads, head_dim, theta, eps, int8):
    """One decoder layer on x [S, H]; ``w`` holds the layer's leaves by
    their short names."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if int8:
        w.update({k + ".weight": _int8(w[k + ".weight"])
                  for k in PROJECTIONS})

    def mm(a, name):
        return jnp.matmul(a, w[name + ".weight"], precision=HI)

    s = x.shape[0]
    h = _rms(x, w["input_layernorm.weight"], eps)
    q = _rope(mm(h, "self_attn.q_proj").reshape(s, heads, head_dim), theta)
    k = _rope(mm(h, "self_attn.k_proj").reshape(s, kv_heads, head_dim),
              theta)
    v = mm(h, "self_attn.v_proj").reshape(s, kv_heads, head_dim)
    group = heads // kv_heads
    q = q.reshape(s, kv_heads, group, head_dim)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k,
                        precision=HI) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("kgqs,skd->qkgd", probs, v, precision=HI)
    x = x + mm(ctx.reshape(s, heads * head_dim), "self_attn.o_proj")
    h = _rms(x, w["post_attention_layernorm.weight"], eps)
    y = jax.nn.silu(mm(h, "mlp.gate_proj")) * mm(h, "mlp.up_proj")
    return x + mm(y, "mlp.down_proj")


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, norm_w, head_w, *, eps):
    x = _rms(x, norm_w.astype(jnp.float32), eps)
    return jnp.matmul(x, head_w.astype(jnp.float32), precision=HI)


def logits_at(state: dict, model: dict, ids, rows, *, int8: bool = False):
    """Float32 logits [len(rows), V] at positions ``rows`` of one causal
    forward over ``ids`` [S] (right padding is invisible to the rows
    before it)."""
    hd = model.get("head_dim") or (model["hidden_size"]
                                   // model["num_attention_heads"])
    x = jnp.take(state["llama.embed_tokens.weight"], jnp.asarray(ids),
                 axis=0).astype(jnp.float32)
    for n in range(model["num_hidden_layers"]):
        p = f"llama.layers.{n}."
        w = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
        x = layer(x, w, heads=model["num_attention_heads"],
                  kv_heads=model["num_key_value_heads"], head_dim=hd,
                  theta=float(model["rope_theta"]),
                  eps=float(model["rms_norm_eps"]), int8=bool(int8))
    head_w = (state["llama.embed_tokens.weight"].T
              if model.get("tie_word_embeddings")
              else state["lm_head.weight"])
    return head(x[jnp.asarray(rows)], state["llama.norm.weight"], head_w,
                eps=float(model["rms_norm_eps"]))


def served_gaps(state: dict, model: dict, prompt, served, *, pad_to: int,
                pad_rows: int = 0, int8: bool = False) -> dict:
    """Teacher-forced reading of one finished request.

    One forward over prompt + served tokens, padded to ``pad_to``.  At
    every position that produced a served token: the gap by which that
    token's reference logit lies below the reference's best.  With
    ``int8`` the token judged is not the served one but the one the
    int8 forward puts first at that position: the control."""
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
