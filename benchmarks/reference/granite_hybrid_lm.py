"""A hybrid decoder LM, Mamba-2 layers beside GQA attention layers with a
shared SwiGLU MLP in each (IBM Granite 4.0 "H", ``model_type``
``granitemoehybrid``; the mixer: Dao & Gu, arXiv:2405.21060): the plain
reference.

``jax.numpy``, float32 with matmul precision ``highest``, one causal
forward over the whole sequence; no cache, no paging, no kernel, nothing
imported from ``paddle_tpu``.  Weights are a flat dict under the
published parameter names (``benchmarks/lib/hybrid_state.shapes``),
[in, out], in the served dtype; each layer's are upcast inside that
layer's call, and layers run one jitted call after another, so the
reference holds one layer in float32 at a time.

Every layer, with ``rm = residual_multiplier``::

    h0     = embed(ids) * embedding_multiplier
    h      = h + rm * Mixer(RMSNorm(h))
    h      = h + rm * down(silu(gate(n)) * up(n)),  n = RMSNorm(h)
    logits = (RMSNorm(h) @ embed^T) / logits_scaling

Attention has no position term at all (``position_embedding_type``
"nope") and its softmax scale is ``attention_multiplier``.  The Mamba
layer is the SEQUENTIAL recurrence, one token at a time under
``jax.lax.scan``, per head h with state S [P, N]::

    [z | xBC | dt] = n @ W_in
    xBC = silu(causal_depthwise_conv1d(xBC) + b)
    S_t = exp(dt_t A) S_{t-1} + dt_t (x_t outer B_t),  dt = softplus(dt + dt_bias)
    y_t = S_t C_t + D x_t
    out = RMSNorm_w(y * silu(z)) @ W_out

which is not the chunked matmul form the program prefills with, nor its
kernel's layout.  Departures from the published code: the published
code runs the chunked form for a prompt and this recurrence for one
token at a time, which are the same function; between tokens it keeps
the state in a cache of the model's dtype (``ssm_states``, allocated
``dtype=dtype`` by ``HybridMambaAttentionDynamicCache``), where this
reference carries it in float32 (``state_bf16`` is the published
rounding); its ``time_step_limit`` is (0, inf), a clamp that changes
nothing; the gated norm is over all of ``d_inner`` (one group).

Controls of ``correct``: ``int8=True`` rounds every projection matrix
of every layer (``in_proj``, ``out_proj``, q/k/v/o, the MLP's two) to
int8 with one float32 scale per output channel; embedding, norms,
convolution and the per-head vectors stay as served.  ``state_bf16=True``
rounds the carried state S to bfloat16 after every token: what a
program that holds its recurrent state in bfloat16 computes.  It is a
planted fault for a configuration that states a float32 state (the
toy one of the tests); for one that states bfloat16 it is the stated
precision, and its reading says what that precision costs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EMBED = "model.embed_tokens.weight"
MLP = ("shared_mlp.input_linear", "shared_mlp.output_linear")
PROJECTIONS = {
    "attention": ("self_attn.q_proj", "self_attn.k_proj",
                  "self_attn.v_proj", "self_attn.o_proj") + MLP,
    "mamba": ("mamba.in_proj", "mamba.out_proj") + MLP}


def _int8(w):
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _weights(w, kind, int8):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if int8:
        w.update({k + ".weight": _int8(w[k + ".weight"])
                  for k in PROJECTIONS[kind]})
    return w


def _mlp(x, w, mm, eps, rm):
    h = _rms(x, w["post_attention_layernorm.weight"], eps)
    gu = mm(h, "shared_mlp.input_linear")
    half = gu.shape[-1] // 2
    y = jax.nn.silu(gu[:, :half]) * gu[:, half:]
    return x + rm * mm(y, "shared_mlp.output_linear")


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "scale", "eps", "rm", "int8"))
def attention_layer(x, w, *, heads, kv_heads, scale, eps, rm, int8):
    """One attention layer on x [S, H]: causal GQA, no position term."""
    w = _weights(w, "attention", int8)

    def mm(a, name):
        return jnp.matmul(a, w[name + ".weight"], precision=HI)

    s = x.shape[0]
    h = _rms(x, w["input_layernorm.weight"], eps)
    q = mm(h, "self_attn.q_proj").reshape(s, kv_heads, heads // kv_heads, -1)
    k = mm(h, "self_attn.k_proj").reshape(s, kv_heads, -1)
    v = mm(h, "self_attn.v_proj").reshape(s, kv_heads, -1)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k, precision=HI) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
    ctx = jnp.einsum("kgqs,skd->qkgd", probs, v, precision=HI)
    x = x + rm * mm(ctx.reshape(s, -1), "self_attn.o_proj")
    return _mlp(x, w, mm, eps, rm)


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_state", "eps", "rm", "int8", "state_bf16"))
def mamba_layer(x, w, *, heads, d_state, eps, rm, int8, state_bf16):
    """One Mamba-2 layer on x [S, H]: the recurrence token by token."""
    w = _weights(w, "mamba", int8)

    def mm(a, name):
        return jnp.matmul(a, w[name + ".weight"], precision=HI)

    s = x.shape[0]
    d_inner = w["mamba.norm.weight"].shape[0]
    p = d_inner // heads
    taps = w["mamba.conv1d.weight"]                     # [conv_dim, d_conv]
    k = taps.shape[1]
    h = _rms(x, w["input_layernorm.weight"], eps)
    zxbcdt = mm(h, "mamba.in_proj")
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + taps.shape[0]]
    dt = zxbcdt[:, d_inner + taps.shape[0]:]
    # out[t] = bias + sum_j taps[:, j] * xbc[t - (k - 1) + j]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + s] * taps[:, j] for j in range(k))
    xbc = jax.nn.silu(conv + w.get("mamba.conv1d.bias", 0.0))
    xs = xbc[:, :d_inner].reshape(s, heads, p)
    b = xbc[:, d_inner:d_inner + d_state]
    c = xbc[:, d_inner + d_state:]
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])       # [S, heads]
    a = -jnp.exp(w["mamba.A_log"])                      # [heads]

    def token(state, at):
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        if state_bf16:
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HI)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, d_state), jnp.float32),
                        (xs, b, c, dt))
    y = (y + w["mamba.D"][None, :, None] * xs).reshape(s, d_inner)
    g = _rms(y * jax.nn.silu(z), w["mamba.norm.weight"], eps)
    x = x + rm * mm(g, "mamba.out_proj")
    return _mlp(x, w, mm, eps, rm)


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def head(x, norm_w, embed, *, eps, scaling):
    x = _rms(x, norm_w.astype(jnp.float32), eps)
    return jnp.matmul(x, embed.astype(jnp.float32).T,
                      precision=HI) / scaling


def logits_at(state: dict, model: dict, ids, rows, *, int8: bool = False,
              state_bf16: bool = False):
    """Float32 logits [len(rows), V] at positions ``rows`` of one causal
    forward over ``ids`` [S] (right padding is invisible to the rows
    before it).  ``model`` holds the published keys."""
    eps, rm = float(model["rms_norm_eps"]), float(model["residual_multiplier"])
    x = jnp.take(state[EMBED], jnp.asarray(ids), axis=0).astype(
        jnp.float32) * float(model["embedding_multiplier"])
    for n, kind in enumerate(model["layer_types"]):
        p = f"model.layers.{n}."
        w = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
        if kind == "attention":
            x = attention_layer(
                x, w, heads=model["num_attention_heads"],
                kv_heads=model["num_key_value_heads"],
                scale=float(model["attention_multiplier"]), eps=eps, rm=rm,
                int8=bool(int8))
        else:
            x = mamba_layer(
                x, w, heads=model["mamba_n_heads"],
                d_state=model["mamba_d_state"], eps=eps, rm=rm,
                int8=bool(int8), state_bf16=bool(state_bf16))
    return head(x[jnp.asarray(rows)], state["model.norm.weight"],
                state[EMBED], eps=eps,
                scaling=float(model["logits_scaling"]))


def served_gaps(state: dict, model: dict, prompt, served, *, pad_to: int,
                pad_rows: int = 0, int8: bool = False,
                state_bf16: bool = False) -> dict:
    """Teacher-forced reading of one finished request.

    One forward over prompt + served tokens, padded to ``pad_to``.  At
    every position that produced a served token: the gap by which that
    token's reference logit lies below the reference's best.  With a
    control (``int8``, ``state_bf16``) the token judged is not the
    served one but the one the controlled forward puts first at that
    position."""
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    rows = np.arange(n_p - 1, n_p + n_s - 1)
    rows = np.concatenate([rows, np.full(max(0, pad_rows - n_s), rows[-1])])
    ref = logits_at(state, model, ids, rows)[:n_s]
    if int8 or state_bf16:
        judged = jnp.argmax(logits_at(
            state, model, ids, rows, int8=int8,
            state_bf16=state_bf16)[:n_s], axis=-1)
    else:
        judged = jnp.asarray(np.asarray(served, np.int32))
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, judged[:, None], axis=1)[:, 0]
    if not bool(jnp.all(jnp.isfinite(ref))):
        raise RuntimeError("reference logits are not finite")
    return {"gaps": np.asarray(gap), "best": np.asarray(best),
            "std": float(jnp.std(ref))}
