"""A hybrid decoder LM whose every layer holds routed experts beside its
shared MLP, Mamba-2 layers beside GQA attention layers (IBM Granite 4.0
"H" Small, ``model_type`` ``granitemoehybrid`` with ``num_local_experts``
> 0): the plain reference.

``jax.numpy``, float32 with matmul precision ``highest``, one causal
forward over the whole sequence; no cache, no paging, no kernel, nothing
imported from ``paddle_tpu``.  The head is ``granite_hybrid_lm``'s, the
Mamba mixer its SEQUENTIAL recurrence, token by token (below);
attention (no position term, the softmax scale ``attention_multiplier``)
is computed here ``ROWS`` query rows at a time, the same sums as
``granite_hybrid_lm``'s, since the whole score matrix of a 4,096-token
sequence would not fit beside these weights; this file adds the second
half of a layer.
Every layer, with ``rm = residual_multiplier`` and ``u`` the normed
input of the second half::

    h   = h + rm * Mixer(RMSNorm_1(h))
    u   = RMSNorm_2(h)
    l   = u @ W_router                          (every published expert)
    K   = top_k(l);  g = softmax(l[K])          (over the k chosen alone)
    h   = h + rm * (sum_{e in K, held} g_e down_e(silu(u W1_e[:, :F]) * (u W1_e[:, F:]))
                    + down_s(silu(u W_s[:, :F_s]) * (u W_s[:, F_s:])))

``W1_e`` is the published ``input_linear`` of expert ``e`` (gate | up),
``down_e`` its ``output_linear``; the shared MLP's ``input_linear`` is
fused the same way.  Of the chosen experts only those in
``local_experts`` ``[first, count]`` are held here and their terms
computed: the others belong to other chips and are left out as the
program leaves them out.  Weights are a flat dict under the published
names (``benchmarks/lib/granite_moe_state.shapes``), [in, out], the held
experts stacked, in the served dtype; each layer's are upcast inside
that layer's call (the experts one at a time), so the reference holds
one layer in float32 at a time.

The Mamba mixer is ``granite_hybrid_lm``'s recurrence written out again
(``mamba_mixer``) with one difference, the published cache's precision:
between tokens the published model keeps the state in a cache of the
model's dtype (``HybridMambaAttentionDynamicCache``: ``ssm_states``
allocated ``dtype=dtype``); its prefill carries the state in float32
through the prompt and stores it at the prompt's end, and each decode
step reads it, updates it in float32, reads ``y`` from the update and
stores it again.  So from the prompt's last position on this reference
rounds the carried state to ``state_dtype`` after every token, by
``jax.lax.reduce_precision``: an ``astype`` round trip, which
``granite_hybrid_lm`` uses, is removed by XLA on the TPU (excess
precision allowed) and would round nothing there.  A bfloat16 state
stores a slow head's increment (``dt * A`` from 0.001) under its
resolution, so a long greedy trajectory of the published model can
drift from a float32-state forward's.  The convolution's window stays float32, as every other
activation here.

Controls of ``correct``: ``int8=True`` rounds every projection matrix
(``in_proj``, ``out_proj``, q/k/v/o, the shared MLP's two, each held
expert's two) to int8 with one float32 scale per output channel;
embedding, router, norms, convolution and the per-head vectors stay as
served.  ``softmax_all=True`` routes as a Mixtral-style reading of the
config would: a softmax over all the router's logits, then the top k of
those probabilities, not renormalised (the same experts, gates that sum
to less than one): a planted fault in the router.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.granite_hybrid_lm import EMBED, HI, _int8, _rms, head

MOE = "block_sparse_moe."
ROWS = 512                      # query rows of one block of attention
SECOND_HALF = ("post_attention_layernorm.weight", MOE + "router.layer.weight",
               "shared_mlp.input_linear.weight",
               "shared_mlp.output_linear.weight")


def layer_types(model: dict) -> tuple:
    """The served layers: the first ``num_hidden_layers`` of the
    published ``layer_types``."""
    return tuple(model["layer_types"][:int(model["num_hidden_layers"])])


def held_in(state, dtype: str):
    """``state`` rounded to the float format ``dtype``, by an operation
    XLA keeps (float32: unchanged)."""
    info = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(state, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_state", "eps", "rm", "int8", "state_dtype"))
def mamba_mixer(x, w, held, *, heads, d_state, eps, rm, int8, state_dtype):
    """x + rm * Mamba(RMSNorm_1(x)) on x [S, H], the recurrence token by
    token; after a token where ``held`` [S] is true the carried state
    is rounded to ``state_dtype`` (``y`` of that token is read before)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}

    def mm(a, name):
        m = w["mamba." + name + ".weight"]
        return jnp.matmul(a, _int8(m) if int8 else m, precision=HI)

    s = x.shape[0]
    d_inner = w["mamba.norm.weight"].shape[0]
    taps = w["mamba.conv1d.weight"]                     # [conv_dim, d_conv]
    k = taps.shape[1]
    zxbcdt = mm(_rms(x, w["input_layernorm.weight"], eps), "in_proj")
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + taps.shape[0]]
    dt = zxbcdt[:, d_inner + taps.shape[0]:]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + s] * taps[:, j] for j in range(k))
                      + w.get("mamba.conv1d.bias", 0.0))
    xs = xbc[:, :d_inner].reshape(s, heads, d_inner // heads)
    b = xbc[:, d_inner:d_inner + d_state]
    c = xbc[:, d_inner + d_state:]
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])       # [S, heads]
    a = -jnp.exp(w["mamba.A_log"])                      # [heads]

    def token(state, at):
        x_t, b_t, c_t, dt_t, held_t = at
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y_t = jnp.einsum("hpn,n->hp", state, c_t, precision=HI)
        return jnp.where(held_t, held_in(state, state_dtype), state), y_t

    _, y = jax.lax.scan(token, jnp.zeros(
        (heads, d_inner // heads, d_state), jnp.float32),
        (xs, b, c, dt, held))
    y = (y + w["mamba.D"][None, :, None] * xs).reshape(s, d_inner)
    g = _rms(y * jax.nn.silu(z), w["mamba.norm.weight"], eps)
    return x + rm * mm(g, "out_proj")


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "scale", "eps", "rm", "int8"))
def attention_mixer(x, w, *, heads, kv_heads, scale, eps, rm, int8):
    """x + rm * GQA(RMSNorm_1(x)) on x [S, H]: causal, no position term,
    softmax scale ``scale``; S a multiple of ROWS or under it."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}

    def mm(a, name):
        m = w["self_attn." + name + ".weight"]
        return jnp.matmul(a, _int8(m) if int8 else m, precision=HI)

    s = x.shape[0]
    h = _rms(x, w["input_layernorm.weight"], eps)
    q = mm(h, "q_proj").reshape(s, kv_heads, heads // kv_heads, -1)
    k = mm(h, "k_proj").reshape(s, kv_heads, -1)
    v = mm(h, "v_proj").reshape(s, kv_heads, -1)
    rows = min(ROWS, s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * scale
        seen = (jnp.arange(s)[None, :]
                <= (start + jnp.arange(rows))[:, None])
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf),
                           axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    ctx = jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, -1)
    return x + rm * mm(ctx, "o_proj")


def _swiglu(u, gate_up, down, int8):
    """down(silu(u G) * (u U)) with ``gate_up`` = [G | U], float32."""
    gate_up, down = gate_up.astype(jnp.float32), down.astype(jnp.float32)
    if int8:
        gate_up, down = _int8(gate_up), _int8(down)
    gu = jnp.matmul(u, gate_up, precision=HI)
    f = gu.shape[-1] // 2
    return jnp.matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], down,
                      precision=HI)


def route(u, w_router, *, top_k: int, softmax_all: bool = False):
    """u [T, H] float32 -> (experts [T, K], gates [T, K]) over every
    published expert: a softmax over the k largest logits (or, with
    ``softmax_all``, the k largest of a softmax over all of them)."""
    logits = jnp.matmul(u, w_router.astype(jnp.float32), precision=HI)
    if softmax_all:
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        return idx, gates
    top, idx = jax.lax.top_k(logits, top_k)
    return idx, jax.nn.softmax(top, axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "eps", "rm", "top_k", "softmax_all", "int8"))
def moe_shared(x, w, *, eps, rm, top_k, softmax_all, int8):
    """(u, x + rm * shared MLP(u), experts, gates) of a layer's second
    half, ``u = RMSNorm_2(x)``."""
    u = _rms(x, w["post_attention_layernorm.weight"].astype(jnp.float32),
             eps)
    idx, gates = route(u, w[MOE + "router.layer.weight"], top_k=top_k,
                       softmax_all=softmax_all)
    shared = _swiglu(u, w["shared_mlp.input_linear.weight"],
                     w["shared_mlp.output_linear.weight"], int8)
    return u, x + rm * shared, idx, gates


@functools.partial(jax.jit, static_argnames=("rm", "int8"))
def add_expert(y, u, idx, gates, expert, gate_up, down, *, rm, int8):
    """y + rm * (the tokens' gate for ``expert``) * expert(u)."""
    mine = jnp.sum(jnp.where(idx == expert, gates, 0.0), axis=1)
    return y + rm * mine[:, None] * _swiglu(u, gate_up, down, int8)


def logits_at(state: dict, model: dict, ids, rows, *, int8: bool = False,
              softmax_all: bool = False, held_from: int | None = None,
              state_dtype: str = "float32"):
    """Float32 logits [len(rows), V] at positions ``rows`` of one causal
    forward over ``ids`` [S] (right padding is invisible to the rows
    before it).  ``model`` holds the published keys, ``num_local_experts``
    the router's width, and ``local_experts`` ``[first, count]`` (absent:
    every expert is held).  From position ``held_from`` on (None: at
    none) the Mamba state is held in ``state_dtype`` between tokens."""
    eps, rm = float(model["rms_norm_eps"]), float(model["residual_multiplier"])
    first, held = model.get("local_experts") or (
        0, int(model["num_local_experts"]))
    at = jnp.arange(len(ids))
    cached = at >= (len(ids) if held_from is None else held_from)
    x = jnp.take(state[EMBED], jnp.asarray(ids), axis=0).astype(
        jnp.float32) * float(model["embedding_multiplier"])
    for n, kind in enumerate(layer_types(model)):
        p = f"model.layers.{n}."
        w = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
        if kind == "attention":
            x = attention_mixer(
                x, {k: v for k, v in w.items()
                    if k.startswith(("input_layernorm.", "self_attn."))},
                heads=model["num_attention_heads"],
                kv_heads=model["num_key_value_heads"],
                scale=float(model["attention_multiplier"]), eps=eps, rm=rm,
                int8=bool(int8))
        else:
            x = mamba_mixer(
                x, {k: v for k, v in w.items()
                    if k.startswith(("input_layernorm.", "mamba."))},
                cached, heads=model["mamba_n_heads"],
                d_state=model["mamba_d_state"], eps=eps, rm=rm,
                int8=bool(int8), state_dtype=state_dtype)
        gate_up = w[MOE + "input_linear.weight"]
        down = w[MOE + "output_linear.weight"]
        u, x, idx, gates = moe_shared(
            x, {k: w[k] for k in SECOND_HALF}, eps=eps, rm=rm,
            top_k=int(model["num_experts_per_tok"]), softmax_all=bool(softmax_all), int8=bool(int8))
        for e in range(held):
            x = add_expert(x, u, idx, gates, first + e, gate_up[e], down[e],
                           rm=rm, int8=bool(int8))
    return head(x[jnp.asarray(rows)], state["model.norm.weight"],
                state[EMBED], eps=eps,
                scaling=float(model["logits_scaling"]))


def served_gaps(state: dict, model: dict, prompt, served, *, pad_to: int,
                pad_rows: int = 0, int8: bool = False,
                softmax_all: bool = False,
                state_dtype: str = "float32") -> dict:
    """Teacher-forced reading of one finished request.

    One forward over prompt + served tokens, padded to ``pad_to``.  At
    every position that produced a served token: the gap by which that
    token's reference logit lies below the reference's best.  With a
    control (``int8``, ``softmax_all``) the token judged is not the
    served one but the one the controlled forward puts first at that
    position.  Both forwards hold the Mamba state in ``state_dtype``
    from the prompt's last position on, as the published cache does."""
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    rows = np.arange(n_p - 1, n_p + n_s - 1)
    rows = np.concatenate([rows, np.full(max(0, pad_rows - n_s), rows[-1])])
    cache = {"held_from": n_p - 1, "state_dtype": state_dtype}
    ref = logits_at(state, model, ids, rows, **cache)[:n_s]
    if int8 or softmax_all:
        judged = jnp.argmax(logits_at(
            state, model, ids, rows, int8=int8, softmax_all=softmax_all,
            **cache)[:n_s], axis=-1)
    else:
        judged = jnp.asarray(np.asarray(served, np.int32))
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, judged[:, None], axis=1)[:, 0]
    if not bool(jnp.all(jnp.isfinite(ref))):
        raise RuntimeError("reference logits are not finite")
    return {"gaps": np.asarray(gap), "best": np.asarray(best),
            "std": float(jnp.std(ref))}
