"""A hybrid decoder LM whose blocks are ONE part each, a Mamba-2 mixer in
groups, a GQA attention or a routed-expert layer (NVIDIA Nemotron-H /
Nemotron 3 Nano, ``model_type`` ``nemotron_h``; the mixer: Dao & Gu,
arXiv:2405.21060; the router: DeepSeek-V3, arXiv:2412.19437): the plain
reference.

``jax.numpy``, float32 with matmul precision ``highest``, one causal
forward over the whole sequence; no cache, no paging, no kernel, no
chunks, nothing imported from ``paddle_tpu``.  Weights are a flat dict
under the published parameter names (``benchmarks/lib/
hybrid_moe_state.shapes``), [in, out], the held experts stacked, in the
served dtype; each block's are upcast inside that block's call (an
expert block's one expert at a time), and blocks run one jitted call
after another, so the reference holds one block in float32 at a time
and fits beside the served weights.

Every block ``i`` of ``hybrid_override_pattern`` (``M``, ``*``, ``E``)::

    h0     = embed(ids)
    h      = h + Part_i(RMSNorm_i(h))
    logits = RMSNorm(h) @ lm_head

    M: [z | xBC | dt] = n @ W_in                      (n the normed input)
       xBC = silu(causal_depthwise_conv1d(xBC) + b);  x, B, C = split(xBC)
       dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_h = exp(dt_h A_h) S_h + dt_h x_h (x) B_g(h);  y_h = S_h C_g(h) + D_h x_h
       out = (GroupRMSNorm(y * silu(z)) * w) @ W_out
    *: causal GQA, no bias, softmax scale 1 / sqrt(head_dim), no position term
    E: s = sigmoid(n @ W_r);  top-k of s + e_score_correction_bias
       w = s[chosen] / (sum + 1e-20) * routed_scaling_factor
       out = sum_e w_e down_e(relu(up_e n)^2) + down_s(relu(up_s n)^2)

``B`` and ``C`` come in ``n_groups`` groups of ``ssm_state_size``; head
``h`` reads group ``g(h) = h // (heads / n_groups)``; the gated norm is
over each group's ``d_inner / n_groups`` channels apart.  The Mamba
block is the SEQUENTIAL recurrence, one token at a time under
``jax.lax.scan``: not the chunked matmul form the program prefills
with, nor its kernel's layout.  The router scores all
``n_routed_experts`` (the published count); of the chosen experts only
those in ``local_experts`` ``[first, count]`` are held here and their
terms computed: the others belong to other chips, and are left out as
the program leaves them out.  Attention is computed ``ROWS`` query rows
at a time (the same sums; the whole score matrix of a 4,096-token
sequence would not fit beside the weights).

Departures from the published code: ``NemotronHAttention`` applies no
rotary embedding although the config carries ``rope_theta`` (the
configuration file lists that under ``assumed``); ``time_step_limit`` is
(0, inf), a clamp that changes nothing; the published code keeps the
state between tokens in the model's dtype, this reference in float32.

Controls of ``correct``: ``int8=True`` rounds every projection matrix
(``in_proj``, ``out_proj``, q/k/v/o, the shared expert's two, each held
expert's two) to int8 with one float32 scale per output channel;
embedding, head, router, norms, convolution and the per-head vectors
stay as served.  ``one_group=True`` gives every head group 0's ``B`` and
``C``: what a program that ignores ``n_groups`` computes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EMBED, NORM, HEAD = ("backbone.embeddings.weight", "backbone.norm_f.weight",
                     "lm_head.weight")
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}
ROWS = 512                      # query rows of one block of attention


def _int8(w):
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _mm(a, w, int8=False):
    w = w.astype(jnp.float32)
    return jnp.matmul(a, _int8(w) if int8 else w, precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _relu2_mlp(h, up, down, int8):
    return _mm(jnp.square(jax.nn.relu(_mm(h, up, int8))), down, int8)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "int8"))
def attention_block(x, w, *, heads, kv_heads, eps, int8):
    """x + GQA(rms(x)) on x [S, H]: causal, no position term, softmax
    scale 1 / sqrt(head dim); S a multiple of ROWS or under it."""
    s = x.shape[0]
    h = _rms(x, w["norm.weight"], eps)
    q = _mm(h, w["mixer.q_proj.weight"], int8).reshape(
        s, kv_heads, heads // kv_heads, -1)
    k = _mm(h, w["mixer.k_proj.weight"], int8).reshape(s, kv_heads, -1)
    v = _mm(h, w["mixer.v_proj.weight"], int8).reshape(s, kv_heads, -1)
    scale = q.shape[-1] ** -0.5
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
    return x + _mm(ctx, w["mixer.o_proj.weight"], int8)


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "d_state", "eps", "int8", "one_group"))
def mamba_block(x, w, *, heads, groups, d_state, eps, int8, one_group):
    """x + Mamba2(rms(x)) on x [S, H]: the recurrence token by token."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    s = x.shape[0]
    d_inner = w["mixer.norm.weight"].shape[0]
    p = d_inner // heads
    taps = w["mixer.conv1d.weight"]                     # [conv_dim, d_conv]
    k = taps.shape[1]
    h = _rms(x, w["norm.weight"], eps)
    zxbcdt = _mm(h, w["mixer.in_proj.weight"], int8)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + taps.shape[0]]
    dt = zxbcdt[:, d_inner + taps.shape[0]:]
    # out[t] = bias + sum_j taps[:, j] * xbc[t - (k - 1) + j]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + s] * taps[:, j] for j in range(k))
    xbc = jax.nn.silu(conv + w.get("mixer.conv1d.bias", 0.0))
    gn = groups * d_state
    xs = xbc[:, :d_inner].reshape(s, heads, p)
    b = xbc[:, d_inner:d_inner + gn].reshape(s, groups, d_state)
    c = xbc[:, d_inner + gn:].reshape(s, groups, d_state)
    # each head's own B and C [S, heads, N]: its group's
    of_head = (jnp.zeros((heads,), jnp.int32) if one_group
               else jnp.arange(heads) // (heads // groups))
    b, c = b[:, of_head], c[:, of_head]
    dt = jax.nn.softplus(dt + w["mixer.dt_bias"])       # [S, heads]
    a = -jnp.exp(w["mixer.A_log"])                      # [heads]

    def token(state, at):
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HI)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, d_state), jnp.float32),
                        (xs, b, c, dt))
    y = (y + w["mixer.D"][None, :, None] * xs).reshape(s, d_inner)
    g = (y * jax.nn.silu(z)).reshape(s, groups, d_inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    g = g.reshape(s, d_inner) * w["mixer.norm.weight"]
    return x + _mm(g, w["mixer.out_proj.weight"], int8)


def route(h, w_gate, bias, *, groups, keep_groups, top_k, norm, factor):
    """h [T, H] float32 -> (experts [T, K], weights [T, K]) over every
    published expert.  The bias moves the choice, never the weight."""
    t, e = h.shape[0], w_gate.shape[1]
    sc = jax.nn.sigmoid(_mm(h, w_gate))
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
def expert_block_shared(x, w, *, eps, groups, keep_groups, top_k, norm,
                        factor, int8):
    """(normed input, x + shared expert, routing) of an expert block."""
    h = _rms(x, w["norm.weight"], eps)
    idx, wt = route(h, w["mixer.gate.weight"],
                    w["mixer.gate.e_score_correction_bias"], groups=groups,
                    keep_groups=keep_groups, top_k=top_k, norm=norm,
                    factor=factor)
    y = x + _relu2_mlp(h, w["mixer.shared_experts.up_proj.weight"],
                       w["mixer.shared_experts.down_proj.weight"], int8)
    return h, y, idx, wt


@functools.partial(jax.jit, static_argnames=("int8",))
def add_expert(y, h, idx, wt, expert, up, down, *, int8):
    """y + (the tokens' weight for ``expert``) * expert(h)."""
    mine = jnp.sum(jnp.where(idx == expert, wt, 0.0), axis=1)
    return y + mine[:, None] * _relu2_mlp(h, up, down, int8)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, norm_w, w_head, *, eps):
    return _mm(_rms(x, norm_w, eps), w_head)


def logits_at(state: dict, model: dict, ids, rows, *, int8: bool = False,
              one_group: bool = False):
    """Float32 logits [len(rows), V] at positions ``rows`` of one causal
    forward over ``ids`` [S] (right padding is invisible to the rows
    before it).  ``model`` holds the published keys, ``n_routed_experts``
    the router's width, and ``local_experts`` ``[first, count]`` (absent:
    every expert is held)."""
    eps = float(model["layer_norm_epsilon"])
    first, held = model.get("local_experts") or (
        0, int(model["n_routed_experts"]))
    x = jnp.take(state[EMBED], jnp.asarray(ids), axis=0).astype(jnp.float32)
    for n, c in enumerate(model["hybrid_override_pattern"]):
        p = f"backbone.layers.{n}."
        w = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
        if KINDS[c] == "attention":
            x = attention_block(
                x, w, heads=model["num_attention_heads"],
                kv_heads=model["num_key_value_heads"], eps=eps,
                int8=bool(int8))
        elif KINDS[c] == "mamba":
            x = mamba_block(
                x, w, heads=model["mamba_num_heads"],
                groups=model["n_groups"], d_state=model["ssm_state_size"],
                eps=eps, int8=bool(int8), one_group=bool(one_group))
        else:
            ups = w.pop("mixer.experts.up_proj.weight")
            downs = w.pop("mixer.experts.down_proj.weight")
            h, x, idx, wt = expert_block_shared(
                x, w, eps=eps, groups=model["n_group"],
                keep_groups=model["topk_group"],
                top_k=model["num_experts_per_tok"],
                norm=bool(model["norm_topk_prob"]),
                factor=float(model["routed_scaling_factor"]),
                int8=bool(int8))
            for e in range(held):
                x = add_expert(x, h, idx, wt, first + e, ups[e], downs[e],
                               int8=bool(int8))
    return head(x[jnp.asarray(rows)], state[NORM], state[HEAD], eps=eps)


def served_gaps(state: dict, model: dict, prompt, served, *, pad_to: int,
                pad_rows: int = 0, int8: bool = False,
                one_group: bool = False) -> dict:
    """Teacher-forced reading of one finished request.

    One forward over prompt + served tokens, padded to ``pad_to``.  At
    every position that produced a served token: the gap by which that
    token's reference logit lies below the reference's best.  With a
    control (``int8``, ``one_group``) the token judged is not the served
    one but the one the controlled forward puts first at that
    position."""
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    rows = np.arange(n_p - 1, n_p + n_s - 1)
    rows = np.concatenate([rows, np.full(max(0, pad_rows - n_s), rows[-1])])
    ref = logits_at(state, model, ids, rows)[:n_s]
    if int8 or one_group:
        judged = jnp.argmax(logits_at(
            state, model, ids, rows, int8=int8,
            one_group=one_group)[:n_s], axis=-1)
    else:
        judged = jnp.asarray(np.asarray(served, np.int32))
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, judged[:, None], axis=1)[:, 0]
    if not bool(jnp.all(jnp.isfinite(ref))):
        raise RuntimeError("reference logits are not finite")
    return {"gaps": np.asarray(gap), "best": np.asarray(best),
            "std": float(jnp.std(ref))}
