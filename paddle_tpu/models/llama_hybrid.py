"""Hybrid-parallel (pp × dp × tp + sp) Llama pretraining step, TPU-native.

Reference analog: the fleet hybrid-parallel stack —
fleet/meta_parallel/pipeline_parallel.py (1F1B :575, train_batch :820),
fleet/layers/mpu/mp_layers.py (Column/RowParallelLinear :336,:543),
fleet/utils/sequence_parallel_utils.py, hybrid_parallel_optimizer.py :266.

TPU formulation (SURVEY.md §7-§8): one jitted SPMD program over a
('pp','dp','tp') mesh.
  * tp  — GSPMD weight shardings (colwise Shard(-1) on q/k/v/gate/up,
          rowwise on o/down); XLA inserts the mp allreduces the reference
          codes by hand in mp_ops.py.
  * dp  — batch dim sharded; grad allreduce is XLA's psum, replacing the
          bucketed Reducer (fluid/distributed/collective/reducer.cc).
  * sp  — Megatron-SP: activations outside attention carry a
          sequence-dim sharding constraint over the tp axis, replacing the
          scatter/allgather PyLayers in sequence_parallel_utils.py.
  * pp  — stage-stacked weights sharded over 'pp'; activations hop
          stages via ppermute on ICI inside a shard_map that is manual
          over 'pp' only.  Two schedules: "gpipe" differentiates through
          the fill-drain scan (pipelining.py); "1f1b" (+ interleaved
          n_virtual>1) runs the hand-scheduled engine with bounded
          in-flight residuals (distributed/pipeline_schedules.py) —
          replacing pipeline_parallel.py:575/:1174 + p2p_communication.
  * remat — jax.checkpoint on the per-layer body (reference:
          fleet/recompute/recompute.py).

Everything below is pure functional jax: params/opt-state pytrees, one
donated train step.  This is the flagship path bench.py measures.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .llama import LlamaConfig, _rope_tables, apply_rotary_pos_emb
from ..distributed.pipeline_schedules import pipeline_1f1b
from ..ops.pallas.flash_attention import kernel_route, sdpa


# ----------------------------------------------------------------- mesh
def build_mesh(n_devices=None, pp=1, dp=1, tp=1, devices=None):
    """('pp','dp','tp') mesh. Axis sizes must multiply to n_devices."""
    devices = devices if devices is not None else jax.devices()
    n = n_devices or len(devices)
    assert pp * dp * tp == n, (pp, dp, tp, n)
    grid = np.asarray(devices[:n]).reshape(pp, dp, tp)
    return Mesh(grid, ("pp", "dp", "tp"))


def default_axes(n):
    """Factorize n into the most BALANCED (pp, dp, tp) triple — every
    axis exercised when possible (8 -> 2x2x2, 64 -> 4x4x4, the v5p-64
    shape of BASELINE.json's north star)."""
    pp = max(d for d in range(1, int(round(n ** (1 / 3))) + 1)
             if n % d == 0)
    rem = n // pp
    tp = max(d for d in range(1, int(rem ** 0.5) + 1) if rem % d == 0)
    return pp, rem // tp, tp


# ------------------------------------------------------------ parameters
def init_params(config: LlamaConfig, n_pp: int, key, dtype=jnp.float32,
                n_virtual: int = 1):
    """Params pytree. Decoder leaves are stage-stacked:
    [n_pp, layers_per_stage, ...] (or [n_pp, n_virtual, lps, ...] for the
    interleaved schedule — device s owns virtual stages {c*n_pp+s})."""
    sv = n_pp * n_virtual
    assert config.num_hidden_layers % sv == 0
    lps = config.num_hidden_layers // sv
    lead = (n_pp, n_virtual, lps) if n_virtual > 1 else (n_pp, lps)
    h, i = config.hidden_size, config.intermediate_size
    hd, nh, kvh = config.head_dim, config.num_attention_heads, \
        config.num_key_value_heads
    ks = jax.random.split(key, 9)

    def w(k, *shape, fan_in):
        std = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(k, lead + shape, jnp.float32)
                * std).astype(dtype)

    layer = {
        "input_ln": jnp.ones(lead + (h,), dtype),
        "q": w(ks[0], h, nh * hd, fan_in=h),
        "k": w(ks[1], h, kvh * hd, fan_in=h),
        "v": w(ks[2], h, kvh * hd, fan_in=h),
        "o": w(ks[3], nh * hd, h, fan_in=nh * hd),
        "post_ln": jnp.ones(lead + (h,), dtype),
        "gate": w(ks[4], h, i, fan_in=h),
        "up": w(ks[5], h, i, fan_in=h),
        "down": w(ks[6], i, h, fan_in=i),
    }
    emb = (jax.random.normal(ks[7], (config.vocab_size, h), jnp.float32)
           * 0.02).astype(dtype)
    head = (jax.random.normal(ks[8], (h, config.vocab_size), jnp.float32)
            / math.sqrt(h)).astype(dtype)
    return {"embed": emb, "stages": layer,
            "norm": jnp.ones((h,), dtype), "head": head}


def param_shardings(mesh: Mesh, n_virtual: int = 1):
    """NamedShardings implementing the reference TP plan + pp stacking."""
    s = functools.partial(NamedSharding, mesh)
    pad = (None,) * (1 if n_virtual > 1 else 0)  # extra chunk dim
    col = s(P("pp", *pad, None, None, "tp"))  # [pp,(v),lps,in,out] colwise
    row = s(P("pp", *pad, None, "tp", None))  # row-parallel
    ln = s(P("pp", *pad, None, None))
    return {
        "embed": s(P(None, "tp")),
        "stages": {"input_ln": ln, "q": col, "k": col, "v": col, "o": row,
                   "post_ln": ln, "gate": col, "up": col, "down": row},
        "norm": s(P(None)),
        "head": s(P(None, "tp")),
    }


# ------------------------------------------------------------- layer math
def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _attention(q, k, v, mesh):
    """Causal attention over [mb, S, heads, D], one call per shard.

    The Pallas kernel is one program per device and the partitioner
    cannot split it ("Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map"), so on a mesh the call is
    mapped: batch over dp, heads over tp.  Attention needs no collective
    — every head sees its whole sequence — and q heads stay beside their
    kv heads because tp divides both counts.  Every mesh axis has to be
    manual around a Mosaic call: with pp > 1 the layer already runs
    inside the pipeline's pp-manual region and dp, tp complete the set;
    with pp == 1 nothing is manual yet and all three are named."""
    if mesh is None or mesh.size == 1 or not kernel_route(q, k, v):
        return sdpa(q, k, v, is_causal=True)    # XLA: GSPMD partitions it
    nested = mesh.shape["pp"] > 1       # then the context mesh is used
    axes = {"dp", "tp"} if nested else set(mesh.axis_names)
    spec = P("dp", None, "tp", None)
    return jax.shard_map(
        lambda q, k, v: sdpa(q, k, v, is_causal=True),
        mesh=None if nested else mesh, in_specs=(spec, spec, spec),
        out_specs=spec, axis_names=frozenset(axes),
        check_vma=False)(q, k, v)


def _decoder_layer(lp, x, cos, sin, config: LlamaConfig, mesh=None):
    """One decoder layer, functional. x: [mb, S, H]."""
    nh, kvh, hd = (config.num_attention_heads, config.num_key_value_heads,
                   config.head_dim)
    b, sq, _ = x.shape
    r = x
    h = _rms(x, lp["input_ln"], config.rms_norm_eps)
    q = (h @ lp["q"]).reshape(b, sq, nh, hd)
    k = (h @ lp["k"]).reshape(b, sq, kvh, hd)
    v = (h @ lp["v"]).reshape(b, sq, kvh, hd)
    q, k = apply_rotary_pos_emb(q, k, cos, sin)
    a = _attention(q, k, v, mesh)
    from jax.ad_checkpoint import checkpoint_name as _ckpt_name
    a = _ckpt_name(a, "attn_out")
    x = r + (a.reshape(b, sq, nh * hd) @ lp["o"])
    r = x
    h = _rms(x, lp["post_ln"], config.rms_norm_eps)
    ff = jax.nn.silu(h @ lp["gate"]) * (h @ lp["up"])
    return r + ff @ lp["down"]


# Unroll the stage's layer loop instead of lax.scan.  The MoE-rung A/B
# measured ~2 ms/layer of scan stacked-weight overhead (BASELINE.md r5);
# default OFF here pending a same-session A/B on the 1B flagship (the
# scan is the known-good shipping config; flip via env to trial).
import os as _os

UNROLL_STAGE = _os.environ.get("PADDLE_TPU_UNROLL_STAGE", "0") == "1"


def _stage_fn(stage_params, x, cos, sin, config, remat=True, mesh=None):
    """Apply this stage's layers_per_stage layers (leaves [lps, ...]).
    remat: True = full per-layer checkpoint; "attn" = checkpoint but keep
    the flash-attention outputs resident (skips the most expensive
    recompute for ~1 GB at 1B/2k/8 scale); False = no remat."""
    body = functools.partial(_decoder_layer, cos=cos, sin=sin, config=config,
                             mesh=mesh)
    if remat == "attn":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out"))
    elif remat:
        body = jax.checkpoint(body)

    lps = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    if UNROLL_STAGE and lps <= 32:
        h = x
        for i in range(lps):
            lp = jax.tree_util.tree_map(lambda a: a[i], stage_params)
            h = body(lp, h)
        return h

    def scan_body(h, lp):
        return body(lp, h), None
    out, _ = jax.lax.scan(scan_body, x, stage_params)
    return out


# --------------------------------------------------------------- pipeline
def pipelined_trunk(stacked, mbs, cos, sin, config, mesh, remat=True):
    """mbs: [M, mb, S, H] -> outputs of final stage, same shape.
    Manual over 'pp' only; dp/tp/sp stay under GSPMD inside."""
    n_pp = mesh.shape["pp"]
    if n_pp == 1:
        squeeze = jax.tree_util.tree_map(lambda a: a[0], stacked)
        return jax.vmap(
            lambda mb: _stage_fn(squeeze, mb, cos, sin, config, remat,
                                 mesh))(mbs)

    def per_device(stk, mbs):
        lp = jax.tree_util.tree_map(lambda a: a[0], stk)  # my stage
        stage = jax.lax.axis_index("pp")
        m = mbs.shape[0]
        total = m + n_pp - 1
        perm = [(i, (i + 1) % n_pp) for i in range(n_pp)]

        def tick(carry, t):
            state, outs = carry
            inj = mbs[jnp.minimum(t, m - 1)]
            state = jnp.where(stage == 0, inj, state)
            state = _stage_fn(lp, state, cos, sin, config, remat, mesh)
            oi = t - (n_pp - 1)
            ok = jnp.logical_and(stage == n_pp - 1,
                                 jnp.logical_and(oi >= 0, oi < m))
            idx = jnp.clip(oi, 0, m - 1)
            outs = outs.at[idx].set(jnp.where(ok, state, outs[idx]))
            state = jax.lax.ppermute(state, "pp", perm)
            return (state, outs), None

        init = (jnp.zeros_like(mbs[0]), jnp.zeros_like(mbs))
        (_, outs), _ = jax.lax.scan(tick, init, jnp.arange(total))
        # keep outs pp-stacked: only the last stage's row is real, and the
        # caller slices it — a broadcast from the last stage replaces the
        # old full-buffer psum (pp x less data on the wire)
        return outs[None]

    stacked_out = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), stacked), P()),
        out_specs=P("pp"), axis_names=frozenset({"pp"}),
        check_vma=False)(stacked, mbs)
    return stacked_out[-1]


# ------------------------------------------------------------- train step
def loss_fn(params, ids, config: LlamaConfig, mesh: Mesh, n_micro=1,
            remat=True, sp=True):
    """Next-token CE over a [B, S+1] token batch."""
    inp, lab = ids[:, :-1], ids[:, 1:]
    b, s = inp.shape
    x = jnp.take(params["embed"], inp, axis=0)
    if mesh.shape["tp"] > 1:
        # the gather of a col-sharded [V, H/tp] table keeps tp on the
        # hidden dim; saying so stops GSPMD's "involuntary full
        # rematerialization" (replicate-then-reshard) of the embedding
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", None, "tp")))
    if sp and mesh.shape["tp"] > 1 and s % mesh.shape["tp"] == 0:
        # Megatron-SP: sequence dim sharded over tp outside attention
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "tp", None)))
    cos, sin = _rope_tables(s, config.head_dim, config.rope_theta)
    mb = b // n_micro
    mbs = x.reshape(n_micro, mb, s, x.shape[-1])
    out = pipelined_trunk(params["stages"], mbs, cos, sin, config, mesh,
                          remat)
    h = out.reshape(b, s, -1)
    h = _rms(h, params["norm"], config.rms_norm_eps)
    return _chunked_ce_sum(h, lab, params["head"]) / (b * s)


def _chunked_ce_sum(h, lab, head):
    """Summed next-token CE.  For small [B,S,V] (≤ ~1.1 GB fp32) the
    logits fit HBM and ONE wide matmul beats the chunked path (the
    [tokens, V] head matmul is the fastest shape on the chip — measured
    ~8% of the MoE-rung step).  Above that, chunk over the sequence dim
    so the full fp32 logits never materialize (the usual OOM at vocab
    32k+); logsumexp's VJP re-derives softmax from the saved chunk logits
    instead of keeping a log_softmax copy."""
    b, s = lab.shape
    v = head.shape[-1]

    def ce_chunk(args):
        hc, lc = args
        logits = (hc @ head).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - tgt)

    if b * s * v * 4 <= 1.1e9:
        return ce_chunk((h.reshape(b * s, -1), lab.reshape(b * s)))

    n_chunks = next(c for c in (8, 7, 6, 5, 4, 3, 2, 1) if s % c == 0)
    hs = h.reshape(b, n_chunks, s // n_chunks, h.shape[-1]).swapaxes(0, 1)
    ls = lab.reshape(b, n_chunks, s // n_chunks).swapaxes(0, 1)
    return jnp.sum(jax.lax.map(jax.checkpoint(ce_chunk), (hs, ls)))


def grad_1f1b(params, ids, config: LlamaConfig, mesh: Mesh, n_micro,
              n_virtual=1, remat=True, sp=True, zero_bubble=False):
    """(loss, grads) via the hand-scheduled 1F1B / interleaved pipeline
    (distributed/pipeline_schedules.py) instead of AD through the GPipe
    scan.  Embedding runs at stage 0, final-norm+head+CE at the last
    stage, so each microbatch's backward starts as soon as its forward
    leaves the pipe — in-flight residuals are bounded by ~2*pp
    microbatches instead of all of them.

    Reference: fleet/meta_parallel/pipeline_parallel.py:575 (1F1B),
    :1174 (interleaved VPP)."""
    b, s_tot = ids.shape
    s = s_tot - 1
    assert b % n_micro == 0, (b, n_micro)
    aux = ids.reshape(n_micro, b // n_micro, s_tot)
    fp = {"embed": params["embed"]}
    lp = {"norm": params["norm"], "head": params["head"]}
    inv_tok = 1.0 / (b * s)
    cos, sin = _rope_tables(s, config.head_dim, config.rope_theta)

    def first_fn(fp, aux_j):
        # NOTE: unlike loss_fn, no explicit with_sharding_constraint here
        # — the XLA SPMD partitioner aborts on auto-axis constraints
        # inside this pp-manual shard_map (jaxlib 0.9 CPU, verified).
        # tp/dp placement of the gather follows GSPMD propagation from
        # the tp-sharded table instead; `sp` is honored by the gpipe
        # schedule only.
        return jnp.take(fp["embed"], aux_j[:, :-1], axis=0)

    def stage_fn(cp, x):
        return _stage_fn(cp, x, cos, sin, config, remat, mesh)

    def last_fn(lp, y, aux_j):
        h = _rms(y, lp["norm"], config.rms_norm_eps)
        return _chunked_ce_sum(h, aux_j[:, 1:], lp["head"]) * inv_tok

    stages = params["stages"]
    if n_virtual == 1:  # [pp, lps, ...] -> engine layout [pp, 1, lps, ...]
        stages = jax.tree_util.tree_map(lambda a: a[:, None], stages)
    loss, dstk, dfp, dlp = pipeline_1f1b(
        stage_fn, first_fn, last_fn, stages, fp, lp, aux, mesh,
        n_virtual=n_virtual, zero_bubble=zero_bubble)
    if n_virtual == 1:
        dstk = jax.tree_util.tree_map(lambda a: a[:, 0], dstk)
    grads = {"embed": dfp["embed"], "stages": dstk,
             "norm": dlp["norm"], "head": dlp["head"]}
    return loss, grads


class AdamWState(NamedTuple):
    step: jax.Array
    m: dict
    v: dict


def _f32_zeros_like(params):
    """fp32 buffers matching the param tree (optimizer state and grad
    accumulators share this dtype/shape contract)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def init_adamw(params):
    """Zero AdamW state placed like the (concrete) params: each zero
    buffer is made shard by shard where its param lives.  Plain
    ``jnp.zeros`` lands whole on ``jax.devices()[0]`` and the first step
    then replicates it — 12 GB of state on every chip for a 1.5 B model
    whose shards are 0.4 B."""
    def zeros(p):
        return jnp.zeros(p.shape, jnp.float32, device=p.sharding)
    return AdamWState(jnp.zeros((), jnp.int32),
                      jax.tree_util.tree_map(zeros, params),
                      jax.tree_util.tree_map(zeros, params))


def build_train_step(config: LlamaConfig, mesh: Mesh, lr=3e-4, wd=0.01,
                     n_micro=1, remat=True, sp=True, b1=0.9, b2=0.95,
                     eps=1e-8, grad_accum=1, schedule="gpipe",
                     n_virtual=1, zero1=False):
    """Returns jitted (params, opt, ids) -> (loss, params, opt).

    schedule: "gpipe" = AD through the fill-drain scan (pipelining.py);
    "1f1b" = hand-scheduled 1F1B (pipeline_schedules.py) with bounded
    in-flight residuals; "zb" = 1F1B with the ZB-H1 deferred-dW unit
    placement (zero_bubble=True, composes with VPP); n_virtual > 1
    selects the interleaved/VPP variant (params must come from
    setup(..., n_virtual=v)).

    grad_accum > 1 splits the batch into sequential chunks and averages
    their grads before ONE optimizer step (reference: gradient-merge
    pass / fleet accumulate_steps) — live activations stay bounded by
    one chunk, trading wall-clock for a larger effective batch."""
    use_1f1b = schedule in ("1f1b", "zb") and mesh.shape["pp"] > 1
    if n_virtual > 1 and not use_1f1b:
        raise ValueError(
            "n_virtual > 1 (interleaved/VPP) requires schedule='1f1b' "
            f"or 'zb' and a pp axis > 1; got schedule={schedule!r}, "
            f"pp={mesh.shape['pp']}")

    def one_batch(params, ids):
        if use_1f1b:
            return grad_1f1b(params, ids, config, mesh, n_micro,
                             n_virtual, remat, sp,
                             zero_bubble=schedule == "zb")
        return jax.value_and_grad(loss_fn)(
            params, ids, config, mesh, n_micro, remat, sp)

    def grad_of(params, ids):
        if grad_accum == 1:
            return one_batch(params, ids)
        b = ids.shape[0]
        assert b % grad_accum == 0, (b, grad_accum)
        chunks = ids.reshape(grad_accum, b // grad_accum, ids.shape[1])

        def acc(carry, chunk):
            lsum, gsum = carry
            loss, grads = one_batch(params, chunk)
            gsum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gsum, grads)
            return (lsum + loss, gsum), None

        (lsum, gsum), _ = jax.lax.scan(
            acc, (jnp.float32(0.0), _f32_zeros_like(params)), chunks)
        inv = 1.0 / grad_accum
        return lsum * inv, jax.tree_util.tree_map(
            lambda g: g * inv, gsum)

    def step(params, opt, ids):
        loss, grads = grad_of(params, ids)
        t = opt.step + 1
        tf = t.astype(jnp.float32)

        def upd(p, g, m, v, osh=None, psh=None):
            gf = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * gf
            v = b2 * v + (1 - b2) * jnp.square(gf)
            if osh is not None:
                # ZeRO-1: keep the fp32 state dp-sharded through the
                # update (each dp rank updates only its slice; GSPMD
                # shards the surrounding arithmetic to match)
                m = jax.lax.with_sharding_constraint(m, osh)
                v = jax.lax.with_sharding_constraint(v, osh)
            mhat = m / (1 - b1 ** tf)
            vhat = v / (1 - b2 ** tf)
            pf = p.astype(jnp.float32)
            pf = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
            new_p = pf.astype(p.dtype)
            if psh is not None:
                # pin the updated param BACK to its own sharding: mixing
                # dp-sharded m/v into the update would otherwise let
                # GSPMD return dp-sharded params, violating the stage-1
                # contract (params stay replicated over dp) and forcing
                # a recompile + per-step all-gathers on the next call
                new_p = jax.lax.with_sharding_constraint(new_p, psh)
            return new_p, m, v

        flat_p, td = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(opt.m)
        flat_v = jax.tree_util.tree_leaves(opt.v)
        if zero1:
            flat_osh = jax.tree_util.tree_leaves(
                zero1_shardings(params, mesh, n_virtual))
            psh_tree = param_shardings(mesh, n_virtual)
            flat_psh = [
                NamedSharding(mesh, P(*(list(sh.spec)
                                        + [None] * (p.ndim
                                                    - len(sh.spec)))))
                for p, sh in zip(
                    flat_p, jax.tree_util.tree_leaves(psh_tree))]
        else:
            flat_osh = [None] * len(flat_p)
            flat_psh = [None] * len(flat_p)
        out = [upd(p, g, m, v, osh, psh) for p, g, m, v, osh, psh
               in zip(flat_p, flat_g, flat_m, flat_v, flat_osh, flat_psh)]
        new_p = jax.tree_util.tree_unflatten(td, [o[0] for o in out])
        new_m = jax.tree_util.tree_unflatten(td, [o[1] for o in out])
        new_v = jax.tree_util.tree_unflatten(td, [o[2] for o in out])
        return loss, new_p, AdamWState(t, new_m, new_v)

    return jax.jit(step, donate_argnums=(0, 1))


def zero1_shardings(params, mesh, n_virtual=1):
    """ZeRO-1 (sharding stage 1, reference fleet DygraphShardingOptimizer):
    optimizer-state shardings = the param sharding with the first
    dp-divisible unsharded axis re-sharded over 'dp', so each dp rank
    holds 1/dp of the fp32 m/v state.  Params/grads stay dp-replicated —
    GSPMD inserts the gather on read, which is exactly stage 1."""
    base = param_shardings(mesh, n_virtual)
    dp = mesh.shape["dp"]

    def one(p, sh):
        spec = list(sh.spec) + [None] * (p.ndim - len(sh.spec))
        if dp > 1:
            for ax in range(p.ndim):
                if spec[ax] is None and p.shape[ax] % dp == 0:
                    spec[ax] = "dp"
                    break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, params, base)


def setup(config: LlamaConfig, mesh: Mesh, seed=0, dtype=jnp.float32,
          n_virtual=1, zero1=False):
    """Init + place params and optimizer state on the mesh.
    zero1=True places AdamW m/v dp-sharded (pair with
    build_train_step(zero1=True))."""
    params = init_params(config, mesh.shape["pp"], jax.random.key(seed),
                         dtype, n_virtual)
    sh = param_shardings(mesh, n_virtual)
    params = jax.tree_util.tree_map(jax.device_put, params, sh)
    opt = init_adamw(params)
    if zero1:
        osh = zero1_shardings(params, mesh, n_virtual)
        opt = AdamWState(
            opt.step,
            jax.tree_util.tree_map(jax.device_put, opt.m, osh),
            jax.tree_util.tree_map(jax.device_put, opt.v, osh))
    return params, opt
