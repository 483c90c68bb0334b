"""Per-op microbenchmark + regression gate.

Reference analog: tools/ci_op_benchmark.sh + check_op_benchmark_result.py
— the reference gates op-level perf in CI against stored baselines so a
kernel regression (like the r2 eager-dispatch cost) trips a wire instead
of surfacing as a mysterious end-to-end slowdown.

Usage:
    python tools/op_bench.py                 # run suite, print JSON lines
    python tools/op_bench.py --save          # write tools/op_baseline.json
    python tools/op_bench.py --check [tol]   # exit 1 on >tol regression

Timing methodology: each case runs inside one jitted lax.scan chain (a
data dependency threads iterations) and cost is the T(n2)-T(n1) delta —
host-fetch and dispatch latency cancel.
Run --check on an otherwise-idle host: heavy concurrent CPU load can
skew the calibration pass and produce a false 2-3x reading (observed
once against a full pytest run; re-run confirms).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BASELINE = os.path.join(os.path.dirname(__file__), "op_baseline.json")


def device_time(f, *args, reps=7, target=0.15):
    """Auto-calibrated scan-delta: chain length scales until the timed
    span is ~`target` seconds, so sub-0.1ms ops stay above the host's
    dispatch/fetch jitter."""
    args = tuple(jnp.asarray(a) for a in args)

    def chain(n):
        @jax.jit
        def run(args):
            def body(c, _):
                bump = (args[0].astype(jnp.float32)
                        + c * 1e-30).astype(args[0].dtype)
                out = f(bump, *args[1:])
                leaf = jax.tree_util.tree_leaves(out)[0]
                return c + leaf.reshape(-1)[0].astype(jnp.float32) * 1e-30, \
                    None
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
            return c
        return run

    # rough calibration pass
    # every timed execution gets FRESH input values, so no two timed
    # calls dispatch the same (executable, buffers) pair
    def variant(i):
        # 1% steps: large enough to change the BITS in bfloat16 (a 1e-6
        # bump rounds away and the buffers stay identical)
        return tuple(
            (a * (1 + (i + 1) * 0.01)).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a
            for a in args)

    variants = [variant(i) for i in range(2 * reps + 2)]
    jax.block_until_ready(variants)
    vi = iter(variants)

    probe = chain(64)
    float(probe(args))
    t0 = time.perf_counter(); float(probe(next(vi)))
    est = max((time.perf_counter() - t0) / 64, 1e-7)
    n2 = int(min(4000, max(60, target / est)))
    n1 = max(4, n2 // 6)
    r1, r2 = chain(n1), chain(n2)
    float(r1(args)); float(r2(args))
    deltas = []
    for _ in range(reps):
        a1, a2 = next(vi), next(vi)
        t0 = time.perf_counter(); float(r1(a1)); t1 = time.perf_counter() - t0
        t0 = time.perf_counter(); float(r2(a2)); t2 = time.perf_counter() - t0
        deltas.append((t2 - t1) / (n2 - n1))
    # median of positive deltas: host jitter inflates AND deflates
    # individual two-length differences, so the floor statistic can
    # latch onto sub-physical values — the median is the stable center
    pos = sorted(d for d in deltas if d > 0)
    if not pos:
        return 0.0
    return pos[len(pos) // 2]


def _cases():
    """The hot-op suite: matmul/conv/norm/attention/softmax/MoE-dispatch
    shapes the bench ladder leans on."""
    key = jax.random.PRNGKey(0)
    on_tpu = jax.devices()[0].platform != "cpu"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    big = 2048 if on_tpu else 128
    cases = {}

    a = jax.random.normal(key, (big, big), dt)
    cases["matmul_2kx2k"] = (lambda a: a @ a, (a,))

    x4 = jax.random.normal(key, (32, 56, 56, 64), dt)
    w4 = jax.random.normal(key, (3, 3, 64, 64), dt) * 0.1

    def conv(x, w=w4):
        dn = jax.lax.conv_dimension_numbers(
            x.shape, w4.shape, ("NHWC", "HWIO", "NHWC"))
        return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                            dimension_numbers=dn)
    cases["conv3x3_56x56x64"] = (conv, (x4,))

    xb = jax.random.normal(key, (32, 56, 56, 64), dt)

    def bn(x):
        from paddle_tpu.nn.functional import batch_norm
        out, _, _ = batch_norm.__op_body__(
            x, jnp.zeros(64), jnp.ones(64), jnp.ones(64), jnp.zeros(64),
            training=True, data_format="NHWC")
        return out
    cases["batch_norm_train"] = (bn, (xb,))

    s = 512 if on_tpu else 128
    q = jax.random.normal(key, (4, s, 8, 64), dt)

    def flash(q):
        from paddle_tpu.ops.pallas.flash_attention import sdpa
        return sdpa(q, q, q, is_causal=True)
    cases["flash_causal_s512"] = (flash, (q,))

    xs = jax.random.normal(key, (4096, 1024) if on_tpu else (256, 64), dt)
    cases["softmax_wide"] = (lambda x: jax.nn.softmax(
        x.astype(jnp.float32), axis=-1), (xs,))

    tok = jax.random.normal(key, (4096 if on_tpu else 128, 512), dt)
    gw = jax.random.normal(key, (512, 8), jnp.float32) * 0.3

    def moe_disp(x, gw=gw):
        from paddle_tpu.distributed.moe import (sort_dispatch_combine,
                                                _topk_choices, _capacity)
        logits = x @ gw.astype(x.dtype)
        idx, gv, _aux = _topk_choices(logits, 2, False, None)
        cap = _capacity(x.shape[0], 2, 1.25, 8, None)
        return sort_dispatch_combine(x, idx, gv, 8, cap, lambda t: t)
    cases["moe_sort_dispatch"] = (moe_disp, (tok,))

    emb = jax.random.normal(key, (32000, 512) if on_tpu else (1000, 64),
                            jnp.float32)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, emb.shape[0], (64, 128)))
    cases["embedding_gather"] = (lambda e: jnp.take(e, ids, axis=0), (emb,))

    # ================= round-4 widening (VERDICT r3 #6): every op
    # family the bench ladder touches gets a gated shape ===============
    rs = np.random.RandomState(1)

    def _grad(f):
        return jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32)))

    # ---- matmul family: decode GEMV, lm_head, weight-only kernels ----
    hK, hN, vN = (2048, 5632, 32000) if on_tpu else (128, 256, 512)
    hvec = jnp.asarray(rs.randn(8, hK) * 0.3, dt)
    wKN = jnp.asarray(rs.randn(hK, hN) * 0.02, dt)
    wKV = jnp.asarray(rs.randn(hK, vN) * 0.02, dt)
    cases["matmul_gemv_decode"] = (lambda h: h @ wKN, (hvec,))
    cases["matmul_lmhead"] = (lambda h: h @ wKV, (hvec,))
    if on_tpu:
        from paddle_tpu.ops.pallas import quant_matmul as QM
        q8 = jnp.asarray(rs.randint(-127, 128, (hK, hN)), jnp.int8)
        sc = jnp.asarray(rs.rand(hN).astype(np.float32) * 0.01)
        wq8 = QM.QuantizedWeight(q8, sc, kind="int8")
        wq4 = QM.QuantizedWeight(QM.pack_int4(
            jnp.clip(q8, -8, 7)), sc, kind="int4", k=hK)
        cases["wo_int8_gemv"] = (
            lambda h: QM.weight_only_matmul(h, wq8), (hvec,))
        cases["wo_int4_gemv"] = (
            lambda h: QM.weight_only_matmul(h, wq4), (hvec,))

    # ---- norms fwd + bwd ---------------------------------------------
    xn = jax.random.normal(key, (4096, 2048) if on_tpu else (64, 64), dt)
    gn = jnp.ones((xn.shape[-1],), dt)

    def rms(x):
        from paddle_tpu.ops.pallas.rms_norm import rms_norm
        return rms_norm(x, gn, 1e-6)
    cases["rms_norm_fwd"] = (rms, (xn,))
    cases["rms_norm_bwd"] = (_grad(rms), (xn,))

    def ln(x):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(
            xf.var(-1, keepdims=True) + 1e-5)).astype(x.dtype) * gn
    cases["layer_norm_fwd"] = (ln, (xn,))
    cases["layer_norm_bwd"] = (_grad(ln), (xn,))
    cases["batch_norm_bwd"] = (_grad(lambda x: bn(x)), (xb,))

    # ---- attention variants ------------------------------------------
    from paddle_tpu.ops.pallas.flash_attention import sdpa as _sdpa
    cases["flash_causal_bwd_s512"] = (_grad(
        lambda q: _sdpa(q, q, q, is_causal=True)), (q,))
    qg = jax.random.normal(key, (4, s, 8, 64), dt)
    kg = jax.random.normal(key, (4, s, 2, 64), dt)
    cases["flash_gqa_fwd"] = (
        lambda qq: _sdpa(qq, kg, kg, is_causal=True), (qg,))
    if on_tpu:
        from paddle_tpu.ops.pallas import flash_mask as FM
        seg = np.zeros((4, s), np.int32)
        seg[:, s // 2:] = 1
        vecs = FM.segment_intervals(jnp.asarray(seg), causal=True)
        cases["flashmask_fwd"] = (
            lambda qq: _sdpa(qq, qq, qq, flashmask=vecs, is_causal=True),
            (q,))
        cases["flashmask_bwd"] = (_grad(
            lambda qq: _sdpa(qq, qq, qq, flashmask=vecs, is_causal=True)),
            (q,))
        sl = 8192
        ql = jax.random.normal(key, (1, sl, 4, 128), dt)
        cases["flash_streamed_8k_fwd"] = (
            lambda qq: _sdpa(qq, qq, qq, is_causal=True), (ql,))
        # decode + paged serving kernels
        from paddle_tpu.ops.pallas.decode_attention import decode_attention
        dq8 = jax.random.normal(key, (8, 16, 128), dt)
        kc = jax.random.normal(key, (8, 16, 2048, 128), dt)
        pos = jnp.full((8,), 1500, jnp.int32)
        cases["decode_attention_t2048"] = (
            lambda qq: decode_attention(qq, kc, kc, pos), (dq8,))

    # ---- activations / elementwise -----------------------------------
    cases["gelu_fwd"] = (jax.nn.gelu, (xn,))
    cases["silu_mul_ffn"] = (
        lambda x: jax.nn.silu(x) * x, (xn,))
    cases["softmax_bwd"] = (_grad(
        lambda x: jax.nn.softmax(x.astype(jnp.float32), axis=-1)), (xs,))
    cases["bf16_cast_roundtrip"] = (
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), (xs,))

    # ---- loss / sampling ---------------------------------------------
    vlab = jnp.asarray(rs.randint(0, vN, (256,)))
    hl = jax.random.normal(key, (256, hK), dt)

    def ce(h):
        logits = (h @ wKV).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, vlab[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - tgt)
    cases["cross_entropy_32k"] = (ce, (hl,))
    cases["cross_entropy_32k_bwd"] = (jax.grad(ce), (hl,))
    cases["top_k_logits"] = (
        lambda h: jax.lax.top_k(h @ wKV, 50)[0], (hvec,))

    # ---- optimizer steps ---------------------------------------------
    pt = jax.random.normal(key, (4096, 2048) if on_tpu else (64, 64),
                           jnp.float32)

    def adamw(p):
        m = 0.9 * p + 0.1 * p
        v_ = 0.95 * jnp.square(p) + 0.05
        return p - 1e-3 * (m / (jnp.sqrt(v_) + 1e-8) + 0.01 * p)
    cases["adamw_update_8m"] = (adamw, (pt,))
    cases["momentum_update_8m"] = (
        lambda p: p - 0.1 * (0.9 * p + p), (pt,))

    # ---- data movement -----------------------------------------------
    cases["kv_cache_update"] = (
        lambda c: jax.lax.dynamic_update_slice_in_dim(
            c, c[:, :, :1] * 2, 100, axis=2),
        (jax.random.normal(key, (8, 16, 512, 128) if on_tpu else
                           (2, 4, 64, 32), dt),))
    cases["transpose_bshd_bhsd"] = (
        lambda x: jnp.swapaxes(x, 1, 2).copy(),
        (jax.random.normal(key, (8, 512, 16, 128) if on_tpu else
                           (2, 64, 4, 32), dt),))
    cases["argsort_32k"] = (
        lambda x: jnp.argsort(x, axis=-1),
        (jax.random.normal(key, (64, 32000) if on_tpu else (8, 512),
                           jnp.float32),))
    cases["scatter_add_rows"] = (
        lambda e: e.at[ids[0]].add(1.0), (emb,))

    # ---- rope ---------------------------------------------------------
    from paddle_tpu.models.llama import _rope_tables, _rotate_half
    cos_t, sin_t = _rope_tables(s, 64, 10000.0)

    def rope(qq):
        c = cos_t[None, :, None, :].astype(qq.dtype)
        si = sin_t[None, :, None, :].astype(qq.dtype)
        return qq * c + _rotate_half(qq) * si
    cases["rope_apply"] = (rope, (q,))

    # ---- conv bwd ------------------------------------------------------
    cases["conv3x3_bwd"] = (_grad(conv), (x4,))

    return cases


def run_suite():
    out = {}
    for name, (f, args) in _cases().items():
        try:
            dt = device_time(f, *args)
        except Exception as e:  # keep the rest of the suite running
            print(json.dumps({"op": name,
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
            continue
        out[name] = dt
        print(json.dumps({"op": name, "ms": round(dt * 1e3, 4)}),
              flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", action="store_true",
                    help="store results as the regression baseline")
    ap.add_argument("--check", nargs="?", const=2.0, type=float,
                    default=None, metavar="TOL",
                    help="fail if any op is > TOL x its baseline "
                         "(default 2.0)")
    ap.add_argument("--runs", type=int, default=None,
                    help="full-suite repetitions; per-op MEDIAN is the "
                         "result (default: 5 for --save, 3 for --check) "
                         "— single runs vary with the host's load")
    args = ap.parse_args(argv)

    n_runs = args.runs or (5 if args.save else 3 if args.check else 1)
    runs = [run_suite() for _ in range(n_runs)]
    results = {}
    all_keys = sorted({k for r in runs for k in r})  # union: an op that
    for k in all_keys:       # errored in run 0 must not escape the gate
        vals = sorted(r[k] for r in runs if k in r)
        if vals:
            results[k] = vals[len(vals) // 2]
    if n_runs > 1:
        for k, v in results.items():
            print(json.dumps({"op": k, "median_ms": round(v * 1e3, 4),
                              "runs": n_runs}), flush=True)
    if args.save:
        meta = {"device": jax.devices()[0].device_kind,
                "ops": {k: v for k, v in results.items()}}
        with open(BASELINE, "w") as f:
            json.dump(meta, f, indent=1)
        print(f"baseline saved: {BASELINE}")
        return 0
    if args.check is not None:
        if not os.path.exists(BASELINE):
            print("no baseline stored; run with --save first")
            return 0
        with open(BASELINE) as f:
            base = json.load(f)
        if base.get("device") != jax.devices()[0].device_kind:
            print(f"baseline device {base.get('device')!r} != current "
                  f"{jax.devices()[0].device_kind!r}; skipping gate")
            return 0
        cases = _cases()
        # common-mode rejection: what moves EVERY op together between
        # runs is the host, not a kernel; a regression is an op that
        # slowed relative to the rest.  Normalize by the median per-op
        # ratio before applying the tolerance.
        ratios = sorted(v / base["ops"][k] for k, v in results.items()
                        if base["ops"].get(k))
        mode = ratios[len(ratios) // 2] if ratios else 1.0
        # clamp: a uniformly faster run is not a shield, and a >5x
        # "uniform slowdown" is beyond any observed run-to-run swing —
        # past that the ops themselves must answer for it
        mode = min(max(mode, 1.0), 5.0)
        bad = []
        for k, v in results.items():
            b = base["ops"].get(k)
            if b:
                b = b * mode
            if not b or v <= b * args.check:
                continue
            # retry-to-confirm: a REAL regression reproduces, a spike
            # of host jitter does not
            best = v
            for _ in range(2):
                try:
                    f, a = cases[k]
                    best = min(best, device_time(f, *a))
                except Exception:
                    break
                if best <= b * args.check:
                    break
            if best > b * args.check:
                bad.append((k, b, best))
        for k, b, v in bad:
            print(f"REGRESSION {k}: {v*1e3:.3f} ms vs baseline "
                  f"{b*1e3:.3f} ms (> {args.check}x, confirmed x3)")
        return 1 if bad else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
