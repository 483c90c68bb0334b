"""Platform-ceiling measurements — re-runnable evidence for or against
the claim that the ResNet and MoE rungs are bound by the platform's
shapes (VERDICT r3 weak #2/#3: the claim must be driver-verifiable, not
builder lore).

Measures with SELF-FEEDING timed chains (x_{t+1} = f(x_t)): every probe
feeds its output back into its input, so no two iterations compute on
the same bits and nothing can collapse the repeats:

  * big/medium square matmuls — the chip's practical matmul ceiling;
  * the three conv shapes ResNet50 spends its time in;
  * raw-jax ResNet50 train step (BN on and off) — the framework-free
    ceiling the vision rung is judged against;
  * the MoE expert-FFN matmul at the bench rung's shapes.

Usage: python tools/platform_ceiling.py   # prints one JSON line each
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

def _emit(name, tfs, detail=None):
    print(json.dumps({"probe": name, "tflops": round(tfs, 2),
                      **(detail or {})}), flush=True)
    return tfs


def _chain_time(step, x0, iters=None, reps=3, target=0.6):
    """Self-feeding timed chain: x_{t+1} = step(x_t), so every
    iteration's INPUT BITS differ and nothing can collapse repeats (the
    op_bench methodology note).  Returns seconds per step via a
    two-length delta so dispatch and fetch latency cancel."""
    import time

    def chain(n):
        @jax.jit
        def run(x):
            def body(x, _):
                return step(x), None
            x, _ = jax.lax.scan(body, x, None, length=n)
            # reduce over EVERY leaf: depending on one leaf lets XLA
            # dead-code the whole chain when that leaf happens to be a
            # fixed point (observed: summing an unused-BN param turned
            # the resnet probe into a no-op reading 115 PF/s)
            return sum(jnp.sum(l.astype(jnp.float32))
                       for l in jax.tree_util.tree_leaves(x))
        return run

    # every timed call gets FRESH input values (op_bench methodology
    # note) — 1% steps so the bf16 bits actually change
    def variant(i):
        return jax.tree_util.tree_map(
            lambda a: (a * (1 + (i + 1) * 0.01)).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, x0)

    variants = [variant(i) for i in range(2 * reps + 2)]
    jax.block_until_ready(variants)
    vi = iter(variants)

    probe = chain(8)
    float(probe(x0))
    t0 = time.perf_counter()
    float(probe(next(vi)))
    est = max((time.perf_counter() - t0) / 8, 1e-7)
    n2 = int(min(4000, max(24, target / est)))
    n1 = max(4, n2 // 4)
    r1, r2 = chain(n1), chain(n2)
    float(r1(x0))
    float(r2(x0))
    deltas = []
    for _ in range(reps):
        a1, a2 = next(vi), next(vi)
        t0 = time.perf_counter()
        float(r1(a1))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(r2(a2))
        t2 = time.perf_counter() - t0
        deltas.append((t2 - t1) / (n2 - n1))
    pos = sorted(d for d in deltas if d > 0)
    return pos[len(pos) // 2] if pos else float("inf")


def _renorm(y):
    """Keep a self-feeding chain's values ~unit-scale (and the bits
    changing) without meaningful cost next to the op under test."""
    yf = y.astype(jnp.float32)
    return (yf * jax.lax.rsqrt(jnp.mean(jnp.square(yf)) + 1e-6)).astype(
        y.dtype)


def matmul_ceilings():
    rs = np.random.RandomState(0)
    for n in (8192, 4096, 2048):
        a = jnp.asarray(rs.randn(n, n) * 0.1, jnp.bfloat16)
        dt = _chain_time(lambda x: _renorm(x @ x), a)
        _emit(f"matmul_{n}", 2 * n ** 3 / dt / 1e12)
    # the skinny-N shape decode lives in
    a = jnp.asarray(rs.randn(8, 4096) * 0.1, jnp.bfloat16)
    b = jnp.asarray(rs.randn(4096, 256) * 0.1, jnp.bfloat16)

    def skinny(x):
        y = x @ b                      # [8, 256]
        # fold the result back so the next input's bits change
        return _renorm(x + jnp.pad(y, ((0, 0), (0, 4096 - 256))))
    dt = _chain_time(skinny, a)
    _emit("matmul_skinny_8x4096x256", 2 * 8 * 4096 * 256 / dt / 1e12)


def conv_ceilings():
    rs = np.random.RandomState(1)
    shapes = [  # (N, H, W, C, k) — resnet50's hot trio (stride 1)
        (128, 56, 56, 64, 3),
        (128, 28, 28, 128, 3),
        (128, 14, 14, 256, 3),
    ]
    for (n, h, w, c, k) in shapes:
        x = jnp.asarray(rs.randn(n, h, w, c) * 0.1, jnp.bfloat16)
        kw = jnp.asarray(rs.randn(k, k, c, c) * 0.1, jnp.bfloat16)

        def f(x, kw=kw):
            dn = jax.lax.conv_dimension_numbers(
                x.shape, kw.shape, ("NHWC", "HWIO", "NHWC"))
            return _renorm(jax.lax.conv_general_dilated(
                x, kw, (1, 1), "SAME", dimension_numbers=dn))
        dt = _chain_time(f, x)
        flops = 2 * n * h * w * c * c * k * k
        _emit(f"conv{k}x{k}_{h}x{w}x{c}", flops / dt / 1e12)


# --------------------------- raw-jax resnet50 (framework-free ceiling)
_BLOCKS = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]


def _rn_params(key):
    p = {}
    ks = iter(jax.random.split(key, 256))

    def conv_w(ci, co, k):
        return jax.random.normal(next(ks), (k, k, ci, co)) \
            * (1.0 / np.sqrt(ci * k * k))

    p["stem"] = conv_w(3, 64, 7)
    p["stem_bn"] = (jnp.ones(64), jnp.zeros(64))
    cin = 64
    for bi, (cmid, n, stride) in enumerate(_BLOCKS):
        cout = cmid * 4
        for j in range(n):
            blk = {"w1": conv_w(cin, cmid, 1),
                   "bn1": (jnp.ones(cmid), jnp.zeros(cmid)),
                   "w2": conv_w(cmid, cmid, 3),
                   "bn2": (jnp.ones(cmid), jnp.zeros(cmid)),
                   "w3": conv_w(cmid, cout, 1),
                   "bn3": (jnp.ones(cout), jnp.zeros(cout))}
            if j == 0:
                blk["wd"] = conv_w(cin, cout, 1)
                blk["bnd"] = (jnp.ones(cout), jnp.zeros(cout))
            p[f"b{bi}_{j}"] = blk
            cin = cout
    p["fc"] = jax.random.normal(next(ks), (cin, 1000)) * 0.01
    return p


def _conv(x, w, s):
    dn = jax.lax.conv_dimension_numbers(
        x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (s, s),
        [(k // 2, k // 2)] * 2, dimension_numbers=dn)


def _bn_relu(x, gb, with_bn):
    if not with_bn:
        return jax.nn.relu(x)
    g, b = gb
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=(0, 1, 2))
    v = jnp.maximum(jnp.mean(jnp.square(xf), axis=(0, 1, 2))
                    - m * m, 0.0)
    out = (xf - m) * jax.lax.rsqrt(v + 1e-5) * g + b
    return jax.nn.relu(out).astype(x.dtype)


def _rn_fwd(p, x, with_bn):
    x = _conv(x, p["stem"], 2)
    x = _bn_relu(x, p["stem_bn"], with_bn)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    cin = 64
    for bi, (cmid, n, stride) in enumerate(_BLOCKS):
        for j in range(n):
            s = stride if j == 0 else 1
            blk = p[f"b{bi}_{j}"]
            r = x
            y = _bn_relu(_conv(x, blk["w1"], s), blk["bn1"], with_bn)
            y = _bn_relu(_conv(y, blk["w2"], 1), blk["bn2"], with_bn)
            y = _conv(y, blk["w3"], 1)
            if j == 0:
                r = _conv(x, blk["wd"], s)
                if with_bn:
                    r = _bn_relu(r, blk["bnd"], True)
            x = jax.nn.relu(y + r)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    return x @ p["fc"].astype(jnp.float32)


# ResNet50 fwd ~4.1 GFLOP/image at 224: train step ~3x
_RN_FLOPS_IMG = 4.1e9 * 3


def rawjax_resnet(with_bn):
    batch = 128
    p = _rn_params(jax.random.key(0))
    y = jnp.asarray(np.random.RandomState(0).randint(0, 1000, (batch,)))

    def loss(p, x):
        logits = _rn_fwd(p, x, with_bn)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - tgt)

    x = jnp.asarray(np.random.RandomState(1).rand(batch, 224, 224, 3),
                    jnp.bfloat16)

    # params MUTATE along the chain (real SGD), so iterations are never
    # bit-identical — the honest self-feeding form
    def step(p):
        g = jax.grad(loss)(p, x)
        return jax.tree_util.tree_map(lambda a, b: a - 1e-4 * b, p, g)

    dt = _chain_time(step, p, target=2.0)
    img_s = batch / dt
    from paddle_tpu.observability.resources import _peak_flops
    kind = jax.devices()[0].device_kind
    peak = _peak_flops(kind)
    if peak is None:    # a device missing from the table is an error
        raise KeyError(f"no peak FLOP/s on record for device {kind!r}")
    mfu = img_s * _RN_FLOPS_IMG / peak
    _emit(f"rawjax_resnet50_{'bn' if with_bn else 'nobn'}",
          img_s * _RN_FLOPS_IMG / 1e12,
          {"images_per_sec": round(img_s, 1), "mfu": round(mfu, 4),
           "batch": batch})


def moe_ffn_ceiling():
    """The grouped expert-FFN matmul at the MoE rung's shapes:
    [E, cap, H] x [E, H, I] einsum."""
    rs = np.random.RandomState(2)
    e, cap, h, i = 8, 2048, 1024, 1408
    x = jnp.asarray(rs.randn(e, cap, h) * 0.1, jnp.bfloat16)
    w1 = jnp.asarray(rs.randn(e, h, i) * 0.05, jnp.bfloat16)
    w2 = jnp.asarray(rs.randn(e, i, h) * 0.05, jnp.bfloat16)

    def f(x):
        u = jnp.einsum("ech,ehi->eci", x, w1)
        return _renorm(jnp.einsum("eci,eih->ech", jax.nn.silu(u), w2))
    dt = _chain_time(f, x)
    flops = 2 * e * cap * h * i * 2
    _emit("moe_expert_ffn", flops / dt / 1e12,
          {"experts": e, "capacity": cap})


def rawjax_moe_step():
    """End-to-end raw-jax MoE train-step ceiling at the bench rung's
    exact config (models/moe_llm.py IS raw jax; this probe additionally
    measures the NO-ROUTING bound — identical model with the top-2
    expert FFN applied densely — so the rung can be judged against both
    a same-program ceiling and the perfect-dispatch bound)."""
    import time

    from paddle_tpu.models import moe_llm as M

    cfg = M.MoEConfig(vocab_size=32000, hidden_size=1024,
                      moe_intermediate_size=1408, num_hidden_layers=8,
                      num_attention_heads=8, num_key_value_heads=8,
                      num_experts=8, top_k=2, dtype="bfloat16")
    batch, seq, steps = 16, 512, 10
    mesh = M.build_mesh(1, dp=1, ep=1)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq + 1)),
                      jnp.int64)

    def timed_step(step_fn):
        p = M.setup(cfg, mesh)
        loss, p = step_fn(p, ids)
        float(loss)
        for _ in range(2):
            loss, p = step_fn(p, ids)
        float(loss)
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss, p = step_fn(p, ids)
            float(loss)
            dt = (time.perf_counter() - t0) / steps
            best = dt if best is None else min(best, dt)
        return batch * seq / best

    tok_full = timed_step(M.build_train_step(cfg, mesh))

    # perfect-dispatch bound: same model, top-2-equivalent dense FFN
    from paddle_tpu.models.llama import _rope_tables as _rope
    from paddle_tpu.models.llama_hybrid import _rms, _chunked_ce_sum
    from paddle_tpu.models.llama import apply_rotary_pos_emb
    from paddle_tpu.ops.pallas.flash_attention import sdpa

    def loss_dense(p, ids):
        inp, lab = ids[:, :-1], ids[:, 1:]
        b, s = inp.shape
        x = jnp.take(p["embed"], inp, axis=0)
        cos, sin = _rope(s, cfg.head_dim, cfg.rope_theta)
        nh = kvh = cfg.num_attention_heads
        hd = cfg.head_dim
        for i in range(cfg.num_hidden_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], p["layers"])
            r = x
            h = _rms(x, lp["input_ln"], cfg.rms_norm_eps)
            wqkv = jnp.concatenate([lp["q"], lp["k"], lp["v"]], axis=1)
            qkv = h @ wqkv
            q = qkv[..., :nh * hd].reshape(b, s, nh, hd)
            k = qkv[..., nh * hd:2 * nh * hd].reshape(b, s, kvh, hd)
            v = qkv[..., 2 * nh * hd:].reshape(b, s, kvh, hd)
            q, k = apply_rotary_pos_emb(q, k, cos, sin)
            a = sdpa(q, k, v, is_causal=True)
            x = r + (a.reshape(b, s, nh * hd) @ lp["o"])
            r = x
            h = _rms(x, lp["post_ln"], cfg.rms_norm_eps)
            flat = h.reshape(b * s, cfg.hidden_size)
            y = jax.nn.silu(flat @ lp["w1"][0]) @ lp["w2"][0] \
                + jax.nn.silu(flat @ lp["w1"][1]) @ lp["w2"][1]
            x = r + y.reshape(b, s, cfg.hidden_size)
        h = _rms(x, p["norm"], cfg.rms_norm_eps)
        return _chunked_ce_sum(h, lab, p["head"]) / (b * s)

    def dense_step(p, ids):
        loss, grads = jax.value_and_grad(loss_dense)(p, ids)
        p = jax.tree_util.tree_map(
            lambda a, g: (a.astype(jnp.float32)
                          - 3e-4 * g.astype(jnp.float32)).astype(a.dtype),
            p, grads)
        return loss, p

    tok_dense = timed_step(jax.jit(dense_step, donate_argnums=(0,)))
    # throughput probe: its own key (NOT _emit's "tflops" field)
    print(json.dumps({
        "probe": "rawjax_moe_step", "ktok_per_sec":
        round(tok_full / 1e3, 1),
        "perfect_dispatch_ktok_s": round(tok_dense / 1e3, 1),
        "routing_overhead_frac": round(1 - tok_full / tok_dense, 4)}),
        flush=True)


def main():
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind,
                      "platform": dev.platform}), flush=True)
    matmul_ceilings()
    conv_ceilings()
    moe_ffn_ceiling()
    rawjax_resnet(with_bn=False)
    rawjax_resnet(with_bn=True)
    rawjax_moe_step()


if __name__ == "__main__":
    main()
