"""Where does the MoE rung's step time go?  Times the full step and
ablated variants on the chip (device-resident params mutating per
step, best-of-2 medians)."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import moe_llm as M
from paddle_tpu.distributed.moe import moe_dispatch_combine
from paddle_tpu.models.llama import _rope_tables, apply_rotary_pos_emb
from paddle_tpu.models.llama_hybrid import _rms, _chunked_ce_sum
from paddle_tpu.ops.pallas.flash_attention import sdpa

cfg = M.MoEConfig(vocab_size=32000, hidden_size=1024,
                  moe_intermediate_size=1408, num_hidden_layers=8,
                  num_attention_heads=8, num_key_value_heads=8,
                  num_experts=8, top_k=2, dtype="bfloat16")
batch, seq, steps = 16, 512, 10
mesh = M.build_mesh(1, dp=1, ep=1)
params = M.setup(cfg, mesh)
ids = jnp.asarray(np.random.randint(0, cfg.vocab_size,
                                    (batch, seq + 1)), jnp.int64)


def timed(fn, p0):
    p = jax.tree_util.tree_map(lambda a: a + 0, p0)   # private copy
    loss, p = fn(p, ids)
    float(loss)
    for _ in range(2):
        loss, p = fn(p, ids)
    float(loss)
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, p = fn(p, ids)
        float(loss)
        dt = (time.perf_counter() - t0) / steps
        best = dt if best is None else min(best, dt)
    return best


def make_step(loss_f):
    def step(p, ids):
        loss, grads = jax.value_and_grad(loss_f)(p, ids)
        p = jax.tree_util.tree_map(
            lambda a, g: (a.astype(jnp.float32)
                          - 3e-4 * g.astype(jnp.float32)).astype(a.dtype),
            p, grads)
        return loss, p
    return jax.jit(step, donate_argnums=(0,))


def loss_variant(mode):
    def loss_fn(p, ids):
        inp, lab = ids[:, :-1], ids[:, 1:]
        b, s = inp.shape
        x = jnp.take(p["embed"], inp, axis=0)
        cos, sin = _rope_tables(s, cfg.head_dim, cfg.rope_theta)
        nh = kvh = cfg.num_attention_heads
        hd = cfg.head_dim

        def body(carry, lp):
            h, aux = carry
            bsz, sq, hdim = h.shape
            r = h
            hh = _rms(h, lp["input_ln"], cfg.rms_norm_eps)
            if mode != "ffn_only":
                wqkv = jnp.concatenate([lp["q"], lp["k"], lp["v"]],
                                       axis=1)
                qkv = hh @ wqkv
                q = qkv[..., :nh * hd].reshape(bsz, sq, nh, hd)
                k = qkv[..., nh * hd:(nh + kvh) * hd] \
                    .reshape(bsz, sq, kvh, hd)
                v = qkv[..., (nh + kvh) * hd:].reshape(bsz, sq, kvh, hd)
                q, k = apply_rotary_pos_emb(q, k, cos, sin)
                a = sdpa(q, k, v, is_causal=True)
                h = r + (a.reshape(bsz, sq, nh * hd) @ lp["o"])
            r = h
            hh = _rms(h, lp["post_ln"], cfg.rms_norm_eps)
            flat = hh.reshape(bsz * sq, hdim)
            if mode == "attn_only":
                y = flat
                a2 = jnp.float32(0.0)
            elif mode == "dense_ffn":
                # same ACTIVE flops as top-2 of 8: two experts' worth
                w1 = lp["w1"][0]
                w2 = lp["w2"][0]
                y = jax.nn.silu(flat @ w1) @ w2
                w1b = lp["w1"][1]
                w2b = lp["w2"][1]
                y = y + jax.nn.silu(flat @ w1b) @ w2b
                a2 = jnp.float32(0.0)
            elif mode == "dense_dispatch":
                y, a2 = moe_dispatch_combine(
                    flat, lp["gate"], lp["w1"], lp["b1"], lp["w2"],
                    lp["b2"], top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor,
                    activation=jax.nn.silu, mesh=mesh, ep_axis="ep",
                    dispatch_mode="dense")
            else:
                y, a2 = moe_dispatch_combine(
                    flat, lp["gate"], lp["w1"], lp["b1"], lp["w2"],
                    lp["b2"], top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor,
                    activation=jax.nn.silu, mesh=mesh, ep_axis="ep")
            return (r + y.reshape(bsz, sq, hdim), aux + a2), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                   p["layers"])
        h = _rms(x, p["norm"], cfg.rms_norm_eps)
        ce = _chunked_ce_sum(h, lab, p["head"]) / (b * s)
        return ce + cfg.aux_loss_weight * aux / cfg.num_hidden_layers
    return loss_fn


full = timed(make_step(loss_variant("full")), params)
print(f"full sort-dispatch step: {full*1e3:.1f} ms  "
      f"tok/s={batch*seq/full:,.0f}")
for mode in ("dense_ffn", "attn_only", "ffn_only"):
    dt = timed(make_step(loss_variant(mode)), params)
    print(f"{mode:>16}: {dt*1e3:.1f} ms  tok/s={batch*seq/dt:,.0f}")
