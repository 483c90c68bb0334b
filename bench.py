"""Benchmark ladder (BASELINE.json's five rungs) on the available chip(s).

Prints ONE JSON line per metric, flagship Llama first:
  llama_train_tokens_per_sec_per_chip   (ladder #4-lite, MFU vs 40% target)
  resnet50_train_images_per_sec_per_chip (ladder #2, conv/BN/AMP)
  bert_base_train_examples_per_sec_per_chip (ladder #3, encoder/AdamW)
  moe_train_tokens_per_sec_per_chip     (ladder #5, gating+dispatch)
  lenet_eager_steps_per_sec             (ladder #1, dygraph dispatch vs jit)

vs_baseline: the reference publishes no absolute numbers;
where MFU is defined the north star is >=40% MFU so vs_baseline =
measured_MFU / 0.40; for LeNet it is the eager/jit throughput ratio
(dygraph dispatch efficiency).
"""
from __future__ import annotations

import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# bf16 peak TFLOP/s per chip by device kind (public figures)
PEAK_TFLOPS = {
    "TPU v5p": 459.0, "TPU v5 lite": 197.0, "TPU v5e": 197.0,
    "TPU v6 lite": 918.0, "TPU v6e": 918.0, "TPU v4": 275.0,
    "TPU v3": 123.0, "TPU v2": 45.0,
}


def _peak_flops(kind: str) -> float:
    for k, v in PEAK_TFLOPS.items():
        if kind.lower().startswith(k.lower()):
            return v * 1e12
    raise KeyError(f"no peak FLOP/s on record for device kind {kind!r}: "
                   "add it to PEAK_TFLOPS with its source, do not guess")


def _env():
    """The chip this run measures on.  A measurement path that finds no
    chip fails: a number taken on the CPU says how fast XLA's CPU backend
    is, and must never be printed under a device metric's name."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures on a TPU; JAX reports "
                         f"{dev.platform} ({dev.device_kind})")
    return dev, True, len(jax.devices())


_SUMMARY: list = []


def _emit(metric, value, unit, vs_baseline, detail):
    print(json.dumps({
        "metric": metric, "value": round(float(value), 2), "unit": unit,
        "vs_baseline": round(float(vs_baseline), 4), "detail": detail,
    }), flush=True)
    _SUMMARY.append((metric, round(float(value), 2), unit,
                     round(float(vs_baseline), 4)))


def _llama_throughput(cfg, mesh, batch, seq, steps, dtype, on_tpu, dev,
                      dp_shard=False, n_chips=1):
    """Shared llama-rung core: setup -> compile -> warmup -> timed steps.
    Returns (tokens/s, mfu, loss).  Timing notes: each window ends in a
    host fetch of the loss scalar (which waits for the step that made
    it); warmup absorbs the slow first post-compile steps."""
    from paddle_tpu.models import llama_hybrid as H

    params, opt = H.setup(cfg, mesh, dtype=dtype)
    step = H.build_train_step(cfg, mesh, n_micro=1, remat=on_tpu, sp=False)
    ids_np = np.random.randint(0, cfg.vocab_size,
                               (batch, seq + 1)).astype(np.int64)
    if dp_shard:
        ids = jax.device_put(ids_np, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("dp", None)))
    else:
        ids = jnp.asarray(ids_np)
    loss, params, opt = step(params, opt, ids)
    float(loss)
    for _ in range(3):
        loss, params, opt = step(params, opt, ids)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, params, opt = step(params, opt, ids)
    loss_val = float(loss)
    dt = time.perf_counter() - t0

    tps = batch * seq * steps / dt
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    attn_flops = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    # tps is TOTAL tokens/s across the mesh; peak scales with chip count
    mfu = tps * (6 * n_params + attn_flops) / (
        n_chips * _peak_flops(dev.device_kind if on_tpu else "cpu"))
    return tps, (mfu if on_tpu else 0.0), loss_val, n_params


def bench_llama():
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models import llama_hybrid as H

    dev, on_tpu, n = _env()
    if on_tpu:
        # ~1B params saturates the MXU on one v5e chip (~16G HBM) with
        # bf16 params + fp32 AdamW state + flash attention + chunked CE
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16")
        batch, seq, steps = 8, 2048, 10
        dtype = jnp.bfloat16
    else:  # CPU smoke mode so the bench is runnable anywhere
        cfg = LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=512)
        batch, seq, steps = 4, 256, 3
        dtype = jnp.float32

    pp, dp, tp = (1, n, 1) if n > 1 else (1, 1, 1)
    mesh = H.build_mesh(n, pp=pp, dp=dp, tp=tp)
    tps, mfu, loss_val, n_params = _llama_throughput(
        cfg, mesh, batch, seq, steps, dtype, on_tpu, dev, dp_shard=n > 1,
        n_chips=n)
    _emit("llama_train_tokens_per_sec_per_chip", tps / n,
          "tokens/s/chip", mfu / 0.40 if on_tpu else 0.0,
          {"mfu": round(mfu, 4), "chips": n, "device": dev.device_kind,
           "params": int(n_params), "loss": loss_val})


def bench_resnet50():
    """Ladder #2: ResNet50 + AMP O1 (conv/BN/momentum on the MXU)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.vision.models import resnet50

    dev, on_tpu, _ = _env()
    n = 1  # runs on one device; per-chip numbers divide by what is used
    # batch 128 (measured r4 with the multi_step harness: 2570 img/s vs
    # 2377 at b512 — the earlier "b512 wins" came from a per-dispatch
    # harness whose launch overhead shrank with batch)
    batch, steps = (128, 2) if on_tpu else (4, 1)
    hw = 224 if on_tpu else 32

    model = resnet50(num_classes=1000)
    model.train()
    o = opt.Momentum(learning_rate=0.1, momentum=0.9,
                     parameters=model.parameters())

    def loss_fn(m, x, y):
        with paddle.amp.auto_cast(enable=on_tpu, level="O1"):
            out = m(x)
        return F.cross_entropy(out, y)

    # one dispatch per `chunk` steps, so per-dispatch host latency does
    # not masquerade as step time
    chunk = 25 if on_tpu else 2
    step = paddle.jit.train_step(model, o, loss_fn).multi_step(chunk)
    x = paddle.to_tensor(
        np.random.randn(batch, 3, hw, hw).astype(np.float32))
    y = paddle.to_tensor(
        np.random.randint(0, 1000, (batch,)).astype(np.int64))
    float(step(x, y))                      # compile (chunk steps)
    float(step(x, y))
    best_dt = float("inf")
    for _ in range(2):    # reports the better of two timed windows
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(x, y)
        loss_val = float(loss)
        best_dt = min(best_dt, time.perf_counter() - t0)

    imgs_per_sec = batch * steps * chunk / best_dt
    # ResNet50 fwd ~4.1 GFLOPs/image at 224^2; train ~3x fwd
    flops_per_img = 3 * 4.1e9 * (hw / 224) ** 2
    mfu = imgs_per_sec * flops_per_img / (n * _peak_flops(dev.device_kind))
    if not on_tpu:
        mfu = 0.0
    _emit("resnet50_train_images_per_sec_per_chip", imgs_per_sec / n,
          "images/s/chip", mfu / 0.40 if on_tpu else 0.0,
          {"mfu": round(mfu, 4), "batch": batch, "amp": "O1" if on_tpu
           else "off", "device": dev.device_kind, "loss": loss_val})


def bench_bert():
    """Ladder #3: BERT-base fine-tune shape (encoder + AdamW)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.bert import BertConfig, \
        BertForSequenceClassification

    dev, on_tpu, _ = _env()
    n = 1  # single-device bench
    if on_tpu:
        cfg = BertConfig()                         # base: 12L/768H
        batch, seq, steps = 32, 384, 3
    else:
        cfg = BertConfig(vocab_size=512, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=256)
        batch, seq, steps = 2, 64, 1

    model = BertForSequenceClassification(cfg)
    model.train()
    o = opt.AdamW(learning_rate=3e-5, parameters=model.parameters())

    def loss_fn(m, ids, y):
        with paddle.amp.auto_cast(enable=on_tpu, level="O1"):
            logits = m(ids)
        return F.cross_entropy(logits, y)

    chunk = 10 if on_tpu else 2
    step = paddle.jit.train_step(model, o, loss_fn).multi_step(chunk)
    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    y = paddle.to_tensor(
        np.random.randint(0, cfg.num_labels, (batch,)).astype(np.int64))
    float(step(ids, y))
    float(step(ids, y))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, y)
    loss_val = float(loss)
    dt = time.perf_counter() - t0

    ex_per_sec = batch * steps * chunk / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_ex = 6 * n_params * seq \
        + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq * seq
    mfu = ex_per_sec * flops_per_ex / (n * _peak_flops(dev.device_kind))
    if not on_tpu:
        mfu = 0.0
    _emit("bert_base_train_examples_per_sec_per_chip", ex_per_sec / n,
          "examples/s/chip", mfu / 0.40 if on_tpu else 0.0,
          {"mfu": round(mfu, 4), "seq": seq, "batch": batch,
           "params": int(n_params), "device": dev.device_kind,
           "loss": loss_val})


def bench_longctx():
    """Long-context rung: the SAME 0.95B llama trained at seq 8192 on one
    chip — runs on the grid-streamed flash kernels (VMEM-independent of
    sequence length), the single-chip face of the long-context story
    (ring/Ulysses attention covers the multi-chip face)."""
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models import llama_hybrid as H

    dev, on_tpu, n = _env()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=8192,
            dtype="bfloat16")
        batch, seq, steps = 1, 8192, 8
        dtype = jnp.bfloat16
    else:
        cfg = LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=1024)
        batch, seq, steps = 1, 512, 2
        dtype = jnp.float32

    mesh = H.build_mesh(1, pp=1, dp=1, tp=1)
    tps, mfu, loss_val, _np_ = _llama_throughput(
        cfg, mesh, batch, seq, steps, dtype, on_tpu, dev)
    _emit("llama_longctx8k_tokens_per_sec_per_chip", tps,
          "tokens/s/chip", mfu / 0.40 if on_tpu else 0.0,
          {"mfu": round(mfu, 4), "seq": seq, "batch": batch,
           "device": dev.device_kind, "loss": loss_val,
           "note": "seq-8192 single-chip training on the streamed "
                   "flash kernels"})
    if on_tpu:
        bench_longctx_masked()


def bench_longctx_masked():
    """Masked long-seq attention (VERDICT r3 #2 gate): fwd+bwd of the
    STREAMED segment-masked kernel at seq 8192 vs the unmasked streamed
    kernel — packed-document pretraining must not lose the Pallas path.
    vs_baseline = masked/unmasked effective-MFU ratio (gate: >= 0.9)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from op_bench import device_time
    from paddle_tpu.ops.pallas import flash_attention as FA
    from paddle_tpu.ops.pallas import flash_mask as FM

    dev, on_tpu, _ = _env()
    B, S, H, D = 1, 8192, 16, 128
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.bfloat16)
    seg = np.zeros((B, S), np.int32)      # three packed documents
    seg[:, S // 3:2 * S // 3] = 1
    seg[:, 2 * S // 3:] = 2
    vecs = FM.segment_intervals(jnp.asarray(seg), causal=True)

    def grad_plain(q):
        return jax.grad(lambda q: jnp.sum(FA.sdpa(
            q, k, v, is_causal=True).astype(jnp.float32) ** 2))(q)

    def grad_masked(q):
        return jax.grad(lambda q: jnp.sum(FA.sdpa(
            q, k, v, flashmask=vecs, is_causal=True)
            .astype(jnp.float32) ** 2))(q)

    t_plain = device_time(grad_plain, q, reps=3)
    t_masked = device_time(grad_masked, q, reps=3)
    ratio = t_plain / max(t_masked, 1e-9)
    _emit("longctx8k_masked_attn_relative_mfu", ratio, "ratio",
          ratio / 0.9,
          {"unmasked_ms": round(t_plain * 1e3, 2),
           "masked_ms": round(t_masked * 1e3, 2),
           "seq": S, "device": dev.device_kind,
           "note": "streamed segment-masked flash fwd+bwd vs unmasked "
                   "streamed at seq 8192 (>= 0.9 required; masked may "
                   "exceed 1.0 — the mask skips work)"})


def bench_moe():
    """Ladder #5: MoE LM (gating + dense-dispatch einsums) on this chip."""
    from paddle_tpu.models import moe_llm as M

    dev, on_tpu, _ = _env()
    n = 1  # single-device bench (mesh is built with 1 device below)
    if on_tpu:
        # sort-based dispatch (no [tokens, E, capacity] one-hot) freed
        # the HBM that used to cap this rung at 4x512.  head_dim 128
        # (8 heads), matching DeepSeekMoE/Qwen2-MoE: D=64 halves the
        # MXU contraction in the flash kernel (measured r4: the D=64
        # attention cost 2.2x the D=128 one at identical flops)
        cfg = M.MoEConfig(vocab_size=32000, hidden_size=1024,
                          moe_intermediate_size=1408, num_hidden_layers=8,
                          num_attention_heads=8, num_key_value_heads=8,
                          num_experts=8, top_k=2, dtype="bfloat16")
        batch, seq, steps = 16, 512, 10
    else:
        cfg = M.moe_tiny()
        batch, seq, steps = 2, 64, 2

    mesh = M.build_mesh(1, dp=1, ep=1)
    params = M.setup(cfg, mesh)
    step = M.build_train_step(cfg, mesh)
    ids = jnp.asarray(
        np.random.randint(0, cfg.vocab_size, (batch, seq + 1)), jnp.int64)
    loss, params = step(params, ids)
    float(loss)
    for _ in range(2):
        loss, params = step(params, ids)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, params = step(params, ids)
    loss_val = float(loss)
    dt = time.perf_counter() - t0

    tok_per_sec = batch * seq * steps / dt
    # active params per token: top_k of num_experts expert FFNs
    leaves = jax.tree_util.tree_leaves(params)
    total = sum(x.size for x in leaves)
    expert = sum(x.size for x in leaves if x.ndim >= 3 and
                 x.shape[-3:-2] == (cfg.num_experts,))
    active = total - expert + expert * cfg.top_k // cfg.num_experts
    mfu = tok_per_sec * 6 * active / (n * _peak_flops(dev.device_kind))
    if not on_tpu:
        mfu = 0.0
    _emit("moe_train_tokens_per_sec_per_chip", tok_per_sec / n,
          "tokens/s/chip", mfu / 0.40 if on_tpu else 0.0,
          {"mfu_active": round(mfu, 4), "params_total": int(total),
           "params_active_per_tok": int(active),
           "experts": cfg.num_experts, "top_k": cfg.top_k,
           "device": dev.device_kind, "loss": loss_val})


def _decode_model():
    """Shared decode/paged rung model (built fresh per rung so one
    rung's failure cannot poison the other's state)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    dev, on_tpu, _ = _env()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=4096,
            dtype="bfloat16")
        batch = 8
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=512)
        batch = 2

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model, cfg, batch, dev, on_tpu


def bench_decode():
    """Serving-path rung: KV-cache decode tokens/s (VERDICT r1 item 9;
    reference block_multi_head_attention_kernel.cu).  Emits the dense
    bf16 number plus the weight_quant="int8" number — the rung VERDICT
    r3 #1 gates on (quant decode must BEAT dense, not just match)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import generation as G

    model, cfg, batch, dev, on_tpu = _decode_model()
    prompt, new = (128, 128) if on_tpu else (8, 8)
    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (batch, prompt)).astype(
            np.int64))

    def run(**kw):
        G._FN_CACHE.clear()
        out = G.generate(model, ids, max_new_tokens=new, **kw)
        float(np.asarray(out._data[0, -1]))       # compile + fetch
        best = 0.0
        for _ in range(2):   # reports the better of two timed windows
            t0 = time.perf_counter()
            out = G.generate(model, ids, max_new_tokens=new, **kw)
            float(np.asarray(out._data[0, -1]))
            best = max(best, batch * new / (time.perf_counter() - t0))
        return best

    tps_dense = run()
    tps_int8 = run(weight_quant="int8")
    _emit("llama_decode_tokens_per_sec_per_chip", tps_dense,
          "tokens/s/chip", tps_int8 / max(tps_dense, 1e-9),
          {"int8_weight_quant_tokens_per_sec": round(tps_int8, 2),
           "batch": batch, "new_tokens": new, "device": dev.device_kind,
           "note": "vs_baseline = int8-weight-quant/dense decode ratio "
                   "(>1: the weight-only kernel wins)"})


def bench_paged():
    """Ragged serving: paged (block-table) cache vs dense cache — the
    scenario the reference's block_multi_head_attention exists for: one
    long context + short requests; dense pays batch*max_len everywhere,
    paged pays each sequence's own pages.  Split from bench_decode so a
    transport flake in one cannot take out the other (VERDICT r3 weak #1)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import generation as G

    if not _env()[1]:
        return  # the ragged-batch scenario only means anything on the chip
    model, cfg, batch, dev, on_tpu = _decode_model()
    prompt_r, new_r = 2048, 64
    lens = np.array([2048, 160, 96, 224, 128, 192, 96, 160],
                    np.int64)[:batch]
    ids_r = paddle.to_tensor(np.random.randint(
        0, cfg.vocab_size, (batch, prompt_r)).astype(np.int64))
    lens_t = paddle.to_tensor(lens)

    def run_ragged(**kw):
        G._FN_CACHE.clear()
        out = G.generate(model, ids_r, max_new_tokens=new_r,
                         lengths=lens_t, **kw)
        float(np.asarray(out._data[0, -1]))
        t0 = time.perf_counter()
        out = G.generate(model, ids_r, max_new_tokens=new_r,
                         lengths=lens_t, **kw)
        float(np.asarray(out._data[0, -1]))
        return batch * new_r / (time.perf_counter() - t0)

    tps_dense = run_ragged()
    tps_paged = run_ragged(cache="paged", page_size=128)
    _emit("llama_paged_ragged_tokens_per_sec_per_chip", tps_paged,
          "tokens/s/chip", tps_paged / max(tps_dense, 1e-9),
          {"dense_tokens_per_sec": round(tps_dense, 2),
           "batch": batch, "prompt": prompt_r, "new_tokens": new_r,
           "lengths": lens.tolist(), "device": dev.device_kind,
           "note": "vs_baseline = paged/dense on the ragged batch "
                   "(>1: block-table cache wins)"})


def bench_lenet():
    """Ladder #1: LeNet dygraph (eager tape) vs one-program jit steps/s —
    the per-op dispatch overhead number (reference hot-path goal,
    paddle/phi/README.md §1.2)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.vision.models import LeNet

    dev, on_tpu, _ = _env()
    batch = 64
    steps = 30 if on_tpu else 10
    x_np = np.random.randn(batch, 1, 28, 28).astype(np.float32)
    y_np = np.random.randint(0, 10, (batch,)).astype(np.int64)

    def make():
        paddle.seed(0)
        m = LeNet()
        m.train()
        return m, opt.SGD(learning_rate=0.01, parameters=m.parameters())

    # eager (dygraph) loop
    model, o = make()
    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
    for _ in range(3):
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        o.step()
        o.clear_grad()
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        o.step()
        o.clear_grad()
    float(loss)
    eager_sps = steps / (time.perf_counter() - t0)

    # compiled
    model, o = make()
    step = paddle.jit.train_step(
        model, o, lambda m, a, b: F.cross_entropy(m(a), b))
    float(step(x, y))
    for _ in range(3):
        loss = step(x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    float(loss)
    jit_sps = steps / (time.perf_counter() - t0)

    _emit("lenet_eager_steps_per_sec", eager_sps, "steps/s",
          eager_sps / jit_sps,
          {"jit_steps_per_sec": round(jit_sps, 2), "batch": batch,
           "device": dev.device_kind,
           "note": "vs_baseline = eager/jit ratio (dispatch overhead)"})


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    # the eager-dispatch rung goes FIRST: it measures per-op
    # Python+dispatch latency, which degrades (measured 29 -> 16
    # steps/s) once the other rungs' compiled executables and buffers
    # live in the process.  One process: the chip belongs to it.
    # A rung that raises ends the run with its traceback and a non-zero
    # exit: no retry, no "_error" metric beside real ones.
    for fn in (bench_lenet, bench_llama, bench_resnet50, bench_bert,
               bench_moe, bench_decode, bench_paged, bench_longctx):
        fn()
        gc.collect()

    # compact end-of-run recap: one short line per rung, so that every
    # rung survives a capture that keeps only the tail of the output
    print(json.dumps({"summary": [
        f"{m}={v}{u} (x{vs})" for m, v, u, vs in _SUMMARY]}), flush=True)


if __name__ == "__main__":
    main()
