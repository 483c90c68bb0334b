"""Same-session A/B: llama flagship step with scanned vs unrolled
layer loop (remat kept identical)."""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_hybrid as H

cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                  intermediate_size=5632, num_hidden_layers=16,
                  num_attention_heads=16, num_key_value_heads=16,
                  max_position_embeddings=2048, dtype="bfloat16")
batch, seq, steps = 8, 2048, 8
mesh = H.build_mesh(1, pp=1, dp=1, tp=1)
ids = jnp.asarray(np.random.randint(0, 32000, (batch, seq + 1)),
                  jnp.int64)


def run(tag):
    params, opt = H.setup(cfg, mesh, dtype=jnp.bfloat16)
    step = H.build_train_step(cfg, mesh, n_micro=1, remat=True, sp=False)
    loss, params, opt = step(params, opt, ids)
    float(loss)
    for _ in range(2):
        loss, params, opt = step(params, opt, ids)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, params, opt = step(params, opt, ids)
    float(loss)
    dt = (time.perf_counter() - t0) / steps
    print(f"{tag}: {dt*1e3:.1f} ms  tok/s={batch*seq/dt:,.0f}",
          flush=True)


def unrolled_stage(stage_params, x, cos, sin, config, remat=True,
                   mesh=None):
    body = functools.partial(H._decoder_layer, cos=cos, sin=sin,
                             config=config, mesh=mesh)
    if remat == "attn":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out"))
    elif remat:
        body = jax.checkpoint(body)
    lps = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    h = x
    for i in range(lps):
        lp = jax.tree_util.tree_map(lambda a: a[i], stage_params)
        h = body(lp, h)
    return h


orig = H._stage_fn
H._stage_fn = unrolled_stage
run("unroll")
H._stage_fn = orig
run("scan  ")
H._stage_fn = unrolled_stage
run("unroll2")
