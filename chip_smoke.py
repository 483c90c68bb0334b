"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py               # one TPU chip: a server, then a trainer
    python chip_smoke.py --four-chips  # four chips: the sharded paths only

One process, which touches JAX itself and starts no child.  Any phase that
raises, any device that is not a TPU, any check that fails ends the run
with a non-zero exit and no result line.  The last line of standard output
is one JSON object,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and every other line (phase timings, first-call compile seconds, peak device
bytes, logit gaps, the kernels in each compiled program) comes before it.

Default run, on one chip:

  serve  LlamaForCausalLM at Llama-3-8B widths, 8 layers (the only cut),
         bf16, seeded, behind ``paddle_tpu.serving.serve`` and driven over
         HTTP: a 1536-token prompt (prefill through the masked flash
         kernel), four concurrent 128-256 token prompts with one of them
         streamed (decode through the paged kernel at batch > 1), and the
         first prompt again (prefix-cache hits).  What was served is held
         against one cache-free forward of the same weights through plain
         XLA attention, teacher forced.
  train  BERT-base at its published defaults, batch 32 x seq 384,
         ``paddle.jit.train_step`` + AdamW + bf16 autocast, until the loss
         on one fixed batch falls below the first step's.

``--four-chips`` runs only what exists across chips, each beside what it is
compared with: the same server at ``mesh="tp=4"`` and at ``mesh=None``, and
the hybrid trainer at Llama-3-8B widths (2 layers) at tp=4 and at dp=2 x
tp=2 from one seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# A served token passes when its logit in the reference forward is within
# LOGIT_TOL of that position's reference maximum.  Server and reference run
# the same bf16 weights through different programs (Pallas flash / paged
# kernels with f32 accumulators against XLA attention), so they differ by
# bf16 rounding: one ulp is 2**-8 relative, and the logits here
# (unit-RMS hidden state x Xavier lm_head, sigma ~0.25, maxima ~1.1) sit
# where an ulp is 2**-8..2**-7 absolute.  A few roundings per layer over 8
# layers stay within a handful of ulps; 16 ulps at magnitude 1 is 0.0625.
# A wrong page, position or mask decorrelates the hidden state from the
# reference: the served token's reference logit then falls anywhere in the
# sigma ~0.25 bulk, ~1.1 below the maximum, more than 15 tolerances away.
# Equal tokens are NOT required: near-ties among 128k random logits flip on
# rounding, and a flipped argmax still has a gap of a few ulps.
LOGIT_TOL = 0.0625

SEED = 0     # weights, prompts and batches are all made from it

PALLAS_MODULES = ("flash_attention", "flash_mask", "paged_attention",
                  "decode_attention", "quant_matmul", "lora_matmul",
                  "grouped_ffn")


def say(**fields):
    print(json.dumps(fields), flush=True)


def require_tpu(count: int):
    """The devices, or exit: a measurement path that finds no chip fails."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU device(s); JAX reports "
                 f"{len(devices)} x {devices[0].platform} "
                 f"({devices[0].device_kind})")
    return devices


def interpret_is_off():
    """The kernels must run as kernels: interpret mode is a test switch."""
    import importlib
    for name in PALLAS_MODULES:
        mod = importlib.import_module(f"paddle_tpu.ops.pallas.{name}")
        if getattr(mod, "_INTERPRET", False):
            raise RuntimeError(f"ops.pallas.{name}._INTERPRET is on")


def device_bytes(devices) -> list:
    """[bytes_in_use, peak_bytes_in_use] per device, as the backend
    counts them (None where it does not: the CPU)."""
    stats = [d.memory_stats() or {} for d in devices]
    return [[s.get("bytes_in_use"), s.get("peak_bytes_in_use")]
            for s in stats]


def shard_bytes(tree, devices) -> list:
    """Bytes of ``tree``'s leaves on each of ``devices``, from their
    addressable shards; raises if a leaf misses one of the devices.  Code
    that has only ever seen one chip puts everything on the first."""
    import jax
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        on = {s.device for s in leaf.addressable_shards}
        if on != set(devices):
            raise RuntimeError(
                f"a {leaf.shape} leaf sits on {sorted(map(str, on))}, "
                f"not on all of {[str(d) for d in devices]}")
        for s in leaf.addressable_shards:
            held[s.device] += s.data.nbytes
    return [held[d] for d in devices]


def kernels_in(jitted, args) -> dict:
    """Pallas kernels in the program ``jitted`` traces for ``args``, by
    name and count, read from the lowered module: no compile, no run."""
    import jax

    def aval(a):
        if not isinstance(a, jax.Array):
            return a
        # only a placed array says where it lives; a fresh jnp.zeros sits
        # on the first device by default and would contradict a mesh
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None)

    text = jitted.lower(*jax.tree.map(aval, args)).as_text()
    found: dict = {}
    for name in re.findall(r'kernel_name\s*=\s*"([^"]+)"', text):
        found[name] = found.get(name, 0) + 1
    return found


def runner_kernels(runner) -> dict:
    """Kernels in every program the serving runner has built so far.  The
    argument tuples mirror ``ModelRunner.decode_step`` / ``prefill`` /
    ``prefill_cached``; tracing again bumps the runner's trace counters,
    so this runs after the server has stopped."""
    import jax.numpy as jnp
    r = runner
    pools = (r.kpool, r.vpool, r.kscale, r.vscale)
    tail = (r._cos, r._sin, r.lora, r._prefill_aidx(0))
    i32 = jnp.int32
    out = {"decode_step": kernels_in(r._step_fn, (
        r.state, *pools, r._table_dev, r._pos_dev, r._tok_dev,
        r._active_dev, r._ring_dev, r._ridx_dev, r._cos, r._sin, r.lora,
        r._aidx_dev, r._counters_dev))}
    for bucket, fn in sorted(r._prefill_fns.items()):
        out[f"prefill[{bucket}]"] = kernels_in(fn, (
            r.state, jnp.zeros((1, bucket), i32), jnp.zeros((1,), i32),
            jnp.zeros((bucket // r.page_size,), i32), *pools, *tail))
    for bucket, fn in sorted(r._prefill_cached_fns.items()):
        out[f"prefill_cached[{bucket}]"] = kernels_in(fn, (
            r.state, jnp.zeros((1, bucket), i32), jnp.zeros((1,), i32),
            jnp.zeros((), i32), jnp.zeros((r.table_width,), i32), *pools,
            *tail))
    return out


# ------------------------------------------------------------------ serve
def build_llama(cfg, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def make_prompts(vocab: int, lengths, seed: int) -> list:
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, n).tolist() for n in lengths]


def drive_server(model, prompts, new_tokens, *, mesh=None,
                 max_model_len=2048) -> dict:
    """Serve ``model`` and send the three request groups over HTTP.

    ``prompts[0]`` is the long prompt (sent first, alone, and again last);
    the rest go concurrently, the first of them streamed.  Returns the
    served tokens in request order, the server's compile ledger, the
    prefix-cache hits the repeat earned, the page census after the stop,
    and the runner (for :func:`runner_kernels`)."""
    from paddle_tpu.serving import serve
    from paddle_tpu.serving.client import ServingClient

    server = serve(model, port=0, max_slots=8, page_size=16,
                   max_model_len=max_model_len, enable_prefix_cache=True,
                   mesh=mesh)
    try:
        # the first request of each shape compiles its program
        client = ServingClient(server.address, timeout=900.0)
        long_p, short_ps = prompts[0], prompts[1:]
        n_long, n_short = new_tokens

        def hits():
            m = re.search(r'serving_prefix_cache_pages_total'
                          r'\{result="hit"\} (\S+)', client.metrics_text())
            return float(m.group(1)) if m else 0.0

        def streamed(prompt):
            toks = []
            for ev in client.completion(prompt, max_tokens=n_short,
                                        stream=True):
                toks.extend(ev["choices"][0]["token_ids"])
            return toks

        def ledger():       # process-wide: read it as a difference
            jits = client.request("GET", "/debug/resources")["compiles"]
            return {k: v["seconds"] for k, v in jits["jits"].items()}

        ledger_before = ledger()
        t0 = time.perf_counter()
        served = [client.completion_tokens(long_p, max_tokens=n_long)]
        t1 = time.perf_counter()
        with ThreadPoolExecutor(len(short_ps)) as pool:
            futs = [pool.submit(streamed, short_ps[0])] + [
                pool.submit(client.completion_tokens, p,
                            max_tokens=n_short) for p in short_ps[1:]]
            served += [f.result() for f in futs]
        t2 = time.perf_counter()
        hits_before = hits()
        served.append(client.completion_tokens(long_p, max_tokens=n_long))
        t3 = time.perf_counter()
        hit_pages = hits() - hits_before
        compile_s = {k: round(v - ledger_before.get(k, 0.0), 2)
                     for k, v in ledger().items()
                     if v > ledger_before.get(k, 0.0)}
    finally:
        server.stop()
    engine = server.worker.engine
    for toks, want in zip(served, [n_long] + [n_short] * len(short_ps)
                          + [n_long]):
        if len(toks) != want:
            raise RuntimeError(f"asked for {want} tokens, got {len(toks)}")
    if hit_pages <= 0:
        raise RuntimeError("the repeated prompt hit no prefix-cache page")
    census = engine.blocks.pool_accounting()
    if census["leak"] != 0:
        raise RuntimeError(f"page census leaks: {census}")
    return {"served": served, "hit_pages": hit_pages, "census": census,
            "compile_s": compile_s,
            "group_s": [round(t1 - t0, 2), round(t2 - t1, 2),
                        round(t3 - t2, 2)],
            "decode_traces": engine.runner.decode_traces,
            "runner": engine.runner}


def reference_gaps(model, sequences) -> dict:
    """Teacher-forced check of served tokens against the model's own
    cache-free forward through XLA attention.

    ``sequences`` is [(prompt, served tokens), ...] of one padded width
    class (right padding is invisible under the causal mask).  For every
    generated position: gap = reference max logit - reference logit of the
    served token.  Returns the worst gap, the argmax agreement and the
    control (the same gap for a wrong token); the caller holds them to
    ``LOGIT_TOL``."""
    import jax.numpy as jnp
    import paddle_tpu as paddle

    width = max(len(p) + len(t) for p, t in sequences)
    ids = np.zeros((len(sequences), width), np.int64)
    rows, cols, toks = [], [], []
    for b, (p, t) in enumerate(sequences):
        ids[b, :len(p) + len(t)] = list(p) + list(t)
        for i, tok in enumerate(t):     # logits at p+i-1 chose token i
            rows.append(b)
            cols.append(len(p) + i - 1)
            toks.append(tok)
    kernel = model.config.use_flash_attention
    model.config.use_flash_attention = False
    try:
        with paddle.no_grad():
            logits = model(paddle.to_tensor(ids))._data
    finally:
        model.config.use_flash_attention = kernel
    picked = logits[jnp.asarray(rows), jnp.asarray(cols)].astype(
        jnp.float32)                                    # [n, V]
    if not bool(jnp.all(jnp.isfinite(picked))):
        raise RuntimeError("reference logits are not finite")
    top = jnp.max(picked, axis=-1)
    at = jnp.arange(len(toks))
    gaps = np.asarray(top - picked[at, jnp.asarray(toks)])
    # the control: each position judged on a token that was NOT served
    # (half the vocabulary away; a neighbour's token will not do, greedy
    # decoding of random weights repeats itself).  A reference that cannot
    # tell these from the right ones (constant or collapsed logits) would
    # pass anything
    other = (np.asarray(toks) + logits.shape[-1] // 2) % logits.shape[-1]
    wrong = np.asarray(top - picked[at, jnp.asarray(other)])
    return {"worst_gap": float(gaps.max()), "positions": len(toks),
            "argmax_agree": int((gaps == 0.0).sum()),
            "control_gap": float(np.median(wrong)),
            "logit_std": float(jnp.std(picked))}


def check_served(model, prompts, served) -> dict:
    """Hold every served token to LOGIT_TOL; two reference forwards (the
    long prompt's two servings, then the short prompts as one batch)."""
    long_ref = reference_gaps(
        model, [(prompts[0], served[0]), (prompts[0], served[-1])])
    short_ref = reference_gaps(
        model, list(zip(prompts[1:], served[1:-1])))
    worst = max(long_ref["worst_gap"], short_ref["worst_gap"])
    if not worst <= LOGIT_TOL:
        raise RuntimeError(
            f"served tokens leave the reference: worst logit gap {worst} "
            f"> {LOGIT_TOL} (long {long_ref}, short {short_ref})")
    control = min(long_ref["control_gap"], short_ref["control_gap"])
    if not control > 4 * LOGIT_TOL:
        raise RuntimeError(
            f"the reference cannot tell a wrong token: an unserved token "
            f"sits only {control} below the maximum (long {long_ref}, "
            f"short {short_ref})")
    return {"worst_gap": worst, "tol": LOGIT_TOL, "long": long_ref,
            "short": short_ref}


def serve_phase(cfg, devices, *, seed, meshes=(None,),
                lengths=(1536, 136, 144, 248, 256), new_tokens=(16, 32),
                max_model_len=2048) -> dict:
    """One model behind one server per entry of ``meshes`` (``None`` is
    one chip, ``"tp=4"`` four), each driven over HTTP and held to the one
    eager reference.  Sizes are arguments so the tests can run the same
    code at a toy width on the CPU."""
    from paddle_tpu.serving.parallel.mesh import parse_mesh

    t0 = time.perf_counter()
    model = build_llama(cfg, seed)
    out = {"phase": "serve", "layers": cfg.num_hidden_layers,
           "hidden": cfg.hidden_size,
           "build_s": round(time.perf_counter() - t0, 2)}
    prompts = make_prompts(cfg.vocab_size, lengths, seed)
    for mesh in meshes:
        tp = parse_mesh(mesh)
        run = drive_server(model, prompts, new_tokens, mesh=mesh,
                           max_model_len=max_model_len)
        runner = run.pop("runner")
        held = shard_bytes(runner.state, devices[:tp])
        whole = sum(v.nbytes for v in runner.state.values())
        if tp > 1 and (len(set(held)) != 1 or held[0] >= whole):
            raise RuntimeError(
                f"tp={tp} weights are not sharded evenly: {held} of {whole}")
        out[f"tp{tp}"] = {
            "logits": check_served(model, prompts, run.pop("served")),
            "weight_bytes": held,
            "pool_bytes": shard_bytes((runner.kpool, runner.vpool),
                                      devices[:tp]),
            "kernels": runner_kernels(runner),
            "device_bytes": device_bytes(devices), **run}
        del runner, run
        gc.collect()            # the engine holds reference cycles
    out["seconds"] = round(time.perf_counter() - t0, 2)
    return out


# ------------------------------------------------------------------ train
def train_phase(cfg, devices, *, seed, batch=32, seq=384, max_steps=10,
                autocast=True) -> dict:
    """BERT through paddle.jit.train_step + AdamW (+ bf16 autocast), as
    bench.py builds it, on one fixed seeded batch."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.bert import BertForSequenceClassification

    t0 = time.perf_counter()
    paddle.seed(seed)
    model = BertForSequenceClassification(cfg)
    model.train()
    o = opt.AdamW(learning_rate=3e-5, parameters=model.parameters())

    def loss_fn(m, ids, y):
        with paddle.amp.auto_cast(enable=autocast, level="O1"):
            logits = m(ids)
        return F.cross_entropy(logits, y)

    step = paddle.jit.train_step(model, o, loss_fn)
    rng = np.random.RandomState(seed)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    y = paddle.to_tensor(
        rng.randint(0, cfg.num_labels, (batch,)).astype(np.int64))

    losses, step_s = [], []
    for _ in range(max_steps):
        t = time.perf_counter()
        losses.append(float(step(ids, y)))      # host fetch ends the step
        step_s.append(round(time.perf_counter() - t, 3))
        if not np.isfinite(losses[-1]):
            raise RuntimeError(f"loss is not finite: {losses}")
        if len(losses) > 1 and losses[-1] < losses[0]:
            break
    else:
        raise RuntimeError(f"loss never fell below the first: {losses}")

    params = {k: p._data for k, p in model.named_parameters()}
    for name, p in params.items():
        if p.devices() != {devices[0]}:
            raise RuntimeError(f"{name} is on {p.devices()}")
    bufs = {"buffers." + k: b._data for k, b in model.named_buffers()}
    kernels = kernels_in(step._compiled, (
        params, bufs, o.opt_state(), jax.random.key(0), ids._data,
        y._data))
    return {"phase": "train", "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size, "batch": batch, "seq": seq,
            "seconds": round(time.perf_counter() - t0, 2),
            # steps 1 and 2 compile (the optimizer state appears at 1)
            "step_s": step_s, "losses": losses, "kernels": kernels,
            "params_on": str(devices[0]),
            "device_bytes": device_bytes(devices)}


# ------------------------------------------------------------- four chips
# The two trainer layouts start from one seed and one batch, so their
# losses are one function evaluated under two shardings.  They differ by
# where bf16 partial sums are rounded (row-parallel o/down products are
# reduced over 4 shards or over 2): ~2**-8 relative on activations, which
# a mean over 8192 tokens of a loss near ln(128256) + 0.5 = 12.3 carries
# at the 1e-3 level.  A shard dropped or counted twice changes the logit
# variance and moves the loss by tenths.
LOSS_TOL = 0.02


def hybrid_phase(cfg, devices, *, seed, layouts=((1, 1, 4), (1, 2, 2)),
                 batch=4, seq=2048, steps=3, dtype="bfloat16",
                 tol=LOSS_TOL) -> dict:
    """``llama_hybrid`` for a few steps under each (pp, dp, tp) layout,
    from one seed and one batch; the first-step losses must agree."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.models import llama_hybrid as H

    t0 = time.perf_counter()
    ids_np = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int64)
    out = {"phase": "hybrid_train", "layers": cfg.num_hidden_layers,
           "hidden": cfg.hidden_size, "batch": batch, "seq": seq,
           "tol": tol}
    first = {}
    for pp, dp, tp in layouts:
        name = f"pp{pp}_dp{dp}_tp{tp}"
        mesh = H.build_mesh(len(devices), pp=pp, dp=dp, tp=tp,
                            devices=devices)
        params, opt = H.setup(cfg, mesh, seed=seed, dtype=jnp.dtype(dtype))
        placed = {"param_bytes": shard_bytes(params, devices),
                  "adam_bytes": shard_bytes((opt.m, opt.v), devices)}
        step = H.build_train_step(cfg, mesh, n_micro=1, remat=True,
                                  sp=False)
        ids = jax.device_put(ids_np, NamedSharding(mesh, P("dp", None)))
        kernels = kernels_in(step, (params, opt, ids))
        losses, step_s = [], []
        for _ in range(steps):
            t = time.perf_counter()
            loss, params, opt = step(params, opt, ids)
            losses.append(float(loss))          # host fetch ends the step
            step_s.append(round(time.perf_counter() - t, 3))
        if not np.all(np.isfinite(losses)):
            raise RuntimeError(f"{name}: loss is not finite: {losses}")
        n_params = sum(x.size for x in jax.tree.leaves(params))
        out[name] = {"params": int(n_params), "losses": losses,
                     "step_s": step_s, "kernels": kernels, **placed,
                     "param_bytes_after": shard_bytes(params, devices),
                     "device_bytes": device_bytes(devices)}
        first[name] = losses[0]
        del params, opt, step, ids
        gc.collect()
    spread = max(first.values()) - min(first.values())
    out["first_loss_spread"] = spread
    if not spread <= tol:
        raise RuntimeError(
            f"layouts disagree on the first-step loss: {first} "
            f"(spread {spread} > {tol})")
    out["seconds"] = round(time.perf_counter() - t0, 2)
    return out


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips")
    args = ap.parse_args(argv)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = require_tpu(4 if args.four_chips else 1)
    interpret_is_off()
    import jax
    say(jax=jax.__version__, compile_cache=cache_dir,
        devices=[str(d) for d in devices])

    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.models.llama import llama3_8b

    cfg = llama3_8b()
    cfg.num_hidden_layers = 8           # depth is the only cut
    if args.four_chips:
        # the trainer goes first: it frees what it placed, while a model
        # that has run an eager forward stays on its device (the eager
        # segment cache keeps the layer alive), and 5.6 GB left on chip 0
        # would not leave room for the dp=2 x tp=2 step's 10.3 GB
        trainer_cfg = llama3_8b()
        trainer_cfg.num_hidden_layers = 2
        say(**hybrid_phase(trainer_cfg, devices[:4], seed=SEED))
        gc.collect()
        say(**serve_phase(cfg, devices[:4], seed=SEED,
                          meshes=("tp=4", None)))
    else:
        say(**serve_phase(cfg, devices, seed=SEED))
        say(**train_phase(BertConfig(), devices, seed=SEED))

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
