"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py               # one TPU chip: a server, then a trainer
    python chip_smoke.py --four-chips  # four chips: the sharded paths only
    python chip_smoke.py --ssm-update [G]  # one chip: the state-update kernel
    python chip_smoke.py --grouped-matmul 16,2688,1856   # the experts' kernel
    python chip_smoke.py --mla-decode  # one chip: the latent decode kernel
    python chip_smoke.py --paged-decode [CELL]  # the K/V paged decode kernel

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

``--ssm-update [G]`` runs ``ops/pallas/ssm_update.py``'s kernel at the
serving cells' shape (64 slots of 128 x 4096, bfloat16 and float32; B and
C in ``G`` groups, 1 where none is given) against its XLA form with slots
parked at the front, in the middle, at the end, all and none, at each block
width swept (a block inside one group, and several groups sliced out of one
block): the live slots' new states and outputs, every parked slot's and
every other layer's state bit for bit; then times it with every slot live
and with half of them parked (a parked slot moves no bytes: half the time).

``--grouped-matmul E,K,N`` runs ``ops/pallas/grouped_ffn.py``'s
``grouped_matmul`` over ``E`` experts' ``[K, N]`` matrices against its
dense-gather XLA form at the decode step's and a prefill's row tiles, with
an expert that no row chose and tiles past the live ones, and times the
decode call against the bytes of the experts it reads.

``--mla-decode`` runs ``ops/pallas/mla_paged_attention.py``'s kernel at
the GigaChat cell's shape (64 slots, 64 heads over latents of 512 + 64,
pages of 16 rows, 256 table columns, 5 layers in one pool) against its
dense-gather XLA form at contexts of 1, 628, 1,170 and 4,032 tokens and
at a mix with an empty slot, the largest gap printed; then times it, call
after call inside one program, at each block size swept.

``--paged-decode [CELL]`` runs ``ops/pallas/paged_attention.py``'s kernel
at the three paged cells' shapes (``PAGED_CELLS``; one of them where it is
named) against ``paged_attention_xla`` at each context and at a mix with
an empty slot, at each block (tokens a grid step) and round (rows a round
of the softmax) swept, the largest gap printed; then times it, call after
call inside one program.

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
                  "grouped_ffn", "ssm_update", "mla_paged_attention")


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
    name and count, read from the lowered module: no compile, no run.
    (Bodies, not calls: the paged decode kernel, whose wrapper is one
    ``jit`` with the layer as an operand, counts once a program.)"""
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
    # a recurrent family's per-slot state and the prefill's slot; empty
    # tuples for the others
    slot = jnp.zeros((), jnp.int32) if r.recurrent else ()
    i32 = jnp.int32
    out = {"decode_step": kernels_in(r._step_fn, (
        r.state, *pools, r._table_dev, r._pos_dev, r._tok_dev,
        r._active_dev, r._ring_dev, r._ridx_dev, r._cos, r._sin, r.lora,
        r._aidx_dev, r._counters_dev, r._rstate))}
    for bucket, fn in sorted(r._prefill_fns.items()):
        out[f"prefill[{bucket}]"] = kernels_in(fn, (
            r.state, jnp.zeros((1, bucket), i32), jnp.zeros((1,), i32),
            jnp.zeros((bucket // r.page_size,), i32), *pools, *tail,
            r._rstate, slot))
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


# ------------------------------------------------------- the state update
PARKED = {"none": lambda s: [], "first": lambda s: [0],
          "middle": lambda s: [s // 2], "last": lambda s: [s - 1],
          "front-half": lambda s: list(range(s // 2)),
          "every-other": lambda s: list(range(0, s, 2)),
          "all-but-last": lambda s: list(range(s - 1)),
          "all": lambda s: list(range(s))}


def ssm_update_phase(*, seed, slots=64, n=128, hp=4096, layers=3, groups=1,
                     dtypes=("bfloat16", "float32"),
                     lane_blocks=(512, 2048, 4096), reps=20) -> dict:
    """``ssm_state_update`` (B and C in ``groups`` groups) against
    ``ssm_state_update_xla`` on layer 1 of ``layers`` with slots parked
    as ``PARKED`` names them, at every block width of ``lane_blocks``,
    then its time a layer with every slot live and with the front half
    parked."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import ssm_update as U
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    rows = {k: jnp.asarray(v, jnp.float32) for k, v in (
        ("decay", rng.uniform(0.5, 1.0, (slots, hp))),
        ("dtx", rng.normal(size=(slots, hp))),
        ("b", rng.normal(size=(slots, groups, n))),
        ("c", rng.normal(size=(slots, groups, n))))}
    lane_blocks = [x for x in lane_blocks if hp % x == 0]
    out = {"phase": "ssm_update", "slots": slots, "state": [n, hp],
           "groups": groups, "checks": {}, "ms_a_layer": {}}

    def active_of(parked):
        act = np.ones((slots,), np.int32)
        act[parked] = 0
        return jnp.asarray(act)

    def kernel_at(lanes):
        """The kernel traced at ``lanes`` to a block (a jit of its own:
        the width is read when it is traced)."""
        def at(pool, *args):
            was, U.LANE_BLOCK = U.LANE_BLOCK, lanes
            try:
                return U.ssm_state_update(pool, 1, *args)
            finally:
                U.LANE_BLOCK = was
        return jax.jit(at)

    for dtype in dtypes:
        pool = jnp.asarray(rng.normal(size=(layers, slots, n, hp)),
                           jnp.float32).astype(dtype)
        was = np.asarray(pool.astype(jnp.float32))
        # one rounding of the pool's dtype: the two forms may fuse the
        # multiply and the add differently
        ulp = 2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -22
        twin = jax.jit(U.ssm_state_update_xla, static_argnums=1)
        kernels = {lanes: kernel_at(lanes) for lanes in lane_blocks}
        for name, pick in PARKED.items():
            act = active_of(pick(slots))
            live = np.asarray(act, bool)
            args = (rows["decay"], rows["dtx"], rows["b"], rows["c"], act)
            want, want_y = twin(pool, 1, *args)
            want = np.asarray(want.astype(jnp.float32))
            for lanes, kernel in kernels.items():
                got, got_y = kernel(pool, *args)
                got = np.asarray(got.astype(jnp.float32))
                state_gap = float(np.max(
                    np.abs(got[1][live] - want[1][live])
                    / np.maximum(np.abs(want[1][live]), 1.0), initial=0.0))
                y_gap = float(np.max(np.abs(np.asarray(got_y)
                                            - np.asarray(want_y))
                                     / (1.0 + np.abs(np.asarray(want_y)))))
                found = {
                    "live": int(live.sum()), "state_gap": state_gap,
                    "y_gap": y_gap,
                    "parked_untouched": bool(
                        np.array_equal(got[1][~live], was[1][~live])),
                    "other_layers_untouched": bool(
                        np.array_equal(got[0], was[0])
                        and np.array_equal(got[2:], was[2:])),
                    "parked_y_zero": not np.asarray(got_y)[~live].any()}
                out["checks"][f"{dtype}.lanes{lanes}.{name}"] = found
                if not (state_gap <= ulp and y_gap <= 1e-4
                        and found["parked_untouched"]
                        and found["other_layers_untouched"]
                        and found["parked_y_zero"]):
                    raise RuntimeError(
                        f"ssm_state_update differs from its XLA form with "
                        f"{name} parked ({dtype}, {groups} groups, {lanes} "
                        f"lanes a block): {found}")
        del was
        for lanes in lane_blocks:
            for name in ("none", "front-half"):
                act = active_of(PARKED[name](slots))
                out["ms_a_layer"][f"{dtype}.lanes{lanes}.{name}"] = (
                    _time_update(U, lanes, pool, rows, act, reps))
        del pool
    out["seconds"] = round(time.perf_counter() - t0, 2)
    return out


def _time_update(U, lanes, pool, rows, act, reps):
    """Milliseconds a layer of ``ssm_state_update`` over every layer of
    a donated pool at ``lanes`` to a block, or the compiler's refusal."""
    import jax
    layers = pool.shape[0]
    was, U.LANE_BLOCK = U.LANE_BLOCK, lanes

    def every_layer(pool, decay, dtx, b, c, act):
        for layer in range(layers):
            pool, y = U.ssm_state_update(pool, layer, decay, dtx, b, c,
                                         act)
        return pool, y

    try:
        fn = jax.jit(every_layer, donate_argnums=0)
        args = (rows["decay"], rows["dtx"], rows["b"], rows["c"], act)
        try:
            held, y = fn(pool + 0, *args)       # a copy to donate
        except Exception as e:       # e.g. more VMEM than a kernel may use
            return f"refused: {type(e).__name__}: {str(e)[:200]}"
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(reps):
            held, y = fn(held, *args)
        jax.block_until_ready((held, y))
        return round((time.perf_counter() - t0) * 1e3 / (reps * layers), 4)
    finally:
        U.LANE_BLOCK = was


# ------------------------------------------------- the experts' kernel
def grouped_matmul_phase(*, seed, experts=16, k=2688, n=1856,
                         tiles=((16, 384), (128, 1536)), reps=50,
                         dtype="bfloat16") -> dict:
    """``grouped_matmul`` over ``experts`` matrices ``[k, n]`` against
    ``grouped_matmul_xla``: for each (row tile, rows of pairs) of
    ``tiles`` a sorted buffer as the expert layer builds it (every
    expert's rows padded to the tile, the last expert chosen by no row,
    dead tiles behind the live ones), the widest gap over the live
    rows; then the first tile's call timed, and the rate at which it
    read the weights of the experts it touched."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import grouped_ffn as GF
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(experts, k, n)) / np.sqrt(k),
                    jnp.float32).astype(dtype)
    out = {"phase": "grouped_matmul", "shape": [experts, k, n],
           "blocks": list(GF.expert_blocks(k, n, w.dtype.itemsize)),
           "checks": {}}
    for tile, pairs in tiles:
        rows = -(-pairs // tile) * tile + experts * tile
        # rows an expert: uneven, none for the last
        share = rng.multinomial(pairs, [1.0 / (experts - 1)] * (experts - 1))
        emap = np.concatenate([np.full(-(-c // tile), e) for e, c
                               in enumerate(share)]).astype(np.int32)
        live = len(emap)
        emap = np.pad(emap, (0, rows // tile - live), constant_values=0)
        x = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32).astype(dtype)
        args = (x, w, jnp.asarray(emap), jnp.int32(live))
        kernel = jax.jit(lambda *a, t=tile: GF.grouped_matmul(*a, tile_m=t))
        twin = jax.jit(lambda *a, t=tile: GF.grouped_matmul_xla(
            *a, tile_m=t))
        got = np.asarray(kernel(*args).astype(jnp.float32))[:live * tile]
        want = np.asarray(twin(*args).astype(jnp.float32))[:live * tile]
        gap = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
        found = {"rows": rows, "live_tiles": live,
                 "experts_touched": int((share > 0).sum()), "gap": gap}
        # both accumulate in float32 and round once to the served dtype
        if not gap <= 2.0 ** -7:
            raise RuntimeError(f"grouped_matmul differs from its XLA form "
                               f"at tile {tile}: {found}")
        if "ms_a_call" not in out:
            jax.block_until_ready(kernel(*args))
            t1 = time.perf_counter()
            for _ in range(reps):
                y = kernel(*args)
            jax.block_until_ready(y)
            ms = (time.perf_counter() - t1) * 1e3 / reps
            touched = found["experts_touched"] * k * n * w.dtype.itemsize
            out["ms_a_call"] = round(ms, 4)
            out["weights_gb_per_s"] = round(touched / ms / 1e6, 1)
        out["checks"][f"tile{tile}"] = found
    out["seconds"] = round(time.perf_counter() - t0, 2)
    return out


# ------------------------------------------------- the decode kernels' cases
def decode_cases(rng, *, slots, width, page, contexts) -> tuple:
    """What both paged decode kernels' checks run over: every slot at
    each of ``contexts`` (cut to the table) and at a mix (one slot
    empty, the rest anywhere up to the table's width), pages scattered
    over a pool of ``slots * width`` and the table padded by the dump
    page after them.  ({name: (lens, table, lens on the device)}, the
    table's tokens.)"""
    import jax.numpy as jnp
    dump = slots * width
    owned = rng.permutation(dump).reshape(slots, width).astype(np.int32)
    most = width * page
    mixed = rng.integers(1, most + 1, slots)
    mixed[slots // 2] = 0
    lens_of = {str(c): np.full((slots,), min(c, most)) for c in contexts}
    lens_of["mixed"] = mixed
    cases = {}
    for name, lens in lens_of.items():
        used = np.arange(width)[None, :] < -(-lens[:, None] // page)
        cases[name] = (lens, jnp.asarray(np.where(used, owned, dump),
                                         jnp.int32),
                       jnp.asarray(lens, jnp.int32))
    return cases, most


# ------------------------------------------------- the latent decode kernel
# Both forms round the softmax's weights to bfloat16 before p.c, the
# kernel against a running maximum and a chunk at a time, so they differ
# by roundings of 2**-9 of a weight that mostly cancel: outputs are
# means of unit normals, and a wrong page, length or mask moves them by
# tenths.
MLA_TOL = 2.0 ** -6


def mla_decode_phase(*, seed, slots=64, heads=64, rank=512, rope=64,
                     page=16, width=256, layers=5,
                     contexts=(1, 628, 1170, 4032),
                     blocks=(256, 512, 1024, 2048, 4096), calls=200,
                     dtype="bfloat16", tol=MLA_TOL) -> dict:
    """``mla_paged_attention`` against ``mla_paged_attention_xla`` with
    every slot at each of ``contexts`` and at a mix (one slot empty, the
    rest anywhere up to the table's width), pages scattered over the
    pool and the table padded by the dump page; then milliseconds a call
    and GB/s of the rows' content at each of ``blocks`` tokens a block."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import mla_paged_attention as M
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    kp, kl, kr = jax.random.split(jax.random.key(seed), 3)
    dump = slots * width
    pool = jax.random.normal(kp, (layers, dump + 1, page, M.row_width(
        rank + rope)), jnp.float32).astype(dtype)
    ql = jax.random.normal(kl, (slots, heads, rank), jnp.float32
                           ).astype(dtype)
    qr = jax.random.normal(kr, (slots, heads, rope), jnp.float32
                           ).astype(dtype)
    scale = (rank + rope) ** -0.5
    out = {"phase": "mla_decode", "slots": slots, "heads": heads,
           "row": [rank, rope], "page": page, "table": width,
           "layers": layers, "tol": tol, "gap": {}, "ms_a_call": {},
           "gb_a_s": {}}
    twin = jax.jit(lambda ql, qr, pool, table, lens:
                   M.mla_paged_attention_xla(ql, qr, pool, layers - 1,
                                             table, lens, sm_scale=scale))
    # name -> (lens, table, lens on the device, XLA's answer)
    cases = {name: (lens, table, n, twin(ql, qr, pool, table, n))
             for name, (lens, table, n) in decode_cases(
                 rng, slots=slots, width=width, page=page,
                 contexts=contexts)[0].items()}
    was = M.BLOCK_TOKENS
    try:
        for tokens in blocks:
            M.BLOCK_TOKENS = tokens     # read as a call is traced

            def one_call(ql, qr, pool, table, lens, layer=layers - 1):
                return M.mla_paged_attention(ql, qr, pool, layer, table,
                                             lens, sm_scale=scale)

            def every_call(ql, qr, pool, table, lens):
                # one program, a call a turn of the loop on layer
                # i % layers: the device's time, no dispatch between
                return jax.lax.fori_loop(
                    0, calls, lambda i, _: one_call(ql, qr, pool, table,
                                                    lens, i % layers),
                    jnp.zeros_like(ql))

            kernel, timed = jax.jit(one_call), jax.jit(every_call)
            for name, (lens, table, n, want) in cases.items():
                key = f"block{tokens}.ctx{name}"
                try:
                    got = kernel(ql, qr, pool, table, n)
                except Exception as e:  # more VMEM than a kernel may use
                    out["ms_a_call"][key] = (
                        f"refused: {type(e).__name__}: {str(e)[:200]}")
                    continue
                gap = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                            - want.astype(jnp.float32))))
                out["gap"][key] = gap
                if not gap <= tol:      # a NaN fails too
                    raise RuntimeError(
                        f"mla_paged_attention differs from its XLA form "
                        f"at {key}: {gap} > {tol}")
                jax.block_until_ready(timed(ql, qr, pool, table, n))
                t = time.perf_counter()
                jax.block_until_ready(timed(ql, qr, pool, table, n))
                ms = (time.perf_counter() - t) * 1e3 / calls
                out["ms_a_call"][key] = round(ms, 4)
                out["gb_a_s"][key] = round(
                    int(lens.sum()) * (rank + rope) * pool.dtype.itemsize
                    / ms / 1e6, 1)
    finally:
        M.BLOCK_TOKENS = was
    out["seconds"] = round(time.perf_counter() - t0, 2)
    return out


# ------------------------------------------------- the K/V decode kernel
# The paged cells' decode calls: slots, pool rows a page (KV heads; two
# heads of 64 a 128-lane row in Granite's), query heads a row (half of
# Granite's 8 are ``_attend_packed``'s zero padding), the table's width
# in pages of 16, the layers in a pool and the contexts the mix runs.
PAGED_CELLS = {
    "mistral": dict(slots=32, kvh=8, rep=4, width=64, layers=16,
                    contexts=(1, 145, 500, 900)),
    "granite": dict(slots=64, kvh=4, rep=8, width=256, layers=4,
                    contexts=(1, 300, 1100, 4032)),
    "nemotron": dict(slots=64, kvh=2, rep=16, width=256, layers=6,
                     contexts=(1, 300, 1100, 4032)),
}

# As MLA_TOL: both forms round the softmax's weights to bfloat16 before
# p.v, the kernel a round at a time; relative to 1 + |the XLA form's|.
PAGED_TOL = 2.0 ** -6


def paged_decode_phase(*, seed, slots, kvh, rep, width, layers, contexts,
                       d=128, page=16, blocks=(256, 512, 1024, 2048, 4096),
                       rounds=(256, 512, 1024), calls=200,
                       dtype="bfloat16", tol=PAGED_TOL) -> dict:
    """``paged_attention`` against ``paged_attention_xla`` with every
    slot at each of ``contexts`` and at a mix (one slot empty, the rest
    anywhere up to the table's width), pages scattered over the pools
    and the table padded by the dump page; then milliseconds a call and
    GB/s of the K and V the contexts hold, at each of ``blocks`` tokens
    a grid step (those the table holds) and ``rounds`` rows a round."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as PA
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    kk, kv, kq = jax.random.split(jax.random.key(seed), 3)
    dump = slots * width
    shape = (layers, dump + 1, kvh, page, d)
    kpool = jax.random.normal(kk, shape, jnp.float32).astype(dtype)
    vpool = jax.random.normal(kv, shape, jnp.float32).astype(dtype)
    q = jax.random.normal(kq, (slots, kvh * rep, d), jnp.float32
                          ).astype(dtype)
    page_bytes = PA.page_bytes(kpool)
    token_bytes = 2 * page_bytes // page            # K and V
    out = {"phase": "paged_decode", "slots": slots, "kv_rows": kvh,
           "rep": rep, "d": d, "page": page, "table": width,
           "layers": layers, "page_bytes": page_bytes, "tol": tol,
           "gap": {}, "ms_a_call": {}, "gb_a_s": {}}

    last = (calls - 1) % layers     # the layer the timed loop ends on
    twin = jax.jit(lambda q, k, v, table, lens: PA.paged_attention_xla(
        q, k, v, last, table, lens))
    found, most = decode_cases(rng, slots=slots, width=width, page=page,
                               contexts=contexts)
    # name -> (lens, table, lens on the device, XLA's answer)
    cases = {name: (lens, table, n, twin(q, kpool, vpool, table, n
                                         ).astype(jnp.float32))
             for name, (lens, table, n) in found.items()}
    was = PA.BLOCK_BYTES, PA.ROUND_TOKENS
    try:
        for tokens, rows in ((t, r) for t in blocks for r in rounds
                             if r <= t <= most):
            # read as a call is traced
            PA.BLOCK_BYTES = tokens // page * page_bytes
            PA.ROUND_TOKENS = rows

            def every_call(q, k, v, table, lens):
                # one program, a call a turn of the loop on layer
                # i % layers: the device's time, no dispatch between
                return jax.lax.fori_loop(
                    0, calls, lambda i, _: PA.paged_attention(
                        q, k, v, i % layers, table, lens),
                    jnp.zeros_like(q))

            timed = jax.jit(every_call)
            for name, (lens, table, n, want) in cases.items():
                key = f"block{tokens}.round{rows}.ctx{name}"
                try:
                    got = timed(q, kpool, vpool, table, n)
                except Exception as e:  # more VMEM than a kernel may use
                    out["ms_a_call"][key] = (
                        f"refused: {type(e).__name__}: {str(e)[:200]}")
                    continue
                # an empty slot's row is NaN in the XLA form (a softmax
                # over nothing) and zeros in the kernel's
                got = got.astype(jnp.float32)
                gap = float(jnp.max(jnp.where(
                    (n > 0)[:, None, None],
                    jnp.abs(got - want) / (1.0 + jnp.abs(want)),
                    jnp.abs(got))))
                out["gap"][key] = gap
                if not gap <= tol:      # a NaN fails too
                    raise RuntimeError(
                        f"paged_attention differs from its XLA form at "
                        f"{key}: {gap} > {tol}")
                t = time.perf_counter()
                jax.block_until_ready(timed(q, kpool, vpool, table, n))
                ms = (time.perf_counter() - t) * 1e3 / calls
                out["ms_a_call"][key] = round(ms, 4)
                out["gb_a_s"][key] = round(
                    int(lens.sum()) * token_bytes / ms / 1e6, 1)
    finally:
        PA.BLOCK_BYTES, PA.ROUND_TOKENS = was
    out["seconds"] = round(time.perf_counter() - t0, 2)
    return out


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips")
    ap.add_argument("--ssm-update", nargs="?", type=int, const=1,
                    metavar="GROUPS",
                    help="run only the state-update kernel's check, B and "
                         "C in GROUPS groups (1 if none is given)")
    ap.add_argument("--grouped-matmul", metavar="E,K,N",
                    help="run only the experts' grouped matmul's check, "
                         "over E matrices [K, N]")
    ap.add_argument("--mla-decode", action="store_true",
                    help="run only the latent decode kernel's check")
    ap.add_argument("--paged-decode", nargs="?", const="all",
                    choices=("all", *PAGED_CELLS), metavar="CELL",
                    help="run only the K/V paged decode kernel's check, "
                         "at every paged cell's shape or at CELL's")
    args = ap.parse_args(argv)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = require_tpu(4 if args.four_chips else 1)
    interpret_is_off()
    import jax
    say(jax=jax.__version__, compile_cache=cache_dir,
        devices=[str(d) for d in devices])

    if args.ssm_update:
        say(**ssm_update_phase(seed=SEED, groups=args.ssm_update))
        return _ok(devices)
    if args.grouped_matmul:
        e, k, n = (int(x) for x in args.grouped_matmul.split(","))
        say(**grouped_matmul_phase(seed=SEED, experts=e, k=k, n=n))
        return _ok(devices)
    if args.mla_decode:
        say(**mla_decode_phase(seed=SEED))
        return _ok(devices)
    if args.paged_decode:
        for cell, shape in PAGED_CELLS.items():
            if args.paged_decode in ("all", cell):
                say(cell=cell, **paged_decode_phase(seed=SEED, **shape))
        return _ok(devices)

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

    return _ok(devices)


def _ok(devices) -> int:
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
