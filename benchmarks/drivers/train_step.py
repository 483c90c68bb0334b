"""Driver ``train_step``: ``paddle.jit.train_step`` on one chip.

Set-up builds ONE object, the compiled step with its model and optimizer
state, loads the seeded weights into it, drives it through its first
``follow_steps`` steps on the pool's first batches (every row differs)
and hands that same object to the window.  Those steps go through the
window's own call and feed.  What the program says of them (each loss;
the first gradient's norm, leaf by leaf, as the optimizer got it, read
from its first moment after step 1; the norm of every leaf's change
after the steps) is held against the plain reference once the window
has closed, the peak has been read and the program's state is freed.

The configuration's ``model`` keys are ``BertConfig``'s; its ``trainer``
keys name the optimizer and autocast; the mix names batch, sequence
length, pool and how often the loss is fetched.  No size lives here.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.lib import device, state, stats, traffic, xplane


# ---------------------------------------------------------------- set-up
def _labels(ctx: dict) -> int:
    return int(ctx["config"]["assumed"]["num_labels"])


def seeded_weights(ctx: dict) -> dict:
    m = ctx["config"]["model"]
    return state.bert_state(m, ctx["seed"], num_labels=_labels(ctx),
                            std=m["initializer_range"])


def seeded_pool(ctx: dict):
    return traffic.train_pool(ctx["mix"], ctx["config"]["model"]["vocab_size"],
                              _labels(ctx), ctx["seed"])


def build(ctx: dict) -> dict:
    """The trainer: model with seeded weights, optimizer, compiled step,
    the pool of batches; nothing has run yet."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.bert import (BertConfig,
                                        BertForSequenceClassification)

    m, tr = ctx["config"]["model"], ctx["config"]["trainer"]
    labels = _labels(ctx)
    bcfg = BertConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        intermediate_size=m["intermediate_size"],
        max_position_embeddings=m["max_position_embeddings"],
        type_vocab_size=m["type_vocab_size"],
        layer_norm_eps=m["layer_norm_eps"], num_labels=labels)
    model = BertForSequenceClassification(bcfg)
    model.train()
    weights = seeded_weights(ctx)
    names = {name: p for name, p in model.named_parameters()}
    if set(names) != set(weights):
        raise RuntimeError(
            "the program's BERT parameters are not the benchmark's: "
            f"{sorted(set(names) ^ set(weights))[:6]}")
    for name, p in names.items():
        if tuple(p.shape) != tuple(weights[name].shape):
            raise RuntimeError(f"{name}: {p.shape} != {weights[name].shape}")
    model.load_functional_state(weights)
    del weights
    o = opt.AdamW(learning_rate=tr["learning_rate"], beta1=tr["beta1"],
                  beta2=tr["beta2"], epsilon=tr["epsilon"],
                  weight_decay=tr["weight_decay"],
                  parameters=model.parameters())
    autocast, level = bool(tr["autocast"]), tr.get("autocast_level", "O1")

    def loss_fn(mod, ids, tts, y):
        with paddle.amp.auto_cast(enable=autocast, level=level,
                                  dtype=tr.get("autocast_dtype",
                                               "bfloat16")):
            logits = mod(ids, tts)
        return F.cross_entropy(logits, y)

    step = paddle.jit.train_step(model, o, loss_fn)
    ids, ys = seeded_pool(ctx)
    tts = jnp.zeros(ids.shape[1:], jnp.int32)
    batches = [(ids[i], tts, ys[i]) for i in range(ids.shape[0])]
    return {"model": model, "optimizer": o, "step": step,
            "batches": batches, "by_key": {p.name: n
                                           for n, p in names.items()}}


def _norm_fns(ctx: dict):
    """Two small jitted programs: leaf norms of the first moment with a
    sample of its elements, and leaf norms of the parameters' change from the seeded start (made again from the
    seed's key inside the program, so no second copy is kept, and the
    key is an argument, so every seed runs the same program)."""
    import jax
    import jax.numpy as jnp
    m = ctx["config"]["model"]
    start_of = state.maker(state.bert_shapes(m, _labels(ctx)),
                           std=m["initializer_range"], dtype="float32")

    n = int(ctx["mix"]["grad_sample"])

    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in tree.items()}

    def seen(tree):
        return norms(tree), {k: stats.strided(v, n).astype(jnp.float32)
                             for k, v in tree.items()}

    def change(params, key):
        start = start_of(key)
        return norms({k: params[k] - start[k] for k in params})

    return jax.jit(seen), jax.jit(change)


def first_steps(trainer: dict, ctx: dict) -> dict:
    """Drive the first steps through the window's own call; returns the
    program's readings as floats."""
    tr = ctx["config"]["trainer"]
    n = int(ctx["mix"]["follow_steps"])
    seen, change = _norm_fns(ctx)
    model, o, step = trainer["model"], trainer["optimizer"], trainer["step"]
    scale = 1.0 / (1.0 - tr["beta1"])       # m1 = (1 - beta1) g
    losses, grad, sample = [], None, None
    for k in range(n):
        losses.append(float(step(*trainer["batches"][k])))
        stats.mark(ctx, f"step_{k + 1}")
        if k == 0:
            acc = o.opt_state()["acc"]
            moment = {trainer["by_key"][key]: slots["moment1"]
                      for key, slots in acc.items()}
            got, some = seen(moment) if moment else ({}, {})
            # a leaf the optimizer holds no moment for got no gradient
            grad = {name: float(got.get(name, 0.0)) * scale
                    for name in trainer["by_key"].values()}
            sample = {name: np.asarray(v) * scale
                      for name, v in some.items()}
    params = {name: p._data for name, p in model.named_parameters()}
    moved = {k: float(v) for k, v in change(
        params, state.key_of(ctx["seed"])).items()}
    return {"losses": losses, "grad_norm": grad, "grad_sample": sample,
            "change_norm": moved}


def compiles(trainer: dict) -> int:
    """Programs the step's jit holds: read before and after a window."""
    fn = trainer["step"]._compiled
    return int(fn._cache_size()) if fn is not None else 0


# ---------------------------------------------------------------- window
def window(trainer: dict, ctx: dict, seconds: float) -> dict:
    """Step for ``seconds``; the loss is fetched every ``fetch_every``
    steps as a logging user does, and the window ends in
    ``block_until_ready`` on the last step's outputs."""
    import jax
    mix = ctx["mix"]
    step, batches = trainer["step"], trainer["batches"]
    every, n_pool = int(mix["fetch_every"]), len(batches)
    offset = int(mix["follow_steps"])
    annotate = jax.profiler.TraceAnnotation
    before = compiles(trainer)
    marks, fetched = [], []
    with annotate("bench.window"):
        t0 = time.perf_counter()
        marks.append(t0)
        i = 0
        while True:
            with annotate("bench.train.step"):
                loss = step(*batches[(offset + i) % n_pool])
            i += 1
            if i % every == 0:
                with annotate("bench.fetch_loss"):
                    fetched.append(float(loss))
                marks.append(time.perf_counter())
                if marks[-1] - t0 >= seconds:
                    break
        with annotate("bench.fetch_loss"):
            jax.block_until_ready(
                [p._data for p in trainer["model"].parameters()])
            fetched.append(float(loss))
        t1 = time.perf_counter()
    if compiles(trainer) != before:
        raise RuntimeError(
            f"the step compiled inside the window: {before} -> "
            f"{compiles(trainer)} programs")
    if not np.all(np.isfinite(fetched)):
        raise RuntimeError(f"loss is not finite: {fetched}")
    groups = np.diff(marks) / every
    batch, seq = int(mix["batch"]), int(mix["seq"])
    return {"window_s": t1 - t0, "steps": i, "tokens": i * batch * seq,
            "step_ms_p50": 1e3 * stats.median(groups.tolist()),
            "losses_fetched": len(fetched), "last_loss": fetched[-1],
            "batch": batch, "seq": seq}


# --------------------------------------------------------------- correct
def gaps(program: dict, reference: dict) -> dict:
    """The numbers compared.  Norms are taken by the worst leaf: the gap
    between the program's norm and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Leaves whose reference gradient is under a thousandth of
    the median leaf's (a key's bias under softmax, an embedding row no
    token reaches) move by round-off alone and are left out of the
    change."""
    ref_g, ref_c = reference["grad_norm"], reference["change_norm"]
    med_g = float(np.median(list(ref_g.values())))
    med_c = float(np.median(list(ref_c.values())))

    def by_leaf(prog, ref, med, keys):
        by = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
        top = sorted(by, key=by.get, reverse=True)[:3]
        return (by[top[0]], [[k, by[k]] for k in top],
                float(np.median(list(by.values()))))

    # direction as well as length: the norm of the difference over the
    # sampled elements, which rounding noise moves where it leaves the
    # norms alone
    ref_s, got_s = reference["grad_sample"], program["grad_sample"]
    size = {k: float(np.linalg.norm(v)) for k, v in ref_s.items()}
    mid = float(np.median(list(size.values())))
    apart = [float(np.linalg.norm(got_s[k] - ref_s[k])) / max(size[k], mid)
             if k in got_s else 1.0 for k in ref_s]
    live = [k for k in ref_g if ref_g[k] >= 1e-3 * med_g]
    steps = [abs(p - r) / abs(r) for p, r in zip(
        program["losses"], reference["losses"])]
    grad, grad_top, grad_mid = by_leaf(program["grad_norm"], ref_g, med_g,
                                        list(ref_g))
    change, change_top, change_mid = by_leaf(program["change_norm"], ref_c,
                                              med_c, live)
    return {"loss_gap": max(steps), "loss_gap_first": steps[0],
            "grad_norm_gap": grad, "grad_norm_gap_median": grad_mid,
            "grad_sample_gap": max(apart),
            "grad_sample_gap_median": float(np.median(apart)),
            "change_norm_gap": change, "change_norm_gap_median": change_mid,
            "loss_gaps": steps, "worst_grad_leaves": grad_top,
            "worst_change_leaves": change_top,
            "worst_grad_leaf": grad_top[0][0],
            "worst_change_leaf": change_top[0][0],
            "leaves_left_out": len(ref_g) - len(live)}


def reference_readings(ctx: dict, *, fp8: bool = False,
                       rows: slice | None = None) -> dict:
    """The plain reference over the same first steps, from the seed.
    ``fp8`` is the control; ``rows`` plants the fault of a batch whose
    other rows were left out."""
    from benchmarks.reference import bert_classifier as ref
    ids, ys = seeded_pool(ctx)
    rows = rows or slice(None)
    batches = [(ids[k][rows], np.zeros(ids[k][rows].shape, np.int32),
                ys[k][rows]) for k in range(int(ctx["mix"]["follow_steps"]))]
    return ref.follow(seeded_weights(ctx), batches,
                      model=ctx["config"]["model"],
                      optimizer=ctx["config"]["trainer"], fp8=fp8,
                      sample=int(ctx["mix"]["grad_sample"]))


# ------------------------------------------------------------------- run
def run(ctx: dict) -> dict:
    """One run of the cell: set-up, window, peak, then the reference."""
    mix = ctx["mix"]
    trainer = build(ctx)
    stats.mark(ctx, "built")
    program = first_steps(trainer, ctx)
    stats.mark(ctx, "first_steps")
    seconds = ctx["seconds"]
    if ctx["trace_dir"]:
        seconds = min(seconds, float(mix["trace_seconds"]))
        xplane.start(ctx["trace_dir"])
    setup_s = time.perf_counter() - ctx["t_start"]
    try:
        seen = window(trainer, ctx, seconds)
    finally:
        if ctx["trace_dir"]:
            xplane.stop()
    stats.mark(ctx, "window")
    peak = device.memory_peak_bytes(ctx["devices"])
    trainer.clear()
    del trainer
    gc.collect()
    reference = reference_readings(ctx)
    found = gaps(program, reference)
    stats.mark(ctx, "reference")
    correct, checks = stats.judge(found, ctx["limits"])
    seen.update(loss_first=program["losses"],
                loss_reference=reference["losses"],
                worst_grad_leaf=found["worst_grad_leaf"],
                worst_change_leaf=found["worst_change_leaf"])
    return {"setup_s": setup_s, "window_s": seen["window_s"],
            "attempted": seen["steps"], "failed": 0,
            "end_to_end": {
                "train_tokens_per_s": seen["tokens"] / seen["window_s"]},
            "observed": seen, "correct": correct, "checks": checks,
            "memory_peak_bytes": peak}


# ------------------------------------------------------------- calibrate
def calibrate(ctx: dict, seeds: list, controls: int) -> dict:
    """Lower and upper readings for this cell's limits (see
    ``benchmarks/calibrate.py``): one trainer, given each seed's weights
    and a fresh optimizer state in turn, then the reference; for the
    first ``controls`` seeds also the fp8 control and the fault of half a
    batch, each as the reference put in the program's place."""
    rows = []
    trainer = None
    half = slice(0, int(ctx["mix"]["batch"]) // 2)
    for n, seed in enumerate(seeds):
        ctx = dict(ctx, seed=seed)
        t0 = time.perf_counter()
        if trainer is None:
            trainer = build(ctx)
        else:
            _reseed(trainer, ctx)
        program = first_steps(trainer, ctx)
        trainer["optimizer"].load_opt_state(
            {"acc": {}, "master": {}, "step": 0})    # room for the reference
        gc.collect()
        reference = reference_readings(ctx)
        row = {"seed": seed, "program": gaps(program, reference)}
        if n < controls:
            for name, kw in (("control_fp8", dict(fp8=True)),
                             ("fault_half_batch", dict(rows=half))):
                row[name] = gaps(reference_readings(ctx, **kw), reference)
        row["seconds"] = time.perf_counter() - t0
        print(row, flush=True)
        rows.append(row)
    return {"cell": ctx["workload"], "rows": rows}


def _reseed(trainer: dict, ctx: dict):
    trainer["model"].load_functional_state(seeded_weights(ctx))
    trainer["optimizer"].load_opt_state(
        {"acc": {}, "master": {}, "step": 0})
    ids, ys = seeded_pool(ctx)
    tts = trainer["batches"][0][1]
    trainer["batches"] = [(ids[i], tts, ys[i]) for i in range(ids.shape[0])]
