"""Driver ``engine_closed_loop_hybrid_moe``: ``engine_closed_loop`` for a
``nemotron_h`` configuration (blocks of one part each: Mamba-2 in
groups, GQA attention, routed experts of which this chip holds a share;
a per-slot recurrent state beside the K/V pages).

The callers, the window and its sums are ``engine_closed_loop``'s
(``Loop``, ``window``, ``summarize`` through it, ``sample_finished``):
the same ``Engine.submit`` / ``Engine.step`` loop on the same
Scheduler, BlockManager and ring.  This file brings what the family
changes: ``build`` (the model description, its seeded weights, the
engine) and ``reference_gaps`` (the plain reference
``benchmarks/reference/nemotron_h_lm.py``, given the same share of the
experts and the same slice of the vocabulary, with its two controls),
and hands the window the Mamba blocks' and the expert blocks' counters,
read from ``engine.stats()`` just outside it.

The configuration's ``model`` keys are the published ones,
``n_routed_experts`` being the experts held here; ``published`` has the
router's width, ``expert_parallel`` this chip's rank, ``assumed`` the
served dtype, the position term and how the weights are seeded.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.drivers.engine_closed_loop import (   # noqa: F401
    Loop, _listed, plain, sample_finished, summarize, window)
from benchmarks.lib import (device, hybrid_moe_state, hybrid_moe_work, stats,
                            traffic, xplane)

COUNTERS = ("ssm_rows_live", "moe_routed_pairs", "moe_local_pairs",
            "moe_experts_live")
CONTROLS = ("int8", "one_group")


def routed_model(conf: dict) -> dict:
    """``model`` as the program and the reference read it: the router
    over every published expert, this chip's share named beside it."""
    return dict(conf["model"],
                n_routed_experts=hybrid_moe_state.router_width(conf),
                local_experts=list(hybrid_moe_state.local_experts(conf)))


# ---------------------------------------------------------------- set-up
def build(ctx: dict) -> dict:
    """Seeded weights and the engine over them.  A checkout whose
    program lacks the family fails here, at once."""
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    from paddle_tpu.serving.engine import Engine

    conf, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    m = routed_model(conf)
    cfg = NemotronHConfig.from_published(
        {k: v for k, v in m.items() if k != "local_experts"},
        local_experts=tuple(m["local_experts"]),
        position_embedding_type=conf["assumed"]["position_embedding_type"],
        dtype=conf["assumed"]["torch_dtype"])
    weights = hybrid_moe_state.seeded(conf, seed)
    stats.mark(ctx, "weights")
    kw = dict(conf["engine"])
    kw.update(mix.get("engine", {}))
    engine = Engine(config=cfg, state=weights, **kw)
    # the rooflines count the state in the dtype the file states
    held = engine.stats()["recurrent_state_bytes"]
    stated = hybrid_moe_work.recurrent_state_bytes(conf, kw["max_slots"])
    if held != stated:
        raise RuntimeError(
            f"the program keeps {held} bytes of recurrent state, the "
            f"configuration's assumed.ssm_state_dtype states {stated}")
    return {"engine": engine, "weights": weights,
            "traffic": traffic.ClosedLoop(mix, conf["model"]["vocab_size"],
                                          seed)}


def counters(engine) -> dict:
    s = engine.stats()
    return {k: int(s[k]) for k in COUNTERS}


def counted_window(loop: Loop, seconds: float, conf: dict) -> dict:
    """``window`` with the device counters' differences over the same
    decode steps (no step runs between the two readings on either
    side)."""
    before = counters(loop.engine)
    seen = window(loop, seconds)
    after = counters(loop.engine)
    seen.update({k: after[k] - before[k] for k in COUNTERS})
    d = hybrid_moe_state.dims(conf)
    seen["ssm_layers"] = d["mamba_layers"]
    seen["moe_layer_experts"] = (d["expert_layers"]
                                 * hybrid_moe_state.local_experts(conf)[1])
    return seen


# --------------------------------------------------------------- correct
def reference_gaps(ctx: dict, weights: dict, sample: list, *,
                   int8: bool = False, one_group: bool = False) -> dict:
    """Every served token of ``sample`` against the plain reference."""
    from benchmarks.reference import nemotron_h_lm as ref
    conf = ctx["config"]
    m = routed_model(conf)
    longest = int(dict(conf["engine"],
                       **ctx["mix"].get("engine", {}))["max_model_len"])
    step = int(ctx["mix"].get("check_pad", longest))
    pad_rows = int(ctx["mix"]["new_tokens"]["high"])
    all_gaps = []
    for rec in sample:
        n = len(rec["prompt"]) + len(rec["tokens"])
        got = ref.served_gaps(weights, m, rec["prompt"], rec["tokens"],
                              pad_to=min(longest, -(-n // step) * step),
                              pad_rows=pad_rows, int8=int8,
                              one_group=one_group)
        all_gaps.append(got["gaps"])
    cat = np.concatenate(all_gaps) if all_gaps else np.zeros((0,))
    if not cat.size:
        return {"logit_gap_max": float("nan"), "logit_gap_mean": float("nan"),
                "logit_gap_p99": float("nan"), "positions": 0,
                "requests": len(sample), "flipped": 0, "gaps": cat}
    return {"logit_gap_max": float(cat.max()),
            "logit_gap_mean": float(cat.mean()),
            "logit_gap_p99": float(np.quantile(cat, 0.99)),
            "positions": int(cat.size), "requests": len(sample),
            "flipped": int((cat > 0).sum()), "gaps": cat}


# ------------------------------------------------------------------- run
def run(ctx: dict) -> dict:
    mix = ctx["mix"]
    served = build(ctx)
    stats.mark(ctx, "engine")
    loop = Loop(served)
    loop.start()
    loop.ramp()
    stats.mark(ctx, "ramp")
    seconds = ctx["seconds"]
    if ctx["trace_dir"]:
        seconds = min(seconds, float(mix["trace_seconds"]))
        xplane.start(ctx["trace_dir"])
    setup_s = time.perf_counter() - ctx["t_start"]
    try:
        seen = counted_window(loop, seconds, ctx["config"])
    finally:
        if ctx["trace_dir"]:
            xplane.stop()
    stats.mark(ctx, "window")
    peak = device.memory_peak_bytes(ctx["devices"])
    weights = served["weights"]
    finished = seen.pop("finished")
    itl, ttft = seen.pop("itl_ms"), seen.pop("ttft_ms")
    sample = plain(sample_finished(finished, ctx["seed"],
                                   int(mix["check_requests"])))
    # drop the engine (pools, decode state) before the reference runs
    loop.records.clear()
    served.clear()
    del loop, finished
    gc.collect()
    found = reference_gaps(ctx, weights, sample)
    found.pop("gaps")
    stats.mark(ctx, "reference")
    found["requests_finished"] = seen["finished_count"] = len(sample)
    correct, checks = stats.judge(found, ctx["limits"])
    correct = correct and found["positions"] > 0 and seen["failed"] == 0
    seen.update(check_positions=found["positions"],
                check_flipped=found["flipped"],
                # shown, not compared, where the limits leave them out
                logit_gap_max=found["logit_gap_max"],
                logit_gap_p99=found["logit_gap_p99"],
                **{f"itl_p{int(q * 100)}_ms": stats.percentile(itl, q)
                   for q in (0.5, 0.9, 0.99)},
                **{f"ttft_p{int(q * 100)}_ms": stats.percentile(ttft, q)
                   for q in (0.5, 0.95)},
                ttft_mean_ms=float(np.mean(ttft)) if ttft else float("nan"),
                itl_mean_ms=float(np.mean(itl)), gaps_timed=len(itl))
    return {"setup_s": setup_s, "window_s": seen["window_s"],
            "attempted": seen["attempted"], "failed": seen["failed"],
            "end_to_end": {
                "serve_tokens_per_s": seen["tokens"] / seen["window_s"],
                "itl_p95_ms": stats.percentile(itl, 0.95)},
            "observed": seen, "correct": correct, "checks": checks,
            "memory_peak_bytes": peak}


# ------------------------------------------------------------- calibrate
def calibrate(ctx: dict, seeds: list, controls: int) -> dict:
    """Lower readings: the program's gaps on every seed, from a short
    window at the cell's own load.  Upper readings, for the first
    ``controls`` seeds: the int8 control and the ``one_group`` control
    on the same prompts and tokens."""
    rows = []
    for n, seed in enumerate(seeds):
        c = dict(ctx, seed=seed)
        t0 = time.perf_counter()
        served = build(c)
        loop = Loop(served)
        loop.start()
        loop.ramp()
        seen = window(loop, c["seconds"])
        sample = plain(sample_finished(seen["finished"], seed,
                                       int(c["mix"]["check_requests"])))
        weights = served["weights"]
        rate = seen["tokens"] / seen["window_s"]
        loop.records.clear()
        served.clear()
        del loop, seen
        gc.collect()
        row = {"seed": seed, "tokens_per_s": rate,
               "program": _listed(reference_gaps(c, weights, sample))}
        if n < controls:
            for name in CONTROLS:
                row["control_" + name] = _listed(reference_gaps(
                    c, weights, sample, **{name: True}))
        row["seconds"] = time.perf_counter() - t0
        print({k: ({a: b for a, b in v.items() if a != "gaps"}
                   if isinstance(v, dict) else v) for k, v in row.items()},
              flush=True)
        rows.append(row)
        del weights
        gc.collect()
    return {"cell": ctx["workload"], "rows": rows}
