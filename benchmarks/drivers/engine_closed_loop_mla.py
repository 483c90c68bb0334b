"""Driver ``engine_closed_loop_mla``: ``engine_closed_loop`` for a
``deepseek_v3`` configuration (latent attention, a share of the routed
experts).

The callers, the window and its sums are ``engine_closed_loop``'s
(``Loop``, ``window``, ``summarize`` through it, ``sample_finished``):
the same ``Engine.submit`` / ``Engine.step`` loop on the same
Scheduler, BlockManager and ring.  This file brings what the family
changes: ``build`` (the model description, its seeded weights, the
engine) and ``reference_gaps`` (the plain reference
``benchmarks/reference/deepseek_v3_lm.py``, given the same share of the
experts and the same slice of the vocabulary), and hands the window the
expert layers' counters, read from ``engine.stats()`` just outside it.

The configuration's ``model`` keys are the published ones,
``n_routed_experts`` being the experts held here; ``published`` has the
router's width, ``expert_parallel`` this chip's rank.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.drivers.engine_closed_loop import (   # noqa: F401
    Loop, _listed, plain, sample_finished, summarize, window)
from benchmarks.lib import (device, mla_moe_state, mla_moe_work, state,
                            stats, traffic, xplane)

MOE_COUNTERS = ("moe_routed_pairs", "moe_local_pairs", "moe_experts_live")


# ---------------------------------------------------------------- set-up
def build(ctx: dict) -> dict:
    """Seeded weights and the engine over them.  A checkout whose
    program lacks the family fails here, at once."""
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
    from paddle_tpu.serving.engine import Engine

    conf, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    m = conf["model"]
    dtype = conf["assumed"]["torch_dtype"]
    cfg = DeepseekV3Config(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        first_k_dense_replace=m["first_k_dense_replace"],
        num_attention_heads=m["num_attention_heads"],
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        n_routed_experts=mla_moe_state.router_width(conf),
        n_shared_experts=m["n_shared_experts"],
        num_experts_per_tok=m["num_experts_per_tok"], n_group=m["n_group"],
        topk_group=m["topk_group"],
        routed_scaling_factor=m["routed_scaling_factor"],
        norm_topk_prob=m["norm_topk_prob"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        rope_scaling=m["rope_scaling"],
        local_experts=mla_moe_state.local_experts(conf), dtype=dtype)
    if (m["topk_method"], m["scoring_func"]) != ("noaux_tc", "sigmoid"):
        raise RuntimeError("the program has the noaux_tc sigmoid router only")
    std = conf["assumed"]["initializer_range"]
    weights = state.seeded(mla_moe_state.shapes(conf), seed, std=std,
                           dtype=dtype)
    # the routers' biases at their own, smaller deviation (the file says
    # why): 256 values a layer, scaled after the one seeded call
    shrink = conf["assumed"].get("e_score_correction_bias_std", std) / std
    for key in [k for k in weights if k.endswith("e_score_correction_bias")]:
        weights[key] = (weights[key].astype("float32")
                        * shrink).astype(dtype)
    stats.mark(ctx, "weights")
    kw = dict(conf["engine"])
    kw.update(mix.get("engine", {}))
    engine = Engine(config=cfg, state=weights, **kw)
    return {"engine": engine, "weights": weights,
            "traffic": traffic.ClosedLoop(mix, m["vocab_size"], seed)}


def moe_counters(engine) -> dict:
    s = engine.stats()
    return {k: int(s[k]) for k in MOE_COUNTERS}


def counted_window(loop: Loop, seconds: float, conf: dict) -> dict:
    """``window`` with the expert counters' difference over the same
    decode steps (no step runs between the two readings on either
    side)."""
    before = moe_counters(loop.engine)
    seen = window(loop, seconds)
    after = moe_counters(loop.engine)
    seen.update({k: after[k] - before[k] for k in MOE_COUNTERS})
    seen["moe_layer_experts"] = (mla_moe_work.expert_layers(conf)
                                 * mla_moe_state.local_experts(conf)[1])
    return seen


# --------------------------------------------------------------- correct
def reference_gaps(ctx: dict, weights: dict, sample: list, *,
                   int8: bool = False) -> dict:
    """Every served token of ``sample`` against the plain reference."""
    from benchmarks.reference import deepseek_v3_lm as ref
    conf = ctx["config"]
    m = dict(conf["model"],
             local_experts=list(mla_moe_state.local_experts(conf)),
             n_routed_experts=mla_moe_state.router_width(conf))
    longest = int(dict(conf["engine"],
                       **ctx["mix"].get("engine", {}))["max_model_len"])
    step = int(ctx["mix"].get("check_pad", longest))
    pad_rows = int(ctx["mix"]["new_tokens"]["high"])
    all_gaps = []
    for rec in sample:
        n = len(rec["prompt"]) + len(rec["tokens"])
        got = ref.served_gaps(weights, m, rec["prompt"], rec["tokens"],
                              pad_to=min(longest, -(-n // step) * step),
                              pad_rows=pad_rows, int8=int8)
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
    # drop the engine (pool, decode state) before the reference runs
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
                logit_gap_max=found["logit_gap_max"],
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
    ``controls`` seeds: the int8 control on the same prompts and
    tokens."""
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
            row["control_int8"] = _listed(reference_gaps(
                c, weights, sample, int8=True))
        row["seconds"] = time.perf_counter() - t0
        print({k: ({a: b for a, b in v.items() if a != "gaps"}
                   if isinstance(v, dict) else v) for k, v in row.items()},
              flush=True)
        rows.append(row)
        del weights
        gc.collect()
    return {"cell": ctx["workload"], "rows": rows}
