"""Driver ``engine_closed_loop``: a fixed pool of callers on one Engine.

The window drives ``Engine.submit(..., on_token=...)`` and
``Engine.step()`` in-process from one thread: the loop that
``serving/server.py``'s worker runs, without the HTTP front end (which
has no metric yet).  Each caller submits its next request as soon as the
step in which its last one ended has returned.  Weights are made on the
device from the seed and handed to ``Engine(config=, state=)``; no
``nn`` model is built.

Set-up ends with the ramp: every caller admitted and its first token
out, a prompt of every stated length prefilled, so every program the mix
needs has run; the mix cuts the callers' first answers short by spread
fractions (``stagger_first``), so they are out of step with each other
from the start.  The window then measures; a program traced or
compiled inside it (``decode_traces``, the prefill buckets) fails the
run.  Once it has closed, the peak is read, the engine is dropped, and a
sample of the requests the window finished, drawn from the seed with the
longest in it, is held to the plain reference: one teacher-forced
forward over each prompt with its served tokens, and the widest gap by
which a served token's logit lies below the reference's best.

The configuration's ``model`` keys are the published ones; its
``engine`` keys are ``Engine``'s arguments, which a mix may override
under its own ``engine`` key.  No size lives here.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.lib import device, state, stats, traffic, work, xplane


# ---------------------------------------------------------------- set-up
def build(ctx: dict) -> dict:
    """Seeded weights and the engine over them."""
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving.engine import Engine

    cfg, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    m = cfg["model"]
    lcfg = LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        tie_word_embeddings=m["tie_word_embeddings"],
        dtype=m["torch_dtype"])
    if lcfg.head_dim != work.decoder_head_dim(m):
        raise RuntimeError("the program cannot express this head_dim")
    weights = state.decoder_state(m, seed, std=m["initializer_range"],
                                  dtype=m["torch_dtype"])
    stats.mark(ctx, "weights")
    kw = dict(cfg["engine"])
    kw.update(mix.get("engine", {}))
    engine = Engine(config=lcfg, state=weights, **kw)
    return {"engine": engine, "weights": weights,
            "traffic": traffic.ClosedLoop(mix, m["vocab_size"], seed)}


class Loop:
    """The callers.  ``records`` holds one dict per request."""

    def __init__(self, served: dict):
        import jax
        self.engine = served["engine"]
        self.traffic = served["traffic"]
        self.records: list = []
        self._live: dict = {}           # caller -> its request in flight
        self._span = jax.profiler.TraceAnnotation

    def submit(self, caller: int, last_answer=None):
        from paddle_tpu.models.generation import GenerationConfig
        prompt, n_out, sampled = self.traffic.next_request(
            caller, last_answer)
        rec = {"caller": caller, "prompt": prompt, "want": n_out,
               "sampled": sampled is not None, "times": [], "tokens": [],
               "request": None}

        def on_token(req, tok, rec=rec):
            rec["times"].append(time.perf_counter())
            rec["tokens"].append(tok)

        gen = GenerationConfig(max_new_tokens=n_out, **(
            dict(do_sample=True, **sampled) if sampled else {}))
        rec["submitted"] = time.perf_counter()
        with self._span("bench.submit"):
            rec["request"] = self.engine.submit(prompt, gen,
                                                on_token=on_token)
        self.records.append(rec)
        self._live[caller] = rec

    def start(self):
        with self._span("bench.next_batch"):
            for caller in range(self.traffic.callers):
                self.submit(caller)

    def step(self) -> list:
        """One engine step; returns the callers whose request ended in
        it (their next request is not yet submitted)."""
        with self._span("bench.engine.step"):
            self.engine.step()
        return [c for c, rec in self._live.items()
                if rec["request"].is_finished()]

    def resubmit(self, callers):
        with self._span("bench.next_batch"):
            for caller in callers:
                self.submit(caller, self._live[caller]["tokens"])

    def ramp(self, max_steps: int = 100000):
        """Until every caller's first request has its first token and a
        prompt of every length the mix states has been prefilled: every
        caller is admitted and every program the mix needs has run."""
        first = list(self._live.values())
        lengths = set() if self.traffic.mix.get("turns") else {
            int(n) + self.traffic.shared.size
            for n in self.traffic.mix["prompt_lengths"]}
        for _ in range(max_steps):
            self.resubmit(self.step())
            seen = {r["prompt"].size for r in self.records if r["times"]}
            if all(r["times"] for r in first) and lengths <= seen:
                return
        raise RuntimeError("the ramp did not end")


def counters(engine) -> dict:
    s = engine.stats()
    return {"decode_steps": s["decode_steps"],
            "host_syncs": s["host_syncs"],
            "decode_traces": s["decode_traces"],
            "programs": (tuple(s["prefill_buckets"]),
                         tuple(s["cached_prefill_buckets"]),
                         s["verify_traces"]),
            "prefix_hits": s["prefix_hits"],
            "prefix_misses": s["prefix_misses"],
            "quarantines": s["quarantines"],
            **{k: float(v) for k, v in engine.timings.items()}}


# ---------------------------------------------------------------- window
def window(loop: Loop, seconds: float) -> dict:
    import jax
    engine = loop.engine
    before = counters(engine)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            ended = loop.step()
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            loop.resubmit(ended)
        t1 = now
    after = counters(engine)
    for key in ("decode_traces", "programs"):
        if before[key] != after[key]:
            raise RuntimeError(
                f"a program was traced inside the window: {key} "
                f"{before[key]} -> {after[key]}")
    return summarize(loop.records, t0, t1, before, after,
                     max_slots=engine.max_slots)


def summarize(records, t0, t1, before, after, *, max_slots) -> dict:
    """Whole-window quantities from the requests' token times and the
    differences of the engine's own counters."""
    span = t1 - t0
    tokens = 0
    itl, ttft = [], []
    decode_tokens = decode_ctx = prompt_tokens = prefill_ctx = 0
    attempted = failed = 0
    finished = []
    for rec in records:
        t = np.asarray(rec["times"])
        inside = (t >= t0) & (t <= t1)
        tokens += int(inside.sum())
        if t.size > 1:
            itl.extend((np.diff(t)[inside[1:]] * 1e3).tolist())
        n_p = int(rec["prompt"].size)
        later = np.nonzero(inside[1:])[0] + 1       # decode tokens inside
        decode_tokens += later.size
        decode_ctx += int((n_p + later).sum())
        if t.size and inside[0]:
            prompt_tokens += n_p
            prefill_ctx += work.prefill_context_sum(n_p)
        if t0 <= rec["submitted"] <= t1:
            attempted += 1
            bad = rec["request"].finish_reason == "error" or t.size == 0
            failed += int(bad)
            ttft.append(span * 1e3 if bad
                        else (t[0] - rec["submitted"]) * 1e3)
        if (rec["request"].finish_reason == "length" and t.size
                and t0 <= t[-1] <= t1):
            finished.append(rec)
    diff = {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))}
    return {"window_s": span, "tokens": tokens, "attempted": attempted,
            "failed": failed, "itl_ms": itl, "ttft_ms": ttft,
            "finished": finished, "decode_tokens": decode_tokens,
            "decode_context_sum": decode_ctx,
            "prompt_tokens": prompt_tokens,
            "prefill_context_sum": prefill_ctx, "max_slots": max_slots,
            **diff}


# --------------------------------------------------------------- correct
def sample_finished(finished: list, seed: int, n: int) -> list:
    """``n`` of the greedy requests the window finished, drawn from the
    seed, the longest always among them."""
    greedy = [r for r in finished if not r["sampled"]]
    if not greedy:
        return []
    longest = max(range(len(greedy)), key=lambda i: (
        greedy[i]["prompt"].size + len(greedy[i]["tokens"])))
    rest = [i for i in range(len(greedy)) if i != longest]
    rng = np.random.default_rng([int(seed), 3])
    picked = [longest] + list(rng.permutation(rest)[:max(0, n - 1)])
    return [greedy[i] for i in picked]


def plain(sample: list) -> list:
    """The sample without the program's request objects, so that the
    engine can be dropped before the reference runs."""
    return [{"prompt": r["prompt"], "tokens": list(r["tokens"]),
             "sampled": r["sampled"]} for r in sample]


def reference_gaps(ctx: dict, weights: dict, sample: list, *,
                   int8: bool = False) -> dict:
    """Every served token of ``sample`` against the plain reference."""
    from benchmarks.reference import decoder_lm as ref
    m = ctx["config"]["model"]
    longest = int(dict(ctx["config"]["engine"],
                       **ctx["mix"].get("engine", {}))["max_model_len"])
    step = int(ctx["mix"].get("check_pad", longest))
    pad_rows = int(ctx["mix"]["new_tokens"]["high"])
    all_gaps = []
    for rec in sample:
        n = len(rec["prompt"]) + len(rec["tokens"])
        # a few padded lengths, so the reference compiles a few programs
        # and a short request does not pay for the longest
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
        seen = window(loop, seconds)
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
                logit_gap_max=found["logit_gap_max"],
                **{f"itl_p{int(q * 100)}_ms": stats.percentile(itl, q)
                   for q in (0.5, 0.9, 0.99)},
                **{f"ttft_p{int(q * 100)}_ms": stats.percentile(ttft, q)
                   for q in (0.5, 0.95)},
                ttft_mean_ms=float(np.mean(ttft)),
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
    ``controls`` seeds: the int8 control on the same prompts and tokens
    (the gap of the token that int8 puts first)."""
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


def _listed(found: dict) -> dict:
    return dict(found, gaps=[round(float(g), 5) for g in found["gaps"]])
