#!/usr/bin/env python
"""Synthetic serving benchmark for the continuous-batching engine.

Drives paddle_tpu.serving over a staggered-arrival workload (requests
arrive on an open-loop schedule, with mixed prompt and output lengths)
and reports throughput, TTFT, and per-output-token latency, plus an
observability dump for tools/metrics_report.py.

Usage:
    python tools/serve_bench.py [--requests 16] [--max-slots 4]
        [--page-size 16] [--arrival-gap-ms 5]
        [--arrival uniform|bursty|heavytail]
        [--prompt-len 8 24] [--new-tokens 4 24]
        [--shared-prefix-len 0] [--sync-interval 1] [--spec-k 0]
        [--prefix-cache | --no-prefix-cache]
        [--layers 2 --hidden 64 --vocab 128]
        [--metrics-dir /tmp/serve_metrics] [--seed 0]

``--arrival`` shapes the open-loop schedule while keeping the mean
inter-arrival at ``--arrival-gap-ms``: ``uniform`` is the constant-gap
default, ``bursty`` drops requests in back-to-back groups (queueing
spikes), ``heavytail`` draws Pareto inter-arrivals (rare long lulls,
dense clumps).  Tail latency (p99 TTFT/TPOT) is reported per run so the
three patterns can be compared at identical offered load.

``--spec-k K`` turns on speculative decoding (prompt-lookup drafting +
one K+1-position verify step); greedy outputs are identical, only the
step count changes.

``--priority-mix hi:0.2,lo:0.8`` assigns each request a priority class
drawn from the given weights (hi/high -> 1, normal -> 0, lo/low -> -1,
or any bare int), and the report adds per-class p50/p99 TTFT/TPOT
lines.  Combine with ``--prefill-chunk N`` (chunked admission prefill)
and ``--preempt`` (priority preempt-and-swap) to exercise the overload
path; ``--overload-baseline`` re-runs the identical workload on an
FCFS engine (no chunking, no preemption) in the same invocation and
prints a per-class tail-latency comparison.

``--tenants teamA:0.5,teamB:0.3,free:0.2`` draws a tenant label per
request from the given weights, wires a usage meter into the engine,
and prints the per-tenant cost table (computed/cached/decode tokens,
KV page-seconds by tier, queue seconds, preemptions, sheds) plus the
page-seconds conservation check.  Works in both the in-process and
``--http`` modes (the HTTP path carries the tenant in the request body
and merges the per-replica tables).

``--adapters sum:0.4,cls:0.3,none:0.3`` registers one random LoRA
adapter per named class (rank ``--lora-rank``) in an AdapterStore
wired into the engine and draws an adapter per request from the
weights (the reserved names ``none``/``-`` mean dense base-model
requests); the report adds a per-adapter p50/p99 TTFT/TPOT table —
the multi-tenant adapter-serving overhead view.

``--batch-file FILE`` drip-feeds an offline JSONL batch job (one
``{"prompt": [...]}`` record per line) through the engine at the
batch priority lane while the interactive workload runs, and reports
the interactive-vs-batch goodput split plus the preemptions the
interactive traffic inflicted on the lane (in-process mode only).

``--shared-prefix-len N`` prepends one common N-token prefix to every
prompt (the system-prompt / few-shot pattern prefix caching targets);
with ``--prefix-cache`` (default on) the report adds the prefix-cache
page hit rate, pages saved, and host-sync counts next to TTFT/TPOT.

``--http [--replicas N]`` drives the real serving stack instead of the
in-process engine loop: N HTTP replicas (each its own engine + worker
thread) behind a prefix-affinity Router, with streaming clients over
localhost.  TTFT/TPOT then include HTTP + SSE overhead, and the report
adds per-replica latency percentiles (grouped by which replica served
each stream), request counts, and the aggregate prefix hit rate.

``--trace out.json`` writes a chrome://tracing-loadable timeline of the
run: request/queue/prefill/decode spans and gauge counters, merged with
the native host profile when one is active (profiler.export_host_trace).

``--profile out.folded`` samples a phase-attributed host profile of the
run (stacks split by the engine's published phase: prefill /
prefill_chunk / decode / verify / host_sync / idle) and writes folded
stacks — flamegraph.pl / speedscope input, rendered by
``tools/profile_report.py``.

``--explain-tail`` wires a per-request lifecycle log
(observability.requestlog.RequestLog) into the engine and prints the
critical-path attribution of the p99-TTFT cohort ("p99 TTFT is 71%
queue, 18% chunk_gap, ...") plus the overall per-cause totals and the
conservation check — the numbers match what ``tools/request_report.py``
renders from the run's ``exemplars.json`` dump (in-process mode only).

``--record OUT.json`` writes a machine-readable bench artifact after
the run: tok/s, TTFT/TPOT p50/p95/p99, the scenario knobs, and (with
``--explain-tail``) the tail attribution — the input for regression
dashboards and A/B diffs.

The model is a randomly initialized tiny llama (this benchmarks the
ENGINE — scheduling, paging, dispatch — not the matmuls); sizes are
flags so the same harness scales up on real hardware.
"""
from __future__ import annotations

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _percentile(vals, q):
    if not vals:
        return float("nan")
    vals = sorted(vals)
    idx = min(len(vals) - 1, int(round(q * (len(vals) - 1))))
    return vals[idx]


# priority-mix class names <-> engine priority ints (mirrors the
# server's low/normal/high vocabulary; bare ints pass through)
_MIX_NAMES = {"hi": 1, "high": 1, "normal": 0, "mid": 0,
              "lo": -1, "low": -1}
_CLASS_NAMES = {1: "high", 0: "normal", -1: "low"}


def _parse_priority_mix(spec):
    """``"hi:0.2,lo:0.8"`` -> ``[(priority, weight), ...]`` with the
    weights normalised to sum to 1.  Empty spec -> None."""
    if not spec:
        return None
    out = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        name = name.strip().lower()
        pri = _MIX_NAMES.get(name)
        if pri is None:
            pri = int(name)
        out.append((pri, float(w) if w else 1.0))
    if not out:
        return None
    total = sum(w for _, w in out)
    if total <= 0:
        raise ValueError(f"--priority-mix {spec!r}: weights must be > 0")
    return [(p, w / total) for p, w in out]


def _assign_priorities(mix, rng, n):
    """One priority per request, drawn from the mix weights with the
    bench rng (same seed -> same assignment).  No mix -> all zeros."""
    if not mix:
        return [0] * n
    out = []
    for _ in range(n):
        u = rng.random()
        acc = 0.0
        pri = mix[-1][0]
        for p, w in mix:
            acc += w
            if u < acc:
                pri = p
                break
        out.append(pri)
    return out


def _class_label(pri):
    return _CLASS_NAMES.get(pri, str(pri))


def _parse_tenant_mix(spec):
    """``"teamA:0.5,teamB:0.5"`` -> ``[(name, weight), ...]`` with the
    weights normalised to sum to 1.  Empty spec -> None."""
    if not spec:
        return None
    out = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        name = name.strip()
        if not name:
            continue
        out.append((name, float(w) if w else 1.0))
    if not out:
        return None
    total = sum(w for _, w in out)
    if total <= 0:
        raise ValueError(f"--tenants {spec!r}: weights must be > 0")
    return [(n, w / total) for n, w in out]


def _assign_tenants(mix, rng, n):
    """One tenant label per request, drawn from the mix weights with
    the bench rng (same seed -> same assignment).  No mix -> None."""
    if not mix:
        return [None] * n
    out = []
    for _ in range(n):
        u = rng.random()
        acc = 0.0
        name = mix[-1][0]
        for t, w in mix:
            acc += w
            if u < acc:
                name = t
                break
        out.append(name)
    return out


def _print_tenant_table(usage):
    """Per-tenant cost table from a UsageMeter snapshot (or a
    merge_usage result — conservation is then absent and skipped)."""
    tenants = usage.get("tenants") or {}
    if not tenants:
        return
    print("  tenant cost table (page-seconds ledger):")
    print(f"    {'tenant':<12} {'reqs':>5} {'good':>5} {'computed':>9} "
          f"{'cached':>7} {'decode':>7} {'page-s':>9} {'host-s':>8} "
          f"{'queue-s':>8} {'preempt':>7} {'shed':>5}")
    for name in sorted(tenants):
        row = tenants[name]
        print(f"    {name:<12} {row['requests']:>5} "
              f"{row['goodput_requests']:>5} "
              f"{row['prefill_computed_tokens']:>9} "
              f"{row['prefill_cached_tokens']:>7} "
              f"{row['decode_tokens']:>7} "
              f"{row['page_seconds']:>9.4f} "
              f"{row['host_page_seconds']:>8.4f} "
              f"{row['queue_seconds']:>8.4f} "
              f"{row['preemptions']:>7} {row['shed']:>5}")
    cons = usage.get("conservation")
    if cons:
        print(f"    conservation         device_delta="
              f"{cons['device_delta']} host_delta={cons['host_delta']} "
              f"(both must be 0)")


def _per_class_latency(samples):
    """``samples``: iterable of (priority, ttft_or_None, tpot_or_None)
    -> ``{label: {"ttft_s": [...], "tpot_s": [...], "requests": n}}``."""
    out = {}
    for pri, ttft, tpot in samples:
        d = out.setdefault(_class_label(pri),
                           {"ttft_s": [], "tpot_s": [], "requests": 0})
        d["requests"] += 1
        if ttft is not None:
            d["ttft_s"].append(ttft)
        if tpot is not None:
            d["tpot_s"].append(tpot)
    return out


def _print_per_class(per_class, kind="class"):
    for label in sorted(per_class):
        d = per_class[label]
        line = f"  {kind} {label:<8} n={d['requests']}"
        if d["ttft_s"]:
            line += (f"  TTFT p50/p99 "
                     f"{_percentile(d['ttft_s'], 0.5) * 1e3:.2f}/"
                     f"{_percentile(d['ttft_s'], 0.99) * 1e3:.2f} ms")
        if d["tpot_s"]:
            line += (f"  TPOT p50/p99 "
                     f"{_percentile(d['tpot_s'], 0.5) * 1e3:.2f}/"
                     f"{_percentile(d['tpot_s'], 0.99) * 1e3:.2f} ms")
        print(line)


def _per_replica_latency(results):
    """Group --http results by the replica that served each stream:
    ``{replica_name: (ttfts, tpots, n_requests)}``."""
    out: dict = {}
    for r in results:
        if not r or r[4] is None:
            continue
        sent, first, last, n_toks, replica = r
        ttfts, tpots, n = out.setdefault(replica, ([], [], 0))
        out[replica] = (ttfts, tpots, n + 1)
        if first is not None:
            ttfts.append(first - sent)
        if n_toks > 1:
            tpots.append((last - first) / (n_toks - 1))
    return out


def run_bench(args):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import GenerationConfig, create_engine

    rng = np.random.default_rng(args.seed)
    paddle.seed(args.seed)
    cfg = llama_tiny(num_hidden_layers=args.layers, hidden_size=args.hidden,
                     intermediate_size=2 * args.hidden,
                     vocab_size=args.vocab,
                     num_attention_heads=args.heads,
                     num_key_value_heads=args.kv_heads,
                     max_position_embeddings=args.max_model_len)
    model = LlamaForCausalLM(cfg)
    model.eval()

    tenant_mix = _parse_tenant_mix(getattr(args, "tenants", ""))
    usage_meter = None
    if tenant_mix:
        from paddle_tpu.observability.usage import UsageMeter
        usage_meter = UsageMeter()

    # --adapters sum:0.4,none:0.6: random rank-r adapters registered in
    # an AdapterStore; the reserved names none/- mean dense requests
    adapter_mix = _parse_tenant_mix(getattr(args, "adapters", ""))
    lora_store = None
    if adapter_mix:
        from paddle_tpu.serving.lora import AdapterStore, random_adapter
        names = [n for n, _ in adapter_mix if n not in ("none", "-")]
        lora_store = AdapterStore(cfg, capacity=max(1, len(names)),
                                  rank=args.lora_rank)
        for j, nm in enumerate(names):
            lora_store.register(
                nm, random_adapter(cfg, args.lora_rank,
                                   seed=args.seed + j))

    # --explain-tail: per-request lifecycle timelines + critical-path
    # attribution (requestlog=None keeps the zero-overhead-off default)
    requestlog = None
    if getattr(args, "explain_tail", False):
        from paddle_tpu.observability.requestlog import RequestLog
        requestlog = RequestLog(max_requests=max(512, args.requests))

    engine = create_engine(model, max_slots=args.max_slots,
                           page_size=args.page_size,
                           num_pages=args.num_pages,
                           max_model_len=args.max_model_len,
                           enable_prefix_cache=args.prefix_cache,
                           sync_interval=args.sync_interval,
                           mesh=args.mesh, spec_k=args.spec_k,
                           prefill_chunk=getattr(args, "prefill_chunk",
                                                 None),
                           preempt=getattr(args, "preempt", None),
                           usage=usage_meter, lora=lora_store,
                           quant=(None if getattr(args, "quant", "none")
                                  == "none" else args.quant),
                           kv_quant=getattr(args, "kv_quant", None),
                           requestlog=requestlog)

    # --batch-file FILE: an offline JSONL job rides the batch priority
    # lane, drip-fed between interactive admissions
    batch_job = None
    if getattr(args, "batch_file", ""):
        from paddle_tpu.serving.lora import BatchJob
        batch_job = BatchJob.from_jsonl(args.batch_file)

    # --chaos SEED: seed a probabilistic fault plan (poisoned steps,
    # synthetic OOM, slow steps) and drive through the self-healing
    # supervisor — the run then reports availability alongside latency
    chaos = getattr(args, "chaos", None)
    supervisor = None
    if chaos is not None:
        from paddle_tpu.serving import EngineSupervisor, FaultPlan
        plan = FaultPlan(seed=int(chaos))
        plan.add("step_raise", p=0.01)
        plan.add("page_alloc", p=0.01)
        plan.add("slow_step", p=0.02, seconds=0.002)
        plan.add("spill_fail", p=0.05)
        engine.faults = plan
        engine.blocks.faults = plan
        supervisor = EngineSupervisor(engine)
    step = engine.step if supervisor is None else supervisor.step

    # --profile out.folded: continuous phase-attributed sampling of
    # the bench (this driver thread runs the engine, so its stacks
    # split by engine.current_phase); folded stacks land at the path
    profiler = None
    if getattr(args, "profile", None):
        bench_ident = threading.get_ident()
        profiler = obs.SamplingProfiler(
            0.005, phases=lambda: {bench_ident: engine.current_phase})
        profiler.start_sampling()

    workload = _build_workload(args, rng, np)
    mix = _parse_priority_mix(getattr(args, "priority_mix", ""))
    priorities = _assign_priorities(mix, rng, len(workload))
    tenants = _assign_tenants(tenant_mix, rng, len(workload))
    adapters = [None if a in (None, "none", "-") else a
                for a in _assign_tenants(adapter_mix, rng,
                                         len(workload))]

    t0 = time.monotonic()
    pending = list(enumerate(workload))
    reqs = []
    # open-loop driver: submit what has "arrived", run one iteration,
    # repeat — admissions interleave with decode exactly as in a server
    while (pending or engine.scheduler.has_work()
           or (batch_job is not None and not batch_job.done)):
        if batch_job is not None and not batch_job.done:
            batch_job.pump(engine.submit)
        now = time.monotonic() - t0
        while pending and pending[0][1][0] <= now:
            i, (_, prompt, n_new) = pending.pop(0)
            reqs.append(engine.submit(
                prompt, GenerationConfig(max_new_tokens=n_new),
                priority=priorities[i], tenant=tenants[i],
                adapter=adapters[i]))
        if not step() and pending:
            time.sleep(min(1e-3, max(0.0, pending[0][1][0] - now)))
    wall = time.monotonic() - t0

    toks = sum(r.num_generated for r in reqs)
    ttfts = [r.first_token_at - r.arrival_time for r in reqs
             if r.first_token_at is not None]
    tpots = []
    for r in reqs:
        if r.num_generated > 1:
            tpots.append((r.last_token_at - r.first_token_at)
                         / (r.num_generated - 1))
    stats = engine.stats()

    print(f"serve_bench: {len(reqs)} requests, {toks} tokens, "
          f"{wall:.3f}s wall ({args.arrival} arrivals)")
    print(f"  throughput      {toks / wall:10.1f} tok/s")
    print(f"  TTFT   mean/p50/p95/p99  {np.mean(ttfts) * 1e3:8.2f} / "
          f"{_percentile(ttfts, 0.5) * 1e3:.2f} / "
          f"{_percentile(ttfts, 0.95) * 1e3:.2f} / "
          f"{_percentile(ttfts, 0.99) * 1e3:.2f} ms")
    if tpots:
        print(f"  TPOT   mean/p50/p95/p99  {np.mean(tpots) * 1e3:8.2f} / "
              f"{_percentile(tpots, 0.5) * 1e3:.2f} / "
              f"{_percentile(tpots, 0.95) * 1e3:.2f} / "
              f"{_percentile(tpots, 0.99) * 1e3:.2f} ms")
    print(f"  decode-step traces   {stats['decode_traces']} "
          f"(continuous batching wants exactly 1)")
    print(f"  prefill buckets      {stats['prefill_buckets']}"
          + (f" cached={stats['cached_prefill_buckets']}"
             if stats['cached_prefill_buckets'] else ""))
    lookups = stats["prefix_hits"] + stats["prefix_misses"]
    hit_rate = stats["prefix_hits"] / lookups if lookups else 0.0
    if args.prefix_cache:
        print(f"  prefix cache         hit rate {hit_rate * 100:.1f}% "
              f"({stats['prefix_hits']}/{lookups} page lookups), "
              f"{stats['prefix_hits']} pages saved, "
              f"{stats['cached_tokens']} prompt tokens skipped, "
              f"{stats['cow_copies']} CoW copies, "
              f"{stats['prefix_evictions']} evictions")
    print(f"  host syncs           {stats['host_syncs']} ring "
          f"(~1/{args.sync_interval} per token) + "
          f"{stats['logit_fetches']} logits fetches")
    if args.spec_k:
        steps = stats["decode_steps"]
        print(f"  spec decode          k={args.spec_k}: "
              f"{stats['spec_accepted']}/{stats['spec_proposed']} drafts "
              f"accepted ({stats['spec_acceptance_rate'] * 100:.1f}%), "
              f"{stats['spec_verify_steps']} verify steps, "
              f"{toks / steps if steps else 0.0:.2f} tokens/decode-step")

    def _req_samples():
        for r in reqs:
            ttft = (r.first_token_at - r.arrival_time
                    if r.first_token_at is not None else None)
            tpot = ((r.last_token_at - r.first_token_at)
                    / (r.num_generated - 1)
                    if r.num_generated > 1 else None)
            yield getattr(r, "priority", 0), ttft, tpot

    per_class = _per_class_latency(_req_samples())
    if mix:
        _print_per_class(per_class)
    if (stats.get("prefill_chunk") or stats.get("preemptions")
            or stats.get("spill_aborts")):
        print(f"  scheduling           chunk={stats['prefill_chunk']}: "
              f"{stats['prefill_chunks']} prefill chunks "
              f"(max decode gap {stats['max_prefill_gap']} tok), "
              f"{stats['preemptions']} preemptions "
              f"({stats['spill_aborts']} aborted), "
              f"{stats['spilled_pages']}/{stats['restored_pages']} pages "
              f"spilled/restored ({stats['spill_bytes']} bytes)")

    per_adapter = {}
    if adapter_mix:
        per_adapter = _per_class_latency(
            (getattr(r, "adapter", None) or "(dense)", ttft, tpot)
            for (_, ttft, tpot), r in zip(_req_samples(), reqs))
        _print_per_class(per_adapter, kind="adapter")
        print(f"  adapter bank         "
              f"{engine.lora_snapshot()['bank_bytes_device']} device "
              f"bytes, {lora_store.loads} loads, "
              f"{lora_store.evictions} evictions")

    batch_out = {}
    if batch_job is not None:
        prog = batch_job.progress()
        print(f"  batch lane           job {prog['id']}: "
              f"{prog['completed']}/{prog['total']} rows "
              f"({prog['failed']} failed), {prog['output_tokens']} "
              f"tokens -> {prog['output_path']}")
        print(f"  goodput split        interactive {toks} tok "
              f"({toks / wall:.1f} tok/s) vs batch "
              f"{prog['output_tokens']} tok "
              f"({prog['output_tokens'] / wall:.1f} tok/s), "
              f"{stats['preemptions']} preemptions")
        batch_out = {"batch": prog}

    usage_out = {}
    if usage_meter is not None:
        snap = usage_meter.snapshot()
        _print_tenant_table(snap)
        usage_out = {"usage": snap}

    tail_out = {}
    if requestlog is not None:
        tail_out = {"tail": _explain_tail(requestlog, reqs, ttfts)}

    chaos_out = {}
    if supervisor is not None:
        ok = sum(1 for r in reqs if r.finish_reason in ("length", "eos"))
        availability = ok / len(reqs) if reqs else 1.0
        leak = engine.blocks.pool_accounting()["leak"]
        print(f"  chaos (seed {chaos})  availability "
              f"{availability * 100:.1f}% ({ok}/{len(reqs)}), "
              f"{engine.recoveries} recoveries, "
              f"{engine.quarantines} quarantines, "
              f"faults {dict(engine.faults.injected)}, leak {leak}")
        print(f"  p99 under faults     TTFT "
              f"{_percentile(ttfts, 0.99) * 1e3:.2f} ms, TPOT "
              f"{_percentile(tpots, 0.99) * 1e3:.2f} ms")
        chaos_out = {"chaos_seed": int(chaos),
                     "availability": availability,
                     "recoveries": engine.recoveries,
                     "quarantines": engine.quarantines,
                     "faults_injected": dict(engine.faults.injected),
                     "leaked_pages": leak,
                     "spill_aborts": engine.spill_aborts}

    profile_out = {}
    if profiler is not None:
        profiler.stop()
        with open(args.profile, "w") as f:
            f.write(profiler.folded() + "\n")
        by_phase = profiler.by_phase()
        top = ", ".join(f"{k}={v}" for k, v in
                        list(by_phase.items())[:4])
        print(f"  profile              {profiler.samples} samples -> "
              f"{args.profile} (render: python tools/profile_report.py "
              f"{args.profile}; phases: {top})")
        profile_out = {"profile_path": args.profile,
                       "profile_samples": profiler.samples,
                       "profile_by_phase": by_phase}

    if args.metrics_dir:
        out = obs.dump(args.metrics_dir)
        print(f"  metrics dump         {out} "
              f"(render: python tools/metrics_report.py {out})")
    _export_trace(args)
    return {**profile_out,
            "requests": len(reqs), "tokens": toks, "wall_s": wall,
            "arrival": args.arrival, "spec_k": args.spec_k,
            "throughput": toks / wall, "ttft_s": ttfts, "tpot_s": tpots,
            "decode_traces": stats["decode_traces"],
            "prefix_hit_rate": hit_rate,
            "pages_saved": stats["prefix_hits"],
            "host_syncs": stats["host_syncs"],
            "logit_fetches": stats["logit_fetches"],
            "per_class": per_class, "per_adapter": per_adapter,
            "prefill_chunks": stats["prefill_chunks"],
            "max_prefill_gap": stats["max_prefill_gap"],
            "preemptions": stats["preemptions"],
            "spill_aborts": stats["spill_aborts"],
            "spilled_pages": stats["spilled_pages"],
            "restored_pages": stats["restored_pages"],
            **batch_out, **usage_out, **tail_out, **chaos_out}


def _explain_tail(requestlog, reqs, ttfts):
    """--explain-tail report: critical-path attribution of the
    p99-TTFT cohort (every request whose TTFT reached the p99
    estimate) plus the run-wide per-cause totals and the conservation
    check.  Seconds are rounded to 6 decimals — identical to what the
    run's exemplars.json dump carries, so tools/request_report.py
    renders the same numbers."""
    snap = requestlog.snapshot()
    totals = snap["attribution_totals_s"]

    thresh = _percentile(ttfts, 0.99) if ttfts else float("inf")
    cohort = []
    for r in reqs:
        if r.first_token_at is None:
            continue
        if r.first_token_at - r.arrival_time >= thresh:
            tl = requestlog.get(r.id)
            if tl is not None:
                cohort.append(tl)
    cohort_s: dict = {}
    for tl in cohort:
        for cause, v in tl.attribution().items():
            cohort_s[cause] = cohort_s.get(cause, 0.0) + v
    cohort_s = {c: round(v, 6) for c, v in cohort_s.items()}

    def shares(by_cause):
        spent = sum(by_cause.values())
        if spent <= 0:
            return "no attributed seconds"
        top = sorted(by_cause.items(), key=lambda kv: -kv[1])
        return ", ".join(f"{100.0 * v / spent:.0f}% {c}"
                         for c, v in top if v > 0)

    if cohort_s:
        print(f"  tail attribution     p99 TTFT cohort "
              f"({len(cohort)} req): {shares(cohort_s)}")
    print(f"  latency attribution  {shares(totals)} "
          f"over {snap['finished']} finished requests")
    print(f"  conservation         max |sum(buckets) - e2e| = "
          f"{snap['conservation_max_delta']} (must be 0)")
    return {"attribution_totals_s": totals,
            "p99_ttft_cohort": {"requests": len(cohort),
                                "attribution_s": cohort_s},
            "finished": snap["finished"],
            "conservation_max_delta": snap["conservation_max_delta"],
            "exemplars": snap["exemplars"]}


# scenario knobs --record captures alongside the results — enough to
# reproduce the run (with --seed) and to group artifacts in dashboards
_RECORD_KNOBS = (
    "requests", "max_slots", "page_size", "num_pages", "arrival_gap_ms",
    "arrival", "prompt_len", "new_tokens", "shared_prefix_len",
    "sync_interval", "spec_k", "prefix_cache", "prefill_chunk",
    "preempt", "priority_mix", "tenants", "adapters", "lora_rank",
    "quant", "kv_quant", "http", "replicas", "layers", "hidden",
    "vocab", "heads", "kv_heads", "max_model_len", "seed")


def _write_record(args, res):
    """--record OUT.json: machine-readable bench artifact (throughput,
    latency percentiles, scenario knobs, and — with --explain-tail —
    the p99-cohort attribution)."""
    import json

    def pcts(vals):
        if not vals:
            return None
        return {"p50": _percentile(vals, 0.5),
                "p95": _percentile(vals, 0.95),
                "p99": _percentile(vals, 0.99),
                "mean": sum(vals) / len(vals), "n": len(vals)}

    doc = {"tool": "serve_bench",
           "scenario": {k: (list(v) if isinstance(v, tuple) else v)
                        for k in _RECORD_KNOBS
                        for v in [getattr(args, k, None)]},
           "requests": res.get("requests"),
           "tokens": res.get("tokens"),
           "wall_s": res.get("wall_s"),
           "tokens_per_s": res.get("throughput"),
           "ttft_s": pcts(res.get("ttft_s") or []),
           "tpot_s": pcts(res.get("tpot_s") or []),
           "tail": res.get("tail")}
    with open(args.record, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"  record               {args.record}")


def run_overload_compare(args):
    """--overload-baseline: run the configured engine, then the same
    seeded workload (identical arrivals, prompts, priorities) on an
    FCFS engine with chunking and preemption off, and print the
    per-class tail-latency comparison.  Returns (configured, fcfs)."""
    import copy

    res = run_bench(args)
    base_args = copy.copy(args)
    base_args.prefill_chunk = 0
    base_args.preempt = False
    base_args.profile = ""      # the configured run owns the profile
    print("\n--- FCFS baseline: same workload, prefill-chunk 0, "
          "no preemption ---")
    ref = run_bench(base_args)

    print("\noverload comparison (configured vs FCFS baseline):")
    labels = sorted(set(res.get("per_class", {}))
                    | set(ref.get("per_class", {})))
    rows = [(f"class {lab}",
             res["per_class"].get(lab, {}),
             ref["per_class"].get(lab, {})) for lab in labels]
    rows.append(("overall",
                 {"ttft_s": res["ttft_s"], "tpot_s": res["tpot_s"]},
                 {"ttft_s": ref["ttft_s"], "tpot_s": ref["tpot_s"]}))
    for name, a, b in rows:
        for metric in ("ttft_s", "tpot_s"):
            va, vb = a.get(metric, []), b.get(metric, [])
            if not va or not vb:
                continue
            pa = _percentile(va, 0.99) * 1e3
            pb = _percentile(vb, 0.99) * 1e3
            tag = metric[:4].upper()
            print(f"  {name:<14} p99 {tag} {pa:8.2f} ms vs "
                  f"{pb:8.2f} ms FCFS "
                  f"({'-' if pa <= pb else '+'}"
                  f"{abs(pa - pb) / pb * 100 if pb else 0.0:.1f}%)")
    return res, ref


def _export_trace(args):
    if not getattr(args, "trace", None):
        return
    from paddle_tpu import profiler
    if profiler.export_host_trace(args.trace):
        print(f"  chrome trace         {args.trace} "
              f"(load in chrome://tracing or https://ui.perfetto.dev)")
    else:
        print(f"  chrome trace         FAILED to write {args.trace}")


def _arrival_times(args, rng):
    """Arrival offsets (seconds) for each request.  Every pattern keeps
    the mean inter-arrival at ``--arrival-gap-ms`` so runs differ only
    in burstiness, not offered load."""
    gap = args.arrival_gap_ms / 1e3
    n = args.requests
    if args.arrival == "uniform":
        return [i * gap for i in range(n)]
    if args.arrival == "bursty":
        # back-to-back groups of 4, bursts spaced to preserve the rate
        burst = 4
        return [(i // burst) * burst * gap for i in range(n)]
    # heavytail: Pareto (alpha=1.5) inter-arrivals scaled to mean gap —
    # E[pareto+1] = alpha/(alpha-1), so multiply by (alpha-1)/alpha
    alpha = 1.5
    gaps = (rng.pareto(alpha, n) + 1.0) * gap * (alpha - 1.0) / alpha
    t, out = 0.0, []
    for g in gaps:
        out.append(t)
        t += float(g)
    return out


def _build_workload(args, rng, np):
    plo, phi = args.prompt_len
    nlo, nhi = args.new_tokens
    shared = rng.integers(0, args.vocab,
                          args.shared_prefix_len).astype(np.int32)
    arrivals = _arrival_times(args, rng)
    workload = []
    for i in range(args.requests):
        suffix = rng.integers(0, args.vocab,
                              int(rng.integers(plo, phi + 1))).astype(
                                  np.int32)
        workload.append((
            arrivals[i],
            np.concatenate([shared, suffix]) if shared.size else suffix,
            int(rng.integers(nlo, nhi + 1))))
    return workload


def run_http_bench(args):
    """End-to-end benchmark over the HTTP serving stack: N replica
    servers behind a Router, streaming SSE clients over localhost."""
    import threading

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import Router, serve

    rng = np.random.default_rng(args.seed)
    paddle.seed(args.seed)
    cfg = llama_tiny(num_hidden_layers=args.layers, hidden_size=args.hidden,
                     intermediate_size=2 * args.hidden,
                     vocab_size=args.vocab,
                     num_attention_heads=args.heads,
                     num_key_value_heads=args.kv_heads,
                     max_position_embeddings=args.max_model_len)
    model = LlamaForCausalLM(cfg)
    model.eval()

    tenant_mix = _parse_tenant_mix(getattr(args, "tenants", ""))

    def _replica_kw():
        if not tenant_mix:
            return {}
        from paddle_tpu.observability.usage import UsageMeter
        return {"usage": UsageMeter()}      # one meter per replica

    # each replica announces itself via the SSE "model" field, so the
    # client side can attribute every stream to the replica that ran it
    servers = [serve(model, max_slots=args.max_slots,
                     page_size=args.page_size,
                     num_pages=args.num_pages,
                     max_model_len=args.max_model_len,
                     enable_prefix_cache=args.prefix_cache,
                     sync_interval=args.sync_interval,
                     spec_k=args.spec_k,
                     quant=(None if args.quant == "none"
                            else args.quant),
                     kv_quant=args.kv_quant,
                     model_name=f"replica-{i}", **_replica_kw())
               for i in range(args.replicas)]
    router = Router([s.address for s in servers],
                    page_size=args.page_size)
    workload = _build_workload(args, rng, np)
    mix = _parse_priority_mix(getattr(args, "priority_mix", ""))
    priorities = _assign_priorities(mix, rng, len(workload))
    tenants = _assign_tenants(tenant_mix, rng, len(workload))

    results = [None] * len(workload)
    rejected = [False] * len(workload)
    t0 = time.monotonic()

    def drive(i, at, prompt, n_new):
        time.sleep(max(0.0, at - (time.monotonic() - t0)))
        sent = time.monotonic()
        first = last = None
        n_toks = 0
        replica = None
        try:
            for ev in router.completion([int(t) for t in prompt],
                                        max_tokens=n_new, stream=True,
                                        priority=priorities[i],
                                        tenant=tenants[i]):
                replica = ev.get("model", replica)
                got = ev["choices"][0]["token_ids"]
                if got:
                    n_toks += len(got)
                    last = time.monotonic()
                    if first is None:
                        first = last
        except Exception:
            # shed (429) or replica failure — counted, not fatal
            rejected[i] = True
            return
        results[i] = (sent, first, last, n_toks, replica)

    threads = [threading.Thread(target=drive, args=(i, at, p, n),
                                daemon=True)
               for i, (at, p, n) in enumerate(workload)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0

    toks = sum(r[3] for r in results if r)
    ttfts = [r[1] - r[0] for r in results if r and r[1] is not None]
    tpots = [(r[2] - r[1]) / (r[3] - 1) for r in results
             if r and r[3] > 1]

    rstats = router.stats()
    hits = misses = 0
    for srv in servers:
        st = srv.worker.stats()
        hits += st["prefix_hits"]
        misses += st["prefix_misses"]
    lookups = hits + misses
    hit_rate = hits / lookups if lookups else 0.0

    print(f"serve_bench --http: {len(results)} requests over "
          f"{args.replicas} replica(s), {toks} tokens, {wall:.3f}s wall "
          f"({args.arrival} arrivals)")
    print(f"  throughput      {toks / wall:10.1f} tok/s")
    if ttfts:
        print(f"  TTFT   mean/p50/p95/p99  {np.mean(ttfts) * 1e3:8.2f} / "
              f"{_percentile(ttfts, 0.5) * 1e3:.2f} / "
              f"{_percentile(ttfts, 0.95) * 1e3:.2f} / "
              f"{_percentile(ttfts, 0.99) * 1e3:.2f} ms")
    if tpots:
        print(f"  TPOT   mean/p50/p95/p99  {np.mean(tpots) * 1e3:8.2f} / "
              f"{_percentile(tpots, 0.5) * 1e3:.2f} / "
              f"{_percentile(tpots, 0.95) * 1e3:.2f} / "
              f"{_percentile(tpots, 0.99) * 1e3:.2f} ms")
    per_class = _per_class_latency(
        (priorities[i],
         r[1] - r[0] if r[1] is not None else None,
         (r[2] - r[1]) / (r[3] - 1) if r[3] > 1 else None)
        for i, r in enumerate(results) if r)
    if mix:
        _print_per_class(per_class)
    n_rejected = sum(rejected)
    if n_rejected:
        print(f"  rejected             {n_rejected} requests "
              f"(shed or replica failure)")
    per_replica = _per_replica_latency(results)
    for name in sorted(per_replica):
        r_ttft, r_tpot, n = per_replica[name]

        def pcts(vals):
            return (f"{_percentile(vals, 0.5) * 1e3:.2f}/"
                    f"{_percentile(vals, 0.95) * 1e3:.2f}/"
                    f"{_percentile(vals, 0.99) * 1e3:.2f}")

        line = f"  {name:<12} n={n}"
        if r_ttft:
            line += f"  TTFT p50/p95/p99 {pcts(r_ttft)} ms"
        if r_tpot:
            line += f"  TPOT p50/p95/p99 {pcts(r_tpot)} ms"
        print(line)
    for rep in rstats["replicas"]:
        print(f"  replica {rep['address']}  up={rep['up']} "
              f"fails={rep['fails']} inflight={rep['inflight']}")
    if args.prefix_cache:
        print(f"  prefix cache         hit rate {hit_rate * 100:.1f}% "
              f"({hits}/{lookups} page lookups across replicas)")

    usage_out = {}
    if tenant_mix:
        from paddle_tpu.observability.usage import merge_usage
        merged = merge_usage(srv.worker.engine.usage.snapshot()
                             for srv in servers)
        _print_tenant_table(merged)
        usage_out = {"usage": merged}

    router.stop()
    for srv in servers:
        srv.stop(drain_timeout=5.0)
    if args.metrics_dir:
        out = obs.dump(args.metrics_dir)
        print(f"  metrics dump         {out} "
              f"(render: python tools/metrics_report.py {out})")
    _export_trace(args)
    return {"requests": len(results), "tokens": toks, "wall_s": wall,
            "arrival": args.arrival, "spec_k": args.spec_k,
            "throughput": toks / wall, "ttft_s": ttfts, "tpot_s": tpots,
            "prefix_hit_rate": hit_rate, "router": rstats,
            "per_class": per_class, "rejected": n_rejected,
            "per_replica": {k: {"ttft_s": v[0], "tpot_s": v[1],
                                "requests": v[2]}
                            for k, v in per_replica.items()},
            **usage_out}


def _build_parser() -> argparse.ArgumentParser:
    """THE bench argument parser — the single source of defaults.
    ``bench_args()`` derives embedder/test Namespaces from it, so a
    newly added flag can never be missing from a hand-built one."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool size (default: full residency)")
    ap.add_argument("--arrival-gap-ms", type=float, default=5.0)
    ap.add_argument("--arrival", default="uniform",
                    choices=("uniform", "bursty", "heavytail"),
                    help="arrival pattern at the same mean rate: "
                         "constant gap, back-to-back groups of 4, or "
                         "Pareto inter-arrivals")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(8, 24),
                    metavar=("LO", "HI"))
    ap.add_argument("--new-tokens", type=int, nargs=2, default=(4, 24),
                    metavar=("LO", "HI"))
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="common prompt prefix prepended to every "
                         "request (exercises the prefix cache)")
    ap.add_argument("--sync-interval", type=int, default=1,
                    help="greedy decode steps per host sync")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding draft length (0 = off); "
                         "greedy outputs are identical either way")
    ap.add_argument("--prefix-cache",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="automatic prefix caching over the KV pool")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--max-model-len", type=int, default=128)
    ap.add_argument("--http", action="store_true",
                    help="drive the real HTTP stack (replica servers + "
                         "router + SSE clients) instead of the "
                         "in-process engine loop")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica server count for --http")
    ap.add_argument("--metrics-dir", default="")
    ap.add_argument("--trace", default="",
                    help="write a chrome://tracing JSON of the run's "
                         "request/prefill/decode spans to this path")
    ap.add_argument("--mesh", default=None,
                    help="tensor-parallel mesh size for the in-process "
                         "engine (e.g. 4 or tp=4; default FLAGS_serving_"
                         "mesh_tp).  CPU: export XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N first.  tp>1 "
                         "needs head counts divisible by tp — pass "
                         "--heads/--kv-heads accordingly")
    ap.add_argument("--heads", type=int, default=4,
                    help="attention heads of the bench model")
    ap.add_argument("--kv-heads", type=int, default=2,
                    help="KV heads of the bench model")
    ap.add_argument("--priority-mix", default="", metavar="SPEC",
                    help="per-request priority classes drawn from "
                         "weighted spec, e.g. hi:0.2,lo:0.8 "
                         "(hi/high=1, normal=0, lo/low=-1, or bare "
                         "ints); adds per-class p50/p99 TTFT/TPOT")
    ap.add_argument("--tenants", default="", metavar="SPEC",
                    help="per-request tenant labels drawn from a "
                         "weighted spec, e.g. teamA:0.5,teamB:0.3,"
                         "free:0.2; wires a usage meter into the "
                         "engine and prints the per-tenant cost table "
                         "(page-seconds ledger) with the conservation "
                         "check")
    ap.add_argument("--adapters", default="", metavar="SPEC",
                    help="per-request LoRA adapters drawn from a "
                         "weighted spec, e.g. sum:0.4,cls:0.3,none:0.3 "
                         "(none/- = dense); registers one random "
                         "rank=--lora-rank adapter per name and adds a "
                         "per-adapter p50/p99 TTFT/TPOT table "
                         "(in-process mode only)")
    ap.add_argument("--lora-rank", type=int, default=4,
                    help="rank of the random adapters --adapters "
                         "registers")
    ap.add_argument("--batch-file", default="", metavar="FILE",
                    help="drip-feed this JSONL file (one "
                         "{'prompt': [...]} record per line) as an "
                         "offline batch job on the lowest-priority "
                         "lane while the interactive workload runs; "
                         "reports the interactive-vs-batch goodput "
                         "split (in-process mode only)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split admission prefill into chunks of this "
                         "many tokens, interleaved with decode steps "
                         "(0 = single-shot; default FLAGS_serving_"
                         "prefill_chunk)")
    ap.add_argument("--preempt",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="priority preempt-and-swap: spill a lower-"
                         "priority resident's KV to host RAM to admit "
                         "a higher class (default FLAGS_serving_"
                         "preempt)")
    ap.add_argument("--quant", choices=("none", "int8", "int4"),
                    default="none",
                    help="weight-only quantized serving: convert the "
                         "checkpoint to int8 or int4 QuantizedWeight "
                         "shards at engine construction (embeddings/"
                         "norms/lm_head stay dense; default "
                         "FLAGS_serving_quant)")
    ap.add_argument("--kv-quant",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="int8 KV pages: pools store int8 with per-"
                         "(page-row, head) f32 scales — quantize on "
                         "write, dequant fused into the attention "
                         "gather, spill/restore move the quantized "
                         "bytes (default FLAGS_serving_kv_quant)")
    ap.add_argument("--overload-baseline", action="store_true",
                    help="after the configured run, re-run the "
                         "identical workload on an FCFS engine "
                         "(prefill-chunk 0, no preemption) and print "
                         "a per-class tail-latency comparison "
                         "(in-process mode only)")
    ap.add_argument("--explain-tail", action="store_true",
                    help="wire a per-request lifecycle log into the "
                         "engine and print the critical-path "
                         "attribution of the p99-TTFT cohort plus the "
                         "run-wide per-cause totals and conservation "
                         "check (in-process mode only)")
    ap.add_argument("--record", default="", metavar="OUT.json",
                    help="write a machine-readable bench artifact "
                         "(tok/s, TTFT/TPOT p50/p95/p99, scenario "
                         "knobs, tail attribution) to this path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject a seeded probabilistic fault plan "
                         "(poisoned steps, synthetic OOM, slow steps) "
                         "and drive through the self-healing "
                         "supervisor; reports availability and p99 "
                         "TTFT/TPOT under faults (in-process mode only)")
    ap.add_argument("--profile", default="", metavar="OUT.folded",
                    help="sample a phase-attributed host profile of "
                         "the run (observability.SamplingProfiler) and "
                         "write folded stacks to this path — feed to "
                         "flamegraph.pl / speedscope or "
                         "tools/profile_report.py (in-process mode "
                         "only)")
    return ap


def bench_args(**overrides) -> argparse.Namespace:
    """Default bench Namespace built from the REAL parser
    (``parse_args([])``), with keyword overrides by attribute name
    (``prefill_chunk=8``, not ``--prefill-chunk``).  Tests and
    embedders use this instead of hand-building a Namespace, so a
    newly added bench flag can never silently be missing (the PR 10 /
    PR 13 breakage class).  Unknown names raise."""
    args = _build_parser().parse_args([])
    for k, v in overrides.items():
        if not hasattr(args, k):
            raise TypeError(f"bench_args(): unknown bench arg {k!r}")
        setattr(args, k, v)
    return args


def main(argv=None):
    args = _build_parser().parse_args(argv)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.http:
        res = run_http_bench(args)
    elif args.overload_baseline:
        res, _ = run_overload_compare(args)
    else:
        res = run_bench(args)
    if args.record:
        _write_record(args, res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
