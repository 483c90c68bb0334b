#!/usr/bin/env python
"""perf-gate: deterministic serving-efficiency regression gate.

Runs small serve scenarios on a tiny model and gates on **counters**
(retraces, host syncs per step, logits transfers, pages per token,
prefix hit rate, goodput ratio) — never wall time, so the gate is
stable on CPU under tier-1.

Usage:
    python tools/perf_gate.py                    # gate vs committed baseline
    python tools/perf_gate.py --json             # machine-readable output
    python tools/perf_gate.py --update-baseline  # accept current counters
    python tools/perf_gate.py --scenarios steady_decode,prefix_cache
    python tools/perf_gate.py --list-scenarios

Exit status mirrors tools/lint.py: 0 when every counter is within its
baseline (counters may *improve*: fewer retraces / higher hit rate pass
and are reported as improvements — tighten with ``--update-baseline``),
1 on a regression or a counter with no baseline entry, 2 on usage
errors (unknown scenario, missing baseline file).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

DEFAULT_BASELINE = os.path.join(_REPO_ROOT, "tools",
                                "perf_baseline.json")

# comparison direction per counter: "low" = current <= baseline passes,
# "high" = current >= baseline passes, "exact" = must match
DIRECTIONS = {
    "decode_traces": "low",
    "prefill_compiles": "low",
    "host_syncs": "low",
    "host_syncs_per_decode_step": "low",
    "logits_fetches": "low",
    "pages_per_token": "low",
    "pages_allocated": "low",
    "cow_copies": "exact",
    "prefix_hit_rate": "high",
    "cached_tokens": "high",
    "steps_per_sync": "high",
    "goodput_ratio": "high",
    "host_syncs_delta_vs_tp1": "exact",
    "pages_per_token_delta_vs_tp1": "exact",
    "mesh_tp": "exact",
    # speculative decoding: the verify program must be its own single
    # trace beside the plain step (exactly 2 decode traces, 1 verify
    # trace), commit more than one token per device step on repetitive
    # text, keep the drafter's acceptance above its floor, and stay
    # bit-identical to the plain engine (parity gates at exactly 1)
    "spec_decode_traces": "exact",
    "verify_traces": "exact",
    "tokens_per_decode_step": "high",
    "acceptance_rate": "high",
    "decode_steps_saved_vs_plain": "high",
    "greedy_parity_vs_plain": "exact",
    # fault recovery: one injected poisoned step must cost exactly one
    # rebuild, replay every in-flight request (sharing the prefix cache
    # on the way back in), keep greedy outputs identical to the
    # unfaulted run, and hand back every page
    "recoveries": "exact",
    "quarantines": "exact",
    "replayed_requests": "exact",
    "recovered_parity": "exact",
    "leaked_pages": "exact",
    "faults_injected": "exact",
    "replay_cached_tokens": "high",
    # overload degradation: preempt-and-swap must spill and restore an
    # exact page count with zero spill failures, keep the preempted
    # request's greedy output identical to an uninterrupted run, and
    # hand back every page; chunked prefill must split a long admission
    # into an exact chunk count and bound the longest decode-free
    # prefill burst (the head-of-line-blocking witness) — all without
    # a single new decode trace
    "preemptions": "exact",
    "spill_aborts": "exact",
    "spilled_pages": "exact",
    "restored_pages": "exact",
    "preempt_parity": "exact",
    "prefill_chunks": "exact",
    "chunk_parity": "exact",
    "max_prefill_gap": "low",
    # telemetry: the sampler must be deterministic under a fake clock
    # (exact ticks/samples/alerts) and free under the control run
    # (exactly zero extra host syncs / decode traces)
    "sampler_ticks": "exact",
    "samples_taken": "exact",
    "series_tracked": "exact",
    "alert_rules": "exact",
    "alerts_fired": "exact",
    "host_syncs_delta_vs_off": "exact",
    "decode_traces_delta_vs_off": "exact",
    # profiling: the sampler must sweep exactly once per driven step
    # with zero stack-table drops, the injected slow_step alert must
    # produce exactly one on-disk capture (a second fire inside the
    # rate-limit window is rejected, not written), and arming the
    # whole stack must add ZERO host syncs / decode traces over the
    # bare control (the zero-overhead-off contract of
    # FLAGS_obs_profile_interval_s / FLAGS_obs_capture_*)
    "captures_written": "exact",
    "capture_files": "exact",
    "capture_rate_limited": "exact",
    "profile_samples_delta_vs_steps": "exact",
    "profile_dropped": "exact",
    # usage metering: every per-request ledger field must sum exactly
    # to the matching engine/pool global (attribution is accounting,
    # not sampling), the page-seconds conservation identity must hold
    # at 0 for both tiers, the preemption spill must bill the victim's
    # tenant alone, outputs must be bit-identical to the meter-off run,
    # and arming the meter must add ZERO host syncs / decode traces
    "ledger_computed_tokens": "exact",
    "ledger_cached_delta": "exact",
    "ledger_decode_delta": "exact",
    "ledger_spilled_delta": "exact",
    "ledger_restored_delta": "exact",
    "ledger_spill_bytes_minus_restore_bytes": "exact",
    "ledger_preemptions_delta": "exact",
    "victim_tenant_spilled_pages": "exact",
    "bystander_spilled_pages": "exact",
    "page_seconds_conservation_delta": "exact",
    "host_page_seconds_conservation_delta": "exact",
    "tenants_tracked": "exact",
    "usage_parity_vs_off": "exact",
    # multi-LoRA serving: two live adapters in one mixed batch must
    # share the ONE decode trace, match the merged-weights dense
    # reference token-for-token, actually diverge from the base model,
    # and an armed-but-unused store must cost exactly nothing (dense
    # parity, zero extra host syncs / decode traces)
    "adapters_resident": "exact",
    "lora_loads": "exact",
    "lora_evictions": "exact",
    "lora_parity_vs_merged": "exact",
    "lora_off_parity_vs_dense": "exact",
    "adapter_divergence": "exact",
    # offline batch lane: the job must complete every row with zero
    # failures while interactive arrivals preempt its residents, the
    # preempted rows must resume token-for-token (row parity vs an
    # idle engine), interactive outputs must be untouched, and the
    # pool must balance
    "batch_rows_completed": "exact",
    "batch_rows_failed": "exact",
    "batch_job_done": "exact",
    "batch_row_parity": "exact",
    "interactive_parity_vs_idle": "exact",
    # tail-latency forensics: every finished timeline's bucket seconds
    # must telescope exactly to its measured E2E (the conservation
    # identity pinned at 0), the event / exemplar counts are exact
    # under the nanosecond SLO (every request violates every
    # dimension, so the reservoir census is arithmetic, not timing),
    # greedy outputs are bit-identical to the forensics-off run, and
    # arming the RequestLog adds ZERO host syncs / decode traces (the
    # zero-overhead-off contract of the ``requestlog is not None``
    # seams)
    "requests_tracked": "exact",
    "requests_finished": "exact",
    "timeline_events": "exact",
    "attribution_conservation_max_delta": "exact",
    "exemplars_captured": "exact",
    "forensics_parity_vs_off": "exact",
    # int8 weights and int8 KV pages: a page (and what a spill moves of
    # it) costs (hd + 4) / (4 * hd) of the dense page's bytes, never
    # more; greedy output stays within the quantization tolerance of
    # the dense run (the gate pins the verdict at exactly 1)
    "pages_per_token_x1000": "low",
    "spill_bytes_ratio_vs_dense_x1000": "low",
    "quant_parity_within_tol": "exact",
}


def _force_cpu():
    """The gate's counters are platform-independent, but CPU is the
    only backend tier-1 guarantees — never touch an accelerator.  The
    tp_decode scenario additionally needs >= 2 host devices, so ask XLA
    for 8 before the backend initializes (a no-op once it has — under
    pytest the conftest already forced the same count)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass            # backend already initialized (e.g. under pytest)


def _engine(**kw):
    """Fresh tiny model + engine per scenario: counters are read from
    the engine's own python mirrors, so scenarios never see each
    other's (or the host process's) metrics."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import create_engine
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return create_engine(LlamaForCausalLM(cfg), **kw)


def _tiny_state():
    """The gate's tiny config + its generation-state dict — scenarios
    that transform the checkpoint (the merged-weight LoRA reference)
    build Engines from state directly instead of through a model."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    state = {k: (v._data if isinstance(v, Tensor) else v)
             for k, v in model.functional_state().items()}
    return cfg, state


def _gen(max_new_tokens):
    from paddle_tpu.models.generation import GenerationConfig
    return GenerationConfig(max_new_tokens=max_new_tokens)


def _goodput(reqs) -> float:
    useful = sum(r.num_generated for r in reqs
                 if r.finish_reason in ("length", "eos"))
    total = sum(r.num_generated for r in reqs)
    return round(useful / total, 6) if total else 1.0


def _reinject_retrace(eng):
    """Test hook: rebuild the decode-step jit so the next decode call
    traces again — the exact regression serving_decode_step_traces_total
    exists to catch."""
    eng.runner.reinject_step()


def scenario_steady_decode(inject_retrace=False) -> dict:
    """Greedy decode across two admission waves: the decode step must
    trace ONCE for the engine's lifetime, each step that makes a token
    costs exactly one host sync (sync_interval=1), and no logits ever
    cross the wire.  The host runs one step behind the device, so each
    wave's last finish is seen after one more step has been dispatched:
    a wave is 7 steps whose rows are fetched and the overrun step whose
    row is dropped unfetched — 14 syncs over 16 steps = 0.875."""
    eng = _engine(max_slots=2, page_size=4, sync_interval=1)
    reqs = [eng.submit([1, 2, 3, 4, 5, 6], _gen(8)),
            eng.submit([3, 4, 5, 6, 7, 8], _gen(8))]
    eng.run_until_complete(max_steps=400)
    if inject_retrace:
        _reinject_retrace(eng)
    reqs.append(eng.submit([5, 6, 7, 8, 9, 10, 11], _gen(8)))
    eng.run_until_complete(max_steps=400)
    tokens = sum(r.num_generated for r in reqs)
    return {
        "decode_traces": eng.decode_traces,
        "prefill_compiles": (len(eng._prefill_fns)
                             + len(eng._prefill_cached_fns)),
        "host_syncs_per_decode_step": round(
            eng.host_syncs / max(eng.decode_steps, 1), 6),
        "logits_fetches": eng.logit_fetches,
        "pages_per_token": round(
            eng.blocks.pages_allocated / max(tokens, 1), 6),
        "goodput_ratio": _goodput(reqs),
    }


def scenario_prefix_cache() -> dict:
    """A second wave sharing a 12-token (3-page) prefix must hit the
    chain index for every shared chunk, pay pages only for its suffix,
    and CoW exactly once for the tail that diverges after one token."""
    eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                  enable_prefix_cache=True)
    prefix = list(range(1, 13))
    reqs = [eng.submit(prefix + [20, 21], _gen(4))]
    eng.run_until_complete(max_steps=200)
    reqs.append(eng.submit(prefix + [20, 25], _gen(4)))   # CoW tail
    reqs.append(eng.submit(prefix + [30, 31], _gen(4)))   # fresh tail
    eng.run_until_complete(max_steps=200)
    b = eng.blocks
    lookups = b.prefix_hits + b.prefix_misses
    return {
        "prefix_hit_rate": round(b.prefix_hits / max(lookups, 1), 6),
        "cached_tokens": b.cached_tokens,
        "pages_allocated": b.pages_allocated,
        "cow_copies": b.cow_copies,
        "goodput_ratio": _goodput(reqs),
    }


def scenario_deferred_sync() -> dict:
    """sync_interval=4 greedy decode must amortize the ring fetch over
    4 device steps — host syncs are the serving scalability ceiling.
    9 steps over 2 fetches: the 7 that make tokens 2..8, the second
    group's 4th row (overrun, as ever at this interval), and the step
    dispatched before that group was fetched (the host runs one step
    behind; its row is dropped unfetched)."""
    eng = _engine(max_slots=2, page_size=4, sync_interval=4)
    reqs = [eng.submit([1, 2, 3, 4, 5, 6], _gen(8)),
            eng.submit([2, 3, 4, 5, 6, 7], _gen(8))]
    eng.run_until_complete(max_steps=400)
    del reqs
    return {
        "steps_per_sync": round(
            eng.decode_steps / max(eng.host_syncs, 1), 6),
        "host_syncs": eng.host_syncs,
        "decode_traces": eng.decode_traces,
    }


def scenario_goodput_cancel() -> dict:
    """A client cancel after 3 streamed tokens wastes exactly those 3
    tokens; the surviving request's 8 are useful — ratio 8/11.  Counted
    from request outcomes (no wall clocks, no deadlines)."""
    eng = _engine(max_slots=2, page_size=4, sync_interval=1)

    def cancel_after_3(req, tok):
        if req.num_generated >= 3:
            req.cancel()

    reqs = [eng.submit([1, 2, 3, 4, 5, 6], _gen(8)),
            eng.submit([2, 3, 4, 5, 6, 7], _gen(8),
                       on_token=cancel_after_3)]
    eng.run_until_complete(max_steps=400)
    return {
        "goodput_ratio": _goodput(reqs),
        "decode_traces": eng.decode_traces,
        "logits_fetches": eng.logit_fetches,
    }


def scenario_tp_decode() -> dict:
    """Tensor-parallel decode on a tp=2 host-device mesh, same workload
    twice (tp=1 then tp=2) with an admit + a mid-decode cancel-eviction
    in wave two: the mesh must keep ONE decode trace across admit/evict,
    and pay exactly the single-chip host-sync and page bills (the
    ``*_delta_vs_tp1`` counters gate at 0)."""

    def drive(tp):
        eng = _engine(max_slots=2, page_size=4, sync_interval=1, mesh=tp)

        def cancel_after_3(req, tok):
            if req.num_generated >= 3:
                req.cancel()

        reqs = [eng.submit([1, 2, 3, 4, 5, 6], _gen(8)),
                eng.submit([3, 4, 5, 6, 7, 8], _gen(8))]
        eng.run_until_complete(max_steps=400)
        reqs.append(eng.submit([5, 6, 7, 8, 9, 10, 11], _gen(8)))
        reqs.append(eng.submit([2, 4, 6, 8], _gen(8),
                               on_token=cancel_after_3))
        eng.run_until_complete(max_steps=400)
        return eng, reqs

    e1, _ = drive(1)
    e2, reqs = drive(2)
    tokens = sum(r.num_generated for r in reqs)
    ppt = round(e2.blocks.pages_allocated / max(tokens, 1), 6)
    ppt1 = round(e1.blocks.pages_allocated / max(tokens, 1), 6)
    return {
        "mesh_tp": e2.tp,
        "decode_traces": e2.decode_traces,
        "prefill_compiles": (len(e2._prefill_fns)
                             + len(e2._prefill_cached_fns)),
        # 0.875 as in steady_decode: each wave ends with one overrun
        # step whose row is dropped unfetched (7 syncs over 8 steps)
        "host_syncs_per_decode_step": round(
            e2.host_syncs / max(e2.decode_steps, 1), 6),
        "host_syncs_delta_vs_tp1": e2.host_syncs - e1.host_syncs,
        "pages_per_token_delta_vs_tp1": round(ppt - ppt1, 6),
        "logits_fetches": e2.logit_fetches,
        "goodput_ratio": _goodput(reqs),
    }


def scenario_spec_decode() -> dict:
    """Speculative decoding on repetitive text: the same greedy
    workload runs with spec_k=0 and spec_k=4, and the spec engine must
    emit identical tokens while committing > 1 token per device step
    (the tentpole win), tracing exactly two decode programs (plain +
    verify) across two admission waves, and spending strictly fewer
    device steps and host syncs than the plain engine — counters only,
    no wall clocks.  Single slot, so tokens/step measures speculation
    rather than batching (concurrent slots would inflate it even with
    spec_k=0)."""

    def drive(spec_k):
        eng = _engine(max_slots=1, page_size=4, sync_interval=1,
                      spec_k=spec_k)
        # prompts whose greedy continuations collapse into repeats —
        # the n-gram drafter's best case, deterministic under seed 0
        reqs = [eng.submit([5, 6, 5, 6, 5, 6], _gen(12))]
        eng.run_until_complete(max_steps=400)
        # second wave: admission after a finished request must not
        # retrace either the plain or the verify program
        reqs.append(eng.submit([3, 4, 3, 4, 3, 4], _gen(12)))
        eng.run_until_complete(max_steps=400)
        return eng, reqs

    plain, ref_reqs = drive(0)
    eng, reqs = drive(4)
    st = eng.stats()
    tokens = sum(r.num_generated for r in reqs)
    return {
        "greedy_parity_vs_plain": int(
            [r.output_tokens for r in reqs]
            == [r.output_tokens for r in ref_reqs]),
        "spec_decode_traces": eng.decode_traces,
        "verify_traces": st["verify_traces"],
        "tokens_per_decode_step": round(
            tokens / max(eng.decode_steps, 1), 6),
        "acceptance_rate": round(st["spec_acceptance_rate"], 6),
        "decode_steps_saved_vs_plain": (plain.decode_steps
                                        - eng.decode_steps),
        "host_syncs": eng.host_syncs,
        "goodput_ratio": _goodput(reqs),
    }


def scenario_fault_recovery() -> dict:
    """A poisoned decode step mid-batch under the engine supervisor:
    exactly one runner rebuild, both in-flight requests replayed (the
    shared prompt prefix rides back in through the prefix cache), token
    outputs identical to an unfaulted run, and a clean pool census.
    The unfaulted drive doubles as the zero-overhead control — it runs
    the same supervised loop with fault injection off."""
    from paddle_tpu.serving import EngineSupervisor, FaultPlan

    prefix = list(range(1, 13))

    def drive(plan):
        eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                      enable_prefix_cache=True, faults=plan)
        sup = EngineSupervisor(eng, max_recoveries=3)
        reqs = [eng.submit(prefix + [20, 21], _gen(8)),
                eng.submit(prefix + [20, 25], _gen(8))]
        steps = 0
        while not all(r.is_finished() for r in reqs) and steps < 400:
            sup.step()
            steps += 1
        return eng, reqs

    ref_eng, ref_reqs = drive(None)
    plan = FaultPlan(seed=0)
    plan.add("step_raise", at=5)
    eng, reqs = drive(plan)
    return {
        "recoveries": eng.recoveries,
        "quarantines": eng.quarantines,
        "replayed_requests": eng.replayed_requests,
        "recovered_parity": int([r.output_tokens for r in reqs]
                                == [r.output_tokens for r in ref_reqs]),
        "leaked_pages": eng.blocks.pool_accounting()["leak"],
        "faults_injected": plan.injected.get("step_raise", 0),
        # cache-served prompt tokens ABOVE the unfaulted run = what the
        # replay path got back from the prefix cache instead of
        # recomputing
        "replay_cached_tokens": (eng.blocks.cached_tokens
                                 - ref_eng.blocks.cached_tokens),
        "decode_traces": eng.decode_traces,
        "goodput_ratio": _goodput(reqs),
    }


def scenario_telemetry() -> dict:
    """Fake-clock sampler determinism: the same faulted workload runs
    twice — with a ticking TimeSeriesStore + the default alert rules,
    and without — gating that the sampler takes an exact number of
    samples, fires exactly the expected alerts, and adds ZERO host
    syncs / decode traces over the sampler-off control (the
    zero-overhead contract of FLAGS_obs_timeseries_interval_s).
    Sources read engine python mirrors, not the process registry, so
    the scenario is isolated no matter which scenarios ran before.
    One tick a supervisor step and one before: 9 = the 7 steps whose
    rows make the surviving request's tokens 2..8, the overrun step
    after which the host sees the last of them (it runs one step behind
    the device), and the baseline tick."""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import EngineSupervisor, FaultPlan

    prompt = list(range(1, 9))

    def drive(with_store):
        plan = FaultPlan(seed=0)
        plan.add("nan_logits", at=1, slot=0, phase="prefill")
        eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                      faults=plan)
        sup = EngineSupervisor(eng, max_recoveries=3)
        store = None
        fake = [0.0]
        reqs = []
        if with_store:
            store = obs.TimeSeriesStore(capacity=256,
                                        clock=lambda: fake[0])
            store.add_source("tokens", lambda: float(
                sum(r.num_generated for r in reqs)))
            store.add_source("active_slots",
                             lambda: float(eng.scheduler.active_count))
            store.add_source("fragmentation",
                             lambda: eng.blocks.fragmentation())
            store.add_source("recoveries", lambda: float(
                eng.recoveries + eng.quarantines))
            store.add_rate("tok_s", of="tokens")
            for rule in obs.default_rules(shed_burn_rate=1.0):
                store.add_rule(rule)
            store.tick()        # t=0 baseline before any fault
        reqs += [eng.submit(prompt + [20], _gen(8)),
                 eng.submit(prompt + [25], _gen(8))]
        steps = 0
        while not all(r.is_finished() for r in reqs) and steps < 400:
            sup.step()
            steps += 1
            if store is not None:
                fake[0] += 1.0
                store.tick()
        return eng, store

    eng_off, _ = drive(False)
    eng_on, store = drive(True)
    return {
        "sampler_ticks": store.ticks,
        "samples_taken": store.samples,
        "series_tracked": len(store.windows(n=1)),
        "alert_rules": len(store.rules),
        "alerts_fired": store.alerts_fired,
        "quarantines": eng_on.quarantines,
        "leaked_pages": eng_on.blocks.pool_accounting()["leak"],
        # the zero-overhead contract: sampling adds no device work
        "host_syncs_delta_vs_off": eng_on.host_syncs
        - eng_off.host_syncs,
        "decode_traces_delta_vs_off": eng_on.decode_traces
        - eng_off.decode_traces,
    }


def scenario_profiling() -> dict:
    """Alert-triggered diagnostic capture + sampling profiler,
    counters only, fake clocks throughout.  The same slow-step-marked
    workload runs twice — bare, and with the full PR-15 stack armed
    (TimeSeriesStore + a deterministic slow_steps alert rule +
    DiagnosticCapture into a throwaway dir + a SamplingProfiler swept
    inline once per step).  Gates: the alert fires exactly once, the
    capture lands exactly once on disk, a second on_alert inside the
    rate-limit window is rejected (not written), the profiler takes
    exactly one sweep per driven step with zero drops, and the armed
    run adds ZERO host syncs / decode traces over the bare control."""
    import tempfile
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import FaultPlan

    prompt = list(range(1, 9))

    def drive(with_obs, tmp=None):
        plan = FaultPlan(seed=0)
        # marker fault: the injected-count drives the alert; a zero
        # sleep keeps the gate fast and the workload byte-identical
        plan.add("slow_step", at=3, seconds=0.0)
        eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                      faults=plan)
        store = prof = cap = None
        fake = [0.0]
        if with_obs:
            store = obs.TimeSeriesStore(capacity=256,
                                        clock=lambda: fake[0])
            store.add_source("slow_steps", lambda: float(
                plan.injected.get("slow_step", 0)))
            store.add_rule(obs.AlertRule(
                "slow_step_injected", "slow_steps", above=0,
                min_samples=1,
                help_="deterministic capture trigger for the gate"))
            prof = obs.SamplingProfiler(0.0)   # inline sweeps only
            cap = obs.DiagnosticCapture(
                dir_=tmp, min_interval_s=3600.0, max_captures=4,
                profiler=prof, clock=lambda: fake[0])
            cap.attach(store)
            store.tick()        # t=0 baseline before the fault lands
        reqs = [eng.submit(prompt + [20], _gen(8)),
                eng.submit(prompt + [25], _gen(8))]
        steps = 0
        while not all(r.is_finished() for r in reqs) and steps < 400:
            eng.step()
            steps += 1
            if store is not None:
                fake[0] += 1.0
                prof.sample(fake[0])
                store.tick()
        return eng, store, prof, cap, steps

    eng_off, *_ = drive(False)
    with tempfile.TemporaryDirectory() as tmp:
        eng_on, store, prof, cap, steps = drive(True, tmp)
        # a second fire inside the rate-limit window: rejected exactly
        cap.on_alert("slow_step_injected", {"value": 1.0},
                     now=float(steps))
        files = len([f for f in os.listdir(tmp)
                     if f.startswith("capture_")])
    return {
        "alerts_fired": store.alerts_fired,
        "captures_written": cap.captures,
        "capture_files": files,
        "capture_rate_limited": cap.rate_limited,
        "profile_samples_delta_vs_steps": prof.samples - steps,
        "profile_dropped": prof.dropped,
        "leaked_pages": eng_on.blocks.pool_accounting()["leak"],
        # the zero-overhead contract: the armed stack adds no device
        # work over the bare control
        "host_syncs_delta_vs_off": eng_on.host_syncs
        - eng_off.host_syncs,
        "decode_traces_delta_vs_off": eng_on.decode_traces
        - eng_off.decode_traces,
    }


def scenario_overload_degrade() -> dict:
    """Graceful degradation under overload, counters only.

    Preempt half: two low-priority residents fill both slots and
    decode for a while; a high-priority submit must preempt the
    most-recently-admitted one — spilling its full KV pages to the
    host tier (exact page count, zero aborts), re-queueing it, and
    restoring the parked pages on resume.  The preempted request's
    greedy tokens must equal an uninterrupted run's (parity gates at
    exactly 1) and the pool census must balance.

    Chunk half: a 40-token prompt admitted behind a decoding resident
    with prefill_chunk=8 must prefill in exactly 5 chunks, and the
    longest run of prefill tokens with no intervening decode step
    (max_prefill_gap, the head-of-line-blocking witness) must stay at
    the chunk size instead of the full prompt length.  Both halves
    reuse the existing decode/prefill programs — decode_traces gates
    at 1 per engine."""
    # --- preempt-and-swap (prefix cache off: spills, not cache, must
    # carry the KV back) ---
    eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                  enable_prefix_cache=False, preempt=True)
    lo_a = eng.submit([1, 2, 3, 4, 5, 6], _gen(8))
    lo_b = eng.submit([3, 4, 5, 6, 7, 8], _gen(8))
    for _ in range(4):              # both residents mid-decode
        eng.step()
    hi = eng.submit([5, 6, 7, 8, 9, 10], _gen(8), priority=1)
    eng.run_until_complete(max_steps=400)
    reqs = [lo_a, lo_b, hi]

    ref = _engine(max_slots=3, page_size=4, sync_interval=1,
                  enable_prefix_cache=False)
    ref_reqs = [ref.submit([1, 2, 3, 4, 5, 6], _gen(8)),
                ref.submit([3, 4, 5, 6, 7, 8], _gen(8)),
                ref.submit([5, 6, 7, 8, 9, 10], _gen(8))]
    ref.run_until_complete(max_steps=400)

    # --- chunked prefill (long admission behind a decoding resident) ---
    long_prompt = list(range(1, 41))
    eng2 = _engine(max_slots=2, page_size=4, sync_interval=1,
                   enable_prefix_cache=False, prefill_chunk=8)
    short = eng2.submit([1, 2, 3, 4, 5, 6], _gen(16))
    for _ in range(3):              # short request is decoding
        eng2.step()
    chunked = eng2.submit(long_prompt, _gen(4))
    eng2.run_until_complete(max_steps=400)

    ref2 = _engine(max_slots=2, page_size=4, sync_interval=1,
                   enable_prefix_cache=False, prefill_chunk=0)
    ref2_req = ref2.submit(long_prompt, _gen(4))
    ref2.run_until_complete(max_steps=400)

    return {
        "preemptions": eng.preemptions,
        "spill_aborts": eng.spill_aborts,
        "spilled_pages": eng.blocks.spilled_pages,
        "restored_pages": eng.blocks.restored_pages,
        "preempt_parity": int(
            [r.output_tokens for r in reqs]
            == [r.output_tokens for r in ref_reqs]),
        "leaked_pages": (eng.blocks.pool_accounting()["leak"]
                         + eng2.blocks.pool_accounting()["leak"]),
        "decode_traces": max(eng.decode_traces, eng2.decode_traces),
        "prefill_chunks": eng2.prefill_chunks,
        "max_prefill_gap": eng2.max_prefill_gap,
        "chunk_parity": int(chunked.output_tokens
                            == ref2_req.output_tokens),
        "goodput_ratio": _goodput(reqs + [short, chunked]),
    }


def scenario_usage_meter() -> dict:
    """Per-request cost attribution + tenant metering, counters only.

    The same 3-tenant preempt-and-swap workload (two low-priority
    residents, then a high-priority arrival that preempts one of them)
    runs twice — bare, and with a UsageMeter wired in.  Gates: every per-request ledger field sums exactly to
    the matching engine/pool global (computed/cached prefill split,
    decode tokens, spilled/restored pages, spill bytes == restore
    bytes, preemptions), the page-seconds conservation identity holds
    at delta == 0 on both the device and host tiers, the spill bills
    the preempted tenant alone (bystanders at 0), greedy outputs are
    bit-identical to the meter-off run, and arming the meter adds ZERO
    host syncs / decode traces (the zero-overhead-off contract of the
    ``usage is not None`` seams)."""
    from paddle_tpu.observability.usage import UsageMeter, request_ledger

    def drive(meter):
        eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                      enable_prefix_cache=False, preempt=True,
                      usage=meter)
        lo_a = eng.submit([1, 2, 3, 4, 5, 6], _gen(8), tenant="teamA")
        lo_b = eng.submit([3, 4, 5, 6, 7, 8], _gen(8), tenant="teamB")
        for _ in range(4):              # both residents mid-decode
            eng.step()
        hi = eng.submit([5, 6, 7, 8, 9, 10], _gen(8), priority=1,
                        tenant="teamC")
        eng.run_until_complete(max_steps=400)
        return eng, [lo_a, lo_b, hi]

    eng_off, ref_reqs = drive(None)
    meter = UsageMeter()
    eng, reqs = drive(meter)
    snap = meter.snapshot()
    rows = snap["tenants"]
    cons = snap["conservation"]
    ledgers = [request_ledger(r) for r in reqs]

    def total(field):
        return sum(led[field] for led in ledgers)

    # both low residents admit in the same scheduler pass (identical
    # admitted_at), so slot order breaks the tie: slot 0 == teamA
    victim = rows.get("teamA", {})
    bystanders = (rows.get("teamB", {}).get("spilled_pages", 0)
                  + rows.get("teamC", {}).get("spilled_pages", 0))
    return {
        "preemptions": eng.preemptions,
        "spill_aborts": eng.spill_aborts,
        "spilled_pages": eng.blocks.spilled_pages,
        "restored_pages": eng.blocks.restored_pages,
        "ledger_computed_tokens": total("prefill_computed_tokens"),
        "ledger_cached_delta": (total("prefill_cached_tokens")
                                - eng.blocks.cached_tokens),
        "ledger_decode_delta": (
            sum(r.get("decode_tokens", 0) for r in rows.values())
            - sum(r.num_generated for r in reqs)),
        "ledger_spilled_delta": (total("spilled_pages")
                                 - eng.blocks.spilled_pages),
        "ledger_restored_delta": (total("restored_pages")
                                  - eng.blocks.restored_pages),
        "ledger_spill_bytes_minus_restore_bytes": (
            total("spill_bytes") - total("restore_bytes")),
        "ledger_preemptions_delta": (total("preemptions")
                                     - eng.preemptions),
        "victim_tenant_spilled_pages": victim.get("spilled_pages", 0),
        "bystander_spilled_pages": bystanders,
        "page_seconds_conservation_delta": cons["device_delta"],
        "host_page_seconds_conservation_delta": cons["host_delta"],
        "tenants_tracked": len(rows),
        "usage_parity_vs_off": int(
            [r.output_tokens for r in reqs]
            == [r.output_tokens for r in ref_reqs]),
        "leaked_pages": eng.blocks.pool_accounting()["leak"],
        "host_syncs_delta_vs_off": eng.host_syncs - eng_off.host_syncs,
        "decode_traces_delta_vs_off": (eng.decode_traces
                                       - eng_off.decode_traces),
        "goodput_ratio": _goodput(reqs),
    }


def scenario_quant_decode() -> dict:
    """Quantized serving (int8 weights + int8 KV pages) vs the dense
    reference on the identical two-wave workload, counters only.

    Gates: ONE decode trace with quantized weights and pools, greedy
    parity within tolerance (>= 75% token match on the tiny random
    model — int8 weight error may flip a late low-margin argmax, so
    exact parity would be flaky by construction while genuine breakage
    lands far below the floor), the KV page byte cost pinned at the
    closed-form ratio ``(hd + 4) / (4 * hd)`` of dense (the pages-per-
    token byte cost under ``--kv-quant``; 375/1000 at head_dim=8), the
    spill tier moving the same reduced bytes (read_page parks int8 +
    scales, never a dequantized copy), and the quant-off control: the
    dense run beside it must show zero extra host syncs and zero extra
    decode traces, the zero-overhead-off pin every scenario carries."""

    def drive(quant, kv_quant):
        eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                      quant=quant, kv_quant=kv_quant)
        reqs = [eng.submit([1, 2, 3, 4, 5, 6], _gen(8)),
                eng.submit([3, 4, 5, 6, 7, 8], _gen(8))]
        eng.run_until_complete(max_steps=400)
        reqs.append(eng.submit([5, 6, 7, 8, 9, 10, 11], _gen(8)))
        eng.run_until_complete(max_steps=400)
        return eng, reqs

    eng_off, ref_reqs = drive(None, None)
    eng, reqs = drive("int8", True)
    match = total = 0
    for r, rr in zip(reqs, ref_reqs):
        a, b = r.output_tokens, rr.output_tokens
        total += max(len(a), len(b))
        match += sum(int(x == y) for x, y in zip(a, b))
    snap = eng.quant_snapshot()
    dense_page = sum(a.nbytes for a in eng_off.runner.read_page(0))
    quant_page = sum(a.nbytes for a in eng.runner.read_page(0))
    return {
        "decode_traces": eng.decode_traces,
        "quant_parity_within_tol": int(match >= 0.75 * max(total, 1)),
        "pages_per_token_x1000": round(
            1000 * snap["page_bytes"] / snap["dense_page_bytes"]),
        "spill_bytes_ratio_vs_dense_x1000": round(
            1000 * quant_page / dense_page),
        "host_syncs_delta_vs_off": eng.host_syncs - eng_off.host_syncs,
        "decode_traces_delta_vs_off": (eng.decode_traces
                                       - eng_off.decode_traces),
        "goodput_ratio": _goodput(reqs),
    }


def scenario_lora_decode() -> dict:
    """Multi-LoRA serving vs the merged-weights dense reference,
    counters only.

    A mixed batch (adapter 'a' on one slot, adapter 'b' on the other)
    must run in ONE decode trace with both adapters live in the bank,
    and each request's greedy tokens must equal a dense engine built
    from ``W + (alpha/r) A^T B`` merged weights — token-for-token, the
    gather-from-bank path against the fold-into-checkpoint ground
    truth.  The adapters must also actually change the outputs (a zero
    delta would make the parity vacuous).  The off half pins the
    zero-overhead contract: an engine with the store ATTACHED but only
    dense requests must produce bit-identical tokens and exactly zero
    extra host syncs / decode traces vs a store-less engine."""
    from paddle_tpu.serving.engine import Engine
    from paddle_tpu.serving.lora import (AdapterStore, merge_adapter,
                                         random_adapter)

    cfg, state = _tiny_state()
    rank, alpha = 4, 8.0
    wa = random_adapter(cfg, rank, seed=1)
    wb = random_adapter(cfg, rank, seed=2)
    prompts = ([1, 2, 3, 4, 5, 6], [3, 4, 5, 6, 7, 8])

    def store():
        s = AdapterStore(cfg, capacity=2)
        s.register("a", wa, alpha=alpha)
        s.register("b", wb, alpha=alpha)
        return s

    def drive(st=None, lora=None, adapters=(None, None)):
        eng = Engine(config=cfg,
                     state=dict(state if st is None else st),
                     max_slots=2, page_size=4, sync_interval=1,
                     lora=lora)
        reqs = [eng.submit(list(p), _gen(8), adapter=ad)
                for p, ad in zip(prompts, adapters)]
        eng.run_until_complete(max_steps=400)
        return eng, [list(r.output_tokens) for r in reqs]

    dense_eng, dense_out = drive()
    off_eng, off_out = drive(lora=store())      # armed, requests dense
    live = store()
    eng, out = drive(lora=live, adapters=("a", "b"))
    _, merged_a = drive(st=merge_adapter(state, cfg, wa, alpha=alpha))
    _, merged_b = drive(st=merge_adapter(state, cfg, wb, alpha=alpha))
    snap = live.snapshot()
    return {
        "decode_traces": eng.decode_traces,
        "adapters_resident": len(snap["resident"]),
        "lora_loads": snap["loads"],
        "lora_evictions": snap["evictions"],
        "lora_parity_vs_merged": int(out == [merged_a[0], merged_b[1]]),
        "adapter_divergence": int(out[0] != dense_out[0]
                                  and out[1] != dense_out[1]),
        "lora_off_parity_vs_dense": int(off_out == dense_out),
        "host_syncs_delta_vs_off": off_eng.host_syncs
        - dense_eng.host_syncs,
        "decode_traces_delta_vs_off": (off_eng.decode_traces
                                       - dense_eng.decode_traces),
        "leaked_pages": eng.blocks.pool_accounting()["leak"],
    }


def scenario_batch_lane() -> dict:
    """Offline batch lane under interactive pressure, counters only.

    A 6-row JSONL job drip-feeds through a 2-slot preemptive engine
    with a 2-request window; two interactive priority-0 requests land
    mid-job and must preempt the batch residents (preemptions is
    pinned exact — the lane runs at priority -2, below every
    interactive class).  Gates: the job completes every row with zero
    failures, each preempted row resumes token-for-token (row outputs
    equal an idle engine's run of the same prompt), the interactive
    outputs equal an idle engine's (the lane never perturbs them), the
    whole dance reuses the ONE decode trace, and the pool balances."""
    import json as _json
    import tempfile
    from paddle_tpu.serving.lora import BatchJob

    eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                  enable_prefix_cache=False, preempt=True)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "batch.jsonl")
        with open(path, "w") as f:
            for i in range(6):
                f.write(_json.dumps({"prompt": [1, 2, 3, 4],
                                     "max_tokens": 6,
                                     "id": f"r{i}"}) + "\n")
        job = BatchJob.from_jsonl(path, window=2)
        interactive = []
        steps = 0
        while (job.pump(eng.submit) or eng.scheduler.has_work()) \
                and steps < 2000:
            if steps == 3:
                interactive += [eng.submit([5, 6, 7], _gen(4)),
                                eng.submit([6, 7, 8], _gen(4))]
            eng.step()
            steps += 1
        prog = job.progress()
        with open(prog["output_path"]) as f:
            rows = [_json.loads(line) for line in f]

    ref = _engine(max_slots=2, page_size=4, sync_interval=1,
                  enable_prefix_cache=False)
    ref_reqs = [ref.submit([5, 6, 7], _gen(4)),
                ref.submit([6, 7, 8], _gen(4))]
    batch_ref = ref.submit([1, 2, 3, 4], _gen(6))
    ref.run_until_complete(max_steps=200)
    batch_tokens = list(batch_ref.output_tokens)
    return {
        "batch_job_done": int(prog["status"] == "completed"),
        "batch_rows_completed": prog["completed"],
        "batch_rows_failed": prog["failed"],
        "batch_row_parity": int(
            len(rows) == 6
            and all(r.get("tokens") == batch_tokens for r in rows)),
        "interactive_parity_vs_idle": int(
            [list(r.output_tokens) for r in interactive]
            == [list(r.output_tokens) for r in ref_reqs]),
        "preemptions": eng.preemptions,
        "leaked_pages": eng.blocks.pool_accounting()["leak"],
        "decode_traces": eng.decode_traces,
        "goodput_ratio": _goodput(interactive),
    }


def scenario_tail_forensics() -> dict:
    """Tail-latency forensics, counters only.

    The overload workload (the preempt-and-swap half plus the chunked-
    prefill half of overload_degrade) runs twice — bare, and with a
    RequestLog attached behind an always-violating SLOTracker
    (nanosecond targets: every finished request trips every dimension,
    so the exemplar census is arithmetic, not timing).  Gates: every
    finished timeline's bucket seconds telescope exactly to its
    measured E2E (attribution_conservation_max_delta pinned at 0 —
    the advancing-cursor construction, checked against wall clocks),
    the lifecycle event count is exact across preemption / spill /
    resume / chunked admission, the reservoir keeps exactly one
    exemplar per request per dimension, greedy outputs are
    bit-identical to the forensics-off run, and arming the log adds
    ZERO host syncs / decode traces (the zero-overhead-off contract
    of the ``requestlog is not None`` seams)."""
    from paddle_tpu.observability.requestlog import RequestLog
    from paddle_tpu.serving.slo import SLOConfig, SLOTracker

    def slo():
        # nanosecond targets: any measured latency violates, so every
        # finished request lands in the exemplar store exactly once
        # per dimension (ttft, tpot, e2e)
        return SLOTracker(SLOConfig(ttft_s=1e-9, tpot_s=1e-9,
                                    e2e_s=1e-9))

    def drive(with_log):
        # --- preempt-and-swap half (decode -> preempted -> resume) ---
        log1 = RequestLog(k=8) if with_log else None
        eng = _engine(max_slots=2, page_size=4, sync_interval=1,
                      enable_prefix_cache=False, preempt=True,
                      slo=slo(), requestlog=log1)
        lo_a = eng.submit([1, 2, 3, 4, 5, 6], _gen(8))
        lo_b = eng.submit([3, 4, 5, 6, 7, 8], _gen(8))
        for _ in range(4):              # both residents mid-decode
            eng.step()
        hi = eng.submit([5, 6, 7, 8, 9, 10], _gen(8), priority=1)
        eng.run_until_complete(max_steps=400)

        # --- chunked-prefill half (chunk_gap attribution) ---
        log2 = RequestLog(k=8) if with_log else None
        eng2 = _engine(max_slots=2, page_size=4, sync_interval=1,
                       enable_prefix_cache=False, prefill_chunk=8,
                       slo=slo(), requestlog=log2)
        short = eng2.submit([1, 2, 3, 4, 5, 6], _gen(16))
        for _ in range(3):              # short request is decoding
            eng2.step()
        chunked = eng2.submit(list(range(1, 41)), _gen(4))
        eng2.run_until_complete(max_steps=400)
        return (eng, eng2, [lo_a, lo_b, hi, short, chunked],
                log1, log2)

    e_off, e2_off, ref_reqs, _, _ = drive(False)
    e_on, e2_on, reqs, log1, log2 = drive(True)
    s1, s2 = log1.snapshot(), log2.snapshot()
    return {
        "requests_tracked": (s1["requests_tracked"]
                             + s2["requests_tracked"]),
        "requests_finished": s1["finished"] + s2["finished"],
        "timeline_events": s1["events_total"] + s2["events_total"],
        "attribution_conservation_max_delta": max(
            s1["conservation_max_delta"],
            s2["conservation_max_delta"]),
        "exemplars_captured": (s1["exemplars"]["kept"]
                               + s2["exemplars"]["kept"]),
        "preemptions": e_on.preemptions,
        "prefill_chunks": e2_on.prefill_chunks,
        "forensics_parity_vs_off": int(
            [r.output_tokens for r in reqs]
            == [r.output_tokens for r in ref_reqs]),
        "leaked_pages": (e_on.blocks.pool_accounting()["leak"]
                         + e2_on.blocks.pool_accounting()["leak"]),
        "host_syncs_delta_vs_off": (
            e_on.host_syncs + e2_on.host_syncs
            - e_off.host_syncs - e2_off.host_syncs),
        "decode_traces_delta_vs_off": (
            e_on.decode_traces + e2_on.decode_traces
            - e_off.decode_traces - e2_off.decode_traces),
        "goodput_ratio": _goodput(reqs),
    }


SCENARIOS = {
    "steady_decode": scenario_steady_decode,
    "prefix_cache": scenario_prefix_cache,
    "deferred_sync": scenario_deferred_sync,
    "goodput_cancel": scenario_goodput_cancel,
    "tp_decode": scenario_tp_decode,
    "spec_decode": scenario_spec_decode,
    "fault_recovery": scenario_fault_recovery,
    "telemetry": scenario_telemetry,
    "overload_degrade": scenario_overload_degrade,
    "profiling": scenario_profiling,
    "usage_meter": scenario_usage_meter,
    "quant_decode": scenario_quant_decode,
    "lora_decode": scenario_lora_decode,
    "batch_lane": scenario_batch_lane,
    "tail_forensics": scenario_tail_forensics,
}


def run_scenarios(names, inject_retrace=False) -> dict:
    results = {}
    for name in names:
        fn = SCENARIOS[name]
        if name == "steady_decode":
            results[name] = fn(inject_retrace=inject_retrace)
        else:
            results[name] = fn()
    return results


def compare(results: dict, baseline: dict):
    """Direction-aware comparison.  Returns (regressions,
    improvements); a counter with no baseline entry is a regression
    (the gate must be told, via --update-baseline, that it exists)."""
    regressions, improvements = [], []
    for scen in sorted(results):
        base_scen = baseline.get(scen, {})
        for name in sorted(results[scen]):
            cur = results[scen][name]
            entry = {"scenario": scen, "counter": name, "current": cur,
                     "direction": DIRECTIONS.get(name, "exact")}
            if name not in base_scen:
                entry["baseline"] = None
                entry["why"] = "no baseline entry"
                regressions.append(entry)
                continue
            ref = base_scen[name]
            entry["baseline"] = ref
            d = entry["direction"]
            if d == "low":
                if cur > ref:
                    regressions.append(entry)
                elif cur < ref:
                    improvements.append(entry)
            elif d == "high":
                if cur < ref:
                    regressions.append(entry)
                elif cur > ref:
                    improvements.append(entry)
            else:
                if cur != ref:
                    regressions.append(entry)
    return regressions, improvements


def load_baseline(path: str) -> dict | None:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    return data.get("scenarios", {})


def save_baseline(path: str, results: dict):
    with open(path, "w") as f:
        json.dump({"version": 1, "scenarios": results}, f, indent=2,
                  sort_keys=True)
        f.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_gate.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated scenario subset "
                         f"(default: {' '.join(sorted(SCENARIOS))})")
    ap.add_argument("--json", action="store_true",
                    help="emit results as JSON")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: tools/"
                         "perf_baseline.json)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the current counters as the new "
                         "baseline and exit 0")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="list scenario names and exit")
    ap.add_argument("--inject-retrace", action="store_true",
                    help="test hook: force an extra decode-step trace "
                         "in steady_decode (the gate must exit 1)")
    args = ap.parse_args(argv)

    if args.list_scenarios:
        width = max(len(s) for s in SCENARIOS)
        for name in sorted(SCENARIOS):
            print(f"{name:<{width}}  {SCENARIOS[name].__doc__.splitlines()[0]}")
        return 0

    if args.scenarios:
        names = [s.strip() for s in args.scenarios.split(",")
                 if s.strip()]
        unknown = [s for s in names if s not in SCENARIOS]
        if unknown:
            print(f"perf_gate.py: unknown scenario(s): "
                  f"{', '.join(unknown)} (have: "
                  f"{', '.join(sorted(SCENARIOS))})", file=sys.stderr)
            return 2
    else:
        names = sorted(SCENARIOS)

    _force_cpu()
    results = run_scenarios(names,
                            inject_retrace=args.inject_retrace)

    if args.update_baseline:
        # subset runs only refresh the scenarios they ran
        merged = load_baseline(args.baseline) or {}
        merged.update(results)
        save_baseline(args.baseline, merged)
        print(f"wrote {len(merged)} scenario"
              f"{'' if len(merged) == 1 else 's'} to "
              f"{os.path.relpath(args.baseline, _REPO_ROOT)}")
        return 0

    baseline = load_baseline(args.baseline)
    if baseline is None:
        print(f"perf_gate.py: no baseline at {args.baseline} — run "
              "with --update-baseline first", file=sys.stderr)
        return 2

    regressions, improvements = compare(results, baseline)
    if args.json:
        sys.stdout.write(json.dumps(
            {"scenarios": results, "regressions": regressions,
             "improvements": improvements}, indent=2, sort_keys=True))
        sys.stdout.write("\n")
    else:
        for e in regressions:
            print(f"REGRESSION {e['scenario']}.{e['counter']}: "
                  f"{e['current']} vs baseline {e['baseline']} "
                  f"(want {e['direction']})"
                  + (f" — {e['why']}" if "why" in e else ""))
        for e in improvements:
            print(f"improved {e['scenario']}.{e['counter']}: "
                  f"{e['current']} vs baseline {e['baseline']} "
                  "(tighten with --update-baseline)")
        n_counters = sum(len(v) for v in results.values())
        print(f"{len(names)} scenario{'' if len(names) == 1 else 's'}, "
              f"{n_counters} counters: "
              f"{len(regressions)} regression"
              f"{'' if len(regressions) == 1 else 's'}, "
              f"{len(improvements)} improvement"
              f"{'' if len(improvements) == 1 else 's'}")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
