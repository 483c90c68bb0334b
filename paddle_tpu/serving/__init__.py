"""paddle_tpu.serving — continuous-batching LLM inference engine.

Orca-style iteration-level scheduling over the paged KV machinery
(ops/pallas/paged_attention.py + models/generation.py), the layer that
turns "can run a batch" into "can serve traffic": requests are
admitted, interleaved, streamed, and cancelled between single-token
decode steps of ONE jitted program.

    from paddle_tpu.serving import create_engine, GenerationConfig
    engine = create_engine(model, max_slots=8, page_size=64,
                           enable_prefix_cache=True, sync_interval=8)
    req = engine.submit(prompt_ids, GenerationConfig(max_new_tokens=32))
    for tok in req.stream():
        ...

``enable_prefix_cache=True`` adds automatic prefix caching (vLLM-style
hash-chained page reuse + copy-on-write tails + LRU eviction): prompts
sharing page-aligned prefixes skip prefill for the shared part and are
charged pages only for their uncached suffix.  Decode state lives on
device and the greedy loop's host runs one step behind it: a step is
dispatched before the one before it is fetched, so the device never
waits for the host's walk of the tokens.  ``sync_interval=N`` is the
rows a fetch brings: the host drains the sampled-token ring once every
N steps (tokens then surface in bursts of N).

Under overload the stack degrades in a defined order instead of all at
once: long prefills chunk in behind decode
(``FLAGS_serving_prefill_chunk``), low-priority decoding residents
preempt-and-swap their KV to a pinned-host tier and resume with greedy
token-for-token parity (``FLAGS_serving_preempt``; ``submit`` takes
``priority=``), and burn-rate shedding 429s the lowest queued class —
see README "Overload handling".

Modules:
  * request.py       — request lifecycle + streaming
  * block_manager.py — KV pages: free list / block tables / prefix
                       cache (refcounts, chain index, CoW, LRU)
  * scheduler.py     — priority admission (FIFO within class),
                       iteration-level eviction, preempt-and-swap
                       victim selection, drain
  * engine.py        — the prefill/decode driver (host scheduling,
                       deferred host sync, chunked prefill, preempted-
                       KV spill/restore) over a parallel.ModelRunner
  * quantize.py      — dense checkpoint -> quantized serving state
                       (int8/int4 QuantizedWeight per projection;
                       embeddings/norms/lm_head stay dense); pairs
                       with ``create_engine(quant=..., kv_quant=...)``
                       for int8 KV pages with per-page scales
  * spec.py          — speculative decoding: prompt-lookup (n-gram)
                       drafter + acceptance bookkeeping; the runner's
                       verify program scores k+1 positions per step
                       with bit-identical greedy outputs
  * lora/            — multi-LoRA serving: AdapterStore (LRU device
                       bank + host parking, per-request row pinning),
                       batched gather-LoRA matmul over the seven
                       projections (``submit(adapter=...)``), and the
                       offline batch lane (BatchJob JSONL drip-feed at
                       ``BATCH_PRIORITY``, ``POST /v1/batches``);
                       ``lora=None`` keeps dense jaxprs byte-identical
  * parallel/        — mesh-aware ModelRunner: tensor-parallel weight
                       placement, head-sharded KV pools, and every
                       jitted program (tp=1 == exact single-chip path)
  * server.py        — OpenAI-compatible HTTP front-end (SSE streaming,
                       backpressure, graceful drain) over one engine
  * router.py        — multi-replica router: prefix-affinity routing,
                       health probing + circuit breaking, bounded retry
  * client.py        — stdlib blocking/streaming HTTP client
  * watchdog.py      — stalled-decode-loop detector (flight-recorder +
                       thread-stack hang dumps)
  * slo.py           — per-request TTFT/TPOT/E2E SLO verdicts and
                       burn-rate gauges
  * faults.py        — deterministic fault injection (seedable
                       FaultPlan firing named faults at existing seams;
                       zero overhead when off)
  * supervisor.py    — engine self-healing: step-failure/stall recovery
                       via runner rebuild + in-flight replay, bounded
                       restart budget, escalate-to-drain

Every request is traced end to end (observability.tracing): the client,
router, server, and engine each open spans under ONE trace id carried
in the W3C ``traceparent`` header; ``GET /debug/trace`` on any replica
or router returns a chrome://tracing-loadable JSON of recent spans,
``GET /debug/flight`` the engine flight-recorder ring.

The engine additionally publishes ``current_phase`` (prefill /
prefill_chunk / decode / verify / host_sync / idle) as a plain
attribute at the same seams that charge
``serving_step_phase_seconds_total``, feeding the phase-attributed
sampling profiler (``FLAGS_obs_profile_interval_s``;
``GET /debug/profile?seconds=N`` on a replica, fanned out by the
router).  Alert fires snapshot evidence bundles via
``observability.capture`` — ``GET /debug/captures`` lists them (see
README "Continuous profiling & diagnostic capture").

Reference analog: the block_multi_head_attention serving path +
paddle_infer predictors, restructured as a vLLM/Orca-style engine.
"""
from __future__ import annotations

from .block_manager import BlockManager  # noqa: F401
from .client import ServingClient, ServingHTTPError  # noqa: F401
from .engine import (  # noqa: F401
    Engine, NonFiniteLogitsError, create_engine)
from .faults import (  # noqa: F401
    FaultPlan, InjectedFault, fault_plan_from_flags)
from .lora import (  # noqa: F401
    AdapterStore, BATCH_PRIORITY, BatchJob, merge_adapter,
    random_adapter)
from .parallel import ModelRunner, parse_mesh  # noqa: F401
from .quantize import quantize_state  # noqa: F401
from .request import GenerationConfig, Request, RequestState  # noqa: F401
from .router import (  # noqa: F401
    NoReplicaAvailable, Replica, Router, RouterServer)
from .scheduler import Scheduler  # noqa: F401
from .server import (  # noqa: F401
    BackpressureError, DrainingError, EngineWorker, ServingServer, serve)
from .slo import SLOConfig, SLOTracker  # noqa: F401
from .spec import NgramProposer, SpecStats  # noqa: F401
from .supervisor import EngineSupervisor  # noqa: F401
from .watchdog import Watchdog  # noqa: F401

__all__ = ["AdapterStore", "BATCH_PRIORITY", "BackpressureError",
           "BatchJob", "BlockManager", "DrainingError", "Engine",
           "EngineSupervisor", "EngineWorker", "FaultPlan",
           "GenerationConfig", "InjectedFault", "ModelRunner",
           "NgramProposer", "NoReplicaAvailable", "NonFiniteLogitsError",
           "Replica", "Request", "RequestState", "Router", "RouterServer",
           "SLOConfig", "SLOTracker", "Scheduler", "ServingClient",
           "ServingHTTPError", "ServingServer", "SpecStats", "Watchdog",
           "create_engine", "fault_plan_from_flags", "merge_adapter",
           "parse_mesh", "quantize_state", "random_adapter", "serve"]
