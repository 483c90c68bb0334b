"""Continuous-batching inference engine over the paged KV pool.

The design that turns the paged kernels into a serving system (Orca's
iteration-level scheduling over vLLM-style PagedAttention, mapped onto
the reference block_multi_head_attention serving path):

  * ONE jitted single-token decode step over a fixed number of decode
    slots and one shared page pool.  Slot occupancy, positions, and
    block tables are *data* (int32 arrays), never shapes — admitting or
    evicting a request between steps re-traces nothing.  The step
    runs ``decode_layer`` of ``models/generation.py``, the layer body
    of the one-shot ``build_generate_fn_paged``, so engine numerics
    match it token for token under greedy decoding.
  * prefill-on-admit: an admitted request's prompt runs through
    ``prefill_layer`` (padded to a page-multiple bucket; one trace per
    bucket) and pages its KV straight into the shared pool; the token
    sampled from the prompt's last logits is the request's first output
    (its TTFT mark).  With ``enable_prefix_cache=True`` the admission
    only reserves pages for (and prefills) the prompt's UNCACHED
    suffix: shared prefix pages come straight from the
    :class:`BlockManager` chain index, a matching partial tail page is
    copied (copy-on-write) on device, and the suffix runs through a
    cached-prefill jit that attends over the resident prefix KV.
  * device-resident decode state: ``table``/``pos``/``tok``, the active
    mask, and a ``[sync_interval, slots]`` sampled-token ring live on
    device; all but the ring are donated through the step — a
    steady-state decode iteration uploads nothing.  The step feeds its
    own argmax forward, so the device never needs the host to go on,
    and the host runs ONE STEP BEHIND it: step n+1 is dispatched first,
    and only then is step n's ring (kept, its copy started at its own
    dispatch) fetched and walked — tokens handed to callers, finishes,
    parked slots, the next scheduling pass all happen while the device
    computes.  ``sync_interval`` is the rows a fetch brings (greedy
    path); the ``[slots, V]`` logits come only when an active request
    actually samples; an admission or eviction patches its slot's row
    in place with one program.
  * what the lag costs and where it yields: a finish is seen one step
    late, so the slot decodes one row too many (discarded on the host;
    its write lands in the slot's own reserved tail or the dump page:
    ``overrun_rows``).  A resident request that samples, or a
    configured draft proposer, needs every token on the host before the
    next step: the loop is then in lockstep (``overlapped_steps`` stays
    put).  A preemption drains what is in flight first and
    ``recover()`` drops it and replays from request state.  An
    admission's first-token fetch still blocks; the step in flight
    stays in flight across it and is walked after the next dispatch
    like any other, so a closed loop keeps every slot in every step.
  * idle slots park on the dump page (table row all-dump, pos 0): their
    lockstep writes land in scratch, their outputs are discarded
    host-side — no masking inside the program.

The device half of all of this — weight placement, the KV pools, the
decode state, and the jitted programs themselves — lives in a
:class:`~paddle_tpu.serving.parallel.ModelRunner` (the engine never
owns a jit directly).  The runner optionally spans a tensor-parallel
mesh (``mesh=`` / ``FLAGS_serving_mesh_tp``): heads and the FFN hidden
dim shard across the ``tp`` axis, the pool shards along the head axis,
and the engine's host-side page table and scheduling stay mesh-
agnostic.  ``tp=1`` is exactly the single-chip programs.

Sampling is host-side per request (greedy = argmax of the step's f32
logits, matching ``_sample``'s greedy branch exactly; stochastic
requests draw from a per-request numpy RNG so results do not depend on
batch composition).  Set ``emit_logits=True`` at engine construction to
serve ``do_sample`` requests — any active sampling request forces a
per-step sync in lockstep (the host must feed the sampled token back
before the next step), so neither the lag nor ``sync_interval`` pays
off while one is resident.
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from .. import observability as _obs
from ..flags import FLAGS
from ..observability.resources import resource_tracker
from ..models.generation import GenerationConfig
from ..models.llama import LlamaConfig
from ..ops.pallas.mla_paged_attention import (
    pages_per_block as latent_pages_per_block)
from ..ops.pallas.paged_attention import page_bytes, pages_per_block
from .block_manager import BlockManager
from .faults import InjectedFault, fault_plan_from_flags
from .parallel import ModelRunner, parse_mesh
from .parallel import latent as _latent
from .parallel import recurrent as _recurrent
from .request import Request, RequestState
from .scheduler import Scheduler

__all__ = ["Engine", "NonFiniteLogitsError", "create_engine"]

_M_STEPS = _obs.counter(
    "serving_decode_steps_total", "engine decode iterations")
_M_TOKENS = _obs.counter(
    "serving_tokens_total", "tokens emitted to requests")
_M_REQUESTS = _obs.counter(
    "serving_requests_total", "finished requests", ("outcome",))
_M_FINISH = _obs.counter(
    "serving_finish_total",
    "finished requests by finish_reason "
    "(length|eos|cancelled|deadline|error)", ("reason",))
_M_RECOVERY = _obs.counter(
    "serving_recovery_total",
    "self-healing events: 'quarantine' = one request failed in place "
    "(finish_reason='error', batch kept running), 'rebuild' = runner "
    "rebuilt + in-flight requests replayed, 'stall' = rebuild declared "
    "by the watchdog, 'drain' = restart budget exhausted, escalated",
    ("kind",))
_M_HOST_SYNCS = _obs.counter(
    "serving_host_syncs_total",
    "device->host transfers on the serving hot path: 'ring' = sampled-"
    "token ring fetch (one per sync_interval decode steps on the greedy "
    "path), 'logits' = [slots, V] logits fetch (only when an active "
    "request samples), 'prefill' = first-token logits at admission",
    ("kind",))
_M_PHASE_SECONDS = _obs.counter(
    "serving_step_phase_seconds_total",
    "engine wall seconds by phase: 'schedule' passes, 'prefill' jit "
    "calls (incl. CoW copies), 'decode' step dispatch, 'host_sync' "
    "blocking ring fetches, 'emit' row walks and callbacks — the "
    "resource tracker's tokens/s and MFU denominator",
    ("phase",))
_M_CHUNKS = _obs.counter(
    "serving_prefill_chunks_total",
    "chunked-prefill jit calls: admission prefill split into "
    "FLAGS_serving_prefill_chunk-token pieces interleaved with decode "
    "steps (chunk K attends chunks 1..K-1 via the cached-prefill jit)")


class NonFiniteLogitsError(ValueError):
    """A request's logits hold no usable probability mass (NaN/Inf from
    the model, or top_k/top_p masked every candidate).  A per-request
    failure: the engine quarantines the offending request
    (finish_reason='error') and keeps the rest of the batch running."""


def _serving_hists():
    buckets = _obs.registry.SERVING_LATENCY_BUCKETS
    ttft = _obs.histogram(
        "serving_ttft_seconds", "request arrival -> first token",
        buckets=buckets)
    tpot = _obs.histogram(
        "serving_tpot_seconds", "inter-token latency during decode",
        buckets=buckets)
    e2e = _obs.histogram(
        "serving_e2e_seconds", "request arrival -> completion",
        buckets=buckets)
    return ttft, tpot, e2e


class Engine:
    """Drives admission, prefill, and the shared decode step.

    Static shapes (fixed at construction — the no-retrace contract):
    ``max_slots`` decode slots, ``table_width`` pages per sequence,
    ``num_pages (+ dump)`` pool rows, ``sync_interval`` ring rows, and
    the per-bucket prefill widths.  Everything per-request is data.
    """

    def __init__(self, model=None, *, config: LlamaConfig = None,
                 state: dict | None = None, max_slots: int = 4,
                 page_size: int = 64, num_pages: int | None = None,
                 max_model_len: int | None = None,
                 emit_logits: bool = False,
                 enable_prefix_cache: bool = False,
                 sync_interval: int = 1, clock=time.monotonic,
                 slo=None, mesh=None, spec_k: int | None = None,
                 prefill_chunk: int | None = None,
                 preempt: bool | None = None, faults=None, usage=None,
                 quant: str | None = None,
                 kv_quant: bool | None = None, lora=None,
                 requestlog=None):
        if model is not None:
            from ..framework.tensor import Tensor
            config = model.config
            state = {k: (v._data if isinstance(v, Tensor) else v)
                     for k, v in model.functional_state().items()}
        if config is None or state is None:
            raise ValueError("pass a model, or both config= and state=")
        # quantized serving: convert the dense checkpoint at
        # construction (embeddings/norms/lm_head stay dense, so the
        # dtype read below still sees the checkpoint dtype).  quant off
        # (the default) leaves the state untouched — zero behavior
        # change, same guard style as faults/sanitizer.
        if quant is None:
            quant = str(FLAGS.get("FLAGS_serving_quant") or "")
        if kv_quant is None:
            kv_quant = bool(FLAGS.get("FLAGS_serving_kv_quant"))
        if quant not in ("", "int8", "int4"):
            raise ValueError(
                f"quant must be '', 'int8', or 'int4', got {quant!r}")
        # the model description names its family; what a family does not
        # have is refused here, by name, before anything is built
        self.latent = _latent.is_latent(config)
        if self.latent:     # the runner refuses tp, kv_quant and spec_k
            _latent.check_options(quant=bool(quant), lora=lora is not None)
        self.recurrent = _recurrent.is_recurrent(config)
        if self.recurrent:  # before anything is quantized; the rest below
            _recurrent.check_options(config, quant=bool(quant))
        self.quant = quant
        self.kv_quant = bool(kv_quant)
        if self.quant:
            from .quantize import quantize_state
            state = quantize_state(state, kind=self.quant)
        # multi-LoRA serving: an AdapterStore sizes the runner's packed
        # adapter bank (rows x rank fixed at construction — the
        # no-retrace contract extends to the bank shape).  lora=None
        # (the default) passes empty tuples through every jitted
        # program: the dense jaxprs are byte-identical to a build
        # without the knob, same guard style as quant/kv_quant.
        self.lora = lora
        if lora is not None and lora.rank is None:
            raise ValueError(
                "the AdapterStore has no adapters and no explicit "
                "rank= — the runner cannot size the bank (register "
                "one adapter first, or pass AdapterStore(rank=...))")
        self.config = config
        self.state = state
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.max_model_len = int(max_model_len
                                 or config.max_position_embeddings)
        if self.max_model_len > config.max_position_embeddings:
            raise ValueError(
                f"max_model_len {self.max_model_len} exceeds the model's "
                f"max_position_embeddings {config.max_position_embeddings}")
        self.table_width = -(-self.max_model_len // self.page_size)
        if num_pages is None:       # full residency: every slot can run
            num_pages = self.max_slots * self.table_width  # at max length
        self.emit_logits = bool(emit_logits)
        self.enable_prefix_cache = bool(enable_prefix_cache)
        self.sync_interval = int(sync_interval)
        if self.sync_interval < 1:
            raise ValueError(
                f"sync_interval must be >= 1, got {sync_interval}")
        self._clock = clock
        if mesh is None:
            mesh = int(FLAGS.get("FLAGS_serving_mesh_tp") or 1)
        self.tp = parse_mesh(mesh)
        if spec_k is None:
            spec_k = int(FLAGS.get("FLAGS_serving_spec_k") or 0)
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if self.spec_k:
            from .spec import NgramProposer, SpecStats
            self._proposer = NgramProposer(self.spec_k)
            self._spec = SpecStats()
        else:
            self._proposer = None
            self._spec = None
        if prefill_chunk is None:
            prefill_chunk = int(
                FLAGS.get("FLAGS_serving_prefill_chunk") or 0)
        self.prefill_chunk = max(int(prefill_chunk), 0)
        if preempt is None:
            # on by default, but where the family refuses the spill it
            # is off unless asked for (and then refused by name)
            preempt = (bool(FLAGS.get("FLAGS_serving_preempt"))
                       and not self.recurrent)
        self.preempt = bool(preempt)
        if self.recurrent:
            # what a per-slot recurrent state cannot do yet, by name
            _recurrent.check_options(
                config, mesh=self.tp > 1, kv_quant=self.kv_quant,
                lora=lora is not None,
                spec_k=self.spec_k > 0,
                enable_prefix_cache=self.enable_prefix_cache,
                preempt=self.preempt,
                prefill_chunk=self.prefill_chunk > 0)
        # chaos harness: None (the default when FLAGS_serving_fault_plan
        # is empty) keeps every injection site to a single None test
        self.faults = fault_plan_from_flags() if faults is None else faults

        self.blocks = BlockManager(
            num_pages, self.page_size,
            enable_prefix_cache=self.enable_prefix_cache,
            faults=self.faults)
        # chunked admissions must not be cache-matchable until their KV
        # has actually been written: the scheduler admits every queue
        # head before the engine runs any prefill, so eager registration
        # would let a same-pass admission attend over unwritten pages.
        # allocate_seq defers registration past this many fresh tokens
        # and the engine publishes after the last chunk lands.
        self.blocks.defer_publish = self.prefill_chunk
        self.scheduler = Scheduler(self.blocks, self.max_slots,
                                   clock=self._clock,
                                   preempt_enabled=self.preempt)
        self.scheduler._finalize = self._finalize
        # preempt-and-swap: the scheduler picks the victim, the engine
        # owns the device side (spill exclusive KV pages to the host
        # tier, release the pages, park the slot)
        self.scheduler._preempt = self._preempt
        # every eviction parks its slot — not just the length/eos path in
        # _emit.  A cancel/deadline eviction inside scheduler.schedule()
        # would otherwise leave the slot's table/pos pointing at freed
        # pages, and the lockstep decode step (which writes KV for every
        # slot) would corrupt them once reallocated to a new request.
        self.scheduler._on_evict = self._park
        # per-request cost attribution (observability.usage): every
        # call site below is a single ``is not None`` test, so the
        # default (no meter) adds zero work to the serving path
        self.usage = usage
        if usage is not None:
            if usage._clock is None:
                usage._clock = self._clock   # page-seconds on engine clock
            self.blocks.usage = usage        # page hold/release + host tier
            self.scheduler.usage = usage     # fair-share victim selection
            if slo is not None:
                slo.verdict_hook = usage.slo_verdict
            # the process-active meter: obs.dump() writes usage.json
            # from it (last engine built wins, like the profiler)
            _obs.set_active_usage(usage)
        # tail-latency forensics (observability.requestlog): per-
        # request lifecycle timelines + critical-path attribution +
        # SLO-violation exemplars.  Same zero-overhead-off contract:
        # every seam below is a single ``is not None`` test when no
        # log is attached (pinned by the tail_forensics gate scenario)
        self.requestlog = requestlog
        if requestlog is not None:
            if slo is not None:
                # violation exemplars ride the tracker's verdicts; the
                # usage meter's verdict_hook is untouched — the two
                # subsystems compose through separate hooks
                slo.exemplar_hook = requestlog.slo_verdict
            # the process-active log: obs.dump() writes exemplars.json
            _obs.set_active_requestlog(requestlog)

        L = config.num_hidden_layers
        if self.latent:
            dtype = state[_latent.EMBED].dtype
            self._embed_itemsize = int(np.dtype(dtype).itemsize)
            # one latent row a token a layer, no heads to shard
            sizing = self.blocks.pool_bytes(
                num_layers=L, dtype_itemsize=self._embed_itemsize,
                latent_width=_latent.pool_shape(config, 0, 1)[-1])
        elif self.recurrent:
            dtype = state[_recurrent.embed_name(config)].dtype
            self._embed_itemsize = int(np.dtype(dtype).itemsize)
            # pages for the attention layers alone; the other layers'
            # state is a fixed number of bytes a slot, which the runner
            # counts (``recurrent_state_bytes``)
            sizing = self.blocks.pool_bytes(
                num_layers=len(config.attention_layers),
                num_kv_heads=config.num_key_value_heads,
                head_dim=config.head_dim,
                dtype_itemsize=self._embed_itemsize)
        else:
            kvh, hd = config.num_key_value_heads, config.head_dim
            dtype = state["llama.embed_tokens.weight"].dtype
            self._embed_itemsize = int(np.dtype(dtype).itemsize)
            # head-sharded pool sizing: the BlockManager knows how many
            # bytes each mesh position holds, the runner reports it
            sizing = self.blocks.pool_bytes(
                num_layers=L, num_kv_heads=kvh, head_dim=hd,
                dtype_itemsize=self._embed_itemsize, tp=self.tp,
                kv_quant=self.kv_quant)
        # the device half: mesh, weight placement, pools, decode state,
        # and every jitted program live behind the runner seam.  The
        # kwargs are kept so recover() can rebuild an identical runner
        # after a poisoned step (fresh pools, same static shapes).
        self._runner_kw = dict(
            tp=self.tp, max_slots=self.max_slots,
            page_size=self.page_size, table_width=self.table_width,
            num_pages=self.blocks.num_pages,
            dump_page=self.blocks.dump_page,
            sync_interval=self.sync_interval,
            emit_logits=self.emit_logits, spec_k=self.spec_k,
            kv_quant=self.kv_quant,
            lora_slots=(self.lora.capacity if self.lora is not None
                        else 0),
            lora_rank=(self.lora.rank if self.lora is not None else 0),
            per_device_pool_bytes=sizing["per_device_bytes"])
        self.runner = ModelRunner(config, state, **self._runner_kw)
        if self.lora is not None:
            # bind the store to the bank: resident adapters (if any)
            # upload now; later acquires patch single rows in place
            self.lora.attach(self.runner)
        # the paged decode kernel's unit of work, by its own rule (the
        # latent kernel has its own; the K/V kernel's reads the bytes of
        # a page as a device's pool holds it): the tokens one grid step
        # covers and the steps a slot's row makes
        if self.latent:
            blk = latent_pages_per_block(self.page_size, self.table_width)
        else:
            blk = pages_per_block(
                self.table_width,
                page_bytes(self.runner.kpool) // self.tp)
        self._block_tokens = blk * self.page_size
        self._blocks_per_row = -(-self.table_width // blk)

        # host-side mirrors of the slot state (bookkeeping + targeted
        # device patches on admit/evict; NEVER re-uploaded per step)
        self.table = np.tile(self.blocks.empty_row(self.table_width),
                             (self.max_slots, 1))
        self._pos = np.zeros((self.max_slots,), np.int32)
        self._tok = np.zeros((self.max_slots,), np.int32)
        self._active = np.zeros((self.max_slots,), np.int32)
        # per-slot adapter bank row (0 = the permanently-zero no-adapter
        # row); patched on admit/evict alongside the other mirrors
        self._aidx = np.zeros((self.max_slots,), np.int32)
        self._ring_cursor = 0           # host mirror of the ring index
        # ring rows the host has not consumed yet, in decode order:
        # [(ring row, [(slot, request), ...], drafts-or-None), ...] —
        # the third element is the verify step's {slot: draft tokens}
        # (a verify row syncs immediately, so it is always solitary).
        # ``_pending`` is the group still filling (sync_interval rows);
        # ``_flight`` the full group whose ring the runner holds: it is
        # fetched and walked after the NEXT step's dispatch (_land)
        self._pending: list[tuple[int, list, dict | None]] = []
        self._flight: list[tuple[int, list, dict | None]] | None = None
        self._last_logits = None        # device handle, fetched lazily

        self.decode_steps = 0       # mirror of serving_decode_steps_total
        # grid steps of the paged decode kernel that held visible tokens,
        # and all of them, summed over decode steps (_count_paged_blocks)
        self.paged_blocks_live = 0
        self.paged_blocks_grid = 0
        # the device's expert counters as of the last stats() (the decode
        # span carries them; a step never fetches them)
        self._counters_seen: dict = {}
        self.host_syncs = 0         # ring fetches (1 per sync_interval)
        # decode steps dispatched while an earlier step's row was still
        # unfetched (the device did not wait for the host), and rows
        # decoded for a slot whose request had already ended
        self.overlapped_steps = 0
        self.overrun_rows = 0
        self.logit_fetches = 0      # [slots, V] transfers (sampling only)
        # chunked prefill: in-flight admission prefills advanced one
        # chunk per engine step — {slot: state dict} (see _begin_chunks)
        self._chunking: dict[int, dict] = {}
        self.prefill_chunks = 0     # mirror of serving_prefill_chunks_total
        self.preemptions = 0        # successful preempt-and-swap spills
        self.spill_aborts = 0       # preemptions aborted by a failed spill
        # overload-degradation witness: the most prompt tokens prefilled
        # between two decode steps — bounded by prefill_chunk when
        # chunking is on, by the longest prompt when it is off
        self._prefill_since_decode = 0
        self.max_prefill_gap = 0
        # self-healing mirrors of serving_recovery_total
        self.recoveries = 0         # runner rebuilds (recover() calls)
        self.quarantines = 0        # requests failed in place
        self.replayed_requests = 0  # in-flight requests re-prefilled
        # per-phase wall seconds (mirror of serving_step_phase_seconds_
        # total; resource_snapshot() reports them per engine), each the
        # summed intervals of one span name (see _phase); queue_wait_s
        # is the requests' summed wait for admission, not engine time
        self.timings = {"schedule_s": 0.0, "prefill_s": 0.0,
                        "decode_s": 0.0, "host_sync_s": 0.0,
                        "emit_s": 0.0, "queue_wait_s": 0.0}
        # monotonically increasing iteration counter.  The serving
        # watchdog reads it lock-free (comparing against active_count)
        # to detect a wedged decode loop — never reset.
        self.progress = 0
        # what the engine is doing RIGHT NOW, published for the
        # sampling profiler (observability/profiling.py): prefill /
        # prefill_chunk / decode / verify / host_sync / idle.  A plain
        # attribute store at each section entry — read lock-free from
        # the sampler thread, same contract as the watchdog's
        # ``progress`` reads; costs nothing when no profiler runs.
        self.current_phase = "idle"
        self.slo = slo              # optional slo.SLOTracker
        self._emitted = 0           # tokens handed to requests, ever
        self._rngs: dict[int, np.random.Generator] = {}
        self._ttft, self._tpot, self._e2e = _serving_hists()
        self._pages_hist = _obs.histogram(
            "serving_pages_in_use_hist",
            "pages-in-use sampled at each decode step",
            buckets=_pages_buckets(self.blocks.num_pages))

        # resource tracker: model size + device kind feed the MFU
        # estimate (tokens/s * 2 * n_params / peak_flops)
        n_params = sum(int(np.prod(v.shape))
                       for v in state.values() if hasattr(v, "shape"))
        try:
            device_kind = jax.devices()[0].device_kind
        except Exception:
            device_kind = None
        resource_tracker().set_model(n_params=n_params,
                                     device_kind=device_kind)

        # quantized-serving metric surface: registered only when quant
        # is on, so a dense engine exports exactly the pre-quant set
        if self.quant or self.kv_quant:
            _obs.gauge(
                "serving_quant_weight_bits",
                "weight-only quantization width of the serving state "
                "(8 = int8, 4 = nibble-packed int4, 0 = dense weights)"
            ).set({"int8": 8, "int4": 4}.get(self.quant, 0))
            _obs.gauge(
                "serving_quant_kv_page_bits",
                "KV pool element width: 8 under the int8 page mode "
                "(per-(page-row, head) f32 scales ride separately), "
                "else the checkpoint dtype width"
            ).set(8 if self.kv_quant
                  else int(np.dtype(dtype).itemsize) * 8)
            _obs.gauge(
                "serving_quant_kv_page_bytes",
                "bytes one KV page pair (k + v + scales) occupies — "
                "what each spill/restore moves and what pool sizing "
                "charges per page"
            ).set(self._page_bytes())
            # quant.json provider for obs.dump() (last engine wins,
            # like the profiler/usage holders)
            _obs.set_active_quant(self)

        # multi-LoRA metric surface: registered only when a store is
        # attached, so a dense engine exports exactly the pre-LoRA set
        if self.lora is not None:
            _obs.gauge(
                "serving_lora_bank_bytes",
                "device bytes the packed adapter bank occupies "
                "(all rows, every projection, + the scale vector)"
            ).set(self.runner.lora_bank_bytes())
            # lora.json provider for obs.dump() (last engine wins)
            _obs.set_active_lora(self)

    # ------------------------------------------------ runner delegation
    # python-side mirror of serving_decode_step_traces_total: counted at
    # trace time inside the runner's step body (the no-retrace contract)
    @property
    def decode_traces(self) -> int:
        return self.runner.decode_traces

    @property
    def kpool(self):
        return self.runner.kpool

    @property
    def vpool(self):
        return self.runner.vpool

    @property
    def _prefill_fns(self):
        return self.runner._prefill_fns

    @property
    def _prefill_cached_fns(self):
        return self.runner._prefill_cached_fns

    # ----------------------------------------------------------- intake
    def submit(self, prompt, gen: GenerationConfig | None = None, *,
               deadline: float | None = None, on_token=None,
               arrival_time: float | None = None, trace=None,
               priority: int = 0, tenant: str | None = None,
               adapter: str | None = None) -> Request:
        """``trace`` is an optional tracing.SpanContext (or Span) the
        request's root span is parented under — the server passes the
        extracted ``traceparent`` here so the engine-side spans join the
        caller's distributed trace.  Without it the root span inherits
        the submitting thread's current span, if any.  ``priority``
        sets the scheduling class: higher admits first and (with
        preemption enabled) may preempt lower-priority residents.
        ``tenant`` is the billing dimension for the usage meter
        (HTTP ``X-Tenant`` / body field; default ``"anon"``).
        ``adapter`` names a LoRA adapter registered with the engine's
        :class:`~paddle_tpu.serving.lora.AdapterStore` (HTTP
        ``X-Adapter`` / body field); unknown names are rejected here,
        before any page or span is held."""
        req = Request(prompt, gen, deadline=deadline, on_token=on_token,
                      priority=priority, tenant=tenant, adapter=adapter,
                      arrival_time=(self._clock() if arrival_time is None
                                    else arrival_time))
        if req.adapter is not None and self.lora is None:
            raise ValueError(
                f"request names adapter {req.adapter!r} but the engine "
                "was built without lora= (pass an AdapterStore)")
        total = req.prompt.size + req.gen.max_new_tokens
        if total > self.max_model_len:
            raise ValueError(
                f"prompt ({req.prompt.size}) + max_new_tokens "
                f"({req.gen.max_new_tokens}) = {total} exceeds "
                f"max_model_len {self.max_model_len}")
        need = self.blocks.pages_needed(req.prompt.size,
                                        req.gen.max_new_tokens)
        if need > self.blocks.num_pages:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.blocks.num_pages}; it could never be admitted "
                "(raise num_pages or lower max_new_tokens)")
        if req.gen.do_sample and not self.emit_logits:
            raise ValueError(
                "do_sample requests need an engine built with "
                "emit_logits=True (host-side sampling reads the logits)")
        # pin the adapter's bank row for the request's whole lifetime
        # (submit -> _finalize): preemption parks KV, never the adapter,
        # so a resume re-enters decode on the same row.  Unknown names
        # KeyError here; a full bank (every row pinned) RuntimeErrors.
        req._adapter_row = (self.lora.acquire(req.adapter)
                            if self.lora is not None else 0)
        req._engine = self
        # spans only after every validation — a rejected submit must not
        # leave dangling open spans
        tr = _obs.tracer()
        attrs = {"req": req.id, "prompt_len": int(req.prompt.size),
                 "max_new_tokens": int(req.gen.max_new_tokens)}
        req.trace_parent = trace
        if trace is not None:
            req.root_span = tr.start_span("request", parent=trace,
                                          attributes=attrs)
        else:
            req.root_span = tr.start_span("request", attributes=attrs)
        req.queue_span = tr.start_span("scheduler.queue_wait",
                                       parent=req.root_span)
        if self.requestlog is not None:
            # after the root span exists so the timeline carries the
            # trace id (the /debug/trace <-> /debug/exemplars join)
            self.requestlog.attach(req)
        try:
            _obs.flight("engine", "submit", req=req.id,
                        prompt_len=int(req.prompt.size),
                        trace=req.root_span.trace_id)
            if self.usage is not None:
                # register BEFORE the scheduler sees the request so any
                # admission-time page holds already attribute to it
                self.usage.on_submit(req)
            self.scheduler.submit(req)
        except BaseException:
            # a rejected submit (queue full, shutdown race) must not
            # leave the request's spans open in the tracer ring — nor
            # its adapter row pinned
            if self.lora is not None and req.adapter is not None:
                self.lora.release(req.adapter)
            if self.requestlog is not None:
                self.requestlog.discard(req.id)
            req.queue_span.end()
            req.root_span.end()
            raise
        return req

    # -------------------------------------------------------- main loop
    def step(self) -> bool:
        """One engine iteration: evict/admit (scheduler pass), prefill
        admissions, then one decode step over the active slots, all of
        them in one program.  The step is dispatched first; the tokens
        that reach callers in this iteration are those of the step
        before it (see the module docstring).  Returns whether any work
        happened."""
        with _obs.tracer().phase("engine.step", parent=None,
                                 step=self.progress) as st:
            now = self._clock()
            evicted = self.scheduler.evictions
            with self._phase("engine.schedule", "schedule") as ph:
                admitted = self.scheduler.schedule(now)
                ph.set_attribute("admitted", len(admitted))
                ph.set_attribute("evicted",
                                 self.scheduler.evictions - evicted)
            # chunk states registered by THIS step's admissions already
            # ran their first chunk inside _prefill — snapshot the
            # in-flight set first so each prefill advances exactly one
            # chunk per step
            inflight = list(self._chunking)
            for slot, req in admitted:
                self._prefill(slot, req)
            advanced = 0
            for slot in inflight:
                if slot in self._chunking:  # evicted states drop out
                    self._advance_chunk(slot)
                    advanced += 1
            active = [i for i, r in enumerate(self.scheduler.slots)
                      if r is not None and r.state == RequestState.DECODE]
            st.set_attribute("active", len(active))
            if active:
                self._decode(active)
            else:
                # gap witness: nothing was decoding, so this step's
                # prefill work starved no resident — the stall meter
                # restarts
                self._prefill_since_decode = 0
                # rows still out are of requests that have ended
                self._settle()
            self.current_phase = "idle"
            self.progress += 1      # watchdog heartbeat
        return bool(admitted) or bool(active) or bool(advanced)

    def run_until_complete(self, max_steps: int | None = None):
        """Drive step() until no live or queued work remains."""
        steps = 0
        while self.scheduler.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"engine did not quiesce within {max_steps} steps")

    def drain(self):
        """Graceful drain: stop admitting; finish what is running.
        Queued requests stay queued until :meth:`resume`."""
        self.scheduler.drain()
        while self.scheduler.active_count:
            self.step()

    def resume(self):
        self.scheduler.resume()

    # ----------------------------------------------------------- prefill
    def _prefill(self, slot: int, req: Request):
        if req.queue_span is not None:      # queue wait ends at admission
            req.queue_span.end()
            req.queue_span = None
        if req.admitted_at is not None:
            # ledger: queue-wait seconds — every wait (first admission
            # and each preemption re-queue) sums into the same field
            waited = max(0.0, req.admitted_at - req._queued_since)
            req.queue_seconds += waited
            self.timings["queue_wait_s"] += waited
            req._queued_since = req.admitted_at
            if req.timeline is not None:
                # a re-queue wait after preemption charges to the
                # preempted bucket — the request would not have waited
                # had it not been preempted
                req.timeline.note(
                    "preempted" if req.num_generated else "queue",
                    req.admitted_at, event="admit", slot=slot,
                    then="prefill_compute")
        if req.num_generated:
            # re-admission of a preempted request: rebuild device KV
            # from the prefix cache + host spill tier + a re-prefill of
            # the remainder; no token is emitted
            self._resume(slot, req)
            return
        self.current_phase = "prefill"
        failed = None
        with self._phase("engine.prefill", "prefill", parent=req.root_span,
                         req=req.id, slot=slot) as ph:
            ps = self.page_size
            plen = req.prompt.size
            meta = self.blocks.seq_meta(req.id)
            cached = int(meta["cached_len"])
            row = self.blocks.table_row(req.id, self.table_width)
            # chunked admission: CoW once up front, then one chunk per
            # engine step so decoding slots keep stepping in between
            chunked = bool(self.prefill_chunk
                           and plen - cached > self.prefill_chunk)
            ph.set_attribute("cached_tokens", cached)
            ph.set_attribute("cow", meta["cow_src"] is not None)
            ph.set_attribute("kind", "chunked_admit" if chunked else
                             "cached_suffix" if cached else "full")
            try:
                if meta["cow_src"] is not None:
                    # copy-on-write: duplicate the matching tail page
                    # into this request's own tail before any writes
                    # land there
                    self.runner.copy_page(int(meta["cow_src"]),
                                          int(row[cached // ps]))
                if not chunked:
                    logits, bucket = self._dispatch_prefill(
                        req.prompt[cached:], cached, row, req._adapter_row,
                        slot)
                    ph.set_attribute("bucket", bucket)
                req.num_cached_tokens = cached
                req.prefill_cached_tokens += cached
                req.prefill_computed_tokens += plen - cached
                if not chunked:
                    self._note_gap(plen - cached)
                    tok = self._fetch_first_token(slot, req, logits)
                    now = self._clock()
                    self._ttft.observe(now - req.arrival_time)
            except Exception as e:
                failed = e
        if failed is not None:
            # a failed prefill kills ONE request, never the process:
            # pages release, the slot parks, the batch keeps running
            self._quarantine(slot, req, failed, self._clock())
            return
        if chunked:
            self._begin_chunks(slot, req, req.prompt, cached, row)
            return
        if req.timeline is not None:
            req.timeline.note_prefill(now, cached=cached,
                                      computed=plen - cached, slot=slot)
        _obs.flight("engine", "prefill", req=req.id, slot=slot,
                    bucket=bucket, cached=cached)
        self._enter_decode(slot, req, row, plen, tok, now)
        if self._proposer is not None:
            # seed the drafter with the prompt; emitted tokens extend
            # the history through _emit
            self._proposer.register(req.id, req.prompt)
        self._emit(slot, req, tok, now)

    def _dispatch_prefill(self, tokens, cached: int, row,
                          adapter_row: int, slot: int = 0):
        """Hand ``tokens`` (what follows the ``cached`` resident tokens
        of a sequence), padded to their page-multiple bucket, to the
        runner's prefill program, with the ``slot`` the sequence decodes
        in (where a family keeps a state by slot).  Returns (logits
        handle, bucket)."""
        n = len(tokens)
        bucket = -(-n // self.page_size) * self.page_size
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = tokens
        with _obs.tracer().phase("engine.prefill.dispatch"):
            if cached == 0:
                logits = self.runner.prefill(ids, n, row,
                                             adapter_row=adapter_row,
                                             slot=slot)
            else:
                logits = self.runner.prefill_cached(
                    ids, n, cached, row, adapter_row=adapter_row)
        return logits, bucket

    def _fetch_first_token(self, slot: int, req: Request, logits) -> int:
        """The blocking half of an admission's prefill: fetch the
        last-position logits and pick the request's first token."""
        _M_HOST_SYNCS.labels("prefill").inc()
        with _obs.tracer().phase("engine.prefill.fetch"):
            logits_row = np.asarray(logits)[0]
            if (self.faults is not None
                    and self.faults.check("nan_logits", req=req.id,
                                          slot=slot,
                                          phase="prefill") is not None):
                logits_row = np.full_like(logits_row, np.nan)
            return self._pick_token(req, logits_row)

    # --------------------------------------------------- chunked prefill
    def _note_gap(self, tokens: int):
        """Account ``tokens`` prompt tokens prefilled since the last
        decode step — the overload-degradation witness: chunking bounds
        this by ``prefill_chunk``; without it one long prompt stalls
        every decoding slot for its whole length."""
        self._prefill_since_decode += int(tokens)
        if self._prefill_since_decode > self.max_prefill_gap:
            self.max_prefill_gap = self._prefill_since_decode

    def _begin_chunks(self, slot: int, req: Request, ids_all, done: int,
                      row, *, resume_tok: int | None = None):
        """Arm chunked prefill for ``slot`` and run its first chunk:
        ``ids_all`` past position ``done`` pages in ``prefill_chunk``
        tokens at a time, one chunk per engine step.  ``resume_tok``
        marks a preempted-request resume — the final chunk's logits are
        discarded and decode re-enters with that token instead of
        sampling a new one."""
        self._chunking[slot] = {
            "req": req, "ids": np.asarray(ids_all, np.int32).reshape(-1),
            "done": int(done), "row": row, "resume_tok": resume_tok,
            "chunks": 0}
        self._advance_chunk(slot)

    def _advance_chunk(self, slot: int):
        """Run ONE prefill chunk for an in-flight admission.  Chunk K
        attends chunks 1..K-1 through the existing cached-prefill jit
        (arbitrary non-aligned boundaries — no new traced program
        shapes); intermediate chunks never fetch logits, so they cost
        no host sync.  Between chunks the engine keeps decoding and
        ``progress`` keeps heartbeating, so a long prompt neither
        stalls resident TPOT nor trips the watchdog."""
        st = self._chunking[slot]
        req = st["req"]
        if req.timeline is not None:
            # time since the last chunk (decode steps for other slots
            # ran in between) is this request's chunk-gap cost
            req.timeline.note("chunk_gap", self._clock(),
                              then="prefill_compute")
        ids_all = st["ids"]
        n = int(ids_all.size)
        done = st["done"]
        this = min(self.prefill_chunk, n - done)
        last = done + this >= n
        self.current_phase = "prefill_chunk"
        failed = None
        with self._phase("engine.prefill", "prefill", parent=req.root_span,
                         req=req.id, slot=slot, kind="chunk") as ph:
            try:
                logits, _ = self._dispatch_prefill(
                    ids_all[done:done + this], done, st["row"],
                    getattr(req, "_adapter_row", 0), slot)
                st["chunks"] += 1
                self.prefill_chunks += 1
                req.prefill_chunks += 1
                _M_CHUNKS.inc()
                self._note_gap(this)
                if not last:
                    st["done"] = done + this
                elif st["resume_tok"] is None:
                    # admission: the first output token samples from the
                    # final chunk's last-position logits
                    tok = self._fetch_first_token(slot, req, logits)
                else:
                    # resume: the last emitted token re-enters as the
                    # next decode input; the replay logits are discarded
                    tok = int(st["resume_tok"])
            except Exception as e:
                failed = e
            if failed is None and last:
                self._chunking.pop(slot, None)
                # the full chunked prefix is device-resident now —
                # register it in the prefix-cache chain (deferred at
                # allocate_seq)
                self.blocks.publish_seq(req.id, ids_all)
                now = self._clock()
                ph.set_attribute("kind", "chunked")
                ph.set_attribute("chunks", st["chunks"])
                ph.set_attribute("cached_tokens", req.num_cached_tokens)
                ph.set_attribute("resume", st["resume_tok"] is not None)
        if failed is not None:
            self._chunking.pop(slot, None)
            self._quarantine(slot, req, failed, self._clock())
            return
        if not last:
            if req.timeline is not None:
                req.timeline.note(
                    "prefill_compute", self._clock(), event="chunk",
                    slot=slot, done=done + this, total=n,
                    then="chunk_gap")
            _obs.flight("engine", "prefill_chunk", req=req.id,
                        slot=slot, done=done + this, total=n)
            return
        if req.timeline is not None:
            req.timeline.note("prefill_compute", now, event="chunk",
                              slot=slot, done=n, total=n,
                              chunks=st["chunks"], then="decode")
        _obs.flight("engine", "prefill", req=req.id, slot=slot,
                    chunks=st["chunks"], cached=req.num_cached_tokens)
        self._enter_decode(slot, req, st["row"], n, tok, now)
        if st["resume_tok"] is None:
            self._ttft.observe(now - req.arrival_time)
            if self._proposer is not None:
                self._proposer.register(req.id, req.prompt)
            self._emit(slot, req, tok, now)
        elif self._proposer is not None:
            self._proposer.register(req.id, np.append(ids_all, tok))

    def _enter_decode(self, slot: int, req: Request, row, pos: int,
                      tok: int, now: float):
        """Flip an admitted request into decode: patch the slot mirrors
        + the device row, open the decode span."""
        self.table[slot] = row
        self._pos[slot] = pos
        self._tok[slot] = tok
        self._active[slot] = 1
        self._aidx[slot] = req._adapter_row
        self._push_slot(slot)
        req.state = RequestState.DECODE
        if req.root_span is not None:
            req.decode_span = _obs.tracer().start_span(
                "engine.decode", parent=req.root_span,
                attributes={"req": req.id, "slot": slot})

    # -------------------------------------------------------- preemption
    def _preempt(self, slot: int) -> bool:
        """Scheduler callback behind preempt-and-swap: spill ``slot``'s
        exclusive committed KV pages to the BlockManager host tier,
        release its pages (complete chunks re-register in the prefix-
        cache chain when the cache is on), and park the slot.  Returns
        False — victim untouched, preemption aborted — when a page copy
        fails (the ``spill_fail`` chaos site); parked copies from the
        aborted attempt are discarded, so the pool census stays exact.
        What is in flight is walked first: the victim's committed tokens
        and the ledger must be level with the device.  Where that walk
        saw a request end, room was made without a victim: nothing is
        preempted and the scheduler's next pass finds the room."""
        evictions = self.scheduler.evictions
        self._settle()
        req = self.scheduler.slots[slot]
        if (req is None or req.state != RequestState.DECODE
                or self.scheduler.evictions != evictions):
            return False
        with _obs.tracer().phase("engine.preempt_spill",
                                 parent=req.root_span, req=req.id,
                                 slot=slot) as ph:
            return self._spill(slot, req, ph)

    def _spill(self, slot: int, req: Request, ph) -> bool:
        """The body of :meth:`_preempt`, inside its span ``ph``."""
        if req.timeline is not None:
            # decoding ends here; the spill loop below (and, if the
            # preemption lands, the re-queue wait and the restore)
            # charges to the preempted bucket
            req.timeline.note("decode", self._clock(), then="preempted")
        tokens = req.resume_tokens()
        parked: list[str] = []
        for page, digest in self.blocks.spill_plan(req.id, tokens):
            if (self.faults is not None
                    and self.faults.check("spill_fail", req=req.id,
                                          page=page) is not None):
                self.blocks.host_discard(parked)
                self.spill_aborts += 1
                if req.timeline is not None:
                    # the aborted spill attempt was still preemption
                    # cost; the request goes back to decoding
                    req.timeline.note("preempted", self._clock(),
                                      event="spill_abort", slot=slot,
                                      then="decode")
                _obs.flight("engine", "spill_abort", req=req.id,
                            slot=slot, page=page,
                            parked_dropped=len(parked))
                ph.set_attribute("aborted", True)
                return False
            arrays = self.runner.read_page(page)
            self.blocks.host_put(digest, *arrays)
            # ledger: charged per page parked, mirroring host_put's
            # global counters (an abort on a LATER page keeps both) —
            # int8 pages park (k, v, kscale, vscale) and the byte sum
            # reflects the quantized footprint
            req.spilled_pages += 1
            req.spill_bytes += sum(a.nbytes for a in arrays)
            if self.usage is not None:
                self.usage.on_host_park(req, digest)
            parked.append(digest)
        ph.set_attribute("pages", len(parked))
        self.blocks.release_preempted(req.id, tokens)
        self._park(slot)
        self.preemptions += 1
        # back to the queue: the ledger's queue-wait anchor restarts so
        # queue_seconds sums this wait too
        req._queued_since = self._clock()
        if req.timeline is not None:
            req.timeline.note("preempted", req._queued_since,
                              event="preempt", slot=slot,
                              pages=len(parked), then="preempted")
        if self._proposer is not None:
            self._proposer.drop(req.id)  # resume re-registers history
        if req.decode_span is not None:
            req.decode_span.set_attribute("preempted", True)
            req.decode_span.set_attribute("generated", req.num_generated)
            req.decode_span.end()
            req.decode_span = None
        if req.root_span is not None:
            # back to the queue: a fresh queue-wait span covers the
            # time until re-admission
            req.queue_span = _obs.tracer().start_span(
                "scheduler.queue_wait", parent=req.root_span,
                attributes={"resume": True})
        _obs.flight("engine", "preempt_spill", req=req.id, slot=slot,
                    pages=len(parked))
        return True

    def _resume(self, slot: int, req: Request):
        """Re-admit a preempted request.  Its effective prompt is
        prompt + generated-so-far; device KV rebuilds from, in order,
        the prefix-cache match recorded at allocate_seq, the host spill
        tier (page-granular, content-addressed), and a re-prefill of
        whatever remains — then decode continues with the last emitted
        token as the next input, token-for-token identical to an
        uninterrupted greedy run (parity asserted in tests)."""
        self.current_phase = "prefill"
        failed = None
        with self._phase("engine.resume", "prefill", parent=req.root_span,
                         req=req.id, slot=slot) as ph:
            ps = self.page_size
            if self.usage is not None:
                # this request is no longer waiting on its parked pages
                # — per-request host-tier accrual stops here (the tenant
                # keeps paying until the digests fall out of the host
                # LRU)
                self.usage.on_host_release(req)
            tokens = req.resume_tokens()
            ids_all = tokens[:-1]
            n = int(ids_all.size)
            meta = self.blocks.seq_meta(req.id)
            # ledger: the uncapped match length is what allocate_seq
            # added to the global cached_tokens counter for this resume
            req.prefill_cached_tokens += int(meta["cached_len"])
            cached = min(int(meta["cached_len"]), n)
            row = self.blocks.table_row(req.id, self.table_width)
            restored = 0
            chunked = False
            tok = int(tokens[-1])
            try:
                if meta["cow_src"] is not None:
                    # tail CoW page from the admission match: duplicate
                    # it before any writes land (same rule as fresh
                    # admission)
                    self.runner.copy_page(int(meta["cow_src"]),
                                          int(row[cached // ps]))
                else:
                    # host-tier unpark: extend coverage page by page
                    # past the cache match while parked complete chunks
                    # exist
                    while cached % ps == 0 and cached + ps <= n:
                        c = cached // ps
                        entry = self.blocks.host_get(
                            self.blocks.spill_digest(tokens, c))
                        if entry is None:
                            break
                        self.runner.write_page(int(row[c]), *entry)
                        self.blocks.note_restored()
                        req.restored_pages += 1
                        req.restore_bytes += sum(a.nbytes for a in entry)
                        restored += 1
                        cached += ps
                suffix = n - cached
                # ledger: the re-prefilled remainder runs on device
                # (chunked or single-shot alike)
                req.prefill_computed_tokens += suffix
                # a long replay suffix chunks exactly like a long prompt
                # — resumes must not reintroduce the TPOT stall either
                chunked = bool(self.prefill_chunk
                               and suffix > self.prefill_chunk)
                if suffix > 0 and not chunked:
                    self._dispatch_prefill(ids_all[cached:], cached, row,
                                           req._adapter_row, slot)
                    self._note_gap(suffix)
                # the resume logits are discarded (the last token is
                # already known) — no host sync happens here
            except Exception as e:
                failed = e
            if failed is None and not chunked:
                # allocate_seq defers on plen while the chunk test above
                # uses the replay suffix, so a resume can be deferred
                # yet single-shot — publish here too (no-op when
                # registration wasn't deferred)
                self.blocks.publish_seq(req.id, ids_all)
                now = self._clock()
            ph.set_attribute("tokens", n)
            ph.set_attribute("cached_tokens", cached)
            ph.set_attribute("restored_pages", restored)
            ph.set_attribute("chunked", chunked)
        if failed is not None:
            self._quarantine(slot, req, failed, self._clock())
            return
        if chunked:
            if req.timeline is not None:
                # restore work so far charges to preempted; the chunked
                # re-prefill accounts like any chunked admission
                req.timeline.note("preempted", self._clock(),
                                  event="resume", slot=slot,
                                  restored=restored, cached=cached,
                                  chunked=True, then="prefill_compute")
            _obs.flight("engine", "resume", req=req.id, slot=slot,
                        tokens=n, cached=cached, restored=restored,
                        chunked=True)
            self._begin_chunks(slot, req, ids_all, cached, row,
                               resume_tok=tok)
            return
        if req.timeline is not None:
            req.timeline.note("preempted", now, event="resume",
                              slot=slot, restored=restored,
                              cached=cached, then="decode")
        self._enter_decode(slot, req, row, n, tok, now)
        if self._proposer is not None:
            self._proposer.register(req.id, tokens)
        _obs.flight("engine", "resume", req=req.id, slot=slot,
                    tokens=n, cached=cached, restored=restored)

    # ------------------------------------------------------------ decode
    def _decode(self, active: list[int]):
        self.current_phase = "decode"
        if self.faults is not None:
            f = self.faults.check("slow_step", step=self.decode_steps)
            if f is not None:
                time.sleep(float(f.get("seconds", 0.05)))
            # raise BEFORE any dispatch: the pools are never half-
            # donated, so recovery sees a consistent host mirror
            if self.faults.check("step_raise",
                                 step=self.decode_steps) is not None:
                raise InjectedFault(
                    f"injected poisoned decode step "
                    f"(step {self.decode_steps})")
        reqs = [(s, self.scheduler.slots[s]) for s in active]
        drafts = self._propose(reqs)
        if drafts:
            self._decode_spec(reqs, drafts)
            return
        live, grid = self._count_paged_blocks(active)
        # rows the host has not seen: this dispatch does not wait for them
        overlapped = bool(self._pending) or self._flight is not None
        with self._phase("engine.decode.dispatch", "decode",
                         slots=len(active), paged_blocks_live=live,
                         paged_blocks_grid=grid, overlapped=overlapped,
                         **self._counters_seen):
            logits = self.runner.decode_step()
        self.decode_steps += 1
        self.overlapped_steps += overlapped
        self._prefill_since_decode = 0      # gap witness: decode ran
        _M_STEPS.inc()
        self._pages_hist.observe(self.blocks.pages_in_use)
        for slot in active:
            self._pos[slot] += 1            # mirror of pos + active
        self._pending.append((self._ring_cursor, reqs, None))
        self._ring_cursor = (self._ring_cursor + 1) % self.sync_interval
        self._last_logits = logits if self.emit_logits else None
        # the device is busy with this step: now walk the one before it
        self._land()
        # any active sampling request needs its token fed back before
        # the next step, so sampling degrades to a per-step sync in
        # lockstep; so does a proposer, which drafts from what the host
        # has seen.  Else the group, once full, stays in flight until
        # the next step has been dispatched
        sampling = any(r.gen.do_sample for _, r in reqs)
        if (len(self._pending) >= (1 if sampling else self.sync_interval)
                or not _wanted(self._pending)):
            self._send()
            if sampling or self._proposer is not None:
                self._land()

    def _count_paged_blocks(self, active: list[int],
                            rows: int = 1) -> tuple[int, int]:
        """How much of the paged decode kernel's grid this step's active
        slots put to work: (grid steps whose block of pages holds
        visible tokens, all their grid steps), from the host's position
        mirror — no device fetch.  A verify step sends ``rows``
        candidate rows a slot, each one token longer.  Nothing is
        counted under ``kv_quant``: that decode gathers densely and
        never reaches the kernel."""
        if self.kv_quant:
            return 0, 0
        bt = self._block_tokens
        most = self.table_width * self.page_size
        live = sum(-(-min(int(self._pos[s]) + j + 1, most) // bt)
                   for s in active for j in range(rows))
        grid = len(active) * rows * self._blocks_per_row
        self.paged_blocks_live += live
        self.paged_blocks_grid += grid
        return live, grid

    def _propose(self, reqs) -> dict:
        """Collect this step's drafts: ``{slot: [tokens]}``.  Empty —
        take the plain step — when speculation is off, when unsynced
        ring rows are outstanding (the drafter indexes only tokens the
        host has seen; a verify step always syncs immediately, so the
        mirrors it needs are exact), or when an active request samples
        (greedy verification only, for now)."""
        if (self._proposer is None or self._pending
                or self._flight is not None
                or any(r.gen.do_sample for _, r in reqs)):
            return {}
        drafts = {}
        for slot, req in reqs:
            # cap so even a fully-accepted draft commits at most the
            # tokens the request may still emit (rem), keeping every KV
            # write inside the admission reservation
            cap = req.gen.max_new_tokens - req.num_generated - 1
            if cap <= 0:
                continue
            ds = self._proposer.propose(req.id, cap)
            if ds:
                drafts[slot] = ds
        return drafts

    def _decode_spec(self, reqs, drafts: dict):
        """One verify step: upload the draft grid, score k+1 positions
        per slot, then sync immediately — acceptance needs the ring row
        before the next proposal anyway, and the step commits up to k+1
        tokens, so the sync amortizes exactly like deferred plain
        steps."""
        self.current_phase = "verify"
        draft_arr = np.zeros((self.max_slots, self.spec_k), np.int32)
        dlen = np.zeros((self.max_slots,), np.int32)
        for slot, ds in drafts.items():
            draft_arr[slot, :len(ds)] = ds
            dlen[slot] = len(ds)
        live, grid = self._count_paged_blocks([s for s, _ in reqs],
                                              rows=self.spec_k + 1)
        with self._phase("engine.decode.dispatch", "decode",
                         slots=len(reqs), verify=True,
                         paged_blocks_live=live, paged_blocks_grid=grid):
            self.runner.verify_step(draft_arr, dlen)
        self.decode_steps += 1
        self._prefill_since_decode = 0      # gap witness: decode ran
        _M_STEPS.inc()
        self._spec.record_step()
        self._pages_hist.observe(self.blocks.pages_in_use)
        # speculative multi-token append: charge the whole candidate
        # span now; the rejected suffix rolls back at the sync below
        for slot, req in reqs:
            ds = drafts.get(slot)
            if ds:
                self.blocks.append(req.id, len(ds) + 1)
        self._pending.append((self._ring_cursor, reqs, drafts))
        self._ring_cursor = (self._ring_cursor + 1) % self.sync_interval
        self._last_logits = None
        self._settle()

    def _send(self):
        """Close the group of pending rows: the runner keeps the ring as
        the step just dispatched leaves it and starts its copy to the
        host; the rows are in flight until :meth:`_land`.  Rows no
        request is waiting for (each ended before its row came: the
        step after a burst's last finish) are dropped unfetched."""
        rows, self._pending = self._pending, []
        if not _wanted(rows):
            self.overrun_rows += sum(len(e) for _, e, _ in rows)
            return
        self.runner.hold_ring()
        self._flight = rows

    def _land(self):
        """Fetch the ring in flight and walk its rows: ONE
        [sync_interval, slots] int32 transfer covers the group.  Called
        after the next step's dispatch, so the fetch waits for a step
        the device has (nearly) ended and the walk runs beside the one
        it has begun; at once where the loop is in lockstep."""
        rows, self._flight = self._flight, None
        if rows is None:
            return
        self.current_phase = "host_sync"
        with self._phase("engine.host_sync", "host_sync") as ph:
            ring = self.runner.fetch_ring()
        with self._phase("engine.emit", "emit", rows=len(rows)) as em:
            self._drain(ring, rows, ph.seconds, em)

    def _settle(self):
        """Bring the host level with the device: walk what is in
        flight, then whatever rows are pending."""
        self._land()
        if self._pending:
            self._send()
            self._land()

    def _drain(self, ring, rows: list, sync_s: float, em):
        """What the host does with a fetched ring, inside the span
        ``em``: re-derive acceptance, walk the ``rows`` it holds, hand
        every token to its request (``on_token`` callbacks and finishes
        included)."""
        emitted = self._emitted
        self.host_syncs += 1
        _M_HOST_SYNCS.labels("ring").inc()
        poll = int(FLAGS.get("FLAGS_resource_memory_poll_steps") or 0)
        if poll > 0 and self.host_syncs % poll == 0:
            resource_tracker().sample_memory()
        # wide-ring rows: [slots, k+1] candidate grids (speculation on);
        # narrow rows: [slots] sampled tokens.  Re-derive each verify
        # row's acceptance from the drafts the host already holds — the
        # same integer comparison the device ran, no extra transfer.
        wide = ring.ndim == 3
        accepted: dict[int, tuple[int, int]] = {}
        for ridx, entries, drafts in rows:
            if drafts is None:
                continue
            for slot, req in entries:
                if not _decoding(req):
                    continue
                a = 0
                for j, d in enumerate(drafts.get(slot, ())):
                    if int(ring[ridx, slot, j]) != int(d):
                        break
                    a += 1
                accepted[slot] = (len(drafts.get(slot, ())), a)
        n_rows = len(rows)
        # one decode step per row of the group
        em.set_attribute("steps", n_rows)
        if accepted:
            em.set_attribute("spec_proposed",
                             sum(p for p, _ in accepted.values()))
            em.set_attribute("spec_accepted",
                             sum(a for _, a in accepted.values()))
        _obs.flight("engine", "host_sync", rows=n_rows, steps=n_rows,
                    sync_s=round(sync_s, 6))
        logits_np = None
        now = self._clock()
        if self.requestlog is not None:
            # one timeline charge per live request per sync: decode
            # dispatch up to the blocking ring fetch, then the sync
            # itself — overlapping requests each experience the full
            # wall interval, so per-request conservation still holds
            seen: set[int] = set()
            for _, entries, _ in rows:
                for _slot, _req in entries:
                    if (_req.id in seen or not _decoding(_req)
                            or _req.timeline is None):
                        continue
                    seen.add(_req.id)
                    _req.timeline.note_sync(now, sync_s)
        corrections = []
        # host-side sampling for this sync, open from the logits fetch
        # to the end of the walk: the fetch + each per-request pick
        # (argmax/top-k/top-p)
        with contextlib.ExitStack() as sampling:
            sample = None
            for row_i, (ridx, entries, drafts) in enumerate(rows):
                for slot, req in entries:
                    if not _decoding(req):
                        # evicted/finished, seen one step (or a group)
                        # late: the overrun row is discarded
                        self.overrun_rows += 1
                        continue
                    if drafts is not None:
                        self._accept(slot, req, ring[ridx, slot],
                                     *accepted[slot], now)
                        continue
                    tok = raw = int(ring[ridx, slot, 0]) if wide \
                        else int(ring[ridx, slot])
                    if req.gen.do_sample:
                        # sampling rows only exist under eff-interval 1,
                        # so the step's logits handle is always the
                        # right row
                        if logits_np is None:
                            sample = sampling.enter_context(
                                _obs.tracer().phase("engine.sample"))
                            logits_np = np.asarray(self._last_logits)
                            self.logit_fetches += 1
                            _M_HOST_SYNCS.labels("logits").inc()
                        row_logits = logits_np[slot]
                        if (self.faults is not None
                                and self.faults.check(
                                    "nan_logits", req=req.id, slot=slot,
                                    phase="decode") is not None):
                            row_logits = np.full_like(row_logits, np.nan)
                        try:
                            tok = self._pick_token(req, row_logits)
                        except NonFiniteLogitsError as e:
                            # fail ONLY the offending request — the
                            # other slots in this sync keep their tokens
                            self._quarantine(slot, req, e, now)
                            continue
                        if tok != raw:
                            corrections.append((slot, tok))
                    prev = req.last_token_at
                    if prev is not None:
                        # batched sync: spread the interval over the
                        # tokens it covers so TPOT keeps per-token
                        # semantics
                        self._tpot.observe(
                            (now - prev) / (n_rows - row_i))
                    self._tok[slot] = tok
                    self._emit(slot, req, tok, now)
            if sample is not None:
                sample.set_attribute("corrections", len(corrections))
        if corrections:
            self.runner.correct_tokens(corrections)
        em.set_attribute("tokens", self._emitted - emitted)

    def _accept(self, slot: int, req: Request, row, proposed: int,
                a: int, now: float):
        """Commit one verify-row slot: roll back the rejected draft
        suffix (the ledger then charges pages for accepted tokens
        only), advance the pos mirror by the accepted prefix + the
        correction/bonus token, and emit those ``a + 1`` tokens in
        order — stopping at max_new/EOS exactly where sequential decode
        would have stopped."""
        if proposed:
            self.blocks.rollback(req.id, proposed - a)
            self._spec.record(proposed, a)
            req.spec_proposed_tokens += proposed
            req.spec_accepted_tokens += a
        self._pos[slot] += a + 1        # mirror of pos + (acc+1)*active
        prev = req.last_token_at
        dt = None if prev is None else (now - prev) / (a + 1)
        for j in range(a + 1):
            tok = int(row[j])
            if dt is not None:
                # one verify step emitted a+1 tokens: spread the
                # interval so TPOT keeps per-token semantics
                self._tpot.observe(dt)
            self._tok[slot] = tok
            # drafted slots were charged up front at dispatch;
            # ride-along slots (no draft) charge per emit as usual
            self._emit(slot, req, tok, now, charge=proposed == 0)
            if req.is_finished():
                break

    @contextlib.contextmanager
    def _phase(self, name: str, charge: str, **kw):
        """``Tracer.phase(name, **kw)`` whose interval is also charged
        to ``timings[charge + "_s"]``: the ring, the profiler's trace
        and the counters read one and the same interval."""
        ph = _obs.tracer().phase(name, **kw)
        try:
            with ph:
                yield ph
        finally:
            self._note_phase(charge, ph.seconds)

    def _note_phase(self, phase: str, seconds: float):
        """Charge engine wall time to a phase: the per-engine mirror,
        the serving_step_phase_seconds_total counter, and the process
        tracker's throughput denominator."""
        seconds = max(float(seconds), 0.0)
        self.timings[phase + "_s"] += seconds
        _M_PHASE_SECONDS.labels(phase).inc(seconds)
        resource_tracker().note_phase(phase, seconds)

    def _emit(self, slot: int, req: Request, tok: int, now: float,
              charge: bool = True):
        if req.timeline is not None and req.first_token_at is None:
            req.timeline.mark("first_token", now)   # the TTFT moment
        req._emit(tok, now)
        self._emitted += 1
        _M_TOKENS.inc()
        resource_tracker().note_tokens(1)
        if charge:
            # committed-token ledger: one durable token per emit (the
            # speculative path charges its whole span at dispatch and
            # rolls the rejected suffix back instead)
            self.blocks.append(req.id, 1)
        if self._proposer is not None:
            self._proposer.extend(req.id, tok)
        eos = req.gen.eos_token_id
        if req.num_generated >= req.gen.max_new_tokens:
            self._finalize(req, "length", now)
            self.scheduler.evict(slot, "finished", now)
        elif eos is not None and tok == eos:
            self._finalize(req, "eos", now)
            self.scheduler.evict(slot, "finished", now)

    def _park(self, slot: int):
        """Return a slot to the idle state: all writes/reads go to the
        dump page until the next admission."""
        # an eviction mid-chunked-prefill abandons the chunk state (the
        # pages are gone; the request was finalized by the scheduler)
        self._chunking.pop(slot, None)
        self.table[slot] = self.blocks.empty_row(self.table_width)
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._active[slot] = 0
        self._aidx[slot] = 0
        self._push_slot(slot)

    def _push_slot(self, slot: int):
        """Patch ONE slot's row of the device-resident decode state from
        the host mirrors (admission / eviction only — never per step)."""
        self.runner.push_slot(slot, self.table[slot],
                              int(self._pos[slot]), int(self._tok[slot]),
                              int(self._active[slot]),
                              adapter_row=int(self._aidx[slot]))

    # --------------------------------------------------------- sampling
    def _pick_token(self, req: Request, logits: np.ndarray) -> int:
        g = req.gen
        if not g.do_sample:
            # argmax over NaN silently returns the NaN's index (NaN
            # propagates as the max) — poisoned logits must fail the
            # request loudly, not emit a garbage token
            if np.isnan(logits).any() or not np.isfinite(logits).any():
                raise NonFiniteLogitsError(
                    f"request {req.id}: non-finite logits from the "
                    "model (greedy decode)")
            return int(np.argmax(logits))
        rng = self._rngs.get(req.id)
        if rng is None:
            rng = self._rngs[req.id] = np.random.default_rng(
                (g.seed, req.id))
        logits = logits.astype(np.float64)
        if g.temperature != 1.0:
            logits = logits / max(g.temperature, 1e-6)
        if g.top_k and g.top_k > 0:
            k = min(g.top_k, logits.size)
            kth = np.sort(logits)[-k]
            logits = np.where(logits < kth, -np.inf, logits)
        if g.top_p < 1.0:
            order = np.argsort(logits)[::-1]
            probs = _softmax(logits[order])
            cum = np.cumsum(probs)
            cutoff_idx = int(np.sum(cum < g.top_p))
            cutoff = logits[order[min(cutoff_idx, logits.size - 1)]]
            logits = np.where(logits < cutoff, -np.inf, logits)
        if not np.isfinite(logits).any():
            raise NonFiniteLogitsError(
                f"request {req.id}: no finite logits to sample from — "
                "the model emitted non-finite logits (or top_k/top_p "
                "masked every candidate)")
        return int(rng.choice(logits.size, p=_softmax(logits)))

    # -------------------------------------------------------- lifecycle
    def _finalize(self, req: Request, reason: str, now: float):
        if req.is_finished():
            return
        req.finish_reason = reason
        req.state = RequestState.CANCELLED \
            if reason in ("cancelled", "deadline") else RequestState.DONE
        req.finished_at = now
        if self.lora is not None and req.adapter is not None:
            # unpin the bank row (acquired at submit); the weights stay
            # resident until LRU pressure evicts them
            self.lora.release(req.adapter)
        self._rngs.pop(req.id, None)
        if self._proposer is not None:
            self._proposer.drop(req.id)
        self._e2e.observe(now - req.arrival_time)
        _M_REQUESTS.labels(reason).inc()
        _M_FINISH.labels(reason).inc()
        resource_tracker().note_finish(reason, req.num_generated)
        if self.requestlog is not None:
            # close the timeline (residual charge + conservation check)
            # BEFORE slo.observe, so a violation exemplar snapshots the
            # finished attribution, not a half-charged one
            self.requestlog.on_finish(req, reason, now)
        if self.slo is not None:
            self.slo.observe(req, now)
        if self.usage is not None:
            # after slo.observe so per-tenant verdicts land first; the
            # page-seconds accumulator folds when the pages release
            self.usage.on_finish(req, reason, now)
        _obs.flight("engine", "finish", req=req.id, reason=reason,
                    generated=req.num_generated)
        if req.queue_span is not None:      # dropped while still queued
            req.queue_span.set_attribute("dropped", True)
            req.queue_span.end()
            req.queue_span = None
        if req.decode_span is not None:
            req.decode_span.set_attribute("generated", req.num_generated)
            req.decode_span.end()
            req.decode_span = None
        if req.root_span is not None:
            rs = req.root_span
            rs.set_attribute("finish_reason", reason)
            rs.set_attribute("generated", req.num_generated)
            rs.set_attribute("cached_tokens", req.num_cached_tokens)
            if reason == "deadline" and req.deadline is not None:
                # how far past its deadline the request was when the
                # scheduler finally evicted it (engine clock)
                rs.set_attribute("deadline_overrun_s",
                                 round(now - req.deadline, 6))
            rs.end()

    # -------------------------------------------------------- self-healing
    def _quarantine(self, slot: int, req: Request, why, now: float):
        """Fail ONE request in place: finish_reason='error', pages
        released, slot parked — the batch keeps running.  The failure
        detail lands on ``req.error`` for the server's error payload."""
        req.error = str(why)
        self.quarantines += 1
        _M_RECOVERY.labels("quarantine").inc()
        _obs.flight("engine", "quarantine", req=req.id, slot=slot,
                    error=str(why)[:160])
        self._finalize(req, "error", now)
        self.scheduler.evict(slot, "error", now)

    def recover(self) -> dict:
        """Rebuild the ModelRunner after a poisoned step and replay
        every in-flight request.

        The BlockManager is entirely host-side, so page ownership, block
        tables, and the committed-token ledger all survive — only the
        device KV *content* is gone.  Each DECODE-state request re-runs
        its committed tokens (prompt + generated so far, minus the last
        token, which re-enters as the next decode input) through the
        prefill path; the prefix-cache chain is flushed first (it
        described dead KV) and re-registered by the replays themselves,
        so sequences sharing prefix pages replay the shared part once.
        Requests that cannot be replayed are quarantined.  Typically
        called by the :class:`~.supervisor.EngineSupervisor`, not
        user code."""
        with _obs.tracer().phase("engine.recover", parent=None) as ph:
            out = self._rebuild()
            for key, value in out.items():
                ph.set_attribute(key, value)
        return out

    def _rebuild(self) -> dict:
        """The body of :meth:`recover`, inside its span."""
        now = self._clock()
        # drop un-synced device state: the ring rows and logits handle
        # belong to the dead runner (the pos mirrors they would have
        # advanced are recomputed from request state below)
        self._pending.clear()
        self._flight = None
        self._ring_cursor = 0
        self._last_logits = None
        flushed = self.blocks.flush_prefix_cache()
        self.runner = ModelRunner(self.config, self.state,
                                  **self._runner_kw)
        if self.lora is not None:
            # the fresh runner's bank is zeroed — re-upload every
            # resident adapter before any replayed prefill reads it
            self.lora.attach(self.runner)
        replayed = 0
        for slot, req in enumerate(self.scheduler.slots):
            if req is None:
                self._park(slot)        # sync the fresh decode state
                continue
            if req.state != RequestState.DECODE or not req.output_tokens:
                self._quarantine(slot, req,
                                 "not replayable at runner rebuild", now)
                continue
            try:
                self._replay(slot, req)
                replayed += 1
                self.replayed_requests += 1
            except Exception as e:
                self._quarantine(slot, req, f"replay failed: {e}", now)
        self.recoveries += 1
        _obs.flight("engine", "recover", replayed=replayed,
                    flushed_cached_pages=flushed)
        return {"replayed": replayed, "flushed_cached_pages": flushed}

    def _replay(self, slot: int, req: Request):
        """Re-prefill one in-flight request's committed tokens into the
        rebuilt runner.  Restores the decode invariant exactly: device
        KV covers positions ``0..pos-1`` where ``pos = prompt +
        generated - 1``, and the last generated token re-enters as the
        next step's input — decode then continues token-for-token as if
        the fault never happened (greedy parity is asserted in tests)."""
        self.current_phase = "prefill"
        with self._phase("engine.replay", "prefill", parent=req.root_span,
                         req=req.id, slot=slot) as ph:
            tokens = [int(t) for t in req.prompt] + list(req.output_tokens)
            ids_all = tokens[:-1]
            n = len(ids_all)
            plan = self.blocks.replay_plan(req.id, ids_all)
            cached = int(plan["cached_len"])
            ph.set_attribute("tokens", n)
            ph.set_attribute("cached_tokens", cached)
            # ledger: recovery replays re-run committed tokens; the
            # cache match mirrors replay_plan's global cached_tokens bump
            req.replays += 1
            req.prefill_cached_tokens += cached
            req.prefill_computed_tokens += n - cached
            row = self.blocks.table_row(req.id, self.table_width)
            arow = getattr(req, "_adapter_row", 0)
            self._dispatch_prefill(ids_all[cached:], cached, row, arow,
                                   slot)
            # the replay's logits are discarded (the last token is
            # already known), so no host sync happens here
            drift = self.blocks.committed_tokens(req.id) - len(tokens)
            if drift > 0:
                # a fault between a speculative dispatch and its sync
                # left uncommitted draft positions charged — roll them
                # back
                self.blocks.rollback(req.id, drift)
            self.table[slot] = row
            self._pos[slot] = n
            self._tok[slot] = tokens[-1]
            self._active[slot] = 1
            self._aidx[slot] = arow
            self._push_slot(slot)
        if req.timeline is not None:
            # everything since the last charge — the poisoned step, the
            # runner rebuild's share, and this replay — was recovery
            req.timeline.note("recovery", self._clock(), event="replay",
                              slot=slot, tokens=n, cached=cached,
                              then="decode")
        _obs.flight("engine", "replay", req=req.id, slot=slot,
                    tokens=n, cached=cached)

    # -------------------------------------------------------------- info
    def stats(self) -> dict:
        b = self.blocks
        spec = {"spec_k": self.spec_k,
                "verify_traces": self.runner.verify_traces}
        if self._spec is not None:
            spec.update(self._spec.snapshot())
        return {
            **spec,
            "queued": len(self.scheduler.queue),
            "active": self.scheduler.active_count,
            "pages_in_use": b.pages_in_use,
            "pages_total": b.num_pages,
            "decode_traces": self.decode_traces,
            "prefill_buckets": sorted(self._prefill_fns),
            "cached_prefill_buckets": sorted(self._prefill_cached_fns),
            "prefix_hits": b.prefix_hits,
            "prefix_misses": b.prefix_misses,
            "prefix_evictions": b.prefix_evictions,
            "cow_copies": b.cow_copies,
            "cached_tokens": b.cached_tokens,
            "cached_pages": b.cached_pages,
            "host_syncs": self.host_syncs,
            "logit_fetches": self.logit_fetches,
            "decode_steps": self.decode_steps,
            "overlapped_steps": self.overlapped_steps,
            "overrun_rows": self.overrun_rows,
            "paged_blocks_live": self.paged_blocks_live,
            "paged_blocks_grid": self.paged_blocks_grid,
            **self._device_counters(),
            "recurrent_state_bytes": self.runner.recurrent_state_bytes,
            "pages_allocated": b.pages_allocated,
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunks": self.prefill_chunks,
            "max_prefill_gap": self.max_prefill_gap,
            "preemptions": self.preemptions,
            "spill_aborts": self.spill_aborts,
            "spilled_pages": b.spilled_pages,
            "restored_pages": b.restored_pages,
            "spill_bytes": b.spill_bytes,
            "host_parked_pages": b.host_parked,
            "mesh_tp": self.tp,
            "quant": self.quant,
            "kv_quant": self.kv_quant,
            "lora": (self.lora.snapshot()
                     if self.lora is not None else None),
            "timings": {k: round(v, 6) for k, v in self.timings.items()},
            "progress": self.progress,
            "slo": self.slo.stats() if self.slo is not None else None,
            "recoveries": self.recoveries,
            "quarantines": self.quarantines,
            "replayed_requests": self.replayed_requests,
            "faults_injected": (dict(self.faults.injected)
                                if self.faults is not None else {}),
        }

    def _device_counters(self) -> dict:
        """What the decode step counts on the device (the expert layers'
        ``moe_*``, a recurrent family's ``ssm_rows_live``; a family that
        counts nothing has none), fetched now.  The decode step's span
        shows what the last call here read."""
        self._counters_seen = self.runner.device_counters()
        return self._counters_seen

    def _page_bytes(self, *, dense: bool = False) -> int:
        """Bytes one KV page pair (k + v, full heads) occupies — the
        unit every spill/restore moves.  Under ``kv_quant`` that is the
        int8 elements plus the per-(page-row, head) f32 scale rows;
        ``dense=True`` prices the same page at the checkpoint dtype
        (the savings baseline)."""
        cfg = self.config
        if self.latent:
            return int(np.prod(self.runner.kpool.shape[2:])
                       * cfg.num_hidden_layers * self._embed_itemsize)
        # a recurrent family pages its attention layers alone
        layers = (len(cfg.attention_layers) if self.recurrent
                  else cfg.num_hidden_layers)
        rows = layers * cfg.num_key_value_heads * self.page_size
        elems = rows * cfg.head_dim
        if self.kv_quant and not dense:
            return 2 * elems + 2 * rows * 4
        return 2 * elems * self._embed_itemsize

    def quant_snapshot(self) -> dict:
        """The ``quant.json`` side-file: what is quantized, the
        per-page byte math, and the spill-tier savings vs what the same
        traffic would have moved with dense pages."""
        b = self.blocks
        dense_page = self._page_bytes(dense=True)
        return {
            "weight_kind": self.quant or "dense",
            "kv_quant": self.kv_quant,
            "page_bytes": self._page_bytes(),
            "dense_page_bytes": dense_page,
            "spilled_pages": b.spilled_pages,
            "spill_bytes": b.spill_bytes,
            "spill_bytes_dense_estimate": b.spilled_pages * dense_page,
        }

    def lora_snapshot(self) -> dict:
        """The ``lora.json`` side-file: the adapter store's residency
        census plus the device bank footprint."""
        snap = self.lora.snapshot() if self.lora is not None else {}
        snap["bank_bytes_device"] = self.runner.lora_bank_bytes()
        return snap

    def resource_snapshot(self) -> dict:
        """Engine-local half of ``GET /debug/resources``: the exact
        pool census (live/cached/free with a leak check), per-resident-
        request page footprints, fragmentation against the queue head,
        per-mesh-device memory from the runner, and the phase timing
        breakdown.  The process-wide tracker snapshot (memory/compiles/
        goodput) complements it."""
        b = self.blocks
        head_need = None
        if self.scheduler.queue:
            head = self.scheduler.queue[0]
            head_need = b.pages_needed(head.prompt.size,
                                       head.gen.max_new_tokens)
        requests = {}
        for slot, req in enumerate(self.scheduler.slots):
            if req is not None:
                fp = b.seq_footprint(req.id)
                fp["slot"] = slot
                requests[str(req.id)] = fp
        pool = b.pool_accounting()
        pool["fragmentation_ratio"] = round(b.fragmentation(head_need), 6)
        return {
            "pool": pool,
            "requests": requests,
            "mesh": self.runner.mesh_info(),
            "lora": (self.lora_snapshot()
                     if self.lora is not None else None),
            "timings": {k: round(v, 6) for k, v in self.timings.items()},
            "counters": {
                "decode_steps": self.decode_steps,
                "decode_traces": self.decode_traces,
                "host_syncs": self.host_syncs,
                "logit_fetches": self.logit_fetches,
                "pages_allocated": b.pages_allocated,
                "recoveries": self.recoveries,
                "quarantines": self.quarantines,
                "prefill_chunks": self.prefill_chunks,
                "preemptions": self.preemptions,
                "spilled_pages": b.spilled_pages,
                "restored_pages": b.restored_pages,
            },
        }


def _decoding(req: Request) -> bool:
    """Whether a ring row's token is still wanted by its request."""
    return not req.is_finished() and req.state == RequestState.DECODE


def _wanted(rows: list) -> bool:
    """Whether any request still waits for a token of ``rows``."""
    return any(_decoding(req) for _, entries, _ in rows
               for _, req in entries)


def _softmax(x):
    x = x - np.max(x[np.isfinite(x)]) if np.isfinite(x).any() else x
    e = np.exp(np.where(np.isfinite(x), x, -np.inf))
    return e / e.sum()


def _pages_buckets(num_pages):
    """Integer page-count buckets spanning the pool (pages-in-use is a
    count, not a latency; the default ms-scale buckets would collapse)."""
    n = max(num_pages, 1)
    edges = sorted({max(1, round(n * f))
                    for f in (0.125, 0.25, 0.375, 0.5, 0.625, 0.75,
                              0.875, 1.0)})
    return tuple(float(e) for e in edges)


def create_engine(model, *, max_slots: int = 4, page_size: int = 64,
                  num_pages: int | None = None,
                  max_model_len: int | None = None,
                  emit_logits: bool = False,
                  enable_prefix_cache: bool = False,
                  sync_interval: int = 1, clock=time.monotonic,
                  slo=None, mesh=None,
                  spec_k: int | None = None,
                  prefill_chunk: int | None = None,
                  preempt: bool | None = None, faults=None,
                  usage=None, quant: str | None = None,
                  kv_quant: bool | None = None, lora=None,
                  requestlog=None) -> Engine:
    """`create_predictor`-style entry point: build a continuous-batching
    engine over a LlamaForCausalLM (or any model exposing ``config`` and
    ``functional_state()`` with the llama state-dict layout).

    ``enable_prefix_cache=True`` turns on automatic prefix caching:
    prompts sharing page-aligned prefixes reuse resident KV pages and
    prefill only their uncached suffix.  ``sync_interval=N`` makes the
    greedy decode loop fetch N steps' tokens at a time (tokens stream
    out in bursts of N — fewer transfers, higher streaming latency;
    sampling requests force per-step syncs regardless).  At every N the
    host runs one step behind the device: the next step is dispatched
    before a fetch, so the overlap does not depend on N.

    ``spec_k=K`` (default ``FLAGS_serving_spec_k``) turns on
    speculative decoding: a host-side prompt-lookup (n-gram) drafter
    proposes up to K tokens per slot and one jitted verify step scores
    all K+1 positions, committing the longest matching prefix plus a
    correction token.  Greedy outputs are token-for-token identical to
    ``spec_k=0``; the win is tokens-per-step > 1 on repetitive text.

    ``prefill_chunk=N`` (default ``FLAGS_serving_prefill_chunk``)
    splits admission prefill into N-token chunks interleaved with
    decode steps — one long prompt can no longer stall every decoding
    slot's TPOT; greedy outputs are token-for-token identical to
    whole-prompt prefill.  ``preempt`` (default
    ``FLAGS_serving_preempt``) enables priority preempt-and-swap:
    when a higher-priority ``submit(..., priority=...)`` cannot be
    placed, the lowest-priority most-recently-admitted resident spills
    its KV to host RAM and re-queues for a parity-preserving resume.

    ``mesh`` selects the tensor-parallel mesh: an int / ``"tp=N"`` /
    1-tuple tp size (default: ``FLAGS_serving_mesh_tp``).  ``tp>1``
    shards attention heads, the FFN hidden dim, and the paged KV pool
    across the first N local devices; greedy outputs are token-exact
    against ``tp=1``.  For CPU testing export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first.

    ``quant`` (default ``FLAGS_serving_quant``) turns on weight-only
    quantized serving: ``'int8'`` or ``'int4'`` converts the dense
    checkpoint at construction via
    :func:`paddle_tpu.serving.quantize_state` (per-projection matmul
    weights only; embeddings/norms/lm_head stay dense) and composes
    with any ``tp``.  ``kv_quant`` (default
    ``FLAGS_serving_kv_quant``) switches the paged KV pools to int8
    with per-(page-row, head) f32 scales — quantize-on-write inside
    the jitted step, dequant fused into the attention gather, and
    spill/restore moving the quantized bytes.  Both default off, and
    off means the dense programs are byte-identical to a build without
    these knobs; greedy outputs under quant match dense within a small
    token tolerance (pinned by the ``quant_decode`` perf-gate
    scenario).

    ``lora`` attaches a :class:`~paddle_tpu.serving.lora.AdapterStore`:
    the runner allocates a packed ``capacity + 1``-row adapter bank
    beside the base weights (row 0 stays zero — the no-adapter row),
    ``submit(..., adapter='name')`` pins the adapter's row for the
    request's lifetime, and every slot in the shared decode step
    gathers its own adapter's (A, B) pair — mixed-adapter batches run
    in the single jitted program.  ``lora=None`` (the default) passes
    empty pytrees through every program: the dense jaxprs are
    byte-identical to a build without the knob.

    ``requestlog`` attaches a
    :class:`~paddle_tpu.observability.requestlog.RequestLog` for
    tail-latency forensics: per-request lifecycle timelines whose
    critical-path attribution buckets sum exactly to the measured E2E,
    plus a worst-K SLO-violation exemplar reservoir (behind
    ``GET /debug/requests/<id>`` and ``GET /debug/exemplars``).
    ``requestlog=None`` (the default, or ``FLAGS_serving_request_log``
    unset under ``serve()``) records nothing and every seam costs one
    ``is not None`` test.

    Example::

        engine = create_engine(model, max_slots=8, page_size=64,
                               enable_prefix_cache=True, sync_interval=8)
        req = engine.submit([1, 2, 3], GenerationConfig(max_new_tokens=32))
        for tok in req.stream():
            ...
    """
    return Engine(model, max_slots=max_slots, page_size=page_size,
                  num_pages=num_pages, max_model_len=max_model_len,
                  emit_logits=emit_logits,
                  enable_prefix_cache=enable_prefix_cache,
                  sync_interval=sync_interval, clock=clock, slo=slo,
                  mesh=mesh, spec_k=spec_k, prefill_chunk=prefill_chunk,
                  preempt=preempt, faults=faults, usage=usage,
                  quant=quant, kv_quant=kv_quant, lora=lora,
                  requestlog=requestlog)
