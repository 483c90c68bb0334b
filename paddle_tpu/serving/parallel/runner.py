"""Mesh-aware model runner: the engine's device half.

The :class:`ModelRunner` owns everything that lives on (or is traced
for) the accelerator side of the serving engine: the weights and their
placement, the paged KV pools, the rope tables, the device-resident
decode state (table/pos/tok/active + the sampled-token ring), and the
four jit families — decode step, per-bucket prefill, per-bucket cached
prefill, and the CoW page copy.  The engine keeps the host half:
scheduler, block manager, host mirrors, sampling, and request
lifecycle.

Two construction modes, selected by ``tp``:

``tp == 1``
    The programs jitted as they are — no mesh, no ``shard_map``, no
    ``device_put``.

A model description whose ``family`` is ``"deepseek_v3"`` keeps the
seam and changes the cache: one pool of latent rows, and the programs of
``latent.py`` (one chip only; the value pool is then the empty tuple).
One whose ``family`` is ``"granitemoehybrid"`` keeps K/V pages for its
attention layers and adds a per-slot recurrent state for the others: the
programs of ``recurrent.py``, and ``rstate``, one more donated argument
of every step and prefill program (the empty tuple for the other
families).

``tp > 1``
    A 1-axis ``jax.sharding.Mesh`` over the first ``tp`` devices.
    q/k/v/gate/up are column-sharded and o/down row-sharded with
    ``NamedSharding``; embeddings, norms, and the LM head are
    replicated; the KV pools shard along the head axis
    (``[L, pages+1, kvh/tp, page_size, hd]`` per device) so the
    BlockManager's page table stays host-side and mesh-agnostic.  All
    four jit families run as ``shard_map`` computations whose only
    collectives are the attention-output and FFN-down ``psum``s.

Both modes build ONE body a program (``_build_step(axis)``,
``_build_verify(axis)``, ``_prefill_fn``, ``_prefill_cached_fn``) around
the two layer bodies of ``models/generation.py``, ``decode_layer`` and
``prefill_layer``, which take the mesh axis (``None`` on one chip) and
say where the two all-reduces sit.  Plain or int8 pages is the cache
value's business: a program makes one ``PagedKV``
(``ops/pallas/paged_attention.py``) of its four pool arguments, the
bodies write, attend and gather through it, and the program returns its
four members.

The engine's serving invariants carry over unchanged: slot occupancy /
positions / tables are data (ONE decode trace per engine lifetime —
``decode_traces`` counts them), the decode state is donated through the
step, and an admission or eviction patches its slot's row in place with
one program (:meth:`ModelRunner.push_slot`).  The sampled-token ring
alone is NOT donated: a step returns a new ``[sync_interval, slots]``
array, so the engine can hold one step's ring (:meth:`hold_ring`) while
the next step runs and fetch it afterwards.

The pools go through every program WHOLE: a layer body takes
``[L, pages+1, kvh, page_size, hd]`` and its layer's index, the cache
scatters the step's rows at ``[li, page, head, off]``, hands the same
array and ``li`` to the paged kernel and returns it.  A layer sliced out
for the kernel or layers stacked back would each copy the pool; as it is
the donated pools are updated where they lie and the decode and verify
programs declare no pool-sized temporary (``tests/test_chip_compile.py``
holds them to it).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ... import observability as _obs
from ...observability.resources import record_compile, resource_tracker
from ...models.generation import (_layer_weights, _mm, _rope_at,
                                  decode_layer, prefill_layer)
from ...models.llama import _rope_tables
from ...models.llama_hybrid import _rms
from ...ops.pallas.paged_attention import PagedKV
from ...ops.pallas.quant_matmul import QuantizedWeight
from . import latent, recurrent
from .mesh import TP_AXIS, mesh_devices, validate_tp

__all__ = ["ModelRunner"]

_M_STEP_TRACES = _obs.counter(
    "serving_decode_step_traces_total",
    "decode-step jit traces — continuous batching keeps this at 1 per "
    "engine (2 with speculative decoding: the plain step + the verify "
    "program); growth means admissions are re-tracing")
_M_VERIFY_TRACES = _obs.counter(
    "serving_spec_verify_traces_total",
    "verify-program jit traces — exactly 1 per speculative engine; "
    "growth means drafts are leaking into shapes")
_M_PREFILL_TRACES = _obs.counter(
    "serving_prefill_traces_total",
    "prefill jit traces (one per prompt-length bucket)", ("bucket",))

# weight suffixes sharded on tp: columns for the input-side projections
# (each device owns nh/tp query heads, kvh/tp KV heads, I/tp FFN
# columns), rows for the output-side projections whose partial products
# the layer all-reduces
_COL_SHARDED = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                "self_attn.v_proj.weight", "mlp.gate_proj.weight",
                "mlp.up_proj.weight")
_ROW_SHARDED = ("self_attn.o_proj.weight", "mlp.down_proj.weight")
_FUSED_KEYS = ("self_attn.qkv_fused.weight", "mlp.gateup_fused.weight")
# LoRA bank keys whose BASE weight is row-sharded: the adapter's A
# (which contracts the sharded input) splits with it, B replicates;
# every other key shards B's output columns and replicates A
_LORA_ROW_KEYS = ("o", "down")


def _leaf_bytes(v) -> int:
    """Device bytes of one weight leaf: QuantizedWeight counts its int8
    (or nibble-packed int4) values plus the f32 scale vector; dense
    arrays count shape * itemsize; shapeless leaves count 0."""
    if isinstance(v, QuantizedWeight):
        return (int(np.prod(v.q.shape)) * jnp.dtype(v.q.dtype).itemsize
                + int(np.prod(v.scale.shape))
                * jnp.dtype(v.scale.dtype).itemsize)
    if hasattr(v, "shape"):
        return int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
    return 0


class ModelRunner:
    """Device-side serving runner (see module docstring).

    The engine talks to it through a narrow seam: :meth:`decode_step`,
    :meth:`prefill`, :meth:`prefill_cached`, :meth:`copy_page`,
    :meth:`push_slot`, :meth:`hold_ring`, :meth:`fetch_ring`,
    :meth:`correct_tokens`.
    """

    def __init__(self, config, state: dict, *, tp: int = 1,
                 max_slots: int, page_size: int, table_width: int,
                 num_pages: int, dump_page: int, sync_interval: int = 1,
                 emit_logits: bool = False, spec_k: int = 0,
                 kv_quant: bool = False, lora_slots: int = 0,
                 lora_rank: int = 0,
                 per_device_pool_bytes: int | None = None):
        self.config = config
        self.tp = int(tp)
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.table_width = int(table_width)
        self.num_pages = int(num_pages)
        self.dump_page = int(dump_page)
        self.sync_interval = int(sync_interval)
        self.emit_logits = bool(emit_logits)
        self.spec_k = int(spec_k)
        self.kv_quant = bool(kv_quant)
        # LoRA adapter bank: lora_slots usable rows + the zeroed
        # no-adapter row 0, one static rank axis.  lora_slots == 0 is
        # the off mode: the bank and the per-slot index vector are
        # empty tuples — zero pytree leaves in every jitted signature,
        # and the bodies trace no adapter op (as the scale pools of
        # plain pages: see "jitted bodies" below).
        self.lora_slots = int(lora_slots)
        self.lora_rank = int(lora_rank)
        if self.lora_slots and self.lora_rank < 1:
            raise ValueError(
                f"lora_slots={self.lora_slots} requires lora_rank >= 1,"
                f" got {self.lora_rank}")
        # the cache the model description asks for: K and V pages per
        # head, or (latent.py) one pool of latent rows and no V pool
        # ... or (recurrent.py) K/V pages for the attention layers and a
        # per-slot recurrent state for the rest
        self.latent = latent.is_latent(config)
        self.recurrent = recurrent.is_recurrent(config)
        if self.latent:
            latent.check_options(tp=self.tp > 1, kv_quant=self.kv_quant,
                                 lora_slots=self.lora_slots > 0,
                                 spec_k=self.spec_k > 0)
        elif self.recurrent:
            recurrent.check_options(config, mesh=self.tp > 1,
                                    kv_quant=self.kv_quant,
                                    lora=self.lora_slots > 0,
                                    spec_k=self.spec_k > 0)
        else:
            validate_tp(config, self.tp)
        self._validate_quantized_state(state)

        L = config.num_hidden_layers
        pool_rows = self.num_pages + 1               # + dump page
        self._rope_len = self.table_width * self.page_size
        if self.latent:
            from ...models.deepseek_v3 import rope_tables
            dtype = state[latent.EMBED].dtype
            pool_shape = latent.pool_shape(config, self.num_pages,
                                           self.page_size)
            scale_shape = ()
            cos, sin = rope_tables(config, self._rope_len)
        elif self.recurrent:            # no position encoding at all
            dtype = state[recurrent.embed_name(config)].dtype
            pool_shape = recurrent.kv_pool_shape(config, self.num_pages,
                                                 self.page_size)
            scale_shape = ()
            cos = sin = jnp.zeros((0,), jnp.float32)
        else:
            kvh, hd = config.num_key_value_heads, config.head_dim
            dtype = state["llama.embed_tokens.weight"].dtype
            pool_shape = (L, pool_rows, kvh, self.page_size, hd)
            scale_shape = (L, pool_rows, kvh, self.page_size)
            cos, sin = _rope_tables(self._rope_len, hd, config.rope_theta)
        # int8 KV page mode: pools store int8, one f32 scale per
        # (layer, page row, head, slot) rides in separate scale pools.
        # Plain pages have no scale pools: the members are empty
        # tuples, zero pytree leaves in every jitted signature, and
        # that absence is how the cache value knows its format.
        pool_dtype = jnp.int8 if self.kv_quant else dtype
        cos = cos.astype(jnp.float32)
        sin = sin.astype(jnp.float32)
        table0 = np.full((self.max_slots, self.table_width),
                         self.dump_page, np.int32)
        # with speculation the ring rows are WIDE ([slots, k+1]: a verify
        # step deposits every candidate token; the plain step uses column
        # 0) so the host sync stays ONE transfer either way
        ring_shape = ((self.sync_interval, self.max_slots)
                      if self.spec_k == 0 else
                      (self.sync_interval, self.max_slots,
                       self.spec_k + 1))

        if self.tp == 1:
            self.mesh = None
            self.devices = list(jax.devices()[:1]) if jax.devices() else []
            self.state = state
            self.kpool = jnp.zeros(pool_shape, pool_dtype)
            self.vpool = (() if self.latent
                          else jnp.zeros(pool_shape, pool_dtype))
            if self.kv_quant:
                self.kscale = jnp.zeros(scale_shape, jnp.float32)
                self.vscale = jnp.zeros(scale_shape, jnp.float32)
            else:
                self.kscale = self.vscale = ()
            if self.lora_slots:
                self.lora = self._build_lora_bank()
                self._aidx_dev = jnp.zeros((self.max_slots,), jnp.int32)
            else:
                self.lora = self._aidx_dev = ()
            self._cos, self._sin = cos, sin
            self._table_dev = jnp.asarray(table0)
            self._pos_dev = jnp.zeros((self.max_slots,), jnp.int32)
            self._tok_dev = jnp.zeros((self.max_slots,), jnp.int32)
            self._active_dev = jnp.zeros((self.max_slots,), jnp.int32)
            self._ring_dev = jnp.zeros(ring_shape, jnp.int32)
            self._ridx_dev = jnp.zeros((), jnp.int32)
            # the expert layers' counters, kept on the device by the
            # decode step (no leaves where the family has no experts)
            self._counters_dev = (
                latent.counters0() if self.latent else
                recurrent.counters0(config) if self.recurrent else ())
            # the per-slot recurrent state (ssm, conv); no leaves where
            # the family has none
            self._rstate = (recurrent.state_pools(config, self.max_slots)
                            if self.recurrent else ())
        else:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            self._check_state_shardable(state)
            self.devices = mesh_devices(self.tp)
            self.mesh = Mesh(np.asarray(self.devices), (TP_AXIS,))
            self._pool_pspec = PartitionSpec(
                None, None, TP_AXIS, None, None)
            self._scale_pspec = PartitionSpec(None, None, TP_AXIS, None)
            rep = NamedSharding(self.mesh, PartitionSpec())
            self.state = {k: self._place(k, v) for k, v in state.items()}
            pool_sh = NamedSharding(self.mesh, self._pool_pspec)
            self.kpool = jax.device_put(jnp.zeros(pool_shape, pool_dtype),
                                        pool_sh)
            self.vpool = jax.device_put(jnp.zeros(pool_shape, pool_dtype),
                                        pool_sh)
            if self.kv_quant:
                scale_sh = NamedSharding(self.mesh, self._scale_pspec)
                self.kscale = jax.device_put(
                    jnp.zeros(scale_shape, jnp.float32), scale_sh)
                self.vscale = jax.device_put(
                    jnp.zeros(scale_shape, jnp.float32), scale_sh)
            else:
                self.kscale = self.vscale = ()
            if self.lora_slots:
                specs = self._lora_pspecs()
                bank = self._build_lora_bank()
                self.lora = {
                    "a": {k: jax.device_put(
                        v, NamedSharding(self.mesh, specs["a"][k]))
                        for k, v in bank["a"].items()},
                    "b": {k: jax.device_put(
                        v, NamedSharding(self.mesh, specs["b"][k]))
                        for k, v in bank["b"].items()},
                    "scale": jax.device_put(bank["scale"], rep),
                }
                self._aidx_dev = jax.device_put(
                    jnp.zeros((self.max_slots,), jnp.int32), rep)
            else:
                self.lora = self._aidx_dev = ()
            self._cos = jax.device_put(cos, rep)
            self._sin = jax.device_put(sin, rep)
            self._table_dev = jax.device_put(jnp.asarray(table0), rep)
            self._pos_dev = jax.device_put(
                jnp.zeros((self.max_slots,), jnp.int32), rep)
            self._tok_dev = jax.device_put(
                jnp.zeros((self.max_slots,), jnp.int32), rep)
            self._active_dev = jax.device_put(
                jnp.zeros((self.max_slots,), jnp.int32), rep)
            self._ring_dev = jax.device_put(
                jnp.zeros(ring_shape, jnp.int32), rep)
            self._ridx_dev = jax.device_put(
                jnp.zeros((), jnp.int32), rep)
            self._counters_dev = self._rstate = ()

        self.decode_traces = 0      # python mirror of _M_STEP_TRACES
        self.verify_traces = 0      # python mirror of _M_VERIFY_TRACES
        self.push_traces = 0        # the slot patch: one for every slot
        self._ring_held = None      # hold_ring()'s, until fetch_ring()
        self._step_fn = self._make_step_fn()
        self._push_fn = self._make_push_fn()
        self._verify_fn = (self._make_verify_fn() if self.spec_k
                           else None)
        self._prefill_fns: dict[int, object] = {}   # bucket -> jitted fn
        self._prefill_cached_fns: dict[int, object] = {}
        self._copy_page_fn = self._make_copy_page_fn()
        self._copy_page_compiled = False    # compile-ledger first-call

        # per-device footprint estimates + mesh-position registration for
        # the resource snapshot (CPU devices export no memory_stats, so
        # /debug/resources reports these alongside whatever stats exist)
        itemsize = jnp.dtype(pool_dtype).itemsize
        pool_total = ((1 if self.latent else 2)
                      * int(np.prod(pool_shape)) * itemsize)
        if self.kv_quant:           # + the f32 scale pools
            pool_total += 2 * int(np.prod(scale_shape)) * 4
        self._pool_bytes_per_device = (
            int(per_device_pool_bytes) if per_device_pool_bytes
            else pool_total // self.tp)
        self.recurrent_state_bytes = (
            recurrent.state_bytes(config, self.max_slots)
            if self.recurrent else 0)
        sharded = sum(
            _leaf_bytes(v) for k, v in state.items()
            if k.endswith(_COL_SHARDED) or k.endswith(_ROW_SHARDED))
        replicated = sum(_leaf_bytes(v)
                         for v in state.values()) - sharded
        self._weight_bytes_per_device = sharded // self.tp + replicated
        if self.lora_slots:
            # bank halves shard like their base weights: A for the
            # row-sharded projections, B for the column-sharded ones
            lora_sharded = sum(
                _leaf_bytes(self.lora["a"][k]) for k in _LORA_ROW_KEYS
            ) + sum(_leaf_bytes(self.lora["b"][k])
                    for k in self.lora["b"] if k not in _LORA_ROW_KEYS)
            lora_total = sum(_leaf_bytes(v) for v in
                             jax.tree_util.tree_leaves(self.lora))
            self._lora_bytes_per_device = (
                lora_sharded // self.tp + (lora_total - lora_sharded))
        else:
            self._lora_bytes_per_device = 0
        resource_tracker().set_mesh({
            f"{d.platform}:{d.id}": {TP_AXIS: i}
            for i, d in enumerate(self.devices)})

    # ----------------------------------------------------------- placement
    @staticmethod
    def _spec_for(key: str):
        from jax.sharding import PartitionSpec
        if key.endswith(_COL_SHARDED):
            return PartitionSpec(None, TP_AXIS)
        if key.endswith(_ROW_SHARDED):
            return PartitionSpec(TP_AXIS, None)
        return PartitionSpec()      # embeddings / norms / lm_head

    @staticmethod
    def _validate_quantized_state(state: dict):
        """Loud construction-time rejection of MALFORMED quantized
        leaves (both tp modes): a broken QuantizedWeight would otherwise
        surface as an opaque shape error deep inside the first trace."""
        for key, v in state.items():
            if not isinstance(v, QuantizedWeight):
                continue
            if v.kind not in ("int8", "int4"):
                raise ValueError(
                    f"state[{key!r}]: unsupported quant kind {v.kind!r}"
                    " (expected 'int8' or 'int4')")
            if not (hasattr(v.q, "shape") and hasattr(v.scale, "shape")):
                raise ValueError(
                    f"state[{key!r}]: QuantizedWeight q/scale must be "
                    "arrays (missing scale?)")
            if v.q.ndim != 2:
                raise ValueError(
                    f"state[{key!r}]: quantized values must be 2-D, "
                    f"got shape {tuple(v.q.shape)}")
            if v.scale.ndim != 1 or v.scale.shape[0] != v.q.shape[1]:
                raise ValueError(
                    f"state[{key!r}]: scale shape "
                    f"{tuple(v.scale.shape)} does not match one scale "
                    f"per output channel (expected ({v.q.shape[1]},))")
            rows = v.k // 2 if v.kind == "int4" else v.k
            if v.q.shape[0] != rows:
                raise ValueError(
                    f"state[{key!r}]: {v.kind} values have "
                    f"{v.q.shape[0]} rows, expected {rows} for "
                    f"K={v.k}")

    def _check_state_shardable(self, state: dict):
        for k, v in state.items():
            if k.endswith(_FUSED_KEYS):
                raise ValueError(
                    f"state has fused weight {k!r}: fused serving "
                    "states are single-chip only (tp=1) — the tp>1 "
                    "runner shards the per-projection q/k/v and "
                    "gate/up weights individually")
            if isinstance(v, QuantizedWeight):
                if k.endswith(_ROW_SHARDED):
                    if v.q.shape[0] % self.tp:
                        raise ValueError(
                            f"state[{k!r}]: quantized K rows "
                            f"{v.q.shape[0]} not divisible by tp="
                            f"{self.tp}" + (
                                " (int4 packs two K rows per int8 "
                                "byte — K/2 must divide)"
                                if v.kind == "int4" else ""))
                elif k.endswith(_COL_SHARDED):
                    if v.q.shape[1] % self.tp:
                        raise ValueError(
                            f"state[{k!r}]: quantized N columns "
                            f"{v.q.shape[1]} not divisible by tp="
                            f"{self.tp}")
                continue
            if not isinstance(v, (np.ndarray, jnp.ndarray)):
                raise ValueError(
                    f"state[{k!r}] is {type(v).__name__}, not an array "
                    "or QuantizedWeight — cannot be head-sharded")

    def _quant_specs(self, key: str, v: QuantizedWeight):
        """(q_spec, scale_spec, local_k) for one quantized leaf.

        Column-sharded projections split q and the per-output-channel
        scale along N and keep the global K.  Row-sharded projections
        split q along K — each shard's ``weight_only_matmul`` K-check
        must see the LOCAL contraction length, so the placed leaf's aux
        ``k`` becomes ``k // tp`` — while the per-N scale replicates
        (it multiplies the partial products before the psum, which is
        linear, so scaling per shard is exact)."""
        from jax.sharding import PartitionSpec
        if key.endswith(_COL_SHARDED):
            return (PartitionSpec(None, TP_AXIS),
                    PartitionSpec(TP_AXIS), v.k)
        if key.endswith(_ROW_SHARDED):
            return (PartitionSpec(TP_AXIS, None), PartitionSpec(),
                    v.k // self.tp)
        return PartitionSpec(), PartitionSpec(), v.k

    def _place(self, key: str, v):
        """device_put one weight leaf with its tp sharding."""
        from jax.sharding import NamedSharding
        if isinstance(v, QuantizedWeight):
            qspec, sspec, k_local = self._quant_specs(key, v)
            q = jax.device_put(jnp.asarray(v.q),
                               NamedSharding(self.mesh, qspec))
            scale = jax.device_put(jnp.asarray(v.scale),
                                   NamedSharding(self.mesh, sspec))
            return QuantizedWeight(q, scale, kind=v.kind, k=k_local)
        return jax.device_put(
            jnp.asarray(v), NamedSharding(self.mesh, self._spec_for(key)))

    def _state_specs(self):
        """Pytree of shard_map in_specs mirroring the placed state:
        QuantizedWeight leaves become QuantizedWeight-of-PartitionSpecs
        whose aux (kind, k) copies the PLACED leaf — row shards already
        carry the local k — so the spec tree and the argument tree
        flatten identically."""
        specs = {}
        for k, v in self.state.items():
            if isinstance(v, QuantizedWeight):
                qspec, sspec, _ = self._quant_specs(k, v)
                specs[k] = QuantizedWeight(qspec, sspec, kind=v.kind,
                                           k=v.k)
            else:
                specs[k] = self._spec_for(k)
        return specs

    # ---------------------------------------------------------- LoRA bank
    def _build_lora_bank(self):
        """Zeroed packed bank ``{"a": {key: [L, rows, r, in]}, "b":
        {key: [L, rows, r, out]}, "scale": [rows]}`` — row 0 stays all
        zero forever (the no-adapter row), so a mixed batch indexes one
        bank in ONE traced program.  f32 regardless of base dtype: the
        delta matmuls accumulate in f32 anyway and the bank is tiny."""
        from ..lora.store import lora_key_dims
        dims = lora_key_dims(self.config)
        L = self.config.num_hidden_layers
        rows, r = self.lora_slots + 1, self.lora_rank
        return {
            "a": {k: jnp.zeros((L, rows, r, ind), jnp.float32)
                  for k, (ind, _) in dims.items()},
            "b": {k: jnp.zeros((L, rows, r, outd), jnp.float32)
                  for k, (_, outd) in dims.items()},
            "scale": jnp.zeros((rows,), jnp.float32),
        }

    def _lora_pspecs(self):
        """shard_map/placement specs mirroring the bank pytree: B
        column-sharded for q/k/v/gate/up, A row-sharded for o/down
        (both on the trailing dim axis of [L, rows, r, dim]), scale
        replicated — the existing o/down psums stay the only
        collectives.  Off mode collapses to one P() broadcast over the
        empty tuple."""
        from jax.sharding import PartitionSpec as P
        if not self.lora_slots:
            return P()
        from ..lora.store import lora_key_dims
        keys = list(lora_key_dims(self.config))
        col = P(None, None, None, TP_AXIS)
        return {
            "a": {k: (col if k in _LORA_ROW_KEYS else P())
                  for k in keys},
            "b": {k: (P() if k in _LORA_ROW_KEYS else col)
                  for k in keys},
            "scale": P(),
        }

    def load_adapter(self, row: int, a: dict, b: dict, scale: float):
        """Write one adapter into bank row ``row`` (eager ``.at[].set``
        per leaf — admission-path, never per step).  ``a``/``b`` map
        each projection key to its full [L, r, dim] host tensor; on a
        mesh the updated leaves re-pin to their bank sharding so the
        next traced step sees the layout it was traced for."""
        if not self.lora_slots:
            raise RuntimeError(
                "runner built with lora_slots=0 has no adapter bank")
        if not 1 <= int(row) <= self.lora_slots:
            raise ValueError(
                f"bank row {row} out of range 1..{self.lora_slots} "
                "(row 0 is the reserved no-adapter row)")
        new_a = {k: v.at[:, row].set(jnp.asarray(a[k], jnp.float32))
                 for k, v in self.lora["a"].items()}
        new_b = {k: v.at[:, row].set(jnp.asarray(b[k], jnp.float32))
                 for k, v in self.lora["b"].items()}
        scale_arr = self.lora["scale"].at[row].set(float(scale))
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            specs = self._lora_pspecs()
            new_a = {k: jax.device_put(
                v, NamedSharding(self.mesh, specs["a"][k]))
                for k, v in new_a.items()}
            new_b = {k: jax.device_put(
                v, NamedSharding(self.mesh, specs["b"][k]))
                for k, v in new_b.items()}
            scale_arr = jax.device_put(
                scale_arr, NamedSharding(self.mesh, specs["scale"]))
        self.lora = {"a": new_a, "b": new_b, "scale": scale_arr}

    def lora_bank_bytes(self) -> int:
        """Total device bytes of the adapter bank (0 when off)."""
        if not self.lora_slots:
            return 0
        return sum(_leaf_bytes(v)
                   for v in jax.tree_util.tree_leaves(self.lora))

    # ------------------------------------------------------- jitted bodies
    # Every jitted signature threads (kscale, vscale) right after the
    # pools, and (lora, aidx) at the tail.  Off modes pass the empty
    # tuples stored at construction: zero pytree leaves, so an option
    # that is off adds no argument and no op to the program.  The
    # shard_map specs use P() for those positions (a pspec broadcasts
    # over an empty subtree).
    def _make_step_fn(self):
        if self.tp == 1:
            return jax.jit(self._build_step(None),
                           donate_argnums=(1, 2, 3, 4, 6, 7, 10, 15, 16))
        from jax.sharding import PartitionSpec as P
        pool = self._pool_pspec
        sspec = self._scale_pspec if self.kv_quant else P()
        mapped = jax.shard_map(
            self._build_step(TP_AXIS), mesh=self.mesh,
            in_specs=(self._state_specs(), pool, pool, sspec, sspec,
                      P(), P(), P(), P(), P(), P(), P(), P(),
                      self._lora_pspecs(), P(), P(), P()),
            out_specs=(pool, pool, sspec, sspec, P(), P(), P(), P(),
                       P(), P(), P()),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(1, 2, 3, 4, 6, 7, 10))

    def _count_step_trace(self):
        """Runs when a decode step is traced, never when it runs: a
        second count means an admission/eviction re-traced the step."""
        self.decode_traces += 1
        _M_STEP_TRACES.inc()

    def _build_step(self, axis):
        """The decode step's body: on one chip (``axis=None``) the jitted
        program, on a mesh the ``shard_map`` body.  There everything
        except the pools is replicated; the head-parallel layers psum at
        the o/down projections, so the post-norm logits (and therefore
        the argmax'd next token and the ring) are device-invariant."""
        if self.latent:
            return latent.build_step(self, self._count_step_trace)
        if self.recurrent:
            return recurrent.build_step(self, self._count_step_trace)
        cfg = self.config
        L = cfg.num_hidden_layers
        emit_logits = self.emit_logits
        rope_len = self._rope_len
        wide_ring = self.spec_k > 0
        count_trace = self._count_step_trace

        def decode_step(state, kpool, vpool, kscale, vscale, table, pos,
                        tok, active, ring, ridx, cos, sin, lora, aidx,
                        counters, rstate):
            count_trace()
            cache = PagedKV(kpool, vpool, kscale, vscale)
            # a finished slot keeps decoding until the next host sync
            # (deferred-sync overrun); clamp so its rope/table lookups
            # stay in range — overrun writes land in the slot's own
            # reserved tail or the dump page, never another sequence
            with jax.named_scope("embed"):
                posc = jnp.minimum(pos, rope_len - 1)
                emb = jnp.take(state["llama.embed_tokens.weight"], tok,
                               axis=0)
                cos1, sin1 = _rope_at(cos, sin, posc)
            h = emb
            for i in range(L):
                h, cache = decode_layer(
                    _layer_weights(state, i), h, cache, table, cos1, sin1,
                    posc, cfg, li=i, axis=axis, lora=lora, aidx=aidx)
            with jax.named_scope("head"):
                h = _rms(h[:, None], state["llama.norm.weight"],
                         cfg.rms_norm_eps)[:, 0]
                logits = _logits_of(state, h).astype(jnp.float32)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                act = active.astype(bool)
                pos2 = pos + active                 # idle slots stay parked
                tok2 = jnp.where(act, nxt, tok)     # greedy chains on device
                ring2 = (ring.at[ridx, :, 0].set(nxt) if wide_ring
                         else ring.at[ridx].set(nxt))
                ridx2 = (ridx + 1) % ring.shape[0]
            return (*cache, pos2, tok2, ring2, ridx2,
                    logits if emit_logits else jnp.zeros((), jnp.float32),
                    counters, rstate)

        return decode_step

    def _make_verify_fn(self):
        if self.tp == 1:
            return jax.jit(self._build_verify(None),
                           donate_argnums=(1, 2, 3, 4, 6, 7, 10))
        from jax.sharding import PartitionSpec as P
        pool = self._pool_pspec
        sspec = self._scale_pspec if self.kv_quant else P()
        mapped = jax.shard_map(
            self._build_verify(TP_AXIS), mesh=self.mesh,
            in_specs=(self._state_specs(), pool, pool, sspec, sspec,
                      P(), P(), P(), P(), P(), P(), P(), P(), P(),
                      P(), self._lora_pspecs(), P()),
            out_specs=(pool, pool, sspec, sspec, P(), P(), P(), P()),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(1, 2, 3, 4, 6, 7, 10))

    def _build_verify(self, axis):
        """The speculative verify program: score ``k+1`` candidate
        positions per slot in ONE step.

        The ``[slots, k+1]`` token grid (the slot's current token +
        its ``k`` draft tokens) flattens to a ``[slots*(k+1)]`` batch
        that runs the SAME paged decode layer as the plain step — every
        row writes its token's KV at ``pos + j`` first, then attends
        with ``lens = pos + j + 1``, so row ``j`` sees exactly the
        prefix a sequential decode would have seen (its own slot's
        writes ``j' <= j``; later rows' writes sit past ``lens`` and
        rejected rows' stale KV is masked the same way until a later
        step overwrites it in place — KV rollback is free).  Acceptance
        is computed on device: the longest prefix where the draft
        matches the target argmax, ``+1`` for the correction/bonus
        token, advances pos/tok; the full candidate row lands in the
        wide ring for the host to re-derive the same acceptance without
        an extra transfer.  Slots with no draft (``dlen == 0``) reduce
        exactly to the plain step.  Shapes depend only on
        ``(slots, k)`` — drafts and their lengths are data, so this
        traces ONCE; with the plain step that makes ``decode_traces``
        exactly 2 for a speculative engine.

        A draft-model proposer or parallel sampling (n>1) later reuses
        this program unchanged: both only change how the ``draft`` grid
        is filled on the host, not how it is scored."""
        cfg = self.config
        L = cfg.num_hidden_layers
        rope_len = self._rope_len
        k = self.spec_k
        M = k + 1
        runner = self

        def verify_step(state, kpool, vpool, kscale, vscale, table, pos,
                        tok, active, ring, ridx, draft, dlen, cos, sin,
                        lora, aidx):
            # trace-time counters, exactly like the plain step body
            runner.decode_traces += 1
            runner.verify_traces += 1
            _M_STEP_TRACES.inc()
            _M_VERIFY_TRACES.inc()
            cache = PagedKV(kpool, vpool, kscale, vscale)
            with jax.named_scope("embed"):
                S = tok.shape[0]
                # [S, M] candidate grid: column 0 is the slot's current
                # token (the plain step's input), columns 1..k its drafts
                grid = jnp.concatenate([tok[:, None], draft], axis=1)
                offs = jnp.arange(M, dtype=jnp.int32)
                pos_f = (pos[:, None] + offs[None, :]).reshape(-1)
                posc = jnp.minimum(pos_f, rope_len - 1)
                tok_f = grid.reshape(-1)
                table_f = jnp.repeat(table, M, axis=0)
                # every candidate row of a slot shares its adapter; `lora`
                # is a pytree whose STRUCTURE (empty vs non-empty tuple)
                # carries the on/off bit — truthiness is trace-time static
                # tpu-lint: disable=jit-traced-branch
                aidx_f = jnp.repeat(aidx, M) if lora else aidx
                emb = jnp.take(state["llama.embed_tokens.weight"], tok_f,
                               axis=0)
                cos1, sin1 = _rope_at(cos, sin, posc)
            h = emb
            for i in range(L):
                h, cache = decode_layer(
                    _layer_weights(state, i), h, cache, table_f, cos1,
                    sin1, posc, cfg, li=i, axis=axis, lora=lora,
                    aidx=aidx_f)
            with jax.named_scope("head"):
                h = _rms(h[:, None], state["llama.norm.weight"],
                         cfg.rms_norm_eps)[:, 0]
                logits = _logits_of(state, h).astype(jnp.float32)
                y = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                y = y.reshape(S, M)
                # longest matching prefix: draft[:, j] proposed what the
                # target's own argmax y[:, j] confirms (or not)
                m = ((draft == y[:, :k]) &
                     (offs[None, :k] < dlen[:, None])).astype(jnp.int32)
                # cast back: cumprod/sum promote to int64 under x64, which
                # would change pos2's dtype and re-trace the plain step
                acc = jnp.cumprod(m, axis=1).sum(axis=1).astype(jnp.int32)
                commit = (acc + 1) * active                     # [S]; idle: 0
                pos2 = pos + commit
                tok_new = jnp.take_along_axis(y, acc[:, None], axis=1)[:, 0]
                tok2 = jnp.where(active.astype(bool), tok_new, tok)
                ring2 = ring.at[ridx].set(y)
                ridx2 = (ridx + 1) % ring.shape[0]
            return (*cache, pos2, tok2, ring2, ridx2)

        return verify_step

    def _make_copy_page_fn(self):
        kv_quant = self.kv_quant

        def copy_page(kp, vp, ks, vs, src, dst):
            # every pool there is (scale rows travel with their page; a
            # latent cache has the one pool): an absent one has no leaves
            return jax.tree.map(lambda p: p.at[:, dst].set(p[:, src]),
                                (kp, vp, ks, vs))

        if self.tp == 1:
            # CoW page copy: src/dst are data — one trace for the engine
            return jax.jit(copy_page, donate_argnums=(0, 1, 2, 3))
        from jax.sharding import PartitionSpec as P
        pool = self._pool_pspec
        sspec = self._scale_pspec if kv_quant else P()
        # per-shard copy: a page holds every local head's rows, so the
        # CoW duplicate is collective-free
        mapped = jax.shard_map(
            copy_page, mesh=self.mesh,
            in_specs=(pool, pool, sspec, sspec, P(), P()),
            out_specs=(pool, pool, sspec, sspec), check_vma=False)
        return jax.jit(mapped, donate_argnums=(0, 1, 2, 3))

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        if self.latent or self.recurrent:
            fn = jax.jit((latent if self.latent else recurrent).build_prefill(
                self, bucket, _M_PREFILL_TRACES.labels(str(bucket)).inc),
                donate_argnums=(4, 5, 6, 7, 12))
            self._prefill_fns[bucket] = fn
            return fn
        cfg = self.config
        L = cfg.num_hidden_layers
        tp = self.tp
        kv_quant = self.kv_quant
        axis = None if tp == 1 else TP_AXIS

        def prefill(state, ids, length, table_row, kpool, vpool,
                    kscale, vscale, cos, sin, lora, aidx, rstate, slot):
            _M_PREFILL_TRACES.labels(str(bucket)).inc()
            cache = PagedKV(kpool, vpool, kscale, vscale)
            with jax.named_scope("embed"):
                x = jnp.take(state["llama.embed_tokens.weight"], ids, axis=0)
                pmask = jnp.arange(bucket)[None, :] < length
            for i in range(L):
                x, k, v = prefill_layer(
                    _layer_weights(state, i), x, cos[:bucket],
                    sin[:bucket], pmask, cfg, li=i, axis=axis, lora=lora,
                    aidx=aidx)
                with jax.named_scope("kv.write"):
                    # one scatter a page (ROADMAP S12)
                    cache = cache.write_pages(i, table_row, k, v)
            with jax.named_scope("head"):
                x = _rms(x, state["llama.norm.weight"], cfg.rms_norm_eps)
                last = jnp.take_along_axis(
                    x, (length - 1)[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0]
                logits = _logits_of(state, last).astype(jnp.float32)
            return (*cache, logits, rstate)

        # kpool/vpool donation: prefill updates the pool in place instead
        # of double-buffering the engine's whole KV footprint per admit
        if tp == 1:
            fn = jax.jit(prefill, donate_argnums=(4, 5, 6, 7, 12))
        else:
            from jax.sharding import PartitionSpec as P
            pool = self._pool_pspec
            sspec = self._scale_pspec if kv_quant else P()
            mapped = jax.shard_map(
                prefill, mesh=self.mesh,
                in_specs=(self._state_specs(), P(), P(), P(), pool,
                          pool, sspec, sspec, P(), P(),
                          self._lora_pspecs(), P(), P(), P()),
                out_specs=(pool, pool, sspec, sspec, P(), P()),
                check_vma=False)
            fn = jax.jit(mapped, donate_argnums=(4, 5, 6, 7))
        self._prefill_fns[bucket] = fn
        return fn

    def _prefill_cached_fn(self, bucket: int):
        """Suffix prefill for a prompt whose first ``cached_len`` tokens
        are already resident in the pool (shared prefix pages and/or a
        CoW-copied tail).  One trace per suffix bucket: the prefix
        length, table row, and positions are all data."""
        fn = self._prefill_cached_fns.get(bucket)
        if fn is not None:
            return fn
        if self.recurrent:
            recurrent.check_options(self.config, enable_prefix_cache=True)
        if self.latent:
            fn = jax.jit(latent.build_prefill_cached(
                self, bucket,
                _M_PREFILL_TRACES.labels(f"cached:{bucket}").inc),
                donate_argnums=(5, 6, 7, 8))
            self._prefill_cached_fns[bucket] = fn
            return fn
        cfg = self.config
        L = cfg.num_hidden_layers
        ps = self.page_size
        W = self.table_width
        dump = self.dump_page
        rope_len = self._rope_len
        tp = self.tp
        kv_quant = self.kv_quant
        axis = None if tp == 1 else TP_AXIS

        def prefill_cached(state, ids, length, cached_len, row, kpool,
                           vpool, kscale, vscale, cos, sin, lora, aidx):
            _M_PREFILL_TRACES.labels(f"cached:{bucket}").inc()
            cache = PagedKV(kpool, vpool, kscale, vscale)
            with jax.named_scope("embed"):
                x = jnp.take(state["llama.embed_tokens.weight"], ids, axis=0)
                j = jnp.arange(bucket)
                absp = cached_len + j               # absolute positions
                posc = jnp.minimum(absp, rope_len - 1)
                cos_s = jnp.take(cos, posc, axis=0)
                sin_s = jnp.take(sin, posc, axis=0)
                # suffix queries see: resident prefix keys (< cached_len),
                # then causal within the (padded) suffix
                t_pre = jnp.arange(W * ps)
                pre_ok = jnp.broadcast_to(t_pre[None, :] < cached_len,
                                          (bucket, W * ps))
                suf_ok = (j[None, :] <= j[:, None]) & (j[None, :] < length[0])
                mask = jnp.concatenate([pre_ok, suf_ok], axis=1)[None, None]
                # per-token write targets (padding lands on the dump page)
                valid = j < length[0]
                page_w = jnp.where(valid,
                                   row[jnp.minimum(absp // ps, W - 1)], dump)
                off = absp % ps
            for i in range(L):
                # the resident prefix (this shard's heads of it) ahead of
                # the projections, then the suffix rows where a decode
                # step would write them
                with jax.named_scope("attn.prefill"):
                    kpre, vpre = cache.gather(i, row, x.dtype)
                x, k, v = prefill_layer(
                    _layer_weights(state, i), x, cos_s, sin_s, mask, cfg,
                    li=i, axis=axis, lora=lora, aidx=aidx,
                    prefix=(kpre[None], vpre[None]))
                with jax.named_scope("kv.write"):
                    cache = cache.write(i, page_w, off, k[0], v[0])
            with jax.named_scope("head"):
                x = _rms(x, state["llama.norm.weight"], cfg.rms_norm_eps)
                last = jnp.take_along_axis(
                    x, (length - 1)[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0]
                logits = _logits_of(state, last).astype(jnp.float32)
            return (*cache, logits)

        if tp == 1:
            fn = jax.jit(prefill_cached, donate_argnums=(5, 6, 7, 8))
        else:
            from jax.sharding import PartitionSpec as P
            pool = self._pool_pspec
            sspec = self._scale_pspec if kv_quant else P()
            mapped = jax.shard_map(
                prefill_cached, mesh=self.mesh,
                in_specs=(self._state_specs(), P(), P(), P(), P(), pool,
                          pool, sspec, sspec, P(), P(),
                          self._lora_pspecs(), P()),
                out_specs=(pool, pool, sspec, sspec, P()),
                check_vma=False)
            fn = jax.jit(mapped, donate_argnums=(5, 6, 7, 8))
        self._prefill_cached_fns[bucket] = fn
        return fn

    # ------------------------------------------------------------ the seam
    def decode_step(self):
        """One lockstep decode step over every slot.  Returns the step's
        [slots, V] logits handle when the runner emits logits, else
        None.  First call after a (re)trace lands in the compile
        ledger."""
        traces_before = self.decode_traces
        t0 = time.perf_counter()
        (self.kpool, self.vpool, self.kscale, self.vscale,
         self._pos_dev, self._tok_dev, self._ring_dev, self._ridx_dev,
         logits, self._counters_dev, self._rstate) = self._step_fn(
            self.state, self.kpool, self.vpool, self.kscale,
            self.vscale, self._table_dev, self._pos_dev, self._tok_dev,
            self._active_dev, self._ring_dev, self._ridx_dev,
            self._cos, self._sin, self.lora, self._aidx_dev,
            self._counters_dev, self._rstate)
        if self.decode_traces != traces_before:
            sig = f"slots={self.max_slots} ring={self.sync_interval}"
            if self.tp > 1:
                sig += f" tp={self.tp}"
            record_compile("decode_step", t0, signature=sig)
        return logits if self.emit_logits else None

    def verify_step(self, draft: np.ndarray, dlen: np.ndarray):
        """One speculative verify step: ``draft`` [slots, k] int32
        candidate tokens, ``dlen`` [slots] int32 drafted counts (0 =
        the slot takes the plain-step path inside the program).  The
        uploads are data — shapes are fixed at construction, so this
        traces once.  Acceptance happens on device (pos/tok advance by
        the accepted count + 1); the host re-derives it from the wide
        ring row at the next sync."""
        if self._verify_fn is None:
            raise RuntimeError("runner built with spec_k=0 has no "
                               "verify program")
        traces_before = self.verify_traces
        t0 = time.perf_counter()
        (self.kpool, self.vpool, self.kscale, self.vscale,
         self._pos_dev, self._tok_dev, self._ring_dev,
         self._ridx_dev) = self._verify_fn(
            self.state, self.kpool, self.vpool, self.kscale,
            self.vscale, self._table_dev, self._pos_dev, self._tok_dev,
            self._active_dev, self._ring_dev, self._ridx_dev,
            jnp.asarray(draft, jnp.int32), jnp.asarray(dlen, jnp.int32),
            self._cos, self._sin, self.lora, self._aidx_dev)
        if self.verify_traces != traces_before:
            sig = (f"slots={self.max_slots} k={self.spec_k} "
                   f"ring={self.sync_interval}")
            if self.tp > 1:
                sig += f" tp={self.tp}"
            record_compile("verify_step", t0, signature=sig)

    def _prefill_aidx(self, adapter_row: int):
        """Scalar bank index for a whole-prompt prefill (one request =
        one adapter); the empty tuple in off mode keeps the jitted
        signature leaf-free."""
        if not self.lora_slots:
            return ()
        return jnp.asarray(int(adapter_row), jnp.int32)

    def prefill(self, ids: np.ndarray, plen: int, row: np.ndarray,
                adapter_row: int = 0, slot: int | None = None):
        """Full-prompt prefill: pages the prompt's KV into the pool and
        returns the last-token logits handle.  ``ids`` is the
        [1, bucket] padded prompt.  ``slot`` is where the request will
        decode: a family with a per-slot recurrent state writes that
        slot's state here (the others take no such argument: an empty
        tuple, no leaf)."""
        if self.recurrent and slot is None:
            raise ValueError("a recurrent family's prefill writes its "
                             "slot's state: pass slot=")
        bucket = ids.shape[1]
        fresh = bucket not in self._prefill_fns
        fn = self._prefill_fn(bucket)
        t0 = time.perf_counter()
        (self.kpool, self.vpool, self.kscale, self.vscale,
         logits, self._rstate) = fn(
            self.state, jnp.asarray(ids),
            jnp.asarray([plen], jnp.int32),
            jnp.asarray(row[:bucket // self.page_size]),
            self.kpool, self.vpool, self.kscale, self.vscale,
            self._cos, self._sin, self.lora,
            self._prefill_aidx(adapter_row), self._rstate,
            jnp.asarray(int(slot), jnp.int32) if self.recurrent else ())
        if fresh:
            record_compile(f"prefill[{bucket}]", t0,
                           signature=f"ids=[1,{bucket}]")
        return logits

    def prefill_cached(self, ids: np.ndarray, suffix_len: int,
                       cached_len: int, row: np.ndarray,
                       adapter_row: int = 0):
        """Cached-suffix prefill against the resident prefix pages."""
        bucket = ids.shape[1]
        fresh = bucket not in self._prefill_cached_fns
        fn = self._prefill_cached_fn(bucket)
        t0 = time.perf_counter()
        (self.kpool, self.vpool, self.kscale, self.vscale,
         logits) = fn(
            self.state, jnp.asarray(ids),
            jnp.asarray([suffix_len], jnp.int32),
            jnp.asarray(cached_len, jnp.int32), jnp.asarray(row),
            self.kpool, self.vpool, self.kscale, self.vscale,
            self._cos, self._sin, self.lora,
            self._prefill_aidx(adapter_row))
        if fresh:
            record_compile(f"prefill_cached[{bucket}]", t0,
                           signature=f"ids=[1,{bucket}]")
        return logits

    def copy_page(self, src: int, dst: int):
        """Copy-on-write page duplicate (head-local on the mesh)."""
        fresh = not self._copy_page_compiled
        t0 = time.perf_counter()
        (self.kpool, self.vpool, self.kscale,
         self.vscale) = self._copy_page_fn(
            self.kpool, self.vpool, self.kscale, self.vscale,
            jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))
        if fresh:
            self._copy_page_compiled = True
            record_compile("copy_page", t0,
                           signature=f"pool={self.kpool.shape}")

    def read_page(self, page: int):
        """Device -> host copy of one KV page: ``(k, v)`` numpy arrays
        of shape [L, kvh, page_size, hd] (full heads — shards gather
        transparently on the mesh), plus ``(kscale, vscale)``
        [L, kvh, page_size] f32 when the pools are int8 — the spill
        tier moves the quantized bytes, never a dequantized copy; a
        latent cache's page is one array [L, page_size, width].
        Preemption-spill only: this is a host sync per call, never on
        the steady decode path."""
        return tuple(np.asarray(p[:, page]) for p in jax.tree.leaves(
            (self.kpool, self.vpool, self.kscale, self.vscale)))

    def write_page(self, page: int, k, v=None, kscale=None, vscale=None):
        """Host -> device copy of one KV page (preempted-request resume
        unparking a host-tier copy).  Eager per-call dispatch is fine —
        this runs once per restored page at admission, not per step."""
        kpool = self.kpool.at[:, page].set(
            jnp.asarray(k, self.kpool.dtype))
        if self.latent:                 # one pool: a page is its rows
            self.kpool = kpool
            return
        vpool = self.vpool.at[:, page].set(
            jnp.asarray(v, self.vpool.dtype))
        if self.kv_quant:
            if kscale is None or vscale is None:
                raise ValueError(
                    "int8 KV pages restore with their scales: "
                    "write_page(page, k, v, kscale, vscale)")
            kscale_p = self.kscale.at[:, page].set(
                jnp.asarray(kscale, jnp.float32))
            vscale_p = self.vscale.at[:, page].set(
                jnp.asarray(vscale, jnp.float32))
        if self.mesh is not None:
            # pin the result back to the head-sharded pool layout so the
            # next shard_map program sees the sharding it was traced for
            from jax.sharding import NamedSharding
            sh = NamedSharding(self.mesh, self._pool_pspec)
            kpool = jax.device_put(kpool, sh)
            vpool = jax.device_put(vpool, sh)
            if self.kv_quant:
                ssh = NamedSharding(self.mesh, self._scale_pspec)
                kscale_p = jax.device_put(kscale_p, ssh)
                vscale_p = jax.device_put(vscale_p, ssh)
        self.kpool = kpool
        self.vpool = vpool
        if self.kv_quant:
            self.kscale = kscale_p
            self.vscale = vscale_p

    def _make_push_fn(self):
        """The slot patch: ONE program for every slot and every option.
        The host's values come as one int32 vector ``[slot, pos, tok,
        active, adapter row, table row...]``; the slot is data, so this
        traces once.  The five state arrays are donated (``aidx`` is the
        empty tuple without adapters: no leaf, no op)."""
        runner = self

        def push_slot(table, pos, tok, active, aidx, packed):
            runner.push_traces += 1     # at trace time, never at run time
            slot = packed[0]
            return (table.at[slot].set(packed[5:]),
                    pos.at[slot].set(packed[1]),
                    tok.at[slot].set(packed[2]),
                    active.at[slot].set(packed[3]),
                    jax.tree.map(lambda a: a.at[slot].set(packed[4]),
                                 aidx))

        kw = {}
        if self.mesh is not None:
            # the decode state is replicated over the mesh and stays so
            from jax.sharding import NamedSharding, PartitionSpec
            kw["out_shardings"] = NamedSharding(self.mesh, PartitionSpec())
        return jax.jit(push_slot, donate_argnums=(0, 1, 2, 3, 4), **kw)

    def push_slot(self, slot: int, row: np.ndarray, pos: int, tok: int,
                  active: int, adapter_row: int = 0):
        """Patch ONE slot's row of the device-resident decode state
        (admission / eviction only — never per step): one small upload,
        one program."""
        packed = np.empty((5 + self.table_width,), np.int32)
        packed[:5] = (slot, pos, tok, active, adapter_row)
        packed[5:] = row
        traces_before = self.push_traces
        t0 = time.perf_counter()
        (self._table_dev, self._pos_dev, self._tok_dev, self._active_dev,
         self._aidx_dev) = self._push_fn(
            self._table_dev, self._pos_dev, self._tok_dev,
            self._active_dev, self._aidx_dev, packed)
        if self.push_traces != traces_before:
            record_compile("push_slot", t0,
                           signature=f"row=[{packed.size}]")

    def device_counters(self) -> dict:
        """What the decode step counts on the device, since the runner
        was built, by name: the expert layers' ``moe_*``, a recurrent
        family's ``ssm_rows_live``; {} for a family that counts nothing.
        A device fetch: on demand, never inside a step."""
        if not (self.latent or self.recurrent):
            return {}
        counters = np.asarray(self._counters_dev)
        if self.latent:
            return latent.counters_by_name(counters)
        return recurrent.counters_by_name(self.config, counters)

    def hold_ring(self):
        """Keep the ring as the last step left it, for the next
        :meth:`fetch_ring`, and start its copy to the host.  The ring is
        not donated, so steps dispatched from here on write their rows
        into arrays of their own: what is held is what this step's
        group of ``sync_interval`` rows was."""
        self._ring_held = self._ring_dev
        self._ring_held.copy_to_host_async()

    def fetch_ring(self) -> np.ndarray:
        """The host sync: ONE [sync_interval, slots] int32 transfer, of
        the ring :meth:`hold_ring` kept (blocks until the step that
        wrote it has ended, not until later ones have), else of the
        ring as it stands."""
        ring, self._ring_held = self._ring_held, None
        return np.asarray(self._ring_dev if ring is None else ring)

    def correct_tokens(self, corrections: list[tuple[int, int]]):
        """Push host-side sampling picks back into the device token
        state before the next step."""
        idx = jnp.asarray([s for s, _ in corrections], jnp.int32)
        val = jnp.asarray([t for _, t in corrections], jnp.int32)
        self._tok_dev = self._tok_dev.at[idx].set(val)

    def reinject_step(self):
        """Rebuild the decode-step jit (perf-gate hook: forces a fresh
        trace so retrace detection can be exercised deterministically)."""
        self._step_fn = self._make_step_fn()

    # ---------------------------------------------------------------- info
    def mesh_info(self) -> dict:
        """Per-device memory keyed by mesh position: footprint estimates
        (KV pool shard + weight shard/replica bytes) merged with live
        ``memory_stats()`` where the backend exports them."""
        devices = []
        for i, d in enumerate(self.devices):
            entry = {
                "device": f"{d.platform}:{d.id}", TP_AXIS: i,
                "kv_pool_bytes": self._pool_bytes_per_device,
                "recurrent_state_bytes": self.recurrent_state_bytes,
                "weight_bytes": self._weight_bytes_per_device,
                "lora_bank_bytes": self._lora_bytes_per_device,
            }
            try:
                stats = d.memory_stats() or {}
            except Exception:
                stats = {}
            if "bytes_in_use" in stats:
                entry["bytes_in_use"] = int(stats["bytes_in_use"])
            if "peak_bytes_in_use" in stats:
                entry["peak_bytes_in_use"] = int(
                    stats["peak_bytes_in_use"])
            devices.append(entry)
        return {"tp": self.tp, "axis": TP_AXIS,
                "kv_quant": self.kv_quant, "devices": devices}


def _logits_of(state, h):
    head = state.get("lm_head.weight")
    if head is not None:
        return _mm(h, head)
    return h @ state["llama.embed_tokens.weight"].T
