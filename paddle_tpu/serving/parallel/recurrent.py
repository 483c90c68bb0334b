"""The runner's programs for a family that keeps a recurrent state
beside its K/V pages.

``ModelRunner`` hands over here when the model description says
``family == "granitemoehybrid"`` (``models/granite_hybrid.py``): the same
seam and the same argument positions as the key/value programs, so the
engine, the ring and ``push_slot`` do not know the difference, and two
kinds of state in one program:

  * K/V pages for the attention layers alone, ``[attention layers,
    pages + 1, kvH / pack, page_size, head_dim * pack]``, indexed by the
    block table as ever (``pack`` KV heads share a 128-lane row where the
    head dim is below the lane width);
  * for the Mamba layers a state indexed **by slot**, of a fixed size
    whatever the context: ``ssm [mamba layers, slots, N, H * P]`` and
    ``conv [mamba layers, slots, (d_conv - 1) * conv_dim]``, both in
    the served dtype.  The decode step reads and writes every live
    slot's state whole; the prefill is told its slot and writes that
    slot's WHOLE state, so nothing of the slot's last request survives
    into its next, whatever step is still in flight (the device runs
    the programs in the order they were dispatched).

The two state pools ride every program as ``rstate``, one more donated
argument that is the empty tuple for the other families (no leaves: their
programs are unchanged).  Nothing is sliced out of a pool that feeds a
Pallas call or stacked again.  What a recurrent state cannot do yet is
refused by name when the engine is built (``check_options``).  The decode
step counts on the device the slots its Mamba layers updated
(``COUNTERS``), which ``engine.stats()`` fetches on demand.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...models import granite_hybrid as gh
from ...models.generation import decode_layer, prefill_layer
from ...models.llama_hybrid import _rms
from ...ops.pallas.paged_attention import PagedKV

EMBED = gh.EMBED
WHY_NOT = {
    "mesh": "its programs are single-chip (the state pools are not "
            "sharded)",
    "kv_quant": "its K/V pages are plain",
    "quant": "its weights are dense",
    "lora": "it has no adapter bank",
    "spec_k": "a verify step would have to roll the recurrent state back",
    "enable_prefix_cache": "a shared prefix's pages come without the "
                           "recurrent state at their end",
    "preempt": "a spilled request's pages come back without its "
               "recurrent state",
    "prefill_chunk": "a chunk would have to start from the slot's "
                     "carried state, which the prefill does not take",
}


def is_recurrent(config) -> bool:
    """Whether the model description asks for this file's programs."""
    return getattr(config, "family", "llama") == "granitemoehybrid"


def check_options(**asked):
    """Raise, by name, for an option (name=whether it was asked for)
    that this family does not have."""
    for name, on in asked.items():
        if on:
            raise ValueError(
                f"{name} is not supported for the granitemoehybrid "
                f"family: {WHY_NOT[name]}")


def kv_pool_shape(cfg, num_pages: int, page_size: int) -> tuple:
    pack = gh.kv_pack(cfg)
    return (len(cfg.attention_layers), num_pages + 1,
            cfg.num_key_value_heads // pack, page_size,
            cfg.head_dim * pack)


def state_pools(cfg, slots: int, zeros=jnp.zeros) -> tuple:
    """(ssm, conv), zeroed: what ``rstate`` carries."""
    shapes = gh.state_shapes(cfg, slots)
    return tuple(zeros(*shapes[k]) for k in ("ssm", "conv"))


def state_bytes(cfg, slots: int) -> int:
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize
               for shape, dt in gh.state_shapes(cfg, slots).values())


def counters0():
    return jnp.zeros((len(gh.COUNTERS),), jnp.int32)


def counters_by_name(counters) -> dict:
    """The device's counters as {name: int}: a device fetch."""
    return dict(zip(gh.COUNTERS, (int(v) for v in counters)))


def _embed(cfg, state, ids):
    x = jnp.take(state[EMBED], ids, axis=0)
    return x * jnp.asarray(cfg.embedding_multiplier, x.dtype)


def _head(cfg, state, h):
    """h [rows, hidden] -> float32 logits over the one embedding
    matrix, divided by ``logits_scaling``."""
    h = _rms(h[:, None], state["model.norm.weight"], cfg.rms_norm_eps)[:, 0]
    logits = jax.lax.dot_general(
        h, state[EMBED], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits / jnp.float32(cfg.logits_scaling)


def build_step(runner, count_trace):
    cfg = runner.config
    emit_logits = runner.emit_logits
    rope_len = runner._rope_len

    def decode_step(state, kpool, vpool, kscale, vscale, table, pos, tok,
                    active, ring, ridx, cos, sin, lora, aidx, counters,
                    rstate):
        count_trace()
        cache = PagedKV(kpool, vpool)
        ssm, conv = rstate
        with jax.named_scope("embed"):
            # an overrun row stays inside its table's row
            posc = jnp.minimum(pos, rope_len - 1)
            h = _embed(cfg, state, tok)
        for i, kind in enumerate(cfg.layer_types):
            w = gh.layer_weights(state, cfg, i)
            if kind == "attention":
                h, cache = decode_layer(w, h, cache, table, None, None,
                                        posc, cfg, li=cfg.ordinal(i))
            else:
                h, ssm, conv = gh.mamba_decode_layer(cfg, w, i, h, ssm,
                                                     conv, active)
                # (a sum of int32 is int64 under x64: the counter's dtype
                # must come back as it went in, or the step traces twice)
                counters = counters + jnp.sum(active).astype(jnp.int32)
        with jax.named_scope("head"):
            logits = _head(cfg, state, h)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            pos2 = pos + active
            tok2 = jnp.where(active.astype(bool), nxt, tok)
            ring2 = ring.at[ridx].set(nxt)
            ridx2 = (ridx + 1) % ring.shape[0]
        return (cache.k, cache.v, kscale, vscale, pos2, tok2, ring2, ridx2,
                logits if emit_logits else jnp.zeros((), jnp.float32),
                counters, (ssm, conv))

    return decode_step


def build_prefill(runner, bucket: int, count_trace):
    cfg = runner.config
    pack = gh.kv_pack(cfg)

    def prefill(state, ids, length, table_row, kpool, vpool, kscale,
                vscale, cos, sin, lora, aidx, rstate, slot):
        count_trace()
        cache = PagedKV(kpool, vpool)
        ssm, conv = rstate
        with jax.named_scope("embed"):
            x = _embed(cfg, state, ids)
            pmask = jnp.arange(bucket)[None, :] < length
        for i, kind in enumerate(cfg.layer_types):
            w = gh.layer_weights(state, cfg, i)
            n = cfg.ordinal(i)
            if kind == "attention":
                x, k, v = prefill_layer(w, x, None, None, pmask, cfg, li=n)
                with jax.named_scope("kv.write"):
                    rows = (1, bucket, -1, cfg.head_dim * pack)
                    cache = cache.write_pages(n, table_row, k.reshape(rows),
                                              v.reshape(rows))
                continue
            x, s_end, tail = gh.mamba_prefill_layer(cfg, w, i, x, length[0])
            with jax.named_scope("ssm.write"):
                # the slot's WHOLE state: nothing of its last request,
                # or of a step still in flight, is left in it
                ssm = ssm.at[n, slot].set(s_end.astype(ssm.dtype))
                conv = conv.at[n, slot].set(tail.astype(conv.dtype))
        with jax.named_scope("head"):
            last = jnp.take_along_axis(
                x, (length - 1)[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]
            logits = _head(cfg, state, last)
        return cache.k, cache.v, kscale, vscale, logits, (ssm, conv)

    return prefill
