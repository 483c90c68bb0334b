"""The runner's programs for a family that keeps a recurrent state
beside its K/V pages.

``ModelRunner`` hands over here when the model description's ``family``
is one of ``FAMILIES``: ``granitemoehybrid`` (``models/
granite_hybrid.py``: every block a mixer and then a dense MLP) or
``nemotron_h`` (``models/nemotron_h.py``: every block ONE part, a Mamba
mixer, an attention or a routed-expert layer).  Both run through the
same two builders, which take the blocks from the description
(``config.blocks``: for each block the parts it runs, each with its own
norm and residual add) and the parts from where every family takes
them: ``granite_hybrid.mamba_*_block``, ``generation.*_attention`` and
``mlp_block``, ``nemotron_h.expert_block`` over ``deepseek_v3``'s router
and grouped experts.  The same seam and the same argument positions as
the key/value programs, so the engine, the ring and ``push_slot`` do not
know the difference, and two kinds of state in one program:

  * K/V pages for the attention blocks alone, ``[attention blocks,
    pages + 1, kvH / pack, page_size, head_dim * pack]``, indexed by the
    block table as ever (``pack`` KV heads share a 128-lane row where the
    head dim is below the lane width);
  * for the Mamba blocks a state indexed **by slot**, of a fixed size
    whatever the context: ``ssm [mamba blocks, slots, N, H * P]`` and
    ``conv [mamba blocks, slots, (d_conv - 1) * conv_dim]``, both in
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
step counts on the device what the family's description names
(``COUNTERS``: the slots its Mamba blocks updated, and for a family with
expert blocks the ``moe_*`` counters of ``deepseek_v3``), which
``engine.stats()`` fetches on demand.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...models import granite_hybrid as gh
from ...models import nemotron_h as nh
from ...models.deepseek_v3 import DECODE_TILE, MOE_COUNTERS, PREFILL_TILE
from ...models.generation import (decode_attention, mlp_block,
                                  prefill_attention)
from ...models.llama_hybrid import _rms
from ...ops.pallas.paged_attention import PagedKV

# family -> the module that describes it: EMBED, COUNTERS, layer_weights
FAMILIES = {"granitemoehybrid": gh, "nemotron_h": nh}
WHY_NOT = {
    "mesh": "its programs are single-chip (the state pools are not "
            "sharded)",
    "kv_quant": "its K/V pages are plain",
    "quant": "its weights are dense (its projections and, where it has "
             "them, its experts)",
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
    return getattr(config, "family", "llama") in FAMILIES


def check_options(config, **asked):
    """Raise, by name, for an option (name=whether it was asked for)
    that ``config``'s family does not have."""
    for name, on in asked.items():
        if on:
            raise ValueError(
                f"{name} is not supported for the {config.family} "
                f"family: {WHY_NOT[name]}")


def embed_name(config) -> str:
    """The state's embedding matrix (its dtype is the served one)."""
    return FAMILIES[config.family].EMBED


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


def counters0(cfg):
    return jnp.zeros((len(FAMILIES[cfg.family].COUNTERS),), jnp.int32)


def counters_by_name(cfg, counters) -> dict:
    """The device's counters as {name: int}: a device fetch."""
    return dict(zip(FAMILIES[cfg.family].COUNTERS,
                    (int(v) for v in counters)))


def _count(cfg, counters, name: str, values):
    """``counters`` with ``values`` [k] added from ``name``'s place in
    the family's ``COUNTERS`` on.  (A sum of int32 is int64 under x64:
    the counters' dtype must come back as it went in, or the step
    traces twice.)"""
    names = FAMILIES[cfg.family].COUNTERS
    at = names.index(name)
    return counters + jnp.pad(values.astype(jnp.int32),
                              (at, len(names) - at - values.shape[0]))


def _embed(cfg, state, ids):
    x = jnp.take(state[embed_name(cfg)], ids, axis=0)
    mult = getattr(cfg, "embedding_multiplier", None)
    return x if mult is None else x * jnp.asarray(mult, x.dtype)


def _head(cfg, state, h):
    """h [rows, hidden] -> float32 logits: over the one embedding matrix
    divided by ``logits_scaling`` (Granite's tied head), or over
    ``lm_head`` where the family has one."""
    fam = FAMILIES[cfg.family]
    if hasattr(fam, "HEAD"):
        h = _rms(h[:, None], state[fam.NORM], cfg.rms_norm_eps)[:, 0]
        return jnp.dot(h, state[fam.HEAD],
                       preferred_element_type=jnp.float32)
    h = _rms(h[:, None], state["model.norm.weight"], cfg.rms_norm_eps)[:, 0]
    logits = jax.lax.dot_general(
        h, state[fam.EMBED], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits / jnp.float32(cfg.logits_scaling)


def build_step(runner, count_trace):
    cfg = runner.config
    weights_of = FAMILIES[cfg.family].layer_weights
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
        for i, parts in enumerate(cfg.blocks):
            w = weights_of(state, cfg, i)
            for part in parts:
                if part == "attention":
                    h, cache = decode_attention(
                        w, h, cache, table, None, None, posc, cfg,
                        li=cfg.ordinal(i))
                elif part == "mamba":
                    h, ssm, conv = gh.mamba_decode_block(
                        cfg, w, cfg.ordinal(i), h, ssm, conv, active)
                    counters = _count(cfg, counters, "ssm_rows_live",
                                      jnp.sum(active).reshape(1))
                elif part == "moe":
                    h, counts = nh.expert_block(
                        cfg, w, h, active.astype(bool), DECODE_TILE)
                    counters = _count(cfg, counters, MOE_COUNTERS[0],
                                      counts)
                else:
                    h = mlp_block(w, h, cfg, li=i)
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
    weights_of = FAMILIES[cfg.family].layer_weights
    pack = gh.kv_pack(cfg)

    def prefill(state, ids, length, table_row, kpool, vpool, kscale,
                vscale, cos, sin, lora, aidx, rstate, slot):
        count_trace()
        cache = PagedKV(kpool, vpool)
        ssm, conv = rstate
        with jax.named_scope("embed"):
            x = _embed(cfg, state, ids)
            pmask = jnp.arange(bucket)[None, :] < length
        for i, parts in enumerate(cfg.blocks):
            w = weights_of(state, cfg, i)
            n = cfg.ordinal(i)
            for part in parts:
                if part == "attention":
                    x, k, v = prefill_attention(w, x, None, None, pmask,
                                                cfg, li=n)
                    kv = (k, v)
                elif part == "mamba":
                    x, s_end, tail = gh.mamba_prefill_block(cfg, w, x,
                                                            length[0])
                elif part == "moe":
                    # (the prefill's expert counts are not kept: the
                    # counters are the decode step's)
                    x = nh.expert_block(cfg, w, x[0], pmask[0],
                                        PREFILL_TILE)[0][None]
                else:
                    x = mlp_block(w, x, cfg, li=i)
            # the block's state is written after its last part
            if "attention" in parts:
                with jax.named_scope("kv.write"):
                    rows = (1, bucket, -1, cfg.head_dim * pack)
                    cache = cache.write_pages(
                        n, table_row, *(t.reshape(rows) for t in kv))
            elif "mamba" in parts:
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
