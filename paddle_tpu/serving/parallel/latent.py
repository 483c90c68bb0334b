"""The runner's programs for a family whose cache is latent rows.

``ModelRunner`` hands over here when the model description says
``family == "deepseek_v3"``: the same seam (decode step, per-bucket
prefill, per-bucket cached prefill) and the same argument positions as
the key/value programs, so the engine, the ring and ``push_slot`` do not
know the difference.  What differs is the cache: ONE pool
``[L, pages + 1, page_size, width]`` of latent rows
(``models/deepseek_v3.py``); the value pool, the scale pools, the
adapter bank and its index are empty tuples (no leaves).  Every program
scatters its new rows into the donated pool and passes the pool on
whole: nothing is sliced a layer or stacked again.

One chip, no options: what the family lacks is refused when the runner
is built (``check_options``).  The decode step also keeps the expert
layers' counters on the device (``MOE_COUNTERS``, summed over steps and
layers), which ``engine.stats()`` fetches on demand.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...models import deepseek_v3 as ds
from ...models.llama_hybrid import _rms
from ...ops.pallas.mla_paged_attention import row_width

EMBED = "model.embed_tokens.weight"


def is_latent(config) -> bool:
    """Whether the model description asks for this file's programs."""
    return getattr(config, "family", "llama") == "deepseek_v3"


def check_options(**asked):
    """Raise, by name, for an option (name=whether it was asked for)
    that this family does not have."""
    for name, on in asked.items():
        if on:
            raise ValueError(
                f"{name} is not supported for the deepseek_v3 family: "
                "its programs are single-chip and dense, with no "
                "adapters, no weight quantization and no verify step")


def pool_shape(cfg, num_pages: int, page_size: int) -> tuple:
    return (cfg.num_hidden_layers, num_pages + 1, page_size,
            row_width(cfg.cache_row))


def counters0():
    return jnp.zeros((len(ds.MOE_COUNTERS),), jnp.int32)


def counters_by_name(counters) -> dict:
    """The device's counters as {name: int}: a device fetch."""
    return dict(zip(ds.MOE_COUNTERS, (int(v) for v in counters)))


def _head(cfg, state, h):
    h = _rms(h, state["model.norm.weight"], cfg.rms_norm_eps)
    return (h @ state["lm_head.weight"]).astype(jnp.float32)


def build_step(runner, count_trace):
    cfg = runner.config
    rope_len = runner._rope_len
    emit_logits = runner.emit_logits

    def decode_step(state, pool, vpool, kscale, vscale, table, pos, tok,
                    active, ring, ridx, cos, sin, lora, aidx, counters,
                    rstate):
        count_trace()
        with jax.named_scope("embed"):
            posc = jnp.minimum(pos, rope_len - 1)
            h = jnp.take(state[EMBED], tok, axis=0)
            cos1 = jnp.take(cos, posc, axis=0)
            sin1 = jnp.take(sin, posc, axis=0)
        for i in range(cfg.num_hidden_layers):
            h, pool, counts = ds.decode_layer(
                cfg, ds.layer_weights(state, cfg, i), i, h, pool, table,
                cos1, sin1, posc, active)
            if counts is not None:
                counters = counters + counts
        with jax.named_scope("head"):
            logits = _head(cfg, state, h[:, None])[:, 0]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            pos2 = pos + active
            tok2 = jnp.where(active.astype(bool), nxt, tok)
            ring2 = ring.at[ridx].set(nxt)
            ridx2 = (ridx + 1) % ring.shape[0]
        return (pool, vpool, kscale, vscale, pos2, tok2, ring2, ridx2,
                logits if emit_logits else jnp.zeros((), jnp.float32),
                counters, rstate)

    return decode_step


def build_prefill(runner, bucket: int, count_trace):
    cfg = runner.config
    ps = runner.page_size
    n_pages = bucket // ps
    row = cfg.cache_row

    def prefill(state, ids, length, table_row, pool, vpool, kscale,
                vscale, cos, sin, lora, aidx, rstate, slot):
        count_trace()
        with jax.named_scope("embed"):
            x = jnp.take(state[EMBED], ids, axis=0)
            valid = jnp.arange(bucket) < length[0]
        for i in range(cfg.num_hidden_layers):
            x, rows, _ = ds.prefill_layer(
                cfg, ds.layer_weights(state, cfg, i), i, x, cos[:bucket],
                sin[:bucket], valid)
            with jax.named_scope("kv.write"):
                pool = pool.at[i, table_row[:n_pages], :, :row].set(
                    rows.reshape(n_pages, ps, row).astype(pool.dtype))
        with jax.named_scope("head"):
            last = jnp.take_along_axis(
                x, (length - 1)[:, None, None].astype(jnp.int32),
                axis=1)
            logits = _head(cfg, state, last)[:, 0]
        return pool, vpool, kscale, vscale, logits, rstate

    return prefill


def build_prefill_cached(runner, bucket: int, count_trace):
    cfg = runner.config
    ps, W = runner.page_size, runner.table_width
    dump, rope_len = runner.dump_page, runner._rope_len
    row = cfg.cache_row

    def prefill_cached(state, ids, length, cached_len, trow, pool, vpool,
                       kscale, vscale, cos, sin, lora, aidx):
        count_trace()
        with jax.named_scope("embed"):
            x = jnp.take(state[EMBED], ids, axis=0)
            j = jnp.arange(bucket)
            absp = cached_len + j
            posc = jnp.minimum(absp, rope_len - 1)
            cos_s = jnp.take(cos, posc, axis=0)
            sin_s = jnp.take(sin, posc, axis=0)
            valid = j < length[0]
            pre_ok = jnp.broadcast_to(
                jnp.arange(W * ps)[None, :] < cached_len, (bucket, W * ps))
            suf_ok = (j[None, :] <= j[:, None]) & valid[None, :]
            mask = jnp.concatenate([pre_ok, suf_ok], axis=1)[None, None]
            # padding lands on the dump page
            page_w = jnp.where(
                valid, trow[jnp.minimum(absp // ps, W - 1)], dump)
            off = absp % ps
        for i in range(cfg.num_hidden_layers):
            with jax.named_scope("attn.prefill"):
                pre = pool[i][trow].reshape(W * ps, -1)
            x, rows, _ = ds.prefill_layer(
                cfg, ds.layer_weights(state, cfg, i), i, x, cos_s, sin_s,
                valid, pre_rows=pre, mask=mask)
            with jax.named_scope("kv.write"):
                pool = pool.at[i, page_w, off, :row].set(
                    rows.astype(pool.dtype))
        with jax.named_scope("head"):
            last = jnp.take_along_axis(
                x, (length - 1)[:, None, None].astype(jnp.int32),
                axis=1)
            logits = _head(cfg, state, last)[:, 0]
        return pool, vpool, kscale, vscale, logits

    return prefill_cached
