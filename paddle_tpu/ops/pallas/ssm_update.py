"""The decode step of a state-space (Mamba-2) layer: one token a slot
updates that slot's recurrent state where it lies and reads it out.

Reference: Dao & Gu, "Transformers are SSMs" (arXiv:2405.21060), the
recurrent form of section 3: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
B_t``, ``y_t = S_t C_t`` for each head.

The state of every state-space layer and every slot is ONE pool
``[layers, slots, N, H * P]`` in the dtype the model is served in (as
the published cache allocates it; float32 for a float32 model): state
size ``N`` on the sublane axis, the heads' channels side by side on the
lane axis.  The arithmetic is float32 whatever the pool holds: a state
is widened as it is read and rounded once as it is written.  In that
layout the whole update is elementwise, broadcasts along one axis only —
the decay ``exp(dt A)`` and the input ``dt x`` are row vectors over the
``H * P`` lanes, ``B`` and ``C`` are column vectors over ``N``, one pair
for each of the ``G`` groups of heads, whose ``H * P / G`` lanes lie
side by side — and the read-out is a sum over sublanes, so nothing goes
through the lane-reduction unit or the MXU.  One slot of
one layer is ``N * H * P`` values (1 MiB in bfloat16 at 64 heads of 64
with state 128), read once and written once a step: the kernel is a
stream over HBM, and at the serving cell's shape it is the largest
single share of the step's bytes.

``ssm_state_update`` passes the pool whole, aliased to its output
(``input_output_aliases``), with the layer as a prefetched scalar: no
layer is sliced out or put back (PR 29's lesson for the K/V pools).  A
parked slot moves no bytes: its grid steps name the block the step
before them named, which Pallas neither fetches again nor writes back,
and their body is skipped (``chip_smoke.py --ssm-update`` holds that
against the XLA form on the chip, by value and by the clock).
``ssm_state_update_xla`` is the same arithmetic as XLA ops, the CPU's
path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ssm_state_update", "ssm_state_update_xla",
           "select_ssm_state_update", "LANE_BLOCK"]

_INTERPRET = False
# lanes of one grid step's block: [N, LANE_BLOCK] in and out, each
# double-buffered, beside the float32 values the body forms of them
# (1 MiB apiece at N = 128).  A block wider than a group's lanes holds
# several groups, each sliced out by the body at a fixed place.
LANE_BLOCK = 2048


def select_ssm_state_update():
    """The kernel on a TPU (or under interpret mode), the XLA form on
    the CPU: the rule of ``select_paged_attention``."""
    if jax.default_backend() not in ("cpu",) or _INTERPRET:
        return ssm_state_update
    return ssm_state_update_xla


def ssm_state_update_xla(pool, layer, decay, dtx, b, c, active):
    """See :func:`ssm_state_update`; one layer's states are read, updated
    and put back with XLA ops."""
    f32 = jnp.float32
    live = active.astype(bool)
    s = pool[layer]
    slots, g, n = b.shape
    by_group = (slots, 1, g, s.shape[-1] // g)
    new = (s.astype(f32).reshape(slots, n, *by_group[2:])
           * decay.astype(f32).reshape(by_group)
           + b.astype(f32).transpose(0, 2, 1)[..., None]
           * dtx.astype(f32).reshape(by_group))
    y = jnp.where(live[:, None], jnp.einsum(
        "sngl,sgn->sgl", new, c.astype(f32),
        precision=jax.lax.Precision.HIGHEST).reshape(slots, -1), 0.0)
    new = new.reshape(s.shape)
    new = jnp.where(live[:, None, None], new.astype(pool.dtype), s)
    return pool.at[layer].set(new), y


def _sticky_blocks(active, blocks: int):
    """For each slot, the (slot, lane block) its grid steps name when it
    is parked: the last block of the nearest live slot before it, else
    the first block of the first live slot (which the next live step
    names too, so nothing is fetched for it twice)."""
    slots = active.shape[0]
    at = jnp.arange(slots, dtype=jnp.int32)
    live = active != 0
    prev = jax.lax.cummax(jnp.where(live, at, -1))
    first = jnp.argmax(live).astype(jnp.int32)
    return (jnp.where(prev >= 0, prev, first).astype(jnp.int32),
            jnp.where(prev >= 0, blocks - 1, 0).astype(jnp.int32))


def _update_kernel(act_ref, src_ref, blk_ref, layer_ref, s_ref, decay_ref,
                   dtx_ref, b_ref, c_ref, o_ref, y_ref, *, group_lanes):
    from jax.experimental import pallas as pl

    slot = pl.program_id(0)
    live = act_ref[slot] != 0

    @pl.when(live)
    def _():
        # the groups this block holds, each at its own lanes (one group,
        # or a part of one: the whole block)
        for g in range(b_ref.shape[0]):
            at = (slice(None) if b_ref.shape[0] == 1 else
                  slice(g * group_lanes, (g + 1) * group_lanes))
            new = (s_ref[:, at].astype(jnp.float32) * decay_ref[:, at]
                   + b_ref[g] * dtx_ref[:, at])
            o_ref[:, at] = new.astype(o_ref.dtype)
            y_ref[:, at] = jnp.sum(new * c_ref[g], axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # no slot is live at all: every grid step names slot 0's first block,
    # and the output buffer that is written back at the end must hold it
    @pl.when(act_ref[src_ref[slot]] == 0)
    def _():
        o_ref[...] = s_ref[...]


def ssm_state_update(pool, layer, decay, dtx, b, c, active):
    """One decode step of one state-space layer, for every slot.

    pool [L, slots, N, HP], every layer's states in float32 or bfloat16
    (donate it: it is aliased to the first output; the arithmetic is
    float32 either way); ``layer`` a Python int or a traced
    scalar; decay, dtx [slots, HP] (``exp(dt A)`` and ``dt x`` spread
    over each head's channels); b, c [slots, G, N], one column for each
    group of ``HP / G`` lanes; active [slots].
    Returns (pool, y [slots, HP] float32) with, for every live slot,
    ``pool[layer, s] = decay * S + b (x) dtx`` and ``y = c . S_new``
    (of the new state before it is rounded to the pool's dtype); a
    parked slot's state is left as it is and its ``y`` is zero."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, slots, n, hp = pool.shape
    groups = b.shape[1]
    lanes = min(LANE_BLOCK, hp)
    group_lanes = hp // groups
    if hp % lanes or hp % groups or (
            lanes % group_lanes and group_lanes % lanes):
        raise ValueError(
            f"the state's {hp} lanes in {groups} groups do not divide "
            f"into blocks of {lanes}")
    blocks = hp // lanes
    held = max(1, lanes // group_lanes)     # groups a block holds
    act = active.astype(jnp.int32)
    src, blk = _sticky_blocks(act, blocks)
    f32 = jnp.float32

    def state_map(s, j, act, src, blk, ly):
        on = act[s] != 0
        return (ly[0], jnp.where(on, s, src[s]), 0,
                jnp.where(on, j, blk[s]))

    def row_map(s, j, act, src, blk, ly):
        return s, 0, j

    def col_map(s, j, act, src, blk, ly):
        return s, j * lanes // (group_lanes * held), 0, 0

    state = pl.BlockSpec((None, None, n, lanes), state_map)
    row = pl.BlockSpec((None, 1, lanes), row_map)
    col = pl.BlockSpec((None, held, n, 1), col_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(slots, blocks),
        in_specs=[state, row, row, col, col], out_specs=[state, row])
    with jax.enable_x64(False):
        pool, y = pl.pallas_call(
            functools.partial(_update_kernel, group_lanes=group_lanes),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                       jax.ShapeDtypeStruct((slots, 1, hp), f32)],
            input_output_aliases={4: 0},
            interpret=_INTERPRET,
            name="ssm_state_update",
        )(act, src, blk, jnp.asarray(layer, jnp.int32).reshape(1), pool,
          decay.astype(f32)[:, None, :], dtx.astype(f32)[:, None, :],
          b.astype(f32)[..., None], c.astype(f32)[..., None])
    return pool, y[:, 0]
