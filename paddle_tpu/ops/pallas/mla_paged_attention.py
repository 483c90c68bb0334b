"""Absorbed-form latent (MLA) attention over a paged latent cache.

Reference analog: the decode half of multi-head latent attention
(DeepSeek-V2, arXiv:2405.04434, section 2.1): the cache holds ONE row a
token a layer, ``[c | k_r]`` = the compressed key/value latent (``rank``
values) and the single rotary key shared by every head.  With the
up-projections absorbed into the query and the output
(``q_lat = q_nope . W_UK^T``, ``o_h = o_lat . W_UV``), every query head
attends the same rows: scores over ``rank + rope`` values, values over
the first ``rank``.

TPU formulation: :mod:`paged_attention`'s block-of-pages design with a
block sized for latent rows (``pages_per_block`` here, ``BLOCK_TOKENS``:
one grid step a slot at the serving cell's 4,096 positions).  Grid
``(slots, ceil(max_pages / blk))``; the pool of every layer stays in HBM
and the kernel copies the pages the scalar-prefetched table names, one
DMA a page (``page_size * width`` contiguous elements) into rows of one
of two VMEM buffers ``[blk * page_size, width]``; the next live block's
copies all start before the first wait for this one's, and since their
semaphore counts bytes a block's copies are waited for a power of two of
pages at a time; nothing is copied or computed past ``lens[b]``.  The
arithmetic walks the block's copied rows in rounds of the online softmax
(float32): whole chunks of ``CHUNK_TOKENS`` rows, then one masked round
of the smallest of ``tail_sizes`` that covers the rest, so a short
context in front of a long block multiplies what it copied and little
more.  A round's rows are read once for all query heads: ``[nh, rank] x
[rows, rank]`` and ``[nh, rope] x [rows, rope]`` give the scores, ``[nh,
rows] x [rows, rank]`` the output.  The pool is passed whole with the
layer as a prefetched scalar, so a step that has scattered its new rows
into the donated pool hands the kernel that very buffer: nothing is
sliced or stacked around the call.

Layout: pool [L, num_pages, page_size, width], table [B, max_pages]
int32 (padding = the dump page), lens [B] visible tokens.  ``width`` is
``row_width(rank + rope)``: the compiler tiles the minor dimension by
128 lanes in HBM whatever is declared (a 576-wide array is laid out 640
wide) and refuses to copy a slice whose declared width is not a whole
number of tiles, so the row is declared at the width it occupies; the
lanes past ``rank + rope`` are never read.  Inference-only (no VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import NUM_LANES

__all__ = ["mla_paged_attention", "mla_paged_attention_xla",
           "select_mla_paged_attention", "pages_per_block", "row_width",
           "write_rows", "write_rows_xla", "select_write_rows"]

_INTERPRET = False

# Tokens of latent rows one grid step of the kernel covers (its pages:
# pages_per_block).  The K/V kernel's rule goes by a page's bytes (two
# pools); a latent page is 20 KB.  Chosen on the v5e at the GigaChat cell's
# shape (64 slots, 64 heads over 512 + 64, page 16, 256 table columns, 5
# layers in the pool; ``chip_smoke.py --mla-decode``, PR 33): 256 / 512 /
# 1024 / 2048 / 4096 took 0.294 / 0.220 / 0.176 / 0.164 / 0.139 ms a call
# at contexts of 628, 0.431 / 0.318 / 0.265 / 0.258 / 0.220 at 1,170, 1.120
# / 0.792 / 0.645 / 0.621 / 0.647 at 4,032 and 0.161 / 0.117 / 0.104 /
# 0.094 / 0.069 at 1 token.  Two buffers of 4,096 rows of 640 bfloat16
# lanes are 10.5 MB of VMEM, inside the 16 MiB a call may use unasked.
BLOCK_TOKENS = 4096

# Rows one round of the online softmax takes at most.  A round costs
# about 0.46 us whatever it holds and 0.2 us more for every 256 rows (256
# / 512 / 1,024 rows a round: 0.62 / 0.80 / 1.28 us, same runs), so a
# block's whole chunks go by at this size and what is left takes one
# round of ``tail_sizes``.
CHUNK_TOKENS = 1024

# Page copies one turn of the loop that starts a block's copies issues
# (4, 8 and 16 measured alike; a turn a page took a fifth longer).
START_UNROLL = 8


def tail_sizes(chunk: int) -> list[int]:
    """Rows the one masked round after a block's whole chunks may take:
    one to four quarters of a chunk."""
    return sorted({chunk * k // 4 for k in (1, 2, 3, 4)} - {0})


def pages_per_block(page_size: int, max_pages: int) -> int:
    """Pages one grid step of :func:`mla_paged_attention` covers: a
    block of about ``BLOCK_TOKENS`` tokens, at least one page and at
    most the table's width.  A latent engine's ``paged_blocks_*``
    counters read the rule here rather than repeat it."""
    return max(1, min(BLOCK_TOKENS // int(page_size), int(max_pages)))


def row_width(values: int) -> int:
    """Lanes a cached row of ``values`` elements is declared at."""
    return -(-int(values) // NUM_LANES) * NUM_LANES


def select_mla_paged_attention():
    """The kernel on a TPU (or under interpret mode), the dense-gather
    XLA form on the CPU: the rule of ``select_paged_attention``."""
    if jax.default_backend() not in ("cpu",) or _INTERPRET:
        return mla_paged_attention
    return mla_paged_attention_xla


def _mla_kernel(table_ref, lens_ref, layer_ref, ql_ref, qr_ref, pool_hbm,
                o_ref, buf, sems, side_ref, acc_ref, m_ref, l_ref, *,
                page_size, blk, chunk, max_pages, rank, rope, sm_scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, j = pl.program_id(0), pl.program_id(1)
    tokens = blk * page_size
    layer = layer_ref[0]

    def visible(b_):
        return jnp.minimum(lens_ref[b_], max_pages * page_size)

    def live_pages(b_, j_):
        n_pages = (visible(b_) + page_size - 1) // page_size
        return jnp.clip(n_pages - j_ * blk, 0, blk)

    def start_block(b_, j_, side):
        # the table rides flat: a row's entry is one add away
        first = b_ * max_pages + j_ * blk

        def start(p):
            rows = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)
            pltpu.make_async_copy(pool_hbm.at[layer, table_ref[first + p]],
                                  buf.at[side, rows, :], sems.at[side]).start()

        def group(g, _):
            for i in range(START_UNROLL):
                start(g * START_UNROLL + i)
        n = live_pages(b_, j_)
        jax.lax.fori_loop(0, n // START_UNROLL, group, None)
        jax.lax.fori_loop(n // START_UNROLL * START_UNROLL, n,
                          lambda p, _: start(p), None)

    def wait_block(b_, j_, side):
        # The semaphore counts bytes, so one wait can stand for the
        # copies of k pages: a wait for each power of two in the live
        # count, not one a page.
        n = live_pages(b_, j_)
        k = 1 << (blk.bit_length() - 1)
        while k:
            rows = buf.at[side, pl.ds(0, k * page_size), :]

            @pl.when((n & k) != 0)
            def _wait(rows=rows):
                pltpu.make_async_copy(rows, rows, sems.at[side]).wait()
            k //= 2

    n_tok = visible(b)

    @pl.when((b == 0) & (j == 0))
    def _first():
        side_ref[0] = 0
        start_block(b, j, 0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the chain of copies runs over every slot's block 0 and over each
    # further block that starts inside the context (paged_attention)
    @pl.when((j == 0) | (j * tokens < n_tok))
    def _block():
        side = side_ref[0]
        more = (j + 1) * tokens < n_tok
        nb = jnp.where(more, b, b + 1)
        nj = jnp.where(more, j + 1, 0)

        @pl.when(nb < pl.num_programs(0))
        def _prefetch():
            start_block(nb, nj, 1 - side)

        side_ref[0] = 1 - side
        wait_block(b, j, side)

        # the block's rows that the slot sees: 0 where n_tok is 0
        seen = jnp.clip(n_tok - j * tokens, 0, tokens)

        def softmax_round(at, size, masked):
            # one round of the online softmax over rows [at, at + size)
            c = buf[side, pl.ds(at, size), :rank]               # [size, rank]
            kr = buf[side, pl.ds(at, size), rank:rank + rope]
            dims = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(ql_ref[...], c, dims,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[...], kr, dims,
                                       preferred_element_type=jnp.float32)
                 ) * jnp.float32(sm_scale)          # [nh, size]
            if masked:
                r_s = at + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(r_s < seen, s, -jnp.inf)
                # past the context the buffer holds whatever was there:
                # 0 * NaN in p.c would poison the row, so c is masked too
                r_c = at + jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
                c = jnp.where(r_c < seen, c, jnp.zeros_like(c))
            m_prev = m_ref[:, :1]
            l_prev = l_ref[:, :1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(c.dtype), c, preferred_element_type=jnp.float32)
            m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

        # A round costs the same half microsecond whatever it holds, so
        # whole chunks go by unmasked and what is left takes ONE masked
        # round, of the smallest size that covers it.
        whole = seen // chunk
        jax.lax.fori_loop(
            0, whole, lambda i, _: softmax_round(
                pl.multiple_of(i * chunk, chunk), chunk, False), None)
        left, covered = seen - whole * chunk, 0
        for size in tail_sizes(chunk):
            @pl.when((left > covered) & (left <= size))
            def _tail(size=size):
                softmax_round(pl.multiple_of(whole * chunk, chunk), size,
                              True)
            covered = size

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def mla_paged_attention(q_lat, q_rope, pool, layer, table, lens, *,
                        sm_scale):
    """q_lat [B, nh, rank], q_rope [B, nh, rope]; pool
    [L, P, page_size, width >= rank + rope] (see ``row_width``);
    ``layer`` the pool's layer to read
    (an int or an int32 scalar); table [B, max_pages]; lens [B].
    Returns o_lat [B, nh, rank] in q_lat's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, nh, rank = q_lat.shape
    rope = q_rope.shape[-1]
    page_size, width = pool.shape[2], pool.shape[3]
    if width < rank + rope:
        raise ValueError(f"pool rows are {width} wide, the queries "
                         f"{rank} + {rope}")
    max_pages = table.shape[1]
    blk = pages_per_block(page_size, max_pages)
    # the buffers hold whole chunks, so the last chunk of a block that is
    # no multiple of one reads rows nothing copies: masked like any other
    chunk = min(CHUNK_TOKENS, blk * page_size)
    rows = -(-blk * page_size // chunk) * chunk

    def q_spec(d):
        return pl.BlockSpec((None, nh, d),
                            lambda b_, j, tbl, ln, ly: (b_, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, -(-max_pages // blk)),
        in_specs=[q_spec(rank), q_spec(rope),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((2, rows, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),          # one a buffer
            pltpu.SMEM((1,), jnp.int32),            # buffer being read
            pltpu.VMEM((nh, rank), jnp.float32),
            pltpu.VMEM((nh, NUM_LANES), jnp.float32),
            pltpu.VMEM((nh, NUM_LANES), jnp.float32),
        ],
    )
    with jax.enable_x64(False):   # see flash_attention._flash_fwd
        return pl.pallas_call(
            functools.partial(_mla_kernel, page_size=page_size, blk=blk,
                              chunk=chunk, max_pages=max_pages, rank=rank,
                              rope=rope,
                              sm_scale=float(sm_scale)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, nh, rank), q_lat.dtype),
            interpret=_INTERPRET,
            name="mla_paged_attention",
        )(table.astype(jnp.int32).reshape(-1), lens.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1), q_lat,
          q_rope.astype(q_lat.dtype), pool)


def mla_paged_attention_xla(q_lat, q_rope, pool, layer, table, lens, *,
                            sm_scale):
    """Dense-gather form of :func:`mla_paged_attention` (the same
    numbers): materializes every slot's rows; off-TPU and in the parity
    tests."""
    rank = q_lat.shape[-1]
    ps = pool.shape[2]
    rows = pool[layer][table]                       # [B, W, ps, width]
    rows = rows.reshape(table.shape[0], table.shape[1] * ps, -1)
    c, kr = rows[..., :rank], rows[..., rank:rank + q_rope.shape[-1]]
    s = (jnp.einsum("bhc,btc->bht", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,btr->bht", q_rope.astype(q_lat.dtype), kr,
                      preferred_element_type=jnp.float32)
         ) * jnp.float32(sm_scale)
    valid = jnp.arange(rows.shape[1])[None, None, :] < lens[:, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0).astype(q_lat.dtype)
    # rows no table entry owns may hold anything: keep them out of p.c
    c = jnp.where(valid[:, 0, :, None], c, jnp.zeros_like(c))
    return jnp.einsum("bht,btc->bhc", p, c,
                      preferred_element_type=jnp.float32
                      ).astype(q_lat.dtype)


# ------------------------------------------------------ the step's new rows
def select_write_rows():
    """The page-rewriting kernel on a TPU (or under interpret mode), the
    XLA scatter on the CPU."""
    if jax.default_backend() not in ("cpu",) or _INTERPRET:
        return write_rows
    return write_rows_xla


def write_rows_xla(pool, layer, page, off, rows):
    """pool[layer, page[b], off[b], :rows.shape[1]] = rows[b], as one
    XLA scatter.  On the v5e that scatter is a loop of 64 sub-word
    updates, 0.55 ms a layer (PERF.md, PR 28): the kernel below is the
    TPU's path."""
    return pool.at[layer, page, off, :rows.shape[1]].set(
        rows.astype(pool.dtype))


def _write_kernel(page_ref, off_ref, layer_ref, row_ref, page_in, page_out):
    from jax.experimental import pallas as pl

    at = jax.lax.broadcasted_iota(jnp.int32, page_in.shape, 0)
    page_out[...] = jnp.where(at == off_ref[pl.program_id(0)],
                              row_ref[...], page_in[...])


def write_rows(pool, layer, page, off, rows):
    """The same update by whole pages, in place: grid ``(B,)``; a step
    takes slot b's page of ``layer`` through VMEM and puts it back with
    row ``off[b]`` replaced.  (A row alone cannot be copied into the
    pool: two bf16 rows share each 32-bit sublane word of a tile.)  The
    pool is aliased to the output, so nothing else of it moves.  Slots
    that share a page (parked ones: the dump page) overwrite each
    other's row there, which nothing reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = rows.shape[0]
    page_size, width = pool.shape[2], pool.shape[3]
    rows = jnp.pad(rows.astype(pool.dtype),
                   ((0, 0), (0, width - rows.shape[1])))[:, None, :]

    def page_map(i, pg, of, ly):
        return ly[0], pg[i], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((None, 1, width),
                               lambda i, pg, of, ly: (i, 0, 0)),
                  pl.BlockSpec((None, None, page_size, width), page_map)],
        out_specs=pl.BlockSpec((None, None, page_size, width), page_map),
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            _write_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            input_output_aliases={4: 0},
            interpret=_INTERPRET,
            name="mla_cache_write",
        )(page.astype(jnp.int32), off.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1), rows, pool)
