"""Paged (block-table) KV-cache attention — the serving-path kernel.

Reference analog: paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu — decode attention over a paged KV
cache: each sequence owns a list of fixed-size pages in a shared pool,
so HBM scales with sum(seq_len) instead of batch * max_len, and ragged
batches stop paying for the longest sequence.

TPU formulation: the page gather CANNOT be one dense einsum (the dense
path's whole trick), so this is where a kernel is the only option.  The
pools stay in HBM and the kernel copies pages itself.  One grid step is
one BLOCK of pages of one slot, for all its KV heads: grid
``(slots, ceil(max_pages / blk))``, ``blk = pages_per_block(...)`` pages,
as many as ``BLOCK_BYTES`` of one pool hold (the whole table where that
is less).  The block table and the lengths ride Pallas scalar prefetch;
the kernel reads ``table[b, p]`` and starts one DMA a page for K and one
for V, ``START_UNROLL`` pages a loop turn — the pool's layout makes a
page contiguous across its KV heads, so that DMA moves
``kvH * page_size * D`` elements into rows
``[p * page_size, (p + 1) * page_size)`` of a VMEM buffer
``[kvH, rows, D]``.  Two such buffers a pool alternate: a step starts
the copies of the next block that holds visible tokens (the slot's next,
or the next slot's first) before it waits for its own, so the copies run
under the arithmetic; the DMA semaphores count bytes, so a block's
copies are waited for a power of two of pages at a time (one wait a pool
for each power of two in the live page count, not one a page).  Nothing
is copied or computed past a slot's context: a grid step whose block
starts at or beyond ``lens[b]`` does nothing, and the last live block
copies only its live pages — table padding (the shared DUMP page) is
never fetched.  The arithmetic walks the block's copied rows in rounds
of a float32 online softmax, all KV heads at once (one batched
``dot_general`` a product): whole rounds of ``ROUND_TOKENS`` rows
unmasked over rows the context covers, then ONE masked round of the
smallest of ``tail_sizes`` that covers the rest.

The call's wrapper is one ``jax.jit`` whose operands include the layer:
the L calls of a step program share one jaxpr and one lowered Mosaic
body, so what the body holds is traced and lowered once a program, not
once a layer (tracing and lowering are paid by every process, whether
or not the compile cache then serves the executable).

Layout: pools [L, num_pages, kvH, page_size, D] (trailing dims tile):
every layer's pages in one array, which the callers pass WHOLE with the
layer to read — a ``kpool[layer]`` that feeds a Pallas call would be
copied out first (the kernel takes a whole HBM operand), 67 MB a layer a
pool at the serving cell's shape.  The layer rides scalar prefetch after
the table and the lengths (the MLA kernel's order) and the page copies
read ``pool[layer, page]``.  Table [B, max_pages] int32, lens [B] =
tokens visible per sequence.  Inference-only (no VJP).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import NUM_LANES

__all__ = ["paged_attention", "pages_per_block", "page_bytes", "PagedPool",
           "PagedKV", "select_paged_attention",
           "gather_kv_pages", "quantize_kv_rows", "gather_scale_pages",
           "gather_kv_pages_quant", "paged_attention_quant"]

_INTERPRET = False

# Bytes of one pool one grid step of the kernel copies at most (its
# pages: pages_per_block): 64 pages of 32 KiB in the Mistral cell (8 KV
# heads; the whole table of 1,024 tokens), 128 of 16 KiB in Granite's (4
# rows of two heads of 64; half its table, 2,048 tokens), 256 of 8 KiB in
# Nemotron's (2 KV heads; the whole table, 4,096 tokens).  Measured on
# the v5e with the kernel alone in one program, 200 calls on end, every
# slot at one context (``chip_smoke.py --paged-decode``, PR 36; rounds of
# 1,024 rows; ms a call at blocks of 256 / 512 / 1,024 / 2,048 / 4,096
# tokens, and the kernel as it was before PR 36 — blocks of 256 tokens,
# a wait a page, one masked round a block — last, in brackets):
#   Mistral  [32 slots, 8 rows a page, rep 4, table 64]
#     context 1      0.047 / 0.042 / 0.037                    [0.047]
#     context 500    0.120 / 0.120 / 0.099                    [0.120]
#     context 900    0.189 / 0.168 / 0.170                    [0.194]
#   Granite  [64, 4, rep 8, table 256]
#     context 1      0.142 / 0.100 / 0.080 / 0.068 / refused  [0.133]
#     context 1,100  0.451 / 0.348 / 0.298 / 0.279 / refused  [0.475]
#     context 4,032  1.284 / 1.009 / 0.866 / 0.842 / refused  [1.418]
#   Nemotron [64, 2, rep 16, table 256]
#     context 1      0.142 / 0.098 / 0.076 / 0.066 / 0.059    [0.134]
#     context 1,100  0.446 / 0.337 / 0.285 / 0.268 / 0.263    [0.468]
#     context 4,032  1.256 / 0.970 / 0.825 / 0.801 / 0.796    [1.385]
# (blocks of 256 and 512 at rounds of their own size).  3 MiB (3,072
# tokens of Granite's) read 1 % faster than 2 and 4 MiB are refused: two
# buffers a pool of 2 MiB are 8 MiB of the 16 MiB of VMEM a call may use
# unasked, and the rounds' own values want the rest.  At 2 KV heads the
# kernel is bound by the issue of its copies, 25 ns a page a pool
# whatever the page holds (8 KiB: 33 % of HBM's rate at contexts of
# 1,100, Granite's 16 KiB 63 %, Mistral's 32 KiB 81 % at 500).
BLOCK_BYTES = 2 << 20

# Rows one whole round of the online softmax takes.  Same runs, ms a
# call at rounds of 256 / 512 / 1,024 / 2,048 rows in the blocks above:
# Mistral at 500 0.100 / 0.099 / 0.099 / -; Granite at 1,100 0.358 /
# 0.306 / 0.279 / 0.284 and at 4,032 1.158 / 0.952 / 0.842 / 0.823;
# Nemotron at 1,100 0.339 / 0.289 / 0.263 / 0.256, at 4,032 1.100 / 0.899
# / 0.796 / 0.759 and at 1 token 0.057 / 0.058 / 0.059 / 0.064.  A round
# costs much the same whatever it holds, as the latent kernel's does;
# 2,048 gains 3 % only where every slot is long and wants the VMEM that
# Granite's block of 3 MiB also asked for (refused together).
ROUND_TOKENS = 1024

# Page copies a pool one turn of the loop that starts a block's copies
# issues (the latent kernel's: 4, 8 and 16 measured alike there).
START_UNROLL = 8


def select_paged_attention(tp_axis: str | None = None):
    """The paged-attention callable for the active backend: the Pallas
    scalar-prefetch kernel on TPU (or under interpret mode), the
    dense-gather XLA reference on CPU.  Single chooser shared by the
    one-shot paged generate and the serving engine so both always take
    the same numeric path.

    ``tp_axis`` selects the head-parallel path for callers running
    inside a ``shard_map`` over a tensor-parallel mesh axis: the pools
    are sharded on the KV-head axis, so each device's q heads attend
    their own KV heads' pages with the full sequence visible locally —
    softmax is per-head and the page gather is head-local, so the SAME
    per-shard kernel applies with NO collective inside attention (the
    axis name is only used to validate the caller's context).  The
    wrapper additionally checks that the LOCAL head counts still divide
    (nh/tp grouped onto kvh/tp), which holds whenever tp divides both —
    the runner's ``validate_tp`` contract."""
    if jax.default_backend() not in ("cpu",) or _INTERPRET:
        base = paged_attention
    else:
        base = paged_attention_xla
    if tp_axis is None:
        return base

    def head_parallel(q, kpool, vpool, layer, table, lens):
        _check_local_heads(q, kpool)
        return base(q, kpool, vpool, layer, table, lens)

    return head_parallel


def _check_local_heads(q, kpool):
    """Inside a ``shard_map`` over the KV-head axis: this shard's q
    heads must still group onto its KV heads."""
    nh_l, kvh_l = q.shape[1], kpool.shape[2]
    if kvh_l == 0 or nh_l % kvh_l:
        raise ValueError(
            f"head-parallel paged attention: local q heads {nh_l} "
            f"do not group onto local KV heads {kvh_l} — the tp "
            "size must divide both head counts")


def page_bytes(pool) -> int:
    """Bytes one page of ``pool`` [L, P, kvH, page_size, D] holds: what
    one of the kernel's page copies moves."""
    kvh, page_size, d = pool.shape[2:]
    return kvh * page_size * d * jnp.dtype(pool.dtype).itemsize


def pages_per_block(max_pages: int, page_bytes: int) -> int:
    """Pages one grid step of :func:`paged_attention` covers: as many
    pages of ``page_bytes`` (one pool's: :func:`page_bytes`) as
    ``BLOCK_BYTES`` hold, at least one and at most the table's width.
    The engine's ``paged_blocks_*`` counters read the rule here rather
    than repeat it."""
    return max(1, min(BLOCK_BYTES // int(page_bytes), int(max_pages)))


def round_tokens(block_tokens: int) -> int:
    """Rows one whole round of the online softmax takes in a block of
    ``block_tokens``."""
    return min(ROUND_TOKENS, int(block_tokens))


def tail_sizes(chunk: int) -> list[int]:
    """Rows the one masked round after a block's whole rounds may take:
    one to four quarters of a round, in whole sublane tiles."""
    return sorted({min(chunk, -(-chunk * k // 64) * 16) for k in (1, 2, 3, 4)})


def _paged_kernel(table_ref, lens_ref, layer_ref, q_ref, k_hbm, v_hbm,
                  o_ref, kbuf, vbuf, sems, side_ref, acc_ref, m_ref, l_ref,
                  *, page_size, blk, chunk, unroll, max_pages, sm_scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, j = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    tokens = blk * page_size                        # a block's tokens

    def visible(b_):
        # never past the table's row, whatever ``lens`` says
        return jnp.minimum(lens_ref[b_], max_pages * page_size)

    def live_pages(b_, j_):
        # Pages of block ``j_`` that hold visible tokens.  Bounded by
        # the context's page count, which ``visible`` holds to
        # ``max_pages``: where ``max_pages`` is no multiple of ``blk``
        # the last block's loops stop at the row's end and no table
        # entry past it is ever read.
        n_pages = (visible(b_) + page_size - 1) // page_size
        return jnp.clip(n_pages - j_ * blk, 0, blk)

    def start_block(b_, j_, side):
        def start(p):
            # one page = all its KV heads, contiguous in the pool
            page = table_ref[b_, j_ * blk + p]
            rows = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)
            pltpu.make_async_copy(k_hbm.at[layer, page],
                                  kbuf.at[side, :, rows, :],
                                  sems.at[0, side]).start()
            pltpu.make_async_copy(v_hbm.at[layer, page],
                                  vbuf.at[side, :, rows, :],
                                  sems.at[1, side]).start()

        def group(g, _):
            for i in range(unroll):
                start(g * unroll + i)
        n = live_pages(b_, j_)
        jax.lax.fori_loop(0, n // unroll, group, None)
        jax.lax.fori_loop(n // unroll * unroll, n,
                          lambda p, _: start(p), None)

    def wait_block(b_, j_, side):
        # The semaphores count bytes, so one wait can stand for the
        # copies of k pages of a pool: a wait a pool for each power of
        # two in the live count, not one a page.
        n = live_pages(b_, j_)
        k = 1 << (blk.bit_length() - 1)
        while k:
            @pl.when((n & k) != 0)
            def _wait(k=k):
                for buf, sem in ((kbuf, sems.at[0, side]),
                                 (vbuf, sems.at[1, side])):
                    rows = buf.at[side, :, pl.ds(0, k * page_size), :]
                    pltpu.make_async_copy(rows, rows, sem).wait()
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

    # The chain of copies runs over every slot's block 0 (no page at all
    # where the slot sees nothing) and over each further block that
    # starts inside the context; a step past it does nothing.
    @pl.when((j == 0) | (j * tokens < n_tok))
    def _block():
        side = side_ref[0]
        more = (j + 1) * tokens < n_tok
        nb = jnp.where(more, b, b + 1)
        nj = jnp.where(more, j + 1, 0)

        @pl.when(nb < pl.num_programs(0))
        def _prefetch():        # the next block's pages, other buffer
            start_block(nb, nj, 1 - side)

        side_ref[0] = 1 - side
        wait_block(b, j, side)

        # the block's rows that the slot sees: 0 where n_tok is 0
        seen = jnp.clip(n_tok - j * tokens, 0, tokens)

        def softmax_round(at, size, masked):
            # one round of the online softmax over rows [at, at + size)
            q = q_ref[...]                          # [kvH, rep, D]
            k = kbuf[side, :, pl.ds(at, size), :]   # [kvH, size, D]
            v = vbuf[side, :, pl.ds(at, size), :]
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * jnp.float32(sm_scale)
            if masked:
                t_s = at + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
                s = jnp.where(t_s < seen, s, -jnp.inf)
                # past the context the buffer holds whatever was there:
                # 0 * NaN in p.v would poison the row, so v is masked too
                t_v = at + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
                v = jnp.where(t_v < seen, v, jnp.zeros_like(v))
            m_prev = m_ref[:, :, :1]
            l_prev = l_ref[:, :, :1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_cur = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

        # Whole rounds go by unmasked over rows the context covers, and
        # what is left takes ONE masked round, of the smallest size that
        # covers it.
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
        l = l_ref[:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attention(q, kpool, vpool, layer, table, lens):
    """q [B, nh, D]; pools [L, P, kvH, page_size, D], passed whole;
    ``layer`` the pools' layer to read (a Python int or a traced
    scalar); table [B, max_pages] int32 page ids (padding = a dump page
    id, as PagedPool builds it — never a real page); lens [B] visible
    tokens.  Returns [B, nh, D].

    Every call of one shape inside a program is a call of ONE traced
    and lowered function (``layer`` is an operand of it): the kernel's
    body is traced and lowered to Mosaic once a program, not once a
    layer."""
    blk = pages_per_block(table.shape[1], page_bytes(kpool))
    return _paged_attention(
        q, kpool, vpool, jnp.asarray(layer, jnp.int32).reshape(1),
        table.astype(jnp.int32), lens.astype(jnp.int32), blk=blk,
        chunk=round_tokens(blk * kpool.shape[3]), unroll=START_UNROLL,
        interpret=_INTERPRET)


@functools.partial(jax.jit,
                   static_argnames=("blk", "chunk", "unroll", "interpret"))
def _paged_attention(q, kpool, vpool, layer, table, lens, *, blk, chunk,
                     unroll, interpret):
    # what the module's switches said when the caller read them rides
    # in as static arguments: a cached trace never reads a global
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, nh, d = q.shape
    kvh, page_size = kpool.shape[2], kpool.shape[3]
    rep = nh // kvh
    max_pages = table.shape[1]
    # the buffers hold whole rounds, so the last round of a block that
    # is no multiple of one reads rows nothing copies: masked like any
    rows = -(-blk * page_size // chunk) * chunk
    qg = q.reshape(b, kvh, rep, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, -(-max_pages // blk)),
        in_specs=[
            pl.BlockSpec((None, kvh, rep, d),
                         lambda b_, j, tbl, ln, ly: (b_, 0, 0, 0)),
            # the pools stay in HBM: the kernel copies the pages the
            # scalar-prefetched table names
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, kvh, rep, d),
                               lambda b_, j, tbl, ln, ly: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, rows, d), kpool.dtype),
            pltpu.VMEM((2, kvh, rows, d), vpool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # [k|v, buffer]
            pltpu.SMEM((1,), jnp.int32),            # buffer being read
            pltpu.VMEM((kvh, rep, d), jnp.float32),
            pltpu.VMEM((kvh, rep, NUM_LANES), jnp.float32),
            pltpu.VMEM((kvh, rep, NUM_LANES), jnp.float32),
        ],
    )
    with jax.enable_x64(False):   # see flash_attention._flash_fwd
        out = pl.pallas_call(
            functools.partial(_paged_kernel, page_size=page_size,
                              blk=blk, chunk=chunk, unroll=unroll,
                              max_pages=max_pages,
                              sm_scale=1.0 / np.sqrt(d)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
            interpret=interpret,
            name="paged_attention",
        )(table, lens, layer, qg, kpool, vpool)
    return out.reshape(b, nh, d)


def gather_kv_pages(pool, table):
    """Materialize a block table's pages token-major: ``pool``
    [P, kvH, page_size, D], ``table`` [..., W] int32 page ids
    (dump-padded) -> [..., W * page_size, kvH, D].  The dense-gather
    building block shared by :func:`paged_attention_xla` and the serving
    engine's cached prefill (which attends suffix queries over the
    resident prefix pages it gathers here)."""
    kvh, ps, d = pool.shape[1:]
    g = pool[table]                            # [..., W, kvh, ps, d]
    g = jnp.swapaxes(g, -3, -2)                # [..., W, ps, kvh, d]
    return g.reshape(table.shape[:-1] + (table.shape[-1] * ps, kvh, d))


def paged_attention_xla(q, kpool, vpool, layer, table, lens):
    """Dense-gather reference (identical numerics; the kernel's
    arguments): materializes each sequence's pages of ``layer`` —
    O(B * max_pages * page_size) HBM — used off-TPU and by the parity
    tests.  The slice of the pool fuses into the gather."""
    b, nh, d = q.shape
    rep = nh // kpool.shape[2]
    # [B, W*ps, kvh, D] -> [B, kvh, W*ps, D]
    kb = gather_kv_pages(kpool[layer], table).transpose(0, 2, 1, 3)
    vb = gather_kv_pages(vpool[layer], table).transpose(0, 2, 1, 3)
    kq = jnp.repeat(kb, rep, axis=1)
    vq = jnp.repeat(vb, rep, axis=1)
    logits = jnp.einsum("bhd,bhtd->bht", q, kq,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    tpos = jnp.arange(kb.shape[2])
    valid = tpos[None, None, :] < lens[:, None, None]
    logits = jnp.where(valid, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,bhtd->bhd", probs, vq)


# --------------------------------------------------- int8 KV page mode
def quantize_kv_rows(x):
    """Symmetric per-(token, head) int8 quantization of new KV rows:
    ``x`` [..., D] float -> (q int8 [..., D], scale f32 [...]).  The
    amax reduction runs on the FLOAT input (never over int8 — a
    narrow-int reduction would promote under x64 and silently clip
    without it; see the dtype_flow lint rule), the scale is floored so
    all-zero rows divide cleanly, and values round into [-127, 127].
    Runs inside the jitted decode/prefill step, so the scale update
    costs no extra host sync."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def gather_scale_pages(scale, table):
    """Scale-pool mirror of :func:`gather_kv_pages`: ``scale``
    [P, kvH, page_size] f32 per-(page-row, head) scales, ``table``
    [..., W] int32 -> [..., W * page_size, kvH] token-major."""
    kvh, ps = scale.shape[1:]
    g = scale[table]                           # [..., W, kvh, ps]
    g = jnp.swapaxes(g, -2, -1)                # [..., W, ps, kvh]
    return g.reshape(table.shape[:-1] + (table.shape[-1] * ps, kvh))


def gather_kv_pages_quant(pool, scale, table, dtype=jnp.float32):
    """Dequantizing gather: int8 ``pool`` + per-row ``scale`` ->
    float token-major [..., W * page_size, kvH, D].  The dequant is
    fused into the gather (one elementwise multiply on the gathered
    block), so downstream attention sees the same layout the dense
    :func:`gather_kv_pages` produces."""
    g = gather_kv_pages(pool, table).astype(jnp.float32)
    s = gather_scale_pages(scale, table)
    return (g * s[..., None]).astype(dtype)


def paged_attention_quant(q, kpool, vpool, kscale, vscale, layer, table,
                          lens, tp_axis=None):
    """Paged attention over int8 KV pools [L, P, kvH, page_size, D] with
    per-(page-row, head) f32 scales [L, P, kvH, page_size], all passed
    whole with the ``layer`` to read: the dense-gather formulation of
    :func:`paged_attention_xla` with dequantization fused into the page
    gather.  ``tp_axis`` marks a head-parallel caller inside a
    ``shard_map`` (pools sharded on the KV-head axis); like the dense
    chooser it only validates the local head grouping — attention
    itself needs no collective."""
    if tp_axis is not None:
        _check_local_heads(q, kpool)
    b, nh, d = q.shape
    rep = nh // kpool.shape[2]
    # [B, W*ps, kvh, D] -> [B, kvh, W*ps, D], dequantized at the gather
    kb = gather_kv_pages_quant(kpool[layer], kscale[layer], table,
                               q.dtype).transpose(0, 2, 1, 3)
    vb = gather_kv_pages_quant(vpool[layer], vscale[layer], table,
                               q.dtype).transpose(0, 2, 1, 3)
    kq = jnp.repeat(kb, rep, axis=1)
    vq = jnp.repeat(vb, rep, axis=1)
    logits = jnp.einsum("bhd,bhtd->bht", q, kq,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    tpos = jnp.arange(kb.shape[2])
    valid = tpos[None, None, :] < lens[:, None, None]
    logits = jnp.where(valid, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,bhtd->bhd", probs, vq)


class PagedKV(NamedTuple):
    """A program's K/V pools as one value that knows its page format.

    ``k``/``v`` [L, P, kvH, page_size, D], every layer's, whole.  Plain
    pages hold the model's dtype and ``kscale``/``vscale`` are the empty
    tuple (no leaves: a program that carries this value has the plain
    program's arguments exactly); int8 pages come with f32 scale pools
    [L, P, kvH, page_size], one scale a (token, head).  A layer body
    writes, attends and gathers through the methods and knows neither
    format; each returns or reads the pools whole, so donated pools are
    updated where they lie."""
    k: jax.Array
    v: jax.Array
    kscale: jax.Array | tuple = ()
    vscale: jax.Array | tuple = ()

    @property
    def quantized(self) -> bool:
        return not isinstance(self.kscale, tuple)

    def write(self, layer, page, off, k, v):
        """Rows ``k``/``v`` [N, kvH, D] to ``[layer, page[n], head,
        off[n]]`` (``page``/``off`` [N]), quantized on the way where the
        pages are int8 — in the same traced step, no host sync."""
        if self.quantized:
            k, sk = quantize_kv_rows(k)
            v, sv = quantize_kv_rows(v)
        idx = (layer, page[:, None], jnp.arange(k.shape[1])[None, :],
               off[:, None])
        kp, vp = self.k.at[idx].set(k), self.v.at[idx].set(v)
        if not self.quantized:
            return PagedKV(kp, vp)
        return PagedKV(kp, vp, self.kscale.at[idx].set(sk),
                       self.vscale.at[idx].set(sv))

    def write_pages(self, layer, pages, k, v):
        """One prompt's rows ``k``/``v`` [1, n * page_size, kvH, D] as
        whole pages ``pages`` [n] of ``layer``, a scatter a page."""
        ps = self.k.shape[3]
        kp, vp, ks, vs = self
        if self.quantized:
            k, sk = quantize_kv_rows(k)
            v, sv = quantize_kv_rows(v)
        for p in range(pages.shape[0]):
            sl = slice(p * ps, (p + 1) * ps)
            rows_k = k[0, sl].swapaxes(0, 1)
            rows_v = v[0, sl].swapaxes(0, 1)
            kp = kp.at[layer, pages[p]].set(rows_k.astype(kp.dtype))
            vp = vp.at[layer, pages[p]].set(rows_v.astype(vp.dtype))
            if self.quantized:
                ks = ks.at[layer, pages[p]].set(sk[0, sl].swapaxes(0, 1))
                vs = vs.at[layer, pages[p]].set(sv[0, sl].swapaxes(0, 1))
        return PagedKV(kp, vp, ks, vs)

    def attend(self, q, layer, table, lens, axis=None):
        """Decode attention of ``q`` [B, nh, D] over ``layer``'s pages:
        the paged kernel (plain pages; its XLA twin off the TPU) or the
        dequantizing gather.  ``axis`` names the mesh axis of a
        head-parallel caller."""
        if self.quantized:
            return paged_attention_quant(q, *self, layer, table, lens,
                                         tp_axis=axis)
        return select_paged_attention(tp_axis=axis)(
            q, self.k, self.v, layer, table, lens)

    def gather(self, layer, row, dtype):
        """The pages of table row ``row`` [W] in ``layer``, token-major:
        (k, v), each [W * page_size, kvH, D], int8 pages dequantized to
        ``dtype``."""
        if self.quantized:
            return (gather_kv_pages_quant(self.k[layer],
                                          self.kscale[layer], row, dtype),
                    gather_kv_pages_quant(self.v[layer],
                                          self.vscale[layer], row, dtype))
        return (gather_kv_pages(self.k[layer], row),
                gather_kv_pages(self.v[layer], row))


class PagedPool:
    """Host-side page allocator (reference: the block tables
    block_multi_head_attention takes as inputs).  Static shapes: each
    sequence reserves ceil((len + max_new) / page_size) pages up front;
    the shared pool holds exactly the reserved pages, so HBM scales
    with sum of lengths, not batch * max_len."""

    def __init__(self, lengths, max_new_tokens, page_size=128,
                 min_table_width=0):
        lengths = np.asarray(lengths, np.int64)
        self.page_size = int(page_size)
        need = -(-(lengths + max_new_tokens) // self.page_size)
        # one extra DUMP page absorbs writes/reads through table padding
        # (a padded prompt's page-granular prefill scatters must never
        # alias a sequence's real pages — repeating a real id would let
        # padding rows clobber real tokens); the decode kernel stops at
        # each context's last page and never fetches it
        self.dump_page = int(need.sum())
        self.num_pages = self.dump_page + 1
        self.max_pages = max(int(need.max()), int(min_table_width))
        table = np.full((len(lengths), self.max_pages), self.dump_page,
                        np.int32)
        start = 0
        for i, n in enumerate(need):
            table[i, :n] = np.arange(start, start + n)
            start += n
        self.table = table
        self.reserved = need
