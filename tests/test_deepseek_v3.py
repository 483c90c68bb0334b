"""The ``deepseek_v3`` family on the serving path, at a tiny size on the
CPU: the Engine against the plain reference's full forward, absorbed
decode attention against expanded, the router by hand, the expert
shares against the uncut layer, latent pages through preemption, resume
and copy-on-write, and the options the family does not have.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu  # noqa: E402,F401
from benchmarks.reference import deepseek_v3_lm as ref  # noqa: E402
from paddle_tpu.models import deepseek_v3 as ds  # noqa: E402
from paddle_tpu.models.generation import GenerationConfig  # noqa: E402
from paddle_tpu.ops.pallas import grouped_ffn as G  # noqa: E402
from paddle_tpu.ops.pallas import mla_paged_attention as M  # noqa: E402
from paddle_tpu.serving.engine import Engine  # noqa: E402

YARN = {"factor": 4.0, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
        "mscale_all_dim": 1.0}


def toy_cfg(**kw):
    """Hidden 64, 4 heads, ranks 24/16, rope 8, 16 experts in 4 groups,
    top 4 of 2 groups, one dense then two expert layers."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=24, n_routed_experts=16,
                n_shared_experts=1, num_experts_per_tok=4, n_group=4,
                topk_group=2, routed_scaling_factor=2.5,
                norm_topk_prob=True, max_position_embeddings=256,
                rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=YARN,
                dtype="float32")
    base.update(kw)
    return ds.DeepseekV3Config(**base)


def toy_state(cfg, seed=0, std=0.1):
    rng = np.random.default_rng(seed)
    out = {}
    for k, shape in ds.weight_shapes(cfg).items():
        if k.endswith("norm.weight"):
            out[k] = jnp.asarray(1.0 + 0.1 * rng.normal(size=shape),
                                 jnp.float32)
        else:
            out[k] = jnp.asarray(std * rng.normal(size=shape), jnp.float32)
    return out


def model_dict(cfg):
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    m["local_experts"] = list(cfg.local_experts)
    return m


def engine(cfg, state, **kw):
    base = dict(max_slots=3, page_size=4, max_model_len=64)
    base.update(kw)
    return Engine(config=cfg, state=state, **base)


@pytest.fixture(scope="module")
def share():
    """A chip that holds experts 4..11 of 16."""
    cfg = toy_cfg(local_experts=(4, 8))
    return cfg, toy_state(cfg)


# ----------------------------------------------- engine against reference
def test_prefill_then_decode_logits_match_the_reference(share):
    """Every logit row the Engine produced (the prefill's last position,
    then one row a decode step through the latent cache) against one
    full forward of the reference over prompt + served tokens."""
    cfg, state = share
    eng = engine(cfg, state, emit_logits=True)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (5, 11, 18)]
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=10))
            for p in prompts]
    rows = {r.id: [] for r in reqs}
    while eng.step():
        logits = eng._last_logits
        if logits is None:
            continue
        logits = np.asarray(logits)
        for slot, r in enumerate(eng.scheduler.slots):
            # the step that produced token n of r read position
            # len(prompt) + n - 2 ... keep (token it makes, row).  The
            # host runs one step behind: the step just dispatched makes
            # the token AFTER those the request has been handed
            if r is not None:
                rows[r.id].append((r.num_generated + 1, logits[slot]))
    assert eng.decode_traces == 1
    m = model_dict(cfg)
    for p, r in zip(prompts, reqs):
        toks = r.result()
        assert len(toks) == 10
        want = np.asarray(ref.logits_at(
            state, m, np.concatenate([p, toks]),
            np.arange(len(p) - 1, len(p) + len(toks) - 1)))
        # greedy: every served token is the reference's best
        assert list(np.argmax(want, -1)) == list(toks)
        seen = {}
        for n, row in rows[r.id]:
            seen.setdefault(n, row)         # first sight of token n
        assert len(seen) >= 8
        for n, row in seen.items():
            np.testing.assert_allclose(row, want[n - 1], atol=2e-4)


def test_served_gaps_and_the_int8_control(share):
    cfg, state = share
    eng = engine(cfg, state)
    p = np.arange(7, dtype=np.int32) * 5 % 96
    toks = eng.submit(p, GenerationConfig(max_new_tokens=12))
    eng.run_until_complete()
    m = model_dict(cfg)
    got = ref.served_gaps(state, m, p, toks.result(), pad_to=32,
                          pad_rows=16)
    assert got["gaps"].shape == (12,) and float(got["gaps"].max()) < 1e-4
    ctl = ref.served_gaps(state, m, p, toks.result(), pad_to=32,
                          pad_rows=16, int8=True)
    assert np.all(ctl["gaps"] >= 0.0) and ctl["gaps"].shape == (12,)


# ------------------------------------------- absorbed = expanded attention
def test_absorbed_decode_equals_expanded_attention():
    """The last position of an expanded prefill over S tokens is the
    absorbed decode of token S-1 over a pool that a prefill of S-1
    tokens filled."""
    cfg = toy_cfg()
    state = toy_state(cfg, seed=3)
    w = ds.layer_weights(state, cfg, 0)
    s, ps = 13, 4
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, s, cfg.hidden_size)), jnp.float32)
    cos, sin = ds.rope_tables(cfg, 32)
    full, rows, _ = ds.prefill_layer(cfg, w, 0, x, cos[:s], sin[:s],
                                     jnp.ones((s,), bool))
    width = M.row_width(cfg.cache_row)
    pool = jnp.full((1, 9, ps, width), jnp.nan, jnp.float32)
    table = np.array([[5, 2, 7, 0, 8, 8]], np.int32)       # 8 = dump
    for t in range(s - 1):
        pool = pool.at[0, table[0, t // ps], t % ps, :cfg.cache_row].set(
            rows[t])
    pool = pool.at[0, table[0, 3], 0].set(0.0)      # the row to be written
    pos = jnp.asarray([s - 1], jnp.int32)
    out, pool2, _ = ds.decode_layer(
        cfg, w, 0, x[0, s - 1:], pool, jnp.asarray(table),
        cos[s - 1:s], sin[s - 1:s], pos, jnp.ones((1,), jnp.int32))
    np.testing.assert_allclose(out[0], full[0, s - 1], atol=1e-5)
    np.testing.assert_allclose(pool2[0, 0, 0, :cfg.cache_row], rows[s - 1],
                               atol=1e-6)


def test_yarn_constants_of_the_published_config():
    cfg = toy_cfg(qk_nope_head_dim=128, qk_rope_head_dim=64,
                  rope_theta=1e5,
                  rope_scaling={"beta_fast": 32, "beta_slow": 1,
                                "factor": 64, "mscale": 1,
                                "mscale_all_dim": 1,
                                "original_max_position_embeddings": 4096})
    m = 0.1 * np.log(64.0) + 1.0
    assert m == pytest.approx(1.4159, abs=1e-4)
    assert ds.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    inv = np.asarray(ds.yarn_inv_freq(cfg))
    plain = 1.0 / (1e5 ** (np.arange(0, 64, 2) / 64))
    # correction dims of 32 and 1 rotations over 4096: floor(8.39), ceil(18.02)
    assert np.allclose(inv[:9], plain[:9])          # fast dims: as they are
    assert np.allclose(inv[19:], plain[19:] / 64)   # slow dims: interpolated
    assert plain[13] / 64 < inv[13] < plain[13]
    cos, sin = ds.rope_tables(cfg, 8)
    assert cos.shape == (8, 64) and float(cos[0, 0]) == 1.0   # mscale ratio 1
    np.testing.assert_allclose(inv, np.asarray(ref.inv_freq(model_dict(cfg))),
                               rtol=1e-6)


# ------------------------------------------------------------------ router
def test_router_by_hand_the_bias_moves_the_choice_never_the_weight():
    """8 experts in 4 groups of 2, 2 groups kept, 2 experts a token.
    Scores are set through an identity gate."""
    cfg = toy_cfg(hidden_size=8, n_routed_experts=8, n_group=4,
                  topk_group=2, num_experts_per_tok=2,
                  routed_scaling_factor=2.0)
    logit = np.array([[2.0, 1.0, 0.5, 0.0, -1.0, 3.0, 0.0, 0.1]], np.float32)
    sc = 1.0 / (1.0 + np.exp(-logit[0]))
    gate = jnp.eye(8, dtype=jnp.float32)
    # group scores (sum of the 2 in each): g0 1.61, g1 1.12, g2 1.22, g3 1.02
    idx, w = ds.route(cfg, jnp.asarray(logit), gate, jnp.zeros((8,)))
    # kept groups 0 (0.881+0.731) and 2 (0.269+0.953): experts 5 and 0
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 5]
    picked = sc[np.asarray(idx)[0]]
    np.testing.assert_allclose(np.asarray(w)[0],
                               picked / picked.sum() * 2.0, rtol=1e-6)
    # a bias on expert 6 lifts group 3 over group 2 and expert 6 over 0's
    # runner-up: the choice changes, the weights are still the scores'
    bias = np.zeros((8,), np.float32)
    bias[6] = 2.0
    idx2, w2 = ds.route(cfg, jnp.asarray(logit), gate, jnp.asarray(bias))
    assert sorted(np.asarray(idx2)[0].tolist()) == [0, 6]
    picked2 = sc[np.asarray(idx2)[0]]
    np.testing.assert_allclose(np.asarray(w2)[0],
                               picked2 / picked2.sum() * 2.0, rtol=1e-6)
    # the reference's router agrees
    ridx, rw = ref.route(jnp.asarray(logit), gate, jnp.asarray(bias),
                         groups=4, keep_groups=2, top_k=2, norm=True,
                         factor=2.0)
    assert sorted(np.asarray(ridx)[0].tolist()) == [0, 6]
    np.testing.assert_allclose(np.sort(np.asarray(rw)[0]),
                               np.sort(np.asarray(w2)[0]), rtol=1e-6)


# ------------------------------------------------ shares add up to the layer
def test_every_share_of_an_expert_layer_adds_up_to_the_uncut_layer():
    """4 chips of 4 experts: the routed parts of all four, and the shared
    expert counted once, are the uncut reference layer."""
    whole = toy_cfg()
    state = toy_state(whole, seed=5)
    li = 1
    p = f"model.layers.{li}."
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(9, whole.hidden_size)), jnp.float32)
    valid = jnp.ones((9,), bool)

    lw = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
    ffn = {k: v for k, v in lw.items()
           if k.startswith("mlp.") or k.startswith("post_")}
    experts = {k: ffn.pop("mlp.experts." + k + "_proj.weight")
               for k in ("gate", "up", "down")}
    h, want, idx, wt = ref.expert_ffn_shared(
        x, ffn, eps=1e-6, groups=4, keep_groups=2, top_k=4, norm=True,
        factor=2.5, int8=False)
    for e in range(16):
        want = ref.add_expert(want, h, idx, wt, e, experts["gate"][e],
                              experts["up"][e], experts["down"][e],
                              int8=False)
    want = want - x                                  # the layer's ffn(h)

    total, shared_once, pairs = 0.0, None, 0
    for rank in range(4):
        cfg = toy_cfg(local_experts=(4 * rank, 4))
        st = dict(state)
        for k in ("gate", "up", "down"):
            name = f"{p}mlp.experts.{k}_proj.weight"
            st[name] = state[name][4 * rank:4 * rank + 4]
        w = ds.layer_weights(st, cfg, li)
        routed, counts = ds.routed_experts(cfg, w, h, valid, 8)
        part, _ = ds.ffn(cfg, w, li, h, valid, 8)
        shared = np.asarray(part) - np.asarray(routed)
        if shared_once is None:
            shared_once = shared
        np.testing.assert_allclose(shared, shared_once, atol=1e-5)
        total = total + np.asarray(routed)
        pairs += int(counts[1])
        assert int(counts[0]) == 9 * 4
    assert pairs == 9 * 4                   # every pair computed once
    np.testing.assert_allclose(total + shared_once, want, atol=2e-5)


def test_dropless_under_extreme_imbalance():
    """Every token choosing the same held experts is computed in full:
    no capacity, no dropped pair."""
    cfg = toy_cfg(local_experts=(0, 4))
    t, k = 20, cfg.num_experts_per_tok
    idx = jnp.tile(jnp.asarray([[0, 1, 2, 9]], jnp.int32), (t, 1))
    valid = jnp.arange(t) < 17
    row_pair, pair_row, emap, n_live, sizes = ds._sorted_rows(
        cfg, idx, valid, 8)
    assert list(np.asarray(sizes)) == [17, 17, 17, 0]
    assert int(n_live) == 9                 # 3 experts x ceil(17 / 8)
    assert list(np.asarray(emap)[:9]) == [0, 0, 0, 1, 1, 1, 2, 2, 2]
    rp, pr = np.asarray(row_pair), np.asarray(pair_row)
    for pair in range(t * k):
        tok, choice = divmod(pair, k)
        here = tok < 17 and choice < 3
        if here:
            assert rp[pr[pair]] == pair
            assert np.asarray(emap)[pr[pair] // 8] == choice
        else:
            assert pr[pair] == rp.size      # reads as zero


# --------------------------------------------------- kernels, interpreted
@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(M, "_INTERPRET", True)
    monkeypatch.setattr(G, "_INTERPRET", True)


def _kernel_equals_the_gather(lens, *, w, ps, rank, rope, nh, lanes=32,
                              layers=2, atol=2e-6):
    """``mla_paged_attention`` against the dense gather for slots that
    see ``lens`` tokens: pages scattered over the pool, every table
    entry past a context the dump page, every page no row names NaN."""
    rng = np.random.default_rng(0)
    lens = np.asarray(lens, np.int32)
    b, pages = len(lens), len(lens) * w + 4
    pool = rng.normal(size=(layers, pages + 1, ps, lanes)).astype(np.float32)
    table = np.full((b, w), pages, np.int32)
    perm, at = rng.permutation(pages), 0
    for i in range(b):
        n = -(-lens[i] // ps)
        table[i, :n] = perm[at:at + n]
        at += n
    owned = set(table.flatten().tolist()) - {pages}
    for page in range(pages + 1):           # NaN where no row names it
        if page not in owned:
            pool[:, page] = np.nan
    ql = jnp.asarray(rng.normal(size=(b, nh, rank)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(b, nh, rope)), jnp.float32)
    for layer in range(layers):
        args = (ql, qr, jnp.asarray(pool), layer, jnp.asarray(table),
                jnp.asarray(lens))
        got = M.mla_paged_attention(*args, sm_scale=0.3)
        want = M.mla_paged_attention_xla(*args, sm_scale=0.3)
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got, want, atol=atol)
        assert not np.asarray(got)[lens == 0].any()


@pytest.mark.parametrize("block_tokens", [8, 16, 256])
def test_mla_kernel_matches_the_dense_gather(interpret, monkeypatch,
                                             block_tokens):
    monkeypatch.setattr(M, "BLOCK_TOKENS", block_tokens)
    _kernel_equals_the_gather([5, 37, 1], w=12, ps=4, rank=16, rope=8, nh=4)


@pytest.mark.parametrize("context", [1, 255, 256, 257, 511, 512, 513, 767,
                                     768, 769, 1023, 1024, 1025, 2048])
def test_mla_kernel_at_its_block_and_chunk_edges(interpret, monkeypatch,
                                                 context):
    """A block of 1,024 tokens walked in whole chunks of 1,024 rows and
    one masked round of 256, 512, 768 or 1,024: a context on either side
    of each edge, up to the table's full width (two blocks), beside an
    empty slot and a short one whose row is mostly the dump page."""
    monkeypatch.setattr(M, "BLOCK_TOKENS", 1024)
    assert M.tail_sizes(M.CHUNK_TOKENS) == [256, 512, 768, 1024]
    assert M.pages_per_block(16, 128) == 64
    _kernel_equals_the_gather([context, 0, 300], w=128, ps=16, rank=16,
                              rope=8, nh=4)


@pytest.mark.parametrize("chunk_tokens", [8, 24, 256])
def test_mla_kernel_walks_a_block_in_chunks(interpret, monkeypatch,
                                            chunk_tokens):
    """Chunks that divide the block, that do not (40 rows in chunks of
    24: the buffer's last rows are copied by nothing) and that exceed
    it; contexts that end inside a page, leave every size of tail and
    none, and live page counts with one bit set and with several."""
    monkeypatch.setattr(M, "BLOCK_TOKENS", 40)
    monkeypatch.setattr(M, "CHUNK_TOKENS", chunk_tokens)
    _kernel_equals_the_gather([41, 0, 7, 96, 80, 33, 64, 22], w=12, ps=8,
                              rank=16, rope=8, nh=4)


def test_mla_kernel_at_the_cells_heads_and_rank(interpret):
    """64 heads over a latent of 512 + 64 in rows declared 640 wide,
    pages of 16: the cell's widths at a table of 80 pages."""
    _kernel_equals_the_gather([1030, 0, 300], w=80, ps=16, rank=512,
                              rope=64, nh=64, lanes=M.row_width(576),
                              layers=1, atol=2e-5)


def test_cache_write_kernel_replaces_one_row_a_slot(interpret):
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(2, 9, 4, 128)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(5, 24)), jnp.float32)
    page = jnp.asarray([3, 0, 8, 7, 5], jnp.int32)
    off = jnp.asarray([1, 3, 0, 2, 2], jnp.int32)
    got = M.write_rows(pool, 1, page, off, rows)
    want = M.write_rows_xla(pool, 1, page, off, rows)
    np.testing.assert_array_equal(got[..., :24], want[..., :24])
    untouched = np.ones((2, 9, 4), bool)
    untouched[1, np.asarray(page), np.asarray(off)] = False
    np.testing.assert_array_equal(np.asarray(got)[untouched],
                                  np.asarray(pool)[untouched])
    # parked slots share the dump page: the others' rows are unharmed
    page = jnp.asarray([3, 8, 8, 8, 5], jnp.int32)
    got = M.write_rows(pool, 0, page, off, rows)
    np.testing.assert_array_equal(got[0, 3, 1, :24], rows[0])
    np.testing.assert_array_equal(got[0, 5, 2, :24], rows[4])
    np.testing.assert_array_equal(got[1], pool[1])


@pytest.mark.parametrize("n_live", [0, 3, 6])
def test_grouped_matmul_matches_the_dense_gather(interpret, monkeypatch,
                                                 n_live):
    monkeypatch.setattr(G, "BLOCK_BYTES", 128 * 128 * 4)
    rng = np.random.default_rng(0)
    e, k, n, tm = 4, 256, 384, 8
    assert G.expert_blocks(k, n, 4) == (128, 128)
    w = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(6 * tm, k)), jnp.float32)
    emap = jnp.asarray([0, 0, 2, 3, 3, 3], jnp.int32)
    got = G.grouped_matmul(x, w, emap, n_live, tile_m=tm)
    want = G.grouped_matmul_xla(x, w, emap, n_live, tile_m=tm)
    rows = n_live * tm
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=1e-5,
                               atol=1e-4)
    assert not np.any(np.asarray(want[rows:]))


def test_expert_blocks_at_the_published_widths():
    # gate/up [7168, 2048] and down [2048, 7168] in bf16: whole rows,
    # the contraction cut in four, 7.3 MB a block
    assert G.expert_blocks(7168, 2048, 2) == (1792, 2048)
    assert G.expert_blocks(2048, 7168, 2) == (512, 7168)
    assert G.expert_blocks(64, 32, 4) == (64, 32)


# -------------------------------------------- latent pages through the engine
def _run(eng, jobs):
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=n))
            for p, n in jobs]
    eng.run_until_complete(max_steps=800)
    return reqs


def test_preempt_spill_resume_over_latent_pages(share):
    """Two low-priority residents, then a high-priority arrival with
    both slots taken: the victim's latent pages go to the host and come
    back, and every request reads as in an uninterrupted run."""
    cfg, state = share
    jobs = [([1, 2, 3, 4, 5, 6], 12), ([3, 4, 5, 6, 7, 8], 12),
            ([5, 6, 7, 8, 9, 10], 8)]
    want = [r.result() for r in _run(engine(cfg, state), jobs)]
    eng = engine(cfg, state, max_slots=2, preempt=True)
    lo = [eng.submit(p, GenerationConfig(max_new_tokens=n))
          for p, n in jobs[:2]]
    for _ in range(6):
        eng.step()
    hi = eng.submit(jobs[2][0], GenerationConfig(max_new_tokens=8),
                    priority=1)
    eng.run_until_complete(max_steps=800)
    assert [r.result() for r in lo + [hi]] == want
    assert eng.preemptions >= 1
    assert eng.blocks.spilled_pages >= 1 and eng.blocks.restored_pages >= 1
    page = eng.runner.read_page(0)
    assert len(page) == 1 and page[0].shape == (
        cfg.num_hidden_layers, 4, M.row_width(cfg.cache_row))
    assert eng.blocks.pool_accounting()["leak"] == 0
    assert eng.decode_traces == 1


def test_prefix_cache_copies_latent_pages_on_write(share):
    """A shared prefix that ends inside a page: the second request's
    tail page is a copy (``copy_page``) and its suffix runs through the
    cached prefill over resident latent rows."""
    cfg, state = share
    a = list(range(10, 24))                 # 14 tokens: 3 pages and a half
    b = a[:14] + [50, 51, 52]
    want = [r.result() for r in _run(engine(cfg, state), [(a, 6), (b, 6)])]
    eng = engine(cfg, state, enable_prefix_cache=True)
    first = _run(eng, [(a, 6)])
    second = _run(eng, [(b, 6)])
    assert [first[0].result(), second[0].result()] == want
    stats = eng.stats()
    assert stats["cow_copies"] >= 1 and stats["cached_tokens"] >= 12
    assert stats["cached_prefill_buckets"]


def test_counters_stay_on_the_device_until_asked(share):
    cfg, state = share
    eng = engine(cfg, state)
    _run(eng, [([1, 2, 3], 5), ([4, 5, 6, 7], 5)])
    s = eng.stats()
    # 2 slots x 5 decode steps x 4 choices x 2 expert layers: 4 steps
    # make tokens 2..5, and the overrun step was dispatched before the
    # host had seen the fourth's row (both finishes are seen one late)
    assert s["decode_steps"] == 5 and s["overrun_rows"] == 2
    assert s["moe_routed_pairs"] == 2 * 5 * 4 * 2
    assert 0 < s["moe_local_pairs"] <= s["moe_routed_pairs"]
    assert 0 < s["moe_experts_live"] <= 4 * 2 * 8
    assert s["moe_local_pairs"] >= s["moe_experts_live"]


# ------------------------------------------------------ what it does not have
class _Store:
    rank, capacity = 4, 2


@pytest.mark.parametrize("option,kw", [
    ("tp", {"mesh": 2}),
    ("kv_quant", {"kv_quant": True}),
    ("lora", {"lora": _Store()}),
    ("spec_k", {"spec_k": 2}),
    ("quant", {"quant": "int8"}),
])
def test_an_option_the_family_lacks_is_refused_by_name(share, option, kw):
    cfg, state = share
    with pytest.raises(ValueError, match=option):
        engine(cfg, state, **kw)


@pytest.mark.parametrize("option,kw", [
    ("tp", {"tp": 2}),
    ("kv_quant", {"kv_quant": True}),
    ("lora_slots", {"lora_slots": 2, "lora_rank": 4}),
    ("spec_k", {"spec_k": 2}),
])
def test_the_runner_refuses_them_too(share, option, kw):
    from paddle_tpu.serving.parallel.runner import ModelRunner
    cfg, state = share
    with pytest.raises(ValueError, match=option):
        ModelRunner(cfg, state, max_slots=2, page_size=4, table_width=4,
                    num_pages=8, dump_page=8, **kw)
