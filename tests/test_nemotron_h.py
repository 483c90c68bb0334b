"""The ``nemotron_h`` family on the serving path, at a tiny size on the
CPU: blocks of ONE part each (Mamba-2 in groups, GQA attention, routed
``relu^2`` experts of which a share is held) through the recurrent
family's programs, against the plain reference's full-forward LOGITS;
the shares of the experts adding up to the uncut layer; the grouped
state-update kernel against its XLA twin; a slot's reuse; the census.

The toy: hidden 32; pattern ``MEM*EME`` (all three kinds); 4 Mamba heads
of 16 in 2 groups of B/C with state 8; 4 query / 2 KV heads of 16; 8
routed experts of width 24 (no multiple of 128: stored 128 wide, zeros
past 24), 3 a token, of which this chip holds 4 (experts 2-5); shared
expert 40.

Tolerances: everything here runs in float32 with the reference at matmul
precision ``highest``; the program's products run at XLA's CPU default,
which is float32 too.  What is left is the order of summation (chunked
against sequential, paged against dense attention, sorted rows against
a loop over experts): 2e-4 absolute on logits of order 1, the bar of
``tests/test_granite_hybrid.py``; 5e-6 was measured.  A bfloat16 run of
the same toy misses it by two orders (``test_a_bfloat16_run_fails_the_
float32_bar``).
"""
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
from benchmarks.reference import nemotron_h_lm as ref  # noqa: E402
from paddle_tpu.models import deepseek_v3 as ds  # noqa: E402
from paddle_tpu.models import granite_hybrid as gh  # noqa: E402
from paddle_tpu.models import nemotron_h as nh  # noqa: E402
from paddle_tpu.models.generation import GenerationConfig  # noqa: E402
from paddle_tpu.ops.pallas import grouped_ffn as GF  # noqa: E402
from paddle_tpu.ops.pallas import ssm_update as U  # noqa: E402
from paddle_tpu.serving.engine import Engine  # noqa: E402
from paddle_tpu.serving.parallel import recurrent  # noqa: E402

ATOL = 2e-4
PATTERN = "MEM*EME"


def toy_cfg(**kw):
    base = dict(vocab_size=96, hidden_size=32,
                hybrid_override_pattern=PATTERN, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, mamba_n_heads=4,
                mamba_d_head=16, mamba_d_state=8, mamba_n_groups=2,
                mamba_chunk_size=8, moe_intermediate_size=24,
                moe_shared_expert_intermediate_size=40, n_routed_experts=8,
                num_experts_per_tok=3, local_experts=(2, 4),
                max_position_embeddings=256, dtype="float32")
    base.update(kw)
    return nh.NemotronHConfig(**base)


def toy_state(cfg, seed=0, std=0.15):
    """Matrices normal; the Mamba vectors as the published
    implementation initialises them; a router bias that moves choices."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, shape in nh.weight_shapes(cfg).items():
        if k.endswith(("norm.weight", "norm_f.weight")):
            v = 1.0 + 0.1 * rng.normal(size=shape)
        elif k.endswith("A_log"):
            v = np.log(rng.uniform(1.0, 16.0, size=shape))
        elif k.endswith("dt_bias"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=shape))
            v = dt + np.log(-np.expm1(-dt))
        elif k.endswith(".D"):
            v = np.ones(shape)
        elif k.endswith("e_score_correction_bias"):
            v = 0.05 * rng.normal(size=shape)
        else:
            v = std * rng.normal(size=shape)
        if ".experts." in k:        # zeros past the published width
            fm = cfg.moe_intermediate_size
            v[(..., slice(fm, None)) if "up_proj" in k
              else (slice(None), slice(fm, None))] = 0.0
        out[k] = jnp.asarray(v, jnp.float32)
    return out


def model_dict(cfg, **kw):
    """The description under the published keys the reference reads."""
    m = {"layer_norm_epsilon": cfg.rms_norm_eps,
         "mamba_num_heads": cfg.mamba_n_heads, "n_groups": cfg.mamba_n_groups,
         "ssm_state_size": cfg.mamba_d_state,
         "local_experts": list(cfg.local_experts)}
    m.update({k: getattr(cfg, k) for k in (
        "hybrid_override_pattern", "num_attention_heads",
        "num_key_value_heads", "n_routed_experts", "n_group", "topk_group",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")})
    m.update(kw)
    return m


def engine(cfg, state, **kw):
    base = dict(max_slots=3, page_size=4, max_model_len=64)
    base.update(kw)
    return Engine(config=cfg, state=state, **base)


@pytest.fixture(scope="module")
def toy():
    cfg = toy_cfg()
    return cfg, toy_state(cfg)


def some_prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in lengths]


def served_logits(eng, prompts, n_new):
    """Every request of ``prompts`` through ``eng``: (requests, {id:
    {n: the logit row that made its n-th token}})."""
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=n_new))
            for p in prompts]
    rows = {r.id: {} for r in reqs}
    while eng.step():
        logits = eng._last_logits
        if logits is None:
            continue
        logits = np.asarray(logits)
        for slot, r in enumerate(eng.scheduler.slots):
            # the host runs one step behind: the step just dispatched
            # makes the token after those the request has been handed
            if r is not None and r.id in rows:
                rows[r.id].setdefault(r.num_generated + 1, logits[slot])
    return reqs, rows


def worst_gap(cfg, state, prompts, n_new=10, ref_state=None, **kw):
    """The widest |served logit - reference logit| over every decode
    row of every request (prefill then decode through the cache, against
    one full forward), and whether every served token was the
    reference's best."""
    eng = engine(cfg, state, emit_logits=True, **kw)
    reqs, rows = served_logits(eng, prompts, n_new)
    assert eng.decode_traces == 1
    m = model_dict(cfg)
    worst, greedy = 0.0, True
    for p, r in zip(prompts, reqs):
        toks = r.result()
        assert len(toks) == n_new and len(rows[r.id]) >= n_new - 2
        want = np.asarray(ref.logits_at(
            ref_state or state, m, np.concatenate([p, toks]),
            np.arange(len(p) - 1, len(p) + len(toks) - 1)))
        greedy &= list(np.argmax(want, -1)) == list(toks)
        worst = max([worst] + [float(np.abs(row - want[n - 1]).max())
                               for n, row in rows[r.id].items()])
    return worst, greedy, eng


# ------------------------------------------- the engine = the reference
def test_prefill_then_decode_logits_match_the_reference(toy):
    """Prompts shorter than a chunk, longer than a bucket's first chunk,
    more than the slots (one waits and takes a used slot): logits, not
    tokens, at every decode row."""
    cfg, state = toy
    worst, greedy, eng = worst_gap(cfg, state,
                                   some_prompts(1, (5, 11, 18, 3, 9)))
    assert greedy and worst < ATOL
    s = eng.stats()
    # 3 Mamba blocks and 3 expert blocks counted every live row
    assert s["ssm_rows_live"] == s["moe_routed_pairs"] // 3 > 0
    assert 0 < s["moe_local_pairs"] < s["moe_routed_pairs"]
    assert 0 < s["moe_experts_live"] <= eng.decode_steps * 3 * 4


def test_a_bfloat16_run_fails_the_float32_bar(toy):
    """The bar is tight enough to tell a precision: the same toy served
    in bfloat16 (weights rounded, activations bfloat16, the state pools
    bfloat16) against the float32 reference of the float32 weights."""
    cfg, state = toy
    half = {k: v.astype(jnp.bfloat16) for k, v in state.items()}
    worst, _, eng = worst_gap(toy_cfg(dtype="bfloat16"), half,
                              some_prompts(1, (5, 11, 18)), ref_state=state)
    assert eng.runner._rstate[0].dtype == jnp.bfloat16
    assert worst > 50 * ATOL


@pytest.mark.parametrize("change", [
    dict(hybrid_override_pattern="M*E"),
    dict(hybrid_override_pattern="EEMM**M"),
    dict(mamba_n_groups=4), dict(mamba_n_groups=1),
    dict(local_experts=(0, 8)), dict(local_experts=(7, 1)),
    dict(n_group=2, topk_group=1), dict(norm_topk_prob=False),
    dict(routed_scaling_factor=1.0)],
    ids=lambda c: ",".join(f"{k}={v}" for k, v in c.items()))
def test_the_description_is_followed_as_the_reference_follows_it(change):
    cfg = toy_cfg(**change)
    worst, greedy, _ = worst_gap(cfg, toy_state(cfg, seed=3),
                                 some_prompts(2, (7, 13)), n_new=6)
    assert greedy and worst < ATOL


@pytest.mark.parametrize("control", ["one_group", "int8"])
def test_the_reference_controls_read_far_from_the_program(toy, control):
    """What the benchmark's two controls plant is visible at this size:
    group 0's B and C for every head, and int8 projections, each move
    the reference's logits by orders more than the program differs."""
    cfg, state = toy
    p = some_prompts(4, (20,))[0]
    rows = np.arange(4, 19)
    m = model_dict(cfg)
    want = np.asarray(ref.logits_at(state, m, p, rows))
    got = np.asarray(ref.logits_at(state, m, p, rows, **{control: True}))
    assert np.abs(got - want).max() > 100 * ATOL


# ------------------------------------------------------ the shares add up
def test_all_ranks_routed_parts_and_the_shared_expert_once_are_the_layer(toy):
    """One expert block, cut 4 ways over one uncut stack of 8 experts:
    every rank computes the shared expert whole and its own share of the
    routed sum.  The four shares and the shared expert ONCE are the
    uncut block, which is the reference's loop over all 8 experts."""
    cfg, state = toy
    li = PATTERN.index("E")
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(10, 32)), jnp.float32)
    ups = jnp.asarray(0.15 * rng.normal(size=(8, 32, 24)), jnp.float32)
    downs = jnp.asarray(0.15 * rng.normal(size=(8, 24, 32)), jnp.float32)
    shared_w = nh.layer_weights(state, cfg, li)

    def block(first, count):
        w = dict(shared_w, e_up=ups[first:first + count],
                 e_down=downs[first:first + count])
        return nh.expert_block(toy_cfg(local_experts=(first, count)), w, x,
                               jnp.ones((10,), bool), ds.DECODE_TILE)

    whole, counts = block(0, 8)
    assert int(counts[1]) == int(counts[0]) == 30       # every pair local
    ranks = [block(2 * r, 2) for r in range(4)]
    assert sum(int(c[1]) for _, c in ranks) == 30       # each pair once
    # x + the shared expert, once, and the routing: the reference's
    p = f"backbone.layers.{li}."
    f = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
    hn, once, idx, wt = ref.expert_block_shared(
        x, f, eps=cfg.rms_norm_eps, groups=1, keep_groups=1, top_k=3,
        norm=True, factor=2.5, int8=False)
    total = once + sum(y - once for y, _ in ranks)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    want = once
    for e in range(8):
        want = ref.add_expert(want, hn, idx, wt, e, ups[e], downs[e],
                              int8=False)
    np.testing.assert_allclose(whole, want, atol=2e-5)


def test_relu2_two_matrix_experts_against_a_dense_loop():
    """``routed_experts`` with a description that says ``relu2``: two
    matrices an expert, a width (24) that is no multiple of a tile, rows
    that choose nothing, against a loop over tokens in numpy."""
    cfg = toy_cfg(local_experts=(0, 8))
    rng = np.random.default_rng(8)
    x = rng.normal(size=(9, 32)).astype(np.float32)
    w = {"router": rng.normal(size=(32, 8)).astype(np.float32),
         "router_bias": (0.1 * rng.normal(size=(8,))).astype(np.float32),
         "e_up": (0.2 * rng.normal(size=(8, 32, 24))).astype(np.float32),
         "e_down": (0.2 * rng.normal(size=(8, 24, 32))).astype(np.float32)}
    valid = np.array([1, 1, 0, 1, 1, 1, 0, 1, 1], bool)
    y, counts = ds.routed_experts(cfg, {k: jnp.asarray(v) for k, v
                                        in w.items()}, jnp.asarray(x),
                                  jnp.asarray(valid), ds.DECODE_TILE)
    idx, wts = ds.route(cfg, jnp.asarray(x), jnp.asarray(w["router"]),
                        jnp.asarray(w["router_bias"]))
    want = np.zeros((9, 32), np.float64)
    for t in np.flatnonzero(valid):
        for e, g in zip(np.asarray(idx)[t], np.asarray(wts)[t]):
            u = np.maximum(x[t].astype(np.float64) @ w["e_up"][e], 0.0)
            want[t] += g * ((u * u) @ w["e_down"][e])
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert list(np.asarray(counts)[:2]) == [21, 21]
    assert not np.asarray(y)[~valid].any()


def test_padding_the_held_experts_to_lane_tiles_changes_nothing(toy):
    """``pad_experts``: zeros up to ``expert_width``, once, at load; the
    expert block over the padded stacks is the block over the published
    ones, and the description's shapes are the padded ones."""
    cfg, state = toy
    li = PATTERN.index("E")
    assert (cfg.moe_intermediate_size, cfg.expert_width) == (24, 128)
    assert nh.NemotronHConfig().expert_width == 1920    # 1,856 -> 15 tiles
    w = nh.layer_weights(state, cfg, li)
    assert w["e_up"].shape == (4, 32, 128) and w["e_down"].shape == (
        4, 128, 32)
    up, down = w["e_up"][:, :, :24], w["e_down"][:, :24]
    again = nh.pad_experts(cfg, up, down)
    np.testing.assert_array_equal(again[0], w["e_up"])
    np.testing.assert_array_equal(again[1], w["e_down"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(7, 32)),
                    jnp.float32)
    valid = jnp.ones((7,), bool)
    padded, _ = nh.expert_block(cfg, w, x, valid, ds.DECODE_TILE)
    plain, _ = nh.expert_block(cfg, dict(w, e_up=up, e_down=down), x, valid,
                               ds.DECODE_TILE)
    np.testing.assert_allclose(padded, plain, atol=1e-6)


def test_a_gated_description_still_takes_three_matrices():
    """The same function under a description that says nothing of the
    activation is DeepSeek-V3's gated expert, as before."""
    assert ds._gated(ds.DeepseekV3Config()) and not ds._gated(toy_cfg())
    with pytest.raises(ValueError, match="mlp_hidden_act='gelu'"):
        ds._gated(type("C", (), {"mlp_hidden_act": "gelu"})())


# ---------------------------------------------------- the kernel, its twin
@pytest.mark.parametrize("lanes", [64, 256], ids=["block-in-group",
                                                  "groups-in-block"])
@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("active", [
    [1, 1, 1, 1, 1], [0, 1, 0, 0, 1], [0, 0, 1, 1, 0], [0, 0, 0, 0, 0]],
    ids=["all-live", "parked-first", "parked-last", "none-live"])
def test_grouped_state_update_kernel_is_its_xla_twin(monkeypatch, active,
                                                     groups, lanes):
    """``ssm_state_update`` under the Pallas interpreter against
    ``ssm_state_update_xla`` with B and C in ``G`` groups: 512 lanes in
    blocks of 64 (a block inside one group, or for G = 8 one group
    whole) and of 256 (2 or 4 groups sliced out of a block; G = 1: two
    blocks of one group); live slots' states and outputs, parked slots'
    states untouched, the other layers' too."""
    monkeypatch.setattr(U, "_INTERPRET", True)
    monkeypatch.setattr(U, "LANE_BLOCK", lanes)
    rng = np.random.default_rng(sum(active) + groups)
    slots, n, hp = 5, 16, 512
    pool = jnp.asarray(rng.normal(size=(3, slots, n, hp)), jnp.bfloat16)
    decay = jnp.asarray(rng.uniform(0.5, 1.0, (slots, hp)), jnp.float32)
    dtx, b, c = (jnp.asarray(rng.normal(size=s), jnp.float32)
                 for s in ((slots, hp), (slots, groups, n),
                           (slots, groups, n)))
    act = jnp.asarray(active, jnp.int32)
    got_pool, got_y = jax.jit(
        lambda *a: U.ssm_state_update(a[0], 1, *a[1:]))(
        pool, decay, dtx, b, c, act)
    want_pool, want_y = U.ssm_state_update_xla(pool, 1, decay, dtx, b, c,
                                               act)
    live = np.asarray(active, bool)
    f32 = jnp.float32
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)
    np.testing.assert_allclose(
        got_pool.astype(f32)[1][live], want_pool.astype(f32)[1][live],
        atol=1e-6, rtol=2.0 ** -7)
    np.testing.assert_array_equal(got_pool[1][~live], pool[1][~live])
    np.testing.assert_array_equal(got_pool[0], pool[0])
    np.testing.assert_array_equal(got_pool[2], pool[2])
    assert not np.asarray(got_y)[~live].any()
    # and each lane saw its own group's column: against plain numpy
    s = np.asarray(pool.astype(f32))[1]
    wide = [np.repeat(np.asarray(v).transpose(0, 2, 1), hp // groups, 2)
            for v in (b, c)]
    new = s * np.asarray(decay)[:, None] + wide[0] * np.asarray(dtx)[:, None]
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               (new * wide[1]).sum(1)[live], atol=1e-4)


def test_lanes_that_do_not_divide_into_groups_are_refused():
    pool = jnp.zeros((1, 2, 8, 96), jnp.float32)
    row, col = jnp.zeros((2, 96)), jnp.zeros((2, 4, 8))
    with pytest.raises(ValueError, match="96 lanes in 4 groups"):
        U.LANE_BLOCK, was = 32, U.LANE_BLOCK
        try:
            U.ssm_state_update(pool, 0, row, row, col, col,
                               jnp.ones((2,), jnp.int32))
        finally:
            U.LANE_BLOCK = was


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688), (32, 24)])
def test_expert_blocks_at_widths_that_are_no_whole_lane_tiles(k, n):
    """1856 = 14.5 x 128: that dimension is taken whole, the other in
    lane tiles that divide it, within ``BLOCK_BYTES``."""
    tk, tn = GF.expert_blocks(k, n, 2)
    assert k % tk == 0 and n % tn == 0
    assert tk * tn * 2 <= GF.BLOCK_BYTES
    for t, d in ((tk, k), (tn, n)):
        assert t == d or t % 128 == 0
    if (k, n) == (2688, 1856):
        assert (tk, tn) == (896, 1856)
    if (k, n) == (1856, 2688):
        assert (tk, tn) == (1856, 896)


@pytest.mark.parametrize("k,n", [(32, 24), (24, 32)])
def test_grouped_matmul_is_its_xla_twin_at_the_toys_widths(monkeypatch,
                                                           k, n):
    monkeypatch.setattr(GF, "_INTERPRET", True)
    rng = np.random.default_rng(k)
    x = jnp.asarray(rng.normal(size=(48, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, k, n)), jnp.float32)
    emap = jnp.asarray([0, 2, 3], jnp.int32)
    got = GF.grouped_matmul(x, w, emap, 2, tile_m=16)
    want = GF.grouped_matmul_xla(x, w, emap, 2, tile_m=16)
    np.testing.assert_allclose(got[:32], want[:32], atol=1e-5)


# ------------------------------------------------------ a slot's next user
def fresh_tokens(cfg, state, prompt, n):
    eng = engine(cfg, state, max_slots=1)
    r = eng.submit(prompt, GenerationConfig(max_new_tokens=n))
    eng.run_until_complete()
    return r.result()


@pytest.mark.parametrize("how", ["finished", "cancelled", "in-flight"])
def test_a_reused_slot_starts_from_its_own_prefill(toy, how):
    """One slot, two requests: the second gets the logits a fresh engine
    gives it, whether the first ran to its end, was cancelled in the
    middle of its decode, or had a step in flight that updated the
    slot's state after its last token."""
    cfg, state = toy
    first, second = some_prompts(7, (14, 6))
    eng = engine(cfg, state, max_slots=1, emit_logits=True)
    r1 = eng.submit(first, GenerationConfig(max_new_tokens=12))
    if how == "cancelled":
        for _ in range(5):
            eng.step()
        assert 0 < r1.num_generated < 12
        r1.cancel()
    elif how == "finished":
        eng.run_until_complete()
        assert r1.is_finished() and eng._flight is None
    reqs, rows = served_logits(eng, [second], 9)
    toks = reqs[0].result()
    assert list(toks) == list(fresh_tokens(cfg, state, second, 9))
    want = np.asarray(ref.logits_at(
        state, model_dict(cfg), np.concatenate([second, toks]),
        np.arange(len(second) - 1, len(second) + 8)))
    for n, row in rows[reqs[0].id].items():
        np.testing.assert_allclose(row, want[n - 1], atol=ATOL)


def test_recover_replays_prompt_and_tokens_into_the_slots(toy):
    cfg, state = toy
    prompts = some_prompts(9, (5, 12, 8))
    want = [fresh_tokens(cfg, state, p, 14) for p in prompts]
    eng = engine(cfg, state)
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=14))
            for p in prompts]
    for _ in range(6):
        eng.step()
    assert all(0 < r.num_generated < 14 for r in reqs)
    assert eng.recover()["replayed"] == 3
    eng.run_until_complete()
    assert [list(r.result()) for r in reqs] == [list(w) for w in want]
    assert eng.blocks.pool_accounting()["leak"] == 0


# ----------------------------------------------------------- the refusals
@pytest.mark.parametrize("option,kw", [
    ("mesh", dict(mesh=2)), ("kv_quant", dict(kv_quant=True)),
    ("quant", dict(quant="int8")), ("quant", dict(quant="int4")),
    ("spec_k", dict(spec_k=2)),
    ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ("preempt", dict(preempt=True)),
    ("prefill_chunk", dict(prefill_chunk=8)), ("lora", None)])
def test_what_the_family_lacks_is_refused_by_name(toy, option, kw):
    cfg, state = toy
    if kw is None:
        from paddle_tpu.serving.lora.store import AdapterStore
        kw = dict(lora=AdapterStore(cfg, rank=2))
    with pytest.raises(ValueError, match=rf"^{option} is not supported "
                                         "for the nemotron_h family"):
        engine(cfg, state, **kw)


@pytest.mark.parametrize("kw,match", [
    (dict(hybrid_override_pattern="M-E"), "'-' MLP block"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act='silu'"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
    (dict(local_experts=(6, 4)), "local_experts"),
    (dict(mamba_n_groups=3), "mamba_n_groups")])
def test_what_the_description_cannot_say_is_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        toy_cfg(**kw)


PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "routed_scaling_factor": 2.5,
    "ssm_state_size": 128, "tie_word_embeddings": False, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "vocab_size": 131072}


def test_the_published_config_is_read_into_the_shared_bodies_names():
    cfg = nh.NemotronHConfig.from_published(PUBLISHED,
                                            local_experts=(0, 16))
    assert cfg == nh.NemotronHConfig(local_experts=(0, 16))
    assert (cfg.d_inner, cfg.conv_dim) == (4096, 4096 + 2 * 8 * 128)
    assert (len(cfg.mamba_layers), len(cfg.attention_layers),
            len(cfg.layers_of("moe"))) == (23, 6, 23)
    assert cfg.attention_layers == (5, 12, 19, 26, 33, 42)
    assert cfg.blocks[:6] == (("mamba",), ("moe",), ("mamba",), ("moe",),
                              ("mamba",), ("attention",))
    shapes = nh.weight_shapes(cfg)
    assert shapes["backbone.layers.0.mixer.in_proj.weight"] == (2688, 10304)
    assert shapes["backbone.layers.1.mixer.experts.up_proj.weight"] == (
        16, 2688, 1920)
    assert shapes["backbone.layers.1.mixer.experts.down_proj.weight"] == (
        16, 1920, 2688)
    assert shapes["backbone.layers.1.mixer.shared_experts.up_proj.weight"
                  ] == (2688, 3712)
    assert shapes["backbone.layers.1.mixer.gate.weight"] == (2688, 128)
    assert gh.kv_pack(cfg) == 1
    with pytest.raises(ValueError, match="tie_word_embeddings=True"):
        nh.NemotronHConfig.from_published(
            dict(PUBLISHED, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="num_hidden_layers blocks"):
        nh.NemotronHConfig.from_published(
            dict(PUBLISHED, num_hidden_layers=26))


def test_granite_runs_more_than_one_group_now():
    """What ``test_more_than_one_group_is_refused_by_name`` held is gone
    with the refusal: the Granite description takes groups too, and its
    chunked prefill in 2 groups is the grouped recurrence (the decode
    kernel's twin, token by token)."""
    cfg = gh.GraniteHybridConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, layer_types=("mamba",), num_attention_heads=4,
        num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=8, mamba_n_groups=2, mamba_chunk_size=8,
        dtype="float32")
    rng = np.random.default_rng(0)
    shapes = gh.weight_shapes(cfg)
    assert shapes["model.layers.0.mamba.in_proj.weight"] == (
        32, 64 + 64 + 2 * 2 * 8 + 4)
    state = {k: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)
             for k, s in shapes.items()}
    w = gh.layer_weights(state, cfg, 0)
    h = jnp.asarray(rng.normal(size=(19, 32)), jnp.float32)
    out, s_end, tail = gh.mamba_prefill(cfg, w, jnp.pad(h, ((0, 5), (0, 0))),
                                        jnp.int32(19))
    ssm, conv = (jnp.zeros(s, d) for s, d in
                 gh.state_shapes(cfg, 1).values())
    for t in range(19):
        got, ssm, conv = gh.mamba_decode(cfg, w, h[t:t + 1], ssm, conv, 0,
                                         jnp.ones((1,), jnp.int32))
        np.testing.assert_allclose(got[0], out[t], atol=ATOL)
    np.testing.assert_allclose(ssm[0, 0], s_end, atol=ATOL)
    np.testing.assert_allclose(conv[0, 0], tail, atol=1e-6)


# ------------------------------------------------------------- the census
def test_the_census_counts_state_by_slot_and_pages_of_attention_blocks(toy):
    cfg, state = toy
    eng = engine(cfg, state)
    pages = eng.blocks.num_pages + 1
    # one attention block of seven: k + v, 2 KV heads of 16, float32
    kv = 2 * 1 * pages * 2 * 4 * 16 * 4
    # three Mamba blocks: state 8 x 64, conv tail 3 x (64 + 2 * 2 * 8)
    per_slot = 3 * (8 * 64 * 4 + 3 * (64 + 32) * 4)
    dev = eng.resource_snapshot()["mesh"]["devices"][0]
    assert dev["kv_pool_bytes"] == kv
    assert dev["recurrent_state_bytes"] == 3 * per_slot
    assert eng._page_bytes() == 2 * 1 * 2 * 4 * 16 * 4
    assert eng.stats()["recurrent_state_bytes"] == 3 * per_slot
    assert eng.runner.kpool.shape == (1, pages, 1, 4, 32)   # heads paired
    assert eng.runner._rstate[0].shape == (3, 3, 8, 64)
    assert recurrent.counters_by_name(cfg, eng.runner._counters_dev) == {
        "ssm_rows_live": 0, "moe_routed_pairs": 0, "moe_local_pairs": 0,
        "moe_experts_live": 0}
    assert set(eng.runner.device_counters()) == set(nh.COUNTERS)
