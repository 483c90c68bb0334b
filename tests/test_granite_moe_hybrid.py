"""The ``granitemoehybrid`` family with routed experts (Granite 4.0-H
Small: every layer's second half is routed SwiGLU experts beside the
shared MLP), at a tiny size on the CPU: the Engine (prefill into a
slot's state and pages, then decode through them) against the plain
reference's full-forward LOGITS, with this chip holding a share of the
experts; the router against a softmax over the top k written out by
hand; the shares of two chips adding up to the uncut layer; the device
counters, the row tiles among them; the reference's state held in the
stated dtype after the prompt; and the dense description
(Granite-micro's) left as it was.

Tolerances: everything here runs in float32 with the reference at matmul
precision ``highest``; the program's products run at XLA's CPU default,
which is float32 too.  What is left is the order of summation (chunked
against sequential, paged against dense attention, the grouped experts'
sorted rows against one expert at a time): 2e-4 absolute on logits of
order 1, the bar of ``tests/test_granite_hybrid.py``.  The shares add up
to 1e-5 on outputs of order 1: the same products summed in another
order.
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
from benchmarks.reference import granite_moe_hybrid_lm as ref  # noqa: E402
from paddle_tpu.models import deepseek_v3 as ds  # noqa: E402
from paddle_tpu.models import granite_hybrid as gh  # noqa: E402
from paddle_tpu.models.generation import GenerationConfig  # noqa: E402
from paddle_tpu.serving.engine import Engine  # noqa: E402
from paddle_tpu.serving.parallel import recurrent  # noqa: E402

ATOL = 2e-4
TYPES = ("mamba", "attention", "mamba")


def toy_cfg(**kw):
    """Hidden 32; 4 query / 2 KV heads of 8; 4 Mamba heads of 16 with
    state 8, chunks of 8; a shared MLP 48 wide; 8 routed experts 16 wide,
    3 a token, of which this chip holds 4 (rank 0)."""
    base = dict(vocab_size=96, hidden_size=32, shared_intermediate_size=48,
                num_hidden_layers=3, layer_types=TYPES,
                num_attention_heads=4, num_key_value_heads=2,
                mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
                mamba_chunk_size=8, max_position_embeddings=256,
                num_local_experts=8, num_experts_per_tok=3,
                expert_intermediate_size=16, local_experts=(0, 4),
                # the layers, not the tied embedding, decide the token
                embedding_multiplier=2.0, residual_multiplier=0.6,
                logits_scaling=2.0, dtype="float32")
    base.update(kw)
    return gh.GraniteHybridConfig(**base)


def toy_state(cfg, seed=0, std=0.15):
    """Matrices normal; the Mamba vectors as the published
    implementation initialises them."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, shape in gh.weight_shapes(cfg).items():
        if k.endswith("norm.weight"):
            v = 1.0 + 0.1 * rng.normal(size=shape)
        elif k.endswith("A_log"):
            v = np.log(rng.uniform(1.0, 16.0, size=shape))
        elif k.endswith("dt_bias"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=shape))
            v = dt + np.log(-np.expm1(-dt))
        elif k.endswith(".D"):
            v = np.ones(shape)
        elif "router" in k:
            v = 0.6 * rng.normal(size=shape)    # routing that varies
        else:
            v = std * rng.normal(size=shape)
        out[k] = jnp.asarray(v, jnp.float32)
    return out


def model_dict(cfg):
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    m["layer_types"] = list(cfg.layer_types)
    return m


@pytest.fixture(scope="module")
def toy():
    cfg = toy_cfg()
    return cfg, toy_state(cfg)


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


# ------------------------------------------------------------ the program
@pytest.mark.parametrize("rank", [0, 1])
def test_prefill_then_decode_matches_the_references_logits(rank):
    """Three prompts through an Engine of 3 slots, chunked and padded
    prompts among them: every logit row the program made, prefill and
    decode, against the reference's full forward with the same share of
    the experts; greedy tokens are the reference's best."""
    cfg = toy_cfg(local_experts=(4 * rank, 4))
    state = toy_state(cfg, seed=rank)
    rng = np.random.default_rng(7 + rank)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (5, 19, 12)]
    eng = Engine(config=cfg, state=state, max_slots=3, page_size=4,
                 max_model_len=64, emit_logits=True)
    reqs, rows = served_logits(eng, prompts, 10)
    assert eng.decode_traces == 1
    m = model_dict(cfg)
    for p, r in zip(prompts, reqs):
        toks = r.result()
        assert len(toks) == 10
        want = np.asarray(ref.logits_at(
            state, m, np.concatenate([p, toks]),
            np.arange(len(p) - 1, len(p) + len(toks) - 1)))
        assert list(np.argmax(want, -1)) == list(toks)
        assert len(rows[r.id]) >= 8
        for n, row in rows[r.id].items():
            np.testing.assert_allclose(row, want[n - 1], atol=ATOL)


def test_the_decode_step_counts_pairs_experts_and_row_tiles(toy):
    """``engine.stats()`` carries the family's counters, the row tiles
    among them: a held expert a step's routing touched fills one row tile
    of 16 here (3 slots x 3 choices), so tiles = experts touched."""
    cfg, state = toy
    assert cfg.counters == ("ssm_rows_live", "moe_routed_pairs",
                            "moe_local_pairs", "moe_experts_live",
                            "moe_row_tiles_live")
    eng = Engine(config=cfg, state=state, max_slots=3, page_size=4,
                 max_model_len=64)
    rng = np.random.default_rng(3)
    for n in (6, 9, 14):
        eng.submit(rng.integers(0, 96, n).astype(np.int32),
                   GenerationConfig(max_new_tokens=12))
    while eng.step():
        pass
    s = eng.stats()
    steps = s["decode_steps"]
    assert s["ssm_rows_live"] <= steps * 3 * 2          # 2 Mamba layers
    assert s["moe_routed_pairs"] == 3 * 3 * s["ssm_rows_live"] // 2
    assert 0 < s["moe_local_pairs"] < s["moe_routed_pairs"]
    assert s["moe_row_tiles_live"] == s["moe_experts_live"] > 0
    assert eng.decode_traces == 1


def test_an_expert_past_one_row_tile_is_counted_once_a_tile(toy):
    """``routed_experts`` at a row tile of 2: an expert with 5 rows
    fills 3 tiles; the counter is the live tiles of the sorted buffer,
    and the result does not depend on the tile."""
    cfg, state = toy
    w = gh.layer_weights(state, cfg, 0)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(11, 32)),
                    jnp.float32)
    valid = jnp.arange(11) < 10
    y2, c2 = ds.routed_experts(cfg, w, x, valid, 2)
    y16, c16 = ds.routed_experts(cfg, w, x, valid, 16)
    idx, _ = ds.route(cfg, x, w["router"], None)
    local = np.asarray(idx)[:10]
    sizes = np.bincount(local[local < 4], minlength=4)
    assert list(np.asarray(c2)) == [
        30, sizes.sum(), (sizes > 0).sum(), (-(-sizes // 2)).sum()]
    assert list(np.asarray(c16)[:3]) == list(np.asarray(c2)[:3])
    assert int(c16[3]) == (sizes > 0).sum()
    np.testing.assert_allclose(y2, y16, atol=1e-5)
    assert not np.asarray(y2)[10].any()             # an invalid row


# ----------------------------------------------------------------- router
def test_the_router_is_a_softmax_over_the_top_k_logits(toy):
    """``route`` against the rule written out by hand in numpy: float32
    logits, the k largest, a softmax over those k alone, no bias, no
    scale; the gates sum to 1."""
    cfg, state = toy
    w = gh.layer_weights(state, cfg, 1)
    x = np.random.default_rng(9).normal(size=(7, 32)).astype(np.float32)
    idx, gates = ds.route(cfg, jnp.asarray(x), w["router"], None)
    logits = x.astype(np.float64) @ np.asarray(w["router"], np.float64)
    for t in range(7):
        top = np.argsort(-logits[t])[:3]
        assert set(np.asarray(idx[t])) == set(top)
        e = np.exp(logits[t, top] - logits[t, top].max())
        want = dict(zip(top, e / e.sum()))
        got = dict(zip(np.asarray(idx[t]), np.asarray(gates[t])))
        for k in top:
            assert got[k] == pytest.approx(want[k], abs=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(1), 1.0, atol=1e-6)
    # the reference's rule is the same; its planted fault is not
    r_idx, r_gates = ref.route(jnp.asarray(x), w["router"], top_k=3)
    np.testing.assert_allclose(np.sort(np.asarray(r_gates), 1),
                               np.sort(np.asarray(gates), 1), atol=1e-6)
    _, bad = ref.route(jnp.asarray(x), w["router"], top_k=3,
                       softmax_all=True)
    assert np.all(np.asarray(bad).sum(1) < 0.999)


def test_the_shares_of_two_chips_add_up_to_the_uncut_layer(toy):
    """The expert part of a layer on rank 0 (experts 0-3) and on rank 1
    (4-7), each with the shared MLP whole, against the same part with
    all 8 held: rank 0 + rank 1 - shared MLP = uncut."""
    cfg, state = toy
    whole = toy_cfg(local_experts=None)
    w = gh.layer_weights(state, whole, 0)
    stack = {k: w[k] for k in ("e_gateup", "e_down")}
    # all 8 experts: the toy's 4 held and 4 more
    rng = np.random.default_rng(11)
    stack = {k: jnp.concatenate([v, jnp.asarray(
        0.15 * rng.normal(size=v.shape), jnp.float32)]) for k, v in
        stack.items()}
    x = jnp.asarray(rng.normal(size=(13, 32)), jnp.float32)
    valid = jnp.ones((13,), bool)
    uncut, _ = gh.expert_block(whole, dict(w, **stack), x, valid, 16)
    parts = []
    for rank in (0, 1):
        c = toy_cfg(local_experts=(4 * rank, 4))
        held = {k: v[4 * rank:4 * rank + 4] for k, v in stack.items()}
        parts.append(gh.expert_block(c, dict(w, **held), x, valid, 16)[0])
    # x + rm * shared MLP(u): what each rank's block adds besides its experts
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + whole.rms_norm_eps) * w["ln2"]
    gu = u @ w["gateup"]
    shared = x + whole.residual_multiplier * (
        (jax.nn.silu(gu[:, :48]) * gu[:, 48:]) @ w["down"])
    np.testing.assert_allclose(parts[0] + parts[1] - shared, uncut,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(parts[0] - shared))) > 1e-2


# ------------------------------------------------------- the description
def test_the_dense_description_is_granite_micros_as_it_was():
    """Without routed experts the description, its blocks, its weight
    names and its counters are the ones Granite-micro has always had;
    ``intermediate_size`` given as a keyword is the shared MLP's width."""
    old = gh.GraniteHybridConfig(intermediate_size=8192)
    assert old == gh.GraniteHybridConfig() == gh.GraniteHybridConfig(
        shared_intermediate_size=8192)
    assert not hasattr(old, "intermediate_size")     # a keyword alone
    with pytest.raises(ValueError, match="given twice"):
        gh.GraniteHybridConfig(intermediate_size=8192,
                               shared_intermediate_size=8192)
    assert old.blocks[:6] == (("mamba", "mlp"),) * 5 + (("attention",
                                                           "mlp"),)
    assert old.counters == ("ssm_rows_live",)
    names = gh.weight_shapes(old)
    assert len(names) == 2 + 36 * 12 + 4 * 8
    assert names["model.layers.0.shared_mlp.input_linear.weight"] == (
        2048, 16384)
    assert not any("block_sparse_moe" in k for k in names)
    assert set(gh.layer_weights(
        {k: 0 for k in names}, old, 0)) == {
        "ln1", "ln2", "gateup", "down", "in", "conv_w", "conv_b",
        "dt_bias", "A_log", "D", "norm", "out"}
    with pytest.raises(ValueError, match="without routed experts"):
        gh.GraniteHybridConfig(local_experts=(0, 4))


def test_from_published_reads_the_published_names():
    """The micro file's keys give what the micro driver builds by hand;
    H-small's give routed experts 768 wide beside a shared MLP 1,536
    wide, every block ``(mixer, "moe")``."""
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        micro = json.load(f)
    m = micro["model"]
    by_hand = gh.GraniteHybridConfig(
        intermediate_size=m["shared_intermediate_size"],
        layer_types=tuple(m["layer_types"]),
        **{k: m[k] for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups",
            "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
            "mamba_conv_bias", "mamba_proj_bias", "embedding_multiplier",
            "attention_multiplier", "residual_multiplier", "logits_scaling",
            "position_embedding_type", "rms_norm_eps",
            "max_position_embeddings", "tie_word_embeddings")})
    assert gh.GraniteHybridConfig.from_published(m) == by_hand
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-small-ep2-l10.json")) as f:
        small = json.load(f)
    s = dict(small["model"], num_local_experts=72,
             layer_types=small["model"]["layer_types"][:10])
    cfg = gh.GraniteHybridConfig.from_published(s, local_experts=(0, 36))
    assert (cfg.shared_intermediate_size, cfg.expert_intermediate_size,
            cfg.num_experts_per_tok, cfg.local_experts) == (
        1536, 768, 10, (0, 36))
    assert cfg.blocks == tuple((k, "moe") for k in s["layer_types"])
    shapes = gh.weight_shapes(cfg)
    e = "model.layers.3.block_sparse_moe."
    assert (shapes[e + "router.layer.weight"],
            shapes[e + "input_linear.weight"],
            shapes[e + "output_linear.weight"]) == (
        (4096, 72), (36, 4096, 1536), (36, 768, 4096))
    with pytest.raises(ValueError, match="hidden_act"):
        gh.GraniteHybridConfig.from_published(dict(s, hidden_act="gelu"))


def test_the_reference_holds_its_state_in_the_stated_dtype_after_the_prompt(
        toy):
    """From ``held_from`` on the reference rounds its carried Mamba state
    to ``state_dtype`` after every token, as the published cache holds
    it, by an operation XLA keeps: the rows up to that position are the
    float32 forward's bit for bit, the later rows move, and a float32
    state rounds nothing."""
    cfg, state = toy
    m = model_dict(cfg)
    ids = np.random.default_rng(3).integers(0, 96, 24).astype(np.int32)
    rows = np.arange(24)
    f32 = np.asarray(ref.logits_at(state, m, ids, rows))
    held = np.asarray(ref.logits_at(state, m, ids, rows, held_from=10,
                                    state_dtype="bfloat16"))
    np.testing.assert_array_equal(held[:11], f32[:11])
    assert float(np.max(np.abs(held[11:] - f32[11:]))) > 1e-5
    np.testing.assert_array_equal(np.asarray(ref.logits_at(
        state, m, ids, rows, held_from=10, state_dtype="float32")), f32)


def test_the_refusals_are_the_familys(toy):
    cfg, state = toy
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        Engine(config=cfg, state=state, max_slots=2, page_size=4,
               max_model_len=64, enable_prefix_cache=True)
    assert recurrent.counters_by_name(
        cfg, jnp.arange(5, dtype=jnp.int32)) == dict(zip(cfg.counters,
                                                         range(5)))
