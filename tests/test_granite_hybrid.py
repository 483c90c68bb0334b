"""The ``granitemoehybrid`` family on the serving path, at a tiny size on
the CPU: the chunked Mamba-2 prefill against the sequential recurrence,
the Engine (prefill into a slot's state, then decode through it) against
the plain reference's full-forward LOGITS, the multipliers, a slot's
reuse with and without a step in flight, ``recover()``, the options the
family refuses, the state-update kernel against its XLA twin.

Tolerances: everything here runs in float32 with the reference at matmul
precision ``highest``; the program's products run at XLA's CPU default,
which is float32 too.  What is left is the order of summation (chunked
against sequential, paged against dense attention): 2e-4 absolute on
logits of order 1, the bar of ``tests/test_deepseek_v3.py``.
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
from benchmarks.reference import granite_hybrid_lm as ref  # noqa: E402
from paddle_tpu.models import granite_hybrid as gh  # noqa: E402
from paddle_tpu.models.generation import GenerationConfig  # noqa: E402
from paddle_tpu.ops.pallas import ssm_update as U  # noqa: E402
from paddle_tpu.serving.engine import Engine  # noqa: E402
from paddle_tpu.serving.parallel import recurrent  # noqa: E402

ATOL = 2e-4
TYPES = ("mamba", "attention", "mamba", "mamba")


def toy_cfg(**kw):
    """Hidden 32; 4 query / 2 KV heads of 8 (two KV heads share a pool
    row); 4 Mamba heads of 16 with state 8, chunks of 8 tokens."""
    base = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                num_hidden_layers=4, layer_types=TYPES,
                num_attention_heads=4, num_key_value_heads=2,
                mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
                mamba_chunk_size=8, max_position_embeddings=256,
                # the layers, not the tied embedding, decide the token
                embedding_multiplier=2.0, residual_multiplier=0.6,
                logits_scaling=2.0, dtype="float32")
    base.update(kw)
    if "layer_types" in kw:
        base["num_hidden_layers"] = len(kw["layer_types"])
    return gh.GraniteHybridConfig(**base)


def toy_state(cfg, seed=0, std=0.15):
    """Matrices normal; the Mamba vectors as the published
    implementation initialises them (slow and fast heads both)."""
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
        else:
            v = std * rng.normal(size=shape)
        out[k] = jnp.asarray(v, jnp.float32)
    return out


def model_dict(cfg):
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    m["layer_types"] = list(cfg.layer_types)
    return m


def engine(cfg, state, cls=Engine, **kw):
    base = dict(max_slots=3, page_size=4, max_model_len=64)
    base.update(kw)
    return cls(config=cfg, state=state, **base)


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


def assert_matches_reference(cfg, state, prompts, n_new=10, **kw):
    eng = engine(cfg, state, emit_logits=True, **kw)
    reqs, rows = served_logits(eng, prompts, n_new)
    assert eng.decode_traces == 1
    m = model_dict(cfg)
    for p, r in zip(prompts, reqs):
        toks = r.result()
        assert len(toks) == n_new
        want = np.asarray(ref.logits_at(
            state, m, np.concatenate([p, toks]),
            np.arange(len(p) - 1, len(p) + len(toks) - 1)))
        # greedy: every served token is the reference's best
        assert list(np.argmax(want, -1)) == list(toks)
        assert len(rows[r.id]) >= n_new - 2
        for n, row in rows[r.id].items():
            np.testing.assert_allclose(row, want[n - 1], atol=ATOL)
    return eng


def some_prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in lengths]


# ------------------------------------- the chunked scan = the recurrence
def sequential(cfg, w, h):
    """The Mamba mixer over h [S, hidden], one token at a time, in
    float64 numpy: (out, state [H, P, N], the conv's last inputs)."""
    f = {k: (None if v is None else np.asarray(v, np.float64))
         for k, v in w.items()}
    h = np.asarray(h, np.float64)
    s, nh, p, n = (h.shape[0], cfg.mamba_n_heads, cfg.mamba_d_head,
                   cfg.mamba_d_state)
    d, k = cfg.d_inner, cfg.mamba_d_conv
    zxbcdt = h @ f["in"]
    z, xbc, dt = (zxbcdt[:, :d], zxbcdt[:, d:d + cfg.conv_dim],
                  zxbcdt[:, d + cfg.conv_dim:])
    padded = np.concatenate([np.zeros((k - 1, cfg.conv_dim)), xbc])
    conv = f["conv_b"] + sum(padded[j:j + s] * f["conv_w"][:, j]
                             for j in range(k))
    act = conv / (1.0 + np.exp(-conv))
    dt = np.log1p(np.exp(dt + f["dt_bias"]))
    a = -np.exp(f["A_log"])
    state = np.zeros((nh, p, n))
    y = np.zeros((s, nh, p))
    for t in range(s):
        x_t = act[t, :d].reshape(nh, p)
        b_t, c_t = act[t, d:d + n], act[t, d + n:]
        state = (np.exp(dt[t] * a)[:, None, None] * state
                 + (dt[t][:, None] * x_t)[:, :, None] * b_t)
        y[t] = state @ c_t + f["D"][:, None] * x_t
    g = y.reshape(s, d) * (z / (1.0 + np.exp(-z)))
    g = g / np.sqrt((g * g).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    return (g * f["norm"]) @ f["out"], state, padded[s:s + k - 1]


@pytest.mark.parametrize("length,bucket", [
    (1, 4), (5, 8), (8, 8), (19, 24), (16, 16), (21, 32)],
    ids=["one-token", "below-the-chunk", "at-the-chunk", "across-chunks",
         "two-whole-chunks", "padded-past-a-chunk"])
def test_chunked_prefill_is_the_sequential_recurrence(toy, length, bucket):
    """``mamba_prefill`` (chunks of 8) against the token-by-token
    recurrence: the output rows, the state after the last real token
    and the convolution's tail, with right padding in the bucket."""
    cfg, state = toy
    w = gh.layer_weights(state, cfg, 0)
    rng = np.random.default_rng(length)
    h = jnp.asarray(rng.normal(size=(bucket, 32)), jnp.float32)
    out, s_end, tail = jax.jit(
        lambda h, n: gh.mamba_prefill(cfg, w, h, n))(
        h, jnp.asarray(length, jnp.int32))
    want, s_want, tail_want = sequential(cfg, w, h[:length])
    np.testing.assert_allclose(out[:length], want, atol=2e-5)
    # the pool's layout: [N, H * P]
    np.testing.assert_allclose(
        s_end, s_want.transpose(2, 0, 1).reshape(8, 64), atol=2e-5)
    np.testing.assert_allclose(tail, tail_want.reshape(-1), atol=1e-6)


def test_decode_continues_the_prefills_state(toy):
    """Prefill 9 tokens, then ``mamba_decode`` 6 more one at a time: the
    state and every output equal the recurrence over all 15."""
    cfg, state = toy
    w = gh.layer_weights(state, cfg, 2)
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(15, 32)), jnp.float32)
    want, s_want, tail_want = sequential(cfg, w, h)
    _, s_end, tail = gh.mamba_prefill(cfg, w, h[:12], jnp.int32(9))
    ssm, conv = recurrent.state_pools(cfg, 2)
    lm = cfg.ordinal(2)
    ssm = ssm.at[lm, 1].set(s_end)
    conv = conv.at[lm, 1].set(tail)
    active = jnp.asarray([0, 1], jnp.int32)
    for t in range(9, 15):
        rows = jnp.stack([h[0], h[t]])              # slot 0 is parked
        out, ssm, conv = gh.mamba_decode(cfg, w, rows, ssm, conv, lm,
                                         active)
        np.testing.assert_allclose(out[1], want[t], atol=2e-5)
    np.testing.assert_allclose(
        ssm[lm, 1], s_want.transpose(2, 0, 1).reshape(8, 64), atol=2e-5)
    np.testing.assert_allclose(conv[lm, 1], tail_want.reshape(-1),
                               atol=1e-6)
    assert not np.asarray(ssm[lm, 0]).any()         # parked: untouched


# ----------------------------------------------- engine against reference
def test_prefill_then_decode_logits_match_the_reference(toy):
    """Every logit row the Engine produced (the prefill's last position
    into the slot's state, then one row a decode step through it)
    against one full forward of the reference over prompt + tokens."""
    cfg, state = toy
    eng = assert_matches_reference(cfg, state,
                                   some_prompts(1, (5, 11, 18, 3, 9)))
    s = eng.stats()
    # two waves through three slots; a Mamba layer counts a live row
    assert s["ssm_rows_live"] > 0 and s["ssm_rows_live"] % 3 == 0
    assert s["ssm_rows_live"] <= s["decode_steps"] * 3 * 3
    assert s["recurrent_state_bytes"] == recurrent.state_bytes(cfg, 3)
    assert s["prefill_buckets"] == [4, 8, 12, 20]


@pytest.mark.parametrize("change", [
    dict(embedding_multiplier=3.0), dict(attention_multiplier=0.3),
    dict(residual_multiplier=0.7), dict(logits_scaling=2.5),
    dict(layer_types=("attention", "mamba", "attention")),
    dict(mamba_conv_bias=False),
    dict(num_key_value_heads=4, num_attention_heads=4)],
    ids=["embedding_multiplier", "attention_multiplier",
         "residual_multiplier", "logits_scaling", "attention-first",
         "no-conv-bias", "four-kv-heads"])
def test_each_multiplier_and_the_layer_pattern_follow_the_reference(change):
    """Each of the four multipliers off its default, attention (which
    has no position term in either) first and last, the convolution
    without its bias, and another head grouping: still the reference's
    logits."""
    cfg = toy_cfg(**change)
    assert_matches_reference(cfg, toy_state(cfg, seed=2),
                             some_prompts(4, (7, 13)), n_new=8)


def test_the_tied_head_is_one_array(toy):
    cfg, state = toy
    shapes = gh.weight_shapes(cfg)
    assert "lm_head.weight" not in shapes
    assert [k for k, s in shapes.items() if s == (96, 32)] == [gh.EMBED]
    # the served logits are the embedding's: scale it and they scale
    eng = engine(cfg, state, emit_logits=True, max_slots=1)
    _, rows = served_logits(eng, some_prompts(5, (6,)), 3)
    flipped = dict(state, **{gh.EMBED: -state[gh.EMBED]})
    eng2 = engine(cfg, flipped, emit_logits=True, max_slots=1)
    _, rows2 = served_logits(eng2, some_prompts(5, (6,)), 3)
    # embed -> -embed negates the input AND the head: the first layer
    # sees -x, so only the sign structure is shared; the logits differ
    a, b = next(iter(rows.values()))[2], next(iter(rows2.values()))[2]
    assert not np.allclose(a, b)


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
    middle of its decode, or (the loop one step ahead) had a step in
    flight that updated the slot's state after its last token."""
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
        eng.run_until_complete()        # the engine stands idle between
        assert r1.is_finished() and eng._flight is None
    # "in-flight": the second waits in the queue and is admitted in the
    # step that sees the first end, while the overrun step is in flight
    reqs, rows = served_logits(eng, [second], 9)
    assert eng.stats()["overrun_rows"] >= (how != "cancelled")
    assert eng.stats()["overlapped_steps"] > 0
    assert (r1.finish_reason == "cancelled") == (how == "cancelled")
    assert len(r1.output_tokens) == (12 if how != "cancelled" else
                                     r1.num_generated)
    toks = reqs[0].result()
    assert list(toks) == list(fresh_tokens(cfg, state, second, 9))
    want = np.asarray(ref.logits_at(
        state, model_dict(cfg), np.concatenate([second, toks]),
        np.arange(len(second) - 1, len(second) + 8)))
    for n, row in rows[reqs[0].id].items():
        np.testing.assert_allclose(row, want[n - 1], atol=ATOL)


def test_recover_replays_prompt_and_tokens_into_the_slots(toy):
    """``recover()`` in the middle of decode: fresh pools, every
    in-flight request re-prefilled (prompt + tokens so far) into its
    slot's state, then the same tokens as an engine that never fell."""
    cfg, state = toy
    prompts = some_prompts(9, (5, 12, 8))
    want = [fresh_tokens(cfg, state, p, 14) for p in prompts]
    eng = engine(cfg, state)
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=14))
            for p in prompts]
    for _ in range(6):
        eng.step()
    assert all(0 < r.num_generated < 14 for r in reqs)
    out = eng.recover()
    assert out["replayed"] == 3
    eng.run_until_complete()
    assert [list(r.result()) for r in reqs] == [list(w) for w in want]
    assert eng.blocks.pool_accounting()["leak"] == 0


# ----------------------------------------------------------- the refusals
@pytest.mark.parametrize("option,kw", [
    ("mesh", dict(mesh=2)), ("kv_quant", dict(kv_quant=True)),
    ("quant", dict(quant="int8")), ("spec_k", dict(spec_k=2)),
    ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ("preempt", dict(preempt=True)),
    ("prefill_chunk", dict(prefill_chunk=8)), ("lora", None)])
def test_what_the_family_lacks_is_refused_by_name(toy, option, kw):
    cfg, state = toy
    if kw is None:
        from paddle_tpu.serving.lora.store import AdapterStore
        kw = dict(lora=AdapterStore(cfg, rank=2))
    with pytest.raises(ValueError, match=rf"^{option} is not supported "
                                         "for the granitemoehybrid"):
        engine(cfg, state, **kw)


# ------------------------------------------------------------- the census
def test_the_census_counts_pages_of_attention_layers_and_state_by_slot(toy):
    cfg, state = toy
    eng = engine(cfg, state)
    pages = eng.blocks.num_pages + 1
    # one attention layer of four: k + v, 2 KV heads of 8, float32
    kv = 2 * 1 * pages * 2 * 4 * 8 * 4
    per_slot = 3 * (8 * 64 * 4 + 3 * (64 + 16) * 4)
    dev = eng.resource_snapshot()["mesh"]["devices"][0]
    assert dev["kv_pool_bytes"] == kv
    assert dev["recurrent_state_bytes"] == 3 * per_slot
    assert eng._page_bytes() == 2 * 1 * 2 * 4 * 8 * 4
    sizing = eng.blocks.pool_bytes(
        num_layers=1, num_kv_heads=2, head_dim=8, dtype_itemsize=4)
    assert sizing["total_bytes"] == kv
    assert eng.runner.recurrent_state_bytes == 3 * per_slot
    assert eng.stats()["recurrent_state_bytes"] == 3 * per_slot
    assert eng.runner.kpool.shape == (1, pages, 1, 4, 16)   # heads paired
    assert eng.runner._rstate[0].shape == (3, 3, 8, 64)
    assert eng.runner._rstate[0].dtype == jnp.float32


def test_a_bfloat16_model_keeps_its_state_in_bfloat16():
    """The recurrent state is held in the served dtype, as the published
    cache allocates it, and updated in float32: served in bfloat16, both
    pools are bfloat16 and the served logits follow the float32
    reference of the same (bfloat16) weights.  Tolerance: every
    activation is rounded to 8 bits (2^-8 relative), some ten times a
    layer over four layers and the head, on logits up to 1.7: 0.036
    measured, 0.08 allowed; a state from the wrong slot or a stale one
    moves logits by their own size."""
    cfg = toy_cfg(dtype="bfloat16")
    state = {k: v.astype(jnp.bfloat16) for k, v in toy_state(cfg).items()}
    eng = engine(cfg, state, emit_logits=True)
    ssm, conv = eng.runner._rstate
    assert ssm.dtype == conv.dtype == jnp.bfloat16
    assert eng.runner.recurrent_state_bytes == 3 * 3 * (
        8 * 64 * 2 + 3 * (64 + 16) * 2)
    prompts = some_prompts(1, (5, 11, 18, 3, 9))
    reqs, rows = served_logits(eng, prompts, 10)
    m = model_dict(cfg)
    for p, r in zip(prompts, reqs):
        toks = r.result()
        want = np.asarray(ref.logits_at(
            state, m, np.concatenate([p, toks]),
            np.arange(len(p) - 1, len(p) + len(toks) - 1)))
        assert len(rows[r.id]) >= 8
        for n, row in rows[r.id].items():
            np.testing.assert_allclose(row, want[n - 1], atol=0.08)


# ---------------------------------------------------- the kernel, its twin
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("active", [
    [1, 1, 1, 1, 1], [0, 1, 0, 0, 1], [0, 0, 1, 1, 0], [0, 0, 0, 0, 0]],
    ids=["all-live", "parked-first", "parked-last", "none-live"])
def test_state_update_kernel_is_its_xla_twin(monkeypatch, active, dtype):
    """``ssm_state_update`` under the Pallas interpreter against
    ``ssm_state_update_xla``, the pool in float32 and in bfloat16 (the
    arithmetic float32 either way): the live slots' states and outputs,
    the parked slots' states untouched, the other layers' too.  (What
    the interpreter cannot show, that a parked slot's grid steps neither
    fetch nor write back, ``chip_smoke.py --ssm-update`` shows on the
    chip.)"""
    monkeypatch.setattr(U, "_INTERPRET", True)
    monkeypatch.setattr(U, "LANE_BLOCK", 128)       # two blocks a slot
    rng = np.random.default_rng(sum(active))
    slots, n, hp = 5, 16, 256
    pool = jnp.asarray(rng.normal(size=(3, slots, n, hp)),
                       jnp.float32).astype(dtype)
    decay = jnp.asarray(rng.uniform(0.5, 1.0, (slots, hp)), jnp.float32)
    dtx, b, c = (jnp.asarray(rng.normal(size=s), jnp.float32)
                 for s in ((slots, hp), (slots, 1, n), (slots, 1, n)))
    act = jnp.asarray(active, jnp.int32)
    assert U.select_ssm_state_update() is U.ssm_state_update
    got_pool, got_y = jax.jit(U.ssm_state_update, static_argnums=1)(
        pool, 1, decay, dtx, b, c, act)
    want_pool, want_y = U.ssm_state_update_xla(pool, 1, decay, dtx, b, c,
                                               act)
    assert got_pool.dtype == pool.dtype and got_y.dtype == jnp.float32
    live = np.asarray(active, bool)
    f32 = jnp.float32
    # the same float32 arithmetic in both, rounded once to the pool's
    # dtype: a rounding of float32 apart at most (1 ulp of bfloat16
    # where that rounding falls on a tie)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)
    np.testing.assert_allclose(
        got_pool.astype(f32)[1][live], want_pool.astype(f32)[1][live],
        atol=1e-6, rtol=2.0 ** -7 if dtype == "bfloat16" else 0)
    np.testing.assert_array_equal(got_pool[1][~live], pool[1][~live])
    np.testing.assert_array_equal(got_pool[0], pool[0])
    np.testing.assert_array_equal(got_pool[2], pool[2])
    assert not np.asarray(got_y)[~live].any()
