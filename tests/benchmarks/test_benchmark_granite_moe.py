"""The Granite 4.0-H Small cell's part of the yardstick on the CPU: the
configuration against the catalog's published keys, the memory sum, the
work functions against hand-worked numbers at the published sizes, the
seeded state against the program's own names, the readers on hand-made
events, and the driver through ``run_cell`` on a toy manifest
(``toy_granite_moe``) with both controls failing and the sound run
passing.  Every toy window here is counted in decode steps, not seconds,
so what a loaded CPU finishes in it does not decide a reading."""
import json
import math
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as R  # noqa: E402
from benchmarks.drivers import engine_closed_loop as closed  # noqa: E402
from benchmarks.lib import granite_moe_state as S  # noqa: E402
from benchmarks.lib import granite_moe_work as W  # noqa: E402
from benchmarks.lib import traffic  # noqa: E402
from benchmarks.readers import (counters_ratio, rate_mfu_of,  # noqa: E402
                                trace_roofline_of)

TOY = os.path.join(HERE, "toy_granite_moe")
TOY_CELL = "granite-moe-toy.chat"
CELL = "granite-4.0-h-small-ep2-l10.reason-closed64"
CPU_TRACE = dict(device_plane=r"^/host:CPU$",
                 ops_line=r"XLAPjRtCpuClient|XLAEigen")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MIB = 1_048_576
TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


SMALL = load("benchmarks", "configs", "granite-4.0-h-small-ep2-l10.json")
# the published settings (the catalog's ``config`` of the source)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": TYPES, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}


# ------------------------------------------------------- the configuration
def test_every_published_key_is_unchanged_but_the_three_reduced():
    assert len(TYPES) == 40 and [i for i, t in enumerate(TYPES)
                                 if t == "attention"] == [5, 15, 25, 35]
    assert SMALL["reduced"] == ["num_hidden_layers", "num_local_experts",
                                "vocab_size"]
    assert SMALL["published"] == {"num_hidden_layers": 40,
                                  "num_local_experts": 72,
                                  "vocab_size": 100352}
    as_run = dict(PUBLISHED, num_hidden_layers=10, num_local_experts=36,
                  vocab_size=50176)
    assert SMALL["model"] == as_run
    for key, value in as_run.items():
        assert SMALL[key] == value, key          # the repeated keys
    # one whole period: 5 Mamba, 1 attention, 4 Mamba
    assert S.layer_types(SMALL) == ["mamba"] * 5 + ["attention"] + [
        "mamba"] * 4
    assert SMALL["expert_parallel"] == {"chips": 2, "rank": 0}
    assert S.local_experts(SMALL) == (0, 36) and S.router_width(SMALL) == 72
    assert SMALL["engine"] == {"max_slots": 64, "page_size": 16,
                               "max_model_len": 4096,
                               "enable_prefix_cache": False}
    a = SMALL["assumed"]
    assert (a["torch_dtype"], a["initializer_range"], a["embedding_std"],
            a["ssm_state_dtype"], a["A_init_range"]) == (
        "bfloat16", 0.02, 0.005, "bfloat16", [1, 16])
    assert (SMALL["driver"], SMALL["reference"]) == (
        "engine_closed_loop_granite_moe", "granite_moe_hybrid_lm")


def test_the_traffic_is_the_other_three_cells_unchanged():
    manifest = load("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    for other in ("granite-4.0-h-micro.reason-closed64",
                  "nemotron-3-nano-30b-a3b-ep8.reason-closed64",
                  "gigachat3.1-702b-a36b-ep16-l5.reason-closed64"):
        assert cells[CELL]["traffic"] == cells[other]["traffic"]
    assert cells[CELL]["chips"] == 1
    mix = load("benchmarks", "traffic", "reason-closed64.json")
    assert mix["callers"] == 64 == SMALL["engine"]["max_slots"]
    gen = traffic.ClosedLoop(mix, SMALL["model"]["vocab_size"], 2**31 + 7)
    assert max(p + a for p, a in gen._pairs) <= 4096
    prompt, _, sampled = gen.next_request(0)
    assert sampled is None and prompt.max() < 50176
    # the decode step's sorted buffer: what the experts' roofline times
    rows = -(-64 * 10 // 16) * 16 + 36 * 16
    events = load("benchmarks", "layer_metrics",
                  "moe_experts_roofline.serve.granite_moe.json")["args"]
    assert rows == 1216 and "[1216," in events["events"][0]
    # 8.9 rows an expert on average: a popular one spans two tiles
    assert round(64 * 10 * 36 / 72 / 36, 1) == 8.9
    assert 256 * 10 // 128 * 128 + 36 * 128 == 7168     # the least prefill


def test_the_cell_reports_every_metric_the_issue_lists():
    manifest = load("BENCHMARK.json")
    mine = {m["name"] for kind in ("end_to_end", "per_layer")
            for m in manifest[kind] if CELL in m.get("workloads", [CELL])}
    assert mine == {
        "setup_s", "serve_tokens_per_s", "itl_p95_ms", "slot_occupancy",
        "decode_step_ms", "ttft_mean_ms", "ttft_p95_ms",
        "prefill_ms_per_ktok", "device_idle_share.serve",
        "ssm_live_row_share", "moe_rows_per_expert", "moe_local_pair_share",
        "setup_split_s.trace", "setup_split_s.lower",
        "setup_split_s.compile", "setup_split_s.rest", "setup_cache_misses",
        "mfu.serve.granite_moe", "ssm_update_roofline.serve.granite_moe",
        "moe_experts_roofline.serve.granite_moe",
        "paged_attention_roofline.serve.granite_moe",
        "moe_tiles_per_live_expert"}
    # six cells, none on four chips
    assert len(manifest["workloads"]) == 6
    assert all(w["chips"] == 1 for w in manifest["workloads"])


# ------------------------------------------------------------------- work
def test_a_layers_parts_by_hand():
    p = W.layer_params(SMALL)
    assert p["mamba"] == 4096 * 16768 + 8192 * 4096 == 102_236_160
    assert p["attention"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 == 41_943_040
    assert p["shared"] == 3 * 4096 * 1536 == 18_874_368
    assert p["expert"] == 4096 * 1536 + 768 * 4096 == 9_437_184
    assert p["router"] == 4096 * 72 == 294_912
    d = S.dims(SMALL)
    assert (d["d_inner"], d["conv_dim"], d["in_proj"]) == (8192, 8448, 16768)
    assert (d["mamba_layers"], d["attention_layers"], d["layers"]) == (
        9, 1, 10)


def test_one_chip_holds_9_51_gb_and_the_memory_sum_is_69_percent():
    p = W.params(SMALL)
    rest = 18_874_368 + 294_912 + 36 * 9_437_184
    assert p["layers"] == 9 * (102_236_160 + rest) + 41_943_040 + rest
    assert p["embedding"] == 50176 * 4096
    assert round(p["total"] / 1e9, 3) == 4.757
    weights = W.weight_bytes(SMALL)
    assert round(weights / 1e9, 2) == 9.51
    assert W.state_bytes_per_slot(SMALL) == 2 * MIB     # bfloat16: 2 MiB
    ssm = 64 * 9 * 2 * MIB
    conv = 64 * 9 * 3 * 8448 * 2
    assert W.kv_bytes_per_token(SMALL) == 4096          # one layer, 8 x 128
    kv = 64 * 4096 * W.kv_bytes_per_token(SMALL)
    assert (round(ssm / 1e9, 2), round(conv / 1e9, 2),
            round(kv / 1e9, 2)) == (1.21, 0.03, 1.07)
    assert W.recurrent_state_bytes(SMALL, 64) == ssm + conv
    total = weights + ssm + conv + kv
    assert round(total / 1e9, 1) == 11.8
    assert round(100 * total / 2**34) == 69
    # the whole model: 40 layers, every expert, the whole vocabulary
    whole = dict(SMALL, model=dict(SMALL["model"], num_hidden_layers=40,
                                   num_local_experts=72, vocab_size=100352))
    assert round(W.params(whole)["total"] / 1e9, 1) == 32.2


def test_a_decode_step_by_bytes():
    seen = {"decode_tokens": 64, "decode_context_sum": 64 * 1100,
            "moe_experts_live": 10 * 36}
    assert W.ssm_update_bytes(SMALL, seen) == 64 * 9 * 2 * 2 * MIB
    assert round(W.ssm_update_bytes(SMALL, seen) / 1e9, 2) == 2.42
    assert W.paged_decode_bytes(SMALL, seen) == 64 * 1100 * 4096
    assert round(W.paged_decode_bytes(SMALL, seen) / 1e9, 2) == 0.29
    assert W.moe_expert_bytes(SMALL, seen) == 360 * 3 * 4096 * 768 * 2
    assert round(W.moe_expert_bytes(SMALL, seen) / 1e9, 2) == 6.79
    for name in ("ssm_update_bytes", "paged_decode_bytes",
                 "moe_expert_bytes"):
        with pytest.raises(KeyError):
            W.WORK[name](SMALL, {})


def test_flops_of_a_token_by_hand():
    every = (9 * 102_236_160 + 41_943_040 + 10 * (18_874_368 + 294_912)
             + 50176 * 4096)
    assert round(every / 1e9, 3) == 1.359
    seen = {"decode_tokens": 1, "prompt_tokens": 0, "moe_local_pairs": 17,
            "decode_context_sum": 1000, "prefill_context_sum": 0}
    want = (2.0 * every + 5.0 * 1_048_576 * 9 + 2.0 * 17 * 9_437_184
            + 4.0 * 1000 * 32 * 128 * 1)
    assert W.serve_flops(SMALL, seen) == want
    # a prompt token's pairs: the held share of 10 a layer
    seen = {"decode_tokens": 0, "prompt_tokens": 2, "moe_local_pairs": 0,
            "decode_context_sum": 0, "prefill_context_sum": 3}
    assert W.serve_flops(SMALL, seen) == (
        2 * (2.0 * every + 5.0 * 1_048_576 * 9)
        + 2.0 * (2 * 10 * 10 * 36 / 72) * 9_437_184
        + 4.0 * 3 * 32 * 128)


# ------------------------------------------------------------------ state
def _description(conf):
    from benchmarks.drivers import engine_closed_loop_granite_moe as D
    from paddle_tpu.models import granite_hybrid as gh
    m = D.routed_model(conf)
    return gh, gh.GraniteHybridConfig.from_published(
        {k: v for k, v in m.items() if k != "local_experts"},
        local_experts=tuple(m["local_experts"]),
        dtype=conf["assumed"]["torch_dtype"])


def test_the_state_has_the_programs_keys_and_the_published_init():
    toy = R.load_json(os.path.join(TOY, "configs", "granite-moe-toy.json"))
    for conf in (SMALL, toy):
        gh, cfg = _description(conf)
        mine = {k: tuple(s) for k, (s, _) in S.shapes(conf).items()}
        assert mine == gh.weight_shapes(cfg)
    _, cfg = _description(SMALL)
    assert cfg.blocks == tuple((k, "moe") for k in S.layer_types(SMALL))
    assert "lm_head.weight" not in S.shapes(SMALL)
    count = sum(math.prod(s) for s, _ in S.shapes(SMALL).values())
    # the work functions leave out the vectors: norms, conv, dt, A, D
    assert count - W.params(SMALL)["total"] == (
        4096 + 10 * 2 * 4096 + 9 * (8448 * 5 + 3 * 128 + 8192))
    made = S.seeded(toy, 2**31 + 5)
    e = "model.layers.2.block_sparse_moe."
    assert made[e + "router.layer.weight"].shape == (64, 8)
    assert made[e + "input_linear.weight"].shape == (4, 64, 48)
    assert made[e + "output_linear.weight"].shape == (4, 24, 64)
    assert 0.07 < float(np.std(np.asarray(
        made[e + "input_linear.weight"]))) < 0.09
    assert "model.layers.4.input_layernorm.weight" not in made
    key = "model.layers.2.self_attn.q_proj.weight"
    assert np.array_equal(made[key], S.seeded(toy, 2**31 + 5)[key])
    assert not np.array_equal(made[key], S.seeded(toy, 2**31 + 6)[key])


# ---------------------------------------------------------------- readers
def _trace(events):
    return {"planes": {"/device:TPU:0": events}}


def _ctx(config=SMALL):
    return {"config": config, "peaks": PEAKS, "devices": [object()]}


EVENTS = [
    ("%ssm_state_update.7 = (bf16[9,64,128,8192]{3,2,1,0}, "
     "f32[64,1,8192]{2,1,0}) custom-call(...)", 0.0, 0.25),
    ("%ssm_state_update = (bf16[9,64,128,8192]{3,2,1,0}, "
     "f32[64,1,8192]{2,1,0}) custom-call(...)", 1.0, 1.25),
    ("%paged_attention.2 = bf16[64,8,1,128]{3,2,1,0} custom-call(...)",
     5.0, 6.0),
    ("%grouped_matmul.3 = bf16[1216,1536]{1,0} custom-call(...)", 7.0, 7.5),
    ("%grouped_matmul.4 = bf16[1216,4096]{1,0} custom-call(...)", 8.0, 8.5),
    # a prefill's products: not the decode step's row count
    ("%grouped_matmul.9 = bf16[7168,1536]{1,0} custom-call(...)", 9.0, 19.0),
    ("%fusion.1 = bf16[64,16768]{1,0} fusion(...)", 20.0, 21.0)]


@pytest.mark.parametrize("metric,share,own", [
    ("ssm_update_roofline.serve.granite_moe", 50.0, EVENTS[:2]),
    ("paged_attention_roofline.serve.granite_moe", 25.0, EVENTS[2:3]),
    ("moe_experts_roofline.serve.granite_moe", 25.0, EVENTS[3:5])])
def test_each_roofline_finds_its_kernel_and_no_other(metric, share, own):
    args = load("benchmarks", "layer_metrics", metric + ".json")["args"]
    # work that takes a quarter of a second at HBM's rate, of each kind
    quarter = 819e9 * 0.25
    seen = {"decode_tokens": quarter / (9 * 2 * 2 * MIB),
            "decode_context_sum": quarter / 4096,
            "moe_experts_live": quarter / (9_437_184 * 2)}
    run = {"observed": seen}
    assert trace_roofline_of.read(args, run, _trace(EVENTS),
                                  _ctx()) == pytest.approx(share)
    rest = [e for e in EVENTS if e not in own]
    # nothing to read: no such event (the parent's program), no counter
    assert trace_roofline_of.read(args, run, _trace(rest), _ctx()) is None
    assert trace_roofline_of.read(args, {"observed": {}}, _trace(EVENTS),
                                  _ctx()) is None


def test_the_whole_steps_share_and_the_counters_ratios():
    args = load("benchmarks", "layer_metrics",
                "mfu.serve.granite_moe.json")["args"]
    seen = {"decode_tokens": 3000, "prompt_tokens": 800,
            "moe_local_pairs": 3000 * 10 * 10 // 2,
            "decode_context_sum": 3_300_000, "prefill_context_sum": 200_000}
    run = {"observed": seen, "window_s": 1.0}
    got = rate_mfu_of.read(args, run, None, _ctx())
    assert got == pytest.approx(100.0 * W.serve_flops(SMALL, seen) / 197e12)
    assert 3 < got < 8              # 3,000 tokens/s: a twentieth
    assert rate_mfu_of.read(args, {"observed": {}, "window_s": 1.0}, None,
                            _ctx()) is None
    seen = {"ssm_rows_live": 64 * 9 * 10 - 9, "decode_steps": 10,
            "max_slots": 64, "ssm_layers": 9, "moe_local_pairs": 320 * 100,
            "moe_routed_pairs": 640 * 100, "moe_layer_experts": 10 * 36,
            "moe_experts_live": 10 * 10 * 36,
            "moe_row_tiles_live": 10 * 10 * 45}
    for name, want in (("ssm_live_row_share", 100 - 100 / 640),
                       ("moe_rows_per_expert", 320 / 36),
                       ("moe_local_pair_share", 50.0),
                       ("moe_tiles_per_live_expert", 1.25)):
        args = load("benchmarks", "layer_metrics", name + ".json")["args"]
        assert counters_ratio.read(args, {"observed": seen}, None,
                                   {}) == pytest.approx(want)
    # the parent's program counts no row tiles: nothing to read
    args = load("benchmarks", "layer_metrics",
                "moe_tiles_per_live_expert.json")["args"]
    seen.pop("moe_row_tiles_live")
    assert counters_ratio.read(args, {"observed": seen}, None, {}) is None


# ------------------------------------------------------- the driver, toy size
def stepped(steps: int):
    """``engine_closed_loop.window`` over a number of decode steps where
    it counts seconds."""
    def window(loop, seconds):
        import jax
        engine = loop.engine
        before = closed.counters(engine)
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            for _ in range(steps):
                loop.resubmit(loop.step())
            t1 = time.perf_counter()
        after = closed.counters(engine)
        assert before["decode_traces"] == after["decode_traces"]
        return closed.summarize(loop.records, t0, t1, before, after,
                                max_slots=engine.max_slots)
    return window


@pytest.fixture(scope="module")
def lines():
    import jax
    from benchmarks.drivers import engine_closed_loop_granite_moe as D
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(D, "window", stepped(120))
        return {tr: R.run_cell(
            manifest, TOY_CELL, seed=2**31 + 19, seconds=1.0, trace=tr,
            devices=jax.devices()[:1], root=TOY,
            t_start=time.perf_counter(), trace_kw=CPU_TRACE,
            peaks=PEAKS if tr else None)
            for tr in (False, True)}


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_of_the_toy_cell(lines, trace):
    line = lines[trace]
    json.loads(json.dumps(line))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and list(line)[-1] == "checks"
    assert set(line["checks"]) == {"logit_gap_mean", "logit_gap_p99"}
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    for m in line["metrics"].values():
        assert np.isfinite(m["value"])
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                        "itl_p95_ms"}


def test_traced_toy_line_reports_the_new_and_the_carried_metrics(lines):
    got = lines[True]["metrics"]
    # no kernel event on the CPU: the three rooflines are left out
    assert set(got) == {
        "slot_occupancy", "decode_step_ms", "ttft_mean_ms", "ttft_p95_ms",
        "prefill_ms_per_ktok", "device_idle_share.serve",
        "mfu.serve.granite_moe", "ssm_live_row_share", "moe_rows_per_expert",
        "moe_local_pair_share", "moe_tiles_per_live_expert",
        "setup_split_s.trace", "setup_split_s.lower",
        "setup_split_s.compile", "setup_split_s.rest", "setup_cache_misses"}
    assert 90 < got["ssm_live_row_share"]["value"] <= 100
    # 4 slots x 3 choices over 8 experts, 4 held: half the pairs, 1.5 rows
    assert 35 < got["moe_local_pair_share"]["value"] < 65
    assert 1.0 < got["moe_rows_per_expert"]["value"] < 2.0
    # a decode tile holds 16 rows: every touched expert fills one
    assert got["moe_tiles_per_live_expert"]["value"] == 1.0
    assert 0 < got["mfu.serve.granite_moe"]["value"] < 100


@pytest.mark.parametrize("seed", [11, 13])
def test_sound_passes_and_both_controls_fail(seed):
    """One window of 600 steps, one sample: the program's tokens pass
    both limits; the reference with int8 projections and experts fails,
    and so does the reference that routes by a softmax over all the
    logits (the top k of it, not renormalised)."""
    import jax
    from benchmarks.drivers import engine_closed_loop_granite_moe as D
    from benchmarks.lib import stats
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    found = R.find_cell(manifest, TOY_CELL, TOY)
    ctx = R.cell_context(found, TOY_CELL, seed=seed, seconds=1.0,
                         devices=jax.devices()[:1])
    served = D.build(ctx)
    loop = D.Loop(served)
    loop.start()
    loop.ramp()
    for _ in range(600):
        loop.resubmit(loop.step())
    finished = [r for r in loop.records if r["times"]
                and r["request"].finish_reason == "length"]
    sample = D.plain(D.sample_finished(finished, seed, 12))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(D, "window", stepped(40))
        seen = D.counted_window(loop, 0.5, ctx["config"])
    steps = seen["decode_steps"]
    assert (seen["ssm_layers"], seen["moe_layer_experts"]) == (3, 4 * 4)
    assert 0.9 * steps * 4 * 3 <= seen["ssm_rows_live"] <= steps * 4 * 3
    # four layers with experts, three choices a live row
    assert seen["moe_routed_pairs"] * 3 == 3 * 4 * seen["ssm_rows_live"]
    assert 0 < seen["moe_local_pairs"] < seen["moe_routed_pairs"]
    assert seen["moe_row_tiles_live"] == seen["moe_experts_live"] > 0
    weights = served["weights"]
    sound = D.reference_gaps(ctx, weights, sample)
    assert sound["positions"] > 400
    assert stats.judge(sound, ctx["limits"])[0]
    assert D.CONTROLS == ("int8", "softmax_all")
    for control in D.CONTROLS:
        found = D.reference_gaps(ctx, weights, sample, **{control: True})
        ok, checks = stats.judge(found, ctx["limits"])
        assert not ok, (control, checks)


def test_the_cells_limits_were_read_on_the_chip():
    limits = load("benchmarks", "limits", CELL + ".json")
    assert "logit_gap_mean" in limits["limits"]
    assert "PLACEHOLDER" not in limits["readings"]
    assert "int8" in limits["readings"]
    assert "softmax_all" in limits["readings"]
    assert len(limits["readings"]) < 2000
