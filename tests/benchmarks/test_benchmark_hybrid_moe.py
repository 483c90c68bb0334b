"""The ``nemotron_h`` cell's part of the yardstick on the CPU: the
configuration against the catalog's published keys, the memory sum, the
work functions against hand-worked numbers at the published sizes, the
seeded state against the program's own names, the readers on hand-made
events, and the driver through ``run_cell`` on a toy manifest
(``toy_hybrid_moe``) with both controls failing and the sound run
passing."""
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
from benchmarks.lib import hybrid_moe_state as S  # noqa: E402
from benchmarks.lib import hybrid_moe_work as W  # noqa: E402
from benchmarks.lib import traffic  # noqa: E402
from benchmarks.readers import (counters_ratio, rate_mfu_of,  # noqa: E402
                                trace_roofline_of)

TOY = os.path.join(HERE, "toy_hybrid_moe")
CELL = "nemotron-3-nano-30b-a3b-ep8.reason-closed64"
CPU_TRACE = dict(device_plane=r"^/host:CPU$",
                 ops_line=r"XLAPjRtCpuClient|XLAEigen")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MIB = 1_048_576


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


NANO = load("benchmarks", "configs", "nemotron-3-nano-30b-a3b-ep8.json")
# the published settings (the catalog's ``config`` of the source)
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
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}


# ------------------------------------------------------- the configuration
def test_every_published_key_is_unchanged_but_the_two_reduced():
    assert NANO["reduced"] == ["n_routed_experts", "vocab_size"]
    assert NANO["published"] == {"n_routed_experts": 128,
                                 "vocab_size": 131072}
    as_run = dict(PUBLISHED, n_routed_experts=16, vocab_size=16384)
    assert NANO["model"] == as_run
    for key, value in as_run.items():
        assert NANO[key] == value, key          # the repeated keys
    pattern = NANO["model"]["hybrid_override_pattern"]
    assert len(pattern) == 52               # depth is not cut
    assert (pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (23, 23, 6)
    assert [i for i, c in enumerate(pattern) if c == "*"] == [
        5, 12, 19, 26, 33, 42]
    assert NANO["expert_parallel"] == {"chips": 8, "rank": 0}
    assert S.local_experts(NANO) == (0, 16) and S.router_width(NANO) == 128
    assert NANO["engine"] == {"max_slots": 64, "page_size": 16,
                              "max_model_len": 4096,
                              "enable_prefix_cache": False}
    a = NANO["assumed"]
    assert (a["torch_dtype"], a["initializer_range"], a["ssm_state_dtype"],
            a["position_embedding_type"], a["e_score_correction_bias_std"],
            a["rescale_prenorm_residual_applied"]) == (
        "bfloat16", 0.02, "bfloat16", "nope", 0.002, False)
    assert "applies no rotary" in a["position_embedding_type_why"]
    assert (NANO["driver"], NANO["reference"]) == (
        "engine_closed_loop_hybrid_moe", "nemotron_h_lm")


def test_the_traffic_is_the_other_two_cells_unchanged():
    manifest = load("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["traffic"] == "reason-closed64" == cells[
        "granite-4.0-h-micro.reason-closed64"]["traffic"]
    assert cells[CELL]["chips"] == 1
    mix = load("benchmarks", "traffic", "reason-closed64.json")
    assert mix["callers"] == 64 == NANO["engine"]["max_slots"]
    gen = traffic.ClosedLoop(mix, NANO["model"]["vocab_size"], 7)
    assert max(p + a for p, a in gen._pairs) <= 4096
    prompt, _, sampled = gen.next_request(0)
    assert sampled is None and prompt.max() < 16384
    # the decode step's sorted buffer: what the experts' roofline times
    rows = -(-64 * 6 // 16) * 16 + 16 * 16
    events = load("benchmarks", "layer_metrics",
                  "moe_experts_roofline.serve.hybrid_moe.json")["args"]
    assert rows == 640 and "[640," in events["events"][0]
    assert 256 * 6 // 128 * 128 + 16 * 128 == 3584      # the least prefill


def test_the_cell_reports_every_metric_the_issue_lists():
    manifest = load("BENCHMARK.json")
    mine = {m["name"] for kind in ("end_to_end", "per_layer")
            for m in manifest[kind] if CELL in m.get("workloads", [CELL])}
    assert mine == {
        "setup_s", "serve_tokens_per_s", "itl_p95_ms", "slot_occupancy",
        "decode_step_ms", "ttft_mean_ms", "ttft_p95_ms",
        "prefill_ms_per_ktok", "device_idle_share.serve",
        "ssm_live_row_share", "moe_rows_per_expert", "moe_local_pair_share",
        "mfu.serve.hybrid_moe", "ssm_update_roofline.serve.hybrid_moe",
        "moe_experts_roofline.serve.hybrid_moe",
        "paged_attention_roofline.serve.hybrid_moe"}


# ------------------------------------------------------------------- work
def test_a_blocks_parts_by_hand():
    p = W.block_params(NANO)
    assert p["mamba"] == 2688 * 10304 + 4096 * 2688 == 38_707_200
    assert p["attention"] == 2 * 2688 * 4096 + 2 * 2688 * 256 == 23_396_352
    assert p["expert"] == 2 * 2688 * 1856 == 9_977_856
    assert p["shared"] == 2 * 2688 * 3712 == 19_955_712
    assert p["router"] == 2688 * 128
    d = S.dims(NANO)
    assert (d["d_inner"], d["conv_dim"], d["in_proj"]) == (4096, 6144, 10304)
    assert d["expert_width"] == 1920        # 1,856 in whole lane tiles
    assert (d["mamba_layers"], d["attention_layers"],
            d["expert_layers"]) == (23, 6, 23)


def test_one_chip_holds_10_52_gb_and_the_memory_sum_is_81_percent():
    p = W.params(NANO)
    per_expert_block = 16 * 9_977_856 + 19_955_712 + 2688 * 128
    assert round(per_expert_block / 1e6, 2) == 179.95
    assert p["blocks"] == (23 * 38_707_200 + 6 * 23_396_352
                           + 23 * per_expert_block)
    assert p["embedding_and_head"] == 2 * 16384 * 2688
    assert round(p["total"] / 1e9, 3) == 5.257
    weights = W.weight_bytes(NANO)
    assert round(weights / 1e9, 2) == 10.51     # 10.52 with the vectors
    assert W.state_elements_per_slot(NANO) == 524_288
    assert W.state_bytes_per_slot(NANO) == MIB      # 1 MiB a slot a block
    ssm = 64 * 23 * MIB
    conv = 64 * 23 * 3 * 6144 * 2
    assert W.kv_bytes_per_token(NANO) == 6 * 1024   # 1,024 B a block
    kv = 64 * 4096 * W.kv_bytes_per_token(NANO)
    assert (round(ssm / 1e9, 2), round(conv / 1e9, 2),
            round(kv / 1e9, 2)) == (1.54, 0.05, 1.61)
    assert W.recurrent_state_bytes(NANO, 64) == ssm + conv
    # the held experts stored 1,920 wide: 3.4 % more of their bytes
    pad = W.padding_bytes(NANO)
    assert pad == 23 * 16 * 2 * 2688 * 64 * 2 == 253_231_104
    assert round(100 * pad / (23 * 16 * 9_977_856 * 2), 1) == 3.4
    total = weights + pad + ssm + conv + kv
    assert round(total / 1e9, 1) == 14.0
    assert round(100 * total / 2**34) == 81
    # the whole model, every expert and the whole vocabulary
    whole = dict(NANO, model=dict(NANO["model"], n_routed_experts=128,
                                  vocab_size=131072))
    assert round(W.params(whole)["total"] / 1e9, 2) == 31.58


def test_a_decode_step_by_bytes():
    seen = {"decode_tokens": 64, "decode_context_sum": 64 * 1250,
            "moe_experts_live": 23 * 15}
    assert W.ssm_update_bytes(NANO, seen) == 64 * 23 * 2 * MIB
    assert round(W.ssm_update_bytes(NANO, seen) / 1e9, 2) == 3.09
    assert W.paged_decode_bytes(NANO, seen) == 64 * 1250 * 6144
    assert round(W.paged_decode_bytes(NANO, seen) / 1e9, 2) == 0.49
    assert W.moe_expert_bytes(NANO, seen) == 23 * 15 * 2 * 9_977_856
    assert round(W.moe_expert_bytes(NANO, seen) / 1e9, 2) == 6.88
    for name in ("ssm_update_bytes", "paged_decode_bytes",
                 "moe_expert_bytes"):
        with pytest.raises(KeyError):
            W.WORK[name](NANO, {})


def test_flops_of_a_token_by_hand():
    every = (23 * 38_707_200 + 6 * 23_396_352
             + 23 * (19_955_712 + 2688 * 128) + 16384 * 2688)
    seen = {"decode_tokens": 1, "prompt_tokens": 0, "moe_local_pairs": 17,
            "decode_context_sum": 1000, "prefill_context_sum": 0}
    want = (2.0 * every + 5.0 * 524_288 * 23 + 2.0 * 17 * 9_977_856
            + 4.0 * 1000 * 32 * 128 * 6)
    assert W.serve_flops(NANO, seen) == want
    # a prompt token's pairs: the held share of 6 a block
    seen = {"decode_tokens": 0, "prompt_tokens": 2, "moe_local_pairs": 0,
            "decode_context_sum": 0, "prefill_context_sum": 3}
    assert W.serve_flops(NANO, seen) == (
        2 * (2.0 * every + 5.0 * 524_288 * 23)
        + 2.0 * (2 * 6 * 23 * 16 / 128) * 9_977_856
        + 4.0 * 3 * 32 * 128 * 6)


# ------------------------------------------------------------------ state
def _description(conf):
    from benchmarks.drivers import engine_closed_loop_hybrid_moe as D
    from paddle_tpu.models import nemotron_h as nh
    m = D.routed_model(conf)
    return nh, nh.NemotronHConfig.from_published(
        {k: v for k, v in m.items() if k != "local_experts"},
        local_experts=tuple(m["local_experts"]),
        dtype=conf["assumed"]["torch_dtype"])


def test_the_state_has_the_programs_keys_and_the_published_init():
    toy = R.load_json(os.path.join(TOY, "configs", "hybrid-moe-toy.json"))
    for conf in (NANO, toy):
        nh, cfg = _description(conf)
        mine = {k: tuple(s) for k, (s, _) in S.shapes(conf).items()}
        assert mine == nh.weight_shapes(cfg)
    assert "lm_head.weight" in S.shapes(NANO)
    count = sum(math.prod(s) for s, _ in S.shapes(NANO).values())
    # the work functions leave out the vectors: norms, conv, dt, A, D and
    # the routers' biases
    assert count - W.params(NANO)["total"] == (
        2688 + 52 * 2688 + 23 * (6144 * 5 + 3 * 64 + 4096) + 23 * 128
        + W.padding_bytes(NANO) // 2)
    assert round((2 * count - W.padding_bytes(NANO)) / 1e9, 2) == 10.52
    made = S.seeded(toy, 2**31 + 5)
    p = "backbone.layers.0.mixer."
    a = np.exp(np.asarray(made[p + "A_log"], np.float64))
    assert a.min() >= 0.01 * 0.99 and a.max() <= 0.2 * 1.01
    dt = np.log1p(np.exp(np.asarray(made[p + "dt_bias"], np.float64)))
    assert dt.min() >= 0.05 * 0.99 and dt.max() <= 0.5 * 1.01
    assert np.all(np.asarray(made[p + "D"]) == 1.0)
    bias = np.asarray(made["backbone.layers.1.mixer.gate."
                           "e_score_correction_bias"], np.float64)
    assert bias.shape == (8,) and 0 < np.abs(bias).max() < 0.08
    up = made["backbone.layers.1.mixer.experts.up_proj.weight"]
    down = made["backbone.layers.1.mixer.experts.down_proj.weight"]
    assert up.shape == (4, 64, 128) and down.shape == (4, 128, 64)
    assert 0.07 < float(np.std(np.asarray(up)[:, :, :24])) < 0.09
    assert not np.asarray(up)[:, :, 24:].any()      # zeros past 24
    assert not np.asarray(down)[:, 24:].any()
    assert np.asarray(down)[:, :24].any()
    other = S.seeded(toy, 2**31 + 6)
    key = "backbone.layers.3.mixer.q_proj.weight"
    assert np.array_equal(made[key], S.seeded(toy, 2**31 + 5)[key])
    assert not np.array_equal(made[key], other[key])


# ---------------------------------------------------------------- readers
def _trace(events):
    return {"planes": {"/device:TPU:0": events}}


def _ctx(config=NANO):
    return {"config": config, "peaks": PEAKS, "devices": [object()]}


EVENTS = [
    ("%ssm_state_update.7 = (bf16[23,64,128,4096]{3,2,1,0}, "
     "f32[64,1,4096]{2,1,0}) custom-call(...)", 0.0, 0.25),
    ("%ssm_state_update = (bf16[23,64,128,4096]{3,2,1,0}, "
     "f32[64,1,4096]{2,1,0}) custom-call(...)", 1.0, 1.25),
    ("%paged_attention.2 = bf16[64,2,16,128]{3,2,1,0} custom-call(...)",
     5.0, 6.0),
    ("%grouped_matmul.3 = bf16[640,1856]{1,0} custom-call(...)", 7.0, 7.5),
    ("%grouped_matmul.4 = bf16[640,2688]{1,0} custom-call(...)", 8.0, 8.5),
    # a prefill's products: not the decode step's row count
    ("%grouped_matmul.9 = bf16[3584,1856]{1,0} custom-call(...)", 9.0, 19.0),
    ("%fusion.1 = bf16[64,10304]{1,0} fusion(...)", 20.0, 21.0)]


@pytest.mark.parametrize("metric,share,own", [
    ("ssm_update_roofline.serve.hybrid_moe", 50.0, EVENTS[:2]),
    ("paged_attention_roofline.serve.hybrid_moe", 25.0, EVENTS[2:3]),
    ("moe_experts_roofline.serve.hybrid_moe", 25.0, EVENTS[3:5])])
def test_each_roofline_finds_its_kernel_and_no_other(metric, share, own):
    args = load("benchmarks", "layer_metrics", metric + ".json")["args"]
    # work that takes a quarter of a second at HBM's rate, of each kind
    quarter = 819e9 * 0.25
    seen = {"decode_tokens": quarter / (23 * 2 * MIB),
            "decode_context_sum": quarter / 6144,
            "moe_experts_live": quarter / (9_977_856 * 2)}
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
                "mfu.serve.hybrid_moe.json")["args"]
    seen = {"decode_tokens": 2700, "prompt_tokens": 700,
            "moe_local_pairs": 2700 * 23 * 6 // 8,
            "decode_context_sum": 3_400_000, "prefill_context_sum": 200_000}
    run = {"observed": seen, "window_s": 1.0}
    got = rate_mfu_of.read(args, run, None, _ctx())
    assert got == pytest.approx(100.0 * W.serve_flops(NANO, seen) / 197e12)
    assert 3 < got < 8              # 2,700 tokens/s: a twentieth
    assert rate_mfu_of.read(args, {"observed": {}, "window_s": 1.0}, None,
                            _ctx()) is None
    seen = {"ssm_rows_live": 64 * 23 * 10 - 23, "decode_steps": 10,
            "max_slots": 64, "ssm_layers": 23, "moe_local_pairs": 48 * 230,
            "moe_routed_pairs": 384 * 230, "moe_layer_experts": 23 * 16}
    for name, want in (("ssm_live_row_share", 100 - 100 / 640),
                       ("moe_rows_per_expert", 3.0),
                       ("moe_local_pair_share", 12.5)):
        args = load("benchmarks", "layer_metrics", name + ".json")["args"]
        assert counters_ratio.read(args, {"observed": seen}, None,
                                   {}) == pytest.approx(want)


# ------------------------------------------------------- the driver, toy size
@pytest.fixture(scope="module")
def lines():
    import jax
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    return {tr: R.run_cell(
        manifest, "hybrid-moe-toy.chat", seed=2**31 + 19, seconds=1.0,
        trace=tr, devices=jax.devices()[:1], root=TOY,
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
        "mfu.serve.hybrid_moe", "ssm_live_row_share", "moe_rows_per_expert",
        "moe_local_pair_share"}
    assert 90 < got["ssm_live_row_share"]["value"] <= 100
    assert got["ssm_live_row_share"]["value"] == pytest.approx(
        got["slot_occupancy"]["value"], abs=8)
    # 4 slots x 3 choices over 8 experts, 4 held: half the pairs, 1.5 rows
    assert 35 < got["moe_local_pair_share"]["value"] < 65
    assert 1.0 < got["moe_rows_per_expert"]["value"] < 2.0
    assert 0 < got["mfu.serve.hybrid_moe"]["value"] < 100


@pytest.mark.parametrize("seed", [11, 13])
def test_sound_passes_and_both_controls_fail(seed):
    """One window, one sample: the program's tokens pass both limits;
    the reference with int8 projections and experts fails, and so does
    the reference that gives every head group 0's B and C."""
    import jax
    from benchmarks.drivers import engine_closed_loop_hybrid_moe as D
    from benchmarks.lib import stats
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    found = R.find_cell(manifest, "hybrid-moe-toy.chat", TOY)
    ctx = R.cell_context(found, "hybrid-moe-toy.chat", seed=seed,
                         seconds=1.0, devices=jax.devices()[:1])
    served = D.build(ctx)
    loop = D.Loop(served)
    loop.start()
    loop.ramp()
    # a sample that no clock decides (see test_benchmark_hybrid.py)
    for _ in range(600):
        loop.resubmit(loop.step())
    finished = [r for r in loop.records if r["times"]
                and r["request"].finish_reason == "length"]
    sample = D.plain(D.sample_finished(finished, seed, 12))
    seen = D.counted_window(loop, 0.5, ctx["config"])
    steps = seen["decode_steps"]
    assert (seen["ssm_layers"], seen["moe_layer_experts"]) == (3, 2 * 4)
    assert 0.9 * steps * 4 * 3 <= seen["ssm_rows_live"] <= steps * 4 * 3
    # two expert blocks, three choices a live row
    assert seen["moe_routed_pairs"] == 2 * seen["ssm_rows_live"]
    assert 0 < seen["moe_local_pairs"] < seen["moe_routed_pairs"]
    assert 0 < seen["moe_experts_live"] <= steps * 2 * 4
    weights = served["weights"]
    sound = D.reference_gaps(ctx, weights, sample)
    assert sound["positions"] > 400
    assert stats.judge(sound, ctx["limits"])[0]
    assert D.CONTROLS == ("int8", "one_group")
    for control in D.CONTROLS:
        found = D.reference_gaps(ctx, weights, sample, **{control: True})
        ok, checks = stats.judge(found, ctx["limits"])
        assert not ok, (control, checks)


def test_the_cells_limits_were_read_on_the_chip():
    limits = load("benchmarks", "limits", CELL + ".json")
    assert "logit_gap_mean" in limits["limits"]
    assert "PLACEHOLDER" not in limits["readings"]
    assert "int8" in limits["readings"]
    assert "one_group" in limits["readings"]
    assert len(limits["readings"]) < 2000
