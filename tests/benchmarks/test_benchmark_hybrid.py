"""The ``granitemoehybrid`` cell's part of the yardstick on the CPU: the
configuration against the catalog's published keys, the memory sum, the
work functions against hand-worked numbers, the seeded state against the
program's own names and the published initialisation, the readers on
hand-made events, and the driver through ``run_cell`` on a toy manifest
(``toy_hybrid``) with both controls failing and the sound run passing."""
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
from benchmarks.lib import hybrid_state as S  # noqa: E402
from benchmarks.lib import hybrid_work as W  # noqa: E402
from benchmarks.lib import traffic  # noqa: E402
from benchmarks.readers import (counters_ratio, rate_mfu_of,  # noqa: E402
                                trace_roofline_of)

TOY = os.path.join(HERE, "toy_hybrid")
CELL = "granite-4.0-h-micro.reason-closed64"
CPU_TRACE = dict(device_plane=r"^/host:CPU$",
                 ops_line=r"XLAPjRtCpuClient|XLAEigen")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


GRANITE = load("benchmarks", "configs", "granite-4.0-h-micro.json")
TYPES = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
# the published settings (the catalog's ``config`` of the source)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": TYPES, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


# ------------------------------------------------------- the configuration
def test_every_published_key_is_unchanged_and_nothing_is_reduced():
    assert GRANITE["reduced"] == []
    assert GRANITE["model"] == PUBLISHED
    for key, value in PUBLISHED.items():
        assert GRANITE[key] == value, key       # the repeated keys
    assert GRANITE["model"]["layer_types"].count("attention") == 4
    assert [i for i, t in enumerate(TYPES) if t == "attention"] == [
        5, 15, 25, 35]
    assert GRANITE["engine"] == {"max_slots": 64, "page_size": 16,
                                 "max_model_len": 4096,
                                 "enable_prefix_cache": False}
    a = GRANITE["assumed"]
    assert (a["torch_dtype"], a["initializer_range"], a["embedding_std"],
            a["ssm_state_dtype"]) == ("bfloat16", 0.02, 0.005, "bfloat16")
    assert (a["dt_min"], a["dt_max"], a["A_init_range"]) == (
        0.001, 0.1, [1, 16])
    assert (GRANITE["driver"], GRANITE["reference"]) == (
        "engine_closed_loop_hybrid", "granite_hybrid_lm")


def test_the_traffic_is_the_gigachat_cells_unchanged():
    manifest = load("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["traffic"] == "reason-closed64" == cells[
        "gigachat3.1-702b-a36b-ep16-l5.reason-closed64"]["traffic"]
    assert cells[CELL]["chips"] == 1
    mix = load("benchmarks", "traffic", "reason-closed64.json")
    assert mix["callers"] == 64 == GRANITE["engine"]["max_slots"]
    gen = traffic.ClosedLoop(mix, GRANITE["model"]["vocab_size"], 7)
    assert max(p + a for p, a in gen._pairs) <= 4096
    prompt, _, sampled = gen.next_request(0)
    assert sampled is None and prompt.max() < 100352


# ------------------------------------------------------------------- work
def test_a_layers_parts_by_hand():
    p = W.layer_params(GRANITE)
    assert p["mamba"] == 2048 * 8512 + 4096 * 2048 == 25_821_184
    assert p["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert p["mlp"] == 3 * 2048 * 8192 == 50_331_648
    assert S.dims(GRANITE) == {
        "d_inner": 4096, "conv_dim": 4352, "in_proj": 8512, "head_dim": 64,
        "mamba_layers": 36, "attention_layers": 4}


def test_one_chip_holds_6_38_gb_and_the_memory_sum_is_64_percent():
    p = W.params(GRANITE)
    assert round(p["layers"] / 1e9, 3) == 2.985
    assert p["embedding"] == 100352 * 2048      # one matrix, the head too
    assert round(p["total"] / 1e9, 2) == 3.19
    weights = W.weight_bytes(GRANITE)
    assert round(weights / 1e9, 2) == 6.38
    # the state in the served dtype, as the published cache holds it
    assert W.state_elements_per_slot(GRANITE) == 524_288
    assert W.state_bytes_per_slot(GRANITE) == 1_048_576
    as_f32 = dict(GRANITE, assumed=dict(GRANITE["assumed"],
                                        ssm_state_dtype="float32"))
    assert W.state_bytes_per_slot(as_f32) == 2_097_152
    ssm = 64 * 36 * W.state_bytes_per_slot(GRANITE)
    conv = 64 * 36 * 3 * 4352 * 2
    kv = 64 * 4096 * W.kv_bytes_per_token(GRANITE)
    assert W.kv_bytes_per_token(GRANITE) == 8192    # 4 layers, not 40
    assert (round(ssm / 1e9, 2), round(conv / 1e9, 2),
            round(kv / 1e9, 2)) == (2.42, 0.06, 2.15)
    assert W.recurrent_state_bytes(GRANITE, 64) == ssm + conv
    total = weights + ssm + conv + kv
    assert round(total / 1e9, 1) == 11.0
    assert round(100 * total / 2**34) == 64


def test_a_decode_step_moves_4_83_gb_of_state():
    seen = {"decode_tokens": 64}
    assert W.ssm_update_bytes(GRANITE, seen) == 64 * 36 * 2 * 1_048_576
    assert round(W.ssm_update_bytes(GRANITE, seen) / 1e9, 2) == 4.83
    with pytest.raises(KeyError):
        W.ssm_update_bytes(GRANITE, {})


def test_a_decode_token_reads_8_kb_of_pages_a_token_of_context():
    seen = {"decode_context_sum": 64 * 1170}
    assert W.paged_decode_bytes(GRANITE, seen) == 64 * 1170 * 8192
    assert W.WORK["paged_decode_bytes"] is W.paged_decode_bytes
    with pytest.raises(KeyError):
        W.paged_decode_bytes(GRANITE, {})


def test_flops_of_a_token_by_hand():
    seen = {"decode_tokens": 1, "prompt_tokens": 0,
            "decode_context_sum": 1000, "prefill_context_sum": 0}
    want = (2.0 * 3_190_292_480 + 5.0 * 524_288 * 36
            + 4.0 * 1000 * 32 * 64 * 4)
    assert W.serve_flops(GRANITE, seen) == want
    seen = {"decode_tokens": 0, "prompt_tokens": 2,
            "decode_context_sum": 0, "prefill_context_sum": 3}
    assert W.serve_flops(GRANITE, seen) == (
        2 * (2.0 * 3_190_292_480 + 5.0 * 524_288 * 36)
        + 4.0 * 3 * 32 * 64 * 4)


# ------------------------------------------------------------------ state
def test_the_state_has_the_programs_keys_and_the_published_init():
    from paddle_tpu.models import granite_hybrid as gh
    toy = R.load_json(os.path.join(TOY, "configs", "hybrid-toy.json"))
    for conf in (GRANITE, toy):
        m = conf["model"]
        cfg = gh.GraniteHybridConfig(
            intermediate_size=m["shared_intermediate_size"],
            layer_types=tuple(m["layer_types"]),
            **{k: m[k] for k in (
                "vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_d_conv", "mamba_expand", "mamba_conv_bias")})
        mine = {k: tuple(s) for k, (s, _) in S.shapes(conf).items()}
        assert mine == gh.weight_shapes(cfg)
        assert "lm_head.weight" not in mine
    count = sum(math.prod(s) for s, _ in S.shapes(GRANITE).values())
    # the work functions leave out the vectors: norms, conv, dt, A, D
    assert count - W.params(GRANITE)["total"] == (
        2048 + 40 * 2 * 2048 + 36 * (4352 * 5 + 3 * 64 + 4096))
    made = S.seeded(dict(toy, assumed=dict(
        toy["assumed"], dt_min=0.001, dt_max=0.1, A_init_range=[1, 16])),
        2**31 + 5)
    p = "model.layers.0.mamba."
    a = np.exp(np.asarray(made[p + "A_log"], np.float64))
    assert a.min() >= 1.0 - 1e-3 and a.max() <= 16.0 + 1e-3
    dt = np.log1p(np.exp(np.asarray(made[p + "dt_bias"], np.float64)))
    assert dt.min() >= 0.001 * 0.99 and dt.max() <= 0.1 * 1.01
    assert np.all(np.asarray(made[p + "D"]) == 1.0)
    taps = np.asarray(made[p + "conv1d.weight"], np.float64)
    assert 0.2 < np.abs(taps).max() <= 0.5
    again = S.seeded(toy, 2**31 + 5)
    other = S.seeded(toy, 2**31 + 6)
    key = "model.layers.2.self_attn.q_proj.weight"
    assert np.array_equal(again[key], S.seeded(toy, 2**31 + 5)[key])
    assert not np.array_equal(again[key], other[key])


# ---------------------------------------------------------------- readers
def _trace(events):
    return {"planes": {"/device:TPU:0": events}}


def _ctx(config=GRANITE):
    return {"config": config, "peaks": PEAKS, "devices": [object()]}


def test_the_roofline_finds_its_kernel_and_no_other():
    args = load("benchmarks", "layer_metrics",
                "ssm_update_roofline.serve.json")["args"]
    events = [
        ("%ssm_state_update.7 = (bf16[36,64,128,4096]{3,2,1,0}, "
         "f32[64,1,4096]{2,1,0}) custom-call(...)", 0.0, 0.25),
        ("%ssm_state_update = (bf16[36,64,128,4096]{3,2,1,0}, "
         "f32[64,1,4096]{2,1,0}) custom-call(...)", 1.0, 1.25),
        ("%paged_attention.2 = bf16[64,4,8,128]{3,2,1,0} custom-call(...)",
         5.0, 6.0),
        ("%fusion.1 = bf16[64,8512]{1,0} fusion(...)", 7.0, 8.0)]
    # tokens whose state takes a quarter of a second at HBM's rate
    seen = {"decode_tokens": 819e9 * 0.25 / (36 * 2 * 1_048_576),
            "decode_context_sum": 819e9 * 0.25 / 8192}
    run = {"observed": seen}
    assert trace_roofline_of.read(args, run, _trace(events),
                                  _ctx()) == pytest.approx(50.0)
    # the paged kernel's share at this family's pool layout: its own
    # event, a second long, against a quarter of a second of K/V bytes
    paged = load("benchmarks", "layer_metrics",
                 "paged_attention_roofline.serve.hybrid.json")["args"]
    assert trace_roofline_of.read(paged, run, _trace(events),
                                  _ctx()) == pytest.approx(25.0)
    assert trace_roofline_of.read(paged, run, _trace(events[:2]),
                                  _ctx()) is None
    # nothing to read: no such event (the parent's program), no counter
    assert trace_roofline_of.read(args, run, _trace(events[2:]),
                                  _ctx()) is None
    assert trace_roofline_of.read(args, {"observed": {}}, _trace(events),
                                  _ctx()) is None


def test_the_whole_steps_share_and_the_live_row_share():
    args = load("benchmarks", "layer_metrics",
                "mfu.serve.hybrid.json")["args"]
    seen = {"decode_tokens": 2500, "prompt_tokens": 500,
            "decode_context_sum": 5_000_000, "prefill_context_sum": 100_000}
    run = {"observed": seen, "window_s": 1.0}
    got = rate_mfu_of.read(args, run, None, _ctx())
    assert got == pytest.approx(100.0 * W.serve_flops(GRANITE, seen)
                                / 197e12)
    assert 8 < got < 12             # 2,500 tokens/s: about a tenth
    share = load("benchmarks", "layer_metrics",
                 "ssm_live_row_share.json")["args"]
    seen = {"ssm_rows_live": 64 * 36 * 10 - 36, "decode_steps": 10,
            "max_slots": 64, "ssm_layers": 36}
    assert counters_ratio.read(share, {"observed": seen}, None,
                               {}) == pytest.approx(100 - 100 / 640)
    del seen["ssm_rows_live"]               # the parent's program
    assert counters_ratio.read(share, {"observed": seen}, None, {}) is None


# ------------------------------------------------------- the driver, toy size
@pytest.fixture(scope="module")
def lines():
    import jax
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    return {tr: R.run_cell(
        manifest, "hybrid-toy.chat", seed=2**31 + 19, seconds=1.0, trace=tr,
        devices=jax.devices()[:1], root=TOY, t_start=time.perf_counter(),
        trace_kw=CPU_TRACE, peaks=PEAKS if tr else None)
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
    # no kernel event on the CPU: the roofline is left out
    assert set(got) == {
        "slot_occupancy", "decode_step_ms", "ttft_mean_ms", "ttft_p95_ms",
        "prefill_ms_per_ktok", "device_idle_share.serve",
        "mfu.serve.hybrid", "ssm_live_row_share"}
    assert 90 < got["ssm_live_row_share"]["value"] <= 100
    assert got["ssm_live_row_share"]["value"] == pytest.approx(
        got["slot_occupancy"]["value"], abs=8)
    assert 0 < got["mfu.serve.hybrid"]["value"] < 100


@pytest.mark.parametrize("seed", [11, 13])
def test_sound_passes_and_both_controls_fail(seed):
    """One window, one sample: the program's tokens pass both limits;
    the reference with int8 projections fails, and so does the reference
    whose carried state is rounded to bfloat16 every token."""
    import jax
    from benchmarks.drivers import engine_closed_loop_hybrid as D
    from benchmarks.lib import stats
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    found = R.find_cell(manifest, "hybrid-toy.chat", TOY)
    ctx = R.cell_context(found, "hybrid-toy.chat", seed=seed, seconds=1.0,
                         devices=jax.devices()[:1])
    served = D.build(ctx)
    loop = D.Loop(served)
    loop.start()
    loop.ramp()
    # a sample that no clock decides: the requests that the ramp and
    # 600 more steps finish, a dozen of them (the timed window comes
    # after it, so that its length moves nothing that is compared)
    for _ in range(600):
        loop.resubmit(loop.step())
    finished = [r for r in loop.records if r["times"]
                and r["request"].finish_reason == "length"]
    sample = D.plain(D.sample_finished(finished, seed, 12))
    seen = D.counted_window(loop, 0.5, ctx["config"])
    # every slot is live in every step of a closed loop, but for the one
    # step between a request's end and the next one's admission
    steps = seen["decode_steps"]
    assert seen["ssm_layers"] == 3
    assert 0.9 * steps * 4 * 3 <= seen["ssm_rows_live"] <= steps * 4 * 3
    weights = served["weights"]
    sound = D.reference_gaps(ctx, weights, sample)
    assert sound["positions"] > 400
    assert stats.judge(sound, ctx["limits"])[0]
    for control in D.CONTROLS:
        found = D.reference_gaps(ctx, weights, sample, **{control: True})
        ok, checks = stats.judge(found, ctx["limits"])
        assert not ok, (control, checks)


def test_the_cells_limits_were_read_on_the_chip():
    limits = load("benchmarks", "limits", CELL + ".json")
    # the mean alone decides: the int8 control's smallest p99 is 2.5
    # times the sound runs' largest, under the three times a control
    # needs, and a traced run takes it over some 400 tokens
    assert set(limits["limits"]) == {"logit_gap_mean"}
    assert "logit_gap_p99" in limits["readings"]
    assert "PLACEHOLDER" not in limits["readings"]
    assert "int8" in limits["readings"]
    assert "state_bf16" in limits["readings"]
