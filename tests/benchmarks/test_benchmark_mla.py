"""The ``deepseek_v3`` cell's part of the yardstick on the CPU: the
configuration against the catalog's published keys, the work functions
against hand-worked numbers, the seeded state against the program's own
names, the two readers on hand-made events, and the driver through
``run_cell`` on a toy manifest (``toy_mla``)."""
import json
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
from benchmarks.lib import mla_moe_state as S  # noqa: E402
from benchmarks.lib import mla_moe_work as W  # noqa: E402
from benchmarks.lib import state, traffic  # noqa: E402
from benchmarks.readers import rate_mfu_of, trace_roofline_of  # noqa: E402

TOY = os.path.join(HERE, "toy_mla")
CELL = "gigachat3.1-702b-a36b-ep16-l5.reason-closed64"
CPU_TRACE = dict(device_plane=r"^/host:CPU$",
                 ops_line=r"XLAPjRtCpuClient|XLAEigen")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


GIGA = load("benchmarks", "configs", "gigachat3.1-702b-a36b-ep16-l5.json")
# the published settings (the catalog's ``config`` of the source)
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 64,
    "num_nextn_predict_layers": 1, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256, "ep_size": 1,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 192,
    "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 8,
    "topk_group": 4, "num_experts_per_tok": 8, "moe_layer_freq": 1,
    "first_k_dense_replace": 3, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "num_key_value_heads": 64,
    "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "rope_type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False,
    "model_type": "deepseek_v3"}


# ------------------------------------------------------- the configuration
def test_only_the_listed_keys_differ_from_the_source():
    cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 16032,
           "num_nextn_predict_layers": 0}
    assert sorted(GIGA["reduced"]) == sorted(cut)
    assert GIGA["published"] == {k: PUBLISHED[k] for k in cut}
    for key, value in PUBLISHED.items():
        assert GIGA["model"][key] == cut.get(key, value), key
        assert GIGA[key] == GIGA["model"][key], key     # the repeated keys
    assert set(GIGA["model"]) == set(PUBLISHED)
    assert GIGA["expert_parallel"] == {"chips": 16, "rank": 0}
    assert GIGA["engine"] == {"max_slots": 64, "page_size": 16,
                              "max_model_len": 4096,
                              "enable_prefix_cache": False}
    assert GIGA["assumed"]["initializer_range"] == 0.02
    # the floors: four expert layers, 8 experts, an eighth of the rows
    assert GIGA["model"]["n_routed_experts"] >= 8
    assert GIGA["model"]["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert W.expert_layers(GIGA) >= 4


def test_the_traffic_is_the_issues():
    mix = load("benchmarks", "traffic", "reason-closed64.json")
    assert mix["callers"] == 64 == GIGA["engine"]["max_slots"]
    assert mix["prompt_lengths"] == [256, 512, 1024]
    assert mix["prompt_weights"] == [0.5, 0.3, 0.2]
    assert mix["new_tokens"] == {"dist": "log_uniform", "low": 1024,
                                 "high": 3008}
    assert (mix["deck"], mix["stagger_first"], mix["check_requests"],
            mix["check_pad"], mix["trace_seconds"]) == (10, True, 8, 512, 5)
    assert not mix["shared_prefix_tokens"] and "sampling" not in mix
    gen = traffic.ClosedLoop(mix, GIGA["model"]["vocab_size"], 7)
    longest = max(p + a for p, a in gen._pairs)
    assert longest <= GIGA["engine"]["max_model_len"]
    assert np.mean([a for _, a in gen._pairs]) == pytest.approx(1840, abs=25)
    prompt, n_out, sampled = gen.next_request(0)
    assert sampled is None and prompt.max() < 16032


# ------------------------------------------------------------------- work
def test_a_layers_parts_by_hand():
    p = W.layer_params(GIGA)
    assert p["attention"] == (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                              + 512 * 64 * 320 + 64 * 192 * 7168)
    assert round(p["attention"] / 1e6, 1) == 132.6
    assert p["expert"] == p["shared"] == 3 * 7168 * 2048 == 44_040_192
    assert p["router"] == 7168 * 256            # its published width
    assert round(p["dense_mlp"] / 1e6, 1) == 396.4


def test_this_chip_holds_8_58_gb():
    p = W.params(GIGA)
    assert round(p["dense_layers"] / 1e6, 1) == 528.9
    assert round(p["expert_layers"] / 4e6, 1) == 883.1      # a layer
    assert round(p["embedding_and_head"] / 1e6, 1) == 229.8
    assert round(p["total"] / 1e9, 3) == 4.291
    assert round(W.weight_bytes(GIGA) / 1e9, 2) == 8.58


def test_the_cache_is_5760_bytes_a_token():
    assert W.cache_bytes_per_token(GIGA) == 576 * 2 * 5 == 5760
    assert W.mla_decode_bytes(GIGA, {"decode_context_sum": 1000}) == 5.76e6
    # 64 slots x 4,096 positions
    assert 64 * 4096 * 5760 == 1_509_949_440


def test_touched_experts_are_88_mb_each():
    got = W.moe_expert_bytes(GIGA, {"moe_experts_live": 14})
    assert got == 14 * 44_040_192 * 2
    with pytest.raises(KeyError):
        W.moe_expert_bytes(GIGA, {})            # a program without counters


def test_flops_of_a_token_by_hand():
    p = W.layer_params(GIGA)
    every = (5 * p["attention"] + p["dense_mlp"]
             + 4 * (p["shared"] + p["router"]) + 16032 * 7168)
    seen = {"decode_tokens": 1, "prompt_tokens": 0, "moe_local_pairs": 2,
            "decode_context_sum": 1000, "prefill_context_sum": 0}
    want = (2.0 * every + 2.0 * 2 * 44_040_192
            + 4.0 * 1000 * 64 * 192 * 5)
    assert W.serve_flops(GIGA, seen) == want
    # a prompt token brings its held share of 8 x 4 pairs: 2 of 32
    seen = {"decode_tokens": 0, "prompt_tokens": 1, "moe_local_pairs": 0,
            "decode_context_sum": 0, "prefill_context_sum": 1}
    assert W.serve_flops(GIGA, seen) == (
        2.0 * every + 2.0 * 2 * 44_040_192 + 4.0 * 64 * 192 * 5)
    assert round(2.0 * every / 1e9, 2) == 2.72  # GFLOP a token, experts apart


def test_the_state_has_the_programs_keys_and_shapes():
    from paddle_tpu.models import deepseek_v3 as ds
    toy = R.load_json(os.path.join(TOY, "configs", "mla-toy.json"))
    for conf in (GIGA, toy):
        m = conf["model"]
        cfg = ds.DeepseekV3Config(
            **{k: m[k] for k in (
                "vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_hidden_layers",
                "first_k_dense_replace", "num_attention_heads",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "n_shared_experts",
                "num_experts_per_tok", "n_group", "topk_group")},
            n_routed_experts=S.router_width(conf),
            local_experts=S.local_experts(conf))
        mine = {k: tuple(s) for k, (s, _) in S.shapes(conf).items()}
        assert mine == ds.weight_shapes(cfg)
    assert S.local_experts(GIGA) == (0, 16) and S.router_width(GIGA) == 256
    assert S.local_experts(toy) == (4, 4) and S.router_width(toy) == 16
    count = sum(int(np.prod(s)) for s, _ in S.shapes(GIGA).values())
    # the work functions leave out norm vectors and the routers' biases
    assert count - W.params(GIGA)["total"] == (
        7168 + 5 * (2 * 7168 + 1536 + 512) + 4 * 256)
    made = state.seeded(S.shapes(toy), 2**31 + 5, std=0.05,
                        dtype="bfloat16")
    bias = made["model.layers.1.mlp.gate.e_score_correction_bias"]
    assert float(np.std(np.asarray(bias, np.float32))) > 0.01


# ---------------------------------------------------------------- readers
def _trace(events):
    return {"planes": {"/device:TPU:0": events}}


def _ctx(config=GIGA):
    return {"config": config, "peaks": PEAKS, "devices": [object()]}


def test_the_rooflines_find_their_kernels_and_no_other():
    mla = load("benchmarks", "layer_metrics",
               "mla_decode_roofline.serve.json")["args"]
    moe = load("benchmarks", "layer_metrics",
               "moe_experts_roofline.serve.json")["args"]
    events = [
        ("%mla_paged_attention.7 = bf16[64,64,512]{2,1,0} custom-call(...)",
         0.0, 0.5),
        ("%grouped_matmul.3 = bf16[768,2048]{1,0} custom-call(...)", 1.0,
         1.25),
        ("%grouped_matmul.9 = bf16[768,7168]{1,0} custom-call(...)", 2.0,
         2.25),
        ("%grouped_matmul.12 = bf16[10240,2048]{1,0} custom-call(...)", 3.0,
         4.0),                              # a prefill's: not timed
        ("%paged_attention.2 = bf16[32,8,4,128]{3,2,1,0} custom-call(...)",
         5.0, 6.0),
        ("%fusion.1 = bf16[64,7168]{1,0} fusion(...)", 7.0, 8.0)]
    seen = {"decode_context_sum": 819e9 / 5760 * 0.25,
            "moe_experts_live": 819e9 / (44_040_192 * 2) * 0.25}
    run = {"observed": seen}
    assert trace_roofline_of.read(mla, run, _trace(events),
                                  _ctx()) == pytest.approx(50.0)
    assert trace_roofline_of.read(moe, run, _trace(events),
                                  _ctx()) == pytest.approx(50.0)
    # nothing to read: no such event, no trace, no peaks, no counter
    assert trace_roofline_of.read(mla, run, _trace(events[3:]),
                                  _ctx()) is None
    assert trace_roofline_of.read(mla, run, None, _ctx()) is None
    assert trace_roofline_of.read(mla, run, _trace(events),
                                  {"config": GIGA}) is None
    assert trace_roofline_of.read(
        moe, {"observed": {"decode_context_sum": 5}}, _trace(events),
        _ctx()) is None


def test_the_whole_steps_share_of_the_peak():
    args = load("benchmarks", "layer_metrics",
                "mfu.serve.mla_moe.json")["args"]
    seen = {"decode_tokens": 1000, "prompt_tokens": 0,
            "moe_local_pairs": 2000, "decode_context_sum": 2_000_000,
            "prefill_context_sum": 0}
    run = {"observed": seen, "window_s": 1.0}
    got = rate_mfu_of.read(args, run, None, _ctx())
    assert got == pytest.approx(100.0 * W.serve_flops(GIGA, seen) / 197e12)
    assert 0 < got < 100
    assert rate_mfu_of.read(args, run, None, {"config": GIGA}) is None
    del seen["moe_local_pairs"]             # the parent's program
    assert rate_mfu_of.read(args, run, None, _ctx()) is None


# ------------------------------------------------------- the driver, toy size
@pytest.fixture(scope="module")
def lines():
    import jax
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    return {tr: R.run_cell(
        manifest, "mla-toy.chat", seed=2**31 + 19, seconds=1.0, trace=tr,
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


def test_traced_toy_line_reports_the_counters_metrics(lines):
    got = lines[True]["metrics"]
    # no kernel event on the CPU: the two rooflines are left out
    assert set(got) == {
        "slot_occupancy", "decode_step_ms", "ttft_mean_ms", "ttft_p95_ms",
        "prefill_ms_per_ktok", "device_idle_share.serve",
        "mfu.serve.mla_moe", "moe_rows_per_expert", "moe_local_pair_share"}
    assert 0 < got["moe_local_pair_share"]["value"] <= 100
    assert got["moe_rows_per_expert"]["value"] > 0
    assert 0 < got["mfu.serve.mla_moe"]["value"] < 100


def test_correct_comes_out_false_with_an_expert_left_out():
    """The comparison sees a held expert that computes nothing."""
    import jax
    from benchmarks.drivers import engine_closed_loop_mla as D
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    found = R.find_cell(manifest, "mla-toy.chat", TOY)
    ctx = R.cell_context(found, "mla-toy.chat", seed=11, seconds=0.5,
                         devices=jax.devices()[:1])
    served = D.build(ctx)
    loop = D.Loop(served)
    loop.start()
    loop.ramp()
    seen = D.counted_window(loop, 0.5, ctx["config"])
    assert seen["moe_routed_pairs"] == (
        seen["decode_steps"] * 4 * 4 * 2)   # slots x choices x layers
    assert seen["moe_layer_experts"] == 2 * 4
    sample = D.plain(D.sample_finished(seen["finished"], 11, 3))
    sound = D.reference_gaps(ctx, served["weights"], sample)
    assert sound["positions"] > 0 and sound["logit_gap_mean"] < 0.02
    # the reference is handed weights whose held experts differ from the
    # ones that served: what the program left out, it finds
    broken = dict(served["weights"])
    for k in list(broken):
        if k.endswith("mlp.experts.down_proj.weight"):
            broken[k] = broken[k] * 0
    found = D.reference_gaps(ctx, broken, sample)
    assert found["logit_gap_mean"] > 10 * max(sound["logit_gap_mean"], 1e-4)


def test_the_cells_limits_were_read_on_the_chip():
    limits = load("benchmarks", "limits", CELL + ".json")
    assert set(limits["limits"]) == {"logit_gap_mean", "logit_gap_p99"}
    assert "PLACEHOLDER" not in limits["readings"]
    assert "int8" in limits["readings"]
