"""The yardstick's arithmetic: work functions against hand-worked
numbers, the seeded state against the program's own key names, the
traffic generator, and the trace reduction on hand-made events and on a
trace recorded on the CPU."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import (peaks, state, stats, traffic, work,  # noqa: E402
                            xplane)


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def mix(name):
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           name + ".json")) as f:
        return json.load(f)


MISTRAL = config("mistral-7b-v0.3-l16")["model"]
BERT = config("bert-large-uncased")["model"]


# ------------------------------------------------------------------ work
def test_mistral_layer_is_218_11_million():
    p = work.decoder_layer_params(MISTRAL)
    assert p["attention"] == 41_943_040 and p["mlp"] == 176_160_768
    assert round(p["total"] / 1e6, 2) == 218.10 or p["total"] == 218_103_808


def test_mistral_16_layers_are_7_52_gb():
    p = work.decoder_params(MISTRAL)
    assert p["embedding"] == p["head"] == 134_217_728
    assert round(p["total"] / 1e9, 3) == 3.758
    assert round(work.decoder_weight_bytes(MISTRAL) / 1e9, 2) == 7.52


def test_mistral_kv_is_65536_bytes_a_token():
    assert work.kv_bytes_per_token(MISTRAL) == 65_536
    assert work.paged_decode_bytes(MISTRAL, 1000) == 65_536_000.0


def test_decoder_flops_by_hand():
    m = dict(hidden_size=8, intermediate_size=16, num_hidden_layers=2,
             num_attention_heads=2, num_key_value_heads=1, head_dim=4,
             vocab_size=10)
    layer = 2 * 8 * 8 + 2 * 8 * 4 + 3 * 8 * 16          # 576
    assert work.decoder_layer_params(m)["total"] == layer
    # 3 tokens; contexts 1 + 2 + 3
    want = 2.0 * 3 * (2 * layer + 80) + 4.0 * 6 * 2 * 4 * 2
    assert work.decoder_flops(m, 3, work.prefill_context_sum(3)) == want


def test_bert_large_step_is_11_8_tflop():
    assert round(work.bert_matrix_params(BERT) / 1e6) == 302
    assert round(work.bert_params(BERT) / 1e6) == 335
    step = work.bert_train_flops_per_step(BERT, 16, 384)
    assert round(step / 1e12, 1) == 11.8
    attn = work.bert_attention_flops(BERT, 16, 384, backward=True)
    assert round(attn / 1e12, 1) == 0.7
    assert attn == 3 * work.bert_attention_flops(BERT, 16, 384,
                                                 backward=False)


@pytest.mark.parametrize("name,seen,want", [
    ("bert_train", dict(steps=2, batch=16, seq=384),
     2 * work.bert_train_flops_per_step(BERT, 16, 384)),
    ("bert_attention_train", dict(steps=1, batch=16, seq=384),
     work.bert_attention_flops(BERT, 16, 384, backward=True)),
])
def test_window_work_of_training(name, seen, want):
    assert work.window_work(name, BERT, seen) == want


def test_window_work_of_serving_and_unknown_names():
    seen = dict(decode_tokens=10, prompt_tokens=5, decode_context_sum=70,
                prefill_context_sum=15)
    assert work.window_work("decoder_serve", MISTRAL, seen) == \
        work.decoder_flops(MISTRAL, 15, 85)
    assert work.window_work("paged_decode_bytes", MISTRAL, seen) == \
        70 * 65_536
    with pytest.raises(KeyError):
        work.window_work("nothing", MISTRAL, seen)


def test_peaks_are_keyed_by_device_kind():
    row = peaks.peaks_of("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert row["source"]
    with pytest.raises(KeyError, match="do not guess"):
        peaks.peaks_of("cpu")


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 0.95) == 95
    assert stats.median([3, 1, 2]) == 2
    assert np.isnan(stats.percentile([], 0.5))


# ----------------------------------------------------------------- state
def test_decoder_state_has_the_programs_keys_shapes_and_dtype():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    cfg = llama_tiny()
    cfg.dtype = "bfloat16"
    want = {k: (tuple(v.shape), str(getattr(v, "_data", v).dtype))
            for k, v in LlamaForCausalLM(cfg).functional_state().items()}
    m = dict(vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
             intermediate_size=cfg.intermediate_size,
             num_hidden_layers=cfg.num_hidden_layers,
             num_attention_heads=cfg.num_attention_heads,
             num_key_value_heads=cfg.num_key_value_heads)
    got = state.decoder_state(m, 5)
    assert {k: (tuple(v.shape), str(v.dtype))
            for k, v in got.items()} == want


def test_bert_state_has_the_programs_parameters():
    from paddle_tpu.models.bert import (BertConfig,
                                        BertForSequenceClassification)
    m = dict(vocab_size=100, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=2, intermediate_size=64,
             max_position_embeddings=48, type_vocab_size=2)
    model = BertForSequenceClassification(BertConfig(
        num_labels=3, **m))
    want = {k: (tuple(p.shape), str(p._data.dtype))
            for k, p in model.named_parameters()}
    got = state.bert_state(m, 5, num_labels=3)
    assert {k: (tuple(v.shape), str(v.dtype))
            for k, v in got.items()} == want
    assert sum(v.size for v in got.values()) == work.bert_params(m, 3)


def test_state_repeats_for_a_seed_and_differs_between_seeds():
    m = dict(vocab_size=64, hidden_size=16, intermediate_size=32,
             num_hidden_layers=1, num_attention_heads=2,
             num_key_value_heads=1)
    big = 2**31 + 12345                       # the driver's seeds are large
    a, b, c = (state.decoder_state(m, s) for s in (big, big, big + 1))
    key = "llama.layers.0.mlp.up_proj.weight"
    assert np.array_equal(np.asarray(a[key], np.float32),
                          np.asarray(b[key], np.float32))
    assert not np.array_equal(np.asarray(a[key], np.float32),
                              np.asarray(c[key], np.float32))
    w = np.asarray(a[key], np.float32)
    assert abs(w.std() - 0.02) < 0.004 and abs(w).max() > 0.02 * 2
    assert np.all(np.asarray(a["llama.norm.weight"], np.float32) == 1.0)
    assert not np.array_equal(
        np.asarray(a[key], np.float32),
        np.asarray(a["llama.layers.0.mlp.gate_proj.weight"], np.float32))


# --------------------------------------------------------------- traffic
def drain(loop, n):
    return [loop.next_request(i % loop.callers) for i in range(n)]


def test_closed_loop_repeats_for_a_seed_and_differs_between_seeds():
    spec = mix("chat-closed32")
    a = drain(traffic.ClosedLoop(spec, 32768, 2**31 + 5), 50)
    b = drain(traffic.ClosedLoop(spec, 32768, 2**31 + 5), 50)
    c = drain(traffic.ClosedLoop(spec, 32768, 2**31 + 6), 50)
    assert all(np.array_equal(x[0], y[0]) and x[1:] == y[1:]
               for x, y in zip(a, b))
    assert any(x[0].size != y[0].size or x[1] != y[1]
               or not np.array_equal(x[0], y[0]) for x, y in zip(a, c))


def test_every_seed_plays_the_same_deck():
    spec = mix("chat-closed32")
    assert spec["stagger_first"] is True
    decks, firsts = [], []
    for seed in (1, 2, 3):
        loop = traffic.ClosedLoop(spec, 32768, seed)
        # the callers' first answers are cut short; then to a deck's end
        first = drain(loop, loop.callers)
        firsts.append([r[1] for r in first])
        drain(loop, -loop.callers % spec["deck"])
        reqs = drain(loop, spec["deck"])
        decks.append((sorted(r[0].size for r in reqs),
                      sorted(r[1] for r in reqs)))
        assert all(r[2] is None for r in reqs)          # greedy
        assert all(0 <= r[0].min() and r[0].max() < 32768 for r in reqs)
    assert decks[0] == decks[1] == decks[2]
    prompts, answers = decks[0]
    n = spec["deck"] // 10
    assert prompts.count(128) == 5 * n and prompts.count(256) == 3 * n \
        and prompts.count(512) == 2 * n
    assert 48 <= answers[0] and answers[-1] <= 384
    assert 150 <= np.mean(answers) <= 175               # log-uniform mean
    assert 512 + answers[-1] <= 1024
    for cut in firsts:      # spread fractions: about half, none under 2
        assert min(cut) >= 2 and max(cut) <= answers[-1]
        assert 0.3 * np.mean(answers) < np.mean(cut) < 0.7 * np.mean(answers)


def test_sessions_grow_a_history_on_a_shared_prefix():
    spec = dict(callers=2, prompt_lengths=[8, 12], prompt_weights=[1, 1],
                new_tokens={"dist": "uniform", "low": 2, "high": 4},
                deck=4, shared_prefix_tokens=16, snap_to=4,
                turns={"low": 3, "high": 3})
    loop = traffic.ClosedLoop(spec, 100, 9)
    p1, n1, _ = loop.next_request(0)
    answer = list(range(n1))
    p2, _, _ = loop.next_request(0, answer)
    other, _, _ = loop.next_request(1)
    assert p1.size % 4 == 0 and p2.size % 4 == 0 and p2.size > p1.size
    assert np.array_equal(p1[:16], loop.shared)
    assert np.array_equal(other[:16], loop.shared)
    assert np.array_equal(p2[:p1.size], p1)
    assert list(p2[p1.size:p1.size + n1]) == answer


def test_sampled_mix_keeps_a_greedy_share():
    spec = dict(mix("chat-closed32"),
                sampling={"top_p": 0.9, "greedy_share": 0.25})
    reqs = drain(traffic.ClosedLoop(spec, 1000, 4), 200)
    greedy = sum(r[2] is None for r in reqs)
    assert 20 < greedy < 90
    assert all(r[2]["top_p"] == 0.9 for r in reqs if r[2])


def test_lengths_and_counts():
    assert traffic.weighted_counts([0.5, 0.3, 0.2], 40) == [20, 12, 8]
    assert sum(traffic.weighted_counts([1, 1, 1], 10)) == 10
    assert traffic.quantile_lengths(
        {"dist": "fixed", "low": 7, "high": 7}, 3) == [7, 7, 7]
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "zipf", "low": 1, "high": 2}, 3)


def test_train_pool_rows_all_differ():
    spec = dict(pool=4, batch=4, seq=16)
    ids, ys = traffic.train_pool(spec, 1000, 2, 2**31 + 7)
    again, _ = traffic.train_pool(spec, 1000, 2, 2**31 + 7)
    other, _ = traffic.train_pool(spec, 1000, 2, 2**31 + 8)
    assert ids.shape == (4, 4, 16) and ys.shape == (4, 4)
    rows = np.asarray(ids).reshape(16, 16)
    assert len({tuple(r) for r in rows}) == 16
    assert np.array_equal(np.asarray(ids), np.asarray(again))
    assert not np.array_equal(np.asarray(ids), np.asarray(other))


def test_stated_labels_are_every_batchs_labels():
    spec = mix("finetune-b16s384")
    assert len(spec["labels"]) == spec["batch"]
    assert set(spec["labels"][:spec["batch"] // 2]) == {1}  # half differs
    small = dict(spec, pool=3, seq=8)
    _, a = traffic.train_pool(small, 100, 2, 5)
    _, b = traffic.train_pool(small, 100, 2, 6)
    assert np.array_equal(np.asarray(a), np.asarray(b))     # whatever seed
    assert np.asarray(a).tolist() == [spec["labels"]] * 3
    with pytest.raises(ValueError):
        traffic.train_pool(dict(small, labels=[0, 1]), 100, 2, 5)
    with pytest.raises(ValueError):
        traffic.train_pool(dict(small, labels=[2] * spec["batch"]), 100, 2, 5)


# ---------------------------------------------------------------- xplane
EVENTS = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("a", 3.0, 4.0),
          ("kern | jit(step)/attn", 6.0, 6.5)]
SPANS = [("bench.window", 0.0, 10.0), ("bench.engine.step", 2.0, 3.2),
         ("bench.submit", 2.1, 2.9), ("bench.next_batch", 4.0, 6.0)]


def test_union_and_busy_seconds():
    assert xplane.union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == \
        [[0, 2], [3, 4]]
    assert xplane.busy_seconds(EVENTS) == 3.5
    assert xplane.busy_seconds(xplane.clip(EVENTS, 0.25, 3.5)) == 2.25


def test_gaps_and_their_names():
    assert xplane.gaps(EVENTS, 0.0, 10.0) == \
        [[2.0, 3.0], [4.0, 6.0], [6.5, 10.0]]
    named = xplane.longest_gaps(EVENTS, SPANS[1:], 0.0, 10.0, 5)
    assert named == [["(no span)", 3.5], ["bench.next_batch", 2.0],
                     ["bench.submit", 1.0]]     # the innermost span
    assert xplane.longest_gaps(EVENTS, SPANS[1:], 0.0, 10.0, 1) == \
        [["(no span)", 3.5]]


def test_top_ops_and_matching_seconds():
    assert xplane.top_ops(EVENTS, 2) == [["a", 2.0], ["b", 1.5]]
    assert xplane.matching_seconds(EVENTS, ["attn"]) == 0.5
    assert xplane.matching_seconds(EVENTS, ["^a$", "^b"]) == 3.5
    assert xplane.matching_seconds(EVENTS, ["_paged_kernel"]) == 0.0
    assert xplane.window_of(SPANS, "bench.window") == (0.0, 10.0)
    with pytest.raises(LookupError):
        xplane.window_of(SPANS, "bench.nothing")


# device events as the v5e's trace names them (my chip run, PR 25): the
# whole HLO instruction, with no kernel name in it
PAGED = ('%step.16 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)} custom-call('
         's32[32,64]{1,0:T(8,128)} %table.1, s32[32]{0:T(128)} '
         '%get-tuple-element.140, bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)} '
         '%bitcast.643, bf16[2049,8,16,128]{3,2,1,0:T(8,128)(2,1)} %fusion.4, '
         'bf16[2049,8,16,128]{3,2,1,0:T(8,128)(2,1)} %fusion.5), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
PAGED_LATER = PAGED.replace("%step.16", "%step.28").replace(
    "{1,0:T(8,128)} %table.1", "{1,0:T(8,128)S(1)} %copy-done.4").replace(
    "{0:T(128)} %get-tuple-element.140", "{0:T(128)S(1)} %copy-done.45")
FLASH = ('%prefill.3 = bf16[1,32,512,128]{3,2,1,0:T(8,128)(2,1)} custom-call('
         'bf16[1,32,512,128]{3,2,1,0:T(8,128)(2,1)} %bitcast.7, '
         'bf16[1,8,512,128]{3,2,1,0:T(8,128)(2,1)} %fusion.9), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
ADAMW = ('%divide_subtract_fusion.1 = (f32[4096,1024]{1,0:T(8,128)}, '
         'f32[4096,1024]{1,0:T(8,128)}) fusion(f32[4096,1024]{1,0:T(8,128)S(1)} '
         '%custom-call.272, f32[]{:T(128)S(6)} %select.623), kind=kOutput, '
         'calls=%fused_computation.2622')


def metric_events(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)["args"]["events"]


def test_roofline_patterns_find_their_kernels_and_no_other():
    events = [(PAGED, 0.0, 1.0), (PAGED_LATER, 1.0, 3.0),
              (FLASH, 3.0, 3.5), (ADAMW, 4.0, 8.0)]
    paged = metric_events("paged_attention_roofline.serve")
    flash = metric_events("flash_attention_roofline.train")
    assert xplane.matching_seconds(events, paged) == 3.0
    assert xplane.matching_seconds(events, flash) == 3.5   # every Mosaic call
    assert xplane.matching_seconds([(ADAMW, 0.0, 1.0)], paged + flash) == 0.0


def test_short_names_add_the_layers_up():
    assert xplane.short_name(PAGED) == xplane.short_name(PAGED_LATER) == \
        "%step bf16[32,8,4,128]"
    assert xplane.short_name(ADAMW) == \
        "%divide_subtract_fusion (f32[4096,1024]"
    assert xplane.short_name("Thunk:dot | jit(f)/dot_general") == "Thunk:dot"
    assert xplane.top_ops([(PAGED, 0.0, 1.0), (PAGED_LATER, 1.0, 3.0),
                           (ADAMW, 3.0, 4.0)], 1) == \
        [["%step bf16[32,8,4,128]", 3.0]]


def test_reduce_reads_a_trace_recorded_here(tmp_path):
    """The reader on a real trace, recorded on the CPU: the benchmark's
    spans are found, the thunks inside the window are the busy time."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    xplane.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(4):
                with jax.profiler.TraceAnnotation("bench.train.step"):
                    f(x).block_until_ready()
    finally:
        xplane.stop()
    got = xplane.reduce(str(tmp_path), device_plane=r"^/host:CPU$",
                        ops_line=r"XLAPjRtCpuClient|XLAEigen")
    assert 0 < got["busy_s"] <= got["window_s"]
    assert sum(n == "bench.train.step" for n, _, _ in got["spans"]) == 4
    names = [n for n, _ in got["breakdown"]["device_ops"]]
    assert any("dot" in n for n in names)
    assert len(got["breakdown"]["idle_gaps"]) <= 5
    with pytest.raises(LookupError):
        xplane.reduce(str(tmp_path))        # no TPU plane in a CPU trace
    with pytest.raises(FileNotFoundError):
        xplane.find_xplane(str(tmp_path / "nothing"))
