"""Tensor-parallel serving: mesh parity + sharded-pool invariants.

The serving/parallel ModelRunner must make the mesh invisible to the
engine: greedy decode on a tp=2/4/8 host-platform mesh is token-exact
with tp=1, the ONE-decode-trace contract survives admission/eviction on
the mesh, prefix-cache CoW and eviction-under-pressure behave
identically, and /debug/resources covers every mesh device.

XLA_FLAGS is set HERE (not only in conftest) so the module is
self-contained: ``pytest tests/test_serving_tp.py`` works without the
harness, as long as it runs before jax initializes its backends.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability.resources import resource_tracker
from paddle_tpu.serving import (GenerationConfig, ModelRunner,
                                RequestState, create_engine, parse_mesh)
from paddle_tpu.serving.parallel import mesh_devices, validate_tp

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 local devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def tp_model():
    # 8 attention heads / 8 KV heads / intermediate 128: divisible by
    # every mesh size under test (tp=2/4/8), hidden 64 -> head_dim 8
    paddle.seed(23)
    cfg = llama_tiny(vocab_size=128, hidden_size=64,
                     intermediate_size=128, num_attention_heads=8,
                     num_key_value_heads=8)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def _greedy(model, prompts, n_new, **kw):
    eng = create_engine(model, **kw)
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=n))
            for p, n in zip(prompts, n_new)]
    eng.run_until_complete(max_steps=500)
    assert all(r.state == RequestState.DONE for r in reqs)
    return eng, [r.output_tokens for r in reqs]


def test_mesh_one_shot_greedy_parity(tp_model):
    """Token-exact greedy parity tp=1 vs tp=2/4/8: the all-reduce is
    only at the attention/FFN output projections, so the sharded
    matmuls recombine to the replicated activations bit-for-bit on the
    deterministic CPU backend."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 128, int(n)).astype(np.int32)
               for n in (4, 9, 14)]
    n_new = [8, 6, 8]
    kw = dict(max_slots=4, page_size=8, max_model_len=64)
    _, ref = _greedy(tp_model, prompts, n_new, **kw)
    for tp in (2, 4, 8):
        eng, got = _greedy(tp_model, prompts, n_new, mesh=tp, **kw)
        assert got == ref, f"tp={tp} diverged from tp=1"
        assert eng.decode_traces == 1
        assert eng.stats()["mesh_tp"] == tp
        assert eng.stats()["pages_in_use"] == 0


def test_mesh_continuous_batching_parity_no_retrace(tp_model):
    """Staggered arrivals through max_slots=2 (continuous batching with
    admissions/evictions between decode steps) on a tp=2 mesh: same
    tokens as tp=1 under the same arrival schedule, and ONE decode
    trace for the engine lifetime — slot churn is data, not a shape."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 128, int(n)).astype(np.int32)
               for n in (5, 12, 7, 15, 3, 10)]
    n_new = [4, 7, 3, 6, 5, 4]

    def drive(tp):
        eng = create_engine(tp_model, max_slots=2, page_size=8,
                            max_model_len=64, sync_interval=3, mesh=tp)
        reqs, pending, steps = [], list(zip(prompts, n_new)), 0
        while pending or eng.scheduler.has_work():
            if pending:
                p, n = pending.pop(0)
                reqs.append(eng.submit(
                    p, GenerationConfig(max_new_tokens=n)))
            eng.step()
            steps += 1
            assert steps < 500
        assert all(r.state == RequestState.DONE for r in reqs)
        return eng, [r.output_tokens for r in reqs]

    e1, ref = drive(1)
    e2, got = drive(2)
    assert got == ref
    assert e1.decode_traces == e2.decode_traces == 1
    # deferred host sync batches ring drains identically on the mesh,
    # and the host lags the mesh's step as it lags one chip's: the same
    # steps dispatched ahead, the same overrun rows (one a finish, and
    # what a group of 3 rows holds past it) dropped
    assert e2.host_syncs == e1.host_syncs
    assert e2.overlapped_steps == e1.overlapped_steps > 0
    assert e2.overrun_rows == e1.overrun_rows >= len(got)
    assert e2.decode_steps == e1.decode_steps


def test_mesh_prefix_cache_cow_divergence(tp_model):
    """Prefix caching on the mesh: two prompts sharing a 19-token
    prefix that diverge in the last prompt token chain-hit 2 full pages
    and copy-on-write the shared tail — with the CoW page copy running
    as a sharded gather/scatter on the head-sharded pools — and stay
    token-exact with the uncached tp=1 reference."""
    a = np.arange(1, 21).astype(np.int32)
    b = a.copy()
    b[19] = 99
    prompts, n_new = [a, b], [6, 6]
    kw = dict(max_slots=2, page_size=8, max_model_len=64)
    _, ref = _greedy(tp_model, prompts, n_new, **kw)
    eng, got = _greedy(tp_model, prompts, n_new, mesh=2,
                       enable_prefix_cache=True, **kw)
    assert got == ref, "prefix caching on the mesh changed greedy output"
    st = eng.stats()
    assert st["prefix_hits"] == 2 and st["cow_copies"] == 1
    assert st["cached_tokens"] == 19
    assert eng.decode_traces == 1


def test_mesh_prefix_cache_eviction_under_pressure(tp_model):
    """LRU cache eviction under pool pressure on a tp=4 mesh: a
    disjoint request reclaims parked pages from the sharded pools and
    both requests still decode token-exact vs tp=1."""
    a = np.arange(1, 17).astype(np.int32)       # 2 full pages, ps=8
    d = np.arange(40, 64).astype(np.int32)      # disjoint, 3 pages
    kw = dict(max_slots=1, page_size=8, num_pages=4, max_model_len=32)
    _, ref = _greedy(tp_model, [a, d], [8, 8], **kw)

    eng = create_engine(tp_model, enable_prefix_cache=True, mesh=4,
                        **kw)
    ra = eng.submit(a, GenerationConfig(max_new_tokens=8))
    eng.run_until_complete(max_steps=100)
    assert eng.stats()["cached_pages"] == 2
    rd = eng.submit(d, GenerationConfig(max_new_tokens=8))
    eng.run_until_complete(max_steps=100)
    assert [ra.output_tokens, rd.output_tokens] == ref
    assert eng.stats()["prefix_evictions"] >= 1
    assert eng.decode_traces == 1


def test_mesh_info_and_resource_snapshot(tp_model):
    """/debug/resources coverage: mesh_info lists every mesh device
    with its tp position and per-device footprint estimates, the
    engine snapshot embeds it, and the process-wide resource tracker
    carries the mesh annotation for each device."""
    eng1, _ = _greedy(tp_model, [np.arange(1, 9).astype(np.int32)],
                      [4], max_slots=2, page_size=8, max_model_len=64)
    full = eng1.runner.mesh_info()["devices"][0]["kv_pool_bytes"]
    # tp=4 AFTER tp=1: the runner registers its mesh positions with the
    # process-wide tracker at construction; latest engine wins
    eng, _ = _greedy(tp_model, [np.arange(1, 9).astype(np.int32)], [4],
                     mesh=4, max_slots=2, page_size=8, max_model_len=64)
    info = eng.runner.mesh_info()
    assert info["tp"] == 4 and info["axis"] == "tp"
    assert len(info["devices"]) == 4
    for i, dev in enumerate(info["devices"]):
        assert dev["tp"] == i
        assert ":" in dev["device"]
        assert dev["kv_pool_bytes"] > 0
        assert dev["weight_bytes"] > 0
    # the pool shard is 1/4 of the tp=1 pool for this config (kvh=8)
    assert info["devices"][0]["kv_pool_bytes"] == full // 4

    snap = eng.resource_snapshot()
    assert snap["mesh"]["tp"] == 4
    assert len(snap["mesh"]["devices"]) == 4

    tracked = resource_tracker().snapshot()["memory"]["devices"]
    for dev in info["devices"]:
        assert tracked[dev["device"]]["mesh"] == {"tp": dev["tp"]}


def test_mesh_spec_parsing_and_validation(tp_model):
    assert parse_mesh(None) == 1
    assert parse_mesh(4) == 4
    assert parse_mesh("4") == 4
    assert parse_mesh("tp=2") == 2
    assert parse_mesh((8,)) == 8
    with pytest.raises(ValueError, match="mesh"):
        parse_mesh("dp=2")
    with pytest.raises(ValueError, match="mesh"):
        parse_mesh((2, 4))
    with pytest.raises(ValueError, match=">= 1"):
        parse_mesh(0)

    # divisibility contract: nh=8/kvh=8/inter=128 reject tp=3 loudly
    with pytest.raises(ValueError, match="must divide"):
        validate_tp(tp_model.config, 3)
    with pytest.raises(ValueError, match="divide"):
        create_engine(tp_model, max_slots=2, page_size=8,
                      max_model_len=32, mesh=3)
    # more devices than the backend exposes
    with pytest.raises(ValueError, match="devices"):
        mesh_devices(jax.device_count() + 1)


def test_mesh_rejects_fused_and_quantized_state(tp_model):
    """tp>1 shards per-projection q/k/v and gate/up weights; fused or
    quantized states cannot be head-sharded and must fail at
    construction, not as a shape error mid-trace."""
    state = dict(tp_model.functional_state())
    kw = dict(tp=2, max_slots=2, page_size=8, table_width=4,
              num_pages=8, dump_page=8)

    fused = dict(state)
    fused["llama.layers.0.self_attn.qkv_fused.weight"] = (
        np.zeros((64, 192), np.float32))
    with pytest.raises(ValueError, match="fused"):
        ModelRunner(tp_model.config, fused, **kw)

    quant = dict(state)
    quant["llama.layers.0.self_attn.q_proj.weight"] = object()
    with pytest.raises(ValueError, match="not an array"):
        ModelRunner(tp_model.config, quant, **kw)
