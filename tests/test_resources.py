"""Resource observatory (paddle_tpu/observability/resources.py).

Covers the process-wide ResourceTracker (goodput math, throughput/MFU,
memory sampling, compile ledger), the block manager's exact pool
accounting (the live+cached+free census invariant across admission,
CoW, eviction and rollback; fragmentation bands; per-seq footprints),
the engine/server integration (`resource_snapshot`, the
``GET /debug/resources`` endpoint, watchdog dumps embedding a
snapshot), and the resources.json dump + report rendering.
"""
import importlib.util
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.flags import FLAGS
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability.registry import default_registry
from paddle_tpu.observability.resources import (CompileLedger,
                                                resource_tracker)
from paddle_tpu.serving import (BlockManager, GenerationConfig,
                                ServingClient, Watchdog, create_engine,
                                serve)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(5)
    cfg = llama_tiny(vocab_size=128, hidden_size=64,
                     intermediate_size=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture()
def flag(request):
    """Set a FLAGS entry for one test and restore it afterwards."""
    saved = {}

    def _set(name, value):
        if name not in saved:
            saved[name] = FLAGS[name]
        FLAGS[name] = value

    yield _set
    FLAGS.update(saved)


# ------------------------------------------------------ resource tracker
class TestResourceTracker:
    def test_goodput_math(self):
        obs.reset()
        t = resource_tracker()
        assert t.snapshot()["goodput"]["ratio"] is None  # no finishes yet
        t.note_finish("length", 6)
        t.note_finish("eos", 3)
        t.note_finish("cancelled", 2)
        t.note_finish("deadline", 1)
        g = t.snapshot()["goodput"]
        assert g["useful_tokens"] == 9
        assert g["wasted_tokens"] == 3
        assert g["ratio"] == 9 / 12
        assert g["finishes"] == {"length": 1, "eos": 1,
                                 "cancelled": 1, "deadline": 1}
        # the same split lands on the serving_goodput_* metrics
        fam = default_registry().get("serving_goodput_tokens_total")
        assert fam.labels("useful").value == 9
        assert fam.labels("wasted").value == 3
        assert default_registry().get(
            "serving_goodput_ratio").value == pytest.approx(0.75)

    def test_throughput_and_mfu(self):
        obs.reset()
        t = resource_tracker()
        t.set_model(n_params=10**9, device_kind="TPU v5e")
        t.note_phase("decode", 1.5)
        t.note_phase("host_sync", 0.5)
        t.note_tokens(100)
        tp = t.snapshot()["throughput"]
        assert tp["tokens"] == 100
        assert tp["tokens_per_s"] == pytest.approx(50.0)
        assert tp["peak_flops"] == pytest.approx(197e12)
        # decode ~2 FLOPs/param/token
        assert tp["mfu"] == pytest.approx(50.0 * 2 * 10**9 / 197e12,
                                          abs=1e-6)

    def test_mfu_none_on_unknown_device(self):
        obs.reset()
        t = resource_tracker()
        t.set_model(n_params=1000, device_kind="cpu")
        t.note_phase("decode", 1.0)
        t.note_tokens(10)
        tp = t.snapshot()["throughput"]
        assert tp["peak_flops"] is None
        assert tp["mfu"] is None

    def test_peak_tflops_flag_overrides_device_table(self, flag):
        obs.reset()
        flag("FLAGS_resource_peak_tflops", 2.0)
        t = resource_tracker()
        t.set_model(n_params=10**9, device_kind="cpu")  # unknown kind
        t.note_phase("decode", 1.0)
        t.note_tokens(10)
        tp = t.snapshot()["throughput"]
        assert tp["peak_flops"] == pytest.approx(2e12)
        assert tp["mfu"] == pytest.approx(0.01)   # 10 tok/s * 2e9 / 2e12

    def test_sample_memory_never_raises_and_records_rss(self):
        obs.reset()
        t = resource_tracker()
        t.sample_memory()                   # CPU backend: no device stats
        mem = t.snapshot()["memory"]
        assert mem["samples"] == 1
        assert isinstance(mem["devices"], dict)
        assert mem["host_rss_bytes"] > 0    # linux /proc probe
        assert default_registry().get("host_rss_bytes").value > 0

    def test_compile_ledger(self):
        obs.reset()
        led = CompileLedger()
        led.record("decode_step", 0.25, "slots=4")
        led.record("decode_step", 0.5, "slots=4")       # dup signature
        led.record("prefill[8]", -1.0, "ids=[1,8]")     # clamped to 0
        snap = led.snapshot()
        assert snap["jits"]["decode_step"]["count"] == 2
        assert snap["jits"]["decode_step"]["seconds"] == pytest.approx(0.75)
        assert snap["jits"]["decode_step"]["signatures"] == ["slots=4"]
        assert snap["jits"]["prefill[8]"]["seconds"] == 0.0
        assert snap["total_compiles"] == 3
        assert snap["total_seconds"] == pytest.approx(0.75)

    def test_obs_reset_clears_tracker(self):
        t = resource_tracker()
        t.note_tokens(5)
        t.note_finish("length", 5)
        t.compiles.record("decode_step", 0.1)
        obs.reset()
        snap = t.snapshot()
        assert snap["throughput"]["tokens"] == 0
        assert snap["goodput"]["ratio"] is None
        assert snap["compiles"]["total_compiles"] == 0


# --------------------------------------------------- pool accounting
def _census_ok(bm):
    acc = bm.pool_accounting()
    assert acc["leak"] == 0
    assert acc["live"] + acc["cached"] + acc["free"] == acc["total"]
    return acc


class TestBlockManagerAccounting:
    def test_census_invariant_across_lifecycle(self):
        bm = BlockManager(num_pages=8, page_size=4,
                          enable_prefix_cache=True)
        _census_ok(bm)
        A = tuple(range(100, 112))              # 3 full chunks
        bm.allocate_seq(0, A, max_new_tokens=4)     # 4 pages, all fresh
        acc = _census_ok(bm)
        assert acc == {"live": 4, "cached": 0, "free": 4, "total": 8,
                       "allocated_total": 4, "host_parked": 0, "leak": 0}
        # same prompt while A is live: shares 2 chain pages, acquires 2
        bm.allocate_seq(1, A, max_new_tokens=4)
        acc = _census_ok(bm)
        assert acc["live"] == 6                 # shared pages counted once
        assert acc["allocated_total"] == 6      # only fresh pages counted
        bm.free_seq(0)
        acc = _census_ok(bm)
        # A's registered 3rd chunk parks; its decode page frees
        assert acc["cached"] == 1 and acc["live"] == 4
        bm.free_seq(1)
        acc = _census_ok(bm)
        assert acc["live"] == 0
        # eviction under pressure: a disjoint prompt recycles LRU pages
        bm.allocate_seq(2, tuple(range(200, 212)), max_new_tokens=16)
        _census_ok(bm)
        bm.free_seq(2)
        _census_ok(bm)

    def test_rollback_not_counted_as_allocation(self):
        bm = BlockManager(num_pages=4, page_size=4,
                          enable_prefix_cache=True)
        A = tuple(range(10, 18))
        bm.allocate_seq(0, A, max_new_tokens=4)     # 3 pages
        assert bm.pages_allocated == 3
        # the suffix does not fit -> None; refs roll back, nothing counted
        assert bm.allocate_seq(1, A + tuple(range(90, 98)),
                               max_new_tokens=8) is None
        assert bm.pages_allocated == 3
        _census_ok(bm)
        bm.free_seq(0)
        _census_ok(bm)

    def test_free_pages_gauge_tracks_free_list(self):
        obs.reset()
        bm = BlockManager(num_pages=8, page_size=4)
        bm.allocate(0, 3)
        assert default_registry().get("serving_pages_free").value == 5
        bm.free_seq(0)
        assert default_registry().get("serving_pages_free").value == 8
        assert default_registry().get(
            "serving_pages_allocated_total").value == 3

    def test_fragmentation_zero_bands(self):
        bm = BlockManager(num_pages=4, page_size=4)
        assert bm.fragmentation(None) == 0.0    # nothing waiting
        assert bm.fragmentation(0) == 0.0
        assert bm.fragmentation(3) == 0.0       # all-free pool: usable
        bm.allocate(0, 4)
        assert bm.fragmentation(1) == 0.0       # idle == 0

    def test_fragmentation_one_when_unplaceable(self):
        bm = BlockManager(num_pages=4, page_size=4)
        bm.allocate(0, 3)
        # 1 idle page, request needs 2 -> every idle page is unusable
        assert bm.fragmentation(2) == 1.0

    def test_fragmentation_all_parked_pages_reclaimable(self):
        bm = BlockManager(num_pages=4, page_size=4,
                          enable_prefix_cache=True)
        bm.allocate_seq(0, tuple(range(50, 62)), max_new_tokens=4)
        bm.free_seq(0)                          # 3 parked chain pages
        # leaf-first peeling reclaims the whole parked chain
        assert bm.fragmentation(4) == 0.0
        _census_ok(bm)

    def test_fragmentation_pinned_parent_middle_band(self):
        # White-box: a parked parent whose cached child is LIVE cannot
        # be evicted (leaf-first), so it is idle-but-unusable.  Normal
        # admission always refs prefixes ahead of suffixes, so wire the
        # pathological shape directly.
        from collections import OrderedDict
        bm = BlockManager(num_pages=4, page_size=4,
                          enable_prefix_cache=True)
        bm._free = [2, 3]
        bm._lru = OrderedDict({0: None})        # page 0 parked
        bm._ref = {1: 1}                        # page 1 live
        bm._tables = {7: [1]}
        bm._children = {0: {1}}                 # 0's child is the live 1
        bm._key_of = {0: ((), tuple(range(4)))}
        # idle = 2 free + 1 parked; usable = 2 (page 0 pinned)
        assert bm._reclaimable() == 0
        assert bm.fragmentation(2) == pytest.approx(1 / 3)
        assert bm.fragmentation(3) == 1.0       # cannot place at all

    def test_record_fragmentation_publishes_gauge(self):
        obs.reset()
        bm = BlockManager(num_pages=4, page_size=4)
        bm.allocate(0, 3)
        ratio = bm.record_fragmentation(2)
        assert ratio == 1.0
        assert default_registry().get(
            "serving_page_fragmentation_ratio").value == 1.0

    def test_seq_footprint_shared_vs_exclusive(self):
        bm = BlockManager(num_pages=8, page_size=4,
                          enable_prefix_cache=True)
        A = tuple(range(100, 112))
        bm.allocate_seq(0, A, max_new_tokens=4)
        bm.allocate_seq(1, A, max_new_tokens=4)
        fp = bm.seq_footprint(1)
        assert fp == {"pages": 4, "shared": 2, "exclusive": 2,
                      "cached_len": 8, "committed_tokens": 12,
                      "committed_pages": 3}
        bm.free_seq(0)
        fp = bm.seq_footprint(1)
        assert fp["shared"] == 0 and fp["exclusive"] == 4
        assert bm.seq_footprint(99) == {"pages": 0, "shared": 0,
                                        "exclusive": 0, "cached_len": 0,
                                        "committed_tokens": 0,
                                        "committed_pages": 0}


# ------------------------------------------------- engine integration
class TestEngineResources:
    def test_resource_snapshot_and_compile_ledger(self, tiny_model):
        obs.reset()
        eng = create_engine(tiny_model, max_slots=2, page_size=16,
                            num_pages=64, max_model_len=128,
                            enable_prefix_cache=True)
        shared = np.arange(1, 20)
        a = eng.submit(shared, GenerationConfig(max_new_tokens=4))
        b = eng.submit(np.concatenate([shared, [21, 22]]),
                       GenerationConfig(max_new_tokens=4))
        eng.run_until_complete(max_steps=100)
        assert a.finish_reason == "length" and b.finish_reason == "length"

        snap = eng.resource_snapshot()
        assert snap["pool"]["leak"] == 0
        assert snap["pool"]["live"] == 0        # all requests finalized
        assert snap["pool"]["allocated_total"] > 0
        assert snap["requests"] == {}
        assert snap["counters"]["decode_steps"] > 0
        assert snap["counters"]["decode_traces"] == 1
        assert snap["counters"]["pages_allocated"] == \
            snap["pool"]["allocated_total"]
        for phase in ("prefill_s", "decode_s", "host_sync_s"):
            assert snap["timings"][phase] > 0.0

        st = eng.stats()
        assert st["decode_steps"] == snap["counters"]["decode_steps"]
        assert st["pages_allocated"] == snap["pool"]["allocated_total"]
        assert st["timings"] == snap["timings"]

        tr = resource_tracker().snapshot()
        jits = tr["compiles"]["jits"]
        assert "decode_step" in jits
        assert any(k.startswith("prefill[") for k in jits)
        assert all(v["seconds"] >= 0 for v in jits.values())
        assert tr["goodput"]["ratio"] == 1.0    # both finished by length
        assert tr["goodput"]["useful_tokens"] == 8
        assert tr["throughput"]["tokens"] == 8
        assert tr["throughput"]["n_params"] > 0
        assert tr["throughput"]["mfu"] is None  # cpu: no peak table entry
        # pool gauges read back through the registry match the engine
        assert tr["pool"]["total"] == 64
        assert tr["pool"]["in_use"] == 0

    def test_memory_polling_follows_flag(self, tiny_model, flag):
        obs.reset()
        flag("FLAGS_resource_memory_poll_steps", 1)   # poll every sync
        eng = create_engine(tiny_model, max_slots=1, page_size=16,
                            num_pages=32, max_model_len=64)
        eng.submit(np.arange(1, 6), GenerationConfig(max_new_tokens=3))
        eng.run_until_complete(max_steps=50)
        assert resource_tracker().snapshot()["memory"]["samples"] > 0

        obs.reset()
        flag("FLAGS_resource_memory_poll_steps", 0)   # disabled
        eng = create_engine(tiny_model, max_slots=1, page_size=16,
                            num_pages=32, max_model_len=64)
        eng.submit(np.arange(1, 6), GenerationConfig(max_new_tokens=3))
        eng.run_until_complete(max_steps=50)
        assert resource_tracker().snapshot()["memory"]["samples"] == 0

    def test_cancel_counts_as_wasted(self, tiny_model):
        obs.reset()
        eng = create_engine(tiny_model, max_slots=1, page_size=16,
                            num_pages=32, max_model_len=64)

        def cancel_after_2(req, tok):
            if req.num_generated >= 2:
                req.cancel()

        r = eng.submit(np.arange(1, 6),
                       GenerationConfig(max_new_tokens=20),
                       on_token=cancel_after_2)
        eng.run_until_complete(max_steps=100)
        assert r.finish_reason == "cancelled"
        g = resource_tracker().snapshot()["goodput"]
        assert g["useful_tokens"] == 0
        assert g["wasted_tokens"] == r.num_generated
        assert g["ratio"] == 0.0


# ------------------------------------------------------ server + watchdog
class _FakeEngine:
    def __init__(self, active=1):
        self.progress = 0
        self.scheduler = SimpleNamespace(active_count=active)


class TestServerResources:
    def test_debug_resources_endpoint(self, tiny_model):
        obs.reset()
        srv = serve(tiny_model, max_slots=2, page_size=16, num_pages=64,
                    max_model_len=128, enable_prefix_cache=True)
        try:
            cl = ServingClient(srv.address)
            cl.completion(list(range(1, 10)), max_tokens=3)
            doc = cl.request("GET", "/debug/resources")
        finally:
            srv.stop(drain_timeout=5.0)
        # process-wide tracker half
        assert doc["goodput"]["useful_tokens"] >= 3
        assert doc["compiles"]["total_compiles"] >= 2  # prefill + decode
        assert "devices" in doc["memory"]
        assert doc["throughput"]["tokens"] >= 3
        # engine-local half: exact census with a leak check
        eng = doc["engine"]
        assert eng["pool"]["leak"] == 0
        assert eng["pool"]["total"] == 64
        assert "fragmentation_ratio" in eng["pool"]
        assert eng["counters"]["decode_steps"] >= 1
        assert eng["timings"]["decode_s"] > 0

    def test_watchdog_dump_embeds_resource_snapshot(self, tmp_path):
        obs.reset()
        resource_tracker().note_finish("length", 4)
        eng = _FakeEngine()
        wd = Watchdog(eng, 10.0, dump_dir=str(tmp_path))
        wd.check(now=0.0)
        assert wd.check(now=10.0) is True
        doc = json.loads(open(wd.last_dump_path).read())
        res = doc["resources"]
        assert res["goodput"]["useful_tokens"] == 4
        assert set(res) >= {"memory", "compiles", "goodput",
                            "throughput", "pool"}


# ------------------------------------------------------- dump + report
class TestDumpAndReport:
    def test_dump_writes_resources_json_and_report_renders(self, tmp_path):
        obs.reset()
        t = resource_tracker()
        t.set_model(n_params=1234, device_kind="cpu")
        t.note_phase("decode", 0.5)
        t.note_tokens(10)
        t.note_finish("length", 8)
        t.note_finish("cancelled", 2)
        t.compiles.record("decode_step", 0.125, "slots=4")
        t.sample_memory()
        out = obs.dump(str(tmp_path))
        assert out == str(tmp_path)
        doc = json.loads((tmp_path / "resources.json").read_text())
        assert doc["goodput"]["ratio"] == 0.8
        assert doc["compiles"]["jits"]["decode_step"]["count"] == 1

        mod = _load_tool("metrics_report")
        metrics, retraces, trace, flight, resources, *_ = \
            mod._load(str(tmp_path))
        assert resources["goodput"]["useful_tokens"] == 8
        text = mod.report(metrics, retraces, trace=trace, flight=flight,
                          resources=resources)
        assert "Resources" in text
        assert "decode_step" in text
        assert "goodput" in text.lower()

    def test_report_tolerates_missing_resources(self, tmp_path):
        obs.reset()
        obs.dump(str(tmp_path))
        os.remove(tmp_path / "resources.json")
        mod = _load_tool("metrics_report")
        resources = mod._load(str(tmp_path))[4]
        assert resources is None
        metrics, retraces, trace, flight, resources, *_ = \
            mod._load(str(tmp_path))
        text = mod.report(metrics, retraces, trace=trace, flight=flight,
                          resources=resources)
        assert "Resources" not in text
