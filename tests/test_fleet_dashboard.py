"""fleet_dashboard units + live smoke: sparklines, render() on
synthetic router/replica payloads (no server needed), and the
deterministic ``--once`` CLI mode against a real serve."""
import importlib.util
import os
import subprocess
import sys

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability.usage import UsageMeter
from paddle_tpu.serving import Router, ServingClient, serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "fleet_dashboard.py")


def _load():
    spec = importlib.util.spec_from_file_location("fleet_dashboard", CLI)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dash = _load()


REPLICA_PAYLOAD = {
    "kind": "replica", "address": "127.0.0.1:9", "model": "m",
    "draining": False,
    "pool": {"total": 64, "live": 4, "cached": 2, "free": 58,
             "leak": 0, "fragmentation_ratio": 0.25},
    "prefix": {"page_size": 4, "roots": ["ab"], "dropped": 0,
               "cached_pages": 2, "cached_tokens": 8, "hits": 3,
               "misses": 1, "hit_rate": 0.75},
    "slots": {"active": 1, "max": 2, "free": 1},
    "queue": {"depth": 3, "max": 64},
    "slo": {"burn_rates": {"e2e": 0.5}, "max_burn_rate": 0.5},
    "spec": {"spec_k": 2, "spec_proposed": 10,
             "spec_acceptance_rate": 0.8},
    "recovery": {"recoveries": 1, "quarantines": 2,
                 "replayed_requests": 3},
    "latency": {"ttft": {"buckets": [[0.1, 2], [1.0, 4], ["+Inf", 4]],
                         "count": 4, "sum": 1.2}},
    "alerts": {"firing": [{"rule": "recovery_surge",
                           "series": "recoveries",
                           "condition": "rate(recoveries) > 0",
                           "value": 0.5}],
               "fired_total": 1, "ticks": 9},
    "series": {"tok_s": [[1, 0.0], [2, 4.0], [3, 8.0]],
               "queue_depth": [[1, 0], [2, 3], [3, 3]]},
    "profiling": {"interval_s": 0.01, "samples": 120,
                  "observations": 110, "distinct_stacks": 7,
                  "dropped": 0},
    "captures": {"captures": 2, "rate_limited": 1,
                 "by_rule": {"slo_burn": 2}, "min_interval_s": 60.0,
                 "max_captures": 8, "dir": "", "retained": []},
    "usage": {"tenants": {
                  "teamA": {"requests": 3, "decode_tokens": 24,
                            "page_seconds": 5.5, "host_page_seconds": 0.5,
                            "preemptions": 1, "shed": 0,
                            "slo": {"e2e": {"good": 3, "violation": 0}}},
                  "anon": {"requests": 1, "decode_tokens": 4,
                           "page_seconds": 0.25,
                           "host_page_seconds": 0.0,
                           "preemptions": 0, "shed": 2, "slo": {}}},
              "evicted_tenants": 0, "live_requests": 0,
              "conservation": {"device_delta": 0.0, "host_delta": 0.0}},
}


class TestSpark:
    def test_shape_and_extremes(self):
        out = dash.spark([0, 1, 2, 3])
        assert len(out) == 4
        assert out[0] == "▁" and out[-1] == "█"

    def test_flat_and_empty(self):
        assert dash.spark([]) == "-"
        assert set(dash.spark([5, 5, 5])) == {"▄"}

    def test_width_truncates_to_newest(self):
        out = dash.spark(list(range(100)), width=10)
        assert len(out) == 10 and out[-1] == "█"


class TestRender:
    def test_replica_frame(self):
        text = dash.render(REPLICA_PAYLOAD)
        assert "REPLICA 127.0.0.1:9" in text
        assert "1 ALERT FIRING" in text
        assert "recovery_surge" in text
        assert "1/2" in text            # slots active/max
        assert "58/64" in text          # pages free/total
        assert "25.0%" in text          # fragmentation
        assert "80.0%" in text          # spec acceptance
        assert "hit rate 75.0%" in text
        assert "2 quarantines" in text
        assert "p50<=" in text and "ttft" in text
        assert "tok_s" in text          # sparkline history
        assert "diagnostics: profiler 120 sweeps @ 0.01s" in text
        assert "captures 2 written / 1 rate-limited" in text
        assert "slo_burn=2" in text
        # tenant cost table, heaviest page-second bill first
        assert "Tenants (page-seconds ledger)" in text
        assert text.index("teamA") < text.index("anon")
        assert "device_delta=0" in text and "host_delta=0" in text

    def test_replica_without_diagnostics_has_no_line(self):
        old = {k: v for k, v in REPLICA_PAYLOAD.items()
               if k not in ("profiling", "captures")}
        assert "diagnostics:" not in dash.render(old)

    def test_replica_without_usage_meter_has_no_tenant_table(self):
        old = {k: v for k, v in REPLICA_PAYLOAD.items() if k != "usage"}
        assert "Tenants" not in dash.render(old)

    def test_router_frame_merges_usage_across_replicas(self):
        r2 = dict(REPLICA_PAYLOAD, address="127.0.0.1:10")
        payload = {"kind": "router", "failovers": 0,
                   "cluster": {"replicas": 2, "up": 2, "summaries": 2,
                               "alerts_firing": []},
                   "replicas": {
                       "127.0.0.1:9": {"up": True,
                                       "summary": REPLICA_PAYLOAD},
                       "127.0.0.1:10": {"up": True, "summary": r2}}}
        text = dash.render(payload)
        assert "raw-merged over 2 replicas" in text
        # counters sum raw: 3 + 3 requests for teamA, 2 + 2 sheds
        row = next(l for l in text.splitlines()
                   if l.startswith("teamA"))
        assert "6" in row.split() and "48" in row.split()

    def test_router_usage_skips_meterless_replicas(self):
        bare = {k: v for k, v in REPLICA_PAYLOAD.items()
                if k != "usage"}
        payload = {"kind": "router", "failovers": 0,
                   "cluster": {"replicas": 2, "up": 2, "summaries": 2,
                               "alerts_firing": []},
                   "replicas": {
                       "127.0.0.1:9": {"up": True,
                                       "summary": REPLICA_PAYLOAD},
                       "127.0.0.1:10": {"up": True, "summary": bare}}}
        assert "raw-merged over 1 replica" in dash.render(payload)

    def test_router_frame_carries_diagnostics(self):
        payload = {"kind": "router", "failovers": 0,
                   "cluster": {"replicas": 1, "up": 1, "summaries": 1,
                               "alerts_firing": []},
                   "replicas": {"127.0.0.1:9": {
                       "up": True, "summary": REPLICA_PAYLOAD}}}
        text = dash.render(payload)
        assert "[127.0.0.1:9]" in text
        assert "diagnostics: profiler 120 sweeps" in text

    def test_router_frame_merges_latency_across_replicas(self):
        r1 = dict(REPLICA_PAYLOAD)
        r2 = dict(REPLICA_PAYLOAD, address="127.0.0.1:10",
                  latency={"ttft": {"buckets": [[0.1, 0], [1.0, 0],
                                                ["+Inf", 4]],
                                    "count": 4, "sum": 8.0}})
        payload = {
            "kind": "router", "failovers": 1,
            "cluster": {"replicas": 2, "up": 2, "summaries": 2,
                        "pages": {"total": 128, "live": 8, "cached": 4,
                                  "free": 116},
                        "slots": {"active": 2, "max": 4, "free": 2},
                        "queue_depth": 6, "max_burn_rate": 0.5,
                        "alerts_firing": [
                            {"replica": "127.0.0.1:9",
                             "rule": "recovery_surge",
                             "condition": "rate(recoveries) > 0",
                             "value": 0.5}],
                        "prefix_digests": 1},
            "replicas": {
                "127.0.0.1:9": {"up": True, "summary": r1},
                "127.0.0.1:10": {"up": True, "summary": r2}},
        }
        text = dash.render(payload)
        assert "FLEET  replicas=2/2 up" in text
        assert "failovers=1" in text
        assert "[127.0.0.1:9]" in text and "127.0.0.1:10" in text
        # 8 observations pooled: 2 in le=0.1, 2 in le=1.0, 4 overflow
        assert "n=8" in text and "p99<=+Inf" in text
        # per-replica alert tag survives aggregation
        assert "[127.0.0.1:9] recovery_surge" in text

    def test_down_replica_without_summary(self):
        payload = {"kind": "router", "failovers": 0,
                   "cluster": {"replicas": 1, "up": 0, "summaries": 0,
                               "alerts_firing": []},
                   "replicas": {"127.0.0.1:9": {"up": False}}}
        text = dash.render(payload)
        assert "DOWN" in text

    def test_empty_payload_degrades(self):
        assert dash.render({"kind": "replica"})
        assert dash.render({"kind": "router"})


class TestOnceSmoke:
    def test_once_against_live_serve(self):
        paddle.seed(0)
        cfg = llama_tiny(vocab_size=64, hidden_size=32,
                         intermediate_size=64, num_attention_heads=4,
                         num_key_value_heads=2,
                         max_position_embeddings=128)
        m = LlamaForCausalLM(cfg)
        m.eval()
        server = serve(m, max_slots=2, page_size=4, num_pages=64,
                       watchdog_s=0, timeseries_interval_s=0.02,
                       profile_interval_s=0.02, usage=UsageMeter())
        router = Router([server.address], page_size=4)
        router.probe_once()
        rs = router.serve()
        try:
            ServingClient(server.address).completion_tokens(
                [1, 2, 3, 4], max_tokens=4, tenant="teamA")
            for addr, marker in ((server.address, "REPLICA"),
                                 (rs.address, "FLEET")):
                # a sweep whose summary fetch timed out on a busy host
                # leaves the router's frame bare until the next one
                for _ in range(10):
                    proc = subprocess.run(
                        [sys.executable, CLI, addr, "--once"],
                        capture_output=True, text=True, timeout=60)
                    assert proc.returncode == 0, proc.stderr
                    if "summaries=0" not in proc.stdout:
                        break
                    router.probe_once()
                assert marker in proc.stdout
                # profiler + capture recorder are armed on the replica,
                # so both frames carry the diagnostics line
                assert "diagnostics: profiler" in proc.stdout
                # the usage meter is armed, so both frames carry the
                # per-tenant cost table with the request's tenant
                assert "page-seconds ledger" in proc.stdout
                assert "teamA" in proc.stdout
        finally:
            rs.stop()
            server.stop(drain_timeout=5.0)
