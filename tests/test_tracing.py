"""End-to-end request tracing, flight recorder, watchdog, and SLO layer.

Acceptance contracts asserted here:
  * W3C ``traceparent`` round-trips and rejects malformed input;
  * a 2-replica routed request produces ONE trace id visible at the
    client, the router, and the replica — with router / queue / prefill
    / decode / stream spans linked parent->child on a single
    ``perf_counter`` clock, exportable as loadable chrome-trace JSON;
  * the Prometheus text export passes a format lint (HELP/TYPE once per
    family in order, ``+Inf`` bucket == ``_count``, ``_sum`` present)
    and ``/metrics`` serves ``text/plain; version=0.0.4``;
  * a forced engine stall (EngineWorker.inject_stall) trips the
    watchdog, which dumps the flight ring containing the stalled
    request's events — and the watchdog unit tests drive ``check(now)``
    with a fake clock, so they run in milliseconds;
  * a deadline eviction lands in ``serving_finish_total{deadline}`` AND
    on the root span (``finish_reason`` + ``deadline_overrun_s``);
  * ``serve_bench --trace`` writes a loadable chrome trace and the
    ``--http`` mode attributes latency per replica;
  * ``tools/metrics_report.py`` renders the new SLO/tracing sections
    and tolerates dumps from older runs that lack them.
"""
import http.client
import importlib.util
import json
import os
import re
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import tracing
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (GenerationConfig, Router, ServingClient,
                                SLOConfig, SLOTracker, Watchdog,
                                create_engine, serve)

PAGE = 16
PROMPT = list(range(1, 20))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = llama_tiny(vocab_size=128, hidden_size=64,
                     intermediate_size=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def server(tiny_model):
    srv = serve(tiny_model, max_slots=4, page_size=PAGE, num_pages=128,
                max_model_len=256, enable_prefix_cache=True)
    yield srv
    srv.stop(drain_timeout=5.0)


@pytest.fixture(scope="module")
def client(server):
    return ServingClient(server.address)


# ----------------------------------------------------------- traceparent
class TestTraceparent:
    def test_round_trip(self):
        ctx = tracing.SpanContext("ab" * 16, "cd" * 8)
        hdr = tracing.format_traceparent(ctx)
        assert hdr == f"00-{'ab' * 16}-{'cd' * 8}-01"
        assert tracing.parse_traceparent(hdr) == ctx

    def test_parse_normalizes_case(self):
        hdr = f"00-{'AB' * 16}-{'CD' * 8}-01"
        ctx = tracing.parse_traceparent(hdr)
        assert ctx == tracing.SpanContext("ab" * 16, "cd" * 8)

    @pytest.mark.parametrize("bad", [
        None, "", 42, "garbage", "00-abc-def-01",
        "00-" + "g" * 32 + "-" + "1" * 16 + "-01",   # non-hex
        "0-" + "a" * 32 + "-" + "1" * 16 + "-01",    # short version
        "ff-" + "a" * 32 + "-" + "1" * 16 + "-01",   # forbidden version
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # all-zero trace
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span
        "00-" + "a" * 31 + "-" + "1" * 16 + "-01",   # short trace id
    ])
    def test_malformed_returns_none(self, bad):
        assert tracing.parse_traceparent(bad) is None


# ---------------------------------------------------------------- tracer
class TestTracer:
    def test_context_manager_nesting(self):
        tr = tracing.Tracer(max_spans=32)
        with tr.start_span("outer") as outer:
            inner = tr.start_span("inner")     # inherits via contextvar
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            inner.end()
        assert outer.end_time is not None
        roots = tr.spans(name="outer")
        assert roots and roots[0].parent_id is None

    def test_parent_none_forces_new_root(self):
        tr = tracing.Tracer(max_spans=8)
        with tr.start_span("outer") as outer:
            detached = tr.start_span("detached", parent=None)
            assert detached.trace_id != outer.trace_id
            assert detached.parent_id is None
            detached.end()

    def test_explicit_context_crosses_threads(self):
        tr = tracing.Tracer(max_spans=8)
        root = tr.start_span("root")

        def worker():
            tr.start_span("child", parent=root.context).end()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        root.end()
        child = tr.spans(name="child")[0]
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id

    def test_ring_is_bounded(self):
        tr = tracing.Tracer(max_spans=4)
        for i in range(6):
            tr.record_span(f"s{i}", 0.0, 1.0)
        assert len(tr) == 4
        assert tr.spans_recorded == 6 and tr.spans_dropped == 2
        assert [s.name for s in tr.spans()] == ["s2", "s3", "s4", "s5"]

    def test_end_is_idempotent(self):
        tr = tracing.Tracer(max_spans=8)
        s = tr.start_span("once")
        s.end()
        s.end()
        assert len(tr.spans(name="once")) == 1

    def test_chrome_events_shape(self):
        tr = tracing.Tracer(max_spans=8)
        s = tr.start_span("op", attributes={"k": "v"})
        s.add_event("mark", x=1)
        s.end()
        evs = tr.chrome_events(pid=1)
        xs = [e for e in evs if e["ph"] == "X"]
        assert xs[0]["name"] == "op" and xs[0]["pid"] == 1
        assert xs[0]["dur"] >= 0 and xs[0]["args"]["k"] == "v"
        assert xs[0]["args"]["trace_id"] == s.trace_id
        insts = [e for e in evs if e["ph"] == "i"]
        assert insts[0]["name"] == "op.mark" and insts[0]["args"]["x"] == 1
        metas = [e for e in evs if e["ph"] == "M"]
        assert metas and metas[0]["name"] == "thread_name"
        json.dumps(evs)                     # loadable chrome trace

    def test_spans_carry_per_thread_tids(self):
        tr = tracing.Tracer(max_spans=8)
        t = threading.Thread(
            target=lambda: tr.record_span("worker-span", 0.0, 1.0),
            name="span-worker")
        t.start()
        t.join()
        tr.record_span("main-span", 2.0, 3.0)
        evs = tr.chrome_events(pid=1)
        tids = {e["tid"] for e in evs if e["ph"] == "X"}
        assert len(tids) == 2, "spans collapsed onto one thread row"
        names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert "span-worker" in names


# ----------------------------------------------------------------- phase
class TestPhase:
    def test_nests_and_commits_one_span_each(self):
        tr = tracing.Tracer(max_spans=8)
        with tr.phase("outer", k="v") as outer:
            assert tr.current_span() is outer
            assert outer.seconds == 0.0         # still open
            with tr.phase("inner") as inner:
                inner.set_attribute("rows", 3)
                time.sleep(0.002)
            assert tr.current_span() is outer
        assert tr.current_span() is None
        assert [s.name for s in tr.spans()] == ["inner", "outer"]
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == outer.trace_id
        assert outer.parent_id is None
        assert outer.attributes == {"k": "v"}
        assert inner.attributes == {"rows": 3}
        # the seconds handed back are the ring's own interval
        assert inner.seconds == inner.end_time - inner.start >= 0.002
        assert outer.start <= inner.start
        assert inner.end_time <= outer.end_time

    def test_explicit_parent_and_forced_root(self):
        tr = tracing.Tracer(max_spans=8)
        root = tr.start_span("request")
        with tr.phase("step", parent=None) as step:
            with tr.phase("prefill", parent=root) as prefill:
                pass
        root.end()
        assert step.parent_id is None
        assert step.trace_id != root.trace_id
        assert prefill.parent_id == root.span_id
        assert prefill.trace_id == root.trace_id

    def test_an_exception_still_commits_and_is_recorded(self):
        tr = tracing.Tracer(max_spans=8)
        with pytest.raises(ValueError):
            with tr.phase("failing") as ph:
                raise ValueError("boom")
        assert tr.spans(name="failing") == [ph]
        assert "boom" in ph.attributes["error"] and ph.seconds > 0.0
        assert tr.current_span() is None

    def test_shows_in_a_profile_under_the_same_name(self, tmp_path):
        """Inside a ``jax.profiler`` session the phase is a host event
        of the same name and length, on the profiler's clock."""
        import glob
        import jax
        from jax.profiler import ProfileData
        tr = tracing.Tracer(max_spans=8)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with tr.phase("phase.probe.outer") as outer:
                with tr.phase("phase.probe.inner") as inner:
                    time.sleep(0.005)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[-1]
        seen = {e.name: e for plane in ProfileData.from_file(path).planes
                for line in plane.lines for e in line.events
                if e.name.startswith("phase.probe.")}
        assert set(seen) == {"phase.probe.outer", "phase.probe.inner"}
        for span in (outer, inner):
            ev = seen[span.name]
            # the annotation opens first and closes last
            assert ev.duration_ns * 1e-9 >= span.seconds
            assert ev.duration_ns * 1e-9 - span.seconds < 0.002
        # one offset puts both ring spans on the profiler's clock
        offs = [seen[s.name].start_ns * 1e-9 - s.start
                for s in (outer, inner)]
        assert abs(offs[0] - offs[1]) < 0.001


class TestEnginePhases:
    STEP_PARTS = ["engine.schedule", "engine.decode.dispatch",
                  "engine.host_sync", "engine.emit"]

    def _run(self, tiny_model, **kw):
        obs.tracer().reset()
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=128,
                               sync_interval=1, **kw)
        reqs = [engine.submit(np.array(PROMPT[:n], np.int32),
                              GenerationConfig(max_new_tokens=m))
                for n, m in ((19, 6), (7, 4), (12, 5))]
        engine.run_until_complete(max_steps=200)
        assert all(r.finish_reason == "length" for r in reqs)
        return engine, reqs, obs.tracer().spans()

    def test_every_step_holds_its_parts_in_order(self, tiny_model):
        engine, reqs, spans = self._run(tiny_model)
        steps = [s for s in spans if s.name == "engine.step"]
        assert len(steps) == engine.progress
        assert [s.attributes["step"] for s in steps] == list(
            range(engine.progress))
        decoding = [s for s in steps if s.attributes["active"]]
        assert len(decoding) == engine.decode_steps > 0
        first_decode = True
        for step in steps:
            kids = sorted((s for s in spans if s.parent_id == step.span_id),
                          key=lambda s: s.start)
            # the host runs one step behind: a step fetches and walks
            # the row of the step BEFORE it, after its own dispatch, so
            # the first decoding step has nothing to fetch yet
            want = self.STEP_PARTS[:1]
            if step.attributes["active"]:
                want = self.STEP_PARTS[:2] if first_decode \
                    else self.STEP_PARTS
                assert kids[1].attributes["overlapped"] is not first_decode
                first_decode = False
            assert [k.name for k in kids] == want
            assert step.parent_id is None
            at = step.start
            for k in kids:                  # in order, inside, disjoint
                assert at <= k.start <= k.end_time <= step.end_time
                at = k.end_time
        names = {s.name for s in spans}
        assert "engine.decode_segment" not in names
        emits = [s for s in spans if s.name == "engine.emit"]
        assert all(s.attributes["rows"] == s.attributes["steps"] == 1
                   for s in emits)
        # decode_steps includes the overrun step dispatched before the
        # last finish was seen: its row is dropped unfetched, every
        # other step's row is fetched by the step after it
        assert len(emits) == engine.host_syncs == engine.decode_steps - 1
        assert engine.overlapped_steps == engine.decode_steps - 1
        # one overrun row a request: each finish is seen one step late
        assert engine.overrun_rows == len(reqs)
        # every token but each request's first comes out of an emit
        assert sum(s.attributes["tokens"] for s in emits) == sum(
            r.num_generated - 1 for r in reqs)
        admitted = [s.attributes["admitted"] for s in spans
                    if s.name == "engine.schedule"]
        assert sum(admitted) == len(reqs) and max(admitted) == 2
        evicted = sum(s.attributes["evicted"] for s in spans
                      if s.name == "engine.schedule")
        assert evicted == 0                 # finishes evict inside emit

    def test_prefill_keeps_its_request_and_gains_two_children(
            self, tiny_model):
        engine, reqs, spans = self._run(tiny_model)
        prefills = [s for s in spans if s.name == "engine.prefill"]
        assert len(prefills) == len(reqs)
        steps = [s for s in spans if s.name == "engine.step"]
        for req, pre in zip(reqs, prefills):
            assert pre.parent_id == req.root_span.span_id
            assert pre.attributes["kind"] == "full"
            assert pre.attributes["req"] == req.id
            kids = sorted((s for s in spans if s.parent_id == pre.span_id),
                          key=lambda s: s.start)
            assert [k.name for k in kids] == ["engine.prefill.dispatch",
                                              "engine.prefill.fetch"]
            assert pre.start <= kids[0].start
            assert kids[-1].end_time <= pre.end_time
            # by time it lies inside one engine.step, after its schedule
            assert sum(st.start <= pre.start and pre.end_time <= st.end_time
                       for st in steps) == 1

    def test_timings_are_the_spans_intervals(self, tiny_model):
        engine, reqs, spans = self._run(tiny_model)

        def total(name):
            return sum(s.seconds for s in spans if s.name == name)

        t = engine.timings
        assert set(t) == {"schedule_s", "prefill_s", "decode_s",
                          "host_sync_s", "emit_s", "queue_wait_s"}
        for key, name in (("schedule_s", "engine.schedule"),
                          ("prefill_s", "engine.prefill"),
                          ("decode_s", "engine.decode.dispatch"),
                          ("host_sync_s", "engine.host_sync"),
                          ("emit_s", "engine.emit")):
            assert t[key] == pytest.approx(total(name), rel=1e-9), key
        parts = sum(t[k] for k in ("schedule_s", "prefill_s", "decode_s",
                                   "host_sync_s", "emit_s"))
        whole = total("engine.step")
        assert 0.9 * whole <= parts <= whole
        assert t["queue_wait_s"] == pytest.approx(
            sum(r.queue_seconds for r in reqs))
        # the third request waited for a slot, the first two did not
        assert reqs[2].queue_seconds > max(reqs[0].queue_seconds,
                                           reqs[1].queue_seconds)

    def test_sampling_is_a_child_of_emit(self, tiny_model):
        obs.tracer().reset()
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=128,
                               emit_logits=True)
        req = engine.submit(np.array(PROMPT, np.int32),
                            GenerationConfig(max_new_tokens=4,
                                             do_sample=True, top_k=8))
        engine.run_until_complete(max_steps=50)
        assert req.finish_reason == "length"
        spans = obs.tracer().spans()
        samples = [s for s in spans if s.name == "engine.sample"]
        emits = {s.span_id: s for s in spans if s.name == "engine.emit"}
        assert len(samples) == 3            # one a decode step
        for s in samples:
            emit = emits[s.parent_id]
            assert emit.start <= s.start and s.end_time <= emit.end_time
            assert "corrections" in s.attributes


class TestDeviceNames:
    """What a profiler's device rows are told apart by: the jitted
    programs' names, the scopes inside them, the kernels' ``name=``."""
    PALLAS = os.path.join(REPO, "paddle_tpu", "ops", "pallas")
    SCOPES = ("embed", "attn.qkv", "kv.write", "attn.decode", "attn.out",
              "mlp", "head")

    @staticmethod
    def _kernel_names(path):
        import ast
        names = []
        for node in ast.walk(ast.parse(open(path).read())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{path}:{node.lineno} has no name="
                assert isinstance(kw["name"], ast.Constant)
                names.append(kw["name"].value)
        return names

    @pytest.mark.parametrize("module, count", [
        ("decode_attention", 1), ("flash_attention", 6),
        ("flash_mask", 8), ("grouped_ffn", 3), ("lora_matmul", 1),
        ("mla_paged_attention", 2), ("paged_attention", 1),
        ("quant_matmul", 2), ("rms_norm", 1)])
    def test_every_pallas_call_has_a_fixed_name(self, module, count):
        names = self._kernel_names(
            os.path.join(self.PALLAS, module + ".py"))
        assert len(names) == count == len(set(names))
        assert all(re.fullmatch(r"[a-z][a-z0-9_]*", n) for n in names)

    def test_kernel_names_are_unique_across_files(self):
        names = [n for f in sorted(os.listdir(self.PALLAS))
                 if f.endswith(".py")
                 for n in self._kernel_names(os.path.join(self.PALLAS, f))]
        assert len(names) == 26 == len(set(names))
        assert {"paged_attention", "flash_fwd", "flash_bwd_dq",
                "flash_bwd_dkv", "rms_norm", "decode_attention",
                "mla_paged_attention", "mla_cache_write",
                "grouped_matmul", "ssm_state_update"} <= set(names)

    def test_programs_and_scopes_carry_their_names(self, tiny_model):
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=128,
                               enable_prefix_cache=True)
        r = engine.runner
        assert r._step_fn.__name__ == "decode_step"
        assert r._copy_page_fn.__name__ == "copy_page"
        assert r._prefill_fn(PAGE).__name__ == "prefill"
        assert r._prefill_cached_fn(PAGE).__name__ == "prefill_cached"
        text = r._step_fn.lower(
            r.state, r.kpool, r.vpool, r.kscale, r.vscale, r._table_dev,
            r._pos_dev, r._tok_dev, r._active_dev, r._ring_dev,
            r._ridx_dev, r._cos, r._sin, r.lora,
            r._aidx_dev, r._counters_dev,
            r._rstate).as_text(debug_info=True)
        assert "jit_decode_step" in text
        for scope in self.SCOPES:
            assert f"jit(decode_step)/{scope}/" in text, scope

    @staticmethod
    def _latent_engine():
        import jax.numpy as jnp
        from paddle_tpu.models import deepseek_v3 as ds
        from paddle_tpu.serving.engine import Engine
        cfg = ds.DeepseekV3Config(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=2,
            first_k_dense_replace=1, num_attention_heads=2,
            q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=8,
            num_experts_per_tok=2, n_group=2, topk_group=1,
            max_position_embeddings=128, local_experts=(0, 4),
            dtype="float32")
        rng = np.random.default_rng(0)
        state = {k: jnp.asarray(0.1 * rng.normal(size=shape), jnp.float32)
                 for k, shape in ds.weight_shapes(cfg).items()}
        return Engine(config=cfg, state=state, max_slots=2,
                      page_size=PAGE, max_model_len=64)

    def test_latent_familys_programs_and_scopes(self):
        """The deepseek_v3 family keeps the programs' names and names
        its own parts: latent attention's two projections, the cache
        write, the router, the held experts, the shared expert."""
        import jax.numpy as jnp
        r = self._latent_engine().runner
        assert r._step_fn.__name__ == "decode_step"
        assert r._prefill_fn(PAGE).__name__ == "prefill"
        assert r._prefill_cached_fn(PAGE).__name__ == "prefill_cached"
        assert r.vpool == () and r.kpool.ndim == 4
        text = r._step_fn.lower(
            r.state, r.kpool, r.vpool, r.kscale, r.vscale, r._table_dev,
            r._pos_dev, r._tok_dev, r._active_dev, r._ring_dev,
            r._ridx_dev, r._cos, r._sin, r.lora,
            r._aidx_dev, r._counters_dev,
            r._rstate).as_text(debug_info=True)
        for scope in ("embed", "attn.mla.q", "attn.mla.kv", "kv.write",
                      "attn.decode", "attn.out", "mlp", "moe.route",
                      "moe.experts", "moe.shared", "head"):
            assert f"jit(decode_step)/{scope}/" in text, scope
        text = r._prefill_fn(PAGE).lower(
            r.state, jnp.zeros((1, PAGE), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            r.kpool, r.vpool, r.kscale, r.vscale, r._cos, r._sin, (),
            (), (), ()).as_text(debug_info=True)
        for scope in ("attn.mla.q", "attn.mla.kv", "attn.prefill",
                      "kv.write", "moe.experts", "head"):
            assert f"jit(prefill)/{scope}/" in text, scope

    @staticmethod
    def _hybrid_engine():
        import jax.numpy as jnp
        from paddle_tpu.models import granite_hybrid as gh
        from paddle_tpu.serving.engine import Engine
        cfg = gh.GraniteHybridConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=3,
            layer_types=("mamba", "attention", "mamba"),
            num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
            mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
            max_position_embeddings=128, dtype="float32")
        rng = np.random.default_rng(0)
        state = {k: jnp.asarray(
            np.ones(s) if k.endswith(("norm.weight", ".D", "A_log"))
            else 0.1 * rng.normal(size=s), jnp.float32)
            for k, s in gh.weight_shapes(cfg).items()}
        return Engine(config=cfg, state=state, max_slots=2, page_size=PAGE,
                      max_model_len=64)

    def test_recurrent_familys_programs_scopes_and_counter(self):
        """The hybrid family's programs under the same names, the Mamba
        layer's scopes beside the attention layer's, and the decode span
        carrying ``ssm_rows_live`` as ``stats()`` last read it."""
        import jax.numpy as jnp
        obs.tracer().reset()
        engine = self._hybrid_engine()
        r = engine.runner
        assert r._step_fn.__name__ == "decode_step"
        assert r._prefill_fn(PAGE).__name__ == "prefill"
        text = r._step_fn.lower(
            r.state, r.kpool, r.vpool, r.kscale, r.vscale, r._table_dev,
            r._pos_dev, r._tok_dev, r._active_dev, r._ring_dev,
            r._ridx_dev, r._cos, r._sin, r.lora, r._aidx_dev,
            r._counters_dev, r._rstate).as_text(debug_info=True)
        for scope in ("embed", "ssm.in_proj", "ssm.conv", "ssm.update",
                      "ssm.gate", "ssm.out", "attn.qkv", "kv.write",
                      "attn.decode", "attn.out", "mlp", "head"):
            assert f"jit(decode_step)/{scope}/" in text, scope
        text = r._prefill_fn(PAGE).lower(
            r.state, jnp.zeros((1, PAGE), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            r.kpool, r.vpool, r.kscale, r.vscale, r._cos, r._sin, (), (),
            r._rstate, jnp.zeros((), jnp.int32)).as_text(debug_info=True)
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate",
                      "ssm.out", "ssm.write", "attn.prefill", "kv.write",
                      "mlp", "head"):
            assert f"jit(prefill)/{scope}/" in text, scope
        engine.submit(np.array(PROMPT, np.int32),
                      GenerationConfig(max_new_tokens=4))
        engine.step()
        engine.step()
        seen = engine.stats()
        assert seen["ssm_rows_live"] >= 2       # two Mamba layers a step
        engine.run_until_complete(max_steps=50)
        spans = [s for s in obs.tracer().spans()
                 if s.name == "engine.decode.dispatch"]
        assert "ssm_rows_live" not in spans[0].attributes
        assert spans[-1].attributes["ssm_rows_live"] == \
            seen["ssm_rows_live"]

    @staticmethod
    def _hybrid_moe_engine():
        import jax.numpy as jnp
        from paddle_tpu.models import nemotron_h as nh
        from paddle_tpu.serving.engine import Engine
        cfg = nh.NemotronHConfig(
            vocab_size=64, hidden_size=32, hybrid_override_pattern="ME*M",
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
            mamba_n_groups=2, mamba_chunk_size=8, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=40, n_routed_experts=8,
            num_experts_per_tok=2, local_experts=(0, 4),
            max_position_embeddings=128, dtype="float32")
        rng = np.random.default_rng(0)
        state = {k: jnp.asarray(
            np.ones(s) if k.endswith(("norm.weight", "norm_f.weight", ".D",
                                      "A_log"))
            else 0.1 * rng.normal(size=s), jnp.float32)
            for k, s in nh.weight_shapes(cfg).items()}
        return Engine(config=cfg, state=state, max_slots=2, page_size=PAGE,
                      max_model_len=64)

    def test_one_part_blocks_keep_the_scopes_and_all_four_counters(self):
        """The nemotron_h family through the recurrent family's programs:
        the same program names; each block's one part under the scopes
        the other families' parts have (``ssm.*``, ``attn.*``,
        ``moe.route`` / ``moe.experts`` / ``moe.shared``) and no ``mlp``
        scope, which no block of it has; the decode span carrying the
        Mamba blocks' and the expert blocks' counters as ``stats()`` last
        read them."""
        import jax.numpy as jnp
        obs.tracer().reset()
        engine = self._hybrid_moe_engine()
        r = engine.runner
        assert r._step_fn.__name__ == "decode_step"
        assert r._prefill_fn(PAGE).__name__ == "prefill"
        text = r._step_fn.lower(
            r.state, r.kpool, r.vpool, r.kscale, r.vscale, r._table_dev,
            r._pos_dev, r._tok_dev, r._active_dev, r._ring_dev,
            r._ridx_dev, r._cos, r._sin, r.lora, r._aidx_dev,
            r._counters_dev, r._rstate).as_text(debug_info=True)
        for scope in ("embed", "ssm.in_proj", "ssm.conv", "ssm.update",
                      "ssm.gate", "ssm.out", "moe.route", "moe.experts",
                      "moe.shared", "attn.qkv", "kv.write", "attn.decode",
                      "attn.out", "head"):
            assert f"jit(decode_step)/{scope}/" in text, scope
        assert "jit(decode_step)/mlp/" not in text
        text = r._prefill_fn(PAGE).lower(
            r.state, jnp.zeros((1, PAGE), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            r.kpool, r.vpool, r.kscale, r.vscale, r._cos, r._sin, (), (),
            r._rstate, jnp.zeros((), jnp.int32)).as_text(debug_info=True)
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate",
                      "ssm.out", "ssm.write", "moe.route", "moe.experts",
                      "moe.shared", "attn.prefill", "kv.write", "head"):
            assert f"jit(prefill)/{scope}/" in text, scope
        assert "jit(prefill)/mlp/" not in text
        engine.submit(np.array(PROMPT, np.int32),
                      GenerationConfig(max_new_tokens=4))
        engine.step()
        engine.step()
        seen = engine.stats()
        assert seen["ssm_rows_live"] >= 2       # two Mamba blocks a step
        assert seen["moe_routed_pairs"] >= 2    # one expert block, top-2
        assert 0 <= seen["moe_local_pairs"] <= seen["moe_routed_pairs"]
        assert "moe_experts_live" in seen
        engine.run_until_complete(max_steps=50)
        spans = [s for s in obs.tracer().spans()
                 if s.name == "engine.decode.dispatch"]
        assert "ssm_rows_live" not in spans[0].attributes
        for name in ("ssm_rows_live", "moe_routed_pairs", "moe_local_pairs",
                     "moe_experts_live"):
            assert spans[-1].attributes[name] == seen[name], name

    def test_decode_span_carries_the_expert_counters(self):
        """``engine.decode.dispatch`` shows the device's expert counters
        as ``stats()`` last read them: a step never fetches them."""
        obs.tracer().reset()
        engine = self._latent_engine()
        engine.submit(np.array(PROMPT, np.int32),
                      GenerationConfig(max_new_tokens=4))
        engine.step()
        engine.step()
        seen = engine.stats()
        assert seen["moe_routed_pairs"] >= 2
        engine.run_until_complete(max_steps=50)
        spans = [s for s in obs.tracer().spans()
                 if s.name == "engine.decode.dispatch"]
        assert "moe_routed_pairs" not in spans[0].attributes
        assert spans[-1].attributes["moe_routed_pairs"] == \
            seen["moe_routed_pairs"]
        assert {"moe_local_pairs", "moe_experts_live",
                "paged_blocks_live"} <= set(spans[-1].attributes)

    def test_verify_program_carries_its_name(self, tiny_model):
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=128, spec_k=2)
        assert engine.runner._verify_fn.__name__ == "verify_step"


# ------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_ring_bound_and_order(self):
        fr = tracing.FlightRecorder(capacity=3)
        for i in range(5):
            fr.record("engine", f"e{i}", n=i)
        evs = fr.snapshot()
        assert len(evs) == 3 and len(fr) == 3
        assert [e["event"] for e in evs] == ["e2", "e3", "e4"]
        assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
        assert all("ts" in e for e in evs)

    def test_dump_is_loadable(self, tmp_path):
        fr = tracing.FlightRecorder(capacity=8)
        fr.record("scheduler", "admit", req="r1", slot=0)
        path = fr.dump(str(tmp_path / "flight.json"))
        doc = json.loads(open(path).read())
        assert doc["capacity"] == 8
        assert doc["events"][0]["event"] == "admit"


# -------------------------------------------- prometheus text conformance
def _parse_sample(line):
    m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                 r"(?:\{(.*)\})? (\S+)$", line)
    assert m, f"unparsable sample line: {line!r}"
    labels = dict(re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)='
                             r'"((?:[^"\\]|\\.)*)"', m.group(2) or ""))
    return m.group(1), labels, float(m.group(3))


def _lint_prometheus(text):
    """Text exposition format 0.0.4 lint: one HELP then one TYPE per
    family (in that order, before its samples), histogram +Inf bucket
    == _count, _sum present, cumulative buckets monotone."""
    helps, types, samples = {}, {}, []
    current = None
    for ln in text.rstrip("\n").split("\n"):
        if ln.startswith("# HELP "):
            name = ln.split(" ", 3)[2]
            assert name not in helps, f"duplicate HELP for {name}"
            assert name not in types, f"HELP after TYPE for {name}"
            helps[name] = True
            current = name
        elif ln.startswith("# TYPE "):
            _, _, name, kind = ln.split(" ", 3)
            assert name == current, f"TYPE {name} without preceding HELP"
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
        elif ln.startswith("#"):
            raise AssertionError(f"unexpected comment line {ln!r}")
        elif ln:
            samples.append(_parse_sample(ln))
    assert set(helps) == set(types)

    def family(metric):
        for suffix in ("_bucket", "_sum", "_count"):
            base = metric[:-len(suffix)] if metric.endswith(suffix) else None
            if base and types.get(base) == "histogram":
                return base
        return metric

    hist = {}
    for metric, labels, value in samples:
        base = family(metric)
        assert base in types, f"sample {metric} for unregistered family"
        if types[base] != "histogram":
            assert metric == base
            continue
        key = (base, tuple(sorted((k, v) for k, v in labels.items()
                                  if k != "le")))
        series = hist.setdefault(key, {"buckets": [], "sum": None,
                                       "count": None})
        if metric.endswith("_bucket"):
            assert "le" in labels, f"{metric} sample without le"
            series["buckets"].append((labels["le"], value))
        elif metric.endswith("_sum"):
            series["sum"] = value
        elif metric.endswith("_count"):
            series["count"] = value
    assert any(k == "histogram" for k in types.values())
    for (base, labels), series in hist.items():
        assert series["sum"] is not None, f"{base}{labels} missing _sum"
        assert series["count"] is not None, f"{base}{labels} missing _count"
        assert series["buckets"], f"{base}{labels} has no buckets"
        assert series["buckets"][-1][0] == "+Inf", \
            f"{base}{labels} last bucket is not +Inf"
        counts = [c for _, c in series["buckets"]]
        assert counts == sorted(counts), f"{base}{labels} not cumulative"
        assert counts[-1] == series["count"], \
            f"{base}{labels} +Inf bucket != _count"
    return types


class TestPrometheusConformance:
    def test_registry_export_lints(self):
        reg = obs.default_registry()
        # make sure at least one labeled counter + histogram have data
        reg.counter("lint_probe_total", "probe\nmultiline help",
                    ("kind",)).labels("a").inc()
        h = reg.histogram("lint_probe_seconds", "probe hist", ("k",))
        h.labels("x").observe(0.003)
        h.labels("x").observe(42.0)         # lands in the +Inf tail
        types = _lint_prometheus(reg.to_prometheus())
        assert types["lint_probe_total"] == "counter"
        assert types["lint_probe_seconds"] == "histogram"

    def test_server_metrics_lint_and_content_type(self, server, client):
        client.completion(PROMPT, max_tokens=2)    # populate serving_*
        conn = http.client.HTTPConnection(server.server_address[0],
                                          server.server_address[1],
                                          timeout=10.0)
        try:
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.headers["Content-Type"] == \
                "text/plain; version=0.0.4"
            text = resp.read().decode()
        finally:
            conn.close()
        types = _lint_prometheus(text)
        assert types["serving_ttft_seconds"] == "histogram"
        assert "serving_finish_total" in types
        assert "serving_watchdog_stalls_total" in types
        assert "serving_slo_requests_total" in types


# ----------------------------------------------------- e2e trace (2 rep)
class TestEndToEndTracing:
    def test_two_replica_routed_request_is_one_trace(self, tiny_model):
        """Acceptance: client -> router proxy -> replica under ONE
        trace id, parent-linked, with queue/prefill/decode/stream spans
        on the shared perf_counter clock."""
        obs.reset()
        servers = [serve(tiny_model, max_slots=2, page_size=PAGE,
                         num_pages=64, max_model_len=128,
                         enable_prefix_cache=True) for _ in range(2)]
        router = Router([s.address for s in servers], page_size=PAGE)
        proxy = router.serve()
        try:
            pc = ServingClient(proxy.address)
            toks = []
            for ev in pc.completion(PROMPT, max_tokens=6, stream=True):
                toks.extend(ev["choices"][0]["token_ids"])
            assert len(toks) == 6
        finally:
            proxy.stop()
            for s in servers:
                s.stop(drain_timeout=5.0)

        tr = obs.tracer()
        client_span = tr.spans(name="client.completion")[-1]
        tid = client_span.trace_id
        needed = ("router.request", "server.request", "server.stream",
                  "request", "scheduler.queue_wait", "engine.prefill",
                  "engine.decode")
        # engine-thread spans commit asynchronously; poll briefly
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            have = {n: tr.spans(name=n, trace_id=tid) for n in needed}
            if all(have.values()):
                break
            time.sleep(0.02)
        for n in needed:
            assert have[n], f"span {n} missing from trace {tid}"

        rout = have["router.request"][0]
        srv_span = have["server.request"][0]
        root = have["request"][0]
        queue = have["scheduler.queue_wait"][0]
        prefill = have["engine.prefill"][0]
        # parent links across the two HTTP hops + the engine-thread hop
        assert rout.parent_id == client_span.span_id
        assert srv_span.parent_id == rout.span_id
        assert root.parent_id == srv_span.span_id
        assert queue.parent_id == root.span_id
        assert prefill.parent_id == root.span_id
        assert have["server.stream"][0].trace_id == tid
        # one consistent clock: admission precedes prefill, which
        # starts no earlier than the request hit the server
        assert queue.start <= prefill.start
        assert srv_span.start >= rout.start - 1e-6
        assert root.attributes["finish_reason"] == "length"
        # the whole thing exports as loadable chrome JSON
        doc = json.loads(json.dumps({"traceEvents": tr.chrome_events()}))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"router.request", "engine.prefill",
                "server.stream"} <= names

    def test_untraced_request_starts_fresh_trace(self, server, client):
        before = len(obs.tracer().spans(name="server.request"))
        out = client.request("POST", "/v1/completions",
                             {"prompt": PROMPT, "max_tokens": 2})
        assert len(out["choices"][0]["token_ids"]) == 2
        # the handler commits its span just after the response flushes
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            spans = obs.tracer().spans(name="server.request")
            if len(spans) > before:
                break
            time.sleep(0.01)
        assert len(spans) > before
        assert spans[-1].attributes["remote"] is False
        assert spans[-1].parent_id is None

    def test_debug_endpoints(self, server, client):
        client.completion(PROMPT, max_tokens=2)
        flight = client.request("GET", "/debug/flight")
        assert flight["capacity"] > 0
        evs = flight["events"]
        assert any(e["category"] == "engine" and e["event"] == "submit"
                   for e in evs)
        assert any(e["event"] == "prefill" for e in evs)
        assert flight["watchdog"]["enabled"] is False   # default off
        trace = client.request("GET", "/debug/trace")
        names = {e["name"] for e in trace["traceEvents"]}
        assert "server.request" in names

    def test_export_host_trace_merges_spans(self, tmp_path):
        from paddle_tpu import profiler
        obs.tracer().record_span("merge-probe", 1.0, 2.0)
        out = tmp_path / "host_trace.json"
        assert profiler.export_host_trace(str(out))
        doc = json.loads(out.read_text())
        assert "merge-probe" in {e.get("name")
                                 for e in doc["traceEvents"]}

    def test_record_event_is_thread_safe(self):
        from paddle_tpu.profiler import RecordEvent
        rec = RecordEvent("shared-span")
        rec.end()                           # end-before-begin: no-op
        errors = []

        def hammer():
            try:
                for _ in range(100):
                    rec.begin()
                    rec.end()
            except Exception as e:          # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors


# -------------------------------------------------------------- watchdog
class _FakeEngine:
    def __init__(self, active=1):
        self.progress = 0
        self.scheduler = SimpleNamespace(active_count=active)


class TestWatchdogUnit:
    """Fake-clock detection tests — milliseconds of wall time."""

    def test_detects_stall_and_dumps_once(self, tmp_path):
        eng = _FakeEngine()
        wd = Watchdog(eng, 10.0, dump_dir=str(tmp_path))
        obs.flight("engine", "submit", req="stuck-req")
        assert wd.check(now=0.0) is False      # first observation
        assert wd.check(now=9.9) is False      # under threshold
        assert wd.check(now=10.0) is True      # trip
        assert wd.stalls == 1
        assert wd.state()["stalled"] is True
        assert wd.check(now=20.0) is False     # latched: one dump/episode
        assert wd.stalls == 1
        doc = json.loads(open(wd.last_dump_path).read())
        assert doc["stalled_for_s"] >= 10.0
        assert doc["active_slots"] == 1
        assert any("stack" in t and t["stack"] for t in doc["threads"])
        assert any(e.get("req") == "stuck-req"
                   for e in doc["flight"]["events"])

    def test_progress_clears_and_retriggers(self, tmp_path):
        eng = _FakeEngine()
        wd = Watchdog(eng, 10.0, dump_dir=str(tmp_path))
        wd.check(now=0.0)
        assert wd.check(now=10.0) is True
        eng.progress += 1                      # engine recovered
        assert wd.check(now=12.0) is False
        assert wd.state()["stalled"] is False
        assert wd.check(now=22.0) is True      # second episode
        assert wd.stalls == 2

    def test_idle_engine_never_stalls(self):
        eng = _FakeEngine(active=0)
        wd = Watchdog(eng, 10.0)
        for now in (0.0, 100.0, 1000.0):
            assert wd.check(now=now) is False
        assert wd.stalls == 0

    def test_disabled_watchdog_start_is_noop(self):
        wd = Watchdog(_FakeEngine(), 0.0)
        wd.start()
        assert wd._thread is None
        assert wd.state()["enabled"] is False
        wd.stop()


class TestWatchdogIntegration:
    def test_inject_stall_trips_watchdog(self, tiny_model, tmp_path):
        """Acceptance: a forced engine stall trips the watchdog, which
        dumps a flight ring containing the stalled request's events.
        Sub-second stall_seconds keeps this under the tier-1 budget."""
        srv = serve(tiny_model, max_slots=2, page_size=PAGE,
                    num_pages=64, max_model_len=256, watchdog_s=0.15)
        srv.watchdog._dump_dir = str(tmp_path)
        cl = ServingClient(srv.address)
        done = {}

        def consume():
            done["toks"] = [t for ev in
                            cl.completion(PROMPT, max_tokens=64,
                                          stream=True)
                            for t in ev["choices"][0]["token_ids"]]

        t = threading.Thread(target=consume, daemon=True)
        try:
            # the first request compiles the programs, which on a busy
            # machine outlasts both the watchdog's 0.15 s and this
            # test's patience: let it pass, and count stalls from here
            # (the watched request is long enough to be caught running
            # once nothing compiles)
            cl.completion(PROMPT, max_tokens=4)
            deadline = time.monotonic() + 5.0
            while srv.watchdog.state()["stalled"]:
                assert time.monotonic() < deadline
                time.sleep(0.02)
            before = srv.watchdog.stalls
            dump_before = srv.watchdog.last_dump_path
            t.start()
            deadline = time.monotonic() + 10.0
            # what the watchdog itself reads, lock-free: stats() waits
            # for the engine's lock, which a stepping worker may not
            # hand over before the request is done
            while not srv.worker.engine.scheduler.active_count:
                assert time.monotonic() < deadline, "request never ran"
                time.sleep(0.002)
            req = srv.worker.requests[-1]
            srv.worker.inject_stall(0.8)
            deadline = time.monotonic() + 5.0
            while srv.watchdog.stalls == before:
                assert time.monotonic() < deadline, \
                    "watchdog did not trip on an injected stall"
                time.sleep(0.01)
            state = srv.watchdog.state()
            assert state["stalled"] is True and state["stalls"] > before
            assert cl.healthz()["watchdog"]["stalls"] > before
            # the count goes up before the report is written
            while srv.watchdog.last_dump_path == dump_before:
                assert time.monotonic() < deadline, "no hang report"
                time.sleep(0.01)
            doc = json.loads(open(srv.watchdog.last_dump_path).read())
            assert doc["active_slots"] >= 1
            assert any(e.get("req") == req.id and e["event"] == "submit"
                       for e in doc["flight"]["events"]), \
                "hang dump lost the stalled request's flight events"
            thread_names = {th["name"] for th in doc["threads"]}
            assert "engine-worker" in thread_names
            # the stall passes, the stream finishes, the latch clears
            t.join(timeout=30.0)
            assert not t.is_alive() and len(done["toks"]) == 64
            deadline = time.monotonic() + 5.0
            while srv.watchdog.state()["stalled"]:
                assert time.monotonic() < deadline
                time.sleep(0.02)
        finally:
            srv.stop(drain_timeout=5.0)


# ------------------------------------------------------------------- SLO
def _fake_req(ttft=None, tpot=None, n=0, arrival=100.0):
    first = None if ttft is None else arrival + ttft
    last = first if (first is not None and (n <= 1 or tpot is None)) \
        else (None if first is None else first + tpot * (n - 1))
    return SimpleNamespace(arrival_time=arrival, first_token_at=first,
                           last_token_at=last, num_generated=n)


class TestSLO:
    def test_config_from_flags_ms_to_s(self):
        paddle.set_flags({"FLAGS_serving_slo_ttft_ms": 250.0,
                          "FLAGS_serving_slo_e2e_ms": 2000.0,
                          "FLAGS_serving_slo_objective": 0.95})
        try:
            cfg = SLOConfig.from_flags()
            assert cfg.ttft_s == 0.25 and cfg.e2e_s == 2.0
            assert cfg.tpot_s == 0.0 and cfg.objective == 0.95
            assert cfg.enabled
        finally:
            paddle.set_flags({"FLAGS_serving_slo_ttft_ms": 0.0,
                              "FLAGS_serving_slo_e2e_ms": 0.0,
                              "FLAGS_serving_slo_objective": 0.99})
        assert not SLOConfig.from_flags().enabled

    def test_invalid_objective_raises(self):
        with pytest.raises(ValueError, match="objective"):
            SLOTracker(SLOConfig(ttft_s=1.0, objective=1.0))

    def test_verdicts_and_burn_rate(self):
        trk = SLOTracker(SLOConfig(ttft_s=0.1, tpot_s=0.01, e2e_s=1.0,
                                   objective=0.9), window=16)
        # good on every dimension
        trk.observe(_fake_req(ttft=0.05, tpot=0.005, n=4), now=100.5)
        # ttft violation, tpot good
        trk.observe(_fake_req(ttft=0.5, tpot=0.005, n=4), now=100.9)
        # single token: tpot not measurable, must not count
        trk.observe(_fake_req(ttft=0.05, n=1), now=100.2)
        # no first token at all: ttft AND e2e violations
        trk.observe(_fake_req(ttft=None, n=0), now=102.0)
        assert trk.good == {"ttft": 2, "tpot": 2, "e2e": 3}
        assert trk.violations == {"ttft": 2, "tpot": 0, "e2e": 1}
        # burn rate = window violation fraction / (1 - objective)
        assert trk.burn_rate("ttft") == pytest.approx((2 / 4) / 0.1)
        assert trk.burn_rate("tpot") == 0.0
        assert trk.burn_rate("e2e") == pytest.approx((1 / 4) / 0.1)
        st = trk.stats()
        assert st["targets"]["objective"] == 0.9
        assert st["violations"]["ttft"] == 2

    def test_disabled_dimensions_record_nothing(self):
        trk = SLOTracker(SLOConfig(e2e_s=1.0))
        trk.observe(_fake_req(ttft=99.0, tpot=99.0, n=4), now=100.1)
        assert trk.good == {"ttft": 0, "tpot": 0, "e2e": 1}

    def test_engine_integration_counts_requests(self, tiny_model):
        trk = SLOTracker(SLOConfig(ttft_s=30.0, tpot_s=30.0, e2e_s=30.0))
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=128, slo=trk)
        for _ in range(2):
            engine.submit(np.array(PROMPT, np.int32),
                          GenerationConfig(max_new_tokens=4))
        engine.run_until_complete()
        assert trk.good["ttft"] == 2 and trk.good["e2e"] == 2
        assert trk.violations == {"ttft": 0, "tpot": 0, "e2e": 0}
        st = engine.stats()
        assert st["slo"]["good"]["e2e"] == 2
        assert st["progress"] > 0

    def test_serve_wires_slo_from_flags(self, tiny_model):
        paddle.set_flags({"FLAGS_serving_slo_ttft_ms": 30000.0})
        try:
            srv = serve(tiny_model, max_slots=2, page_size=PAGE,
                        num_pages=64, max_model_len=128)
            try:
                assert srv.worker.engine.slo is not None
                assert srv.worker.engine.slo.config.ttft_s == 30.0
            finally:
                srv.stop(drain_timeout=5.0)
        finally:
            paddle.set_flags({"FLAGS_serving_slo_ttft_ms": 0.0})


# ------------------------------------------------ finish_reason contract
class TestFinishReason:
    def test_deadline_eviction_hits_counter_and_root_span(self,
                                                          tiny_model):
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=256)
        cnt = obs.default_registry().get("serving_finish_total")
        before = cnt.labels("deadline").value
        req = engine.submit(np.array(PROMPT, np.int32),
                            GenerationConfig(max_new_tokens=200),
                            deadline=engine._clock() + 0.02)
        engine.run_until_complete()
        assert req.finish_reason == "deadline"
        assert req.num_generated < 200
        assert cnt.labels("deadline").value == before + 1
        spans = [s for s in obs.tracer().spans(name="request")
                 if s.attributes.get("req") == req.id]
        assert spans, "deadline eviction left no root span"
        root = spans[-1]
        assert root.attributes["finish_reason"] == "deadline"
        assert root.attributes["deadline_overrun_s"] >= 0.0

    def test_expired_deadline_drops_from_queue(self, tiny_model):
        """A request whose deadline passed before admission still gets
        the full observability treatment (queue-drop path)."""
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=128)
        req = engine.submit(np.array(PROMPT, np.int32),
                            GenerationConfig(max_new_tokens=4),
                            deadline=engine._clock() - 1.0)
        engine.run_until_complete()
        assert req.finish_reason == "deadline"
        assert req.num_generated == 0
        queued = [s for s in
                  obs.tracer().spans(name="scheduler.queue_wait")
                  if s.trace_id == req.root_span.trace_id]
        assert queued and queued[0].attributes.get("dropped") is True

    def test_length_and_counter(self, tiny_model):
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=128)
        cnt = obs.default_registry().get("serving_finish_total")
        before = cnt.labels("length").value
        req = engine.submit(np.array(PROMPT, np.int32),
                            GenerationConfig(max_new_tokens=3))
        engine.run_until_complete()
        assert req.finish_reason == "length"
        assert cnt.labels("length").value == before + 1


# ------------------------------------------------------ CLI tool surface
class TestServeBenchTrace:
    def _args(self, **over):
        # bench_args() builds defaults from the REAL parser, so this
        # helper can never silently miss a newly added bench flag
        mod = _load_tool("serve_bench")
        base = dict(requests=3, max_slots=2, page_size=PAGE,
                    num_pages=64, arrival_gap_ms=1.0, prompt_len=(4, 8),
                    new_tokens=(2, 4), layers=1, hidden=32, vocab=64,
                    max_model_len=64)
        base.update(over)
        return mod.bench_args(**base)

    def test_trace_flag_writes_loadable_chrome_trace(self, tmp_path):
        mod = _load_tool("serve_bench")
        out = tmp_path / "bench_trace.json"
        res = mod.run_bench(self._args(trace=str(out)))
        assert res["requests"] == 3
        doc = json.loads(out.read_text())
        names = {e.get("name") for e in doc["traceEvents"]}
        assert {"request", "engine.prefill", "engine.decode.dispatch",
                "engine.host_sync", "engine.emit"} <= names

    def test_per_replica_latency_grouping(self):
        mod = _load_tool("serve_bench")
        results = [
            (0.0, 0.1, 0.5, 5, "replica-0"),
            (0.0, None, None, 0, "replica-1"),   # no first token
            None,                                # failed request
            (1.0, 1.2, 1.2, 1, "replica-0"),     # 1 token: no TPOT
        ]
        per = mod._per_replica_latency(results)
        ttfts, tpots, n = per["replica-0"]
        assert n == 2
        assert ttfts == pytest.approx([0.1, 0.2])
        assert tpots == pytest.approx([(0.5 - 0.1) / 4])
        assert per["replica-1"] == ([], [], 1)

    def test_http_bench_reports_per_replica(self):
        mod = _load_tool("serve_bench")
        res = mod.run_http_bench(self._args(
            requests=4, http=True, replicas=2, shared_prefix_len=PAGE))
        per = res["per_replica"]
        assert per and set(per) <= {"replica-0", "replica-1"}
        assert sum(v["requests"] for v in per.values()) == 4


class TestMetricsReport:
    def test_old_dump_without_new_sections(self, tmp_path):
        """Missing-section tolerance: a dump from an older run (no SLO
        counters, no trace.json/flight.json) must still render."""
        mod = _load_tool("metrics_report")
        old = {"serving_tokens_total": {
            "type": "counter", "help": "", "series":
            [{"labels": {}, "value": 12.0}]}}
        (tmp_path / "metrics.json").write_text(json.dumps(old))
        metrics, retraces, trace, flight, resources, *_ = \
            mod._load(str(tmp_path))
        assert retraces is None and trace is None and flight is None
        assert resources is None
        text = mod.report(metrics, retraces, trace, flight)
        assert "serving_tokens_total" in text
        assert "SLO" not in text and "Tracing" not in text
        assert mod.report({}, None) == "empty dump"

    def test_corrupt_side_files_are_tolerated(self, tmp_path):
        mod = _load_tool("metrics_report")
        (tmp_path / "metrics.json").write_text("{}")
        (tmp_path / "trace.json").write_text("{not json")
        (tmp_path / "flight.json").write_text("")
        _, _, trace, flight, *_ = mod._load(str(tmp_path))
        assert trace is None and flight is None

    def test_renders_slo_and_tracing_sections(self, tmp_path):
        mod = _load_tool("metrics_report")
        metrics = {
            "serving_slo_requests_total": {
                "type": "counter", "help": "", "series": [
                    {"labels": {"dimension": "ttft", "result": "good"},
                     "value": 9.0},
                    {"labels": {"dimension": "ttft",
                                "result": "violation"}, "value": 1.0}]},
            "serving_slo_burn_rate": {
                "type": "gauge", "help": "", "series": [
                    {"labels": {"dimension": "ttft"}, "value": 2.5}]},
            "serving_finish_total": {
                "type": "counter", "help": "", "series": [
                    {"labels": {"reason": "length"}, "value": 8.0},
                    {"labels": {"reason": "deadline"}, "value": 2.0}]},
        }
        trace = {"spans": [
            {"name": "request", "trace_id": "t1", "duration_s": 0.01},
            {"name": "request", "trace_id": "t2", "duration_s": 0.03}],
            "recorded": 2, "dropped": 0}
        flight = {"capacity": 512, "events": [
            {"category": "engine", "event": "submit"},
            {"category": "engine", "event": "finish"}]}
        text = mod.report(metrics, None, trace, flight)
        assert "SLO / request outcomes" in text
        assert "ttft" in text and "burn-rate 2.5" in text
        assert "deadline=2" in text
        assert "Tracing" in text and "2 spans across 2 traces" in text
        assert "engine.submit=1" in text

    def test_live_dump_round_trip(self, tmp_path, tiny_model):
        """A real obs.dump() renders end to end with the new sections
        present and the old ones intact."""
        engine = create_engine(tiny_model, max_slots=2, page_size=PAGE,
                               num_pages=64, max_model_len=128)
        engine.submit(np.array(PROMPT, np.int32),
                      GenerationConfig(max_new_tokens=2))
        engine.run_until_complete()
        out = obs.dump(str(tmp_path))
        assert out == str(tmp_path)
        mod = _load_tool("metrics_report")
        args = mod._load(str(tmp_path))
        text = mod.report(args[0], args[1], args[2], args[3])
        assert "Serving" in text and "Tracing" in text
        doc = json.loads((tmp_path / "trace.json").read_text())
        assert doc["spans"] and doc["traceEvents"]
        assert json.loads((tmp_path / "flight.json").read_text())["events"]
