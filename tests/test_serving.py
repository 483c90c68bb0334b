"""Continuous-batching serving engine (paddle_tpu/serving/).

Covers the block-manager allocator, the FCFS iteration-level scheduler,
and the engine acceptance criteria: staggered admissions into a single
decode trace, exact greedy parity with the one-shot paged generate,
cancellation/deadlines, streaming, drain, and the serving metrics dump.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models import generation as G
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (BlockManager, GenerationConfig, Request,
                                RequestState, Scheduler, create_engine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------- block manager
class TestBlockManager:
    def test_alloc_free_reuse(self):
        bm = BlockManager(num_pages=8, page_size=4)
        a = bm.allocate(0, 3)
        b = bm.allocate(1, 4)
        assert a == [0, 1, 2] and b == [3, 4, 5, 6]
        assert bm.pages_in_use == 7 and bm.free_pages == 1
        bm.free_seq(0)
        assert bm.free_pages == 4
        # FIFO reuse: the remaining fresh page goes out before recycled
        c = bm.allocate(2, 2)
        assert c == [7, 0]
        bm.free_seq(0)              # idempotent — seq 0 owns nothing now
        assert bm.free_pages == 2
        assert bm.pages_of(1) == [3, 4, 5, 6]
        with pytest.raises(ValueError):
            bm.allocate(1, 1)       # double allocation for a live seq

    def test_pages_needed_non_multiple(self):
        bm = BlockManager(num_pages=8, page_size=4)
        # whole-lifetime reservation, ceil to page size
        assert bm.pages_needed(1, 1) == 1
        assert bm.pages_needed(3, 1) == 1
        assert bm.pages_needed(3, 2) == 2       # 5 tokens -> 2 pages
        assert bm.pages_needed(8, 1) == 3       # 9 tokens -> 3 pages
        assert bm.pages_needed(7, 9) == 4

    def test_exhaustion_is_backpressure_not_error(self):
        bm = BlockManager(num_pages=4, page_size=4)
        assert bm.allocate(0, 3) is not None
        assert not bm.can_allocate(2)
        assert bm.allocate(1, 2) is None        # no exception
        assert bm.pages_of(1) == []             # nothing partially held
        assert bm.pages_in_use == 3
        bm.free_seq(0)
        assert bm.allocate(1, 2) is not None

    def test_table_rows_dump_padded(self):
        bm = BlockManager(num_pages=4, page_size=4)
        bm.allocate(7, 2)
        row = bm.table_row(7, width=5)
        assert row.dtype == np.int32
        assert row.tolist() == [0, 1, 4, 4, 4]  # dump page = num_pages
        assert bm.empty_row(3).tolist() == [4, 4, 4]
        with pytest.raises(ValueError):
            bm.table_row(7, width=1)


# ---------------------------------------------------------- prefix cache
class TestPrefixCacheBlockManager:
    def test_chain_match_refcounts_and_lru_park(self):
        bm = BlockManager(num_pages=16, page_size=4,
                          enable_prefix_cache=True)
        A = tuple(range(100, 112))              # 12 tokens = 3 full chunks
        a = bm.allocate_seq(0, A, max_new_tokens=4)
        assert len(a) == 4                      # 16 tokens -> 4 pages
        assert bm.seq_meta(0) == {"cached_len": 0, "cow_src": None}
        bm.free_seq(0)
        # the 3 registered chunk pages park in the LRU (still matchable);
        # the unregistered decode page went back to the free list
        assert bm.cached_pages == 3
        assert bm.pages_in_use == 0
        b = bm.allocate_seq(1, A, max_new_tokens=4)
        # full-prompt hit drops the LAST chunk so one token still runs
        # through the model (its logits seed decoding)
        assert bm.seq_meta(1)["cached_len"] == 8
        assert b[:2] == a[:2]                   # shared chain pages
        # misses: 3 cold chunks at seq 0's admission + the dropped one
        assert bm.prefix_hits == 2 and bm.prefix_misses == 4
        bm.free_seq(1)
        assert bm.pages_in_use == 0             # refcounts back to 0

    def test_cow_tail_match(self):
        bm = BlockManager(num_pages=8, page_size=4,
                          enable_prefix_cache=True)
        a = bm.allocate_seq(0, (1, 2, 3, 4, 5, 6), max_new_tokens=2)
        bm.free_seq(0)
        # B shares the full chunk and 1 of 2 tail tokens -> chain hit +
        # copy-on-write from A's tail page
        b = bm.allocate_seq(1, (1, 2, 3, 4, 5, 9), max_new_tokens=2)
        meta = bm.seq_meta(1)
        assert b[0] == a[0]                     # shared chunk page
        assert meta["cached_len"] == 5          # 4 (chunk) + 1 (tail lcp)
        assert meta["cow_src"] == a[1]          # A's tail page
        assert bm.cow_copies == 1

    def test_eviction_leaf_first_under_pressure(self):
        bm = BlockManager(num_pages=4, page_size=4,
                          enable_prefix_cache=True)
        bm.allocate_seq(0, tuple(range(50, 62)), max_new_tokens=4)
        bm.free_seq(0)
        assert bm.cached_pages == 3 and bm.free_pages == 1
        assert bm.can_allocate(4)               # LRU pages are reclaimable
        # a disjoint prompt needs all 4 pages: 1 free + 3 LRU evictions
        pages = bm.allocate_seq(1, tuple(range(200, 212)),
                                max_new_tokens=4)
        assert pages is not None and len(pages) == 4
        assert bm.prefix_evictions == 3
        assert bm.cached_pages == 3             # seq 1's chunks registered

    def test_backpressure_rolls_back_matched_refs(self):
        bm = BlockManager(num_pages=4, page_size=4,
                          enable_prefix_cache=True)
        A = tuple(range(10, 18))                # 2 chunks
        bm.allocate_seq(0, A, max_new_tokens=4)     # 3 pages, still live
        # same prefix, but the suffix does not fit -> None, and the
        # matched pages' refcounts roll back to A's alone
        assert bm.allocate_seq(1, A + tuple(range(90, 98)),
                               max_new_tokens=8) is None
        assert bm.pages_of(1) == []
        bm.free_seq(0)
        assert bm.pages_in_use == 0


# ------------------------------------------------------------- scheduler
class TestScheduler:
    def _req(self, plen, n_new, **kw):
        return Request(np.arange(1, plen + 1),
                       GenerationConfig(max_new_tokens=n_new), **kw)

    def test_fcfs_admission_and_slot_backpressure(self):
        sched = Scheduler(BlockManager(num_pages=16, page_size=4), 2)
        reqs = [self._req(4, 4) for _ in range(3)]
        for r in reqs:
            sched.submit(r)
        admitted = sched.schedule(now=0.0)
        assert [r.id for _, r in admitted] == [reqs[0].id, reqs[1].id]
        assert all(r.state == RequestState.PREFILL for _, r in admitted)
        assert len(sched.queue) == 1            # no free slot for #3
        sched.evict(0, "finished", now=1.0)
        admitted = sched.schedule(now=1.0)
        assert [r.id for _, r in admitted] == [reqs[2].id]

    def test_page_backpressure_blocks_head_fcfs(self):
        blocks = BlockManager(num_pages=4, page_size=4)
        sched = Scheduler(blocks, 4)
        big = self._req(12, 4)      # needs 4 pages
        small = self._req(2, 2)     # would fit, but arrives second
        sched.submit(self._req(8, 4))           # 3 pages -> admitted
        sched.submit(big)
        sched.submit(small)
        admitted = sched.schedule(now=0.0)
        assert len(admitted) == 1
        # strict FCFS: small must NOT overtake the blocked big request
        assert small.state == RequestState.QUEUED
        assert blocks.pages_in_use == 3
        sched.evict(admitted[0][0], "finished", now=1.0)
        admitted = sched.schedule(now=1.0)
        assert [r for _, r in admitted] == [big]    # takes all 4 pages
        assert small.state == RequestState.QUEUED
        sched.evict(admitted[0][0], "finished", now=2.0)
        admitted = sched.schedule(now=2.0)
        assert [r for _, r in admitted] == [small]

    def test_queued_cancellation_and_deadline(self):
        sched = Scheduler(BlockManager(num_pages=4, page_size=4), 1)
        a, b = self._req(2, 2), self._req(2, 2, deadline=5.0)
        blocker = self._req(2, 2)
        sched.submit(blocker)
        sched.submit(a)
        sched.submit(b)
        sched.schedule(now=0.0)
        a.cancel()
        sched.schedule(now=10.0)    # b's deadline passed while queued
        assert a.state == RequestState.CANCELLED
        assert a.finish_reason == "cancelled"
        assert b.state == RequestState.CANCELLED
        assert b.finish_reason == "deadline"
        assert not sched.queue


# ---------------------------------------------------------------- engine
@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(11)
    cfg = llama_tiny(vocab_size=128, hidden_size=64, intermediate_size=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def test_engine_acceptance_staggered_parity_and_metrics(tiny_model,
                                                        tmp_path):
    """The ISSUE acceptance test: >=8 staggered requests with mixed
    prompt/output lengths through max_slots=3 (forcing continuous
    batching), ONE decode-step trace, token-for-token greedy parity with
    the one-shot paged generate, and a metrics dump whose TTFT/TPOT
    histograms and pages-in-use samples are non-zero."""
    obs.reset()
    model = tiny_model
    ps = 8
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 128, int(rng.integers(9, 17)))
               .astype(np.int32) for _ in range(8)]
    n_new = [int(rng.integers(3, 11)) for _ in range(8)]

    # one-shot reference over the same prompts: right-pad to width 16 ==
    # the engine's prefill bucket for lens 9..16, so both paths see
    # identical padded prefill shapes
    W = 16
    ids = np.zeros((8, W), np.int64)
    for i, p in enumerate(prompts):
        ids[i, :p.size] = p
    out = G.generate(model, ids, max_new_tokens=max(n_new), cache="paged",
                     page_size=ps,
                     lengths=np.array([p.size for p in prompts], np.int32))
    ref = np.asarray(out._data)[:, W:]

    eng = create_engine(model, max_slots=3, page_size=ps, max_model_len=64)
    reqs = []
    pending = list(zip(prompts, n_new))
    steps = 0
    # staggered arrivals: two submissions between engine iterations, so
    # admissions interleave with in-flight decode (continuous batching)
    while pending or eng.scheduler.has_work():
        for _ in range(2):
            if pending:
                p, n = pending.pop(0)
                reqs.append(eng.submit(
                    p, GenerationConfig(max_new_tokens=n)))
        eng.step()
        steps += 1
        assert steps < 500
    assert len(reqs) == 8

    for i, r in enumerate(reqs):
        assert r.state == RequestState.DONE
        assert r.finish_reason == "length"
        assert r.num_generated == n_new[i]
        assert r.output_tokens == ref[i, :n_new[i]].tolist(), \
            f"request {i} diverged from one-shot paged generate"

    # the no-retrace contract: every admission/eviction reused ONE trace
    assert eng.decode_traces == 1
    assert eng.stats()["pages_in_use"] == 0     # all pages returned

    out_dir = obs.dump(str(tmp_path / "metrics"))
    with open(os.path.join(out_dir, "metrics.json")) as f:
        metrics = json.load(f)

    def total(name, field="value"):
        return sum(s.get(field, 0)
                   for s in metrics.get(name, {}).get("series", []))

    assert total("serving_decode_step_traces_total") == 1
    assert total("serving_ttft_seconds", "count") == 8
    assert total("serving_tpot_seconds", "count") > 0
    assert total("serving_ttft_seconds", "sum") > 0
    assert total("serving_tpot_seconds", "sum") > 0
    assert total("serving_pages_in_use_hist", "count") > 0
    assert total("serving_admissions_total") == 8
    assert total("serving_tokens_total") == sum(n_new)
    assert total("serving_requests_total") == 8

    # the metrics_report CLI renders a serving section from this dump
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
        text = metrics_report.report(metrics, None)
    finally:
        sys.path.pop(0)
    assert "TTFT" in text and "TPOT" in text
    assert "serving_tokens_total" in text


def test_engine_streaming_and_callback(tiny_model):
    eng = create_engine(tiny_model, max_slots=2, page_size=8,
                        max_model_len=64)
    seen = []
    req = eng.submit(np.arange(1, 6),
                     GenerationConfig(max_new_tokens=5),
                     on_token=lambda r, t: seen.append(t))
    got = list(req.stream())        # pulls the engine until done
    assert got == req.output_tokens == seen
    assert len(got) == 5
    assert req.state == RequestState.DONE
    # a second request through the same engine: result() convenience
    req2 = eng.submit(np.arange(1, 10), GenerationConfig(max_new_tokens=3))
    assert req2.result() == req2.output_tokens
    assert eng.decode_traces == 1   # still the one trace


def test_engine_cancel_and_deadline(tiny_model):
    t = [0.0]
    eng = create_engine(tiny_model, max_slots=1, page_size=8,
                        max_model_len=64, clock=lambda: t[0])
    # running request cancelled at an iteration boundary
    a = eng.submit(np.arange(1, 5), GenerationConfig(max_new_tokens=20))
    eng.step()
    assert a.state == RequestState.DECODE and a.num_generated >= 1
    a.cancel()
    eng.step()
    assert a.state == RequestState.CANCELLED
    assert a.finish_reason == "cancelled"
    assert eng.blocks.pages_in_use == 0         # pages came back

    # deadline expiry mid-decode (engine clock is injectable)
    b = eng.submit(np.arange(1, 5),
                   GenerationConfig(max_new_tokens=50), deadline=10.0)
    eng.step()
    n_before = b.num_generated
    t[0] = 11.0
    eng.step()
    assert b.state == RequestState.CANCELLED
    assert b.finish_reason == "deadline"
    assert b.num_generated == n_before
    assert not eng.scheduler.has_work()


def test_engine_scheduler_eviction_parks_slot(tiny_model):
    """Regression: cancel/deadline evictions happen inside
    scheduler.schedule(), not the _emit length/eos path.  The freed slot
    must be parked on the dump page immediately — the lockstep decode
    step writes KV for EVERY slot, so a stale slot would keep writing
    into its freed pages and corrupt them once reallocated to a request
    admitted into a different slot."""
    solo = create_engine(tiny_model, max_slots=1, page_size=8,
                         max_model_len=64)
    ref = solo.submit(np.arange(1, 10), GenerationConfig(max_new_tokens=8))
    solo.run_until_complete(max_steps=50)

    eng = create_engine(tiny_model, max_slots=3, page_size=8,
                        num_pages=12, max_model_len=64)
    dump = eng.blocks.num_pages
    a = eng.submit(np.arange(1, 6), GenerationConfig(max_new_tokens=40))
    b = eng.submit(np.arange(1, 6), GenerationConfig(max_new_tokens=2))
    d = eng.submit(np.arange(1, 6), GenerationConfig(max_new_tokens=30))
    eng.step()                  # all three admitted, first step dispatched
    assert b.state == RequestState.DECODE   # its row is still in flight
    eng.step()                  # ... and walked after the second's
    assert b.state == RequestState.DONE     # dispatch: b finishes (slot 1)
    d.cancel()
    eng.step()                  # scheduler evicts d from slot 2
    assert d.state == RequestState.CANCELLED
    # slot 2 parks even though nothing was admitted into it
    assert eng.table[2].tolist() == [dump] * eng.table_width
    assert eng._pos[2] == 0 and eng._tok[2] == 0
    # e lands in slot 1 (freed by b) but reuses d's freed pages; a stale
    # slot 2 would keep writing garbage KV into them while e decodes
    e = eng.submit(np.arange(1, 10), GenerationConfig(max_new_tokens=8))
    eng.step()
    assert eng.scheduler.slots[1] is e
    assert set(eng.blocks.pages_of(e.id)) & set(range(7, 12))
    eng.run_until_complete(max_steps=200)
    assert a.state == RequestState.DONE and a.num_generated == 40
    assert e.output_tokens == ref.output_tokens, \
        "reallocated pages were corrupted by a stale (unparked) slot"


def test_pick_token_all_masked_logits_clear_error(tiny_model):
    eng = create_engine(tiny_model, max_slots=1, page_size=8,
                        max_model_len=64, emit_logits=True)
    req = Request(np.arange(1, 4),
                  GenerationConfig(max_new_tokens=2, do_sample=True))
    with pytest.raises(ValueError, match="finite logits"):
        eng._pick_token(req, np.full(128, -np.inf))
    with pytest.raises(ValueError, match="finite logits"):
        eng._pick_token(req, np.full(128, np.nan))


def test_engine_drain_and_resume(tiny_model):
    eng = create_engine(tiny_model, max_slots=1, page_size=8,
                        max_model_len=64)
    a = eng.submit(np.arange(1, 4), GenerationConfig(max_new_tokens=4))
    b = eng.submit(np.arange(1, 4), GenerationConfig(max_new_tokens=4))
    eng.step()                      # a admitted; b queued behind it
    eng.drain()                     # finish a, do not admit b
    assert a.state == RequestState.DONE
    assert b.state == RequestState.QUEUED
    assert not eng.scheduler.has_work()
    eng.resume()
    eng.run_until_complete(max_steps=50)
    assert b.state == RequestState.DONE
    assert b.num_generated == 4


def test_engine_submit_validation(tiny_model):
    eng = create_engine(tiny_model, max_slots=2, page_size=8,
                        max_model_len=32)
    with pytest.raises(ValueError, match="max_model_len"):
        eng.submit(np.arange(1, 30), GenerationConfig(max_new_tokens=8))
    with pytest.raises(ValueError, match="emit_logits"):
        eng.submit(np.arange(1, 4),
                   GenerationConfig(max_new_tokens=2, do_sample=True))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.array([], np.int32))
    # oversized-for-the-pool requests are rejected up front, not left to
    # block the FCFS queue forever
    small = create_engine(tiny_model, max_slots=1, page_size=8,
                          num_pages=2, max_model_len=64)
    with pytest.raises(ValueError, match="pages"):
        small.submit(np.arange(1, 20),
                     GenerationConfig(max_new_tokens=10))


def test_engine_sampling_per_request_rng(tiny_model):
    eng = create_engine(tiny_model, max_slots=2, page_size=8,
                        max_model_len=64, emit_logits=True)
    greedy = eng.submit(np.arange(1, 8), GenerationConfig(max_new_tokens=6))
    sampled = eng.submit(
        np.arange(1, 8),
        GenerationConfig(max_new_tokens=6, do_sample=True,
                         temperature=0.8, top_k=20, top_p=0.95, seed=3))
    eng.run_until_complete(max_steps=100)
    assert greedy.num_generated == sampled.num_generated == 6
    assert all(0 <= t < 128 for t in sampled.output_tokens)
    assert eng.decode_traces == 1   # sampling is host-side: same trace


def _greedy_outputs(model, prompts, n_new, **engine_kw):
    eng = create_engine(model, **engine_kw)
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=n))
            for p, n in zip(prompts, n_new)]
    eng.run_until_complete(max_steps=500)
    assert all(r.state == RequestState.DONE for r in reqs)
    return eng, [r.output_tokens for r in reqs]


def test_engine_prefix_cache_parity_and_cow_divergence(tiny_model,
                                                       tmp_path):
    """The ISSUE acceptance invariant: greedy decode is token-for-token
    identical with prefix caching on vs. off, including two requests
    that share a 19-token prefix and diverge in the last prompt token
    (chain hit on 2 full pages + copy-on-write off the shared tail
    page), and again with deferred host sync (sync_interval=4)."""
    obs.reset()
    model = tiny_model
    a = np.arange(1, 21).astype(np.int32)       # 20 tokens, ps=8
    b = a.copy()
    b[19] = 99                                  # diverge at token 19
    prompts, n_new = [a, b], [6, 6]
    kw = dict(max_slots=2, page_size=8, max_model_len=64)

    _, ref = _greedy_outputs(model, prompts, n_new, **kw)
    eng, got = _greedy_outputs(model, prompts, n_new,
                               enable_prefix_cache=True, **kw)
    assert got == ref, "prefix caching changed greedy output"
    # b matched a's two full chunk pages (a registered them at its own
    # admission in the same scheduling pass) and CoW'd the shared tail
    st = eng.stats()
    assert st["prefix_hits"] == 2 and st["cow_copies"] == 1
    assert st["cached_tokens"] == 19
    assert st["pages_in_use"] == 0              # refcounts back to 0
    assert st["cached_pages"] > 0               # ...but still matchable
    assert eng.decode_traces == 1

    # same workload again, submitted AFTER the first pair finished
    # (matches against LRU-parked pages) and with deferred host sync
    eng2, got2 = _greedy_outputs(model, prompts, n_new,
                                 enable_prefix_cache=True,
                                 sync_interval=4, **kw)
    assert got2 == ref, "deferred host sync changed greedy output"
    c = eng2.submit(a, GenerationConfig(max_new_tokens=6))
    eng2.run_until_complete(max_steps=200)
    assert c.output_tokens == ref[0]
    assert c.num_cached_tokens == 19    # CoW cap: >=1 token recomputes
    assert eng2.decode_traces == 1

    # the new metrics render in the serving report
    out_dir = obs.dump(str(tmp_path / "m"))
    with open(os.path.join(out_dir, "metrics.json")) as f:
        metrics = json.load(f)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
        text = metrics_report.report(metrics, None)
    finally:
        sys.path.pop(0)
    assert "prefix-cache page hit rate" in text
    assert "serving_host_syncs_total" in text


def test_engine_prefix_cache_eviction_under_pressure(tiny_model):
    """Cached refcount-0 pages are reclaimed (LRU, leaf-first) when a
    disjoint request needs the pool — and the evicted-cache request
    still decodes correctly."""
    model = tiny_model
    a = np.arange(1, 17).astype(np.int32)       # 2 full pages, ps=8
    d = np.arange(40, 64).astype(np.int32)      # disjoint, 3 pages
    kw = dict(max_slots=1, page_size=8, num_pages=4, max_model_len=32)
    _, ref = _greedy_outputs(model, [a, d], [8, 8], **kw)

    eng = create_engine(model, enable_prefix_cache=True, **kw)
    ra = eng.submit(a, GenerationConfig(max_new_tokens=8))
    eng.run_until_complete(max_steps=100)
    assert eng.stats()["cached_pages"] == 2     # a's chunks parked
    rd = eng.submit(d, GenerationConfig(max_new_tokens=8))
    eng.run_until_complete(max_steps=100)
    assert [ra.output_tokens, rd.output_tokens] == ref
    st = eng.stats()
    assert st["prefix_evictions"] >= 1          # pool forced eviction
    assert eng.decode_traces == 1


def test_engine_sync_interval_host_syncs_and_logits_skip(tiny_model):
    """Device-resident decode: the host drains the token ring once per
    sync_interval greedy steps, and the [slots, vocab] logits transfer
    is skipped entirely unless an active request samples."""
    model = tiny_model
    p = np.arange(1, 10).astype(np.int32)
    kw = dict(max_slots=2, page_size=8, max_model_len=64,
              emit_logits=True)
    _, ref = _greedy_outputs(model, [p], [9], **kw)
    eng, got = _greedy_outputs(model, [p], [9], sync_interval=4, **kw)
    assert got == ref
    # 8 decode steps make tokens 2..9 (the first comes from prefill) =
    # 2 ring drains; the 9th step is the overrun dispatched before the
    # second drain (the host runs one step behind): its row is dropped
    # unfetched, and the 8th row, walked after it, ends the request
    assert eng.host_syncs == 2
    assert eng.decode_steps == 9 and eng.overrun_rows == 1
    assert eng.overlapped_steps == 8            # all but the first
    # all-greedy: emit_logits=True must not pull logits to the host
    assert eng.logit_fetches == 0

    # a sampling request forces per-step syncs + logits fetches
    rs = eng.submit(p, GenerationConfig(max_new_tokens=4,
                                        do_sample=True, seed=5))
    eng.run_until_complete(max_steps=100)
    assert rs.num_generated == 4
    assert eng.logit_fetches >= 3               # one per sampled step
    # ... in lockstep: no step is dispatched ahead of a sampled token
    assert eng.overlapped_steps == 8 and eng.overrun_rows == 1
    assert eng.decode_traces == 1


def test_engine_paged_block_counters(tiny_model, monkeypatch):
    """``paged_blocks_live / paged_blocks_grid``: the share of the paged
    decode kernel's grid steps that hold visible tokens, from the
    lengths the host holds; worked by hand for three requests.  The
    counters only grow, so differences of ``stats()`` add up."""
    from paddle_tpu.ops.pallas import paged_attention as PA
    obs.tracer().reset()
    # blocks of 32 tokens = 4 pages; a row of 12 pages makes 3 grid steps
    cfg = tiny_model.config     # float32 pages of 8 rows
    monkeypatch.setattr(PA, "BLOCK_BYTES", 4 * (
        cfg.num_key_value_heads * 8 * cfg.head_dim * 4))
    eng = create_engine(tiny_model, max_slots=2, page_size=8,
                        max_model_len=96)
    keys = ("paged_blocks_live", "paged_blocks_grid")

    def counted():
        st = eng.stats()
        return np.array([st[k] for k in keys])

    def serve(*jobs):
        reqs = [eng.submit(np.arange(1, n + 1).astype(np.int32),
                           GenerationConfig(max_new_tokens=m))
                for n, m in jobs]
        eng.run_until_complete(max_steps=200)
        assert all(r.state == RequestState.DONE for r in reqs)

    s0 = counted()
    assert s0.tolist() == [0, 0]
    # prompt 30, 5 tokens: the first from the prefill, then 4 decode
    # steps that see 31, 32, 33, 34 tokens = 1 + 1 + 2 + 2 blocks, and
    # the overrun step (dispatched before the host saw the finish) over
    # 35 = 2 more, of 15; prompt 63, 4 tokens: 3 steps over 64, 65, 66
    # = 2 + 3 + 3 and its overrun step over 67 = 3 more, of 12
    serve((30, 5), (63, 4))
    s1 = counted()
    assert (s1 - s0).tolist() == [14 + 2 + 3, 21 + 3 + 3]
    # prompt 10, 3 tokens: 2 steps over 11, 12 tokens = 1 + 1, and the
    # overrun step over 13 = 1 more, of 9
    serve((10, 3))
    s2 = counted()
    assert (s2 - s1).tolist() == [2 + 1, 6 + 3]
    assert (s2 - s0).tolist() == [22, 36]
    assert eng.stats()["overrun_rows"] == 3     # one a request
    # the same numbers ride the dispatch spans, step by step
    spans = [s for s in obs.tracer().spans()
             if s.name == "engine.decode.dispatch"]
    assert len(spans) == eng.decode_steps
    assert [sum(s.attributes[k] for s in spans) for k in keys] == [22, 36]


@pytest.mark.parametrize("family", ["llama", "latent"])
def test_paged_block_counters_follow_the_familys_kernel(tiny_model,
                                                        monkeypatch, family):
    """A latent engine counts grid steps by ``mla_paged_attention``'s
    block of pages, a Llama engine by ``paged_attention``'s: the host's
    arithmetic on its position mirror, nothing dispatched."""
    from paddle_tpu.ops.pallas import mla_paged_attention as MLA
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.serving.engine import Engine
    cfg = tiny_model.config     # the K/V rule goes by a page's bytes
    page = cfg.num_key_value_heads * 8 * cfg.head_dim * 4   # float32
    monkeypatch.setattr(PA, "BLOCK_BYTES", 2 * page)  # 2 pages, 8 blocks a row
    monkeypatch.setattr(MLA, "BLOCK_TOKENS", 64)    # 8 pages, 2 blocks a row
    kw = dict(max_slots=3, page_size=8, max_model_len=128)
    if family == "latent":
        from test_deepseek_v3 import toy_cfg, toy_state
        cfg = toy_cfg()
        eng, block = Engine(config=cfg, state=toy_state(cfg), **kw), 64
    else:
        eng, block = create_engine(tiny_model, **kw), 16
    assert (eng._block_tokens, eng._blocks_per_row) == (block, 128 // block)
    # the step about to run sees 1, 64 and 65 tokens
    eng._pos[:] = [0, 63, 64]
    assert eng._count_paged_blocks([0, 1, 2]) == {
        16: (1 + 4 + 5, 3 * 8), 64: (1 + 1 + 2, 3 * 2)}[block]
    # slots left out add nothing; past the table's row a context is
    # counted as the kernel sees it, cut to the row
    eng._pos[2] = 500
    assert eng._count_paged_blocks([2]) == (128 // block, 128 // block)
    st = eng.stats()
    assert (st["paged_blocks_live"], st["paged_blocks_grid"]) == {
        16: (10 + 8, 24 + 8), 64: (4 + 2, 6 + 2)}[block]
    assert eng.decode_steps == 0


def test_engine_prefix_cache_staggered_no_retrace(tiny_model):
    """Admissions/evictions with caching enabled (shared-prefix
    workload, staggered arrivals, deferred sync) never retrace the
    decode step."""
    model = tiny_model
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 128, 16).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 128, int(n)).astype(
                                   np.int32)])
               for n in rng.integers(2, 9, 6)]
    n_new = [int(n) for n in rng.integers(3, 8, 6)]
    eng = create_engine(model, max_slots=2, page_size=8,
                        max_model_len=64, enable_prefix_cache=True,
                        sync_interval=3)
    reqs, pending, steps = [], list(zip(prompts, n_new)), 0
    while pending or eng.scheduler.has_work():
        if pending:
            pp, nn = pending.pop(0)
            reqs.append(eng.submit(pp, GenerationConfig(
                max_new_tokens=nn)))
        eng.step()
        steps += 1
        assert steps < 500
    assert all(r.state == RequestState.DONE for r in reqs)
    assert eng.decode_traces == 1
    st = eng.stats()
    assert st["prefix_hits"] > 0                # the shared prefix hit
    assert st["pages_in_use"] == 0


@pytest.mark.slow
def test_serve_bench_cli(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--requests", "6", "--max-slots", "2", "--page-size", "8",
         "--new-tokens", "2", "6", "--prompt-len", "4", "12",
         "--layers", "2", "--hidden", "64", "--vocab", "128",
         "--max-model-len", "64",
         "--metrics-dir", str(tmp_path / "m")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "throughput" in out.stdout
    assert "decode-step traces   1" in out.stdout
    assert os.path.exists(tmp_path / "m" / "metrics.json")
