"""The decode loop runs one step behind the device (ISSUE 31).

``Engine._decode`` dispatches step n+1 and only then fetches and walks
step n's ring row; a finish is seen one step late and its overrun row
is dropped.  Every case here holds the lagging loop token-exact against
``Lockstep``: the same engine made to walk every row before the next
dispatch, which is the loop as it was.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.generation import GenerationConfig
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving.engine import Engine
from paddle_tpu.serving.request import RequestState

KW = dict(max_slots=2, page_size=8, max_model_len=64)
PROMPTS = [np.arange(1, 10), np.arange(3, 15), np.arange(2, 8)]
N_NEW = [9, 6, 7]


class Lockstep(Engine):
    """The reference: every step's rows are on the host before the next
    step is dispatched (``overlapped_steps`` stays 0)."""

    def _decode(self, active):
        super()._decode(active)
        self._settle()


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(11)
    model = LlamaForCausalLM(
        llama_tiny(vocab_size=128, hidden_size=64, intermediate_size=128))
    model.eval()
    return model


def serve(cls, model, *, gens=None, before_run=None, **kw):
    """All of PROMPTS through ``cls``; returns (engine, requests, the
    ledger's committed tokens as each token was handed over)."""
    eng = cls(model, **dict(KW, **kw))
    ledger = {}

    def on_token(req, tok):
        ledger.setdefault(req.id, []).append(
            eng.blocks.committed_tokens(req.id))

    gens = gens or [GenerationConfig(max_new_tokens=n) for n in N_NEW]
    reqs = [eng.submit(p.astype(np.int32), g, on_token=on_token)
            for p, g in zip(PROMPTS, gens)]
    if before_run is not None:
        before_run(eng, reqs)
    eng.run_until_complete(max_steps=500)
    assert eng._flight is None and not eng._pending
    assert eng.blocks.pool_accounting()["leak"] == 0
    assert eng.decode_traces == 1
    return eng, reqs, [ledger[r.id] for r in reqs]


def tokens(reqs):
    return [(list(r.output_tokens), r.finish_reason) for r in reqs]


# ------------------------------------------------------------- the order
def test_next_dispatch_opens_before_the_steps_host_sync(tiny_model):
    """The tracer's ring: step n+1's ``engine.decode.dispatch`` has
    closed before step n's ``engine.host_sync`` opens, and that fetch
    and its walk are over before step n+2 is dispatched."""
    obs.tracer().reset()
    eng, reqs, _ = serve(Engine, tiny_model)
    spans = obs.tracer().spans()
    dispatch = [s for s in spans if s.name == "engine.decode.dispatch"]
    syncs = [s for s in spans if s.name == "engine.host_sync"]
    emits = [s for s in spans if s.name == "engine.emit"]
    assert len(dispatch) == eng.decode_steps
    # the last step is the overrun dispatched before the last finish
    # was seen: its row is dropped, every other row is fetched
    assert len(syncs) == len(emits) == eng.host_syncs == len(dispatch) - 1
    for n, (sync, emit) in enumerate(zip(syncs, emits)):
        assert dispatch[n + 1].end_time <= sync.start       # n+1 is out
        assert sync.end_time <= emit.start
        if n + 2 < len(dispatch):
            assert emit.end_time <= dispatch[n + 2].start
    assert [d.attributes["overlapped"] for d in dispatch] == (
        [False] + [True] * (len(dispatch) - 1))
    st = eng.stats()
    assert st["overlapped_steps"] == st["decode_steps"] - 1
    assert st["overrun_rows"] == len(reqs)      # one a finish


# ---------------------------------------------------------- token-exact
@pytest.mark.parametrize("sync_interval", [1, 4])
def test_greedy_token_exact_with_an_eos_finish(tiny_model, sync_interval):
    """Greedy outputs, finish reasons and the block ledger's committed
    tokens equal the lockstep loop's, with a request that ends on EOS
    mid-stream (its overrun row is dropped, not handed over)."""
    _, plain, _ = serve(Lockstep, tiny_model)
    outs = [list(r.output_tokens) for r in plain]
    eos = outs[2][3]                            # ends request 2 early
    assert all(eos not in o for o in (outs[0], outs[1], outs[2][:3]))
    gens = [GenerationConfig(max_new_tokens=n, eos_token_id=eos)
            for n in N_NEW]
    ref_eng, ref, ref_ledger = serve(Lockstep, tiny_model, gens=gens)
    eng, got, ledger = serve(Engine, tiny_model, gens=gens,
                             sync_interval=sync_interval)
    assert [r.finish_reason for r in ref] == ["length", "length", "eos"]
    assert len(ref[2].output_tokens) == 4 < N_NEW[2]
    assert tokens(got) == tokens(ref)
    assert ledger == ref_ledger
    assert ref_eng.overlapped_steps == 0 and ref_eng.overrun_rows == 0
    # each finish a decode step makes is seen (at least) one row late
    assert eng.overrun_rows >= len(got)
    # every step but the first after an idle engine is dispatched with
    # an earlier row unfetched; lockstep never is
    assert eng.overlapped_steps >= eng.decode_steps - 2
    assert eng.blocks.pages_allocated == ref_eng.blocks.pages_allocated


# ------------------------------------------------- where the lag yields
def test_a_sampling_resident_keeps_the_loop_in_lockstep(tiny_model):
    """A resident that samples needs its token fed back: no step is
    dispatched ahead while it is there; the lag comes back when it has
    gone.  ``top_k=1`` makes its draws the greedy tokens."""
    ref_eng, ref, _ = serve(Lockstep, tiny_model, emit_logits=True)
    seen = {}

    def watch(eng, reqs):
        sampler = reqs[1]
        while not sampler.is_finished():
            eng.step()
        seen["while_resident"] = eng.overlapped_steps
        seen["fetches"] = eng.logit_fetches

    gens = [GenerationConfig(max_new_tokens=n) for n in N_NEW]
    gens[1] = GenerationConfig(max_new_tokens=N_NEW[1], do_sample=True,
                               top_k=1, seed=3)
    eng, got, _ = serve(Engine, tiny_model, gens=gens, before_run=watch,
                        emit_logits=True)
    assert tokens(got) == tokens(ref)
    assert seen["while_resident"] == 0
    assert seen["fetches"] == N_NEW[1] - 1      # one a sampled step
    assert eng.overlapped_steps > 0             # greedy again: lagging


@pytest.mark.parametrize("spec_k", [2, 4])
def test_a_proposer_keeps_the_loop_in_lockstep(tiny_model, spec_k):
    """With a proposer configured the host has every token before it
    drafts: nothing is dispatched ahead, and it drafts as often as the
    lockstep loop does."""
    rep = [np.array([5, 6, 5, 6, 5, 6, 5, 6]), np.array([3, 4, 3, 4, 3, 4]),
           np.array([7, 7, 7, 7, 7])]
    out = []
    for cls in (Lockstep, Engine):
        eng = cls(tiny_model, spec_k=spec_k, **KW)
        reqs = [eng.submit(p.astype(np.int32),
                           GenerationConfig(max_new_tokens=12))
                for p in rep]
        eng.run_until_complete(max_steps=500)
        st = eng.stats()
        out.append((tokens(reqs), st["spec_verify_steps"],
                    st["spec_proposed"], st["spec_accepted"],
                    st["decode_steps"], st["host_syncs"]))
        assert st["overlapped_steps"] == 0 and st["overrun_rows"] == 0
    assert out[0] == out[1]
    assert out[1][1] > 0                        # it did draft


# -------------------------------------- what needs the host level first
def until_in_flight(eng, reqs):
    for _ in range(3):
        eng.step()
    assert eng._flight is not None              # a row is out
    assert all(r.state == RequestState.DECODE for r in reqs[:2])


def test_recover_with_a_row_in_flight_is_token_exact(tiny_model):
    """``recover()`` drops the row in flight with the dead runner and
    replays from what the requests were handed."""
    _, ref, _ = serve(Lockstep, tiny_model)

    def fault(eng, reqs):
        until_in_flight(eng, reqs)
        handed = [len(r.output_tokens) for r in reqs]
        out = eng.recover()
        assert out["replayed"] == 2 and eng._flight is None
        assert [len(r.output_tokens) for r in reqs] == handed

    eng, got, _ = serve(Engine, tiny_model, before_run=fault)
    assert tokens(got) == tokens(ref)
    assert eng.recoveries == 1


def test_drain_with_a_row_in_flight_is_token_exact(tiny_model):
    """``drain()`` ends when the residents have: the overrun row that
    is out when the last of them finishes is dropped."""
    _, ref, _ = serve(Lockstep, tiny_model)

    def drain(eng, reqs):
        until_in_flight(eng, reqs)
        eng.drain()
        assert eng.scheduler.active_count == 0
        assert eng._flight is None and not eng._pending
        assert reqs[0].is_finished() and reqs[1].is_finished()
        assert not reqs[2].is_finished()        # queued until resume
        eng.resume()

    eng, got, _ = serve(Engine, tiny_model, before_run=drain)
    assert tokens(got) == tokens(ref)


@pytest.mark.parametrize("cache", [False, True])
def test_preemption_with_a_row_in_flight_is_token_exact(tiny_model, cache):
    """A preemption walks the row in flight before it spills: the
    victim's pages and resume tokens are level with the device."""
    kw = dict(preempt=True, enable_prefix_cache=cache, num_pages=24)
    gens = [GenerationConfig(max_new_tokens=n) for n in N_NEW]
    ref = []
    for p, g in zip(PROMPTS, gens):             # each alone, lockstep
        e = Lockstep(tiny_model, **dict(KW, **kw))
        ref.append(e.submit(p.astype(np.int32), g))
        e.run_until_complete(max_steps=200)

    eng = Engine(tiny_model, **dict(KW, **kw))
    lo = [eng.submit(p.astype(np.int32), g)
          for p, g in zip(PROMPTS[:2], gens[:2])]
    until_in_flight(eng, lo)
    hi = eng.submit(PROMPTS[2].astype(np.int32), gens[2], priority=1)
    eng.step()
    assert eng.preemptions == 1
    eng.run_until_complete(max_steps=500)
    assert tokens(lo + [hi]) == tokens(ref)
    assert sorted(r.preemptions for r in lo) == [0, 1]
    assert eng.blocks.spilled_pages >= 1
    assert eng.blocks.pool_accounting()["leak"] == 0
    assert eng.decode_traces == 1


# ------------------------------------------------------- the slot patch
def test_push_slot_is_one_program_traced_once(tiny_model):
    """A slot's row is patched by one jitted program whatever the slot:
    the five state arrays come out of that one call."""
    eng = Engine(tiny_model, **dict(KW, max_slots=4))
    r = eng.runner
    calls = []
    real = r._push_fn

    def counted(*args):
        out = real(*args)
        calls.append(out)
        return out

    r._push_fn = counted
    for slot in (0, 3, 1, 3):
        row = np.full((eng.table_width,), 7 + slot, np.int32)
        r.push_slot(slot, row, pos=5 + slot, tok=9 + slot, active=1)
        table, pos, tok, active, aidx = calls[-1]
        assert r._table_dev is table and r._pos_dev is pos
        assert r._tok_dev is tok and r._active_dev is active
        assert aidx == () and r._aidx_dev == ()     # no adapters: no leaf
    assert len(calls) == 4 and r.push_traces == 1
    assert np.asarray(r._pos_dev).tolist() == [5, 6, 0, 8]
    assert np.asarray(r._tok_dev).tolist() == [9, 10, 0, 12]
    assert np.asarray(r._active_dev).tolist() == [1, 1, 0, 1]
    table = np.asarray(r._table_dev)
    assert (table[3] == 10).all() and (table[1] == 8).all()
    assert (table[2] == eng.blocks.dump_page).all()
    # the engine's own admissions and evictions go the same way
    eng.submit(PROMPTS[0].astype(np.int32),
               GenerationConfig(max_new_tokens=3))
    eng.run_until_complete(max_steps=50)
    assert len(calls) > 4 and r.push_traces == 1
