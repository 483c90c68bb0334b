"""Speculative decoding: greedy parity matrix + exact page accounting.

The speculation contract has two halves, and both are tested against
the plain engine rather than against expectations of the drafter:

  * **parity** — greedy outputs with ``spec_k>0`` are token-for-token
    identical to ``spec_k=0`` under every engine configuration that is
    itself parity-preserving: prefix cache on/off, deferred host sync,
    and a tp=2 mesh.  The verify program scores each position with
    exactly the context sequential decode would have had, so the
    accepted chain IS the greedy chain.
  * **accounting** — the committed-token ledger charges pages for
    accepted tokens only: speculative appends at dispatch, rejected-
    suffix rollback at sync, and the pool census stays exact through
    mixed accept/reject, finish-inside-a-verify-row, and eviction while
    speculation is active.

XLA_FLAGS is set HERE (not only in conftest) so the module is
self-contained, as long as it runs before jax initializes its backends.
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
from paddle_tpu.serving import (AdapterStore, BlockManager,
                                GenerationConfig, NgramProposer,
                                RequestState, SpecStats, create_engine,
                                merge_adapter, random_adapter)


@pytest.fixture(scope="module")
def spec_model():
    # 8/8 heads + intermediate 128: divisible by tp=2 for the mesh leg
    paddle.seed(31)
    cfg = llama_tiny(vocab_size=128, hidden_size=64,
                     intermediate_size=128, num_attention_heads=8,
                     num_key_value_heads=8)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


# repetitive prompts (the n-gram drafter fires), one novel prompt (it
# degrades to plain decode), one with a shared page-aligned prefix
_PROMPTS = [
    [5, 6, 7, 5, 6, 7, 5, 6],
    [9, 3, 9, 3, 9, 3, 9, 3, 9, 3],
    [11, 12, 13, 14],
    [5, 6, 7, 5, 6, 7, 5, 9],
]
_N_NEW = [12, 10, 8, 12]


_ADAPTER, _RANK, _ALPHA = "alpha", 4, 8.0
# which request rides the adapter when a bank is armed: a mixed batch,
# so the no-adapter row 0 runs through the same traced programs
_ADAPTER_OF = [_ADAPTER, None, _ADAPTER, None]


def _options(model, pages, adapters):
    """Engine keywords of one (pages, adapters) cell."""
    kw = {"kv_quant": True} if pages == "int8" else {}
    if adapters == "on":
        store = AdapterStore(model.config, capacity=2)
        store.register(_ADAPTER, random_adapter(model.config, _RANK,
                                                seed=7), alpha=_ALPHA)
        kw["lora"] = store
    return kw


def _run(model, **kw):
    eng = create_engine(model, max_slots=4, page_size=8,
                        max_model_len=64, **kw)
    reqs = [eng.submit(np.array(p, np.int32),
                       GenerationConfig(max_new_tokens=n),
                       adapter=a if kw.get("lora") else None)
            for p, n, a in zip(_PROMPTS, _N_NEW, _ADAPTER_OF)]
    eng.run_until_complete(max_steps=500)
    assert all(r.state == RequestState.DONE for r in reqs)
    return eng, [r.output_tokens for r in reqs]


@pytest.fixture(scope="module")
def reference(spec_model):
    """The canonical greedy outputs of one (pages, adapters) cell: spec
    off, per-step sync, single chip.  EVERY matrix case of the cell must
    reproduce these.  Plain pages: cache off (a cached prefix is bitwise
    the keys the prompt would have written).  int8 pages: the case's own
    cache setting (a cached prefix is read back dequantized, a fresh
    prompt attends its float keys), and within the quantization
    tolerance of the plain pages' reference.  Adapters on, plain pages:
    the adapter's rows are those of its merged checkpoint."""
    made = {}

    def of(pages, adapters, cache):
        key = (pages, adapters, cache and pages == "int8")
        if key in made:
            return made[key]
        _, ref = _run(spec_model, enable_prefix_cache=key[2],
                      **_options(spec_model, pages, adapters))
        if pages == "int8":
            plain = of("plain", adapters, False)
            total = sum(max(len(a), len(b)) for a, b in zip(ref, plain))
            match = sum(int(x == y) for a, b in zip(ref, plain)
                        for x, y in zip(a, b))
            assert match >= 0.75 * total, f"{match}/{total}"
        elif adapters == "on":
            from paddle_tpu.framework.tensor import Tensor
            state = {k: (v._data if isinstance(v, Tensor) else v)
                     for k, v in spec_model.functional_state().items()}
            merged = merge_adapter(
                state, spec_model.config,
                random_adapter(spec_model.config, _RANK, seed=7),
                alpha=_ALPHA)
            from paddle_tpu.serving.engine import Engine
            eng = Engine(config=spec_model.config, state=merged,
                         max_slots=4, page_size=8, max_model_len=64)
            for i, a in enumerate(_ADAPTER_OF):
                if a is None:
                    continue
                req = eng.submit(np.array(_PROMPTS[i], np.int32),
                                 GenerationConfig(max_new_tokens=_N_NEW[i]))
                eng.run_until_complete(max_steps=500)
                assert list(req.output_tokens) == list(ref[i])
        made[key] = ref
        return ref

    return of


# the cells beside (plain, off) run all four programs in one case: the
# cache on (prefill and cached prefill), speculation (the plain step and
# the verify step), on one chip and on the mesh
_CASES = ([(cache, sync, tp, "plain", "off")
           for tp in (1, 2) for sync in (1, 4) for cache in (False, True)]
          + [(True, 4, tp, pages, adapters) for tp in (1, 2)
             for pages, adapters in (("int8", "off"), ("plain", "on"),
                                     ("int8", "on"))])


@pytest.mark.parametrize("cache,sync_interval,tp,pages,adapters", _CASES)
def test_spec_greedy_parity_matrix(spec_model, reference, cache,
                                   sync_interval, tp, pages, adapters):
    """spec_k {0,2,4} x prefix-cache x sync_interval x tp x pages
    (plain, int8) x adapters (off, on): bit-identical tokens, exact page
    accounting, and the no-retrace contract (plain engines trace 1
    decode program, spec engines exactly 2)."""
    if tp > 1 and jax.device_count() < tp:
        pytest.skip("needs multiple host-platform devices")
    expected = reference(pages, adapters, cache)
    for spec_k in (0, 2, 4):
        eng, got = _run(spec_model, spec_k=spec_k,
                        enable_prefix_cache=cache,
                        sync_interval=sync_interval, mesh=tp,
                        **_options(spec_model, pages, adapters))
        assert got == expected, (
            f"spec_k={spec_k} cache={cache} sync={sync_interval} "
            f"tp={tp} pages={pages} adapters={adapters} diverged from "
            "the plain greedy reference")
        st = eng.stats()
        if spec_k:
            assert st["decode_traces"] == 2      # plain + verify bodies
            assert st["verify_traces"] == 1
            assert st["spec_accepted"] + st["spec_rejected"] \
                == st["spec_proposed"]
            # repetitive prompts must actually speculate — a drafter
            # that never fires would pass parity vacuously
            assert st["spec_proposed"] > 0
            assert st["spec_verify_steps"] > 0
        else:
            assert st["decode_traces"] == 1
            assert st["verify_traces"] == 0
        # exact page accounting after mixed accept/reject: everything
        # released (cache keeps parked pages; the census stays exact)
        acct = eng.blocks.pool_accounting()
        assert acct["leak"] == 0, acct
        assert st["pages_in_use"] == 0


def test_spec_finish_inside_verify_row(spec_model, reference):
    """A request whose last tokens commit inside one verify row (the
    accepted span reaches max_new_tokens) finishes exactly where
    sequential decode finishes, and its pages free completely."""
    eng, got = _run(spec_model, spec_k=4)
    for r_got, r_ref, n in zip(got, reference("plain", "off", False),
                               _N_NEW):
        assert len(r_got) == len(r_ref) == n
    assert eng.blocks.pool_accounting()["leak"] == 0
    assert eng.blocks.pages_in_use == 0


def test_spec_eviction_mid_speculation(spec_model):
    """Deadline eviction while a request is actively speculating: its
    speculative page charges were either rolled back at the sync or
    freed wholesale with the sequence — the pool census stays exact and
    the surviving request still matches plain greedy output."""
    victim_prompt = np.array([5, 6, 7, 5, 6, 7, 5, 6], np.int32)
    other_prompt = np.array([9, 3, 9, 3, 9, 3, 9, 3], np.int32)

    def drive(spec_k):
        clock = {"t": 0.0}
        eng = create_engine(spec_model, max_slots=2, page_size=8,
                            max_model_len=64, spec_k=spec_k,
                            clock=lambda: clock["t"])
        victim = eng.submit(victim_prompt,
                            GenerationConfig(max_new_tokens=40),
                            deadline=4.0)
        other = eng.submit(other_prompt,
                           GenerationConfig(max_new_tokens=12))
        steps = 0
        while eng.scheduler.has_work():
            clock["t"] += 1.0       # the deadline hits mid-decode
            eng.step()
            steps += 1
            assert steps < 200
        return eng, victim, other

    ref_eng, ref_victim, ref_other = drive(0)
    eng, victim, other = drive(3)
    assert victim.finish_reason == ref_victim.finish_reason == "deadline"
    assert other.output_tokens == ref_other.output_tokens
    # the evicted request's partial output is a prefix of the plain
    # engine's partial output (speculation batches commits, so the two
    # engines may cut the victim off at different lengths)
    short, long_ = sorted([victim.output_tokens,
                           ref_victim.output_tokens], key=len)
    assert long_[:len(short)] == short
    assert eng.blocks.pool_accounting()["leak"] == 0
    assert eng.blocks.pages_in_use == 0


def test_spec_verify_traces_stable_across_churn(spec_model):
    """Admissions and evictions between verify steps re-trace nothing:
    a second wave of requests through the same engine reuses both
    compiled programs."""
    eng, _ = _run(spec_model, spec_k=3)
    reqs = [eng.submit(np.array(p, np.int32),
                       GenerationConfig(max_new_tokens=6))
            for p in _PROMPTS[:2]]
    eng.run_until_complete(max_steps=300)
    assert all(r.state == RequestState.DONE for r in reqs)
    st = eng.stats()
    assert st["decode_traces"] == 2
    assert st["verify_traces"] == 1


# --------------------------------------------------------------------------
# committed-token ledger: append / rollback / capacity on the BlockManager
# --------------------------------------------------------------------------

def test_block_manager_append_rollback_ledger():
    bm = BlockManager(8, 4)
    assert bm.allocate(1, 3)        # capacity 12 tokens
    assert bm.committed_tokens(1) == 0
    assert bm.append(1, 5) == 5
    assert bm.committed_pages(1) == 2
    assert bm.rollback(1, 2) == 3
    assert bm.committed_pages(1) == 1
    # floor: prompt tokens (here 0) can never be rolled back past
    with pytest.raises(ValueError, match="admission content"):
        bm.rollback(1, 4)
    # capacity: the ledger refuses to commit past the reservation
    with pytest.raises(ValueError, match="overruns"):
        bm.append(1, 10)
    with pytest.raises(ValueError, match="use rollback"):
        bm.append(1, -1)
    with pytest.raises(ValueError, match="owns no pages"):
        bm.append(99, 1)
    bm.free_seq(1)
    assert bm.committed_tokens(1) == 0
    assert bm.pages_in_use == 0


def test_block_manager_prompt_floor_via_allocate_seq():
    bm = BlockManager(8, 4)
    assert bm.allocate_seq(7, list(range(6)), max_new_tokens=4)
    assert bm.committed_tokens(7) == 6      # the prompt is committed
    bm.append(7, 3)
    bm.rollback(7, 3)
    with pytest.raises(ValueError, match="admission content"):
        bm.rollback(7, 1)                   # would un-commit the prompt
    bm.free_seq(7)


def test_block_manager_free_list_fifo():
    """The deque free list preserves the seed order FIFO: freed pages
    recycle oldest-first, exactly like the list.pop(0) it replaced."""
    bm = BlockManager(6, 4)
    assert bm.allocate(1, 3) == [0, 1, 2]
    bm.free_seq(1)
    assert bm.allocate(2, 2) == [3, 4]       # tail of the seed order
    assert bm.allocate(3, 3) == [5, 0, 1]    # then the freed pages
    bm.free_seq(2)
    bm.free_seq(3)
    assert bm.pages_in_use == 0


# --------------------------------------------------------------------------
# NgramProposer / SpecStats units
# --------------------------------------------------------------------------

def test_ngram_proposer_prompt_lookup():
    p = NgramProposer(4, max_n=3, min_n=1)
    p.register(1, [5, 6, 7, 9, 5, 6, 7])
    # tail (6, 7) last occurred at positions 1-2 -> continuation [9, 5, 6, 7]
    assert p.propose(1) == [9, 5, 6, 7]
    assert p.propose(1, max_tokens=2) == [9, 5]
    assert p.propose(1, max_tokens=0) == []
    # novel history: nothing to look up
    p.register(2, [1, 2, 3, 4])
    assert p.propose(2) == []
    # drafts extend as generation extends the history
    p.extend(2, 1)
    p.extend(2, 2)
    assert p.propose(2) == [3, 4, 1, 2]
    p.drop(1)
    assert p.propose(1) == []       # dropped: no history, no proposal
    assert p.history_len(2) == 6


def test_ngram_proposer_validation():
    with pytest.raises(ValueError, match="k must be"):
        NgramProposer(0)
    with pytest.raises(ValueError, match="min_n"):
        NgramProposer(2, max_n=1, min_n=3)


def test_spec_stats_bookkeeping():
    s = SpecStats()
    s.record_step()
    s.record(4, 2)
    s.record(3, 3)
    s.record(0, 0)                  # ride-along slot: nothing proposed
    snap = s.snapshot()
    assert snap["spec_proposed"] == 7
    assert snap["spec_accepted"] == 5
    assert snap["spec_rejected"] == 2
    assert snap["spec_verify_steps"] == 1
    assert snap["spec_committed_tokens"] == 8   # (2+1) + (3+1) + (0+1)
    assert snap["spec_acceptance_rate"] == pytest.approx(5 / 7)
