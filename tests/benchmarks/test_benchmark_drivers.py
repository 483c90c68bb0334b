"""Each driver through ``benchmarks/run.py``'s inner function, at a toy
size on the CPU: the result line, the plain references against the
program, and ``correct`` coming out false with the timed path broken
underneath.  Nothing here describes a TPU topology or starts a child.
"""
import copy
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
from benchmarks.drivers import engine_closed_loop as E  # noqa: E402
from benchmarks.drivers import train_step as T  # noqa: E402
from benchmarks.lib import stats  # noqa: E402

TOY = os.path.join(HERE, "toy")
TRAIN, SERVE = "bert-toy.ft", "mistral-toy.chat"
# the CPU has no device plane: its thunks run on host threads
CPU_TRACE = dict(device_plane=r"^/host:CPU$",
                 ops_line=r"XLAPjRtCpuClient|XLAEigen")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def manifest():
    return R.load_json(os.path.join(TOY, "manifest.json"))


def cpu():
    import jax
    return jax.devices()[:1]


def run_line(workload, *, trace, seed=3000000019, seconds=1.0):
    return R.run_cell(manifest(), workload, seed=seed, seconds=seconds,
                      trace=trace, devices=cpu(), root=TOY,
                      t_start=time.perf_counter(), trace_kw=CPU_TRACE)


def ctx_for(workload, seed=7):
    found = R.find_cell(manifest(), workload, TOY)
    return R.cell_context(found, workload, seed=seed, seconds=1.0,
                          devices=cpu())


@pytest.fixture(scope="module")
def lines():
    return {(w, tr): run_line(w, trace=tr)
            for w in (TRAIN, SERVE) for tr in (False, True)}


def test_a_device_that_is_no_tpu_is_refused():
    """``main`` asks this before anything runs: no chip, no result."""
    from benchmarks.lib import device
    with pytest.raises(SystemExit) as refused:
        device.require_tpu(1)
    assert refused.value.code not in (0, None)


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
@pytest.mark.parametrize("trace", [False, True])
def test_result_line_keeps_the_contract(lines, workload, trace):
    line = lines[workload, trace]
    assert LINE_KEYS <= set(line)
    assert list(line)[-1] == "checks"       # compared numbers come last
    json.loads(json.dumps(line))            # one JSON object
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in R.metrics_for(manifest(), workload, kind)}
    assert set(line["metrics"]) <= allowed
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 5
    else:
        assert set(line["metrics"]) == allowed
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_lines_leave_out_what_nothing_ran(lines):
    """No Pallas kernel runs on the CPU and no peak is on record for it:
    the rooflines and the MFUs are left out, never reported as 0."""
    train, serve = lines[TRAIN, True], lines[SERVE, True]
    assert {"train_step_ms_p50", "device_idle_share.train"} == set(
        train["metrics"])
    assert {"slot_occupancy", "decode_step_ms", "prefill_ms_per_ktok",
            "ttft_mean_ms", "ttft_p95_ms",
            "device_idle_share.serve"} == set(
        serve["metrics"])
    assert 0 < serve["metrics"]["slot_occupancy"]["value"] <= 100


# ------------------------------------------------------------- training
def test_bert_reference_is_the_program_without_autocast():
    ctx = ctx_for(TRAIN)
    assert ctx["config"]["trainer"]["autocast"] is False
    program = T.first_steps(T.build(ctx), ctx)
    found = T.gaps(program, T.reference_readings(ctx))
    assert found["loss_gap"] < 1e-5
    assert found["grad_norm_gap"] < 1e-4
    assert found["change_norm_gap"] < 1e-3
    assert found["leaves_left_out"] >= 1    # the key biases


def test_bert_under_bf16_autocast_stays_near_the_reference():
    """At hidden 64 bf16 noise is large on the worst leaf; the cell's own
    limits come from chip readings at the real size (PERF.md)."""
    ctx = ctx_for(TRAIN)
    ctx["config"] = copy.deepcopy(ctx["config"])
    ctx["config"]["trainer"]["autocast"] = True
    program = T.first_steps(T.build(ctx), ctx)
    found = T.gaps(program, T.reference_readings(ctx))
    assert 1e-4 < found["loss_gap"] < 0.02      # the loss comes in bf16
    # the classifier bias's gradient is a mean of (p - onehot) that all
    # but cancels; bf16 logits leave it a few times the median leaf
    assert found["worst_grad_leaf"] == "classifier.bias" \
        or found["grad_norm_gap"] < 0.5
    assert found["change_norm_gap"] < 0.5


@pytest.mark.parametrize("kw,number", [
    (dict(rows=slice(0, 2)), "grad_norm_gap"),      # half the batch
])
def test_bert_fault_in_the_reference_reads_over_the_limit(kw, number):
    ctx = ctx_for(TRAIN)
    reference = T.reference_readings(ctx)
    found = T.gaps(T.reference_readings(ctx, **kw), reference)
    assert found[number] > 2 * ctx["limits"][number]


def test_bert_fp8_control_reads_above_the_exact_reference():
    ctx = ctx_for(TRAIN)
    reference = T.reference_readings(ctx)
    found = T.gaps(T.reference_readings(ctx, fp8=True), reference)
    assert found["grad_norm_gap"] > 1e-3


class _Broken:
    """A compiled step with a fault planted under the driver."""

    def __init__(self, real, fault):
        self.real, self.fault = real, fault
        self._compiled = None

    def __call__(self, ids, tts, y):
        import jax.numpy as jnp
        model, o = self.real.model, self.real.optimizer
        if self.fault == "half_batch":      # the mean over the rest
            n = ids.shape[0] // 2
            ids, tts, y = ids[:n], tts[:n], y[:n]
        if self.fault == "state_unchanged":
            kept = {k: jnp.array(v, copy=True)
                    for k, v in model.functional_state().items()}
            opt = o.opt_state()
        loss = self.real(ids, tts, y)
        self._compiled = self.real._compiled
        if self.fault == "state_unchanged":
            model.load_functional_state(kept)
            o.load_opt_state(opt)
        return loss


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_run_with_the_step_broken_is_not_correct(monkeypatch, fault):
    import paddle_tpu as paddle
    real = paddle.jit.train_step
    monkeypatch.setattr(paddle.jit, "train_step",
                        lambda *a: _Broken(real(*a), fault))
    line = run_line(TRAIN, trace=False, seconds=0.3)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_train_window_refuses_a_compile_inside_it():
    ctx = ctx_for(TRAIN)
    trainer = T.build(ctx)
    T.first_steps(trainer, ctx)
    trainer["batches"] = [(i[:2], t[:2], y[:2])
                          for i, t, y in trainer["batches"]]
    with pytest.raises(RuntimeError, match="compiled inside the window"):
        T.window(trainer, ctx, 0.2)


# -------------------------------------------------------------- serving
def test_serve_window_refuses_a_new_program_inside_it():
    ctx = ctx_for(SERVE)
    loop = E.Loop(E.build(ctx))
    loop.start()
    loop.ramp()
    loop.traffic._pairs = [(64, a) for _, a in loop.traffic._pairs]
    loop.traffic._deck = []
    with pytest.raises(RuntimeError, match="traced inside the window"):
        E.window(loop, 1.0)


def test_serve_run_with_a_token_altered_is_not_correct(monkeypatch):
    from paddle_tpu.serving.parallel.runner import ModelRunner
    real = ModelRunner.fetch_ring
    calls = {"n": 0}

    def altered(self):
        ring = np.array(real(self))
        calls["n"] += 1
        if calls["n"] % 3 == 0:             # every third step's tokens
            ring = (ring + 257) % 512
        return ring

    monkeypatch.setattr(ModelRunner, "fetch_ring", altered)
    line = run_line(SERVE, trace=False, seconds=1.0)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_decoder_int8_control_reads_above_the_program():
    """The control at a size a test can hold.  Served in float32 the toy
    program agrees with the reference to rounding and flips no token;
    the int8 reference, judged on the same prompts and tokens, does."""
    ctx = ctx_for(SERVE, seed=11)
    ctx["config"] = copy.deepcopy(ctx["config"])
    ctx["config"]["model"]["torch_dtype"] = "float32"
    served = E.build(ctx)
    loop = E.Loop(served)
    loop.start()
    loop.ramp()
    finished = []
    while len(finished) < 40:               # however slow this machine is
        finished += E.window(loop, 1.0)["finished"]
    sample = E.sample_finished(finished, 11, 40)
    assert len(sample) == 40 and sample[0] is max(
        finished, key=lambda r: r["prompt"].size + len(r["tokens"]))
    program = E.reference_gaps(ctx, served["weights"], sample)
    control = E.reference_gaps(ctx, served["weights"], sample, int8=True)
    assert program["positions"] == control["positions"] > 400
    assert program["flipped"] == 0 and program["logit_gap_max"] == 0.0
    assert control["flipped"] > 0
    limits = {"logit_gap_mean": 1e-7}
    assert stats.judge(program, limits)[0] is True
    assert stats.judge(control, limits)[0] is False
