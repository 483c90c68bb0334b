"""tools/op_bench.py: the per-op perf regression gate (VERDICT r2
missing #7; reference tools/ci_op_benchmark.sh)."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_op_bench_suite_runs_and_gate_logic(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "op_bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    names = {r["op"] for r in rows}
    assert {"matmul_2kx2k", "batch_norm_train", "moe_sort_dispatch",
            "softmax_wide", "embedding_gather"} <= names, names
    assert not any("error" in r for r in rows), rows


def test_gate_flags_regression(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import op_bench

    fake_baseline = tmp_path / "op_baseline.json"
    monkeypatch.setattr(op_bench, "BASELINE", str(fake_baseline))
    import jax
    dev = jax.devices()[0].device_kind
    fake_baseline.write_text(json.dumps(
        {"device": dev, "ops": {"matmul_2kx2k": 1e-9}}))  # impossible floor
    monkeypatch.setattr(op_bench, "run_suite",
                        lambda: {"matmul_2kx2k": 1.0})
    assert op_bench.main(["--check"]) == 1          # regression -> fail
    fake_baseline.write_text(json.dumps(
        {"device": dev, "ops": {"matmul_2kx2k": 2.0}}))
    assert op_bench.main(["--check"]) == 0          # within tolerance
    fake_baseline.write_text(json.dumps(
        {"device": "other chip", "ops": {"matmul_2kx2k": 1e-9}}))
    assert op_bench.main(["--check"]) == 0          # device mismatch skip


def test_op_errors_carry_enforce_context():
    """PADDLE_ENFORCE analog (reference phi/core/enforce.h): exceptions
    escaping op dispatch are annotated with the op name and tensor
    input signatures, on both eager paths."""
    import numpy as np
    import pytest
    import paddle_tpu as paddle
    from paddle_tpu.autograd import tape

    def notes_of(exc):
        return "\n".join(getattr(exc, "__notes__", []) or [])

    with pytest.raises(Exception) as ei:
        paddle.matmul(paddle.ones([3, 4]), paddle.ones([5, 6]))
    assert "op 'matmul'" in notes_of(ei.value)
    assert "float32[3, 4]" in notes_of(ei.value)

    # recorded (vjp) path too
    x = paddle.to_tensor(np.ones((3, 4), np.float32),
                         stop_gradient=False)
    with pytest.raises(Exception) as ei:
        with tape.enable_grad():
            paddle.matmul(x, paddle.ones([5, 6]))
    assert "op 'matmul'" in notes_of(ei.value)
