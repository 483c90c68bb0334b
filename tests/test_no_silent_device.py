"""Paths that name a device fail without it; none answers with the CPU.

`set_device("tpu")` used to hand back whatever `jax.devices()` had,
`bench.py` re-pinned to the CPU and kept printing metric lines, an
unknown chip was assumed to peak at 197 TF/s, and `dryrun_multichip`
probed the default backend in a child process before choosing.
"""
import os
import sys

import jax
import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import bench  # noqa: E402


@pytest.fixture
def default_device():
    yield
    jax.config.update("jax_default_device", None)


def test_set_device_tpu_without_a_tpu_raises(default_device):
    with pytest.raises(RuntimeError, match="tpu"):
        paddle.set_device("tpu")


@pytest.mark.parametrize("name", ["cpu", "gpu:0", "xpu"])
def test_set_device_compat_names_still_resolve(name, default_device):
    assert paddle.set_device(name).platform == "cpu"
    assert paddle.get_device() == "cpu"


def test_bench_refuses_to_measure_on_the_cpu():
    with pytest.raises(SystemExit, match="measures on a TPU"):
        bench._env()


def test_bench_peak_is_a_table_not_a_guess():
    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_flops("TPU v5e") == 197e12
    with pytest.raises(KeyError, match="no peak FLOP/s on record"):
        bench._peak_flops("TPU v9 hypothetical")
    with pytest.raises(KeyError):
        bench._peak_flops("cpu")


def test_dryrun_multichip_is_a_cpu_rehearsal(capsys, monkeypatch):
    """In-process, on the virtual CPU devices conftest set up: no child,
    no probe; the output line is kept."""
    import subprocess

    import __graft_entry__ as g

    def no_child(*a, **k):
        raise AssertionError("dryrun_multichip started a process")

    monkeypatch.setattr(subprocess, "run", no_child)
    monkeypatch.setattr(subprocess, "Popen", no_child)
    g.dryrun_multichip(8)
    out = capsys.readouterr().out
    assert "dryrun_multichip ok: mesh=(pp=2,dp=2,tp=2)" in out
    assert "backend_fallback" not in out and "multichip_skip" not in out
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_dryrun_multichip_wants_enough_devices():
    import __graft_entry__ as g
    with pytest.raises(RuntimeError, match="need 64 virtual devices"):
        g.dryrun_multichip(64)
