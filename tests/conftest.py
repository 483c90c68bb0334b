"""Test env: force an 8-device virtual CPU mesh before jax backends initialize
(SURVEY §4: distributed-vs-single-card equivalence runs on one host).

Unit tests run on host CPU devices (deterministic f32 matmuls, 8 virtual
devices) whatever the machine's default backend is: on a chip host every
xdist worker would otherwise open the chip, and only one process may.  The
pin is set here, in the environment AND in jax's config, because conftest
runs before any test imports jax and before backends are instantiated.
Nothing in this file, and nothing a test file does while it is imported,
may ask jax for its devices.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# PADDLE_TPU_TEST_TPU=1 keeps the real TPU visible (used to exercise the
# pallas kernels, e.g. tests/test_flash_attention_tpu.py).
if not os.environ.get("PADDLE_TPU_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 deselects with -m 'not slow'; register the marker so pytest
    # does not warn it unknown
    config.addinivalue_line(
        "markers",
        "slow: long-running test excluded from the tier-1 gate")
