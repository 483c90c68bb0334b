"""Pallas weight-only GEMV kernel parity (reference:
paddle/phi/kernels/funcs/weight_only_gemv.cu — the int8/int4-weight x
half-activation decode matmul).  CPU runs the kernel in interpret mode
(the Mosaic lowering itself is exercised by the TPU-gated test below,
PADDLE_TPU_TEST_TPU=1)."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import quant_matmul as QM


def _on_tpu():
    """Asked inside a test, never at import (see conftest.py)."""
    return os.environ.get("PADDLE_TPU_TEST_TPU") and \
        jax.default_backend() not in ("cpu",)


def _mk(m, k, n, kind, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(m, k) * 0.3, jnp.bfloat16)
    bound = 127 if kind == "int8" else 7
    q = jnp.asarray(rng.randint(-bound, bound + 1, (k, n)), jnp.int8)
    s = jnp.asarray(rng.rand(n).astype(np.float32) * 0.02 + 1e-3)
    if kind == "int4":
        w = QM.QuantizedWeight(QM.pack_int4(q), s, kind="int4", k=k)
    else:
        w = QM.QuantizedWeight(q, s, kind="int8", k=k)
    ref = (x.astype(jnp.float32)
           @ (q.astype(jnp.float32) * s)).astype(jnp.float32)
    return x, w, ref


def test_pack_unpack_int4_roundtrip():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randint(-8, 8, (64, 256)), jnp.int8)
    packed = QM.pack_int4(q)
    assert packed.shape == (32, 256)
    np.testing.assert_array_equal(np.asarray(QM.unpack_int4(packed)),
                                  np.asarray(q))
    with pytest.raises(ValueError, match="even K"):
        QM.pack_int4(q[:63])


@pytest.mark.parametrize("kind", ["int8", "int4"])
@pytest.mark.parametrize("m,k,n", [(8, 256, 512), (1, 512, 384),
                                   (8, 250, 512)])
def test_interpret_parity(kind, m, k, n):
    """Kernel (interpret mode) vs the dequantized f32 reference."""
    if kind == "int4" and k % 2:
        pytest.skip("int4 needs even K")
    x, w, ref = _mk(m, k, n, kind)
    saved = QM._INTERPRET
    QM._INTERPRET = True
    try:
        out = QM.weight_only_matmul(x, w)
    finally:
        QM._INTERPRET = saved
    assert out.dtype == x.dtype
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
                / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 0.02, rel


def test_xla_fallback_matches_kernel():
    """Large-M (prefill-shaped) calls route to the XLA path; numerics
    must agree with the kernel's."""
    x, w, ref = _mk(256, 256, 512, "int8")
    out = QM.weight_only_matmul(x, w)          # m > _GEMV_MAX_ROWS
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
                / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 0.02, rel


def test_quantized_weight_pytree():
    """QuantizedWeight must flow through jit boundaries as state."""
    x, w, ref = _mk(4, 256, 256, "int4")

    @jax.jit
    def f(x, w):
        return QM.weight_only_matmul(x, w)

    out = f(x, w)
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
                / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 0.02, rel
    leaves = jax.tree_util.tree_leaves(w)
    assert len(leaves) == 2                    # q + scale, kind is aux
    assert w.dequantize().shape == (256, 256)


def test_k_mismatch_raises():
    x, w, _ = _mk(4, 256, 256, "int8")
    with pytest.raises(ValueError, match="K mismatch"):
        QM.weight_only_matmul(x[:, :128], w)


@pytest.mark.parametrize("kind", ["int8", "int4"])
def test_tpu_kernel_parity(kind):
    """Mosaic-compiled kernel on the chip vs dequant reference."""
    if not _on_tpu():
        pytest.skip("needs the real chip")
    x, w, ref = _mk(8, 2048, 5632, kind)
    out = QM.weight_only_matmul(x, w)
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
                / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 0.02, rel
