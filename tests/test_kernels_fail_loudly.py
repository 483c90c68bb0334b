"""On a TPU backend a kernel that fails must raise, not hand back XLA.

The gates used to probe each kernel family once, swallow every
exception, and route to the XLA reference in silence: a chip running
without its kernels looked healthy.  Here the backend string is faked
and the kernel body is made to raise; every public entry point has to
pass the failure on with the kernel's own message.  Routing on what is
visible in the input (CPU backend, short sequences, odd head dims) is a
choice and stays.
"""
import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import decode_attention as DA
from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import lora_matmul as LM
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.ops.pallas import quant_matmul as QM

PALLAS = pathlib.Path(FA.__file__).parent


def _boom(*args, **kwargs):
    raise RuntimeError("Mosaic failed to compile: scoped vmem exceeded")


@pytest.fixture
def fake_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _qkv(s=128, h=2, d=64):
    x = jnp.zeros((1, s, h, d), jnp.bfloat16)
    return x, x, x


def _sdpa_plain():
    return FA.sdpa(*_qkv(), is_causal=True)


def _sdpa_key_padding():
    mask = jnp.ones((1, 1, 1, 128), jnp.bool_)      # the serving prefill
    return FA.sdpa(*_qkv(), attn_mask=mask, is_causal=True)


def _sdpa_bias():
    return FA.sdpa(*_qkv(), attn_mask=jnp.zeros((1, 1, 128, 128),
                                                jnp.float32))


def _decode():
    q = jnp.zeros((1, 4, 64), jnp.bfloat16)
    cache = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)
    return DA.decode_attention(q, cache, cache, jnp.zeros((1,), jnp.int32))


def _int8_matmul():
    w = QM.QuantizedWeight(jnp.zeros((256, 256), jnp.int8),
                           jnp.ones((256,), jnp.float32), kind="int8",
                           k=256)
    return QM.weight_only_matmul(jnp.zeros((4, 256), jnp.bfloat16), w)


def _lora():
    bank = jnp.zeros((3, 8, 256), jnp.bfloat16)
    return LM.lora_gather_matmul(
        jnp.zeros((4, 256), jnp.bfloat16), bank, bank,
        jnp.ones((3,), jnp.float32), jnp.zeros((4,), jnp.int32))


CASES = {
    "sdpa": (FA, "_pallas_sdpa", _sdpa_plain),
    "sdpa_key_padding": (FA, "_pallas_sdpa_masked", _sdpa_key_padding),
    "sdpa_bias": (FA, "_pallas_sdpa_biased", _sdpa_bias),
    "decode_attention": (DA, "_pallas_decode", _decode),
    "weight_only_matmul": (QM, "_pallas_int8", _int8_matmul),
    "lora_gather_matmul": (LM, "_pallas_gather_matmul", _lora),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_failure_raises(case, fake_tpu, monkeypatch):
    module, kernel, call = CASES[case]
    monkeypatch.setattr(module, kernel, _boom)
    monkeypatch.setattr(DA, "PALLAS_DECODE", True)
    with pytest.raises(RuntimeError, match="scoped vmem exceeded"):
        call()


@pytest.mark.parametrize("case", sorted(CASES))
def test_cpu_backend_routes_to_xla(case, monkeypatch):
    """The other half of the rule: on the CPU the same calls never reach
    the kernel, by a choice made on the backend string."""
    module, kernel, call = CASES[case]
    monkeypatch.setattr(module, kernel, _boom)
    monkeypatch.setattr(DA, "PALLAS_DECODE", True)
    assert np.all(np.isfinite(np.asarray(call(), np.float32)))


def test_paged_chooser_takes_the_kernel_on_tpu(fake_tpu):
    assert PA.select_paged_attention() is PA.paged_attention


def test_state_update_chooser_takes_the_kernel_on_tpu(fake_tpu, monkeypatch):
    """The recurrent family's decode step: on a TPU the Pallas kernel and
    nothing else; a failure inside it reaches the caller."""
    from paddle_tpu.ops.pallas import ssm_update as SU
    assert SU.select_ssm_state_update() is SU.ssm_state_update
    monkeypatch.setattr(SU, "ssm_state_update", _boom)
    pool = jnp.zeros((1, 2, 8, 128), jnp.float32)
    row = jnp.zeros((2, 128))
    for groups in (1, 4):           # Granite's call and a grouped one
        col = jnp.zeros((2, groups, 8))
        with pytest.raises(RuntimeError, match="scoped vmem exceeded"):
            SU.select_ssm_state_update()(pool, 0, row, row, col, col,
                                         jnp.ones((2,), jnp.int32))


def test_grouped_matmul_chooser_takes_the_kernel_on_tpu(fake_tpu,
                                                        monkeypatch):
    """The expert layers' products, whatever the description's
    activation: on a TPU the Pallas kernel and nothing else; a failure
    inside it reaches ``routed_experts``' caller."""
    from paddle_tpu.models import deepseek_v3 as ds
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    from paddle_tpu.ops.pallas import grouped_ffn as GF
    assert GF.select_grouped_matmul() is GF.grouped_matmul
    monkeypatch.setattr(GF, "grouped_matmul", _boom)
    cfg = NemotronHConfig(hidden_size=16, n_routed_experts=4,
                          num_experts_per_tok=2, moe_intermediate_size=24,
                          hybrid_override_pattern="E")
    w = {"router": jnp.zeros((16, 4)), "router_bias": jnp.zeros((4,)),
         "e_up": jnp.zeros((4, 16, 24)), "e_down": jnp.zeros((4, 24, 16))}
    with pytest.raises(RuntimeError, match="scoped vmem exceeded"):
        ds.routed_experts(cfg, w, jnp.zeros((3, 16)), jnp.ones((3,), bool),
                          ds.DECODE_TILE)


@pytest.mark.parametrize("name", [
    "flash_attention.py", "decode_attention.py", "paged_attention.py",
    "quant_matmul.py", "lora_matmul.py", "ssm_update.py",
    "grouped_ffn.py"])
def test_no_handler_between_a_kernel_and_its_caller(name):
    """No ``try`` at all in the kernel files: nothing there opens a
    resource, so a handler could only be hiding a kernel failure."""
    tree = ast.parse((PALLAS / name).read_text())
    handlers = [n.lineno for n in ast.walk(tree) if isinstance(n, ast.Try)
                and n.handlers]
    assert handlers == []
