"""What a run has to know about the machine it is on."""
from __future__ import annotations

import importlib
import sys

PALLAS_MODULES = ("flash_attention", "flash_mask", "paged_attention",
                  "decode_attention", "quant_matmul", "lora_matmul",
                  "grouped_ffn")


def require_tpu(count: int):
    """The first ``count`` TPU devices, or exit with no result line: a
    measurement path that finds no chip fails, it does not fall back."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"benchmarks/run.py: JAX found no device: {e}")
    if devices[0].platform != "tpu" or len(devices) < count:
        sys.exit(f"benchmarks/run.py: needs {count} TPU device(s); JAX "
                 f"reports {len(devices)} x {devices[0].platform} "
                 f"({devices[0].device_kind})")
    return devices[:count]


def interpret_is_off():
    """The kernels must run as kernels: interpret mode is a test switch."""
    for name in PALLAS_MODULES:
        mod = importlib.import_module(f"paddle_tpu.ops.pallas.{name}")
        if getattr(mod, "_INTERPRET", False):
            raise RuntimeError(f"ops.pallas.{name}._INTERPRET is on")


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` on the fullest device (0 where the backend
    keeps no count: the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
