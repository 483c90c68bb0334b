"""The chip's published peaks, keyed by ``device_kind``.

One table for every share of a peak or of a roofline the benchmark
reports.  A device that is not in it is an error, never a default.
(The bf16 column is copied from ``bench.py``'s ``PEAK_TFLOPS``; that
table stays where it is for ``bench.py`` and is listed in PERF.md for a
later PR to delete.)
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page): one
# chip has 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at
# 819 GB/s.  jax reports that chip as device_kind "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_of(device_kind: str) -> dict:
    """The row for ``device_kind``; KeyError for a chip not on record."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r}: add it "
            "to benchmarks/lib/peaks.py with its source, do not guess"
        ) from None
