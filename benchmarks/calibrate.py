"""Readings that ``correct``'s limits are set from, taken on the chip.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 11,12,13 --controls 3

For every seed: what sound runs of the program read on each number that
``correct`` compares (the lower readings); for the first ``--controls``
seeds also what the control and each planted fault read (the upper
readings).  The cell's driver does the work (``driver.calibrate``); the
table is written to ``chiprun_out/calibrate.<cell>.json`` and printed.
PERF.md says which limit was set from which readings.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from benchmarks import run as R
    manifest = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    found = R.find_cell(manifest, args.workload, ROOT)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks.lib import device as dev, peaks
    devices = dev.require_tpu(int(found["cell"]["chips"]))
    dev.interpret_is_off()
    ctx = R.cell_context(found, args.workload, seed=0, seconds=args.seconds,
                         devices=devices,
                         peaks=peaks.peaks_of(devices[0].device_kind))
    driver = importlib.import_module(
        "benchmarks.drivers." + found["config"]["driver"])
    seeds = [int(s) for s in args.seeds.split(",")]
    table = driver.calibrate(ctx, seeds, args.controls)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"calibrate.{args.workload}.json"),
              "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
