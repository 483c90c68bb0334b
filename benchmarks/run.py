"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``<config>.<mix>`` of ``BENCHMARK.json``.  Everything that
belongs to it is data found by those names:

    benchmarks/configs/<config>.json     the sizes, as run; names the driver
    benchmarks/traffic/<mix>.json        the mix's parameters
    benchmarks/limits/<cell>.json        the limits ``correct`` holds it to
    benchmarks/drivers/<driver>.py       ``run(ctx) -> dict``
    benchmarks/layer_metrics/<name>.json one per-layer metric: its reader
    benchmarks/readers/<reader>.py       ``read(args, run, trace, ctx)``

This file holds no size and no name of a cell.  It needs a TPU: with no
accelerator, or fewer chips than the cell asks for, it exits non-zero
and prints no result.  The last line of standard output is one JSON
object (see ``result_line``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmarks")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: dict, workload: str, root: str) -> dict:
    """The cell's entry, configuration, mix and limits, by name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in the manifest; it "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config_file = os.path.join(root, entry["file"])
    base = os.path.dirname(os.path.dirname(config_file))
    return {"cell": cell,
            "config": load_json(config_file),
            "mix": load_json(os.path.join(
                base, "traffic", cell["traffic"] + ".json")),
            "limits": load_json(os.path.join(
                base, "limits", workload + ".json"))["limits"]}


def cell_context(found: dict, workload: str, *, seed: int, seconds: float,
                 devices, peaks: dict | None = None,
                 trace_dir: str | None = None,
                 t_start: float | None = None) -> dict:
    """What a driver is handed: the cell's data and this run's arguments."""
    return {"workload": workload, "config": found["config"],
            "mix": found["mix"], "limits": found["limits"],
            "seed": int(seed), "seconds": float(seconds),
            "trace_dir": trace_dir, "devices": devices, "peaks": peaks,
            "t_start": time.perf_counter() if t_start is None else t_start}


def metrics_for(manifest: dict, workload: str, kind: str) -> list:
    """The manifest's ``kind`` metrics that this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or workload in m["workloads"]]


def read_layer_metrics(manifest: dict, workload: str, run: dict,
                       trace, ctx: dict) -> dict:
    """Each per-layer metric through its own reader.  A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_for(manifest, workload, "per_layer"):
        spec = load_json(os.path.join(HERE, "layer_metrics",
                                      m["name"] + ".json"))
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        value = reader.read(spec.get("args", {}), run, trace, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(manifest: dict, workload: str, *, seed: int, seconds: float,
             trace: bool, devices, root: str = ROOT,
             t_start: float | None = None, peaks: dict | None = None,
             trace_kw: dict | None = None) -> dict:
    """Drive one run of ``workload`` on ``devices`` and return the result
    line as a dict.  ``main`` refuses a device that is not a TPU before
    it calls this; the tests call it on the CPU with toy manifests."""
    from benchmarks.lib import device as dev, xplane
    found = find_cell(manifest, workload, root)
    config = found["config"]
    trace_dir = os.path.join(root, ".bench_out", "trace", workload) \
        if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = cell_context(found, workload, seed=seed, seconds=seconds,
                       devices=devices, peaks=peaks, trace_dir=trace_dir,
                       t_start=T_START if t_start is None else t_start)
    driver = importlib.import_module(
        "benchmarks.drivers." + config["driver"])
    run = driver.run(ctx)
    print(json.dumps({"phases_s": ctx.get("phases", {}), "observed": {
        k: v for k, v in run["observed"].items()
        if isinstance(v, (int, float, str))}}), file=sys.stderr, flush=True)
    described = dev.describe(devices)
    described["memory_peak_bytes"] = run["memory_peak_bytes"]
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]), "failed": int(run["failed"])}
    if trace:
        try:
            reduced = xplane.reduce(trace_dir, **(trace_kw or {}))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        line["metrics"] = read_layer_metrics(manifest, workload, run,
                                             reduced, ctx)
        described["busy_s"] = reduced["busy_s"]
        described["window_s"] = reduced["window_s"]
        line["device"] = described
        line["breakdown"] = reduced["breakdown"]
    else:
        values = dict(run["end_to_end"], setup_s=run["setup_s"])
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]),
                        "unit": m["unit"]}
            for m in metrics_for(manifest, workload, "end_to_end")}
        line["device"] = described
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in run["checks"].items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        sys.exit("benchmarks/run.py: the system under test (paddle_tpu/) "
                 "is not beside benchmarks/")
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    chips = find_cell(manifest, args.workload, ROOT)["cell"]["chips"]

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    # small programs too: set-up is then the same work in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from benchmarks.lib import device as dev, peaks
    devices = dev.require_tpu(int(chips))
    dev.interpret_is_off()
    chip = peaks.peaks_of(devices[0].device_kind)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "compile_cache": cache_dir,
                      "devices": [str(d) for d in devices]}),
          file=sys.stderr, flush=True)

    line = run_cell(manifest, args.workload, seed=args.seed,
                    seconds=args.seconds, trace=bool(args.trace),
                    devices=devices, peaks=chip)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
