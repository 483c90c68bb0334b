"""Order statistics the metrics are made of, and the run's phase marks."""
from __future__ import annotations

import math
import time


def percentile(vals, q: float) -> float:
    """Nearest-rank percentile on the sorted values (the arithmetic of
    ``tools/serve_bench.py``'s ``_percentile``, copied so that a later
    change to the tool cannot move the yardstick)."""
    vals = sorted(vals)
    if not vals:
        return math.nan
    idx = min(len(vals) - 1, int(round(q * (len(vals) - 1))))
    return float(vals[idx])


def median(vals) -> float:
    return percentile(vals, 0.5)


def mark(ctx: dict, phase: str):
    """Note the seconds since the run began at which ``phase`` ended
    (``ctx["phases"]``; standard error shows them, no metric reads them)."""
    ctx.setdefault("phases", {})[phase] = round(
        time.perf_counter() - ctx["t_start"], 3)


def judge(found: dict, limits: dict) -> tuple:
    """(correct, {name: [value, limit]}) over the limits the cell's file
    states; a number it does not state is not compared."""
    checks = {k: [float(found[k]), float(lim)] for k, lim in limits.items()}
    ok = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    return bool(ok), checks


def strided(leaf, n: int):
    """``n`` elements (fewer where the leaf is smaller) spread evenly
    over the flattened ``leaf``: the sample of a gradient leaf that the
    program's and the reference's sides both keep for comparison."""
    flat = leaf.reshape(-1)
    return flat[::max(1, flat.shape[0] // n)][:n]
