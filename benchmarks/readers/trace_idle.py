"""Reader ``trace_idle``: 1 - (union of the intervals in which an
operation ran on the device) / (the traced window), averaged over the
chips used."""
from __future__ import annotations


def read(args: dict, run: dict, trace, ctx: dict):
    if not trace or trace["busy_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
