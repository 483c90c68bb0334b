"""Reader ``rate_mfu_of``: ``rate_mfu`` for a family that brings its own
work function (``args["work"]`` of ``benchmarks/lib/<args["lib"]>.py``'s
``WORK`` table, called with the configuration file and what the driver
saw).  None where the driver saw none of what the function counts."""
from __future__ import annotations

import importlib


def read(args: dict, run: dict, trace, ctx: dict):
    if not ctx.get("peaks"):
        return None
    lib = importlib.import_module("benchmarks.lib." + args["lib"])
    try:
        flops = lib.WORK[args["work"]](ctx["config"], run["observed"])
    except KeyError:
        return None
    peak = ctx["peaks"]["bf16_flops"] * len(ctx["devices"])
    return 100.0 * flops / (run["window_s"] * peak)
