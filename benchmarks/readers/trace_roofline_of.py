"""Reader ``trace_roofline_of``: ``trace_roofline`` for a family that
brings its own work functions.

``args["work"]`` names a function of ``benchmarks/lib/<args["lib"]>.py``'s
``WORK`` table, called with the configuration file and what the driver
saw.  Finds nothing to read, and returns None, where no matching event
ran or the driver saw none of what the work function counts (a program
without those counters)."""
from __future__ import annotations

import importlib

from benchmarks.lib import xplane


def read(args: dict, run: dict, trace, ctx: dict):
    if not trace or not ctx.get("peaks"):
        return None
    spent = [xplane.matching_seconds(events, args["events"])
             for events in trace["planes"].values()]
    seconds = sum(spent) / len(spent)
    if seconds <= 0.0:
        return None
    lib = importlib.import_module("benchmarks.lib." + args["lib"])
    try:
        needed = lib.WORK[args["work"]](ctx["config"], run["observed"])
    except KeyError:
        return None
    least = needed / (ctx["peaks"][args["bound"]] * len(spent))
    return 100.0 * least / seconds
