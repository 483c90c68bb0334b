"""Reader ``trace_roofline``: a kernel's share of its roofline.

The least time the chip could take for the work the window's traffic
needed of this kernel (``args["work"]`` over the peak ``args["bound"]``
names) over the summed device time of the trace events whose names match
``args["events"]``.  Finds nothing to read, and says so by returning
None, where no such event ran: never 0."""
from __future__ import annotations

from benchmarks.lib import work, xplane


def read(args: dict, run: dict, trace, ctx: dict):
    if not trace or not ctx.get("peaks"):
        return None
    spent = [xplane.matching_seconds(events, args["events"])
             for events in trace["planes"].values()]
    seconds = sum(spent) / len(spent)
    if seconds <= 0.0:
        return None
    needed = work.window_work(args["work"], ctx["config"]["model"],
                              run["observed"])
    least = needed / (ctx["peaks"][args["bound"]] * len(spent))
    return 100.0 * least / seconds
