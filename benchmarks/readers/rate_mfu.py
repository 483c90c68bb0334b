"""Reader ``rate_mfu``: the whole window's share of the chip's peak.

Operations the window's traffic needed (``benchmarks/lib/work.py``, from
shapes and token counts alone) over the window's seconds times the
chip's bf16 peak times the chips used.  It bounds every kernel's
roofline from above: a kernel taken off the path leaves its roofline
silent and this number standing."""
from __future__ import annotations

from benchmarks.lib import work


def read(args: dict, run: dict, trace, ctx: dict):
    if not ctx.get("peaks"):
        return None
    flops = work.window_work(args["family"], ctx["config"]["model"],
                             run["observed"])
    peak = ctx["peaks"]["bf16_flops"] * len(ctx["devices"])
    return 100.0 * flops / (run["window_s"] * peak)
