"""Reader ``counters_ratio``: scale * sum(num) / product(den) over what
the driver counted in the window (differences of the program's own
counters and host-clock seconds around its calls)."""
from __future__ import annotations


def read(args: dict, run: dict, trace, ctx: dict):
    seen = run["observed"]
    keys = list(args["num"]) + list(args.get("den", []))
    if any(k not in seen for k in keys):
        return None
    den = 1.0
    for k in args.get("den", []):
        den *= float(seen[k])
    if den == 0.0:
        return None
    return float(args.get("scale", 1.0)) * sum(
        float(seen[k]) for k in args["num"]) / den
