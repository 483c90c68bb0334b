"""Reader ``idle_under_spans``: the device's idle time put down to what
the program's host thread was doing in it.

The program's own spans come from its ring
(``paddle_tpu.observability.tracer().spans()``: this process, the
``perf_counter`` clock) and are put on the trace's clock here.  The
driver opens one ``args["bench_step"]`` span around every call of
``Engine.step()``, so the last N ``args["step"]`` spans of the ring are
the N spans of that name in the trace; the clock offset is the median
of their start differences.  The spans named in ``args["spans"]`` are
then put under one another by time (innermost = the one open that
started last; not by parent id, a prefill's parent is its request), and
every idle gap of the window (``xplane.gaps``) is cut up among them.
The metric is the idle seconds that fall where the innermost span is one
of ``args["under"]`` (``"(no span)"`` = outside every span), times
``scale`` over the product of ``den``.

Finds nothing to read, and returns None, where the program has no such
spans, where the counts differ, where a mapped step does not lie inside
its benchmark span to within a millisecond, and where the ring wrapped
inside the window.  Everything below ``read`` is plain Python on
(name, start, end) tuples.
"""
from __future__ import annotations

import statistics

from benchmarks.lib import xplane

OUTSIDE = "(no span)"
SLACK_S = 1e-3


def on_trace_clock(ring: list, bench_steps: list, *, step: str,
                   names, wrapped_before: float | None = None):
    """The ring's spans called one of ``names`` as (name, start, end) on
    the clock of ``bench_steps`` [(name, start, end), ...], or None.

    ``ring`` holds (name, start, end, thread) in the order the spans
    were committed; ``wrapped_before`` is the end of the oldest span a
    ring that has dropped spans still holds (None: nothing dropped)."""
    steps = [s for s in ring if s[0] == step]
    n = len(bench_steps)
    if n == 0 or len(steps) < n:
        return None
    steps = steps[-n:]
    if wrapped_before is not None and wrapped_before > steps[0][1]:
        return None                     # the window's first spans are gone
    offset = statistics.median(b[1] - s[1]
                               for b, s in zip(bench_steps, steps))
    for b, s in zip(bench_steps, steps):
        if (s[1] + offset < b[1] - SLACK_S
                or s[2] + offset > b[2] + SLACK_S):
            return None
    thread, since = steps[0][3], steps[0][1]
    keep = set(names)
    return [(s[0], s[1] + offset, s[2] + offset) for s in ring
            if s[0] in keep and s[3] == thread and s[2] >= since]


def innermost(spans: list) -> list:
    """Disjoint, sorted (start, end, name): at every instant some span
    is open, the one that started last.  The spans of one thread nest;
    one that outlasts its parent is cut at the parent's end."""
    out: list = []
    stack: list = []                    # open spans, outermost first
    at = 0.0

    def close_until(t: float):
        nonlocal at
        while stack and stack[-1][2] <= t:
            name, _, end = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(start)
        if stack:
            end = min(end, stack[-1][2])
            if start > at:
                out.append((at, start, stack[-1][0]))
        if end <= start:
            continue
        stack.append((name, start, end))
        at = start
    close_until(float("inf"))
    return out


def split(gaps: list, segments: list) -> dict:
    """{name: seconds} of the idle ``gaps`` [[start, end], ...] by the
    ``segments`` (start, end, name) they fall in; what falls in none
    goes to ``OUTSIDE``.  Both are disjoint and sorted."""
    found: dict = {OUTSIDE: 0.0}
    i = 0
    for g0, g1 in gaps:
        covered = 0.0
        while i < len(segments) and segments[i][1] <= g0:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < g1:
            s0, s1, name = segments[j]
            part = min(g1, s1) - max(g0, s0)
            if part > 0.0:
                found[name] = found.get(name, 0.0) + part
                covered += part
            j += 1
        found[OUTSIDE] += (g1 - g0) - covered
    return found


def program_ring() -> tuple:
    """The program's ring as ``on_trace_clock`` takes it."""
    from paddle_tpu.observability import tracer
    tr = tracer()
    held = tr.spans()
    ring = [(s.name, s.start, s.end_time, s.tid) for s in held]
    wrapped = held[0].end_time if tr.spans_dropped and held else None
    return ring, wrapped


def idle_by_span(args: dict, trace: dict, ring: list, wrapped) -> dict | None:
    """{name: idle seconds, averaged over the chips} or None."""
    bench = [s for s in trace["spans"] if s[0] == args["bench_step"]]
    spans = on_trace_clock(ring, bench, step=args["step"],
                           names=args["spans"], wrapped_before=wrapped)
    if spans is None:
        return None
    segments = innermost(xplane.clip(spans, trace["t0"], trace["t1"]))
    total: dict = {}
    for events in trace["planes"].values():
        gaps = xplane.gaps(events, trace["t0"], trace["t1"])
        for name, seconds in split(gaps, segments).items():
            total[name] = total.get(name, 0.0) + seconds
    return {k: v / len(trace["planes"]) for k, v in total.items()}


def read(args: dict, run: dict, trace, ctx: dict):
    seen = run["observed"]
    if not trace or any(k not in seen for k in args["den"]):
        return None
    den = 1.0
    for k in args["den"]:
        den *= float(seen[k])
    if den == 0.0:
        return None
    found = idle_by_span(args, trace, *program_ring())
    if found is None:
        return None
    return float(args.get("scale", 1.0)) * sum(
        found.get(name, 0.0) for name in args["under"]) / den
