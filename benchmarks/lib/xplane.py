"""Profiler trace -> events -> intervals.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  A device
plane is ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event for
every operation the chip ran (start and duration in nanoseconds, on the
clock the host planes share).  The host plane holds one line per thread,
and the benchmark's own ``jax.profiler.TraceAnnotation`` spans lie on
the thread that opened them.

Everything below the reader is plain Python on (name, start, end)
tuples, so the tests drive it with hand-made events.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
HOST_PLANE = r"^/host:CPU$"


def start(trace_dir: str):
    """Start the profiler with the Python tracer off: it slows the host
    the window measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop():
    import jax
    jax.profiler.stop_trace()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path: str, *, device_plane: str = DEVICE_PLANE,
                ops_line: str = OPS_LINE, host_plane: str = HOST_PLANE,
                span_prefix: str = "bench.") -> dict:
    """{"device": {plane: [(name, start_s, end_s), ...]},
        "spans": [(name, start_s, end_s), ...]} from one trace file.

    Device events are those of the lines matching ``ops_line`` on planes
    matching ``device_plane``; an event's name is its own name followed
    by the string stats that say where it came from, so that a pattern
    can match the kernel's or the scope's name whichever the backend
    fills in.  Spans are host events whose name starts with
    ``span_prefix``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: dict = {}
    spans: list = []
    for plane in data.planes:
        if re.search(device_plane, plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if not re.search(ops_line, line.name):
                    continue
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    evs.append((_full_name(e), e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
        if re.search(host_plane, plane.name):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    spans.sort(key=lambda s: s[1])
    return {"device": device, "spans": spans}


def _full_name(event) -> str:
    parts = [event.name]
    for _, val in event.stats:
        if isinstance(val, str) and val:
            parts.append(val[:300])
    return " | ".join(dict.fromkeys(parts))


def union(intervals) -> list:
    """Disjoint sorted [start, end] covering the same instants."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, t0: float, t1: float) -> list:
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def busy_seconds(events) -> float:
    return sum(e - s for s, e in union((s, e) for _, s, e in events))


def gaps(events, t0: float, t1: float) -> list:
    """Idle [start, end] between ``t0`` and ``t1``."""
    out, at = [], t0
    for s, e in union((s, e) for _, s, e in events):
        if s > at:
            out.append([at, min(s, t1)])
        at = max(at, e)
    if at < t1:
        out.append([at, t1])
    return [g for g in out if g[1] > g[0]]


def name_gap(gap, spans) -> str:
    """The innermost span open on the host at the gap's middle, or
    ``"(no span)"``.  Innermost is the one that started last."""
    mid = 0.5 * (gap[0] + gap[1])
    held = [s for s in spans if s[1] <= mid < s[2]]
    return max(held, key=lambda s: s[1])[0] if held else "(no span)"


_HLO = re.compile(r"^(%[\w\-]+?)(?:\.\d+)? = (\(?[a-z]\w*\[[\d,]*\])")


def short_name(name: str, limit: int = 96) -> str:
    """A trace event's name, cut to what tells operations apart.  The TPU
    names a device event by the whole HLO instruction; its stem without
    the running number and the (first) output shape stand for it, so the
    copies of one operation in every layer add up under one name."""
    name = name.split(" | ")[0]
    m = _HLO.match(name)
    return (f"{m.group(1)} {m.group(2)}" if m else name)[:limit]


def top_ops(events, n: int = 10) -> list:
    """[[name, seconds], ...] of the ``n`` operations with most time,
    summed over events of one ``short_name``."""
    total: dict = {}
    for name, s, e in events:
        key = short_name(name)
        total[key] = total.get(key, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def longest_gaps(events, spans, t0: float, t1: float, n: int = 5) -> list:
    """[[host span, seconds], ...] of the ``n`` longest idle gaps."""
    found = sorted(gaps(events, t0, t1), key=lambda g: g[0] - g[1])[:n]
    return [[name_gap(g, spans), g[1] - g[0]] for g in found]


def matching_seconds(events, patterns) -> float:
    """Summed duration of the events whose full name matches any of the
    regular expressions ``patterns`` (kernels do not overlap themselves
    on one chip, so the sum is the kernel's time)."""
    regs = [re.compile(p) for p in patterns]
    return sum(e - s for name, s, e in events
               if any(r.search(name) for r in regs))


def window_of(spans, name: str):
    """[start, end] of the first span called ``name``: the traced window
    is the benchmark's own span, on the device's clock."""
    for n, s, e in spans:
        if n == name:
            return s, e
    raise LookupError(f"the trace holds no span called {name!r}")


def reduce(trace_dir: str, *, window_span: str = "bench.window",
           **read_kw) -> dict:
    """What the readers need of one traced run: per device plane the
    events inside the window, the host spans, busy and window seconds
    (busy averaged over the chips), and the breakdown."""
    ev = read_events(find_xplane(trace_dir), **read_kw)
    t0, t1 = window_of(ev["spans"], window_span)
    planes = {p: clip(e, t0, t1) for p, e in ev["device"].items()}
    if not planes:
        raise LookupError("the trace holds no device plane")
    busy = [busy_seconds(e) for e in planes.values()]
    first = planes[sorted(planes)[0]]
    inner = [s for s in ev["spans"] if s[0] != window_span]
    return {"planes": planes, "spans": ev["spans"], "t0": t0, "t1": t1,
            "window_s": t1 - t0, "busy_s": sum(busy) / len(busy),
            "breakdown": {
                "device_ops": top_ops(first, 10),
                "idle_gaps": longest_gaps(first, inner, t0, t1, 5)}}
