"""The reader that takes the program's own spans, ``idle_under_spans``
(the ring's spans on the trace's clock, idle gaps cut up among them), on
hand-made events and through one toy traced run on the CPU, with the
three counters the engine's ``timings`` gained."""
import copy
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as R  # noqa: E402
from benchmarks.lib import xplane  # noqa: E402
from benchmarks.readers import idle_under_spans as I  # noqa: E402

TOY = os.path.join(HERE, "toy")
SERVE = "mistral-toy.chat"
REAL_CELL = "mistral-7b-v0.3-l16.chat-closed32"
# the CPU has no device plane: its thunks run on host threads
CPU_TRACE = dict(device_plane=r"^/host:CPU$",
                 ops_line=r"XLAPjRtCpuClient|XLAEigen")
HOST_SIDE = ["queue_wait_ms", "schedule_ms_per_step", "emit_ms_per_step",
             "idle_ms_per_step.sync", "idle_ms_per_step.dispatch",
             "idle_ms_per_step.caller"]


def spec(name):
    return R.load_json(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                    name + ".json"))


IDLE = {k: spec("idle_ms_per_step." + k)["args"]
        for k in ("sync", "dispatch", "caller")}

# ---- two steps by hand.  The ring's clock runs 100 s ahead of the
# trace's; thread 7 is the engine's.  Step 1 admits and prefills.
RING = [
    ("request", 100.5, 109.0, 7),               # outlives the steps
    ("engine.schedule", 101.0, 101.1, 7),
    ("engine.prefill.dispatch", 101.2, 101.3, 7),
    ("engine.prefill.fetch", 101.3, 101.6, 7),
    ("engine.prefill", 101.1, 101.6, 7),
    ("engine.decode", 101.6, 108.0, 7),         # a request's, not a phase
    ("engine.decode.dispatch", 101.7, 101.8, 7),
    ("engine.host_sync", 101.8, 102.6, 7),
    ("engine.sample", 102.7, 102.8, 7),
    ("engine.emit", 102.6, 102.9, 7),
    ("engine.step", 101.0, 103.0, 7),
    ("server.request", 103.0, 103.4, 9),        # another thread's
    ("engine.schedule", 104.0, 104.1, 7),
    ("engine.decode.dispatch", 104.1, 104.2, 7),
    ("engine.host_sync", 104.2, 105.0, 7),
    ("engine.emit", 105.0, 105.5, 7),
    ("engine.step", 104.0, 105.5, 7),
]
BENCH = [("bench.engine.step", 0.999, 3.001),
         ("bench.engine.step", 3.9995, 5.5005)]
# the device runs 1.35-1.55 (prefill), 1.9-2.5 and 4.3-4.9 (decode steps)
DEVICE = [("prefill", 1.35, 1.55), ("decode_step", 1.9, 2.5),
          ("decode_step", 4.3, 4.9)]
T0, T1 = 0.5, 6.0
TRACE = {"planes": {"/device:TPU:0": DEVICE},
         "spans": [("bench.window", T0, T1)] + BENCH, "t0": T0, "t1": T1}


def mapped(ring=RING, bench=BENCH, **kw):
    return I.on_trace_clock(ring, bench, step="engine.step",
                            names=IDLE["sync"]["spans"], **kw)


def test_ring_spans_land_on_the_trace_clock():
    spans = mapped()
    assert {s[0] for s in spans} == {
        "engine.step", "engine.schedule", "engine.prefill",
        "engine.prefill.dispatch", "engine.prefill.fetch",
        "engine.decode.dispatch", "engine.host_sync", "engine.emit",
        "engine.sample"}                # no request, no other thread
    steps = [s for s in spans if s[0] == "engine.step"]
    # the offset is the median of the two start differences
    assert steps[0][1] == pytest.approx(1.0 - 0.00075)
    assert steps[1][2] == pytest.approx(5.5 - 0.00075)


def test_only_the_last_steps_are_the_windows():
    """Steps of the ramp stay in the ring; the trace holds the window's
    alone, and the ring's last N are those."""
    ramp = [("engine.host_sync", 90.2, 90.8, 7),
            ("engine.step", 90.0, 91.0, 7)]
    assert mapped(ring=ramp + RING) == mapped()


@pytest.mark.parametrize("ring, bench, kw", [
    ([], BENCH, {}),                                    # an empty ring
    ([s for s in RING if s[0] != "engine.step"], BENCH, {}),  # the parent's
    (RING, [], {}),                                     # nothing traced
    (RING[:11], BENCH, {}),                             # one step short
    (RING, [BENCH[0], ("bench.engine.step", 4.2, 5.4)], {}),  # not inside
    (RING, BENCH, {"wrapped_before": 101.05}),          # the ring wrapped
], ids=["empty", "no-step-spans", "no-bench-spans", "unequal", "outside",
        "wrapped"])
def test_what_cannot_be_mapped_reads_none(ring, bench, kw):
    assert mapped(ring=ring, bench=bench, **kw) is None
    trace = dict(TRACE, spans=[TRACE["spans"][0]] + bench)
    assert I.idle_by_span(IDLE["sync"], trace, ring,
                          kw.get("wrapped_before")) is None


def test_a_ring_that_wrapped_before_the_window_still_reads():
    assert mapped(wrapped_before=100.9) == mapped()


def test_innermost_is_the_span_that_started_last():
    got = I.innermost([("step", 0.0, 10.0), ("prefill", 1.0, 4.0),
                       ("fetch", 2.0, 4.0), ("emit", 6.0, 12.0),
                       ("step", 20.0, 21.0)])
    assert got == [(0.0, 1.0, "step"), (1.0, 2.0, "prefill"),
                   (2.0, 4.0, "fetch"), (4.0, 6.0, "step"),
                   (6.0, 10.0, "emit"),         # cut at its parent's end
                   (20.0, 21.0, "step")]
    assert I.innermost([]) == []


def test_idle_gaps_are_cut_up_among_the_segments():
    segments = [(1.0, 2.0, "a"), (2.0, 3.0, "b"), (5.0, 6.0, "a")]
    found = I.split([[0.5, 1.5], [1.75, 2.25], [2.5, 5.5], [7.0, 8.0]],
                    segments)
    assert found == pytest.approx({"a": 0.5 + 0.25 + 0.5, "b": 0.25 + 0.5,
                                   I.OUTSIDE: 0.5 + 2.0 + 1.0})


def test_idle_is_split_by_the_innermost_span_and_adds_up():
    found = I.idle_by_span(IDLE["sync"], TRACE, RING, None)
    o = 0.00075                          # the steps sit that much early
    want = {
        I.OUTSIDE: (1.0 - o - T0) + (4.0 - 3.0) + (T1 - 5.5 + o),
        "engine.schedule": 0.2, "engine.prefill": 0.1,
        "engine.prefill.dispatch": 0.1,
        "engine.prefill.fetch": 0.05 + o + 0.05 - o,
        "engine.step": 0.1 + 0.1,       # after prefill, after emit
        "engine.decode.dispatch": 0.1 + 0.1,
        "engine.host_sync": (1.9 - 1.8 + o) + (2.6 - o - 2.5)
        + (4.3 - 4.2 + o) + (5.0 - o - 4.9),
        "engine.emit": 0.2 + 0.5, "engine.sample": 0.1}
    assert found == pytest.approx(want)
    total = sum(g[1] - g[0] for g in xplane.gaps(DEVICE, T0, T1))
    assert sum(found.values()) == pytest.approx(total)


def test_the_three_metrics_partition_every_span_name():
    names = IDLE["sync"]["spans"]
    assert all(IDLE[k]["spans"] == names for k in IDLE)
    assert all(IDLE[k]["step"] == "engine.step" for k in IDLE)
    under = [n for k in IDLE for n in IDLE[k]["under"]]
    assert sorted(under) == sorted(names + [I.OUTSIDE])
    assert IDLE["caller"]["under"] == [I.OUTSIDE]
    assert set(IDLE["sync"]["under"]) == {
        "engine.host_sync", "engine.emit", "engine.sample"}


def test_the_engine_names_its_phases_as_the_metrics_list_them():
    """Every ``engine.*`` name the engine hands to ``phase()`` is one the
    idle metrics know (else its idle time would fall to its parent)."""
    import re
    src = open(os.path.join(ROOT, "paddle_tpu", "serving",
                            "engine.py")).read()
    used = set(re.findall(r'phase\(\s*"(engine\.[\w.]+)"', src))
    assert used and used == set(IDLE["sync"]["spans"])


def test_read_through_the_programs_own_ring(monkeypatch):
    run = {"observed": {"decode_steps": 2}}
    monkeypatch.setattr(I, "program_ring", lambda: (RING, None))
    parts = {k: I.read(IDLE[k], run, TRACE, {}) for k in IDLE}
    total = sum(g[1] - g[0] for g in xplane.gaps(DEVICE, T0, T1))
    assert sum(parts.values()) == pytest.approx(1000.0 * total / 2)
    assert parts["sync"] == pytest.approx(1000.0 * (0.4 + 0.7 + 0.1) / 2)
    assert I.read(IDLE["sync"], {"observed": {}}, TRACE, {}) is None
    assert I.read(IDLE["sync"], {"observed": {"decode_steps": 0}},
                  TRACE, {}) is None
    assert I.read(IDLE["sync"], run, None, {}) is None


# ------------------------------------------------- a toy traced run
def extended_manifest():
    """A copy of the toy manifest with this PR's metrics appended for the
    toy serving cell, as ``BENCHMARK.json`` has them for the real one."""
    manifest = R.load_json(os.path.join(TOY, "manifest.json"))
    real = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    have = {m["name"] for m in manifest["per_layer"]}
    for m in real["per_layer"]:
        if m["name"] in HOST_SIDE and m["name"] not in have:
            assert m["workloads"] == [REAL_CELL]
            manifest["per_layer"].append(dict(copy.deepcopy(m),
                                              workloads=[SERVE]))
    return manifest


@pytest.fixture(scope="module")
def toy_run():
    """(the result line, what the driver observed) of one traced run."""
    import jax
    from benchmarks.drivers import engine_closed_loop as E
    from paddle_tpu import observability as obs
    obs.tracer().reset()
    observed = {}
    driver_run = E.run

    def spy(ctx):
        out = driver_run(ctx)
        observed.update(out["observed"])
        return out

    E.run = spy
    try:
        # a short window: the ring (4,096 spans) must hold all of it
        line = R.run_cell(extended_manifest(), SERVE, seed=3000000019,
                          seconds=0.25, trace=True,
                          devices=jax.devices()[:1], root=TOY,
                          t_start=time.perf_counter(), trace_kw=CPU_TRACE)
    finally:
        E.run = driver_run
    return line, observed


def test_toy_traced_run_reports_the_host_side_metrics(toy_run):
    line, observed = toy_run
    got = line["metrics"]
    json.loads(json.dumps(line))
    assert line["correct"] is True
    for name in HOST_SIDE:
        assert name in got, name
        assert got[name]["unit"] == "ms"
        assert np.isfinite(got[name]["value"]) and got[name]["value"] >= 0.0
    steps = observed["decode_steps"]
    assert steps > 0
    for name, key in (("schedule_ms_per_step", "schedule_s"),
                      ("emit_ms_per_step", "emit_s")):
        assert got[name]["value"] == pytest.approx(
            1000.0 * observed[key] / steps) and observed[key] > 0.0
    assert got["queue_wait_ms"]["value"] == pytest.approx(
        1000.0 * observed["queue_wait_s"] / observed["attempted"])
    # the outside timers read what they read before
    assert got["decode_step_ms"]["value"] == pytest.approx(
        1000.0 * (observed["decode_s"] + observed["host_sync_s"]) / steps)


def test_toy_idle_parts_add_up_to_the_windows_idle_time(toy_run):
    """``idle_ms_per_step.*`` is ``device_idle_share.serve`` by another
    road: the three add up to the window's idle seconds a step."""
    line, observed = toy_run
    got, dev = line["metrics"], line["device"]
    idle_s = dev["window_s"] - dev["busy_s"]
    assert idle_s == pytest.approx(
        got["device_idle_share.serve"]["value"] / 100.0 * dev["window_s"])
    parts = sum(got["idle_ms_per_step." + k]["value"] for k in IDLE)
    assert parts == pytest.approx(
        1000.0 * idle_s / observed["decode_steps"], rel=0.01)
    # the toy's device is its host: most of its idle time lies in the
    # step, little with the caller
    assert got["idle_ms_per_step.caller"]["value"] < parts


def test_the_parents_program_reads_none_not_zero():
    """With no ``engine.step`` in the ring, as at the parent commit, every
    span reader finds nothing: None, and the line leaves the metric out."""
    from paddle_tpu import observability as obs
    obs.tracer().reset()
    run = {"observed": {"decode_steps": 5}}
    for k in IDLE:
        assert I.read(IDLE[k], run, TRACE, {}) is None
