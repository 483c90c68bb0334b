"""``BENCHMARK.json`` against the contract it is written to, and against
the files its names stand for."""
import importlib
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expand|experts_per_tok")


def load(path):
    with open(path) as f:
        return json.load(f)


MANIFEST = load(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in MANIFEST["paths"])
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(line(w) for w in cmd)
    for word in cmd:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    # the full check has to fit with 24 cells
    s = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128


def test_names_are_unique():
    for group in (MANIFEST["configs"], MANIFEST["workloads"], METRICS):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entry_and_its_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert line(entry["source"]) and line(entry["why"])
    assert any(entry["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert PATH.match(entry["file"])
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    assert entry["name"] in {w["config"] for w in MANIFEST["workloads"]}
    cfg = load(os.path.join(ROOT, entry["file"]))
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert all(k in cfg["model"] for k in entry["reduced"])
    assert isinstance(cfg.get("assumed", {}), dict)
    assert line(cfg["deployment"], 400)
    # the driver and the plain reference the file names exist
    driver = importlib.import_module("benchmarks.drivers." + cfg["driver"])
    assert callable(driver.run) and callable(driver.calibrate)
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "reference", cfg["reference"] + ".py"))


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["name"] == cell["config"] + "." + cell["traffic"]
    assert cell["chips"] in (1, 4) and line(cell["why"])
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    mix = load(os.path.join(ROOT, "benchmarks", "traffic",
                            cell["traffic"] + ".json"))
    assert mix["trace_seconds"] <= MANIFEST["run_seconds"]
    limits = load(os.path.join(ROOT, "benchmarks", "limits",
                               cell["name"] + ".json"))
    assert limits["cell"] == cell["name"] and limits["limits"]
    assert all(v > 0 for v in limits["limits"].values())
    assert line(limits["readings"], 2000)
    # every cell reports set-up, another end-to-end and a per-layer metric
    reports = {m["name"] for m in MANIFEST["end_to_end"]
               if "workloads" not in m or cell["name"] in m["workloads"]}
    assert "setup_s" in reports and len(reports) >= 2
    assert any(cell["name"] in m.get("workloads", CELLS)
               for m in MANIFEST["per_layer"])


def test_four_chip_cells_are_a_quarter_at_most():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_setup_is_reported_everywhere():
    setup = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] == 0.1 and setup[0]["unit"] == "s"


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_and_its_reader(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES and line(metric["layer"])
    moved = {m["name"]: m for m in MANIFEST["end_to_end"]}[metric["moves"]]
    # every cell that reports it reports the end-to-end metric it moves
    assert set(metric.get("workloads", CELLS)) <= set(
        moved.get("workloads", CELLS))
    spec = load(os.path.join(ROOT, "benchmarks", "layer_metrics",
                             metric["name"] + ".json"))
    for key in ("name", "unit", "layer", "moves"):
        assert spec[key] == metric[key], key
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    assert callable(reader.read)
    if metric["name"].endswith("_roofline") or "_roofline." in metric["name"]:
        assert metric["unit"] == "%" and metric["source"] == "device_trace"
        # the whole step's share of the peak stands beside it
        assert any("mfu" in re.split(r"[._]", m["name"])
                   and m["moves"] == metric["moves"]
                   for m in MANIFEST["per_layer"])


def test_layers_are_named_alike():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert len({name.lower() for name in layers}) == len(layers)


def test_files_under_paths_keep_to_the_allowed_characters():
    for top in MANIFEST["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, top)):
            if "__pycache__" in folder:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel
