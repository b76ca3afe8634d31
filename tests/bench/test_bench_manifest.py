"""The benchmark manifest: allowed names and units, every file it names
present, every per-layer metric's end-to-end metric reported in each of
its cells, every configuration used by a cell."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(manifest["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") for w in
               manifest["command"])
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) <= 64 * 1024


def test_names_and_units(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"])
        assert _line(c["source"]) and len(c["reduced"]) <= 16
        names.append(c["name"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        names.append(w["name"])
    for m in manifest["end_to_end"]:
        assert set(m) <= E2E_KEYS and set(m) >= E2E_KEYS - {"workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) <= LAYER_KEYS and set(m) >= LAYER_KEYS - {"workloads"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}


def test_every_configuration_keeps_a_cell(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_files_found_by_name(manifest):
    for c in manifest["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("bench/") and os.path.isfile(path)
        with open(path) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for w in manifest["workloads"]:
        path = os.path.join(ROOT, "bench", "traffic", f"{w['traffic']}.json")
        with open(path) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(ROOT, "bench", "drivers",
                                           f"{kind}.py"))
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))


def _cells_reporting(manifest, metric):
    cells = {w["name"] for w in manifest["workloads"]}
    return set(metric.get("workloads", cells))


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    for w in manifest["workloads"]:
        e2e = [m for m in manifest["end_to_end"]
               if w["name"] in _cells_reporting(manifest, m)]
        layer = [m for m in manifest["per_layer"]
                 if w["name"] in _cells_reporting(manifest, m)]
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer


def test_moves_is_reported_in_each_of_its_cells(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert _cells_reporting(manifest, m) <= _cells_reporting(
            manifest, e2e[m["moves"]])


def test_layers_are_named_as_perf_md_lists_them(manifest):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in manifest["per_layer"]}:
        assert f"**{layer}**" in perf, layer
