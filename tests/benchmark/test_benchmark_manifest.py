"""BENCHMARK.json against the contract's limits on names, and against
the files the harness finds by name: a later PR adds a cell, a
configuration of an existing role or a per-layer metric by adding
files, and this test is what tells it that every name resolves."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load(os.path.join(REPO, "BENCHMARK.json"))


def test_top_level_keys_and_run_length(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(REPO, p)), p
    assert manifest["command"][1].startswith(tuple(manifest["paths"]))


def test_names_units_and_lines(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for entry in manifest["configs"] + manifest["workloads"]:
        assert NAME.match(entry["name"]), entry["name"]
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in manifest["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_cell_resolves_to_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cfg = load(os.path.join(REPO, configs[w["config"]]["file"]))
        assert cfg["name"] == w["config"]
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        assert os.path.isfile(os.path.join(
            BENCH, "roles", cfg["role"] + ".py")), cfg["role"]
        traffic = load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert traffic["name"] == w["traffic"]
        assert traffic["kind"] in ("churn", "closed", "open")
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs), "a configuration without a cell"
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))


def test_every_metric_has_a_reader_and_reports_where_it_says(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def where(m):
        return set(m.get("workloads", cells))

    for m in manifest["end_to_end"] + manifest["per_layer"]:
        spec = load(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        assert os.path.isfile(os.path.join(
            BENCH, "readers", spec["reader"] + ".py")), spec["reader"]
        assert where(m) <= cells, m["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert where(m) <= where(e2e[m["moves"]]), (
            f"{m['name']} lists a cell that does not report {m['moves']}")
    for cell in cells:
        mine = [m for m in manifest["end_to_end"] if cell in where(m)]
        assert len(mine) >= 2, f"{cell} reports setup_s and what else?"
        assert any(cell in where(m) for m in manifest["per_layer"]), cell
    roofs = [m for m in manifest["per_layer"] if "roofline" in m["name"]]
    assert all(m["name"].endswith("_roofline") and m["unit"] == "%"
               for m in roofs)


def test_metric_files_use_allowed_names():
    for kind in ("metrics", "configs", "traffic", "readers", "roles"):
        for fn in os.listdir(os.path.join(BENCH, kind)):
            if fn.startswith("__"):
                continue
            stem = fn.rsplit(".", 1)[0]
            assert NAME.match(stem), f"{kind}/{fn}"
