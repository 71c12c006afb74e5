"""Cells, configurations, mixes and metrics are found by name, and a
new one needs only new files and new ``BENCHMARK.json`` entries."""

import glob
import json
import os
import re
import time

import pytest

import harness
import reference
from conftest import ROOT, TINY_CELLS, make_root

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def test_benchmark_json_keeps_to_its_schema():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    for group, keys in KEYS.items():
        for entry in SPEC[group]:
            extra = set(entry) - keys - ({"workloads"} if group in (
                "end_to_end", "per_layer") else set())
            assert set(entry) >= keys and not extra, (group, entry)
            assert NAME.match(entry["name"])
            for key in ("why", "layer", "source"):
                text = entry.get(key, "x")
                assert 1 <= len(text) <= 200 and not re.search(r"[\t\n]",
                                                               text)
            if "unit" in entry:
                assert UNIT.match(entry["unit"])
                assert entry["better"] in ("lower", "higher")
    cells = {w["name"] for w in SPEC["workloads"]}
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(cells) // 2)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_with_its_files(cell):
    c = harness.load_cell(ROOT, cell)
    assert c.config["limits"]["rel_rmse"] > 0
    assert "program_options" in c.config["control"] or \
        "reference_store" in c.config["control"]
    assert {m["name"] for m in c.end_to_end} >= {"gups", "setup_s"}
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.reader(c.bench_dir, m["name"]).read)


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_reader_declares_its_layer_and_what_it_moves(metric):
    mod = harness.reader(os.path.join(ROOT, "bench"), metric["name"])
    assert mod.LAYER == metric["layer"]
    assert mod.MOVES == metric["moves"]


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(ROOT, "bench", "configs", "*.json"))),
    ids=os.path.basename)
def test_every_configuration_file_is_complete(path):
    with open(path) as f:
        config = json.load(f)
    assert {"scan", "options", "control", "limits", "reduced",
            "assumed"} <= set(config)
    g = reference.geometry(config["scan"])
    assert g["n_proj"] == 512 and config["limits"]["rel_rmse"] == 1e-5


def test_unknown_cell_and_device_are_errors():
    with pytest.raises(KeyError):
        harness.load_cell(ROOT, "no.such.cell")
    with pytest.raises(KeyError):
        harness.peak_of(os.path.join(ROOT, "bench"), "TPU v99")


def test_a_cell_and_a_metric_added_as_files_alone(tmp_path):
    """A new configuration, mix, cell and per-layer metric: files and
    BENCHMARK.json entries, no edit to any file the harness has."""
    root = make_root(tmp_path, {"tiny.new": TINY_CELLS["tiny.fdk"]},
                     mix={"pool": 3, "coarse": 4, "check_voxels": 256})
    with open(os.path.join(root, "bench", "metrics",
                           "scans_in_window.py"), "w") as f:
        f.write('LAYER = "entry"\nMOVES = "gups"\n\n\n'
                'def read(run):\n    return run.n_scans\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["per_layer"].append({
        "name": "scans_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "gups",
        "workloads": ["tiny.new"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    c = harness.load_cell(root, "tiny.new")
    assert c.mix["pool"] == 3
    out = harness.run_cell(c, 3, 0.05, True, time.perf_counter())
    assert out["correct"] is True
    assert out["metrics"]["scans_in_window"]["value"] == out["attempted"]
