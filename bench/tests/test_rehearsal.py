"""The harness core driven end to end on the CPU at the smoke size: the
platform check is the only part of a run left out."""

import json
import time

import pytest

import harness


@pytest.mark.parametrize("cell", ["tiny.fdk", "tiny.fleet4"])
@pytest.mark.parametrize("traced", [False, True])
def test_run_is_correct_and_complete(tiny_root, cell, traced):
    c = harness.load_cell(tiny_root, cell)
    out = harness.run_cell(c, 2 ** 31 + 17, 0.2, traced, time.perf_counter())
    json.dumps(out)
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert out["check"]["rel_rmse"]["value"] < 1e-6
    assert out["device"]["platform"] == "cpu"
    want = {m["name"] for m in (c.per_layer if traced else c.end_to_end)}
    # no peaks for a CPU: the roofline share stays silent, which the
    # entry point would refuse on a chip
    silent = ["bp_roofline"] if traced else []
    assert harness.missing_metrics(c, out, traced) == silent
    want -= set(silent)
    if traced:
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert out["breakdown"]["device_ops"]
        assert out["breakdown"]["idle_gaps"]
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] >= 0
