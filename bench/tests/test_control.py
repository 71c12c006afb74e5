"""The control, put in the program's place, comes out as not correct,
and the program as correct: ``bench/calibrate.py`` at the smoke size on
the CPU (on the chip it runs at each cell's own size)."""

import time

import numpy as np
import pytest

import calibrate
import harness
import reference


@pytest.mark.parametrize("cell", ["tiny.fdk", "tiny.fleet4"])
def test_control_separates_from_program(tiny_root, cell):
    c = harness.load_cell(tiny_root, cell)
    out = calibrate.readings(c, [1, 2, 3], [1, 2, 3])
    assert out["lower"] < out["limit"] < out["upper"]
    assert out["upper"] >= 3 * out["lower"]


def test_program_control_run_reads_incorrect(tiny_root):
    """The program's own bf16 path in a whole run."""
    c = harness.load_cell(tiny_root, "tiny.fdk")
    c.config = dict(c.config, options=dict(
        c.config["options"], **c.config["control"]["program_options"]))
    out = harness.run_cell(c, 5, 0.05, False, time.perf_counter())
    assert out["correct"] is False


def test_reference_control_run_reads_incorrect(tiny_root, monkeypatch):
    """The reference with bf16 filtered views, in the program's place."""
    c = harness.load_cell(tiny_root, "tiny.fleet4")
    store = c.config["control"]["reference_store"]

    def make_request(geom, _options, _span=None):
        ijk = np.stack(np.meshgrid(*(np.arange(geom[a]) for a in
                                     ("nx", "ny", "nz")), indexing="ij"),
                       -1).reshape(-1, 3)

        def request(projections):
            vol = np.zeros((geom["nz"], geom["ny"], geom["nx"]), np.float32)
            vol[ijk[:, 2], ijk[:, 1], ijk[:, 0]] = reference.fdk_at(
                projections, geom, ijk, store)
            return vol
        return request

    monkeypatch.setattr(harness, "make_request", make_request)
    out = harness.run_cell(c, 5, 0.05, False, time.perf_counter())
    assert out["correct"] is False
