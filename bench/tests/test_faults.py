"""A run with the timed path broken underneath reads ``correct: false``:
once for each fault a cell can have. The platform check is left out;
the rest of a run is driven as on the chip."""

import threading
import time

import numpy as np
import pytest

import harness
from repro.runtime.executor import PlanExecutor

ORIGINAL = PlanExecutor.reconstruct


def unchanged(self, projections):
    """The back-projection leaves its accumulator as it found it."""
    return np.zeros(self.plan.vol_shape_xyz[::-1], np.float32)


def half_batch(self, projections):
    """Half of the views left out, the rest weighted up to keep the mean."""
    p = np.array(projections)
    n = p.shape[0] // 2
    p[:n] *= 2.0
    p[n:] = 0.0
    return ORIGINAL(self, p)


def altered(self, projections):
    """One corner block of the volume off by a part in a hundred."""
    vol = np.array(ORIGINAL(self, projections))
    nz, ny, nx = vol.shape
    vol[:, : ny // 2, : nx // 2] *= 1.01
    return vol


def not_a_number(self, projections):
    """One voxel line of the volume written as NaN."""
    vol = np.array(ORIGINAL(self, projections))
    vol[:, 0, :] = np.nan
    return vol


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered, "not_a_number": not_a_number}


def run(root, cell):
    c = harness.load_cell(root, cell)
    return harness.run_cell(c, 11, 0.05, False, time.perf_counter())


@pytest.mark.parametrize("cell", ["tiny.fdk", "tiny.fleet4"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_incorrect(tiny_root, monkeypatch, cell, fault):
    monkeypatch.setattr(PlanExecutor, "reconstruct", FAULTS[fault])
    out = run(tiny_root, cell)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] > 0
    assert out["check"]["rel_rmse"]["value"] > 1e-3


def test_fleet_without_one_chips_steps_reads_incorrect(tiny_root,
                                                       monkeypatch):
    """The exchange between chips left out: chip 1's step outputs never
    reach the host volume."""
    writes = PlanExecutor._step_writes

    def dropped(step, out):
        if threading.current_thread().name == "recon-fleet-1":
            return ()
        return writes(step, out)

    monkeypatch.setattr(PlanExecutor, "_step_writes", staticmethod(dropped))
    out = run(tiny_root, "tiny.fleet4")
    assert out["correct"] is False
    assert out["check"]["rel_rmse"]["value"] > 1e-3


def test_sound_runs_read_correct(tiny_root):
    for cell in ("tiny.fdk", "tiny.fleet4"):
        assert run(tiny_root, cell)["correct"] is True
