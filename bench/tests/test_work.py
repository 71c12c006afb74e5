"""The work arithmetic behind ``gups`` and ``bp_roofline``."""

import json
import os

import pytest

import reference
import work
from conftest import BENCH

P = {name: reference.geometry({"vol": vol, "det": det, "n_proj": 512,
                               "sad": 1000.0, "sdd": 1536.0,
                               "extent": 256.0, "det_margin": 1.25})
     for name, det, vol in (("P5", 512, 512), ("P7", 1024, 256),
                            ("P9", 1024, 1024))}
with open(os.path.join(BENCH, "peaks.json")) as f:
    V5E = json.load(f)["TPU v5 lite"]


def test_updates_are_the_papers_gups_numerator():
    assert work.updates(P["P5"]) == 512 ** 4
    assert work.updates(P["P9"]) == 1024 ** 3 * 512
    # one P5 volume in 12.2 s, the warm subline_pl wall on a TPU v5e
    assert work.gups(work.updates(P["P5"]), 12.2) == pytest.approx(
        5.6327, abs=1e-4)


def test_least_bytes_read_views_once_and_write_the_volume_once():
    g = P["P7"]
    assert work.min_bytes(g) == 4 * (512 * 1024 * 1024 + 256 ** 3)


@pytest.mark.parametrize("name,bound", [("P5", "compute"), ("P7", "memory"),
                                        ("P9", "compute")])
def test_which_bound_sets_the_least_time(name, bound):
    g = P[name]
    compute = work.flops(g) / V5E["flops_per_s"]
    memory = work.min_bytes(g) / V5E["bytes_per_s"]
    assert (compute > memory) == (bound == "compute")
    assert work.least_seconds(g, V5E, 3) == pytest.approx(
        3 * max(compute, memory))
    assert work.least_seconds(P["P5"], V5E) == pytest.approx(
        8 * 512 ** 4 / 197e12)


def test_reference_geometry_matches_the_programs():
    from repro.configs.ct_paper import get_problem
    from repro.core.geometry import CTGeometry
    for name, g in P.items():
        assert CTGeometry(**g) == get_problem(name).geometry()
