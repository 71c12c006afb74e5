"""The readers of the fleet's ``fleet.flush``, ``fleet.flush_wait`` and
``fleet.replicate`` spans, on hand-made timelines."""

import types

import pytest

import harness
import traces
from conftest import BENCH

READERS = ("fleet_flush_ms_per_scan", "fleet_flush_wait_ms_per_scan",
           "fleet_replicate_ms_per_scan")
FOUR = (0, 1, 2, 3)


def read(metric, run):
    return harness.reader(BENCH, metric).read(run)


def run_of(host, devices=FOUR, n_scans=2, window=(0, 100)):
    trace = traces.Trace({d: traces.Device([(0, 100, "k", "jit_one")], [])
                          for d in devices}, sorted(host))
    return types.SimpleNamespace(trace=trace, window=window,
                                 devices=list(devices), n_scans=n_scans)


HOST = [(0, 100, "bench.window"),
        # three chips copy at once, one of them past the window's start
        (-4, 6, "fleet.replicate"), (0, 8, "fleet.replicate"),
        (2, 5, "fleet.replicate"),
        # the flushes follow each other under the lock, the last one
        # past the window's end
        (30, 40, "fleet.flush"), (40, 55, "fleet.flush"),
        (95, 110, "fleet.flush"),
        # two workers wait at once for the same flush
        (30, 40, "fleet.flush_wait"), (32, 40, "fleet.flush_wait"),
        (40, 40.5, "fleet.flush_wait"), (90, 95, "fleet.flush_wait"),
        (-10, -2, "fleet.flush_wait")]


def test_flush_sums_spans_clipped_to_the_window_per_scan():
    assert read("fleet_flush_ms_per_scan", run_of(HOST)) == pytest.approx(
        (10 + 15 + 5) * 1e-6 / 2)


def test_flush_wait_sums_overlapping_waits_of_each_worker():
    assert read("fleet_flush_wait_ms_per_scan", run_of(HOST)) == \
        pytest.approx((10 + 8 + 0.5 + 5) * 1e-6 / 2)


def test_replicate_counts_concurrent_copies_once():
    assert read("fleet_replicate_ms_per_scan", run_of(HOST)) == \
        pytest.approx(8 * 1e-6 / 2)


@pytest.mark.parametrize("metric", READERS)
def test_divides_by_the_scans_in_the_window(metric):
    one, three = (read(metric, run_of(HOST, n_scans=n)) for n in (1, 3))
    assert one == pytest.approx(3 * three) and one > 0


@pytest.mark.parametrize("metric", READERS)
def test_one_chip_reads_zero(metric):
    assert read(metric, run_of(HOST, devices=(0,))) == 0.0
    assert read(metric, run_of([(0, 100, "bench.window")],
                               devices=(0,))) == 0.0


@pytest.mark.parametrize("metric", READERS)
def test_a_fleet_without_its_spans_reads_nothing(metric):
    # a program without the spans, or with them renamed, must fail the
    # run instead of reading 0
    span = {"fleet_flush_ms_per_scan": "fleet.flush",
            "fleet_flush_wait_ms_per_scan": "fleet.flush_wait",
            "fleet_replicate_ms_per_scan": "fleet.replicate"}[metric]
    others = [h for h in HOST if h[2] != span]
    assert read(metric, run_of(others)) is None
    outside = others + [(150, 160, span), (-20, -10, span)]
    assert read(metric, run_of(outside)) is None
    renamed = others + [(20, 30, span + "_renamed")]
    assert read(metric, run_of(renamed)) is None


@pytest.mark.parametrize("metric", READERS)
def test_an_untraced_run_reads_nothing(metric):
    run = types.SimpleNamespace(trace=None, window=None, devices=list(FOUR),
                                n_scans=1)
    assert read(metric, run) is None
