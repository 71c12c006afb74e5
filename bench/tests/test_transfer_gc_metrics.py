"""The readers of the program's ``transfer.h2d`` and ``python.gc``
spans: on hand-made timelines, and on a ``p7.fdk`` window recorded on a
TPU v5e (``bench/run.py --trace 1 --keep-trace``)."""

import os
import types

import pytest

import harness
import traces
import transfers
from conftest import BENCH

CHIP_TRACE = os.path.join(BENCH, "tests", "data", "p7_fdk.xplane.pb.gz")
READERS = ("h2d_ms_per_scan", "h2d_idle_ms_per_scan", "gc_ms_per_scan")


def read(metric, run):
    return harness.reader(BENCH, metric).read(run)


def run_of(host, devices=(0,), n_scans=2, window=(0, 100), chip=False):
    # chip 0 idles in (10, 30) and (60, 100); chip 1 never idles; a chip
    # trace has program events, a CPU trace none
    ops = {0: [(0, 10, "a", "jit_a"), (30, 60, "b", "jit_b")],
           1: [(0, 100, "c", "jit_c")]}
    progs = {0: [(0, 10, "jit_a"), (30, 60, "jit_b")],
             1: [(0, 100, "jit_c")]}
    trace = traces.Trace({d: traces.Device(ops[d], progs[d] if chip else [])
                          for d in (0, 1)}, sorted(host))
    return types.SimpleNamespace(trace=trace, window=window,
                                 devices=list(devices), n_scans=n_scans)


def request(t):
    return (t, t + 0.5, transfers.REQUEST)


def landed(t):
    return (t - 0.5, t, transfers.LANDED)


HOST = [(0, 100, "bench.window"), (-10, 50, "filter.chunk"),
        (-10, 3, "transfer.h2d"),      # clipped to (0, 3), chip 0 busy
        (5, 25, "transfer.h2d"),       # idle in (10, 25)
        (90, 120, "transfer.h2d"),     # clipped to (90, 100), all idle
        (40, 45, "python.gc"), (98, 103, "python.gc")]


def test_h2d_sums_spans_clipped_to_the_window():
    assert read("h2d_ms_per_scan", run_of(HOST)) == pytest.approx(
        (3 + 20 + 10) * 1e-6 / 2)


def test_h2d_idle_is_the_gaps_inside_the_spans():
    assert read("h2d_idle_ms_per_scan", run_of(HOST)) == pytest.approx(
        (15 + 10) * 1e-6 / 2)


def test_h2d_idle_is_the_mean_over_the_chips():
    assert read("h2d_idle_ms_per_scan", run_of(HOST, devices=(0, 1))) == \
        pytest.approx((15 + 10) / 2 * 1e-6 / 2)


def test_h2d_idle_counts_overlapping_spans_once():
    host = HOST + [(12, 20, "transfer.h2d")]
    assert read("h2d_idle_ms_per_scan", run_of(host)) == pytest.approx(
        (15 + 10) * 1e-6 / 2)


def test_gc_sums_spans_clipped_to_the_window():
    assert read("gc_ms_per_scan", run_of(HOST)) == pytest.approx(
        (5 + 2) * 1e-6 / 2)


def test_a_lost_upload_span_reads_nothing():
    host = [h for h in HOST if h[2] != "transfer.h2d"]
    assert read("h2d_ms_per_scan", run_of(host)) is None
    assert read("h2d_idle_ms_per_scan", run_of(host)) is None
    outside = host + [(150, 160, "transfer.h2d")]
    assert read("h2d_ms_per_scan", run_of(outside)) is None


def test_no_collection_reads_zero():
    host = [h for h in HOST if h[2] != "python.gc"]
    assert read("gc_ms_per_scan", run_of(host)) == 0.0


def test_a_program_without_upload_spans_reads_nothing():
    # a program whose spans are not profiler annotations: the upload
    # metrics are missing from its line, and no collection reads 0
    host = [(0, 100, "bench.window"), (20, 30, "step.dispatch"),
            request(20), landed(40)]
    assert read("h2d_ms_per_scan", run_of(host, chip=True)) is None
    assert read("h2d_idle_ms_per_scan", run_of(host, chip=True)) is None
    assert read("gc_ms_per_scan", run_of(host, chip=True)) == 0.0


# On a chip trace the upload goes on after its span closes, until the
# transfers requested in the span have landed.
CHIP = [(0, 100, "bench.window"),
        (5, 7, "transfer.h2d"), request(6), landed(25),
        (62, 63, "transfer.h2d"), request(62.5),
        (64, 65, "transfer.h2d"), request(64.5),
        landed(70), landed(80)]        # two uploads queued back to back


def test_h2d_upload_lasts_until_its_transfers_land():
    run = run_of(CHIP, chip=True)
    assert transfers.uploads(run.trace, *run.window) == [(5, 25), (62, 80)]
    assert read("h2d_ms_per_scan", run) == pytest.approx(
        (20 + 18) * 1e-6 / 2)
    # chip 0 idles in (10, 25) and (62, 80)
    assert read("h2d_idle_ms_per_scan", run) == pytest.approx(
        (15 + 18) * 1e-6 / 2)


def test_h2d_transfers_in_flight_before_the_span_hold_it_open():
    # a small transfer requested before the span and still in flight,
    # then the span's own: the upload ends when both have landed
    host = [request(1), (5, 7, "transfer.h2d"), request(6), landed(9),
            landed(30)]
    assert transfers.uploads(run_of(host, chip=True).trace, 0, 100) == \
        [(5, 30)]


def test_h2d_transfer_landed_inside_its_span_ends_with_the_span():
    host = [(5, 10, "transfer.h2d"), request(6), landed(8),
            request(40), landed(50)]
    assert transfers.uploads(run_of(host, chip=True).trace, 0, 100) == \
        [(5, 10)]


def test_h2d_upload_still_in_flight_ends_with_the_window():
    host = [(90, 91, "transfer.h2d"), request(90.5), landed(130)]
    assert transfers.uploads(run_of(host, chip=True).trace, 0, 100) == \
        [(90, 100)]


def test_chip_spans_without_transfer_events_read_nothing():
    # renamed runtime events: the span alone would read only the call
    host = [(0, 100, "bench.window"), (5, 7, "transfer.h2d"), landed(25)]
    assert read("h2d_ms_per_scan", run_of(host, chip=True)) is None
    assert read("h2d_idle_ms_per_scan", run_of(host, chip=True)) is None
    assert read("h2d_ms_per_scan", run_of(host)) == pytest.approx(
        2 * 1e-6 / 2)


def test_an_untraced_run_reads_nothing():
    run = types.SimpleNamespace(trace=None, window=None, devices=[0],
                                n_scans=1)
    for metric in READERS:
        assert read(metric, run) is None


def test_recorded_p7_chip_trace():
    """Four P7 scans in the window, eight 256 MiB chunks each: every
    chunk's upload is one ``transfer.h2d`` inside its ``filter.chunk``,
    a scan's eight uploads drain as one interval, and the readers give
    what the run printed."""
    t = traces.load(CHIP_TRACE)
    window = t.annotation("bench.window")
    lo, hi = window
    host = [h for h in t.host if h[0] >= lo and h[1] <= hi]
    ups = [h for h in host if h[2] == "transfer.h2d"]
    chunks = [h for h in host if h[2] == "filter.chunk"]
    assert len(ups) == len(chunks) == 4 * 8
    assert all(c[0] <= u[0] and u[1] <= c[1] for u, c in zip(ups, chunks))
    assert len(transfers.uploads(t, lo, hi)) == 4
    run = types.SimpleNamespace(trace=t, window=window, devices=[0],
                                n_scans=4)
    assert read("h2d_ms_per_scan", run) == pytest.approx(163.76755575)
    assert read("h2d_idle_ms_per_scan", run) == pytest.approx(78.5000985)
    assert read("gc_ms_per_scan", run) == 0.0
