"""The reduction from a profiler trace to busy time, idle gaps and
program time: on hand-made timelines, and on a trace of a ``p5.fdk``
window recorded on a TPU v5e."""

import os

import pytest

import traces
from conftest import BENCH

CHIP_TRACE = os.path.join(BENCH, "tests", "data", "p5_fdk.xplane.pb.gz")


def device():
    # two programs; the second holds a loop op enclosing two ops
    ops = [(0, 10, "fusion.1", "jit_a"), (20, 60, "while", "jit_b"),
           (20, 30, "kernel", "jit_b"), (35, 60, "add", "jit_b")]
    return traces.Device(ops, [(0, 12, "jit_a"), (18, 60, "jit_b")])


def test_busy_is_the_union_of_operations():
    d = device()
    assert d.busy(0, 100) == 10 + 40
    assert d.busy(5, 25) == 5 + 5
    assert d.gaps(0, 100) == [(10, 20), (60, 100)]


def test_program_time_prefers_the_program_line():
    d = device()
    assert d.program_time(["jit_b"], 0, 100) == 42
    assert d.program_time(["jit_a", "jit_b"], 0, 15) == 12
    assert traces.Device(d.ops, []).program_time(["jit_b"], 0, 100) == 40


def test_top_ops_count_leaves_and_name_their_program():
    t = traces.Trace({0: device()}, [])
    top = dict(traces.top_ops(t, [0], 0, 100))
    assert top == pytest.approx({"jit_b/add": 25e-9, "jit_a/fusion.1": 10e-9,
                                 "jit_b/kernel": 10e-9})
    last = traces.top_ops(t, [0], 0, 100, n=2)[-1]
    assert last[0] == "other" and last[1] == pytest.approx(20e-9)


def test_gaps_take_the_innermost_host_span():
    t = traces.Trace({0: device()}, [])
    spans = [(0, 100, "bench.request"), (55, 90, "bench.to_host")]
    assert traces.named_gaps(t, 0, 0, 100, spans) == [
        ("bench.to_host", 40e-9), ("bench.request", 10e-9)]


def test_program_names_drop_the_run_id():
    assert traces.program_name("jit_prog(123)") == "jit_prog"
    assert traces.program_name("jit_fdk_filter_chunk") == \
        "jit_fdk_filter_chunk"


@pytest.mark.skipif(not os.path.exists(CHIP_TRACE),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    t = traces.load(CHIP_TRACE)
    assert 0 in t.devices
    lo, hi = t.annotation("bench.window")
    d = t.devices[0]
    busy = d.busy(lo, hi)
    assert 0 < busy <= hi - lo
    bp = d.program_time(["jit_prog"], lo, hi)
    filt = d.program_time(["jit_fdk_filter_chunk"], lo, hi)
    assert 0 < filt < bp <= busy
