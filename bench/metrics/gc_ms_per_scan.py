"""gc_ms_per_scan: Python's cyclic garbage-collection pauses per scan,
in milliseconds: the program's ``python.gc`` spans summed over the
window, 0 where no collection ran in it."""

LAYER = "host runtime"
MOVES = "gups"
SPAN = "python.gc"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window
    return 1e-6 * sum(min(e, hi) - max(s, lo) for s, e, n in run.trace.host
                      if n == SPAN and e > lo and s < hi) / run.n_scans
