"""h2d_ms_per_scan: wall time in which the program's host views are on
their way to the chip, per scan, in milliseconds: from the start of each
``transfer.h2d`` span until the transfers requested in it have landed
(``bench/transfers.py``), overlaps counted once, clipped to the window.
Reads nothing where the window holds no ``transfer.h2d`` span."""

import transfers

LAYER = "host-to-device transfer"
MOVES = "gups"


def read(run):
    if run.trace is None:
        return None
    ups = transfers.uploads(run.trace, *run.window)
    if ups is None:
        return None
    return 1e-6 * sum(e - s for s, e in ups) / run.n_scans
