"""h2d_idle_ms_per_scan: device idle time inside the uploads that
``h2d_ms_per_scan`` measures, per scan, in milliseconds, the mean over
the chips the cell uses: the part of the upload the chip waits for.
Reads nothing where ``h2d_ms_per_scan`` does."""

import transfers

LAYER = "host-to-device transfer"
MOVES = "gups"


def read(run):
    if run.trace is None:
        return None
    if any(d not in run.trace.devices for d in run.devices):
        return None
    ups = transfers.uploads(run.trace, *run.window)
    if ups is None:
        return None
    idle = [sum((e - s) - run.trace.devices[d].busy(s, e) for s, e in ups)
            for d in run.devices]
    return 1e-6 * sum(idle) / len(idle) / run.n_scans
