"""device_idle_pct: the share of the traced window in which no operation
ran on the chip, averaged over the chips the cell uses."""

LAYER = "device"
MOVES = "gups"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window
    busy = [run.trace.devices[d].busy(lo, hi) for d in run.devices
            if d in run.trace.devices]
    if len(busy) != len(run.devices):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
