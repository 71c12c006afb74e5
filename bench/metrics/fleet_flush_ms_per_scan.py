"""fleet_flush_ms_per_scan: the fleet's host accumulation per scan, in
milliseconds: the program's ``fleet.flush`` spans (a step's downloads
and host adds under the fleet's one flush lock, so they never overlap)
summed over the window. 0 on one chip, where there is no fleet; nothing
where a fleet's window holds no such span."""

LAYER = "fleet"
MOVES = "gups"
SPAN = "fleet.flush"


def read(run):
    if run.trace is None:
        return None
    if len(run.devices) == 1:
        return 0.0
    lo, hi = run.window
    spans = [(max(s, lo), min(e, hi)) for s, e, n in run.trace.host
             if n == SPAN and e > lo and s < hi]
    if not spans:
        return None
    return 1e-6 * sum(e - s for s, e in spans) / run.n_scans
