"""fleet_replicate_ms_per_scan: wall time in which the fleet copies the
filtered views onto its other chips, per scan, in milliseconds: the
union of the program's ``fleet.replicate`` spans (each lasts until its
copy has landed; the chips copy at once, so overlaps count once),
clipped to the window. 0 on one chip, where there is no fleet; nothing
where a fleet's window holds no such span."""

from traces import _clip, _length, _union

LAYER = "fleet"
MOVES = "gups"
SPAN = "fleet.replicate"


def read(run):
    if run.trace is None:
        return None
    if len(run.devices) == 1:
        return 0.0
    lo, hi = run.window
    spans = _clip(_union((s, e) for s, e, n in run.trace.host if n == SPAN),
                  lo, hi)
    if not spans:
        return None
    return 1e-6 * _length(spans) / run.n_scans
