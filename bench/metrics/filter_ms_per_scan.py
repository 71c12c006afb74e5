"""filter_ms_per_scan: device time of the FDK filter (cosine
pre-weighting and ramp) per scan, in milliseconds."""

LAYER = "FDK filter"
MOVES = "gups"
# core/filtering.py fdk_filter_chunk, jitted under its own name
PROGRAMS = ("jit_fdk_filter_chunk",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window
    device_s = sum(run.trace.devices[d].program_time(PROGRAMS, lo, hi)
                   for d in run.devices) * 1e-9
    if device_s <= 0:
        return None
    return 1e3 * device_s / run.n_scans
