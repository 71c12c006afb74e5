"""bp_roofline: the back-projection's share of its roofline.

The least time a chip could take for the window's back-projection work
(``work.least_seconds``: the larger of 8 operations per update over the
peak FLOP/s and the least bytes over the peak bandwidth: compute bounds
it at P5, the bytes at P7) over the device time of the back-projection
programs in the trace, summed over the chips.
"""

import work

LAYER = "back-projection kernel"
MOVES = "gups"
# the XLA modules that run the kernel today: the step-major scan
# program (runtime/executor.py ProgramCache.scan_program, a jitted
# ``prog``) and the fleet's step program (core/distributed.py
# make_fleet_bp, a jitted ``one``)
PROGRAMS = ("jit_prog", "jit_one")


def read(run):
    if run.trace is None or not run.peak:
        return None
    lo, hi = run.window
    device_s = sum(run.trace.devices[d].program_time(PROGRAMS, lo, hi)
                   for d in run.devices) * 1e-9
    if device_s <= 0:
        return None
    return 100.0 * work.least_seconds(run.geom, run.peak,
                                      run.n_scans) / device_s
