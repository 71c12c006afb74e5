"""gups: voxel updates of every scan completed in the window over the
window's wall time (host clock), in billions per second -- the paper's
measure (§2.3)."""

import work


def read(run):
    return work.gups(run.updates, run.window_s)
