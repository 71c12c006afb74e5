"""The work a scan asks for, counted from its shape, not from what an
implementation emits: a later kernel is read against the same work.

``g`` is a geometry as :func:`reference.geometry` returns it.
"""

from __future__ import annotations

# 8 fused multiply-adds per voxel-view update -- the "ct-backproject"
# cost model (model_flops = 8 * vol^3 * n_views) the program applies per
# tile step in its trace annotations, so the capacity model and the
# roofline tell one arithmetic-intensity story.
FLOPS_PER_UPDATE = 8.0
BYTES_PER_SAMPLE = 4      # float32 projections and volume


def updates(g: dict) -> int:
    """Voxel updates of one scan, the paper's GUPS numerator (§2.3):
    ``nx * ny * nz * n_proj``."""
    return g["nx"] * g["ny"] * g["nz"] * g["n_proj"]


def flops(g: dict) -> float:
    """Operations of one scan's back-projection."""
    return FLOPS_PER_UPDATE * updates(g)


def min_bytes(g: dict) -> int:
    """Least bytes one scan's back-projection moves: the filtered
    projections read once and the volume written once."""
    return BYTES_PER_SAMPLE * (g["n_proj"] * g["nh"] * g["nw"]
                               + g["nx"] * g["ny"] * g["nz"])


def least_seconds(g: dict, peak: dict, n_scans: int = 1) -> float:
    """The least time a chip with ``peak`` (an entry of ``peaks.json``)
    could take for ``n_scans`` scans' back-projection: the larger of the
    compute and the memory bound."""
    return n_scans * max(flops(g) / peak["flops_per_s"],
                         min_bytes(g) / peak["bytes_per_s"])


def gups(n_updates: int, seconds: float) -> float:
    """Giga voxel updates per second (paper §2.3)."""
    return n_updates / seconds / 1e9
