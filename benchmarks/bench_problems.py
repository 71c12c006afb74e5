"""Paper Table 3 analogue: the P1..P10 problem-size matrix, CPU-scaled.

Full P-sizes do not fit a 1-core CPU budget; each P is scaled by 1/8 per
axis (shape RATIOS preserved: detector/volume/projection proportions are
what drive the locality behaviour the paper studies).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs.ct_paper import PROBLEMS
from repro.core import projection_matrices, standard_geometry, \
    transpose_projections
from repro.core.backproject import bp_subline_symmetry_batch

from .common import emit, gups, time_fn

SCALE = 8


def run(scale: int = SCALE, max_problems: int = 6):
    rows = {}
    for prob in PROBLEMS[:max_problems]:
        n = max(8, prob.vol // scale)
        det = max(8, prob.det // scale)
        np_ = max(4, prob.n_proj // scale)
        geom = standard_geometry(n=n, n_det=det, n_proj=np_)
        rng = np.random.RandomState(0)
        img = jnp.asarray(rng.rand(np_, geom.nh, geom.nw)
                          .astype(np.float32))
        img_t = transpose_projections(img)
        mats = projection_matrices(geom)
        nb = min(8, np_)
        t = time_fn(lambda: bp_subline_symmetry_batch(
            img_t, mats, geom.volume_shape_xyz, nb=nb))
        emit(f"problems/{prob.label}(1/{scale})", t * 1e6,
             f"gups={gups(geom, t):.3f} "
             f"updates={geom.voxel_updates():.2e}")
        rows[prob.label] = t
    return rows


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
