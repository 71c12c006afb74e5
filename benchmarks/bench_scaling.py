"""Paper Fig. 9 analogue: scaling with parallel width.

The paper scales OpenMP threads 1..128 on multicore CPUs. This container
has one core, so hardware thread scaling is not measurable; instead we
measure the structural analogue the TPU mapping relies on — work-scaling
across the voxel-line grid (j-block width), which is the unit the Pallas
kernel parallelizes over.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import projection_matrices, standard_geometry, \
    transpose_projections
from repro.core.backproject import bp_subline_symmetry_batch

from .common import emit, time_fn


def run():
    geom = standard_geometry(n=48, n_det=64, n_proj=16)
    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.rand(16, geom.nh, geom.nw).astype(np.float32))
    img_t = transpose_projections(img)
    mats = projection_matrices(geom)

    # work scaling: time vs number of voxel lines processed
    base = None
    for frac in (1, 2, 4):
        nj = geom.ny // frac
        t = time_fn(lambda nj=nj: bp_subline_symmetry_batch(
            img_t, mats, (geom.nx, nj, geom.nz), nb=8))
        if base is None:
            base = t
        emit(f"scaling/lines_1_over_{frac}", t * 1e6,
             f"work_frac={1/frac:.2f} time_frac={t/base:.2f}")


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
