"""Paper Fig. 6 analogue: performance vs batch number nb, and the
N_mem model fit (§3.1.3/§4.3):

    N_mem ~ (4 + 1/nb) * np * nx * ny * nz
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import projection_matrices, standard_geometry, \
    transpose_projections
from repro.core.backproject import bp_subline_symmetry_batch

from .common import emit, gups, time_fn


def run(n: int = 48, n_det: int = 64, n_proj: int = 32):
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.rand(n_proj, geom.nh, geom.nw).astype(np.float32))
    img_t = transpose_projections(img)
    mats = projection_matrices(geom)
    shape = geom.volume_shape_xyz

    import jax

    from repro.launch import hlo_cost

    out = {}
    vol_bytes_once = None
    for nb in (1, 2, 4, 8, 16, 32):
        if n_proj % nb:
            continue
        t = time_fn(lambda nb=nb: bp_subline_symmetry_batch(
            img_t, mats, shape, nb=nb))
        compiled = jax.jit(
            lambda i, m, nb=nb: bp_subline_symmetry_batch(
                i, m, shape, nb=nb)).lower(img_t, mats).compile()
        la = hlo_cost.analyze(compiled.as_text())
        model = 4.0 + 1.0 / nb   # paper's N_mem coefficient
        emit(f"batch/nb={nb}", t * 1e6,
             f"gups={gups(geom, t):.3f} Nmem_coef={model:.3f} "
             f"hlo_bytes={la['bytes']:.3e}")
        out[nb] = (t, la["bytes"])
    return out


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
