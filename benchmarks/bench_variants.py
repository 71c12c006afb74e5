"""Paper Fig. 7/8 analogue: the optimization-ladder variants (Table 2).

Two views per variant:
  * wall-clock on this container's 1-core XLA-CPU backend (CAVEAT: the
    backend auto-fuses the baseline's gathers and lowers take_along_axis
    slowly — single-core wall time does NOT reproduce the paper's
    multi-core vectorization story and is reported only for
    completeness);
  * structural HLO cost (loop-aware flops / boundary bytes) — this is
    where the paper's ALGORITHMIC claims live and are checked:
    share+symmetry cut the projection dot-work ~5/6 (paper §3.1.2) and
    batching follows the (4 + 1/nb) memory model (paper §3.1.3).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import projection_matrices, standard_geometry, \
    transpose_projections
from repro.core.variants import VARIANTS, get_variant
from repro.launch import hlo_cost

from .common import emit, gups, time_fn

# variants timed on CPU (pure-JAX ladder; Pallas = interpret-only here)
TIMED = ["baseline", "transpose_mp", "share_mp", "symmetry_mp",
         "subline_mp", "algorithm1_mp"]


def run(n: int = 48, n_det: int = 64, n_proj: int = 32, nb: int = 8):
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.rand(n_proj, geom.nh, geom.nw).astype(np.float32))
    img_t = transpose_projections(img)
    mats = projection_matrices(geom)
    shape = geom.volume_shape_xyz

    results = {}
    base_t = None
    base_flops = None
    for name in TIMED:
        fn = get_variant(name)
        t = time_fn(lambda: fn(img_t, mats, shape, nb=nb))
        compiled = jax.jit(
            lambda i, m: fn(i, m, shape, nb=nb)).lower(
                img_t, mats).compile()
        la = hlo_cost.analyze(compiled.as_text())
        results[name] = (t, la)
        if name == "baseline":
            base_t, base_flops = t, la["flops"]
        emit(f"variants/{name}", t * 1e6,
             f"wall_speedup={base_t / t:.2f}x gups={gups(geom, t):.3f} "
             f"hlo_flops={la['flops']:.3e} "
             f"flops_vs_base={la['flops'] / base_flops:.2f} "
             f"hlo_bytes={la['bytes']:.3e}")
    return results


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
