"""Quickstart: reconstruct a Shepp-Logan phantom with the paper's
optimized back-projection, and verify against the RTK-style baseline.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import (
    fdk_reconstruct, shepp_logan_3d, standard_geometry,
)
from repro.core.forward import forward_project


def main() -> int:
    # 1. a CPU-friendly cone-beam geometry (RabbitCT-flavoured)
    geom = standard_geometry(n=32, n_det=48, n_proj=60)
    print(f"geometry: {geom.nw}x{geom.nh}x{geom.n_proj} -> "
          f"{geom.nx}^3, magnification {geom.magnification:.2f}")

    # 2. synthesize projections from a phantom (paper §4.2 protocol)
    phantom = jnp.asarray(shepp_logan_3d(geom.nx))
    projections = forward_project(phantom, geom, oversample=2.0)
    print(f"projections: {projections.shape}, "
          f"max {float(projections.max()):.1f}")

    # 3. reconstruct with the paper's Algorithm 1 (subline+symmetry+batch)
    recon = fdk_reconstruct(projections, geom, variant="algorithm1_mp",
                            nb=12)

    # 4. validate against the RTK-style baseline (paper bar: RMSE < 1e-5)
    baseline = fdk_reconstruct(projections, geom, variant="baseline")
    scale = float(jnp.abs(baseline).max())
    rmse = float(jnp.sqrt(jnp.mean((recon - baseline) ** 2))) / scale
    print(f"variant-vs-baseline relative RMSE: {rmse:.2e} "
          f"({'OK' if rmse < 1e-5 else 'FAIL'})")

    # 5. and against ground truth (interior, cone-beam artifacts excluded)
    n = geom.nx
    sl = slice(n // 4, 3 * n // 4)
    ph = np.asarray(phantom)[sl, sl, sl]
    rc = np.asarray(recon)[sl, sl, sl]
    corr = np.corrcoef(ph.ravel(), rc.ravel())[0, 1]
    print(f"interior corr vs phantom: {corr:.3f}; "
          f"mean {rc.mean():.3f} vs {ph.mean():.3f}")

    # 6. same reconstruction through the Pallas TPU kernel (interpreted
    # on CPU, Mosaic-compiled on TPU)
    recon_pl = fdk_reconstruct(projections, geom, variant="subline_pl")
    rmse_pl = float(jnp.sqrt(jnp.mean((recon_pl - baseline) ** 2))) / scale
    print(f"pallas-kernel relative RMSE: {rmse_pl:.2e} "
          f"({'OK' if rmse_pl < 1e-5 else 'FAIL'})")
    return 0 if max(rmse, rmse_pl) < 1e-5 else 1


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
