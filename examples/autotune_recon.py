"""Autotuned reconstruction demo: measure once, serve forever.

The paper's performance-portability claim means the best (variant, loop
order, blocking, pipeline) choice differs per machine. This demo shows
the repo's measured answer (``runtime/autotune.py``):

  1. a ``ReconService`` warms up with ``tune=True`` — the autotuner
     times candidate configurations on THIS machine (bounded budget)
     and persists the winner in the tuning cache;
  2. requests with ``variant="auto"`` resolve the tuned config with a
     microsecond cache lookup — including from a brand-new process;
  3. re-running this script demonstrates the steady state: the warmup
     is a cache hit with ZERO re-measurement.

    PYTHONPATH=src python examples/autotune_recon.py
"""

import os
import time

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import fdk_reconstruct, shepp_logan_3d, standard_geometry
from repro.core.forward import forward_project
from repro.runtime.autotune import TuningCache
from repro.runtime.service import ReconService


def main() -> None:
    cache_path = os.environ.get("REPRO_TUNING_CACHE",
                                "/tmp/repro_demo_tuning.json")
    tuning = TuningCache(cache_path)
    geom = standard_geometry(n=32, n_det=48, n_proj=24)
    phantom = shepp_logan_3d(geom.nx, geom.ny, geom.nz)
    projs = forward_project(jnp.asarray(phantom), geom)
    opts = dict(nb=4, tiling=(16, 16, 32), proj_batch=8)

    print(f"tuning cache: {cache_path} "
          f"({len(tuning)} entries before warmup)")

    # 1. tune-at-warmup: measured search on a miss, pure lookup on a hit
    svc = ReconService(max_inflight=2, tuning=tuning)
    t0 = time.perf_counter()
    stats = svc.warmup([geom], tune=True, tune_budget_s=15.0,
                       variant="auto", **opts)
    bucket = stats.buckets[0]
    print(f"warmup(tune=True) took {time.perf_counter() - t0:.1f}s -> "
          f"bucket source={bucket.source} variant={bucket.variant} "
          f"schedule={bucket.schedule} pipeline={bucket.pipeline}")

    # 2. tuned traffic: requests join the tuned bucket
    for _ in range(4):
        vol = svc.reconstruct(projs, geom, variant="auto", tuning=tuning,
                              **opts)
    stats = svc.stats()
    print(f"served {stats.requests} requests "
          f"(p50={stats.p50_ms}ms p99={stats.p99_ms}ms); "
          f"volume range [{float(np.min(vol)):.3f}, "
          f"{float(np.max(vol)):.3f}]")
    svc.close()

    # 3. the façade resolves the same winner from the persisted file —
    #    this is what a fresh process does
    t0 = time.perf_counter()
    fdk_reconstruct(projs, geom, variant="auto", tuning=cache_path, **opts)
    print(f"facade variant='auto' warm request: "
          f"{(time.perf_counter() - t0) * 1e3:.1f} ms "
          f"(config resolved by cache lookup, no measurement)")
    print(f"re-run this script to see warmup(tune=True) hit the cache "
          f"with zero re-measurement ({len(tuning)} entries persisted)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
