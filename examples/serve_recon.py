"""Reconstruction as a service: mixed-shape requests through ReconService.

Drives the serving layer (`runtime/service.py`) the way a deployment
would: warm up the shape buckets a scanner fleet will send, then submit
a burst of mixed-shape requests and watch every warm request reuse its
bucket's cached plan + compiled programs (zero retracing) while the
async step pipeline overlaps each tile step's device->host flush with
the next step's scan dispatch. With ``max_batch``/``max_wait_ms`` set,
the BatchFormer additionally coalesces queued same-bucket requests
into ONE batched dispatch stream (mixed buckets never cross-batch) —
the per-bucket occupancy / amortized-cost stats at the end show the
batching in action.

    PYTHONPATH=src python examples/serve_recon.py
    # or with the process-level preset (tcmalloc, quiet logs):
    make serve
"""

import time

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import fdk_reconstruct, shepp_logan_3d, standard_geometry
from repro.core.forward import forward_project
from repro.runtime.service import ReconService


def main():
    # two scanner shape classes: a full-res protocol and a preview one
    geom_a = standard_geometry(n=32, n_det=48, n_proj=40)
    geom_b = standard_geometry(n=16, n_det=24, n_proj=40)
    opts = dict(variant="algorithm1_mp", nb=8, tiling=(16, 16, 32),
                proj_batch=16)

    projections = {}
    for name, geom in (("A", geom_a), ("B", geom_b)):
        phantom = jnp.asarray(shepp_logan_3d(geom.nx))
        projections[name] = forward_project(phantom, geom, oversample=2.0)

    # max_batch: up to 4 same-bucket requests share one dispatch
    # stream; max_wait_ms: a partial batch may hold the queue head up
    # to 5 ms for late same-bucket peers (deadline/priority aware)
    with ReconService(max_inflight=2, max_batch=4, max_wait_ms=5.0) as svc:
        # 1. warmup: pay every compile before the first request lands
        t0 = time.perf_counter()
        svc.warmup([geom_a, geom_b], **opts)
        stats = svc.stats()
        print(f"warmup: {len(stats.buckets)} buckets, "
              f"{stats.cache['programs']} cached programs "
              f"in {time.perf_counter() - t0:.2f} s")

        # 2. a FIFO burst of 8 mixed-shape requests (A B A B ...)
        t0 = time.perf_counter()
        futs = [svc.submit(projections["A" if i % 2 == 0 else "B"],
                           geom_a if i % 2 == 0 else geom_b, **opts)
                for i in range(8)]
        vols = [f.result() for f in futs]
        wall = time.perf_counter() - t0
        print(f"burst: 8 requests in {wall:.2f} s "
              f"({wall / 8 * 1e3:.0f} ms/request warm)")

        # 3. warm requests are exact vs the one-shot façade, and the
        #    façade itself can route through the service (service=)
        ref = fdk_reconstruct(projections["A"], geom_a, **opts)
        via = fdk_reconstruct(projections["A"], geom_a, service=svc, **opts)
        err = float(np.max(np.abs(np.asarray(vols[0]) - np.asarray(ref))))
        print(f"service-vs-façade max|diff|: {err:.2e} "
              f"({'OK' if err < 1e-5 else 'FAIL'}); "
              f"fdk_reconstruct(service=...) matches: "
              f"{np.allclose(np.asarray(via), np.asarray(ref), atol=1e-5)}")

        # 4. the snapshot a dashboard would scrape — including batch
        #    occupancy (requests per dispatch; mixed buckets batch
        #    independently) and the amortized per-request cost
        stats = svc.stats()
        print(f"stats: requests={stats.requests} "
              f"bucket hit-rate={stats.hit_rate:.2f} "
              f"dispatches={stats.dispatches} "
              f"occupancy={stats.mean_occupancy} "
              f"cache={stats.cache}")
        for b in stats.buckets:
            print(f"  bucket {b.variant} vol={b.vol_shape_xyz} "
                  f"np={b.n_proj}: requests={b.requests} hits={b.hits} "
                  f"programs_built={b.programs_built} "
                  f"max_batch={b.max_batch} "
                  f"dispatches={b.dispatches} "
                  f"occupancy={b.mean_occupancy} "
                  f"amortized_us/req={b.amortized_us_per_request}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
