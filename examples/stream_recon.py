"""Online reconstruction demo: a simulated scanner streams views while
back-projection runs behind it.

Offline entry points need the whole projection set before the first
kernel launches; a scanner produces views one rotation angle at a time.
This demo drives the streaming path (``runtime/service.py
open_stream``): a producer thread plays scanner — one Shepp-Logan
projection every ``frame_dt`` seconds — while each completed view-chunk
is filtered and folded into the volume as it lands. When the last view
arrives, almost all back-projection work is already done: the measured
"tail" (last view -> finished volume) is a small fraction of what the
same reconstruction costs offline, and the volume is BIT-identical to
the offline result.

    PYTHONPATH=src python examples/stream_recon.py
"""

import threading
import time

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import shepp_logan_3d, standard_geometry
from repro.core.forward import forward_project
from repro.runtime.executor import PlanExecutor, ProgramCache
from repro.runtime.planner import plan_reconstruction
from repro.runtime.service import ReconService


def main() -> None:
    geom = standard_geometry(n=32, n_det=48, n_proj=24)
    phantom = shepp_logan_3d(geom.nx, geom.ny, geom.nz)
    projs = np.asarray(forward_project(jnp.asarray(phantom), geom))
    opts = dict(nb=4, proj_batch=4, out="host")

    # offline baseline (also warms the shared program cache and is the
    # parity oracle)
    cache = ProgramCache()
    plan = plan_reconstruction(geom, "algorithm1_mp", ingest="stream",
                               **opts)
    ex = PlanExecutor(geom, plan, cache=cache, pipeline="async")
    _ = np.asarray(ex.reconstruct(jnp.asarray(projs)))   # warm programs
    t0 = time.perf_counter()
    ref = np.asarray(ex.reconstruct(jnp.asarray(projs)))
    offline = time.perf_counter() - t0
    print(f"offline reconstruct: {offline * 1e3:.1f} ms "
          f"({len(plan.chunks)} chunks of {plan.chunk_size} views)")

    # a scanner acquiring slightly slower than we reconstruct — the
    # regime where the whole reconstruction can hide behind the scan
    frame_dt = 1.5 * offline / geom.n_proj
    svc = ReconService(max_inflight=1, cache=cache)
    session = svc.open_stream(geom, **opts)

    def scanner():
        for v in range(geom.n_proj):
            time.sleep(frame_dt)            # ... the gantry rotates ...
            session.push(projs[v], start=v)

    producer = threading.Thread(target=scanner)
    t_scan = time.perf_counter()
    producer.start()
    producer.join()                          # last view just arrived
    t_last = time.perf_counter()
    vol = session.close()                    # tail folds + final flush
    tail = time.perf_counter() - t_last
    rep = session.report

    print(f"scan took {t_last - t_scan:.2f} s "
          f"({frame_dt * 1e3:.1f} ms/view); last view -> volume: "
          f"{tail * 1e3:.1f} ms ({tail / offline:.2f}x the offline wall)")
    print(f"hidden fraction: {rep.hidden_fraction:.2f} of "
          f"{rep.compute_s * 1e3:.1f} ms back-projection overlapped "
          f"the scan")
    print("bit-identical to offline:",
          bool(np.array_equal(np.asarray(vol), ref)))
    svc.close()


if __name__ == "__main__":
    enable_compile_cache()
    main()
