"""Traced reconstruction: one service burst under the telemetry layer.

Runs the serving layer exactly like ``serve_recon.py`` — a warmed-up
batched burst plus one streamed session — but inside
``telemetry.tracing(...)``, then shows what the observability layer
produces:

  1. ``recon_trace.json`` — Chrome trace-event JSON. Open it at
     https://ui.perfetto.dev: the service worker, flusher, and stream
     threads are separate lanes; every ``compile`` span is one
     ProgramCache jit miss; every ``step.dispatch`` span names its
     variant, call shape and view count; every host chunk's upload is
     a ``transfer.h2d`` span inside its ``filter.chunk``. Under
     ``jax.profiler.trace`` the same spans are profiler annotations.
  2. The request-ID -> batch-dispatch linkage: each ``submit()`` mints
     a trace ID (returned on the future), and the ``service.dispatch``
     span that executed a k-wide batch lists all k IDs in its args —
     one dispatch span fans back out to every request it served.
  3. The Prometheus text exposition from ``ServiceStats`` — the same
     numbers a scrape endpoint would serve.

    PYTHONPATH=src python examples/trace_recon.py
    # or: make trace
"""

import time

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import shepp_logan_3d, standard_geometry
from repro.core.forward import forward_project
from repro.runtime import telemetry
from repro.runtime.service import ReconService

TRACE_PATH = "recon_trace.json"


def main() -> None:
    geom = standard_geometry(n=24, n_det=32, n_proj=16)
    phantom = jnp.asarray(shepp_logan_3d(geom.nx))
    projs = forward_project(phantom, geom, oversample=2.0)
    opts = dict(variant="algorithm1_mp", nb=4, proj_batch=8)

    with telemetry.tracing(TRACE_PATH):
        with ReconService(max_inflight=2, max_batch=4,
                          max_wait_ms=10.0) as svc:
            svc.warmup([geom], **opts)

            # batched burst: same-bucket requests coalesce into k-wide
            # dispatches; each future carries its minted trace ID
            t0 = time.perf_counter()
            futs = [svc.submit(projs, geom, **opts) for _ in range(6)]
            vols = [f.result() for f in futs]
            wall = time.perf_counter() - t0
            print(f"burst: {len(futs)} requests in {wall:.2f} s")
            for i, f in enumerate(futs):
                print(f"  request {i}: trace_id={f.trace_id}")

            # one streamed session rides along so the trace shows the
            # stream lanes (push instants, fold spans, the tail span)
            session = svc.open_stream(geom, **opts)
            print(f"stream: trace_id={session.trace_id}")
            pa = np.asarray(projs)
            for v in range(geom.n_proj):
                session.push(pa[v], start=v)
            vol = session.close()
            stats = svc.stats()

    # the dispatch spans link each batch back to the requests it served
    print("\nrequest-ID -> batch-dispatch linkage:")
    for e in telemetry.events():
        if e.get("name") == "service.dispatch":
            ids = e["args"].get("trace_ids", [])
            print(f"  dispatch k={e['args'].get('k')} served {ids}")

    n_compiles = sum(1 for e in telemetry.events()
                     if e.get("name") == "compile")
    print(f"\ntrace: {len(telemetry.events())} events "
          f"({n_compiles} compile spans) -> {TRACE_PATH}")
    print("open it at https://ui.perfetto.dev\n")

    print("Prometheus exposition (ServiceStats.export_prometheus):")
    print(stats.export_prometheus())

    assert vols and vol is not None    # keep the results live


if __name__ == "__main__":
    enable_compile_cache()
    main()
