"""Serving-layer benchmark: cold vs warm request latency + pipeline overlap.

Measures the two claims the serving layer (``runtime/service.py``) makes:

  1. **warm << cold** — the first request of a shape pays planning + jit
     compilation of every program the plan needs; every later same-shape
     request hits the bucket's cached executor and compiles nothing.
     Emitted as ``service/cold_request`` and ``service/warm_request``
     with the warm/cold ratio (the acceptance bar is < 0.5x; in
     practice compile dominates and the ratio is tiny).
  2. **async overlap** — the ``pipeline="async"`` flusher thread
     overlaps step N's device->host accumulator copy with step N+1's
     scan dispatch. Emitted as ``service/pipeline_sync`` vs
     ``service/pipeline_async`` with the sync/async wall ratio.

``overlap_gain`` alone is MISLEADING at smoke sizes: the per-step flush
is a few hundred KB, so the copy the thread hides is microseconds while
the thread+GIL handoff it adds is not — gains < 1 here say nothing
about clinical sizes. Both pipeline rows therefore also report
``flush_kb_per_step`` (the modeled device->host bytes each step emits —
the quantity the overlap actually hides), and :func:`run_clinical`
re-measures the pair at a clinical-scale volume where each step flushes
hundreds of KB to MBs (opt-in: ``--clinical`` here, `pytest -m slow` in
tier-1's slow lane — not smoke material).

A mixed-shape burst at the end exercises bucketing under FIFO traffic
and prints the :class:`ServiceStats` snapshot.

  3. **cross-request batching** — a same-bucket burst of k requests
     through ``max_batch=k`` forms ONE ``execute_batch`` dispatch
     stream instead of k dispatch sequences. Emitted as
     ``service/batched_burst_k{1,2,4,8}`` with the AMORTIZED us/request
     (wall / k) and realized occupancy; the k=1 row is the unbatched
     baseline on the same bucket, and the acceptance bar is k=8
     amortized strictly below it.

    PYTHONPATH=src python -m benchmarks.bench_service [--clinical]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import standard_geometry
from repro.runtime.executor import PlanExecutor, ProgramCache
from repro.runtime.planner import plan_reconstruction
from repro.runtime.service import ReconService

from . import common


def _projs(geom, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(
        rng.rand(geom.n_proj, geom.nh, geom.nw).astype(np.float32))


def flush_bytes_per_step(plan) -> float:
    """Modeled device->host bytes ONE step's flush emits (float32 tile
    writes) — the traffic the async pipeline can actually hide."""
    total = 4 * sum(s.ni * s.nj * sum(w.nk for w in s.writes)
                    for s in plan.steps)
    return total / max(1, len(plan.steps))


def _pipeline_pair(geom, projs, plan, suffix: str = ""):
    """Time sync vs async on one warmed plan; emit both rows with the
    flush-bytes context that makes the ratio interpretable."""
    cache = ProgramCache()
    walls = {}
    for pipeline in ("sync", "async"):
        ex = PlanExecutor(geom, plan, cache=cache, pipeline=pipeline)
        walls[pipeline] = common.time_fn(lambda: ex.reconstruct(projs))
    gain = walls["sync"] / walls["async"]
    kb = flush_bytes_per_step(plan) / 1024
    common.emit(f"service/pipeline_sync{suffix}", walls["sync"] * 1e6,
                f"steps={len(plan.steps)} flush_kb_per_step={kb:.1f}")
    common.emit(f"service/pipeline_async{suffix}", walls["async"] * 1e6,
                f"overlap_gain={gain:.2f}x flush_kb_per_step={kb:.1f}")
    return gain, kb


def run(n: int = 24, n_det: int = 32, n_proj: int = 16, nb: int = 4):
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    projs = _projs(geom)
    # several (i, j)-tiles + streamed chunks: the shape class a serving
    # deployment buckets on, and enough steps for the flush pipeline
    opts = dict(variant="algorithm1_mp", nb=nb,
                tiling=(n // 2, n // 2, n), proj_batch=max(nb, n_proj // 2))

    # ---- cold vs warm through the service --------------------------------
    svc = ReconService(max_inflight=1, cache=ProgramCache())
    t0 = time.perf_counter()
    svc.reconstruct(projs, geom, **opts)        # pays plan + all compiles
    cold = time.perf_counter() - t0
    warm = common.time_fn(lambda: svc.reconstruct(projs, geom, **opts))
    common.emit("service/cold_request", cold * 1e6,
                f"programs={svc.stats().cache['programs']}")
    common.emit("service/warm_request", warm * 1e6,
                f"warm_over_cold={warm / cold:.3f}x")
    ok = warm < 0.5 * cold
    print(f"# warm {warm * 1e3:.1f} ms vs cold {cold * 1e3:.1f} ms -> "
          f"{warm / cold:.3f}x ({'OK' if ok else 'FAIL'}: bar 0.5x)")
    svc.close()

    # ---- pipeline overlap: sync vs async flush on one warmed plan --------
    plan = plan_reconstruction(geom, "algorithm1_mp", nb=nb,
                               tile_shape=(n // 2, n // 2, n),
                               proj_batch=max(nb, n_proj // 2), out="host")
    gain, kb = _pipeline_pair(geom, projs, plan)
    print(f"# overlap_gain {gain:.2f}x at {kb:.1f} KB/step flush — "
          f"smoke-size flushes are µs; see pipeline_*_clinical "
          f"(--clinical / pytest -m slow) for the number that matters")

    # ---- mixed-shape FIFO burst ------------------------------------------
    geom_b = standard_geometry(n=max(8, n // 2), n_det=max(8, n_det // 2),
                               n_proj=n_proj)
    projs_b = _projs(geom_b, seed=1)
    svc = ReconService(max_inflight=2, cache=ProgramCache())
    svc.warmup([geom, geom_b], **opts)
    t0 = time.perf_counter()
    futs = []
    for i in range(6):
        g, p = ((geom, projs) if i % 2 == 0 else (geom_b, projs_b))
        futs.append(svc.submit(p, g, **opts))
    for f in futs:
        f.result()
    burst = time.perf_counter() - t0
    stats = svc.stats()
    common.emit("service/mixed_burst6", burst * 1e6,
                f"buckets={len(stats.buckets)} "
                f"hit_rate={stats.hit_rate:.2f}")
    print(f"# {stats}")
    svc.close()

    # ---- cross-request batching: amortized us/request vs k ---------------
    batched_burst(geom, projs, opts)


def batched_burst(geom, projs, opts, ks=(1, 2, 4, 8), repeats: int = 3):
    """Amortized per-request cost of a k-deep same-bucket burst.

    One service per k (its ``max_batch`` IS k), warmed so no compile
    lands in the timed region; the burst is submitted in one go, so the
    BatchFormer coalesces it without waiting (``max_wait_ms=0`` —
    occupancy comes from queue depth alone, the serving steady state
    under load). Median of ``repeats`` bursts, amortized = wall / k.
    The k=1 service is the unbatched baseline on the same bucket.
    """
    amortized = {}
    for k in ks:
        svc = ReconService(max_inflight=1, max_batch=k,
                           cache=ProgramCache())
        svc.warmup([geom], **opts)
        svc.reconstruct(projs, geom, **opts)     # absorb first-call costs
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            futs = [svc.submit(projs, geom, **opts) for _ in range(k)]
            for f in futs:
                f.result()
            walls.append(time.perf_counter() - t0)
        walls.sort()
        wall = walls[len(walls) // 2]
        stats = svc.stats()
        occ = stats.buckets[0].mean_occupancy
        amortized[k] = wall / k * 1e6
        common.emit(f"service/batched_burst_k{k}", amortized[k],
                    f"amortized_us_per_request occupancy={occ} "
                    f"dispatches={stats.buckets[0].dispatches}")
        svc.close()
    gain = amortized[ks[0]] / amortized[ks[-1]]
    ok = amortized[ks[-1]] < amortized[ks[0]]
    print(f"# batched burst: k={ks[-1]} amortized "
          f"{amortized[ks[-1]]:.0f} us/req vs unbatched "
          f"{amortized[ks[0]]:.0f} us/req -> {gain:.2f}x "
          f"({'OK' if ok else 'FAIL'}: bar = strictly below unbatched)")
    return amortized


def run_clinical(n: int = 96, n_det: int = 128, n_proj: int = 48,
                 nb: int = 8) -> float:
    """Clinical-scale sync-vs-async overlap (the satellite the smoke
    number cannot answer): per-step flushes here are MBs, so the
    flusher thread hides real copy time instead of µs. Returns the
    overlap gain. Minutes of compile+run — slow lane only."""
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    projs = _projs(geom)
    plan = plan_reconstruction(geom, "algorithm1_mp", nb=nb,
                               tile_shape=(n // 2, n // 2, n),
                               proj_batch=max(nb, n_proj // 4), out="host")
    gain, kb = _pipeline_pair(geom, projs, plan, suffix="_clinical")
    print(f"# clinical overlap_gain {gain:.2f}x at {kb:.1f} KB/step")
    return gain


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clinical", action="store_true",
                    help="also run the clinical-size overlap measurement "
                         "(minutes; slow lane)")
    args = ap.parse_args(argv)
    common.reset_records()
    run()
    if args.clinical:
        print("# --- clinical size ---")
        run_clinical()


if __name__ == "__main__":
    enable_compile_cache()
    main()
