"""Streaming-ingestion benchmark: a simulated scanner drives online
reconstruction and we measure how much back-projection wall hides
behind acquisition.

The claim under test (ISSUE 8, the iFDK overlap argument): when
projections arrive over a scan of duration T_acq and each view-chunk
folds the moment it completes, the time from the LAST view's arrival to
the finished volume (the "tail") is a small fraction of the offline
reconstruct wall — acquisition time stops being dead time.

Rows:
  * ``stream/offline_wall`` — the same executor's offline
    ``reconstruct`` (the baseline everything is relative to; also the
    bit-parity oracle).
  * ``stream/tail`` — last-view-to-volume time of the streamed run,
    with ``tail_over_offline`` and the executor's ``hidden_fraction``
    (share of busy compute that overlapped acquisition).
  * ``stream/service_tail`` — the same scenario through
    ``ReconService.open_stream`` (the session layer adds the stream
    worker + former hop; its tail must stay in the same regime).

Acceptance (printed OK/FAIL): tail <= 0.3x the offline wall, hidden
fraction >= 0.7 — the ISSUE 8 bars. The simulated frame interval is
``pace``x the offline per-view cost (default 1.5: acquisition slightly
slower than reconstruction, the regime where full overlap is possible;
``--pace`` explores faster/slower scanners).

    PYTHONPATH=src python -m benchmarks.bench_stream [--pace 1.5]
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
    return rng.rand(geom.n_proj, geom.nh, geom.nw).astype(np.float32)


def _feed(push, projs, frame_dt: float) -> None:
    """Deliver one view every ``frame_dt`` seconds (the scanner)."""
    for v in range(projs.shape[0]):
        if frame_dt:
            time.sleep(frame_dt)
        push(projs[v], v)


def run(n: int = 24, n_det: int = 32, n_proj: int = 16, nb: int = 4,
        pace: float = 1.5, trials: int = 3):
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    projs = _projs(geom)
    # the streaming grain: finer chunks than the offline default so the
    # LAST chunk's fold (which can never start before the last view
    # arrives and therefore IS the tail floor) stays a small slice of
    # the total compute — 8 chunks at the smoke size
    snb = max(2, nb // 2)
    plan = plan_reconstruction(geom, "algorithm1_mp", nb=snb,
                               proj_batch=snb, out="host",
                               ingest="stream")
    cache = ProgramCache()
    ex = PlanExecutor(geom, plan, cache=cache, pipeline="async")

    # offline baseline on the SAME executor: warms every chunk program
    # the streamed run reuses, and is the bit-parity oracle
    jprojs = jnp.asarray(projs)
    ref = np.asarray(ex.reconstruct(jprojs))
    offline = common.time_fn(lambda: ex.reconstruct(jprojs))
    common.emit("stream/offline_wall", offline * 1e6,
                f"chunks={len(plan.chunks)} chunk_size={plan.chunk_size}")

    # simulated scanner: one view every pace * (offline/n_proj) seconds;
    # best of ``trials`` runs (single-run tails at ms scale are noisy)
    frame_dt = pace * offline / n_proj
    tail, rep = None, None
    for _ in range(max(1, trials)):
        se = ex.open_stream()
        _feed(lambda v, i: se.push(v, start=i), projs, frame_dt)
        t_last = time.perf_counter()
        vol = se.close()
        t = time.perf_counter() - t_last
        assert np.array_equal(np.asarray(vol), ref), \
            "streamed volume diverged from offline reconstruct"
        if tail is None or t < tail:
            tail, rep = t, se.report
    ratio = tail / offline
    common.emit("stream/tail", tail * 1e6,
                f"tail_over_offline={ratio:.3f}x "
                f"hidden={rep.hidden_fraction:.3f} "
                f"acquire_ms={rep.acquire_s * 1e3:.1f}")
    ok = ratio <= 0.3 and rep.hidden_fraction >= 0.7
    print(f"# stream tail {tail * 1e3:.1f} ms vs offline "
          f"{offline * 1e3:.1f} ms -> {ratio:.3f}x, hidden "
          f"{rep.hidden_fraction:.2f} "
          f"({'OK' if ok else 'FAIL'}: bars 0.3x / 0.7)")

    # the same scanner through the service session layer
    svc = ReconService(max_inflight=1, cache=cache)
    try:
        stail, srep, svol = None, None, None
        for _ in range(max(1, trials)):
            sess = svc.open_stream(geom, nb=snb, proj_batch=snb,
                                   out="host")
            _feed(lambda v, i: sess.push(v, start=i), projs, frame_dt)
            t_last = time.perf_counter()
            svol = sess.close()
            t = time.perf_counter() - t_last
            if stail is None or t < stail:
                stail, srep = t, sess.report
        sref = np.asarray(PlanExecutor(
            geom, next(b for b in svc._buckets.values()
                       if b.plan.ingest == "stream").plan,
            cache=cache).reconstruct(jprojs))
        assert np.array_equal(np.asarray(svol), sref), \
            "service-streamed volume diverged from offline reconstruct"
        common.emit("stream/service_tail", stail * 1e6,
                    f"tail_over_offline={stail / offline:.3f}x "
                    f"hidden={srep.hidden_fraction:.3f}")
        print(f"# service stream tail {stail * 1e3:.1f} ms "
              f"({stail / offline:.3f}x offline), hidden "
              f"{srep.hidden_fraction:.2f}")
    finally:
        svc.close()
    return ratio


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pace", type=float, default=1.5,
                    help="frame interval as a multiple of the offline "
                         "per-view reconstruct cost (default 1.5)")
    args = ap.parse_args(argv)
    common.reset_records()
    run(pace=args.pace)


if __name__ == "__main__":
    enable_compile_cache()
    main()
