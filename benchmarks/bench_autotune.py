"""Autotuner benchmark: heuristic-vs-tuned wall time + search cost.

Measures the claim the autotuning subsystem (``runtime/autotune.py``)
makes — that a MEASURED per-hardware configuration beats (or at worst
matches) the planner's static heuristics — and records it into the BENCH
trajectory so the tuned/heuristic ratio is tracked per PR like every
other perf number:

  * ``autotune/heuristic``  — wall time of the heuristic config (what
    every façade runs without tuning), measured through the same
    harness the tuner uses;
  * ``autotune/tuned``      — wall time of the winning config, with the
    chosen knobs (variant/schedule/pipeline) in the derived string and
    ``speedup`` = heuristic/tuned (>= ~1.0 by construction: the
    heuristic config is always a candidate, so the tuner can only lose
    to measurement noise);
  * ``autotune/search``     — wall clock of the bounded search itself +
    how many candidates it measured (the one-time cost a deployment
    pays per hardware x request shape);
  * ``autotune/cache_resolve`` — lookup-only re-resolution against the
    persisted cache (the steady-state cost: planning stays µs).

The wide (variant="auto") space is searched so the trajectory reflects
real cross-variant portability, restricted to the pure-JAX ladder by
default so the smoke stays CI-sized (Pallas interpret timings belong to
the slow tier).

    PYTHONPATH=src python -m benchmarks.bench_autotune --budget 12
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import standard_geometry
from repro.runtime.autotune import (TuningCache, autotune,
                                    default_tuning_cache, resolve_config)
from repro.runtime.executor import PlanExecutor, ProgramCache

from . import common

# smoke-sized wide search: the mp ladder's realistic contenders (the
# Pallas interpreter is orders slower on CPU CI — measuring it here
# would burn the whole budget on foregone conclusions)
SMOKE_VARIANTS = ("algorithm1_mp", "symmetry_mp", "subline_batch_mp",
                  "share_mp")


def run(n: int = 24, n_det: int = 32, n_proj: int = 16, nb: int = 4,
        budget_s: float = 12.0, cache: TuningCache | None = None,
        variants=SMOKE_VARIANTS) -> None:
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    rng = np.random.RandomState(0)
    projs = jnp.asarray(
        rng.rand(geom.n_proj, geom.nh, geom.nw).astype(np.float32))
    opts = dict(nb=nb, tiling=(n // 2, n // 2, n),
                proj_batch=max(nb, n_proj // 2))
    tcache = cache if cache is not None else default_tuning_cache()
    pcache = ProgramCache()

    # ---- bounded wide search (force: this IS the trajectory number) ----
    t0 = time.perf_counter()
    cfg = autotune(geom, "auto", **opts, budget_s=budget_s, iters=3,
                   cache=tcache, force=True, projections=projs,
                   program_cache=pcache, variants=variants)
    search = time.perf_counter() - t0
    common.emit("autotune/heuristic", cfg.baseline_us,
                "variant=algorithm1_mp source=planner")
    common.emit("autotune/tuned", cfg.wall_us,
                f"variant={cfg.variant} schedule={cfg.schedule} "
                f"pipeline={cfg.pipeline} speedup={cfg.speedup:.2f}x")
    common.emit("autotune/search", search * 1e6,
                f"trials={cfg.trials} budget_s={budget_s}")
    print(f"# tuned {cfg.variant}/{cfg.schedule}/{cfg.pipeline} "
          f"{cfg.wall_us:.0f}us vs heuristic {cfg.baseline_us:.0f}us "
          f"({cfg.speedup:.2f}x) after {cfg.trials} trials; "
          f"cache -> {tcache.path}")

    # ---- steady state: lookup-only resolution off the persisted file ----
    t0 = time.perf_counter()
    resolved = resolve_config(geom, "auto", cache=tcache, **opts)
    resolve_us = (time.perf_counter() - t0) * 1e6
    common.emit("autotune/cache_resolve", resolve_us,
                f"source={resolved.source} variant={resolved.variant}")
    assert resolved.source == "cache", resolved.source

    # sanity: the resolved winner actually runs (warm programs from the
    # search double as the deployment warmup)
    ex = PlanExecutor.from_config(geom, resolved, cache=pcache)
    ex.reconstruct(projs)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", type=float, default=12.0,
                    help="search wall-clock budget in seconds")
    ap.add_argument("--cache", default=None,
                    help="tuning-cache path (default: $REPRO_TUNING_CACHE "
                         "or ~/.cache/repro/tuning.json)")
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--n-det", type=int, default=32)
    ap.add_argument("--n-proj", type=int, default=16)
    ap.add_argument("--nb", type=int, default=4)
    args = ap.parse_args(argv)
    common.reset_records()
    run(n=args.n, n_det=args.n_det, n_proj=args.n_proj, nb=args.nb,
        budget_s=args.budget,
        cache=TuningCache(args.cache) if args.cache else None)


if __name__ == "__main__":
    enable_compile_cache()
    main()
