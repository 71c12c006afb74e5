"""Iterative-solver benchmark: warm amortized per-iteration wall.

Measures the two claims the solver subsystem (``runtime/solvers.py``)
makes:

  1. **warm iterations compile nothing** — iteration 1 of a solve pays
     every jit compile the loop needs (scan programs, forward programs,
     normalizers); iterations 2..N dispatch cached executables. Emitted
     per method as ``solvers/<method>_iter1`` (first-iteration wall,
     compile included) vs ``solvers/<method>_warm`` (amortized
     per-iteration wall of a warm multi-iteration solve) with the
     warm/iter1 ratio and the ``SolveReport`` compile split
     (``compiles_iter1`` / ``compiles_warm`` — the latter must be 0,
     and the row asserts it).
  2. **bf16 per-iteration wall vs f32** — the ``precision="bf16"``
     planner axis re-keys every program at reduced precision; emitted
     as ``solvers/sart_bf16_warm`` with the bf16/f32 warm ratio.

    PYTHONPATH=src python -m benchmarks.bench_solvers
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import standard_geometry
from repro.core.forward import forward_project
from repro.core.phantom import shepp_logan_3d
from repro.runtime.executor import ProgramCache
from repro.runtime.solvers import SOLVERS, solve

from . import common

#: iterations per timed solve — the amortization window
WARM_ITERS = 4


def _setup(n: int, n_det: int, n_proj: int):
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    phantom = jnp.asarray(shepp_logan_3d(n))
    projs = forward_project(phantom, geom, oversample=1.0)
    return geom, projs


def _solve_kw(method: str, nb: int) -> dict:
    kw = dict(oversample=1.0, nb=nb)
    if method == "os_sart":
        kw["proj_batch"] = 4
    return kw


def run(n: int = 24, n_det: int = 32, n_proj: int = 16, nb: int = 4):
    geom, projs = _setup(n, n_det, n_proj)
    t_f32_warm = {}
    for method in SOLVERS:
        kw = _solve_kw(method, nb)
        cache = ProgramCache()
        t0 = time.perf_counter()
        _, rep1 = solve(projs, geom, method, n_iters=1, cache=cache, **kw)
        t_iter1 = time.perf_counter() - t0
        assert rep1.compiles_warm == 0, (method, rep1)

        def timed():
            return solve(projs, geom, method, n_iters=WARM_ITERS,
                         cache=cache, **kw)[0]
        t_warm = common.time_fn(timed) / WARM_ITERS
        t_f32_warm[method] = t_warm
        common.emit(f"solvers/{method}_iter1", t_iter1 * 1e6,
                    f"compiles={rep1.compiles_iter1}")
        common.emit(f"solvers/{method}_warm", t_warm * 1e6,
                    f"gups={common.gups(geom, t_warm):.3f} "
                    f"vs_iter1={t_warm / t_iter1:.2f}x compiles_warm=0")

    # bf16 axis on the cheapest loop: amortized warm wall vs f32
    cache = ProgramCache()
    kw = dict(_solve_kw("sart", nb), precision="bf16")
    solve(projs, geom, "sart", n_iters=1, cache=cache, **kw)   # compile

    def timed_bf16():
        return solve(projs, geom, "sart", n_iters=WARM_ITERS,
                     cache=cache, **kw)[0]
    t_bf16 = common.time_fn(timed_bf16) / WARM_ITERS
    common.emit("solvers/sart_bf16_warm", t_bf16 * 1e6,
                f"gups={common.gups(geom, t_bf16):.3f} "
                f"vs_f32={t_bf16 / t_f32_warm['sart']:.2f}x")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--n-det", type=int, default=32)
    ap.add_argument("--n-proj", type=int, default=16)
    ap.add_argument("--nb", type=int, default=4)
    args = ap.parse_args(argv)
    run(n=args.n, n_det=args.n_det, n_proj=args.n_proj, nb=args.nb)


if __name__ == "__main__":
    enable_compile_cache()
    main()
