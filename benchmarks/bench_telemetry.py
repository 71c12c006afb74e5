"""Telemetry overhead guard: disabled-mode tracing must be free.

The telemetry layer (``runtime/telemetry.py``) instruments every hot
dispatch path — planner, ProgramCache, PlanExecutor steps, the flusher
thread, service workers. That is only acceptable if the *disabled*
no-op path costs nothing: this bench measures it three ways and
ASSERTS the disabled-mode overhead stays under 2% of the smoke-recon
wall (the hard bound from the tier-1 acceptance criteria):

  1. micro: per-call cost of a disabled ``span()`` enter/exit
     (shared ``_NULL`` singleton — no allocation, no clock read);
  2. bound: (spans one traced recon emits) x (no-op cost) as a
     fraction of the untraced recon wall — the analytic ceiling on
     what disabled telemetry can cost the real path;
  3. direct: untraced warm recon wall, re-measured, vs itself across
     enable/disable toggling (reported, not asserted — smoke-size
     walls are noisy at the sub-percent level).

Enabled-mode overhead (full event recording) is reported alongside so
the trajectory tracks the cost of *running* traced.

    PYTHONPATH=src python -m benchmarks.bench_telemetry
"""

from __future__ import annotations

import time

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import standard_geometry
from repro.runtime import telemetry
from repro.runtime.executor import PlanExecutor, ProgramCache
from repro.runtime.planner import plan_reconstruction

from . import common

# the acceptance bound: disabled-mode telemetry < 2% of recon wall
MAX_DISABLED_OVERHEAD = 0.02

_NOOP_CALLS = 200_000


def _noop_span_cost_s() -> float:
    """Per-call wall of one disabled span enter/exit."""
    assert not telemetry.enabled()
    t0 = time.perf_counter()
    for _ in range(_NOOP_CALLS):
        with telemetry.span("noop", x=1):
            pass
    return (time.perf_counter() - t0) / _NOOP_CALLS


def run(n: int = 24, n_det: int = 32, n_proj: int = 16, nb: int = 4) -> None:
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    rng = np.random.RandomState(0)
    proj = jnp.asarray(
        rng.rand(n_proj, geom.nh, geom.nw).astype(np.float32))
    plan = plan_reconstruction(geom, "algorithm1_mp", nb=nb)
    ex = PlanExecutor(geom, plan, ProgramCache())

    telemetry.disable()

    # 1. the no-op path itself
    t_noop = _noop_span_cost_s()
    common.emit("telemetry/noop_span", t_noop * 1e6,
                f"ns_per_call={t_noop * 1e9:.0f}")

    # 2. untraced warm recon wall (programs compiled by time_fn warmup)
    w_off = common.time_fn(ex.reconstruct, proj, iters=5)
    common.emit("telemetry/recon_untraced", w_off * 1e6, "traced=no")

    # 3. traced warm recon: wall + how many events one run emits
    with telemetry.tracing():
        w_on = common.time_fn(ex.reconstruct, proj, iters=5)
        telemetry.clear()
        ex.reconstruct(proj)
        n_events = len(telemetry.events())
    enabled_frac = (w_on - w_off) / w_off
    common.emit("telemetry/recon_traced", w_on * 1e6,
                f"events_per_recon={n_events} "
                f"enabled_overhead={enabled_frac * 100:+.1f}%")

    # the guard: even if EVERY event of a traced run were a span on the
    # untraced path (it is an upper bound — instants are cheaper), the
    # disabled no-op cost must stay under the 2% acceptance bound
    bound = n_events * t_noop / w_off
    common.emit("telemetry/disabled_overhead_bound", bound * w_off * 1e6,
                f"fraction={bound * 100:.4f}% bound={MAX_DISABLED_OVERHEAD * 100:.0f}%")
    assert bound < MAX_DISABLED_OVERHEAD, (
        f"disabled-mode telemetry overhead bound {bound:.4f} exceeds "
        f"{MAX_DISABLED_OVERHEAD} of smoke-recon wall "
        f"({n_events} events x {t_noop * 1e9:.0f} ns vs {w_off * 1e3:.1f} ms)")


if __name__ == "__main__":
    enable_compile_cache()
    run()
