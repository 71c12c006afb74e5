"""Smoke-size perf snapshot: variant ladder + tiled sweep -> JSON (+diff).

Seeds the repo's perf trajectory (BENCH_PR2.json, BENCH_PR3.json, ...):
runs the optimization-ladder timing (``bench_variants``), the
tiled-engine sweep (``bench_tiled``) — which now also times the
step-major vs chunk-major executor schedules on multi-chunk streamed
FDK — the serving-layer cold/warm + pipeline-overlap numbers
(``bench_service``), the bounded-budget autotune smoke
(``bench_autotune`` — heuristic-vs-tuned wall + search cost; the
winners persist in the tuning cache at ``$REPRO_TUNING_CACHE``, which
CI uploads as an artifact), the streaming-ingestion overlap numbers
(``bench_stream`` — last-view-to-volume tail vs offline wall and the
hidden fraction of a simulated scanner run), the iterative-solver
loops (``bench_solvers`` — warm amortized per-iteration wall vs the
compile-heavy first iteration, plus the bf16 precision axis), the
telemetry overhead guard (``bench_telemetry`` — asserts disabled-mode
span overhead stays under 2% of the smoke-recon wall and reports the
enabled-mode cost), and a bigger-size
re-measure of the symmetry
family (the BENCH_PR2 ``symmetry_mp`` 0.48x number was part real
regression — fixed by the affine-fold mirror in core/backproject.py —
and part smoke-size dispatch noise, so the wall claim is re-checked
where arithmetic dominates). Every emitted row is dumped as structured
JSON via ``common.write_json``; ``--diff`` prints per-variant wall/GUPS
deltas against a prior BENCH_*.json and ``--warn-regress`` flags
(without failing — the tier-1 stage is non-gating; ``--strict``, the
nightly CI mode, escalates to a nonzero exit) any wall regression
beyond the given fraction. ``--json auto`` derives the next snapshot
name from the committed BENCH_PR<N>.json sequence
(:func:`next_snapshot_path`) so no caller hardcodes it.

    PYTHONPATH=src python -m benchmarks.bench_smoke \
        --json auto --diff auto --warn-regress 0.25
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import subprocess

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import projection_matrices, standard_geometry, \
    transpose_projections
from repro.core.variants import get_variant

from . import bench_autotune, bench_service, bench_solvers, bench_stream, \
    bench_telemetry, bench_tiled, bench_variants, common

# Smoke sizes: big enough that tiling/batching structure is exercised
# (several tiles, several nb-batches), small enough for a CI stage.
SMOKE = dict(n=24, n_det=32, n_proj=16, nb=4)

# Re-measure sizes for the symmetry family: large enough that kernel
# arithmetic, not per-call dispatch, dominates the wall clock.
BIG = dict(n=48, n_det=64, n_proj=32, nb=8)


def symmetry_recheck(n: int, n_det: int, n_proj: int, nb: int) -> None:
    """Wall-only re-measure of the O3 symmetry family vs share_mp."""
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.rand(n_proj, geom.nh, geom.nw).astype(np.float32))
    img_t = transpose_projections(img)
    mats = projection_matrices(geom)
    shape = geom.volume_shape_xyz
    t_share = common.time_fn(
        lambda: get_variant("share_mp")(img_t, mats, shape))
    common.emit("variants_big/share_mp", t_share * 1e6,
                f"gups={common.gups(geom, t_share):.3f} vs_share=1.00x")
    for name in ("symmetry_mp", "algorithm1_mp"):
        fn = get_variant(name)
        t = common.time_fn(lambda: fn(img_t, mats, shape, nb=nb))
        common.emit(f"variants_big/{name}", t * 1e6,
                    f"gups={common.gups(geom, t):.3f} "
                    f"vs_share={t_share / t:.2f}x")


def next_snapshot_path() -> str:
    """``BENCH_PR<N+1>.json`` where N is the highest COMMITTED snapshot
    number — the ONE place the per-PR snapshot name is derived.

    Both callers (`make bench-smoke` and tests/run_tier1.sh stage 3)
    pass ``--json auto``, so each PR writes the next snapshot without
    either file being edited. Committed names (``git ls-files``) beat a
    directory glob so repeated local runs keep overwriting the same
    not-yet-committed snapshot instead of marching the number forward;
    the glob is the fallback outside a git checkout.
    """
    try:
        listed = subprocess.run(
            ["git", "ls-files", "BENCH_*.json"], capture_output=True,
            text=True, check=True).stdout.split()
    except (OSError, subprocess.CalledProcessError):
        listed = glob.glob("BENCH_*.json")
    ns = [int(m.group(1)) for p in listed
          if (m := re.fullmatch(r"BENCH_PR(\d+)\.json",
                                os.path.basename(p)))]
    return f"BENCH_PR{max(ns, default=0) + 1}.json"


def auto_prior(out_path) -> str | None:
    """Newest committed BENCH_*.json that is not this run's own output
    — the ONE definition of the trajectory-diff base (used by both
    `make bench-smoke` and tests/run_tier1.sh via ``--diff auto``).
    Newest = highest numeric suffix (BENCH_PR10 sorts after BENCH_PR9).
    """
    skip = os.path.abspath(out_path) if out_path else None
    cands = [p for p in glob.glob("BENCH_*.json")
             if os.path.abspath(p) != skip]
    if not cands:
        return None
    return max(cands, key=lambda p: ([int(x) for x in re.findall(r"\d+", p)],
                                     p))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write emitted rows as a perf-trajectory JSON; "
                         "'auto' derives the next committed snapshot "
                         "name (next_snapshot_path -> BENCH_PR<N>.json)")
    ap.add_argument("--diff", metavar="PRIOR_JSON", default=None,
                    help="print per-variant deltas vs a prior "
                         "BENCH_*.json; 'auto' picks the newest one "
                         "that is not --json's output")
    ap.add_argument("--warn-regress", type=float, default=0.25,
                    metavar="FRAC",
                    help="with --diff: warn (never fail) when a row's "
                         "wall time regresses beyond this fraction")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero on any --warn-regress hit "
                         "(reserved for the nightly CI job; the per-PR "
                         "tier-1 stage stays non-gating)")
    ap.add_argument("--n", type=int, default=SMOKE["n"])
    ap.add_argument("--n-det", type=int, default=SMOKE["n_det"])
    ap.add_argument("--n-proj", type=int, default=SMOKE["n_proj"])
    ap.add_argument("--nb", type=int, default=SMOKE["nb"])
    ap.add_argument("--autotune-budget", type=float, default=10.0,
                    metavar="SEC",
                    help="wall-clock budget for the bounded autotune "
                         "smoke (tuning cache honors $REPRO_TUNING_CACHE)")
    args = ap.parse_args(argv)
    if args.json == "auto":
        args.json = next_snapshot_path()

    common.reset_records()
    sizes = dict(n=args.n, n_det=args.n_det, n_proj=args.n_proj, nb=args.nb)
    print("# --- variants (smoke) ---")
    bench_variants.run(**sizes)
    print("# --- tiled (smoke) ---")
    bench_tiled.run(**sizes)
    print("# --- serving layer (smoke) ---")
    bench_service.run(**sizes)
    print("# --- autotuner (bounded-budget smoke) ---")
    bench_autotune.run(**sizes, budget_s=args.autotune_budget)
    print("# --- streaming (simulated scanner) ---")
    bench_stream.run(**sizes)
    print("# --- iterative solvers (warm amortized per-iteration) ---")
    bench_solvers.run(**sizes)
    print("# --- telemetry overhead guard (<2% disabled) ---")
    bench_telemetry.run(**sizes)
    print("# --- symmetry family (realistic size) ---")
    symmetry_recheck(**BIG)
    if args.json:
        # surface the jit-program cache totals of the whole bench run:
        # the step-major executor's claim that interior tiles compile
        # once under the chunk-loop key is auditable from the snapshot
        from repro.runtime.executor import default_program_cache
        common.write_json(args.json, meta={
            "suite": "bench_smoke", **sizes,
            "program_cache": default_program_cache().stats(),
        })
    prior = auto_prior(args.json) if args.diff == "auto" else args.diff
    if args.diff and prior is None:
        print("# --diff auto: no prior BENCH_*.json found, skipping diff")
    elif prior:
        common.print_diff(common.load_json(prior),
                          warn_regress=args.warn_regress,
                          strict=args.strict)


if __name__ == "__main__":
    enable_compile_cache()
    main()
