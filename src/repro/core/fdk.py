"""End-to-end FDK reconstruction pipeline (filter -> back-project).

This is the paper's application context: FDK calls back-projection once;
iterative algorithms (SART/MLEM/...) call forward+back projection per
iteration — either way back-projection dominates, which is why the paper
optimizes it. Both entry points here are thin façades over the repo's
plan/compile/execute core (``runtime.planner`` / ``runtime.executor``):
the planner owns scheduling and option validation, the shared program
cache owns compilation, and the executor streams projection chunks —
so the untiled, tiled, and iterative paths are one code path with
different plans.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import numpy as np

import jax.numpy as jnp

from .geometry import CTGeometry, projection_matrices


def _build_plan(geom: CTGeometry, variant: str, *, nb: int,
                interpret: Optional[bool],
                tiling, memory_budget: Optional[int],
                proj_batch: Optional[int], out: Optional[str],
                schedule: Optional[str] = None, ingest: str = "offline",
                precision: str = "f32", solver: str = "none",
                tuning=None, **kernel_options):
    """Shared façade-to-planner translation (tiling= conventions)."""
    from repro.runtime.planner import plan_reconstruction

    tiled = tiling is not None or memory_budget is not None
    if tiling == "auto" and memory_budget is None:
        raise ValueError(
            "tiling='auto' needs a memory_budget (bytes) to pick the "
            "tile shape; pass one or give an explicit (ti, tj, tk)")
    tile_shape = None if tiling in (None, "auto") else tuple(tiling)
    if out is None:
        out = "host" if tiled and solver == "none" else "device"
    return plan_reconstruction(
        geom, variant, tile_shape=tile_shape, memory_budget=memory_budget,
        nb=nb, proj_batch=proj_batch, out=out, interpret=interpret,
        schedule=schedule, ingest=ingest, precision=precision,
        solver=solver, tuning=tuning, **kernel_options)


def fdk_reconstruct(projections: jnp.ndarray, geom: CTGeometry,
                    variant: str = "algorithm1_mp", *,
                    nb: int = 8, interpret: Optional[bool] = None,
                    tiling: Union[None, str, Sequence[int]] = None,
                    memory_budget: Optional[int] = None,
                    proj_batch: Optional[int] = None,
                    out: Optional[str] = None,
                    schedule: Optional[str] = None,
                    pipeline: Optional[str] = None,
                    precision: str = "f32",
                    tuning=None,
                    service=None,
                    devices=None,
                    **kernel_options) -> jnp.ndarray:
    """Reconstruct volume (nz, ny, nx) from raw projections (np, nh, nw).

    ``tiling`` routes the back-projection through the tiled schedule:
    pass a (ti, tj, tk) tile shape, or "auto" with a ``memory_budget`` in
    bytes to have the tile shape picked so one tile's working set fits
    the budget. ``None`` (default) keeps the untiled single-call plan.

    ``proj_batch`` streams the projections through in chunks of that
    many views (rounded up to a multiple of ``nb``), with FDK
    pre-weighting + ramp filtering fused into the chunk pipeline —
    neither the volume NOR the filtered projection set need fit in
    memory (the latter strictly under ``schedule="chunk"``).

    ``out`` selects the accumulator placement ("host" | "device");
    the default is "host" for tiled plans (the accumulator never
    materializes on device — that is the point) and "device" for the
    untiled plan. ``schedule`` selects the loop order: "step" (scanned
    device-resident tile accumulators, one host crossing per step),
    "chunk" (the chunk-major streaming loop), or None (default — the
    planner picks "chunk" when a ``memory_budget`` bounds device bytes,
    "step" otherwise). All parameter validation happens in the planner.

    ``pipeline`` selects the host flush discipline ("sync" — the
    default — | "async" — a flusher thread overlaps each unit's
    device->host accumulator copy with the next unit's dispatch, in
    every loop order; bit-identical output). ``variant="auto"`` (or an
    explicit ``tuning=`` cache/path) resolves the whole configuration
    — variant, schedule, pipeline, tile and chunk sizes — from the
    measured autotuner's persisted winners for THIS hardware
    (``runtime.autotune``; a cache miss falls back to exactly the
    heuristics described above, and ``ReconService.warmup(tune=True)``
    or ``runtime.autotune.autotune`` populate the cache). ``service``
    routes the request through a
    :class:`repro.runtime.service.ReconService` instead of a one-shot
    executor: repeated same-shape calls land in the same bucket and
    reuse its cached plan + compiled programs (warm requests never
    retrace), and the call shares the service's bounded FIFO request
    queue with any concurrent submitters. The service's bucket
    executors own the flush discipline (``ReconService(pipeline=)``),
    so combining ``service=`` with an explicit ``pipeline=`` is an
    error rather than a silent override.

    ``devices`` shards the step schedule across a reconstruction fleet
    (``PlanExecutor.execute_fleet``): ``"all"`` uses every local
    device, an int N the first N, a sequence (or a
    ``runtime.executor.FleetConfig``) exactly those. Steps run with
    straggler-aware work stealing and per-step failover; the output
    equals the single-device walk (disjoint step boxes). Defaults
    ``out`` to "host" (the fleet accumulates on host). Device
    placement is owned by a service's buckets (``ReconService
    (devices=)``), so ``service=`` + ``devices=`` is an error.
    """
    from repro.runtime.executor import PlanExecutor, as_fleet_config

    if service is not None:
        if pipeline is not None:
            raise ValueError(
                "pipeline= is owned by the service's bucket executors "
                "(ReconService(pipeline=...)); do not pass both "
                "service= and pipeline=")
        if devices is not None:
            raise ValueError(
                "devices= is owned by the service's bucket executors "
                "(ReconService(devices=...)); do not pass both "
                "service= and devices=")
        return service.reconstruct(
            projections, geom, variant=variant, nb=nb, interpret=interpret,
            tiling=tiling, memory_budget=memory_budget,
            proj_batch=proj_batch, out=out, schedule=schedule,
            precision=precision, tuning=tuning, **kernel_options)
    fleet = as_fleet_config(devices)
    if fleet is not None:
        # the fleet accumulates per-device step outputs into a host
        # volume over the step schedule; default unset knobs to that
        # placement (explicit contrary choices fail fast in the
        # executor's validation)
        out = out or "host"
        schedule = schedule or "step"
    if variant == "auto" or tuning is not None:
        # lookup-only tuned resolution: the config also carries the
        # executor-level pipeline knobs the plan cannot
        from repro.runtime.autotune import as_tuning_cache, resolve_config
        cfg = resolve_config(
            geom, variant, cache=as_tuning_cache(tuning), nb=nb,
            interpret=interpret, tiling=tiling,
            memory_budget=memory_budget, proj_batch=proj_batch, out=out,
            schedule=schedule, precision=precision, **kernel_options)
        if pipeline is None and fleet is None:
            ex = PlanExecutor.from_config(geom, cfg)
        else:                         # explicit override beats the cache
            ex = PlanExecutor(geom, cfg.build_plan(geom),
                              pipeline=cfg.pipeline if pipeline is None
                              else pipeline,
                              pipeline_depth=cfg.pipeline_depth,
                              tuned=cfg, fleet=fleet)
        return ex.reconstruct(projections)
    plan = _build_plan(geom, variant, nb=nb, interpret=interpret,
                       tiling=tiling, memory_budget=memory_budget,
                       proj_batch=proj_batch, out=out, schedule=schedule,
                       precision=precision, **kernel_options)
    return PlanExecutor(
        geom, plan,
        pipeline="sync" if pipeline is None else pipeline,
        fleet=fleet,
    ).reconstruct(projections)


def _vol_to_native(vol_t):
    """(nx, ny, nz) -> (nz, ny, nx) for either host or device arrays."""
    if isinstance(vol_t, np.ndarray):
        return np.transpose(vol_t, (2, 1, 0))
    from . import backproject as bp
    return bp.volume_to_native(vol_t)


def sart_step(vol_zyx: jnp.ndarray, projections: jnp.ndarray,
              geom: CTGeometry, *, relax: float = 0.25,
              variant: str = "algorithm1_mp", nb: int = 8,
              oversample: float = 1.0, interpret: Optional[bool] = None,
              tiling: Union[None, str, Sequence[int]] = None,
              memory_budget: Optional[int] = None,
              proj_batch: Optional[int] = None,
              schedule: Optional[str] = None,
              precision: str = "f32",
              **kernel_options) -> jnp.ndarray:
    """One SART update (demonstrates the paper's iterative-recon use).

    Standard SART (Andersen & Kak):

        x += relax * (1 / BP(1)) * BP( (P - FP(x)) / FP(1_vol) )

    FP(1_vol) are the per-ray intersection lengths (projection-domain
    row sums of the system matrix); BP(1) the voxel-domain column sums.

    Thin façade over ``runtime.solvers`` (``n_iters=1``): repeated
    calls with the same configuration land on the SAME persistent
    :class:`~repro.runtime.solvers.IterativeExecutor`, so the
    normalizers are computed once and iterations 2..N of a caller's
    outer loop dispatch warm — no per-call ``PlanExecutor`` rebuild.
    ``interpret=`` still reaches the Pallas variants and ``tiling=`` /
    ``memory_budget=`` / ``proj_batch=`` keep the bounded per-call
    working set of ``fdk_reconstruct``.
    """
    from repro.runtime.solvers import solver_executor

    # out="device" even when tiled: SART's forward projection needs the
    # volume on device every iteration anyway, so host staging of the
    # BP accumulators would only add two full-volume round-trips. The
    # tiling/proj_batch benefit here is the bounded PER-CALL working set
    # (kernel temporaries), not accumulator placement.
    plan = _build_plan(geom, variant, nb=nb, interpret=interpret,
                       tiling=tiling, memory_budget=memory_budget,
                       proj_batch=proj_batch, out="device",
                       schedule=schedule, precision=precision,
                       solver="sart", **kernel_options)
    ex = solver_executor(geom, plan, oversample=oversample)
    vol, _report = ex.solve(projections, n_iters=1, relax=relax,
                            x0=vol_zyx)
    return vol
