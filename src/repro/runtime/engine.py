"""Tiled streaming reconstruction — the plan/compile/execute façade.

Architecture (docs/ARCHITECTURE.md)
-----------------------------------
Every reconstruction entry point in this repo — untiled
``fdk_reconstruct``, this tiled engine, ``sart_step``, and the
reconstruction fleet — is a thin façade over the same three-stage core:

  1. **plan** — ``runtime.planner.plan_reconstruction`` builds a pure
     :class:`~repro.runtime.planner.ReconPlan`: the (i, j)-tile x Z-slab
     schedule (mirror-paired for O3 symmetry variants, depth-bounded
     plain slabs otherwise), per-step variant resolution against the
     declarative ``KernelSpec`` registry (``core.variants.REGISTRY``),
     matrix-translation offsets, the projection-chunk schedule, and ALL
     option validation.
  2. **compile** — ``runtime.executor.ProgramCache.program`` maps
     ``(variant, call_shape, nb, dtype, interpret, options, grid, rb)``
     keys to jitted programs that take the step's box origin as a call
     argument. Interior tiles share shapes, so a plan with hundreds of
     steps compiles a handful of programs; repeated ``reconstruct``
     calls hit the shared cache and never retrace.
  3. **execute** — ``runtime.executor.PlanExecutor`` walks the plan:
     projections stream through in chunks with FDK pre-weighting + ramp
     filtering fused INTO the chunk loop (filtered projections are never
     materialized whole), and host placement is double-buffered so the
     device->host copy of tile ``n`` overlaps tile ``n+1``'s compute.
     Across devices the same step programs run through
     ``PlanExecutor.execute_fleet`` (``fdk_reconstruct(devices=)``).

Why tiles (unchanged from PR 1)
-------------------------------
The pure-JAX ladder materializes full ``(nx, ny, nz)`` temporaries, so
nothing above toy sizes fits in device memory. The paper's locality
discipline (§3.1) applied at volume granularity — (i, j)-tiles x Z-slabs
with *translated* projection matrices (``core.tiling``) — gives every
registered variant an O(tile) working set (the blocking of Treibig et
al., arXiv:1104.5243, composed with the iFDK slab scale-out,
arXiv:1909.02724). The O3 detector-row symmetry pairs voxel ``k`` with
``nz-1-k`` about the FULL volume's Z midplane, so symmetry variants run
on mirror-paired slab calls of virtual depth ``2*bk`` (both slabs filled
by one call — the flop saving survives tiling) and fall back to their
``KernelSpec.slab_safe_fallback`` on non-pairable slabs.

Usage
-----
    from repro.runtime.engine import TiledReconstructor

    eng = TiledReconstructor(geom, variant="algorithm1_mp",
                             tile_shape=(64, 64, geom.nz), nb=8,
                             proj_batch=32)       # stream 32-proj chunks
    vol = eng.reconstruct(projections)            # filtered FDK, (nz,ny,nx)

    eng.recon_plan        # the ReconPlan (steps, chunks, program keys)
    eng.cache_stats()     # jit-program cache hits/misses

    # or pick the tile shape from a byte budget:
    eng = TiledReconstructor(geom, memory_budget=64 << 20)

    # or via the pipeline entry point:
    from repro.core import fdk_reconstruct
    vol = fdk_reconstruct(projections, geom, tiling=(64, 64, geom.nz),
                          proj_batch=32)

    # scale-out: the same tile steps spread over every local device
    vol = fdk_reconstruct(projections, geom, tiling=(64, 64, geom.nz),
                          devices="all")
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.core.geometry import CTGeometry
from repro.core.tiling import TileSpec, make_tiles, plan_z_slabs, \
    plan_z_units
from repro.core.variants import get_spec
from repro.runtime.executor import PlanExecutor, ProgramCache
from repro.runtime.planner import ReconPlan, plan_reconstruction


class TiledReconstructor:
    """Streaming tile/slab back-projection around any registered variant.

    A façade: the constructor builds a :class:`ReconPlan` (all validation
    happens there) and an executor over the shared program cache.

    Parameters
    ----------
    geom : CTGeometry
    variant : registry name (``core.variants.REGISTRY``).
    tile_shape : (ti, tj, tk) maximum tile size in voxels; ``None`` picks
        it from ``memory_budget`` (or uses the full volume if neither is
        given, which degenerates to the untiled call).
    memory_budget : byte budget for one tile's working set (see
        ``core.tiling.tile_working_set_bytes``).
    nb : in-batch projection count handed to the variant (paper O5).
    proj_batch : how many projections stream through per variant call
        (rounded up to a multiple of ``nb``); ``None`` = all at once.
        With ``reconstruct`` this also bounds the *filtering* working
        set: each chunk is pre-weighted + ramp-filtered on the fly.
    out : "host" (numpy accumulator, device holds one tile) | "device".
    interpret : forwarded to the Pallas variants.
    schedule : "step" (scanned device-resident tile accumulators, one
        host crossing per step) | "chunk" (chunk-major streaming:
        filtered projections stay two-chunk-bounded on device —
        current + prefetched) | None
        (default — the planner resolves it: "chunk" when a
        ``memory_budget`` bounds device bytes, "step" otherwise).
    pipeline : "sync" (in-thread double-buffered flush) | "async" (a
        flusher thread overlaps step N's device->host accumulator copy
        with step N+1's scan dispatch; bit-identical output — see
        ``runtime.executor.PlanExecutor``).
    cache : optional private ProgramCache (default: process-shared).
    """

    def __init__(self, geom: CTGeometry, variant: str = "algorithm1_mp", *,
                 tile_shape: Optional[Sequence[int]] = None,
                 memory_budget: Optional[int] = None,
                 nb: int = 8, proj_batch: Optional[int] = None,
                 out: str = "host", interpret: Optional[bool] = None,
                 schedule: Optional[str] = None,
                 pipeline: str = "sync",
                 cache: Optional[ProgramCache] = None,
                 **kernel_options):
        self.geom = geom
        self.recon_plan: ReconPlan = plan_reconstruction(
            geom, variant, tile_shape=tile_shape,
            memory_budget=memory_budget, nb=nb, proj_batch=proj_batch,
            out=out, interpret=interpret, schedule=schedule,
            **kernel_options)
        # variant="auto" resolves through the tuning cache in the
        # planner; record the resolved name for introspection
        self.variant = self.recon_plan.variant
        self._executor = PlanExecutor(geom, self.recon_plan, cache=cache,
                                      pipeline=pipeline)

    # ---- introspection ---------------------------------------------------

    @property
    def tile_shape(self) -> Tuple[int, int, int]:
        return self.recon_plan.tile_shape

    @property
    def nb(self) -> int:
        return self.recon_plan.nb

    @property
    def working_set_bytes(self) -> int:
        """Peak modeled working set over planned calls (the O(tile) bound;
        mirror-paired slabs are billed at their virtual 2*bk depth)."""
        return self.recon_plan.working_set_bytes

    def cache_stats(self) -> dict:
        """Jit-program cache hits/misses/live-programs."""
        return self._executor.cache.stats()

    def plan(self):
        """Legacy view: ((i0, j0, ni, nj) list, ZUnit list).

        The authoritative schedule is ``recon_plan.steps`` (which also
        carries per-step variant resolution); this derived view keeps
        the PR-1 introspection shape for callers that want the raw
        (i, j) x Z decomposition.
        """
        ti, tj, tk = self.recon_plan.tile_shape
        nx, ny, nz = self.geom.volume_shape_xyz
        ij = [(t.i0, t.j0, t.ni, t.nj)
              for t in make_tiles((nx, ny, 1), (ti, tj, 1))]
        z = (plan_z_units(nz, tk) if get_spec(self.variant).uses_symmetry
             else plan_z_slabs(nz, tk))
        return ij, z

    # ---- execution (delegates to the PlanExecutor) -----------------------

    def backproject(self, img_t: jnp.ndarray, mats: jnp.ndarray):
        """Full tiled back-projection of pre-filtered projections.

        img_t: (np, nw, nh) transposed projections; mats: (np, 3, 4).
        Returns vol_t (nx, ny, nz) — numpy when ``out == "host"``.
        """
        return self._executor.backproject(img_t, mats)

    def backproject_tile(self, img_t: jnp.ndarray, mats: jnp.ndarray,
                         tile: TileSpec) -> jnp.ndarray:
        """Back-project one arbitrary sub-box; exact for every variant
        (non-centered boxes run the KernelSpec slab-safe fallback)."""
        return self._executor.backproject_tile(img_t, mats, tile)

    def reconstruct(self, projections: jnp.ndarray) -> jnp.ndarray:
        """Filtered FDK through the plan: (np, nh, nw) -> (nz, ny, nx).

        Filtering streams through the projection-chunk loop; returns
        numpy when ``out == "host"`` (a free transposed view of the host
        accumulator) and a jax array otherwise.
        """
        return self._executor.reconstruct(projections)
