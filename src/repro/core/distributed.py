"""Multi-pod distributed back-projection (iFDK-style scale-out).

Distribution scheme (DESIGN.md §4, mirrors the authors' own SC'19 iFDK):

  * volume sharded over the pod mesh: x -> "data", y -> "model"
    (each device owns an (nx/16, ny/16, nz) voxel slab);
  * a projection batch of nb images is REPLICATED within a pod and
    SHARDED over the "pod" axis (each pod back-projects a disjoint
    angle subset) — partial volumes are psum'd over "pod";
  * each device back-projects its slab with *translated* projection
    matrices: projecting voxel (i+i0, j+j0, k) equals projecting
    (i, j, k) with a matrix whose constant column absorbs the offset —
    so the single-device kernels (pure-JAX ladder or Pallas) run
    UNCHANGED inside shard_map. Locality is preserved at cluster scope:
    the inner loop is all-gather-free; only the final pod-axis
    all-reduce crosses the DCN.

The driver accumulates volume across batches: vol += step(img_batch) —
the paper's O5 batching at the cluster level (one volume buffer, one
reduction per batch).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .backproject import bp_subline_symmetry_batch, \
    bp_subline_symmetry_scan
from .geometry import CTGeometry
from .tiling import translate_matrices  # noqa: F401  (re-export; moved)


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checks off: the psum over
    "pod" is the only cross-slab collective and is explicit."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _pad_up(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def make_distributed_bp(geom: CTGeometry, mesh, *, nb: int = 32,
                        variant: str = "scan", inner_nb: int = 8,
                        vol_shape_xyz=None):
    """Build (fn, (img_spec, mat_spec, out_spec)) for one projection batch.

    fn(img_t_batch (nb, nw, nh), mat_batch (nb, 3, 4), origin (2,) f32)
    -> partial volume (nx_pad, ny_pad, nz) sharded (data, model, None).
    Call repeatedly over batches and accumulate (the driver owns the +=
    and final unpad).

    ``vol_shape_xyz`` reconstructs a sub-box of the full volume;
    ``origin`` is the sub-box origin in global voxel indices, passed at
    CALL time (a traced (2,) array, replicated) so one compiled program
    serves every tile of the same shape: each device's slab origin is
    the tile origin plus its mesh offset, letting the tiled engine
    compose (i, j)-tiles with the data/model/pod mesh unchanged.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    nd = axis_sizes.get("data", 1)
    nm = axis_sizes.get("model", 1)
    npod = axis_sizes.get("pod", 1)
    has_pod = "pod" in mesh.axis_names

    ni, nj, nz = (geom.nx, geom.ny, geom.nz) if vol_shape_xyz is None \
        else tuple(int(v) for v in vol_shape_xyz)
    nx_pad = _pad_up(ni, nd)
    ny_pad = _pad_up(nj, nm)
    bi, bj = nx_pad // nd, ny_pad // nm

    in_specs = (P("pod" if has_pod else None, None, None),  # img over pod
                P("pod" if has_pod else None, None, None),  # mats over pod
                P(None))                                    # origin repl.
    out_spec = P("data", "model", None)

    def shard_fn(img_t_local, mat_local, origin):
        # slab origin from mesh coordinates + the (traced) tile origin
        di = jax.lax.axis_index("data")
        dj = jax.lax.axis_index("model")
        i0 = origin[0] + (di * bi).astype(jnp.float32)
        j0 = origin[1] + (dj * bj).astype(jnp.float32)
        mat_shift = translate_matrices(mat_local, i0, j0)
        if variant == "scan":
            # sequential accumulation: 1x volume-sized temporaries
            vol_local = bp_subline_symmetry_scan(
                img_t_local, mat_shift, (bi, bj, nz))
        else:
            # paper Algorithm 1 with in-batch vmap (nb-x temporaries)
            vol_local = bp_subline_symmetry_batch(
                img_t_local, mat_shift, (bi, bj, nz),
                nb=min(inner_nb, img_t_local.shape[0]))
        if has_pod:
            vol_local = jax.lax.psum(vol_local, "pod")
        return vol_local

    # jit so repeated calls (projection batches, same-shape tiles) reuse
    # one compiled program instead of re-tracing the shard_map each time
    fn = jax.jit(_shard_map(shard_fn, mesh, in_specs, out_spec))
    return fn, (in_specs[0], in_specs[1], in_specs[2], out_spec)


def make_fleet_bp(variant: str, call_shape: Tuple[int, int, int], *,
                  nb: int, n_chunks: int, chunk_size: int,
                  options=(), interpret: bool = False,
                  rb: Optional[int] = None):
    """Per-device step program for the reconstruction fleet
    (``runtime.executor.PlanExecutor.execute_fleet``).

    ``prog(img_s, mat_s, origin) -> vol_t(call_shape)`` where ``img_s``
    / ``mat_s`` are the stacked scan grids ``(n_chunks, chunk_size,
    ...)`` and ``origin`` is the step's sub-box origin ``(i0, j0,
    k_off)`` as a traced (3,) f32 array.

    ``rb`` (cross-request batching) adds a leading request axis: the
    program becomes ``prog(img_b, mat_s, origin) -> vol_b((rb,) +
    call_shape)`` with ``img_b`` of shape ``(rb, n_chunks, chunk_size,
    ...)`` — one ``vmap`` lane per batched request over the SAME
    origin-folded scan, so per-lane output is bit-identical to the
    rb=None program and one dispatch serves k requests' step.

    This is :func:`make_distributed_bp`'s traced-origin trick lifted
    from mesh slabs to the fleet's per-device step queues: the origin
    places the kernel's box INSIDE the program
    (:func:`~repro.core.variants.at_origin` under the jit: whole-volume
    indices for an ``index_origin`` kernel, else folded into the
    matrices' constant column), so ONE compiled program per (variant,
    call_shape, chunk grid) serves EVERY same-shape step on ANY device —
    a stolen or failed-over step is the same program called with a
    different origin on a different device, never a recompile. The
    ``lax.scan`` carries the step's accumulator across all projection
    chunks device-resident, exactly like the single-device step-major
    megaprogram.

    Non-jittable kernels (``KernelSpec.jittable=False`` — banded_pl
    reads concrete matrix values at trace time) fall back to a python
    chunk loop over concrete arrays; the origin fold and the
    one-host-crossing contract are unchanged.
    """
    from repro.core.variants import at_origin, get_spec

    spec = get_spec(variant)
    opts = spec.resolve_options(
        {**dict(options), "nb": int(nb), "interpret": bool(interpret)})
    shape = tuple(call_shape)
    fn = at_origin(spec, spec.fn)
    if spec.jittable:
        def one(img_s, mat_s, origin):
            def body(acc, xs):
                img_c, mat_c = xs
                return acc + fn(img_c, mat_c, shape, origin, **opts), None

            acc, _ = jax.lax.scan(
                body, jnp.zeros(shape, jnp.float32), (img_s, mat_s))
            return acc
        if rb is None:
            return jax.jit(one)
        return jax.jit(jax.vmap(one, in_axes=(0, None, None)))

    def prog(img_s, mat_s, origin):
        def lane(img_l):
            acc = None
            for c in range(int(n_chunks)):
                part = fn(img_l[c], mat_s[c], shape, origin, **opts)
                acc = part if acc is None else acc + part
            return acc
        if rb is None:
            return lane(img_s)
        return jnp.stack([lane(img_s[r]) for r in range(int(rb))])
    return prog


def distributed_backproject(projections_t: jnp.ndarray, mats: jnp.ndarray,
                            geom: CTGeometry, mesh, *, nb: int = 32,
                            variant: str = "scan"):
    """Full distributed reconstruction loop over projection batches.

    projections_t: (np, nw, nh) transposed filtered projections.
    Returns volume (nx, ny, nz) (unpadded), sharded (data, model, None).
    ``n_proj`` need not divide ``nb``: the tail batch is padded with zero
    images (+ repeated matrices), which contribute exactly nothing.

    The projection-chunk schedule comes from the planner's chunk
    substrate (``tiling.plan_proj_chunks``, exactly-nb batches over the
    actual padded extent), and the shard_map program is memoized in the
    shared ProgramCache, so repeated calls on one geometry + mesh never
    rebuild it. The tiled composition (``TiledReconstructor
    .backproject_distributed``) routes through a full ReconPlan.
    """
    from repro.runtime.executor import default_program_cache
    from .tiling import pad_projection_batch, plan_proj_chunks

    projections_t, mats = pad_projection_batch(projections_t, mats, nb)
    # chunk the ACTUAL padded extent by exactly-nb batches (the program's
    # batch size); geom/mesh are hashable, so the shared cache keys on
    # their values and equal setups reuse one shard_map program
    _, _, chunks = plan_proj_chunks(projections_t.shape[0], nb, nb)
    fn = default_program_cache().get_or_build(
        ("dist", variant, geom.volume_shape_xyz, nb, geom, mesh),
        lambda: make_distributed_bp(geom, mesh, nb=nb, variant=variant)[0])
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    nx_pad = _pad_up(geom.nx, axis_sizes.get("data", 1))
    ny_pad = _pad_up(geom.ny, axis_sizes.get("model", 1))
    origin = jnp.zeros((2,), jnp.float32)
    vol = jnp.zeros((nx_pad, ny_pad, geom.nz), jnp.float32)
    for s0, s1 in chunks:
        vol = vol + fn(projections_t[s0:s1], mats[s0:s1], origin)
    return vol[:geom.nx, :geom.ny]
