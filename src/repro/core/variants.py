"""Declarative registry of back-projection kernel variants (paper Table 2).

Each variant is a :class:`KernelSpec` — a capability record the planner
(``runtime.planner``) consumes to schedule work: which paper optimizations
the kernel carries, which call-time options it accepts, and which
symmetry-free member of the ladder substitutes for it on Z-slabs that are
not centered on the volume midplane (the O3 mirror pairs voxel ``k`` with
``nk-1-k`` about the FULL volume's Z center, so symmetry-carrying kernels
are only exact on centered sub-boxes or mirror-paired slab calls — see
``core.tiling.ZUnit``).

Every kernel callable has the uniform signature

    fn(img_t, mat, vol_shape_xyz, **opts) -> vol_t (nx, ny, nz)

operating on transposed layouts. The RTK baseline is exposed through the
same signature by transposing at the edges (the transposes are part of the
measured baseline cost in RTK's favor: the paper also counts its own
transposition as marginal, §3.1.1).

Names follow the paper (Table 2), with `_mp` ~ pure-JAX (the auto-vectorized
path) and `_pl` ~ Pallas kernels (the explicitly tiled path):

    baseline        RTK Listing 1 (native layouts inside)
    transpose_mp    O1
    share_mp        O1+O2
    symmetry_mp     O1+O2+O3
    subline_mp      O1+O2+O4
    subline_batch_mp O1+O2+O4+O5 (no O3 — exact on any Z-slab; the
                    planner's slab-safe fallback)
    algorithm1_mp   O1..O5 (paper Algorithm 1; nb batching)
    subline_pl      Pallas: O1..O5 + O6 (pipelined prefetch)  [kernels/]
    onehot_pl       Pallas: beyond-paper MXU interpolation    [kernels/]
    banded_pl       Pallas: beyond-paper banded prefetch      [kernels/]

``VARIANTS`` / ``OPTIMIZATIONS`` / ``SLAB_SAFE_FALLBACK`` — the three
ad-hoc dicts this registry replaces — are kept as *derived* read-only
views for existing callers; ``REGISTRY`` is the source of truth.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, FrozenSet, Mapping, Optional, Tuple

from . import backproject as bp
from . import baseline as bl


# --------------------------------------------------------------------------
# Kernel callables (uniform signature adapters)
# --------------------------------------------------------------------------

def _baseline_adapter(img_t, mat, vol_shape_xyz, **_):
    img = bp.transpose_projections(img_t)  # back to (np, nh, nw)
    ni, nj, nk = vol_shape_xyz
    vol = bl.backproject_rtk(img, mat, (nk, nj, ni))
    return bp.volume_to_transposed(vol)


def _transpose(img_t, mat, vol_shape_xyz, **_):
    return bp.bp_transpose(img_t, mat, vol_shape_xyz)


def _share(img_t, mat, vol_shape_xyz, **_):
    return bp.bp_share(img_t, mat, vol_shape_xyz)


def _symmetry(img_t, mat, vol_shape_xyz, **_):
    return bp.bp_symmetry(img_t, mat, vol_shape_xyz)


def _subline(img_t, mat, vol_shape_xyz, **_):
    return bp.bp_subline(img_t, mat, vol_shape_xyz)


def _algorithm1(img_t, mat, vol_shape_xyz, nb: int = 8, **_):
    return bp.bp_subline_symmetry_batch(img_t, mat, vol_shape_xyz, nb=nb)


def _subline_batch(img_t, mat, vol_shape_xyz, nb: int = 8, **_):
    return bp.bp_subline_batch(img_t, mat, vol_shape_xyz, nb=nb)


def _subline_pallas(img_t, mat, vol_shape_xyz, nb: int = 8,
                    interpret=None, block=None,
                    proj_loop: bool = False, origin=None, **_):
    from repro.kernels import ops
    return ops.backproject_subline(img_t, mat, vol_shape_xyz, nb=nb,
                                   block=block, interpret=interpret,
                                   proj_loop=proj_loop, origin=origin)


def _onehot_pallas(img_t, mat, vol_shape_xyz, nb: int = 8,
                   interpret=None, block=None,
                   k_chunk: int = 128, proj_loop: bool = False,
                   origin=None, **_):
    from repro.kernels import ops
    return ops.backproject_onehot(img_t, mat, vol_shape_xyz, nb=nb,
                                  block=block, k_chunk=k_chunk,
                                  interpret=interpret, proj_loop=proj_loop,
                                  origin=origin)


def _banded_pallas(img_t, mat, vol_shape_xyz, nb: int = 8,
                   interpret=None, block=None, bw: int = 32,
                   proj_loop: bool = False, **_):
    from repro.kernels import ops
    return ops.backproject_banded(img_t, mat, vol_shape_xyz, nb=nb,
                                  block=block, bw=bw, interpret=interpret,
                                  proj_loop=proj_loop)


# --------------------------------------------------------------------------
# KernelSpec: one declarative capability record per variant
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Capability record for one back-projection kernel.

    Fields
    ------
    name : registry key (paper Table 2 naming).
    fn : kernel callable with the uniform transposed signature.
    optimizations : which paper optimizations the kernel carries
        (Table 2 columns; ``"symmetry"`` has scheduling consequences).
    options : call-time keyword options the kernel actually consumes.
        The planner filters resolved options through this set so kernels
        never see (and silently swallow) irrelevant knobs.
    slab_safe_fallback : name of the strongest symmetry-free variant with
        the same remaining optimizations — what the planner schedules on
        a Z-slab that is neither volume-centered nor mirror-paired.
        ``None`` for symmetry-free kernels (they are their own fallback).
    backend : "reference" | "jax" | "pallas" (Pallas kernels accept
        ``interpret=``, which the planner derives from the platform:
        the interpreter on CPU, Mosaic-compiled on TPU).
    jittable : whether the kernel tolerates traced inputs under an outer
        ``jax.jit`` (the program cache wraps jittable kernels; a kernel
        that inspects concrete matrix VALUES at trace time — e.g. the
        banded kernel's data-dependent band schedule — must opt out and
        is cached un-wrapped instead).
    proj_loop : whether the kernel supports the fused multi-batch mode —
        an in-kernel ``fori_loop`` over ``nb``-sized projection batches
        with the Z-slab accumulator held in the VMEM output ref, cutting
        per-launch output read-modify-write traffic by the batch factor
        (the paper's O1 loop order + O3 locality carried INTO the
        kernel). The planner defaults the ``proj_loop`` option ON for
        specs that advertise it.
    tuning_space : the option axes the autotuner (``runtime.autotune``)
        may flip when it searches this kernel's configuration space,
        as ``((option, (candidate values, ...)), ...)``. Declarative for
        the same reason ``options`` is: the tuner never guesses which
        knobs a kernel takes — the spec advertises them (every key must
        be in ``options``). Heuristic defaults stay with the planner;
        this only widens the MEASURED search.
    index_origin : whether ``fn`` takes ``origin=`` (a (2,) int32 array,
        the whole-volume voxel index (i, j) of the call box's first
        line) and computes its detector coordinates from whole-volume
        indices. For the other kernels :func:`at_origin` folds the box
        origin into the matrices' constant column, whose float32
        rounding moves every sample by up to an ulp, so a sample within
        an ulp of the detector's last column can land on the other side
        of it than in an untiled call.
    """

    name: str
    fn: Callable
    optimizations: Tuple[str, ...]
    options: FrozenSet[str] = frozenset()
    slab_safe_fallback: Optional[str] = None
    backend: str = "jax"
    jittable: bool = True
    proj_loop: bool = False
    tuning_space: Tuple[Tuple[str, Tuple], ...] = ()
    index_origin: bool = False

    @property
    def uses_symmetry(self) -> bool:
        """Whether the kernel's math assumes the volume-centered O3 mirror."""
        return "symmetry" in self.optimizations

    @property
    def is_pallas(self) -> bool:
        return self.backend == "pallas"

    def resolve_options(self, opts: Mapping) -> Dict:
        """Filter caller options down to the ones this kernel accepts."""
        return {k: v for k, v in opts.items()
                if k in self.options and v is not None}


_PL_OPTS = frozenset({"nb", "interpret", "block", "proj_loop"})

# Pallas kernels expose the fused in-kernel projection loop as a measured
# tuning axis: the planner defaults it ON, but whether it beats the
# per-batch launch depends on the machine (VMEM vs dispatch cost) — which
# is exactly what runtime.autotune measures instead of guessing.
_PL_TUNING = (("proj_loop", (True, False)),)

REGISTRY: Dict[str, KernelSpec] = {s.name: s for s in (
    KernelSpec("baseline", _baseline_adapter, (), backend="reference"),
    KernelSpec("transpose_mp", _transpose, ("transpose",)),
    KernelSpec("share_mp", _share, ("transpose", "share")),
    KernelSpec("symmetry_mp", _symmetry,
               ("transpose", "share", "symmetry"),
               slab_safe_fallback="share_mp"),
    KernelSpec("subline_mp", _subline, ("transpose", "share", "subline")),
    KernelSpec("subline_batch_mp", _subline_batch,
               ("transpose", "share", "subline", "batch"),
               options=frozenset({"nb"})),
    KernelSpec("algorithm1_mp", _algorithm1,
               ("transpose", "share", "symmetry", "subline", "batch"),
               options=frozenset({"nb"}),
               slab_safe_fallback="subline_batch_mp"),
    KernelSpec("subline_pl", _subline_pallas,
               ("transpose", "share", "symmetry", "subline", "batch",
                "localmem", "prefetch"),
               options=_PL_OPTS,
               slab_safe_fallback="subline_batch_mp", backend="pallas",
               proj_loop=True, tuning_space=_PL_TUNING, index_origin=True),
    KernelSpec("onehot_pl", _onehot_pallas,
               ("transpose", "share", "symmetry", "subline", "batch",
                "localmem", "prefetch", "mxu-interp"),
               options=_PL_OPTS | {"k_chunk"},
               slab_safe_fallback="subline_batch_mp", backend="pallas",
               proj_loop=True, tuning_space=_PL_TUNING, index_origin=True),
    # jittable=False: the band schedule is computed from concrete matrix
    # values at trace time (np.asarray(mat) in the kernel wrapper)
    KernelSpec("banded_pl", _banded_pallas,
               ("transpose", "share", "symmetry", "subline", "batch",
                "localmem", "prefetch", "banded-prefetch"),
               options=_PL_OPTS | {"bw"},
               slab_safe_fallback="subline_batch_mp", backend="pallas",
               jittable=False, proj_loop=True, tuning_space=_PL_TUNING),
)}


def _validate_registry() -> None:
    for spec in REGISTRY.values():
        if spec.uses_symmetry:
            fb = spec.slab_safe_fallback
            if fb is None or fb not in REGISTRY:
                raise ValueError(
                    f"symmetry variant {spec.name!r} needs a registered "
                    f"slab_safe_fallback, got {fb!r}")
            fspec = REGISTRY[fb]
            if fspec.uses_symmetry:
                raise ValueError(
                    f"{spec.name!r} fallback {fb!r} still uses symmetry")
            if not set(fspec.optimizations) <= set(spec.optimizations):
                raise ValueError(
                    f"{spec.name!r} fallback {fb!r} adds optimizations "
                    f"the primary does not carry")
        elif spec.slab_safe_fallback is not None:
            raise ValueError(
                f"symmetry-free variant {spec.name!r} must not declare a "
                f"slab_safe_fallback")
        if spec.proj_loop and "proj_loop" not in spec.options:
            raise ValueError(
                f"{spec.name!r} advertises proj_loop but does not accept "
                f"the 'proj_loop' call option")
        bad = [k for k, _ in spec.tuning_space if k not in spec.options]
        if bad:
            raise ValueError(
                f"{spec.name!r} tuning_space keys {bad} are not accepted "
                f"call options (KernelSpec.options)")


_validate_registry()


# --------------------------------------------------------------------------
# Derived legacy views + lookups
# --------------------------------------------------------------------------

VARIANTS: Dict[str, Callable] = {n: s.fn for n, s in REGISTRY.items()}

OPTIMIZATIONS: Dict[str, tuple] = {n: s.optimizations
                                   for n, s in REGISTRY.items()}

SLAB_SAFE_FALLBACK: Dict[str, str] = {
    n: s.slab_safe_fallback for n, s in REGISTRY.items()
    if s.slab_safe_fallback is not None}


def get_spec(name: str) -> KernelSpec:
    if name not in REGISTRY:
        raise KeyError(f"unknown back-projection variant {name!r}; "
                       f"have {sorted(REGISTRY)}")
    return REGISTRY[name]


def at_origin(spec: KernelSpec, fn: Callable) -> Callable:
    """``fn`` (``spec``'s kernel, or a wrapper of it) placed in the whole
    volume: ``g(img_t, mat, vol_shape_xyz, origin=None, **opts)``.

    ``origin`` is the call box's voxel origin ``(i0, j0, k0)``, a (3,)
    float32 array (traced or concrete); ``None`` calls ``fn`` as it is.
    A kernel with ``index_origin`` gets ``(i0, j0)`` as voxel indices
    and only ``k0`` folded into the matrices (their x and z rows do not
    depend on k, so those rows stay bit for bit the untranslated ones);
    the others get the whole origin folded in."""
    import jax.numpy as jnp
    from .tiling import translate_matrices

    def g(img_t, mat, vol_shape_xyz, origin=None, **opts):
        if origin is None:
            return fn(img_t, mat, vol_shape_xyz, **opts)
        if spec.index_origin:
            return fn(img_t, translate_matrices(mat, 0.0, 0.0, origin[2]),
                      vol_shape_xyz, origin=origin[:2].astype(jnp.int32),
                      **opts)
        return fn(img_t, translate_matrices(mat, origin[0], origin[1],
                                            origin[2]),
                  vol_shape_xyz, **opts)

    return g


def get_variant(name: str) -> Callable:
    return get_spec(name).fn


def uses_symmetry(name: str) -> bool:
    """Whether a variant's math assumes the volume-centered O3 mirror."""
    return get_spec(name).uses_symmetry


def slab_safe_variant(name: str) -> str:
    """Variant to run on an arbitrary (non-centered) Z-slab."""
    spec = get_spec(name)
    return spec.slab_safe_fallback if spec.uses_symmetry else name
