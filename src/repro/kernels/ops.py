"""Jitted public wrappers for the Pallas back-projection kernels.

Handles arbitrary problem shapes by padding: the volume tile grid (voxel
lines outside the true volume compute garbage that is sliced away; their
projections may be off-detector, which the in-kernel masks already
zero — padding only costs compute, never correctness) and the detector
to the kernels' TPU alignment (zero columns/rows past the true extents,
which the kernels' validity masks never admit).

``interpret=None`` (the default) runs the Pallas interpreter exactly when
the default backend is the CPU (``runtime.planner.resolve_interpret``);
an explicit ``interpret=True`` on a TPU is an error.
"""

from __future__ import annotations

import jax.numpy as jnp

from .backproject_banded import backproject_banded as _backproject_banded
from .backproject_onehot import backproject_onehot_pallas
from .backproject_subline import (_pad_to, backproject_subline_pallas,
                                  fused_batch_ok, padded_lanes, padded_rows)

# KernelSpec contract (core.variants.REGISTRY): the call-time options each
# public wrapper consumes. The registry's Pallas KernelSpecs must declare
# exactly these sets — tests/test_planner.py cross-checks the two layers
# so a new kernel knob cannot be added here without the planner (which
# filters options through KernelSpec.options) learning about it.
ACCEPTED_OPTIONS = {
    "backproject_subline": frozenset({"nb", "block", "proj_loop",
                                      "interpret"}),
    "backproject_onehot": frozenset({"nb", "block", "k_chunk", "proj_loop",
                                     "interpret"}),
    "backproject_banded": frozenset({"nb", "block", "bw", "proj_loop",
                                     "interpret"}),
}


def default_block(ni: int, nj: int):
    """(BI, BJ) voxel-line tile of one grid cell: up to 8 x 32 lines
    (BJ a multiple of the 8 sublanes), shrunk for small volumes so they
    are not padded far past their extent."""
    return (max(1, min(8, ni)), min(32, _pad_to(nj, 8)))


def _interpret(interpret):
    from repro.runtime.planner import resolve_interpret
    return resolve_interpret(interpret)


def _run_padded(fn, img_t, mat, vol_shape_xyz, block, **kw):
    # Only i/j may be padded: extra voxel LINES are masked by the kernel's
    # bounds checks. nz must never be padded — the symmetry pairing
    # k <-> nz-1-k is defined by the true volume center (the kernels
    # handle odd nz natively via an uneven half-split).
    ni, nj, nz = vol_shape_xyz
    BI, BJ = block
    nip = _pad_to(ni, BI)
    njp = _pad_to(nj, BJ)
    _, nw, nh = img_t.shape
    rows, nh_p = padded_rows(nw), padded_lanes(nh)
    if (rows, nh_p) != (nw, nh):
        img_t = jnp.pad(img_t, ((0, 0), (0, rows - nw), (0, nh_p - nh)))
    vol = fn(img_t, mat, (nip, njp, nz), block=block, nw=nw, nh=nh, **kw)
    if (nip, njp) != (ni, nj):
        vol = vol[:ni, :nj]
    return vol


def backproject_subline(img_t: jnp.ndarray, mat: jnp.ndarray,
                        vol_shape_xyz, *, nb: int = 0,
                        block=None, proj_loop: bool = False,
                        interpret=None, origin=None) -> jnp.ndarray:
    """Paper Algorithm 1 as a Pallas kernel (symmetry_pf analogue).

    The output-stationary Pallas schedule holds the volume tile in VMEM
    across ALL projections — the nb -> np ideal of the paper's batching.
    With ``proj_loop`` the projection grid additionally runs over
    nb-sized batches with an in-kernel ``fori_loop``, cutting the
    per-grid-step output read-modify-write by the batch factor (paper
    O5 inside the kernel); without it ``nb`` is accepted for registry-
    signature uniformity but ignored. ``block=None`` picks
    :func:`default_block`. ``origin`` (i, j) places the call's box in
    the whole volume (``backproject_subline.backproject_call``). See
    DESIGN.md §2.
    """
    vol_shape_xyz = tuple(vol_shape_xyz)
    block = tuple(block or default_block(*vol_shape_xyz[:2]))
    nb_k = nb if fused_batch_ok(img_t.shape[0], nb, proj_loop) else 1
    return _run_padded(backproject_subline_pallas, img_t, mat,
                       vol_shape_xyz, block, nb=nb_k,
                       interpret=_interpret(interpret), origin=origin)


def backproject_onehot(img_t: jnp.ndarray, mat: jnp.ndarray,
                       vol_shape_xyz, *, nb: int = 0, block=None,
                       k_chunk: int = 128, proj_loop: bool = False,
                       interpret=None, origin=None) -> jnp.ndarray:
    """Beyond-paper MXU one-hot interpolation kernel (``proj_loop``:
    fused multi-batch mode, and ``origin``: see
    :func:`backproject_subline`)."""
    vol_shape_xyz = tuple(vol_shape_xyz)
    block = tuple(block or default_block(*vol_shape_xyz[:2]))
    nb_k = nb if fused_batch_ok(img_t.shape[0], nb, proj_loop) else 1
    return _run_padded(backproject_onehot_pallas, img_t, mat,
                       vol_shape_xyz, block, k_chunk=k_chunk, nb=nb_k,
                       interpret=_interpret(interpret), origin=origin)


def backproject_banded(img_t: jnp.ndarray, mat: jnp.ndarray,
                       vol_shape_xyz, *, nb: int = 0, block=None,
                       bw: int = 32, proj_loop: bool = False,
                       interpret=None) -> jnp.ndarray:
    """Beyond-paper geometry-prefetched banded kernel (C3): streams only
    the ~2*bw detector columns each (tile, projection) pair touches.
    ``proj_loop`` shares one band per nb-projection batch (the kernel
    wrapper widens bw until the batch union fits)."""
    ni, nj, nz = vol_shape_xyz
    BI, BJ = block = tuple(block or default_block(ni, nj))
    nip, njp = _pad_to(ni, BI), _pad_to(nj, BJ)
    vol = _backproject_banded(img_t, mat, (nip, njp, nz), block=block,
                              bw=bw, nb=nb, proj_loop=proj_loop,
                              interpret=_interpret(interpret))
    if (nip, njp) != (ni, nj):
        vol = vol[:ni, :nj]
    return vol
