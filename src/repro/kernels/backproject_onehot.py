"""Pallas TPU kernel: back-projection with MXU one-hot interpolation.

Beyond-paper variant (DESIGN.md §2, assumption change #2). The paper's
sub-line stage 2 is a per-point gather in the cache-resident sMem buffer —
cheap on CPUs, but on TPU a lane gather stays inside one vreg. This
kernel replaces the gather with a *sparse interpolation matrix contracted
on the MXU*, one voxel line at a time:

    val[k] = sum_n sMem[n] * A[n, k]
    A[n, k] = (1-dy_k) * [n == floor(y_k)] + dy_k * [n == floor(y_k)+1]

A is built from broadcasted iotas (pure VPU compares, no gathers) and the
contraction is a (1, nh) x (nh, kw) matmul on the MXU at
``Precision.HIGHEST`` (float32 accuracy: the 1e-5 RMSE bar must hold on
the chip). The trade: 2*kw*nh FLOPs per line chunk instead of ~6*kw
gather-ops — profitable only when gather throughput, not FLOPs, bounds.

Schedule, blocking, hoisting, symmetry and the sub-line stage 1 are the
sub-line kernel's (``backproject_subline.backproject_call``); only the
stage-2 interpolation differs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .backproject_subline import LANES, _row_coords, backproject_call


def onehot_interp(nh: int):
    """Stage 2 by MXU contraction: (8, kw) row coordinates -> values."""

    def interp(sm_ref, y):
        iyc, dy, ok = _row_coords(y, nh)
        nh_p, kw = sm_ref.shape[1], y.shape[1]
        n_iota = jax.lax.broadcasted_iota(jnp.int32, (nh_p, kw), 0)
        line = jax.lax.broadcasted_iota(jnp.int32, (8, kw), 0)
        out = jnp.zeros((8, kw), jnp.float32)
        for l in range(8):
            i_l = iyc[l:l + 1, :]                          # (1, kw)
            d_l = dy[l:l + 1, :]
            a = jnp.where(n_iota == i_l, 1.0 - d_l,
                          jnp.where(n_iota == i_l + 1, d_l, 0.0))
            v = jax.lax.dot_general(
                sm_ref[l:l + 1, :], a,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)         # (1, kw)
            out = jnp.where(line == l, v, out)
        return jnp.where(ok, out, 0.0)

    return interp


@functools.partial(
    jax.jit,
    static_argnames=("vol_shape_xyz", "block", "k_chunk", "nb", "nw", "nh",
                     "interpret"),
)
def backproject_onehot_pallas(img_t: jnp.ndarray, mat: jnp.ndarray,
                              vol_shape_xyz, *, block=(8, 32),
                              k_chunk: int = LANES, nb: int = 1,
                              nw: int, nh: int,
                              interpret: bool = False,
                              origin=None) -> jnp.ndarray:
    """One-hot kernel on padded projections (see
    ``backproject_subline_pallas`` for the layout contract and
    ``origin``); ``k_chunk`` is the k-chunk width of one contraction (a
    multiple of 128 on TPU)."""
    if not interpret and k_chunk % LANES:
        raise ValueError(
            f"onehot_pl: k_chunk={k_chunk} must be a multiple of {LANES} "
            f"on TPU")
    nh_p = img_t.shape[-1]
    return backproject_call(img_t, mat, tuple(vol_shape_xyz), block=block,
                            nb=nb, nw=nw, nh=nh, interp=onehot_interp(nh),
                            interpret=interpret, kw=k_chunk,
                            k_work=4 * 4 * nh_p * k_chunk, origin=origin)
