"""RTK-style baseline back-projection (the paper's Listing 1), in JAX.

This is the *reference semantics* every optimized variant must match to the
paper's validation bar (RMSE < 1e-5, §4.2). Layouts follow RTK exactly:

    img:    (np, nh, nw)   row-major projections, img[s][y][x]
    mat:    (np, 3, 4)     index-space projection matrices
    volume: (nz, ny, nx)   row-major volume, volume[k][j][i]

For every projection ``s`` and voxel ``(i,j,k)``:

    z = mat[s][2] . (i,j,k,1);  f = 1/z
    x = (mat[s][0] . (i,j,k,1)) * f
    y = (mat[s][1] . (i,j,k,1)) * f
    volume[k][j][i] += Bilinear(img[s], x, y) * f * f

Boundary convention (shared by ALL variants in this repo): a sample
contributes iff ``0 <= x <= nw-2+1`` is interpolable, i.e. ``floor(x)`` and
``floor(x)+1`` are both in-bounds (same for y), and ``z > 0``; otherwise the
contribution is exactly zero. Gathers are index-clamped so out-of-range
lanes read *some* valid element and are then masked — this keeps every
variant (JAX and Pallas) bit-comparable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def bilinear_gather(img: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray):
    """Bilinear interpolation of img[y][x] at fractional (x, y).

    img: (nh, nw). x, y: arbitrary (broadcastable) shapes. Returns
    (values, valid_mask) with the repo-wide boundary convention.
    """
    nh, nw = img.shape
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    ix = x0.astype(jnp.int32)
    iy = y0.astype(jnp.int32)
    dx = x - x0
    dy = y - y0
    valid = (ix >= 0) & (ix <= nw - 2) & (iy >= 0) & (iy <= nh - 2)
    ixc = jnp.clip(ix, 0, nw - 2)
    iyc = jnp.clip(iy, 0, nh - 2)
    v00 = img[iyc, ixc]
    v01 = img[iyc, ixc + 1]
    v10 = img[iyc + 1, ixc]
    v11 = img[iyc + 1, ixc + 1]
    s0 = v00 * (1.0 - dx) + v01 * dx  # mix along x (paper's Listing 2)
    s1 = v10 * (1.0 - dx) + v11 * dx
    val = s0 * (1.0 - dy) + s1 * dy   # mix along y
    return val, valid


def _voxel_index_grid(nz: int, ny: int, nx: int, dtype=jnp.float32):
    """Homogeneous (i, j, k) coordinate grids, each (nz, ny, nx)."""
    k = jnp.arange(nz, dtype=dtype)[:, None, None]
    j = jnp.arange(ny, dtype=dtype)[None, :, None]
    i = jnp.arange(nx, dtype=dtype)[None, None, :]
    return i, j, k


def backproject_single(img_s: jnp.ndarray, mat_s: jnp.ndarray,
                       vol_shape_zyx) -> jnp.ndarray:
    """Back-project ONE projection onto a zero volume (zyx layout)."""
    nz, ny, nx = vol_shape_zyx
    i, j, k = _voxel_index_grid(nz, ny, nx)
    # dot4(mat[r], (i,j,k,1)) for the three rows.
    z = mat_s[2, 0] * i + mat_s[2, 1] * j + mat_s[2, 2] * k + mat_s[2, 3]
    f = 1.0 / z
    x = (mat_s[0, 0] * i + mat_s[0, 1] * j + mat_s[0, 2] * k + mat_s[0, 3]) * f
    y = (mat_s[1, 0] * i + mat_s[1, 1] * j + mat_s[1, 2] * k + mat_s[1, 3]) * f
    val, valid = bilinear_gather(img_s, x, y)
    w = f * f
    ok = valid & (z > 0)
    return jnp.where(ok, val * w, 0.0)


@functools.partial(jax.jit, static_argnames=("vol_shape_zyx",))
def backproject_rtk(img: jnp.ndarray, mat: jnp.ndarray,
                    vol_shape_zyx) -> jnp.ndarray:
    """Full baseline: sequential loop over projections (Listing 1 order).

    img (np, nh, nw); mat (np, 3, 4). Returns volume (nz, ny, nx) float32.
    The projection loop is a ``fori_loop`` (RTK iterates projections
    outermost, one full volume sweep per projection — maximal volume
    traffic; this is precisely the behaviour the paper's nb-batching
    removes).
    """
    nz, ny, nx = vol_shape_zyx

    def body(s, vol):
        return vol + backproject_single(img[s], mat[s], vol_shape_zyx)

    vol0 = jnp.zeros((nz, ny, nx), dtype=jnp.float32)
    return jax.lax.fori_loop(0, img.shape[0], body, vol0)
