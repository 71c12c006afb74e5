"""Pallas TPU kernel: banded, geometry-prefetched sub-line back-projection.

Beyond-paper optimization C3 (EXPERIMENTS.md §Perf CT campaign). The
output-stationary schedule of backproject_subline re-streams every full
projection for every volume tile — at P10 scale that is PBs of HBM
traffic. But a (BI, BJ) voxel tile only touches a NARROW BAND of detector
columns per projection: x(i,j) = (m00 i + m01 j + m03)/(m20 i + m21 j +
m23) is a ratio of linear functions, so its extrema over the tile
rectangle sit at the 4 corners — the needed band is known on the host
from the matrices alone.

Realization:
  * the projections are re-laid-out ONCE into 2x-overlapping bands
    img_b[s, b] = img_t[s, b*BW : b*BW + 2*BW, :]  (2x img memory, read
    O(T) times — amortized immediately);
  * a scalar-prefetch array band[s, ti, tj] = floor(xmin/BW) drives the
    BlockSpec index_map, so the pipeline DMAs exactly one (2*BW, nh) band
    per (tile, projection) — the paper's locality insight promoted into
    the prefetch engine (O6 with geometry awareness);
  * coverage is guaranteed when max tile x-span + 2 <= BW (checked by the
    wrapper, which picks BW from the geometry).

HBM projection traffic drops from T * np * nw * nh to
T * np * 2*BW * nh  (nw/2BW fold; ~14x for P10 at BW=64).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .backproject_subline import (backproject_call, fused_batch_ok,
                                  gather_interp, padded_lanes)

# Band-table entries one kernel call scalar-prefetches (128 KiB of SMEM).
BAND_TABLE_ENTRIES = 32 * 1024


def band_layout(img_t: jnp.ndarray, bw: int):
    """(np, nw, nh) -> overlapping bands (np, n_bands, 2*bw, nh)."""
    n_proj, nw, nh = img_t.shape
    n_bands = max(1, -(-nw // bw))
    pad = n_bands * bw + bw - nw      # so band b slice [b*bw, b*bw+2bw) fits
    imgp = jnp.pad(img_t, ((0, 0), (0, pad), (0, 0)))
    idx = (jnp.arange(n_bands)[:, None] * bw
           + jnp.arange(2 * bw)[None, :])            # (n_bands, 2bw)
    return imgp[:, idx, :], n_bands                  # (np, nb, 2bw, nh)


def tile_bands(mat: np.ndarray, ni: int, nj: int, BI: int, BJ: int,
               bw: int, n_bands: int, nw: int, group: int = 1):
    """band[s, ti, tj] block index + the max span (for the BW check).

    Corner evaluation is exact for z>0 (linear-fractional x over the
    tile rectangle attains extrema at corners). ``group > 1`` reduces
    over groups of that many consecutive projections — the fused
    multi-batch (``proj_loop``) kernel shares ONE band per in-kernel
    batch, so the span check must cover the batch's x-range union and
    the returned array has one row per batch.
    """
    mat = np.asarray(mat, np.float64)
    ti = np.arange(ni // BI)
    tj = np.arange(nj // BJ)
    i_lo, i_hi = ti * BI, ti * BI + (BI - 1)
    j_lo, j_hi = tj * BJ, tj * BJ + (BJ - 1)
    xs = []
    for ic in (i_lo, i_hi):
        for jc in (j_lo, j_hi):
            i = ic[:, None, None]                    # (Ti,1,1)
            j = jc[None, :, None]                    # (1,Tj,1)
            m = mat[None, None]                      # (1,1,ns,3,4)
            z = m[..., 2, 0] * i + m[..., 2, 1] * j + m[..., 2, 3]
            x = (m[..., 0, 0] * i + m[..., 0, 1] * j
                 + m[..., 0, 3]) / np.maximum(z, 1e-6)
            xs.append(x)                             # (Ti,Tj,ns)
    xs = np.stack(xs)                                # (4,Ti,Tj,ns)
    xmin = np.clip(xs.min(0), 0, nw - 1)
    xmax = np.clip(xs.max(0), 0, nw - 1)
    if group > 1:
        t_i, t_j, ns = xmin.shape
        assert ns % group == 0, (ns, group)
        xmin = xmin.reshape(t_i, t_j, ns // group, group).min(-1)
        xmax = xmax.reshape(t_i, t_j, ns // group, group).max(-1)
    span = float((xmax - xmin).max()) + 2.0
    band = np.clip((xmin // bw).astype(np.int32), 0, n_bands - 1)
    # (ns, Ti, Tj) layout for the prefetch array
    return np.ascontiguousarray(np.transpose(band, (2, 0, 1))), span


def backproject_banded(img_t: jnp.ndarray, mat: jnp.ndarray,
                       vol_shape_xyz, *, block=(8, 32), bw: int = 32,
                       nb: int = 0, proj_loop: bool = False,
                       interpret: bool = False) -> jnp.ndarray:
    """Banded back-projection. img_t (np, nw, nh); returns (ni, nj, nz).

    Picks/validates the band width: requires max tile x-span + 2 <= bw
    (doubling bw until it holds), then runs the scalar-prefetched
    kernel. With ``proj_loop`` (and ``n_proj`` divisible by ``nb``) the
    fused multi-batch kernel runs instead: one band per nb-projection
    batch (the span check covers the batch union — wider motion per
    batch may force a larger bw), 1/nb output read-modify-write traffic.
    The band (2*bw detector columns) is 8-aligned and at least
    ``SLAB`` wide, so bw is rounded up to a multiple of 8.
    """
    n_proj, nw, nh = img_t.shape
    ni, nj, nz = vol_shape_xyz
    BI, BJ = block
    assert ni % BI == 0 and nj % BJ == 0 and BJ % 8 == 0
    group = nb if fused_batch_ok(n_proj, nb, proj_loop) else 1
    bw = max(8, -(-int(bw) // 8) * 8)
    mat_np = np.asarray(mat)
    while True:
        n_bands = max(1, -(-nw // bw))
        band, span = tile_bands(mat_np, ni, nj, BI, BJ, bw, n_bands, nw,
                                group=group)
        if span <= bw or bw >= nw:
            break
        bw *= 2
    nh_p = padded_lanes(nh)
    if nh_p != nh:
        img_t = jnp.pad(img_t, ((0, 0), (0, 0), (0, nh_p - nh)))
    img_b, n_bands = band_layout(img_t, bw)
    # The band table is scalar-prefetched into SMEM (1 MiB on v5e): run
    # the projections in runs whose table fits BAND_TABLE_ENTRIES,
    # accumulating into one aliased volume.
    n_batches = band.shape[0]
    per_call = max(1, BAND_TABLE_ENTRIES // (band.shape[1] * band.shape[2]))
    vol = None
    for b0 in range(0, n_batches, per_call):
        b1 = min(b0 + per_call, n_batches)
        vol = _banded_call(
            img_b[b0 * group:b1 * group], mat[b0 * group:b1 * group],
            jnp.asarray(band[b0:b1].reshape(-1)), vol,
            tuple(vol_shape_xyz), block=block, bw=bw, nw=nw, nh=nh,
            nb=group, interpret=interpret)
    return vol


@functools.partial(
    jax.jit,
    static_argnames=("vol_shape_xyz", "block", "bw", "nw", "nh", "nb",
                     "interpret"),
)
def _banded_call(img_b, mat, band, acc, vol_shape_xyz, *, block, bw, nw, nh,
                 nb, interpret):
    # nw = TRUE detector width: the validity mask must not admit the
    # zero-padded band tail (cols nw-1..) or edge columns leak into the
    # interpolation.
    return backproject_call(img_b, mat, vol_shape_xyz, block=block, nb=nb,
                            nw=nw, nh=nh, interp=gather_interp(nh),
                            interpret=interpret, band=band, bw=bw, acc=acc)
