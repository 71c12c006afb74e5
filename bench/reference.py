"""The plain FDK reference that decides ``correct``.

Independent of the code under test: it imports nothing of ``repro`` and
builds its own geometry, projection matrices and filter from the
configuration's numbers. FDK in three stages (Feldkamp, Davis, Kress
1984), all in float32:

1. cosine pre-weighting, ``p * D / sqrt(D^2 + u^2 + v^2)`` with ``(u, v)``
   the physical detector coordinates from the detector centre;
2. the Ram-Lak ramp along each detector row as a direct convolution with
   the discrete kernel of Kak & Slaney eq. 61 (``h[0] = 1/(4 du'^2)``,
   ``h[n] = -1/(pi n du')^2`` for odd ``n``, 0 for even ``n``), at the
   virtual-detector pitch ``du' = du d / D``, scaled by
   ``dtheta du' d^2 / 2``; computed as a matrix product at the highest
   precision, in blocks of views;
3. back-projection by the paper's Listing 1 (RTK): for every view,
   ``z = M[2].(i,j,k,1)``, ``x = M[0].(i,j,k,1)/z``, ``y = M[1].(i,j,k,1)/z``,
   bilinear sample of the filtered view times ``1/z^2``, zero where the
   sample is not interpolable or ``z <= 0`` -- here only at the sampled
   voxels.

``store`` is the precision the filtered views are held in before stage
3: ``float32`` is the reference, ``bfloat16`` the control (the program's
own reduced-precision data path rounds the same samples), rounded on
the bits so that no compiler can keep the excess precision.
"""

from __future__ import annotations

import functools
import math

import numpy as np

VIEW_BLOCK = 32


def geometry(scan: dict) -> dict:
    """The scan's geometry from a configuration's ``scan`` block: a cube
    of ``vol`` voxels spanning ``extent`` world units, a square detector
    of ``det`` pixels that covers the volume's projection ``det_margin``
    times, on a full circle of ``n_proj`` views."""
    n, det = int(scan["vol"]), int(scan["det"])
    sad, sdd = float(scan["sad"]), float(scan["sdd"])
    vox = float(scan["extent"]) / n
    du = float(scan["extent"]) * (sdd / sad) * float(scan["det_margin"]) / det
    return {"nx": n, "ny": n, "nz": n, "nw": det, "nh": det,
            "n_proj": int(scan["n_proj"]), "sad": sad, "sdd": sdd,
            "voxel_size": (vox, vox, vox), "det_spacing": (du, du)}


def projection_matrices(g: dict) -> np.ndarray:
    """(n_proj, 3, 4) float32 index-space matrices: source on a circle of
    radius ``sad`` in the z = 0 plane, detector axis v along world z,
    volume and detector centred."""
    d, D = g["sad"], g["sdd"]
    sx, sy, sz = g["voxel_size"]
    du, dv = g["det_spacing"]
    cx, cy, cz = ((g[a] - 1) / 2.0 for a in ("nx", "ny", "nz"))
    cu, cv = (g["nw"] - 1) / 2.0, (g["nh"] - 1) / 2.0
    mats = []
    for t in np.linspace(0.0, 2.0 * math.pi, g["n_proj"], endpoint=False):
        ct, st = math.cos(t), math.sin(t)
        rz = np.array([-sx * ct, -sy * st, 0.0, d + cx * sx * ct + cy * sy * st])
        ru = (D / du) * np.array([-sx * st, sy * ct, 0.0,
                                  cx * sx * st - cy * sy * ct])
        rv = (D / dv) * np.array([0.0, 0.0, sz, -cz * sz])
        mats.append(np.stack([ru + cu * rz, rv + cv * rz, rz]))
    return np.asarray(mats, np.float32)


def ramp_matrix(g: dict) -> np.ndarray:
    """(nw, nw) float32 ``T`` with ``row @ T`` the scaled ramp filter of a
    detector row: ``T[m, u] = scale * h[u - m]``."""
    nw = g["nw"]
    du_virt = g["det_spacing"][0] * g["sad"] / g["sdd"]
    lag = np.arange(nw)[None, :] - np.arange(nw)[:, None]
    h = np.zeros(lag.shape)
    h[lag == 0] = 1.0 / (4.0 * du_virt * du_virt)
    odd = lag % 2 != 0
    h[odd] = -1.0 / (math.pi * lag[odd] * du_virt) ** 2
    scale = 0.5 * (2.0 * math.pi / g["n_proj"]) * du_virt * g["sad"] ** 2
    return (h * scale).astype(np.float32)


def cosine_weights(g: dict) -> np.ndarray:
    """(nh, nw) float32 cosine pre-weights at the physical detector."""
    du, dv = g["det_spacing"]
    u = (np.arange(g["nw"]) - (g["nw"] - 1) / 2.0) * du
    v = (np.arange(g["nh"]) - (g["nh"] - 1) / 2.0) * dv
    D = g["sdd"]
    return (D / np.sqrt(D * D + u[None, :] ** 2 + v[:, None] ** 2)
            ).astype(np.float32)


def round_to_bfloat16(x):
    """float32 ``x`` rounded to the nearest bfloat16 (ties to even), kept
    in float32. Done on the bits: a float32 -> bfloat16 -> float32 round
    trip of converts may be folded away by the TPU's compiler, which is
    free to keep excess precision."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


@functools.lru_cache(maxsize=None)
def _block_program(nh: int, nw: int, store: str):
    import jax
    import jax.numpy as jnp

    def block(raw, cosw, ramp, mats, ijk):
        """Filter a block of views, then add their Listing-1 samples at
        the voxels ``ijk`` ((3, n) float32 indices i, j, k)."""
        w = raw * cosw[None]
        filt = jnp.matmul(w, ramp, precision=jax.lax.Precision.HIGHEST)
        if store == "bfloat16":
            filt = round_to_bfloat16(filt)
        i, j, k = ijk[0][None], ijk[1][None], ijk[2][None]

        def dot(r):
            m = mats[:, r]
            return (m[:, 0:1] * i + m[:, 1:2] * j + m[:, 2:3] * k
                    + m[:, 3:4])

        z = dot(2)
        f = 1.0 / z
        x = dot(0) * f
        y = dot(1) * f
        x0, y0 = jnp.floor(x), jnp.floor(y)
        ix, iy = x0.astype(jnp.int32), y0.astype(jnp.int32)
        dx, dy = x - x0, y - y0
        ok = (ix >= 0) & (ix <= nw - 2) & (iy >= 0) & (iy <= nh - 2) & (z > 0)
        ix, iy = jnp.clip(ix, 0, nw - 2), jnp.clip(iy, 0, nh - 2)
        flat = filt.reshape(-1)
        base = (jnp.arange(filt.shape[0])[:, None] * nh + iy) * nw + ix
        v00, v01 = flat[base], flat[base + 1]
        v10, v11 = flat[base + nw], flat[base + nw + 1]
        s0 = v00 * (1.0 - dx) + v01 * dx
        s1 = v10 * (1.0 - dx) + v11 * dx
        val = s0 * (1.0 - dy) + s1 * dy
        return jnp.sum(jnp.where(ok, val * f * f, 0.0), axis=0)

    return jax.jit(block)


def fdk_at(projections: np.ndarray, g: dict, ijk: np.ndarray,
           store: str = "float32") -> np.ndarray:
    """FDK of (n_proj, nh, nw) raw ``projections`` at the voxels ``ijk``
    ((n, 3) ints (i, j, k)); float64 (n,) values on the host."""
    import jax.numpy as jnp
    nh, nw = g["nh"], g["nw"]
    prog = _block_program(nh, nw, store)
    cosw = jnp.asarray(cosine_weights(g))
    ramp = jnp.asarray(ramp_matrix(g))
    mats = projection_matrices(g)
    ijk_t = jnp.asarray(np.asarray(ijk, np.float32).T)
    acc = jnp.zeros((len(ijk),), jnp.float32)
    for s in range(0, g["n_proj"], VIEW_BLOCK):
        e = min(s + VIEW_BLOCK, g["n_proj"])
        acc = acc + prog(jnp.asarray(projections[s:e]), cosw, ramp,
                         jnp.asarray(mats[s:e]), ijk_t)
    return np.asarray(acc, np.float64)


def rel_rmse(got: np.ndarray, ref: np.ndarray) -> float:
    """The paper's measure: RMSE over the reference's peak magnitude."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.sqrt(np.mean((got - ref) ** 2))
                 / max(float(np.abs(ref).max()), 1e-30))
