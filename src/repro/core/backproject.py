"""The paper's optimization ladder as composable pure-JAX back-projectors.

Every variant below consumes the *transposed* layouts introduced in §3.1.1:

    img_t:  (np, nw, nh)   img_t[s][x][y]  — detector columns contiguous
    mat:    (np, 3, 4)     index-space projection matrices
    vol_t:  (nx, ny, nz)   vol_t[i][j][k]  — Z contiguous (lane axis on TPU)

and must match ``baseline.backproject_rtk`` (after layout transposes) to
RMSE < 1e-5 — the paper's own validation criterion against RTK.

Ladder (paper Table 2):

    transpose   O1: layouts only
    share       O1+O2: hoist F/W/X out of the k loop
    symmetry    O1+O2+O3: y-dot for half the k range, mirror the rest
    subline     O1+O2+O4: two-stage interpolation through sMem
    subline_symmetry_batch
                O1..O5 = the paper's Algorithm 1 (symmetry_pf analogue);
                O6 (prefetch/double-buffer) exists only in the Pallas kernel,
                where the pallas_call pipeline provides it structurally.

These pure-JAX forms are (a) the oracles for the Pallas kernels, (b) the
variants benchmarked against each other in benchmarks/ (the Fig. 7/8
analogue): the FLOP and byte reductions of O2/O3/O5 are directly visible in
``cost_analysis`` of the jitted functions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Layout helpers (O1)
# --------------------------------------------------------------------------

def transpose_projections(img: jnp.ndarray) -> jnp.ndarray:
    """(np, nh, nw) -> (np, nw, nh)."""
    return jnp.swapaxes(img, 1, 2)


def volume_to_native(vol_t: jnp.ndarray) -> jnp.ndarray:
    """(nx, ny, nz) -> (nz, ny, nx)."""
    return jnp.transpose(vol_t, (2, 1, 0))


def volume_to_transposed(vol: jnp.ndarray) -> jnp.ndarray:
    """(nz, ny, nx) -> (nx, ny, nz)."""
    return jnp.transpose(vol, (2, 1, 0))


# --------------------------------------------------------------------------
# Shared pieces
# --------------------------------------------------------------------------

def _ij_grids(ni: int, nj: int, dtype=jnp.float32):
    i = jnp.arange(ni, dtype=dtype)[:, None]   # (ni, 1)
    j = jnp.arange(nj, dtype=dtype)[None, :]   # (1, nj)
    return i, j


def hoisted_fwx(mat_s: jnp.ndarray, ni: int, nj: int):
    """O2: the k-invariant per-(i,j) quantities for one projection.

    Returns F = 1/z, W = F*F, X = x (detector column), each (ni, nj).
    Exactness relies on mat_s[0,2] == mat_s[2,2] == 0, which geometry.py
    guarantees (V axis parallel to Z).
    """
    i, j = _ij_grids(ni, nj)
    z = mat_s[2, 0] * i + mat_s[2, 1] * j + mat_s[2, 3]
    f = 1.0 / z
    x = (mat_s[0, 0] * i + mat_s[0, 1] * j + mat_s[0, 3]) * f
    return f, f * f, x, z


def _y_coeffs(mat_s: jnp.ndarray, f: jnp.ndarray, ni: int, nj: int):
    """y(i,j,k) = a + b*k with a,b per-(i,j) — affine in k (O2)."""
    i, j = _ij_grids(ni, nj)
    a = (mat_s[1, 0] * i + mat_s[1, 1] * j + mat_s[1, 3]) * f
    b = mat_s[1, 2] * f
    return a, jnp.broadcast_to(b, a.shape)


def _interp_by_hat() -> bool:
    """Whether :func:`_interp_column` sums the interpolation hat instead
    of gathering: on a TPU. XLA lowers a per-element gather there to
    ~20 ns per element from HBM, while the hat is a fused VPU reduction
    (one TPU v5e, P5 step of 128x128x512 voxels and 512 views: 188 s
    gathered, 3.7 s as the hat). On the CPU the gather is the cheaper
    form: the hat costs nh multiply-adds per sample."""
    return jax.default_backend() == "tpu"


def _interp_column(sm: jnp.ndarray, y: jnp.ndarray, nh: int):
    """1-D interpolation inside the sub-line buffer (Fig. 3b).

    sm: (..., nh) sub-line values; y: (..., nk) fractional row coords.
    Returns (vals, valid) of shape (..., nk).

    The hat form sums max(0, 1 - |n - y|) against every row n of the
    sub-line: where ``valid`` holds, only rows floor(y) and floor(y)+1
    weigh, with 1-dy and dy, as in the gather form.
    """
    y0 = jnp.floor(y)
    iy = y0.astype(jnp.int32)
    valid = (iy >= 0) & (iy <= nh - 2)
    if _interp_by_hat():
        n = jnp.arange(sm.shape[-1], dtype=jnp.float32)[:, None]
        hat = jnp.maximum(0.0, 1.0 - jnp.abs(n - y[..., None, :]))
        return jnp.sum(sm[..., :, None] * hat, axis=-2), valid
    dy = y - y0
    iyc = jnp.clip(iy, 0, nh - 2)
    s0 = jnp.take_along_axis(sm, iyc, axis=-1)
    s1 = jnp.take_along_axis(sm, iyc + 1, axis=-1)
    return s0 * (1.0 - dy) + s1 * dy, valid


def _subline_buffer(img_ts: jnp.ndarray, x: jnp.ndarray, nw: int):
    """O4 stage one: blend detector columns floor(x), floor(x)+1 (Fig. 3a).

    img_ts: (nw, nh) one transposed projection; x: (ni, nj).
    Returns (sMem (ni, nj, nh), x_valid (ni, nj)).
    """
    x0 = jnp.floor(x)
    ix = x0.astype(jnp.int32)
    dx = x - x0
    x_valid = (ix >= 0) & (ix <= nw - 2)
    ixc = jnp.clip(ix, 0, nw - 2)
    col0 = jnp.take(img_ts, ixc, axis=0)       # (ni, nj, nh)
    col1 = jnp.take(img_ts, ixc + 1, axis=0)   # (ni, nj, nh)
    return col0 * (1.0 - dx)[..., None] + col1 * dx[..., None], x_valid


# --------------------------------------------------------------------------
# O1: transpose only — per-voxel math identical to the baseline
# --------------------------------------------------------------------------

def _bp_transpose_single(img_ts: jnp.ndarray, mat_s: jnp.ndarray, vol_shape_xyz):
    ni, nj, nk = vol_shape_xyz
    nw, nh = img_ts.shape
    i = jnp.arange(ni, dtype=jnp.float32)[:, None, None]
    j = jnp.arange(nj, dtype=jnp.float32)[None, :, None]
    k = jnp.arange(nk, dtype=jnp.float32)[None, None, :]
    z = mat_s[2, 0] * i + mat_s[2, 1] * j + mat_s[2, 2] * k + mat_s[2, 3]
    f = 1.0 / z
    x = (mat_s[0, 0] * i + mat_s[0, 1] * j + mat_s[0, 2] * k + mat_s[0, 3]) * f
    y = (mat_s[1, 0] * i + mat_s[1, 1] * j + mat_s[1, 2] * k + mat_s[1, 3]) * f
    # Bilinear on the transposed image: img_t[x][y].
    x0 = jnp.floor(x); y0 = jnp.floor(y)
    ix = x0.astype(jnp.int32); iy = y0.astype(jnp.int32)
    dx = x - x0; dy = y - y0
    valid = (ix >= 0) & (ix <= nw - 2) & (iy >= 0) & (iy <= nh - 2) & (z > 0)
    ixc = jnp.clip(ix, 0, nw - 2); iyc = jnp.clip(iy, 0, nh - 2)
    v00 = img_ts[ixc, iyc]
    v10 = img_ts[ixc + 1, iyc]
    v01 = img_ts[ixc, iyc + 1]
    v11 = img_ts[ixc + 1, iyc + 1]
    s0 = v00 * (1.0 - dx) + v10 * dx
    s1 = v01 * (1.0 - dx) + v11 * dx
    val = s0 * (1.0 - dy) + s1 * dy
    return jnp.where(valid, val * f * f, 0.0)


@functools.partial(jax.jit, static_argnames=("vol_shape_xyz",))
def bp_transpose(img_t, mat, vol_shape_xyz):
    def body(s, vol):
        return vol + _bp_transpose_single(img_t[s], mat[s], vol_shape_xyz)
    vol0 = jnp.zeros(vol_shape_xyz, jnp.float32)
    return jax.lax.fori_loop(0, img_t.shape[0], body, vol0)


# --------------------------------------------------------------------------
# O1+O2: hoisting F/W/X
# --------------------------------------------------------------------------

def _bp_share_single(img_ts, mat_s, vol_shape_xyz):
    ni, nj, nk = vol_shape_xyz
    nw, nh = img_ts.shape
    f, w, x, z = hoisted_fwx(mat_s, ni, nj)
    a, b = _y_coeffs(mat_s, f, ni, nj)
    k = jnp.arange(nk, dtype=jnp.float32)
    y = a[..., None] + b[..., None] * k           # (ni, nj, nk)
    # Interpolation still per-point (no subline yet): gather 4 corners.
    x0 = jnp.floor(x); ix = x0.astype(jnp.int32); dx = x - x0
    x_valid = (ix >= 0) & (ix <= nw - 2) & (z > 0)
    ixc = jnp.clip(ix, 0, nw - 2)
    y0 = jnp.floor(y); iy = y0.astype(jnp.int32); dy = y - y0
    y_valid = (iy >= 0) & (iy <= nh - 2)
    iyc = jnp.clip(iy, 0, nh - 2)
    flat = img_ts.reshape(-1)
    v00 = flat[(ixc[..., None] * nh + iyc)]
    v10 = flat[((ixc + 1)[..., None] * nh + iyc)]
    v01 = flat[(ixc[..., None] * nh + iyc + 1)]
    v11 = flat[((ixc + 1)[..., None] * nh + iyc + 1)]
    s0 = v00 * (1.0 - dx)[..., None] + v10 * dx[..., None]
    s1 = v01 * (1.0 - dx)[..., None] + v11 * dx[..., None]
    val = s0 * (1.0 - dy) + s1 * dy
    ok = x_valid[..., None] & y_valid
    return jnp.where(ok, val * w[..., None], 0.0)


@functools.partial(jax.jit, static_argnames=("vol_shape_xyz",))
def bp_share(img_t, mat, vol_shape_xyz):
    def body(s, vol):
        return vol + _bp_share_single(img_t[s], mat[s], vol_shape_xyz)
    vol0 = jnp.zeros(vol_shape_xyz, jnp.float32)
    return jax.lax.fori_loop(0, img_t.shape[0], body, vol0)


# --------------------------------------------------------------------------
# O1+O2+O4: subline interpolation
# --------------------------------------------------------------------------

def _bp_subline_single(img_ts, mat_s, vol_shape_xyz):
    ni, nj, nk = vol_shape_xyz
    nw, nh = img_ts.shape
    f, w, x, z = hoisted_fwx(mat_s, ni, nj)
    sm, x_valid = _subline_buffer(img_ts, x, nw)  # (ni, nj, nh)
    a, b = _y_coeffs(mat_s, f, ni, nj)
    k = jnp.arange(nk, dtype=jnp.float32)
    y = a[..., None] + b[..., None] * k
    val, y_valid = _interp_column(sm, y, nh)
    ok = (x_valid & (z > 0))[..., None] & y_valid
    return jnp.where(ok, val * w[..., None], 0.0)


@functools.partial(jax.jit, static_argnames=("vol_shape_xyz",))
def bp_subline(img_t, mat, vol_shape_xyz):
    def body(s, vol):
        return vol + _bp_subline_single(img_t[s], mat[s], vol_shape_xyz)
    vol0 = jnp.zeros(vol_shape_xyz, jnp.float32)
    return jax.lax.fori_loop(0, img_t.shape[0], body, vol0)


def _nb_batched_scan(single_fn, img_t, mat, vol_shape_xyz, nb):
    """Shared O5 scaffold: scan over nb-batches of projections, vmap the
    in-batch contributions (partial sums stay in registers/VMEM), update
    the volume ONCE per batch — the 1/nb write-traffic reduction of
    §3.1.3. np must be divisible by nb (pad upstream via
    tiling.pad_projection_batch)."""
    n_proj = img_t.shape[0]
    assert n_proj % nb == 0, f"np={n_proj} not divisible by nb={nb}"
    img_b = img_t.reshape(n_proj // nb, nb, *img_t.shape[1:])
    mat_b = mat.reshape(n_proj // nb, nb, 3, 4)

    def body(vol, xs):
        img_bt, mat_bt = xs
        per = jax.vmap(single_fn)(img_bt, mat_bt)
        return vol + per.sum(axis=0), None

    vol0 = jnp.zeros(vol_shape_xyz, jnp.float32)
    vol, _ = jax.lax.scan(body, vol0, (img_b, mat_b))
    return vol


@functools.partial(jax.jit, static_argnames=("vol_shape_xyz", "nb"))
def bp_subline_batch(img_t, mat, vol_shape_xyz, nb: int = 8):
    """O1+O2+O4+O5: nb-batched subline WITHOUT the O3 mirror.

    The symmetry-free member of the batched family: exact on ANY
    translated sub-box of the volume (the O3 pairing k <-> nk-1-k is
    only meaningful when the box is centered on the volume's Z midplane),
    so the tiled engine uses it as the slab-safe fallback for arbitrary
    Z-slabs.
    """
    return _nb_batched_scan(
        lambda im, mm: _bp_subline_single(im, mm, vol_shape_xyz),
        img_t, mat, vol_shape_xyz, nb)


# --------------------------------------------------------------------------
# O1+O2+O3(+O4): symmetry — y-dot for k < nz/2 only, mirror the rest
# --------------------------------------------------------------------------

def _bp_symmetry_single(img_ts, mat_s, vol_shape_xyz, *, use_subline: bool):
    ni, nj, nk = vol_shape_xyz
    # Uneven half-split (matches the Pallas kernels): k in [0, khp)
    # computed directly — including the self-mirrored middle plane when
    # nk is odd — and k in [khp, nk) filled from the O3 mirror.
    kh = nk // 2           # mirrored half
    khp = nk - kh          # direct half (== kh, or kh+1 when nk odd)
    nw, nh = img_ts.shape
    f, w, x, z = hoisted_fwx(mat_s, ni, nj)
    a, b = _y_coeffs(mat_s, f, ni, nj)
    # O3 as a hoisted affine fold. The mirror identity gives the upper
    # half's row coordinate as y'(k) = (nh-1) - y(nk-1-k), which is
    # itself affine in k with the SAME slope b:
    #     y'(k) = (nh-1) - a - b*(nk-1) + b*k = a_m + b*k.
    # So the y dot-product runs once (for ``a``), the mirrored half
    # reuses it through the k-invariant intercept a_m, and BOTH halves
    # evaluate as ONE fused select+FMA over the full k range. The
    # previous formulation (compute the lower half, flip, concatenate)
    # de-fused the XLA CPU lowering and made symmetry_mp 2x SLOWER than
    # share_mp (BENCH_PR2 0.48x); this form is exact to ~1e-11 against
    # it and removes the flip/concat entirely.
    a_m = (nh - 1.0) - a - b * (nk - 1.0)
    k = jnp.arange(nk, dtype=jnp.float32)
    direct = k < khp       # lower half + middle plane: the direct dot
    y = jnp.where(direct, a[..., None], a_m[..., None]) + b[..., None] * k
    if use_subline:
        sm, x_valid = _subline_buffer(img_ts, x, nw)
        val, y_valid = _interp_column(sm, y, nh)
    else:
        # Per-point 4-corner gathers, shared x columns.
        x0 = jnp.floor(x); ix = x0.astype(jnp.int32); dx = x - x0
        x_valid = (ix >= 0) & (ix <= nw - 2)
        ixc = jnp.clip(ix, 0, nw - 2)
        flat = img_ts.reshape(-1)
        y0 = jnp.floor(y); iy = y0.astype(jnp.int32); dy = y - y0
        y_valid = (iy >= 0) & (iy <= nh - 2)
        iyc = jnp.clip(iy, 0, nh - 2)
        v00 = flat[(ixc[..., None] * nh + iyc)]
        v10 = flat[((ixc + 1)[..., None] * nh + iyc)]
        v01 = flat[(ixc[..., None] * nh + iyc + 1)]
        v11 = flat[((ixc + 1)[..., None] * nh + iyc + 1)]
        s0 = v00 * (1.0 - dx)[..., None] + v10 * dx[..., None]
        s1 = v01 * (1.0 - dx)[..., None] + v11 * dx[..., None]
        val = s0 * (1.0 - dy) + s1 * dy
    ok = (x_valid & (z > 0))[..., None] & y_valid
    return jnp.where(ok, val * w[..., None], 0.0)


@functools.partial(jax.jit, static_argnames=("vol_shape_xyz",))
def bp_symmetry(img_t, mat, vol_shape_xyz):
    def body(s, vol):
        return vol + _bp_symmetry_single(
            img_t[s], mat[s], vol_shape_xyz, use_subline=False)
    vol0 = jnp.zeros(vol_shape_xyz, jnp.float32)
    return jax.lax.fori_loop(0, img_t.shape[0], body, vol0)


# --------------------------------------------------------------------------
# O1..O5: the paper's Algorithm 1 — subline + symmetry + nb batching
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("vol_shape_xyz", "nb"))
def bp_subline_symmetry_batch(img_t, mat, vol_shape_xyz, nb: int = 8):
    """Paper Algorithm 1 semantics in pure JAX.

    Projections are processed in batches of ``nb`` (the shared
    ``_nb_batched_scan`` scaffold); within a batch the partial sums
    accumulate in values (registers/VMEM on TPU), and the volume is
    updated ONCE per batch — the 1/nb write-traffic reduction of §3.1.3.
    """
    return _nb_batched_scan(
        lambda im, mm: _bp_symmetry_single(im, mm, vol_shape_xyz,
                                           use_subline=True),
        img_t, mat, vol_shape_xyz, nb)
