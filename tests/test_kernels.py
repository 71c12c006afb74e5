"""Pallas kernels vs the pure-jnp oracle: shape sweeps + unit stages."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import projection_matrices, standard_geometry, \
    translate_matrices, transpose_projections
from repro.kernels import backproject_onehot, backproject_ref, \
    backproject_subline
from repro.kernels.ref import subline_blend_ref

from conftest import rel_rmse

BAR = 1e-5


def _case(n, det, nproj, seed=0):
    geom = standard_geometry(n=n, n_det=det, n_proj=nproj)
    rng = np.random.RandomState(seed)
    img = jnp.asarray(rng.rand(nproj, geom.nh, geom.nw).astype(np.float32))
    img_t = transpose_projections(img)
    mats = projection_matrices(geom)
    ref = backproject_ref(img_t, mats, geom.volume_shape_xyz)
    return geom, img_t, mats, ref


# shape sweep: even/odd volumes, non-square detectors, varied np.
# Interpret-mode Pallas runs the kernel body in Python, so each case
# costs ~5-7 s: the redundant even case and the extra edge cases are
# `slow` (opt in with -m slow); the default tier-1 run keeps one even
# and the odd-everything case, which cover the padding + odd-nz paths.
SWEEP = [
    (16, 24, 6),
    pytest.param(16, 16, 4, marks=pytest.mark.slow),
    (13, 17, 5),     # odd everything (padding + odd-nz symmetry path)
    pytest.param(8, 32, 3, marks=pytest.mark.slow),
    pytest.param(20, 12, 7,          # detector smaller (heavy masking)
                 marks=pytest.mark.slow),
]


@pytest.mark.parametrize("n,det,nproj", SWEEP)
def test_subline_kernel_sweep(n, det, nproj):
    geom, img_t, mats, ref = _case(n, det, nproj)
    out = backproject_subline(img_t, mats, geom.volume_shape_xyz,
                              block=(4, 8))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=BAR * max(1e-9, float(np.abs(ref).max())),
                               rtol=0)
    assert rel_rmse(out, ref) < BAR


@pytest.mark.parametrize("n,det,nproj", SWEEP[:3])
def test_onehot_kernel_sweep(n, det, nproj):
    geom, img_t, mats, ref = _case(n, det, nproj)
    out = backproject_onehot(img_t, mats, geom.volume_shape_xyz,
                             block=(4, 8), k_chunk=8)
    assert rel_rmse(out, ref) < BAR


@pytest.mark.parametrize("kernel", ["subline", "onehot"])
def test_kernel_full_k_chunks(kernel):
    """Lines of 300 voxels: two whole 128-wide k chunks (the first in
    the direct half, the second across the mirror) and a 44-voxel tail.
    The (8, 8) lines are those around the axis of a 300^3 volume, moved
    there by shifting the matrices' translation column."""
    n, det, nproj = 300, 300, 3
    geom = standard_geometry(n=n, n_det=det, n_proj=nproj)
    rng = np.random.RandomState(3)
    img_t = transpose_projections(jnp.asarray(
        rng.rand(nproj, geom.nh, geom.nw).astype(np.float32)))
    mats = np.array(projection_matrices(geom))
    mats[:, :, 3] += (mats[:, :, 0] + mats[:, :, 1]) * (n // 2 - 4)
    mats = jnp.asarray(mats)
    shape = (8, 8, n)
    ref = backproject_ref(img_t, mats, shape)
    if kernel == "subline":
        out = backproject_subline(img_t, mats, shape, block=(8, 8))
    else:
        out = backproject_onehot(img_t, mats, shape, block=(8, 8),
                                 k_chunk=128)
    assert float(np.abs(np.asarray(ref)).min()) > 0
    assert rel_rmse(out, ref) < BAR


@pytest.mark.parametrize("kernel", ["subline", "onehot"])
def test_kernel_origin_keeps_the_whole_volumes_edge_samples(kernel):
    """A sub-box called with ``origin`` reads what the whole volume reads
    there, down to a sample on the detector's last column; the same box
    with its origin folded into the matrices does not. The matrix puts
    line (13, 5) at column 23.0 of a 24-column detector in float32, just
    past the last interpolable position (dropped), while the fold's
    rounding of the constant column moves it to 22.999998 (kept)."""
    f32 = np.float32
    nw, nh, shape = 24, 16, (8, 8, 8)
    mat = np.zeros((1, 3, 4), np.float32)
    mat[0, 0] = [f32(0.9899673461914062), f32(0.027058102190494537), 0,
                 f32(9.995133399963379)]
    mat[0, 1, 3] = 5.5
    mat[0, 2, 3] = 1.0
    mat = jnp.asarray(mat)
    img_t = jnp.ones((1, nw, nh), jnp.float32)
    call = backproject_subline if kernel == "subline" else backproject_onehot
    whole = np.asarray(call(img_t, mat, (16, 8, 8), block=(8, 8)))
    box = np.asarray(call(img_t, mat, shape, block=(8, 8),
                          origin=jnp.asarray([8, 0], jnp.int32)))
    folded = np.asarray(call(img_t, translate_matrices(mat, 8.0, 0.0),
                             shape, block=(8, 8)))
    np.testing.assert_allclose(box, whole[8:], rtol=0, atol=1e-6)
    assert (whole[13, 5] == 0).all()
    assert (folded[5, 5] == 1).all()


@pytest.mark.parametrize("block", [
    (1, 8), (2, 8),
    pytest.param((4, 16), marks=pytest.mark.slow),   # ~9 s each in
    pytest.param((8, 8), marks=pytest.mark.slow),    # interpret mode
])
def test_subline_kernel_block_shapes(block):
    geom, img_t, mats, ref = _case(16, 24, 4)
    out = backproject_subline(img_t, mats, geom.volume_shape_xyz,
                              block=block)
    assert rel_rmse(out, ref) < BAR


def test_kernels_agree_with_each_other():
    geom, img_t, mats, _ = _case(16, 24, 6, seed=7)
    a = backproject_subline(img_t, mats, geom.volume_shape_xyz)
    b = backproject_onehot(img_t, mats, geom.volume_shape_xyz, k_chunk=4)
    assert rel_rmse(a, b) < 1e-6


def test_subline_blend_stage():
    """Fig. 3a stage in isolation: blend of two detector columns."""
    rng = np.random.RandomState(1)
    img_ts = jnp.asarray(rng.rand(12, 9).astype(np.float32))
    x = jnp.asarray([0.25, 3.75, 10.999, 0.0, 11.0])
    out = subline_blend_ref(img_ts, x)
    # manual check for x = 3.75
    expected = 0.25 * np.asarray(img_ts)[3] + 0.75 * np.asarray(img_ts)[4]
    np.testing.assert_allclose(np.asarray(out)[1], expected, rtol=1e-6)


def test_kernel_against_ct_pipeline():
    """Kernel output matches the pure-JAX variant inside FDK."""
    from repro.core import fdk_reconstruct
    from repro.core.forward import forward_project
    from repro.core.phantom import shepp_logan_3d

    geom = standard_geometry(n=16, n_det=24, n_proj=12)
    vol = jnp.asarray(shepp_logan_3d(16))
    projs = forward_project(vol, geom, oversample=1.0)
    rec_jax = fdk_reconstruct(projs, geom, variant="algorithm1_mp", nb=4)
    rec_pl = fdk_reconstruct(projs, geom, variant="subline_pl")
    assert rel_rmse(rec_pl, rec_jax) < BAR


@pytest.mark.parametrize("n,det,nproj,bw", [
    (16, 24, 6, 8),
    pytest.param(16, 48, 4, 16, marks=pytest.mark.slow),
    (13, 17, 5, 8),
])
def test_banded_kernel_sweep(n, det, nproj, bw):
    """Beyond-paper banded scalar-prefetch kernel vs the oracle."""
    # import via ops: the submodule of the same name shadows the package
    # re-export once any test touches repro.kernels.backproject_banded
    from repro.kernels.ops import backproject_banded
    geom, img_t, mats, ref = _case(n, det, nproj, seed=11)
    out = backproject_banded(img_t, mats, geom.volume_shape_xyz,
                             block=(4, 8), bw=bw)
    assert rel_rmse(out, ref) < BAR


def test_banded_band_selection_covers_all_tiles():
    """Corner-derived bands must cover every tile's x-extent (linear-
    fractional extrema at corners)."""
    import numpy as np
    from repro.core import projection_matrices, standard_geometry
    from repro.kernels.backproject_banded import tile_bands
    geom = standard_geometry(n=32, n_det=48, n_proj=8)
    mats = np.asarray(projection_matrices(geom))
    bw = 16
    n_bands = -(-geom.nw // bw)
    band, span = tile_bands(mats, 32, 32, 4, 8, bw, n_bands, geom.nw)
    assert band.shape == (8, 8, 4)
    assert band.min() >= 0 and band.max() < n_bands
    # exhaustive check: every voxel's x falls inside its tile's band
    for s in range(8):
        m = mats[s].astype(np.float64)
        i = np.arange(32)[:, None]
        j = np.arange(32)[None, :]
        z = m[2, 0] * i + m[2, 1] * j + m[2, 3]
        x = (m[0, 0] * i + m[0, 1] * j + m[0, 3]) / z
        for ti in range(8):
            for tj in range(4):
                xt = x[ti * 4:(ti + 1) * 4, tj * 8:(tj + 1) * 8]
                xt = np.clip(xt, 0, geom.nw - 1)
                lo = band[s, ti, tj] * bw
                assert xt.min() >= lo - 1e-6
                assert xt.max() <= lo + 2 * bw - 1 + 1e-6
