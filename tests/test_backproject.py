"""The paper's optimization ladder: every variant must match the RTK
baseline to the paper's own validation bar (RMSE < 1e-5 relative)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    backproject_rtk, bp_share, bp_subline, bp_subline_symmetry_batch,
    bp_symmetry, bp_transpose, projection_matrices, standard_geometry,
    transpose_projections, volume_to_transposed,
)
from repro.core.variants import VARIANTS, get_variant

from conftest import rel_rmse

BAR = 1e-5  # paper §4.2


@pytest.fixture(scope="module")
def ref(small_geom, small_ct_data):
    img, mats = small_ct_data
    vol = backproject_rtk(img, mats, small_geom.volume_shape_zyx)
    return volume_to_transposed(vol)


@pytest.mark.parametrize("fn", [bp_transpose, bp_share, bp_symmetry,
                                bp_subline])
def test_ladder_matches_baseline(fn, small_geom, small_ct_data, ref):
    img, mats = small_ct_data
    img_t = transpose_projections(img)
    out = fn(img_t, mats, small_geom.volume_shape_xyz)
    assert rel_rmse(out, ref) < BAR


@pytest.fixture
def hat_interp(monkeypatch):
    """Trace the TPU's sub-line interpolation (the hat sum) on the CPU."""
    from repro.core import backproject as bp
    monkeypatch.setattr(bp, "_interp_by_hat", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fn", [bp_subline, bp_subline_symmetry_batch])
def test_hat_interpolation_matches_baseline(fn, hat_interp, small_geom,
                                            small_ct_data, ref):
    img, mats = small_ct_data
    img_t = transpose_projections(img)
    out = fn(img_t, mats, small_geom.volume_shape_xyz)
    assert rel_rmse(out, ref) < BAR


@pytest.mark.parametrize("nb", [1, 2, 4, 8])
def test_algorithm1_all_batch_sizes(nb, small_geom, small_ct_data, ref):
    img, mats = small_ct_data
    img_t = transpose_projections(img)
    out = bp_subline_symmetry_batch(img_t, mats,
                                    small_geom.volume_shape_xyz, nb=nb)
    assert rel_rmse(out, ref) < BAR


def test_batching_is_numerically_stable_across_nb(small_geom,
                                                  small_ct_data):
    """O5 changes only summation order: results across nb agree."""
    img, mats = small_ct_data
    img_t = transpose_projections(img)
    outs = [bp_subline_symmetry_batch(img_t, mats,
                                      small_geom.volume_shape_xyz, nb=nb)
            for nb in (1, 4, 8)]
    for o in outs[1:]:
        assert rel_rmse(o, outs[0]) < 1e-6


def test_variant_registry_complete(small_geom, small_ct_data, ref):
    img, mats = small_ct_data
    img_t = transpose_projections(img)
    for name in VARIANTS:
        fn = get_variant(name)
        out = fn(img_t, mats, small_geom.volume_shape_xyz, nb=4)
        assert rel_rmse(out, ref) < BAR, name


def test_projection_partition_additivity(small_geom, small_ct_data):
    """BP over a disjoint partition of projections sums to BP over all —
    the invariant that makes nb batching and pod-sharding correct."""
    img, mats = small_ct_data
    img_t = transpose_projections(img)
    full = bp_subline(img_t, mats, small_geom.volume_shape_xyz)
    part = (bp_subline(img_t[:3], mats[:3], small_geom.volume_shape_xyz)
            + bp_subline(img_t[3:], mats[3:], small_geom.volume_shape_xyz))
    assert rel_rmse(part, full) < 1e-6


def test_linearity_in_projections(small_geom, small_ct_data):
    img, mats = small_ct_data
    img_t = transpose_projections(img)
    shape = small_geom.volume_shape_xyz
    a = bp_subline(img_t, mats, shape)
    b = bp_subline(2.5 * img_t, mats, shape)
    assert rel_rmse(b, 2.5 * np.asarray(a)) < 1e-6


def test_zero_projections_give_zero_volume(small_geom, small_ct_data):
    img, mats = small_ct_data
    img_t = jnp.zeros_like(transpose_projections(img))
    out = bp_subline(img_t, mats, small_geom.volume_shape_xyz)
    assert float(jnp.abs(out).max()) == 0.0


def test_translated_matrices_equal_offset_volume(small_geom,
                                                 small_ct_data):
    """Tiling correctness: back-projecting a sub-slab with translated
    matrices equals the corresponding slab of the full volume."""
    from repro.core.tiling import translate_matrices
    img, mats = small_ct_data
    img_t = transpose_projections(img)
    full = bp_subline(img_t, mats, small_geom.volume_shape_xyz)
    i0, j0 = 4, 8
    bi, bj = 8, 8
    mats_t = translate_matrices(mats, float(i0), float(j0))
    slab = bp_subline(img_t, mats_t, (bi, bj, small_geom.nz))
    assert rel_rmse(slab, np.asarray(full)[i0:i0 + bi, j0:j0 + bj]) < 1e-6
