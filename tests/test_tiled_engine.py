"""Tiled streaming engine vs the untiled reference (runtime/engine.py).

Parity bar: TiledReconstructor must match the RTK baseline to
rel-RMSE < 1e-5 for EVERY registered variant, at tile configurations
that do NOT evenly divide the volume (odd (i, j)-tiles, odd Z-slabs) —
the exactness of matrix translation plus the mirror-paired Z schedule
is the whole correctness story of the engine.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (projection_matrices, standard_geometry,
                        transpose_projections)
from repro.core import backproject as bp
from repro.core.baseline import backproject_rtk
from repro.core.tiling import (TileSpec, make_tiles, pad_projection_batch,
                               pick_tile_shape, plan_z_units,
                               tile_working_set_bytes, translate_matrices)
from repro.core.variants import VARIANTS, slab_safe_variant, uses_symmetry
from repro.runtime.engine import TiledReconstructor

from conftest import rel_rmse

BAR = 1e-5

# 16^3 volume, 5x7 (i, j)-tiles and odd Z-slabs: nothing divides evenly,
# so edge tiles shrink and the Z plan mixes mirror pairs with a centered
# middle slab.  (16, 16, 3) isolates the Z-slab schedule at full (i, j).
TILE_CONFIGS = [(5, 7, 16), (5, 7, 5), (16, 16, 3)]


@pytest.fixture(scope="module")
def setup():
    geom = standard_geometry(n=16, n_det=24, n_proj=6)
    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.rand(geom.n_proj, geom.nh,
                               geom.nw).astype(np.float32))
    img_t = transpose_projections(img)
    mats = projection_matrices(geom)
    ni, nj, nk = geom.volume_shape_xyz
    ref = bp.volume_to_transposed(backproject_rtk(img, mats, (nk, nj, ni)))
    return geom, img_t, mats, np.asarray(ref)


# ---- parity: every variant x non-divisible tile configs ------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("tile", TILE_CONFIGS[:2])
def test_tiled_matches_untiled_reference(setup, variant, tile):
    geom, img_t, mats, ref = setup
    eng = TiledReconstructor(geom, variant, tile_shape=tile, nb=4)
    out = eng.backproject(img_t, mats)
    assert rel_rmse(out, ref) < BAR, (variant, tile)


@pytest.mark.parametrize("variant", ["algorithm1_mp", "subline_pl"])
def test_tiled_full_ij_odd_slabs(setup, variant):
    """Z-slab schedule isolated: full (i, j), odd slabs on even nz."""
    geom, img_t, mats, ref = setup
    eng = TiledReconstructor(geom, variant, tile_shape=TILE_CONFIGS[2],
                             nb=4)
    assert rel_rmse(eng.backproject(img_t, mats), ref) < BAR


def test_tiled_device_accumulator_and_proj_batching(setup):
    """out='device' + streaming projection sub-batches match too."""
    geom, img_t, mats, ref = setup
    eng = TiledReconstructor(geom, "algorithm1_mp", tile_shape=(7, 16, 16),
                             nb=2, proj_batch=4, out="device")
    out = eng.backproject(img_t, mats)
    assert isinstance(out, jnp.ndarray)
    assert rel_rmse(out, ref) < BAR


def test_engine_pipeline_entry_point(setup):
    """fdk_reconstruct(tiling=...) == fdk_reconstruct() end to end."""
    from repro.core import fdk_reconstruct
    geom, _, _, _ = setup
    rng = np.random.RandomState(1)
    projs = jnp.asarray(rng.rand(geom.n_proj, geom.nh,
                                 geom.nw).astype(np.float32))
    untiled = fdk_reconstruct(projs, geom, variant="algorithm1_mp", nb=2)
    tiled = fdk_reconstruct(projs, geom, variant="algorithm1_mp", nb=2,
                            tiling=(5, 7, 5))
    assert rel_rmse(tiled, untiled) < BAR


# ---- property-style: any partition reassembles exactly -------------------

@pytest.mark.parametrize("tile", [(1, 16, 16), (16, 1, 7), (3, 5, 11),
                                  (4, 4, 4), (16, 16, 16)])
def test_any_tile_partition_is_exact_cover(tile):
    """make_tiles yields a disjoint exact cover for ANY tile shape."""
    shape = (16, 16, 16)
    count = np.zeros(shape, np.int32)
    for t in make_tiles(shape, tile):
        assert t.shape == tuple(s.stop - s.start for s in t.slices)
        count[t.slices] += 1
    assert (count == 1).all()


@pytest.mark.parametrize("tile", [(3, 5, 11), (6, 6, 2), (16, 16, 5)])
def test_per_tile_backprojection_reassembles_reference(setup, tile):
    """Back-projecting every sub-box with translated matrices and pasting
    the pieces reproduces the full untiled volume — the engine identity,
    checked tile-by-tile without the engine's own scheduling."""
    geom, img_t, mats, ref = setup
    vol = np.zeros(geom.volume_shape_xyz, np.float32)
    for t in make_tiles(geom.volume_shape_xyz, tile):
        mt = translate_matrices(mats, float(t.i0), float(t.j0), float(t.k0))
        vol[t.slices] = np.asarray(
            bp.bp_subline(img_t, mt, t.shape))
    assert rel_rmse(vol, ref) < BAR


def test_plain_z_slabs_bound_depth_and_cover():
    """Symmetry-free schedule: disjoint cover with every slab <= tk
    (plan_z_units' centered middle slab may reach 2*tk-1; symmetry-free
    variants must not pay that)."""
    from repro.core.tiling import plan_z_slabs
    for nz, tk in [(16, 9), (30, 8), (16, 16), (17, 4), (1, 8)]:
        cover = np.zeros(nz, np.int32)
        for u in plan_z_slabs(nz, tk):
            assert u.nk <= tk and not u.paired
            cover[u.k0:u.k0 + u.nk] += 1
        assert (cover == 1).all(), (nz, tk)


def test_symmetry_free_engine_keeps_slab_depth_bound(setup):
    """The engine schedules symmetry-free variants with plain slabs, so
    no variant call is deeper than tk — the O(tile) contract (a 9-deep
    request on nz=16 used to issue one depth-16 call)."""
    geom, img_t, mats, ref = setup
    eng = TiledReconstructor(geom, "subline_batch_mp",
                             tile_shape=(16, 16, 9), nb=2)
    _, z_units = eng.plan()
    assert all(u.nk <= 9 for u in z_units)
    assert rel_rmse(eng.backproject(img_t, mats), ref) < BAR


def test_tiling_auto_requires_budget(setup):
    from repro.core import fdk_reconstruct
    geom, _, _, _ = setup
    projs = jnp.zeros((geom.n_proj, geom.nh, geom.nw), jnp.float32)
    with pytest.raises(ValueError, match="memory_budget"):
        fdk_reconstruct(projs, geom, tiling="auto")


def test_z_plan_covers_disjointly():
    for nz, tk in [(16, 3), (16, 16), (17, 4), (15, 15), (16, 5), (1, 8)]:
        cover = np.zeros(nz, np.int32)
        for u in plan_z_units(nz, tk):
            cover[u.k0:u.k0 + u.nk] += 1
            if u.paired:
                cover[u.mirror_k0:u.mirror_k0 + u.nk] += 1
                assert u.k0 + u.nk <= u.mirror_k0      # disjoint halves
            else:
                assert u.centered                       # odd middle slab
        assert (cover == 1).all(), (nz, tk)


# ---- tail-batch padding ---------------------------------------------------

def test_pad_projection_batch_is_exact(setup):
    """Zero-image / repeated-matrix padding contributes exactly nothing."""
    geom, img_t, mats, _ = setup
    img_p, mat_p = pad_projection_batch(img_t, mats, 4)
    assert img_p.shape[0] == 8 and mat_p.shape[0] == 8
    full = bp.bp_subline_batch(img_p, mat_p, geom.volume_shape_xyz, nb=4)
    ref = bp.bp_subline(img_t, mats, geom.volume_shape_xyz)
    assert rel_rmse(full, ref) < BAR
    # already-divisible input passes through untouched
    same_img, same_mat = pad_projection_batch(img_t, mats, 3)
    assert same_img is img_t and same_mat is mats


# ---- auto-picker / working-set model -------------------------------------

def test_pick_tile_shape_fits_budget():
    vol, det = (64, 64, 64), (96, 96)
    budget = 2 << 20
    tile = pick_tile_shape(vol, det, budget, nb=8)
    assert tile_working_set_bytes(tile, det, nb=8) <= budget
    assert all(1 <= t <= v for t, v in zip(tile, vol))
    # a generous budget keeps the full volume as one tile
    assert pick_tile_shape(vol, det, 1 << 40, nb=8) == vol
    # an impossible budget degrades to the minimal tile, never loops
    assert pick_tile_shape(vol, det, 0, nb=8) == (1, 1, 1)
    # pair_z: a symmetry-scheduled slab runs at virtual depth 2*tk, and
    # THAT is what must fit the budget
    t2 = pick_tile_shape(vol, det, budget, nb=8, pair_z=True)
    ti, tj, tk = t2
    eff = min(2 * tk, vol[2]) if tk < vol[2] else tk
    assert tile_working_set_bytes((ti, tj, eff), det, nb=8) <= budget


def test_explicit_tile_over_budget_raises(setup):
    """An explicit tile_shape is validated against memory_budget instead
    of silently dropping the budget."""
    geom, _, _, _ = setup
    with pytest.raises(ValueError, match="memory_budget"):
        TiledReconstructor(geom, "algorithm1_mp", tile_shape=(16, 16, 16),
                           memory_budget=1024, nb=4)


def test_proj_batch_rounds_up(setup):
    """proj_batch=5 with nb=2 -> batches of 6 (rounded UP per the
    documented contract), and the result stays exact."""
    geom, img_t, mats, ref = setup
    eng = TiledReconstructor(geom, "subline_batch_mp", tile_shape=(16, 16, 16),
                             nb=2, proj_batch=5)
    assert rel_rmse(eng.backproject(img_t, mats), ref) < BAR


def test_engine_budget_parity(setup):
    """memory_budget path: auto-picked tiles still reconstruct exactly,
    and the engine's reported working set honors the budget."""
    geom, img_t, mats, ref = setup
    budget = 64 << 10
    eng = TiledReconstructor(geom, "algorithm1_mp", memory_budget=budget,
                             nb=4)
    assert eng.working_set_bytes <= budget
    assert eng.tile_shape != geom.volume_shape_xyz   # budget forced tiling
    assert rel_rmse(eng.backproject(img_t, mats), ref) < BAR


# ---- fallback bookkeeping ------------------------------------------------

def test_slab_safe_fallback_strips_symmetry_only():
    from repro.core.variants import OPTIMIZATIONS
    for name in VARIANTS:
        fb = slab_safe_variant(name)
        assert not uses_symmetry(fb)
        if fb != name:
            assert uses_symmetry(name)
            # the fallback keeps every non-symmetry opt it can
            kept = set(OPTIMIZATIONS[fb])
            assert "symmetry" not in kept
            assert kept <= set(OPTIMIZATIONS[name])


def test_uncentered_slab_uses_fallback(setup):
    """A lone non-centered Z-slab through a symmetry variant must be
    exact (the engine swaps in the slab-safe fallback under the hood)."""
    geom, img_t, mats, ref = setup
    eng = TiledReconstructor(geom, "algorithm1_mp", nb=2)
    tile = TileSpec(0, 0, 3, 16, 16, 6)                # 2*3+6 != 16
    out = eng.backproject_tile(img_t, mats, tile)
    assert rel_rmse(out, ref[tile.slices]) < BAR
