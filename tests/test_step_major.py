"""Step-major streamed execution (PR 3).

Covers the schedule-inversion seams:
  * StepMajorSchedule structure — every step carries the FULL chunk
    work list, the scan grid covers the padded projection count, tail
    chunks keep their true extent;
  * scan-vs-loop parity — ``schedule="step"`` (scan-carried
    device-resident accumulators) matches the PR-2 chunk-major loop for
    ALL registered variants, including non-divisible tail chunks and
    both accumulator placements;
  * ProgramCache under the chunk-loop key — interior tiles of equal
    shape compile exactly once per (variant, call_shape, chunk grid),
    and a fleet step runs the single-device step's program;
  * the accumulator — host double buffer, host flusher thread and
    device in-place add each place every step's output bit for bit;
  * the filtered-chunk producer — filtering runs once per chunk no
    matter how many steps consume it, in both schedules;
  * proj_loop — planner resolution per variant and fused-kernel parity
    for the three Pallas kernels.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (fdk_reconstruct, projection_matrices,
                        standard_geometry, transpose_projections)
from repro.core import backproject as bp
from repro.core.variants import REGISTRY, VARIANTS, get_spec
from repro.runtime.executor import (FleetConfig, PlanExecutor, ProgramCache,
                                    _FilteredChunkProducer, _grid,
                                    _pad_mats)
from repro.runtime.planner import (build_step_major, plan_reconstruction)

from conftest import rel_rmse

BAR = 1e-5


@pytest.fixture(scope="module")
def setup():
    geom = standard_geometry(n=16, n_det=24, n_proj=6)
    rng = np.random.RandomState(7)
    projs = jnp.asarray(rng.rand(geom.n_proj, geom.nh,
                                 geom.nw).astype(np.float32))
    img_t = transpose_projections(projs)  # raw reuse for backproject paths
    mats = projection_matrices(geom)
    return geom, projs, img_t, mats


# ---- schedule structure ---------------------------------------------------

def test_step_major_schedule_structure(setup):
    geom, *_ = setup
    # 6 projections, nb=2 -> padded 6; proj_batch=4 -> chunks (0,4),(4,6)
    plan = plan_reconstruction(geom, "algorithm1_mp", nb=2, proj_batch=4,
                               tile_shape=(8, 8, 16))
    sched = plan.step_major
    assert sched.n_chunks == len(plan.chunks) == 2
    assert sched.chunk_size == plan.chunk_size == 4
    assert sched.n_scan == 8 >= plan.n_proj_padded
    assert len(sched.steps) == len(plan.steps)
    for work, step in zip(sched.steps, plan.steps):
        assert work.step is step
        # every step scans the FULL chunk list (filter-once invariant)
        assert [(c.index, c.s0, c.s1) for c in work.chunks] == \
            [(0, 0, 4), (1, 4, 6)]
    tail = sched.steps[0].chunks[-1]
    assert tail.size == 2  # true extent, not the scan slot


def test_build_step_major_uniform_chunks():
    sched = build_step_major((), [(0, 4), (4, 8), (8, 12)], 4)
    assert (sched.n_chunks, sched.chunk_size, sched.n_scan) == (3, 4, 12)
    assert sched.steps == ()


def test_planner_schedule_validation(setup):
    geom, *_ = setup
    with pytest.raises(ValueError, match="schedule"):
        plan_reconstruction(geom, "algorithm1_mp", schedule="sideways")
    assert plan_reconstruction(geom, "algorithm1_mp").schedule == "step"
    assert plan_reconstruction(geom, "algorithm1_mp",
                               schedule="chunk").schedule == "chunk"


def test_memory_budget_resolves_to_chunk_major(setup):
    """An explicit memory_budget is a device-byte contract the per-call
    working-set model only describes under chunk-major execution (the
    step-major scan stacks the whole filtered set on device) — so the
    planner resolves schedule=None to "chunk" there, and an explicit
    schedule still wins."""
    geom, *_ = setup
    budget = plan_reconstruction(geom, "algorithm1_mp", nb=2,
                                 memory_budget=1 << 20)
    assert budget.schedule == "chunk"
    forced = plan_reconstruction(geom, "algorithm1_mp", nb=2,
                                 memory_budget=1 << 20, schedule="step")
    assert forced.schedule == "step"


# ---- scan-vs-loop parity --------------------------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_scan_vs_chunk_loop_parity(setup, variant):
    """Acceptance bar: streamed+tiled FDK under the step-major scan
    matches the PR-2 chunk-major loop for ALL registered variants, with
    a non-divisible tail chunk (6 padded views, proj_batch=4)."""
    geom, projs, *_ = setup
    kw = dict(variant=variant, nb=2, tiling=(5, 16, 5), proj_batch=4)
    step = fdk_reconstruct(projs, geom, **kw)
    chunk = fdk_reconstruct(projs, geom, schedule="chunk", **kw)
    assert rel_rmse(step, chunk) < BAR, variant
    # and both match the untiled whole-filter seed path
    seed = fdk_reconstruct(projs, geom, variant=variant, nb=2)
    assert rel_rmse(step, seed) < BAR, variant


@pytest.mark.parametrize("out", ["host", "device"])
def test_scan_parity_both_placements(setup, out):
    geom, projs, *_ = setup
    kw = dict(variant="algorithm1_mp", nb=2, tiling=(8, 8, 4),
              proj_batch=2, out=out)
    step = fdk_reconstruct(projs, geom, **kw)
    chunk = fdk_reconstruct(projs, geom, schedule="chunk", **kw)
    assert isinstance(step, np.ndarray) == (out == "host")
    assert rel_rmse(step, chunk) < BAR


def test_backproject_any_view_count_step_major(setup):
    """The scan grid follows the DATA extent: view counts that are
    neither the geometry's count nor chunk-divisible stream exactly."""
    geom, _, img_t, mats = setup
    rng = np.random.RandomState(8)
    extra = jnp.asarray(rng.rand(4, geom.nw, geom.nh).astype(np.float32))
    img10 = jnp.concatenate([img_t, extra], axis=0)
    mats10 = jnp.concatenate([mats, mats[:4]], axis=0)
    want = np.asarray(bp.bp_subline(img10, mats10, geom.volume_shape_xyz))
    plan = plan_reconstruction(geom, "subline_batch_mp",
                               tile_shape=(8, 8, 16), nb=4, proj_batch=4)
    got = PlanExecutor(geom, plan, cache=ProgramCache()).backproject(
        img10, mats10)
    assert rel_rmse(got, want) < BAR


# ---- program cache under the chunk-loop key -------------------------------

def test_scan_programs_compile_interior_tiles_once(setup):
    """4 interior (8, 8, 16) tiles x 3 chunks -> ONE scan program build
    (the chunk-loop key is shared), three hits; a second call all hits."""
    geom, projs, *_ = setup
    cache = ProgramCache()
    plan = plan_reconstruction(geom, "subline_batch_mp",
                               tile_shape=(8, 8, 16), nb=2, proj_batch=2)
    assert len(plan.chunks) == 3 and len(plan.steps) == 4
    ex = PlanExecutor(geom, plan, cache=cache)
    ex.reconstruct(projs)
    stats = cache.stats()
    assert stats["misses"] == 1 and stats["programs"] == 1
    assert stats["hits"] == 3
    ex.reconstruct(projs)
    stats = cache.stats()
    assert stats["misses"] == 1 and stats["hits"] == 7


def test_scan_key_distinct_from_kernel_key(setup):
    """The same (variant, shape) under a different chunk grid is a new
    program; the chunk-major loop's per-chunk key family is untouched."""
    geom, _, img_t, mats = setup
    cache = ProgramCache()
    plan2 = plan_reconstruction(geom, "subline_batch_mp",
                                tile_shape=(8, 8, 16), nb=2, proj_batch=2)
    plan3 = plan_reconstruction(geom, "subline_batch_mp",
                                tile_shape=(8, 8, 16), nb=2, proj_batch=3)
    PlanExecutor(geom, plan2, cache=cache).backproject(img_t, mats)
    assert cache.stats()["programs"] == 1
    PlanExecutor(geom, plan3, cache=cache).backproject(img_t, mats)
    assert cache.stats()["programs"] == 2  # different (n_chunks, size)


def test_fleet_and_single_device_steps_share_programs(setup):
    """A fleet step and a single-device step of one plan are the same
    program under the same key: a fleet executor's ``warm()`` after a
    single-device ``warm()`` builds nothing, batched programs too."""
    geom, *_ = setup
    cache = ProgramCache()
    plan = plan_reconstruction(geom, "subline_batch_mp",
                               tile_shape=(8, 8, 16), nb=2, proj_batch=4)
    single = PlanExecutor(geom, plan, cache=cache)
    single.warm()
    single.warm_batch(2)
    misses = cache.stats()["misses"]
    assert misses == 2
    fleet = PlanExecutor(geom, plan, cache=cache, fleet=FleetConfig())
    fleet.warm()
    fleet.warm_batch(2)
    assert cache.stats()["misses"] == misses


# ---- the accumulator: where the step outputs land -------------------------

ACCUMULATORS = {"host-sync": ("host", "sync"),
                "host-async": ("host", "async"),
                "device": ("device", "sync")}


def _steps_one_by_one(ex, projections):
    """The tiled volume with no accumulator: each step's program called
    on its own and its writes assigned into a zero host volume."""
    plan = ex.plan
    sched = plan.step_major
    mat_p = _pad_mats(projection_matrices(ex.geom), plan.n_proj_padded)
    img_s, mat_s = _FilteredChunkProducer(ex, projections,
                                          mat_p).stacked(sched)
    vol = np.zeros(plan.vol_shape_xyz, np.float32)
    for step in plan.steps:
        prog = ex._program(step.variant, step.call_shape, grid=_grid(sched))
        out = prog(img_s, mat_s, ex._origin(step))
        for sl, piece in ex._step_writes(step, out):
            vol[sl] = np.asarray(piece)
    return vol.transpose(2, 1, 0)


@pytest.mark.parametrize("walk", ["reconstruct", "execute_batch"])
@pytest.mark.parametrize("acc", sorted(ACCUMULATORS))
def test_accumulator_places_every_step_bit_for_bit(setup, acc, walk):
    """Each accumulator (host double buffer, host flusher thread,
    device in-place add) lands every step's output exactly where its
    program put it: the tiled volume, mirror-paired slabs and edge boxes
    included, equals the step programs' outputs placed one at a time,
    bit for bit, and the untiled call within the parity bar."""
    geom, projs, *_ = setup
    out, pipeline = ACCUMULATORS[acc]
    plan = plan_reconstruction(geom, "algorithm1_mp", nb=2, proj_batch=4,
                               tile_shape=(5, 7, 4), out=out)
    assert any(step.paired for step in plan.steps)
    ex = PlanExecutor(geom, plan, cache=ProgramCache(), pipeline=pipeline)
    reqs = [projs, 0.5 * projs]
    got = (ex.execute_batch(reqs) if walk == "execute_batch"
           else [ex.reconstruct(projs)])
    untiled = fdk_reconstruct(projs, geom, variant="algorithm1_mp", nb=2,
                              proj_batch=4)
    for p, vol in zip(reqs, got):
        assert isinstance(vol, np.ndarray) == (out == "host")
        assert np.array_equal(np.asarray(vol), _steps_one_by_one(ex, p))
    assert rel_rmse(got[0], untiled) < BAR


# ---- filter-once producer -------------------------------------------------

@pytest.mark.parametrize("schedule", ["step", "chunk"])
def test_filtering_runs_once_per_chunk(setup, schedule, monkeypatch):
    """Satellite: filtering cost is paid once per chunk regardless of
    the step count (4 tiles consume every chunk)."""
    geom, projs, *_ = setup
    plan = plan_reconstruction(geom, "subline_batch_mp",
                               tile_shape=(8, 8, 16), nb=2, proj_batch=2,
                               schedule=schedule)
    assert len(plan.steps) == 4 and len(plan.chunks) == 3
    ex = PlanExecutor(geom, plan, cache=ProgramCache())
    ref = fdk_reconstruct(projs, geom, variant="subline_batch_mp", nb=2)
    calls = []
    real = PlanExecutor._chunk_inputs

    def counting(self, projections, mat_p, s0, s1):
        calls.append((s0, s1))
        return real(self, projections, mat_p, s0, s1)

    monkeypatch.setattr(PlanExecutor, "_chunk_inputs", counting)
    got = ex.reconstruct(projs)
    assert sorted(calls) == [(0, 2), (2, 4), (4, 6)]
    assert rel_rmse(got, ref) < BAR


# ---- proj_loop capability -------------------------------------------------

def test_proj_loop_resolved_per_variant(setup):
    geom, *_ = setup
    for name, spec in REGISTRY.items():
        plan = plan_reconstruction(geom, name, nb=2)
        opts = plan.kernel_options()
        if spec.proj_loop:
            assert opts.get("proj_loop") is True, name
        else:
            assert "proj_loop" not in opts, name
    # explicit override wins
    plan = plan_reconstruction(geom, "subline_pl", nb=2, proj_loop=False)
    assert plan.kernel_options()["proj_loop"] is False


def test_proj_loop_spec_advertised():
    for name in ("subline_pl", "onehot_pl", "banded_pl"):
        spec = get_spec(name)
        assert spec.proj_loop and "proj_loop" in spec.options, name


@pytest.mark.parametrize("name", ["subline_pl", "onehot_pl", "banded_pl"])
def test_fused_kernel_parity(setup, name):
    """proj_loop=True (in-kernel fori_loop over nb-batches) is exact
    against the per-projection grid, odd volume shapes included."""
    geom, _, img_t, mats = setup
    fn = get_spec(name).fn
    for shape in [geom.volume_shape_xyz, (13, 17, 5)]:
        ref = fn(img_t, mats, shape, nb=3, proj_loop=False)
        got = fn(img_t, mats, shape, nb=3, proj_loop=True)
        assert float(jnp.max(jnp.abs(ref - got))) < 1e-5, (name, shape)


def test_fused_kernel_falls_back_on_indivisible(setup):
    """proj_loop with np % nb != 0 silently runs the per-projection
    grid (raw-caller safety; planned paths pad globally)."""
    geom, _, img_t, mats = setup
    fn = get_spec("subline_pl").fn
    ref = fn(img_t, mats, geom.volume_shape_xyz, nb=4, proj_loop=False)
    got = fn(img_t, mats, geom.volume_shape_xyz, nb=4, proj_loop=True)
    assert float(jnp.max(jnp.abs(ref - got))) < 1e-5
