"""Tiled streaming engine: tile-shape sweep vs the untiled variants.

What this measures (and what the paper predicts, §3.1 + Treibig et al.'s
blocking): the tiled engine trades per-call dispatch overhead for an
O(tile) working set. On problems that FIT in memory the untiled call is
the roofline — the sweep quantifies the tiling tax as a function of tile
shape, and reports the modeled working-set bytes per tile so the
crossover (problems whose untiled temporaries exceed device memory and
simply cannot run) is visible in the same table. Full-Z tiles keep the
O3 symmetry free (mirror-paired slabs recover it otherwise); the sweep
includes both, plus the memory-budget auto-picker.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import projection_matrices, standard_geometry, \
    transpose_projections
from repro.core.tiling import tile_working_set_bytes
from repro.core.variants import get_variant
from repro.runtime.engine import TiledReconstructor

from .common import emit, gups, time_fn

VARIANT = "algorithm1_mp"


def run(n: int = 48, n_det: int = 64, n_proj: int = 32, nb: int = 8):
    geom = standard_geometry(n=n, n_det=n_det, n_proj=n_proj)
    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.rand(n_proj, geom.nh,
                               geom.nw).astype(np.float32))
    img_t = transpose_projections(img)
    mats = projection_matrices(geom)
    shape = geom.volume_shape_xyz

    # untiled reference: one variant call over the full volume
    fn = get_variant(VARIANT)
    t_ref = time_fn(lambda: fn(img_t, mats, shape, nb=nb))
    ws_ref = tile_working_set_bytes(shape, (geom.nw, geom.nh), nb=nb)
    emit(f"tiled/untiled_{VARIANT}", t_ref * 1e6,
         f"gups={gups(geom, t_ref):.3f} ws_mib={ws_ref / 2**20:.1f}")

    # tile-shape sweep: full-Z (symmetry free) and slabbed (mirror pairs)
    tiles = [(n, n, n),              # degenerate: 1 tile == untiled path
             (n // 2, n // 2, n),    # 4 full-Z tiles
             (n // 4, n // 4, n),    # 16 full-Z tiles
             (n, n, n // 4),         # Z-slabs only (paired schedule)
             (n // 2, n // 2, n // 4),
             (n // 3 + 1, n // 3 + 1, n // 3)]  # non-divisible edges
    for tile in tiles:
        eng = TiledReconstructor(geom, VARIANT, tile_shape=tile, nb=nb)
        t = time_fn(lambda e=eng: e.backproject(img_t, mats))
        emit(f"tiled/{VARIANT}_t{tile[0]}x{tile[1]}x{tile[2]}", t * 1e6,
             f"gups={gups(geom, t):.3f} tax={t / t_ref:.2f}x "
             f"ws_mib={eng.working_set_bytes / 2**20:.1f} "
             f"steps={len(eng.recon_plan.steps)} "
             f"programs={len(eng.recon_plan.program_keys)}")

    # streamed filtering: chunked FDK (filter fused into the chunk
    # pipeline) vs the whole-set filter — same tiles. The step-major
    # schedule (default; device-resident scanned accumulators, one host
    # crossing per step) keeps the PR-2 row names so the trajectory diff
    # tracks it; the chunk-major rows quantify what the inversion buys
    # at the same sizes (proj_batch = nb forces n_proj/nb >= 4 chunks).
    raw = jnp.asarray(rng.rand(n_proj, geom.nh, geom.nw).astype(np.float32))
    for pb in (None, nb):
        tile = (n // 2, n // 2, n)
        eng_c = TiledReconstructor(geom, VARIANT, tile_shape=tile, nb=nb,
                                   proj_batch=pb, schedule="chunk")
        t_c = time_fn(lambda: eng_c.reconstruct(raw))
        eng_s = TiledReconstructor(geom, VARIANT, tile_shape=tile, nb=nb,
                                   proj_batch=pb)
        t_s = time_fn(lambda: eng_s.reconstruct(raw))
        n_chunks = len(eng_s.recon_plan.chunks)
        streamed = int(eng_s.recon_plan.streams_projections)
        emit(f"tiled/reconstruct_pb{pb or 'all'}_chunkmajor", t_c * 1e6,
             f"gups={gups(geom, t_c):.3f} chunks={n_chunks} "
             f"streamed={streamed}")
        emit(f"tiled/reconstruct_pb{pb or 'all'}", t_s * 1e6,
             f"gups={gups(geom, t_s):.3f} chunks={n_chunks} "
             f"streamed={streamed} step_vs_chunk={t_s / t_c:.2f}x")

    # auto-picker: half / quarter of the untiled working set
    for frac in (2, 4):
        budget = max(1, ws_ref // frac)
        eng = TiledReconstructor(geom, VARIANT, memory_budget=budget,
                                 nb=nb)
        t = time_fn(lambda e=eng: e.backproject(img_t, mats))
        ti, tj, tk = eng.tile_shape
        emit(f"tiled/{VARIANT}_budget_ws/{frac}", t * 1e6,
             f"gups={gups(geom, t):.3f} tax={t / t_ref:.2f}x "
             f"picked={ti}x{tj}x{tk} "
             f"ws_mib={eng.working_set_bytes / 2**20:.1f}")


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
