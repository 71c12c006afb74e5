#!/usr/bin/env python3
"""Smoke test of the reconstruction main path on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --chips 4 [--seed N]

One chip: paper Table 3 P5 (512 views of a 512x512 detector into a 512^3
volume), FDK:

  baseline      the RTK Listing-1 reference (``core/baseline.py``) on
                the voxel columns of ``REF_BOXES``, every view;
  algorithm1_mp the default variant through ``repro.reconstruct``, tiled;
  subline_pl    the paper's Algorithm 1 as a Pallas kernel, untiled;
  service       two requests of one P5 bucket through ``ReconService``.

Every volume is checked against the reference on those columns with
the paper's bar, relative RMSE < 1e-5 (RMSE over the reference's peak
magnitude), and the two Algorithm 1 volumes against each other whole.

``--chips 4``: only paper P9 (512 views, 1024x1024 detector, 1024^3
volume) through the reconstruction fleet on every local device
(``devices="all"``) and the same plan on one device (``devices=1``), in
this process. The two volumes must be bit-identical; the steps each
device ran are printed.

Projections are smooth random fields from ``--seed``, made on the
device (see :func:`make_projections`). Each phase prints one line (wall
seconds of that one run, programs the shared ``ProgramCache`` compiled
in it, device kind, the process's peak device bytes so far) and each
check its RMSE. The last line of standard
output is a JSON object ``{"ok": true, "device": {...}}``; a failed
phase, an RMSE over the bar, or a device that is not a TPU exits nonzero
and prints no such line. Not a benchmark: the walls include compilation.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

BAR = 1e-5
# The algorithm1_mp tile at P5, chosen by compiling its step-major scan
# program for a v5e (tests/test_tpu_compile.py keeps that compile): the
# untiled step asks for 13.0 GiB of the 15.75 GiB XLA may use, beside
# the raw projections; (128, 128, 512) asks for 1.3 GiB.
P5_TILE = (128, 128, 512)
# Where the baseline runs, as (i0, j0, ni, nj) voxel columns over the
# whole Z extent: the volume centre and a corner. The baseline's
# per-voxel gathers are too slow on a TPU for all 512^3 voxels.
REF_BOXES = ((240, 240, 32, 32), (0, 0, 32, 32))
# The --chips 4 fleet tile at P9: 16 steps, four per chip. Its views
# are filtered 64 at a time: the whole-set filter program asks for
# 16.0 GiB at P9, a 64-view chunk for 3.0 GiB.
P9_TILE = (256, 256, 1024)
P9_PROJ_BATCH = 64


def rel_rmse(vol, ref) -> float:
    import numpy as np
    vol = np.asarray(vol, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.sqrt(np.mean((vol - ref) ** 2))
                 / max(np.abs(ref).max(), 1e-30))


class Phases:
    """Runs named phases, prints one line each, remembers failures."""

    def __init__(self, device):
        from repro.runtime.executor import default_program_cache
        self.device = device
        self.cache = default_program_cache()
        self.failed = []

    def run(self, name, fn, **fields):
        misses = self.cache.stats()["misses"]
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception as exc:                     # noqa: BLE001
            self.failed.append(name)
            print(f"[{name}] FAILED {type(exc).__name__}: {exc}",
                  flush=True)
            return None
        wall = time.perf_counter() - t0
        stats = self.device.memory_stats() or {}
        rec = {"wall_s": wall,
               "programs_compiled": self.cache.stats()["misses"] - misses,
               "device_kind": self.device.device_kind,
               "peak_bytes": stats.get("peak_bytes_in_use"), **fields}
        print(f"[{name}] " + json.dumps(rec), flush=True)
        return out

    def check(self, name, vol, ref):
        err = rel_rmse(vol, ref)
        ok = err < BAR
        if not ok:
            self.failed.append(name)
        print(f"[{name}] rel_rmse={err:.3e} bar={BAR:g} "
              f"{'OK' if ok else 'FAIL'}", flush=True)

    def check_boxes(self, name, vol, refs):
        """Check a (nz, ny, nx) volume against the reference boxes."""
        import numpy as np
        got = np.concatenate([vol[:, j0:j0 + bj, i0:i0 + bi].ravel()
                              for i0, j0, bi, bj in REF_BOXES])
        self.check(name, got, np.concatenate([r.ravel() for r in refs]))


def make_projections(geom, seed):
    """Smooth random projections: uniform noise on a grid 8x coarser than
    the detector, upsampled cubically. Projections of a real object are
    smooth at the pixel scale; white noise there puts the float32
    rounding of the detector coordinates at the paper's bar (relative
    RMSE 1.2e-5 on a v5e at P5, 8.3e-6 on the CPU)."""
    import jax
    import jax.numpy as jnp
    coarse = jax.random.uniform(
        jax.random.PRNGKey(seed),
        (geom.n_proj, geom.nh // 8, geom.nw // 8), jnp.float32)
    return jax.block_until_ready(jax.image.resize(
        coarse, (geom.n_proj, geom.nh, geom.nw), method="cubic"))


def reference_boxes(projs, geom):
    """The RTK baseline (``core/baseline.py``) on each of ``REF_BOXES``:
    the same FDK filtering as ``repro.reconstruct``, then Listing 1
    back-projection of every view onto the box (matrices translated to
    its origin). Returns host (nz, bj, bi) arrays."""
    import numpy as np
    from repro.core.baseline import backproject_rtk
    from repro.core.filtering import fdk_filter_chunk
    from repro.core.geometry import projection_matrices
    from repro.core.tiling import translate_matrices

    filtered = fdk_filter_chunk(projs, geom, geom.n_proj)
    mats = projection_matrices(geom)
    return [np.asarray(backproject_rtk(
        filtered, translate_matrices(mats, float(i0), float(j0)),
        (geom.nz, bj, bi))) for i0, j0, bi, bj in REF_BOXES]


def one_chip(ph: Phases, seed: int):
    import jax
    import numpy as np
    import repro
    from repro.configs.ct_paper import get_problem
    from repro.runtime.service import ReconService

    geom = get_problem("P5").geometry()
    projs = make_projections(geom, seed)

    def fdk(variant, **kw):
        opts = repro.ReconOptions(variant=variant, **kw)
        return lambda: np.asarray(
            repro.reconstruct(projs, geom, method="fdk", options=opts))

    refs = ph.run("baseline", lambda: reference_boxes(projs, geom),
                  problem="P5", boxes=len(REF_BOXES),
                  voxels=sum(b[2] * b[3] * geom.nz for b in REF_BOXES))
    if refs is None:
        return
    a1 = ph.run("algorithm1_mp", fdk("algorithm1_mp", tiling=P5_TILE),
                problem="P5", tile=P5_TILE)
    if a1 is not None:
        ph.check_boxes("algorithm1_mp", a1, refs)
    spl = ph.run("subline_pl", fdk("subline_pl"), problem="P5")
    if spl is not None:
        ph.check_boxes("subline_pl", spl, refs)
    if a1 is not None and spl is not None:
        # whole-volume agreement of the two Algorithm 1 implementations
        ph.check("algorithm1_mp~subline_pl", spl, a1)

    def serve():
        with ReconService(max_inflight=1) as svc:
            futs = [svc.submit(projs, geom, variant="subline_pl")
                    for _ in range(2)]
            vols = [jax.block_until_ready(f.result()) for f in futs]
            st = svc.stats()
        print(f"[service] requests={st.requests} "
              f"buckets={st.bucket_misses} hits={st.bucket_hits}",
              flush=True)
        if st.requests != 2 or st.bucket_misses != 1:
            raise RuntimeError(f"expected 2 requests in 1 bucket: {st}")
        return vols

    vols = ph.run("service", serve, problem="P5", requests=2)
    for i, v in enumerate(vols or ()):
        ph.check_boxes(f"service[{i}]", np.asarray(v), refs)


def four_chips(ph: Phases, seed: int, tile):
    import jax
    import numpy as np
    import repro
    from repro.configs.ct_paper import get_problem
    from repro.runtime import telemetry

    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke --chips 4: JAX sees "
                         f"{len(jax.devices())} device(s)")
    geom = get_problem("P9").geometry()
    projs = make_projections(geom, seed)
    opts = repro.ReconOptions(variant="subline_pl", tiling=tile,
                              proj_batch=P9_PROJ_BATCH, out="host",
                              schedule="step")

    def fleet():
        with telemetry.tracing():
            vol = repro.reconstruct(projs, geom, method="fdk",
                                    options=opts, devices="all")
            steps = collections.Counter(
                ev["args"]["device"] for ev in telemetry.events()
                if ev["name"] == "step.dispatch" and "device" in ev["args"])
        print(f"[fleet] steps_by_device="
              f"{[steps.get(d, 0) for d in range(len(jax.devices()))]}",
              flush=True)
        return vol

    # the same plan and step program, on one device
    one = ph.run("one_device", lambda: repro.reconstruct(
        projs, geom, method="fdk", options=opts, devices=1),
        problem="P9", tile=tile)
    many = ph.run("fleet", fleet, problem="P9", tile=tile,
                  devices=len(jax.devices()))
    if one is None or many is None:
        return
    same = np.array_equal(np.asarray(one), np.asarray(many))
    print(f"[fleet] bit_identical={same}", flush=True)
    if not same:
        ph.failed.append("fleet_bit_identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: the repro package is missing next to this "
              f"script ({exc})", file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default device is on "
              f"platform {dev.platform!r}", file=sys.stderr)
        return 2
    enable_compile_cache()
    ph = Phases(dev)
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    if args.chips == 4:
        four_chips(ph, args.seed, P9_TILE)
    else:
        one_chip(ph, args.seed)
    if ph.failed:
        print(f"chip_smoke: failed phases: {ph.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
