"""The benchmark's core: finds a cell's files by name and runs it.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

``<config file>``              as the ``configs`` entry names it: the scan
                               (``scan``), the program's options
                               (``options``), the control and the limits;
``bench/mixes/<traffic>.json`` the traffic mix (see :mod:`traffic`);
``bench/metrics/<metric>.py``  a reader ``read(run) -> float | None``.

A run: set-up makes the mix's pool of scans from the seed, builds the
request and sends one warm-up request (every program the window runs is
then compiled or loaded from the persistent cache); the window sends
requests back to back, each scan whole, until ``seconds`` have passed;
then the plain reference (:mod:`reference`) recomputes each scan at the
sampled voxels and decides ``correct``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402
import traces as tr  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    geom: dict
    devices: List[int]
    setup_s: float
    window_s: float
    n_scans: int
    compiles_in_window: int
    peak: dict
    trace: Optional[tr.Trace] = None
    window: Optional[tr.Interval] = None    # the window on the trace clock

    @property
    def updates(self) -> int:
        return self.n_scans * work.updates(self.geom)


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, "bench")
    with open(os.path.join(bench_dir, "mixes", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    missing = [k for k in traffic.MIX_KEYS if k not in mix]
    if missing:
        raise KeyError(f"traffic mix {w['traffic']!r} lacks {missing}")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, int(w["chips"]), config, mix,
                mine(spec["end_to_end"]), mine(spec["per_layer"]), bench_dir)


def reader(bench_dir: str, metric: str):
    """The module ``bench/metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peak_of(bench_dir: str, kind: str) -> dict:
    """The ``peaks.json`` entry of a device kind; an unknown kind is an
    error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def program_options(config: dict, **override):
    import repro
    opts = {k: tuple(v) if isinstance(v, list) else v
            for k, v in {**config["options"], **override}.items()}
    return repro.ReconOptions(**opts)


def used_devices(config: dict) -> List[int]:
    import jax
    return (list(range(jax.local_device_count()))
            if config["options"].get("devices") == "all" else [0])


def make_request(geom: dict, options, span=contextlib.nullcontext):
    """One request: a host scan in, its (nz, ny, nx) volume on the host
    out, through the path users call. ``span(name)`` marks the call and
    the copy to the host."""
    import repro
    from repro.core.geometry import CTGeometry
    cg = CTGeometry(**geom)

    def request(projections: np.ndarray) -> np.ndarray:
        with span("bench.reconstruct"):
            vol = repro.reconstruct(projections, cg, method="fdk",
                                    options=options)
        with span("bench.to_host"):
            return np.asarray(vol)
    return request


def at(vol: np.ndarray, ijk: np.ndarray) -> np.ndarray:
    """A (nz, ny, nx) volume's values at the voxels ``ijk`` (i, j, k)."""
    return np.array(vol[ijk[:, 2], ijk[:, 1], ijk[:, 0]], np.float64)


def check(cell: Cell, pool: list, got: list, geom: dict,
          ijk: np.ndarray) -> Dict[str, Any]:
    """Compare each window scan's sampled voxels with the reference.
    ``got`` holds (pool index, values). Returns the worst relative RMSE,
    the scans over the limit and the count compared."""
    limit = float(cell.config["limits"]["rel_rmse"])
    refs = {i: reference.fdk_at(pool[i], geom, ijk)
            for i in sorted({i for i, _ in got})}
    errs = [reference.rel_rmse(v, refs[i]) for i, v in got]
    # a NaN compares false with any limit: a scan passes only by a finite
    # reading at or under it
    ok = [bool(np.isfinite(e) and e <= limit) for e in errs]
    worst = max(errs) if errs and all(np.isfinite(errs)) else float("inf")
    return {"rel_rmse": worst, "limit": limit, "over": ok.count(False),
            "compared": len(errs), "all": errs}


def missing_metrics(cell: Cell, out: dict, traced: bool) -> List[str]:
    """The metrics ``BENCHMARK.json`` lists for this cell and kind of run
    that the run's line lacks: a reader that found nothing to read."""
    listed = cell.per_layer if traced else cell.end_to_end
    return [m["name"] for m in listed if m["name"] not in out["metrics"]]


def _spans(on: bool):
    """The benchmark's own host spans: profiler annotations in a traced
    run, nothing otherwise."""
    import jax
    return jax.profiler.TraceAnnotation if on else contextlib.nullcontext


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, keep_trace: Optional[str] = None) -> dict:
    """One run of ``cell``; returns the result line's object. The caller
    has checked the platform and the chips."""
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.runtime import telemetry
    from repro.runtime.executor import default_program_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    geom = reference.geometry(cell.config["scan"])
    devices = used_devices(cell.config)
    dev0 = jax.devices()[0]
    peak = peak_of(cell.bench_dir, dev0.device_kind) \
        if dev0.platform == "tpu" else {}
    span = _spans(traced)
    request = make_request(geom, program_options(cell.config), span)
    pool = traffic.make_pool(geom, cell.mix, seed)
    ijk = traffic.sample_voxels(geom, cell.mix["check_voxels"], seed)
    request(pool[0])                                  # warm-up
    setup_s = time.perf_counter() - t_start

    cache = default_program_cache()
    misses0 = cache.stats()["misses"]
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        telemetry.enable(clear_events=True)
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with span("bench.anchor"):
            anchor_ns = time.perf_counter_ns()
    got, walls = [], []
    p = len(pool)
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            i = (1 + len(got)) % p
            with span("bench.request"):
                t1 = time.perf_counter()
                vol = request(pool[i])
                got.append((i, at(vol, ijk)))
                walls.append(time.perf_counter() - t1)
                del vol
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
        telemetry.disable()
    compiles = cache.stats()["misses"] - misses0
    mem = [jax.devices()[d].memory_stats() or {} for d in devices]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    run = Run(cell, geom, devices, setup_s, window_s, len(got), compiles,
              peak)
    breakdown = None
    if traced:
        xplane = sorted(glob.glob(os.path.join(
            tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        if keep_trace:
            shutil.copy(xplane, keep_trace)
        run.trace = tr.load(xplane)
        shutil.rmtree(tmp, ignore_errors=True)
        run.window = run.trace.annotation("bench.window")
        breakdown = _breakdown(run, telemetry.events(), anchor_ns)
    gc.collect()

    verdict = check(cell, pool, got, geom, ijk)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in getattr(cell, kind):
        value = reader(cell.bench_dir, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    if traced:
        lo, hi = run.window
        device["busy_s"] = float(np.mean(
            [run.trace.devices[d].busy(lo, hi) for d in devices])) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
    correct = verdict["over"] == 0 and verdict["compared"] == len(got) > 0
    print(f"[{cell.name}] seed={seed} setup_s={setup_s!r} "
          f"window_s={window_s!r} scans={len(got)} walls_s={walls!r} "
          f"program_cache_misses={compiles} "
          f"rel_rmse_by_scan={verdict['all']!r}", file=sys.stderr)
    print(f"check rel_rmse={verdict['rel_rmse']!r} "
          f"limit={verdict['limit']!r} (worst of "
          f"{verdict['compared']} scans, {len(ijk)} voxels each)",
          file=sys.stderr, flush=True)
    out = {"correct": bool(correct), "attempted": len(got),
           "failed": int(verdict["over"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {"rel_rmse": {"value": verdict["rel_rmse"],
                                 "limit": verdict["limit"]}}
    return out


def _breakdown(run: Run, events: list, anchor_ns: int) -> dict:
    """The traced window's busiest device operations and longest idle
    gaps, each gap named after the host span open in it: the program's
    telemetry spans (moved onto the trace clock through the
    ``bench.anchor`` annotation) and the benchmark's own."""
    lo, hi = run.window
    anchor = run.trace.annotation("bench.anchor")
    offset = anchor[0] - anchor_ns
    spans = [(e["ts"] * 1e3 + offset, (e["ts"] + e["dur"]) * 1e3 + offset,
              e["name"]) for e in events if e.get("ph") == "X"]
    spans += [s for s in run.trace.host if s[2].startswith("bench.")
              and s[2] != "bench.window"]
    gaps = []
    for d in run.devices:
        prefix = f"tpu{d}:" if len(run.devices) > 1 else ""
        gaps += [(prefix + n, s)
                 for n, s in tr.named_gaps(run.trace, d, lo, hi, spans)]
    return {"device_ops": [[n, s] for n, s in
                           tr.top_ops(run.trace, run.devices, lo, hi)],
            "idle_gaps": [[n, s] for n, s in
                          sorted(gaps, key=lambda g: -g[1])[:10]]}
