"""Reconstruction-fleet tests (subprocess: 8 forced host devices).

The fleet shards the planner's step-major schedule across a device mesh
(``PlanExecutor.execute_fleet``); these prove the fleet's contracts on the
no-hardware CI lane (``XLA_FLAGS=--xla_force_host_platform_device_count
=8``, in a subprocess because the device count must be fixed before jax
initializes — the main test process keeps the default single device):

  * **parity** — the fleet reconstruction of a volume matches the
    single-device step-major walk within tolerance (both place each
    step's box from its origin inside the step program,
    ``core.variants.at_origin``, but in different compiled programs;
    disjoint boxes mean nothing else can differ);
  * **failover** — with one device's steps forcibly failed, the run
    completes BIT-IDENTICALLY via re-run on surviving devices, the
    struck device is retired, and its completion count is zero;
  * **work stealing** — a straggling device's unclaimed steps migrate
    (stolen > 0) with output still bit-identical;
  * **poison step** — a step that fails everywhere exhausts its
    per-step retry budget and aborts the run (an incomplete volume must
    never be returned);
  * **no step input leaves a chip once steps dispatch** — origins are
    put from the host and the view replicas are issued before the first
    step program, so no chip waits behind another's queued step; only a
    spare that steals copies late, which ``late_copies`` counts.

The serving layer rides the same path: ``ReconService(devices="all")``
buckets place every request across the fleet and surface steal/failover
totals in their stats.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time, threading
sys.path.insert(0, "src")
import numpy as np
import jax, jax.numpy as jnp

from repro.core import standard_geometry
from repro.core.fdk import _build_plan, fdk_reconstruct
from repro.runtime.executor import (FleetConfig, PlanExecutor,
                                    default_program_cache)
from repro.runtime.service import ReconService

out = {}
out["n_devices"] = len(jax.local_devices())

geom = standard_geometry(n=32, n_det=48, n_proj=16)
rng = np.random.RandomState(0)
projs = jnp.asarray(rng.rand(geom.n_proj, geom.nh,
                             geom.nw).astype(np.float32))
# (8, 8, nz) tiles -> 16 same-shape steps over 8 devices (2 each);
# proj_batch=8 -> a 2-chunk scan grid inside each fleet program
kw = dict(nb=8, interpret=True, tiling=(8, 8, geom.nz),
          memory_budget=None, proj_batch=8, out="host", schedule="step")

ref = np.asarray(fdk_reconstruct(
    projs, geom, tiling=(8, 8, geom.nz), proj_batch=8, out="host"))

def fleet_run(cfg):
    ex = PlanExecutor(geom, _build_plan(geom, "algorithm1_mp", **kw),
                      fleet=cfg)
    vol = ex.reconstruct(projs)
    return np.asarray(vol), ex.last_fleet_report

# ---- parity: fleet == single-device step-major ---------------------------
vol_fleet, rep = fleet_run(FleetConfig())
scale = float(np.max(np.abs(ref))) or 1.0
out["fleet_rel_err"] = float(np.max(np.abs(vol_fleet - ref))) / scale
out["fleet_devices"] = rep.n_devices
out["fleet_steps"] = rep.n_steps
out["fleet_steps_covered"] = int(sum(rep.steps_by_device))

# ---- a tiling that does not divide the volume: (5, 7, nz) boxes ---------
def fleet_equals_step_walk(geom, plan, run):
    single = np.asarray(run(PlanExecutor(geom, plan)))
    fleet = np.asarray(run(PlanExecutor(geom, plan, fleet=FleetConfig())))
    return bool(np.array_equal(single, fleet))

odd = _build_plan(geom, "algorithm1_mp", **{**kw, "tiling": (5, 7, geom.nz)})
out["odd_tiles_steps"] = len(odd.steps)
out["odd_tiles_bit_identical"] = fleet_equals_step_walk(
    geom, odd, lambda ex: ex.reconstruct(projs))

# ---- n_proj % nb != 0: 6 views in batches of 5 (the tail padded) ---------
from repro.core import projection_matrices, transpose_projections
geom6 = standard_geometry(n=16, n_det=24, n_proj=6)
img6 = transpose_projections(jnp.asarray(
    rng.rand(6, geom6.nh, geom6.nw).astype(np.float32)))
mats6 = projection_matrices(geom6)
plan6 = _build_plan(geom6, "algorithm1_mp", nb=5, interpret=True,
                    tiling=(8, 8, geom6.nz), memory_budget=None,
                    proj_batch=None, out="host", schedule="step")
out["tail_n_proj_padded"] = plan6.n_proj_padded
out["tail_bit_identical"] = fleet_equals_step_walk(
    geom6, plan6, lambda ex: ex.backproject(img6, mats6))

# ---- failover: device 3's steps forcibly failed --------------------------
def fail_dev3(device, step):
    if device == 3:
        raise RuntimeError("injected device fault")
    # the others stay busy meanwhile: a peer that ran dry could steal
    # device 3's second step before it strikes out, and it would then
    # never be retired
    time.sleep(0.05)

vol_fo, rep_fo = fleet_run(FleetConfig(step_hook=fail_dev3))
out["failover_bit_identical"] = bool(np.array_equal(vol_fleet, vol_fo))
out["failover_dead"] = list(rep_fo.dead_devices)
out["failover_retried"] = rep_fo.retried
out["failover_dev3_done"] = rep_fo.steps_by_device[3]
out["failover_steps_covered"] = int(sum(rep_fo.steps_by_device))

# ---- work stealing: device 0 straggles -----------------------------------
def slow_dev0(device, step):
    if device == 0:
        time.sleep(1.0)

vol_st, rep_st = fleet_run(FleetConfig(step_hook=slow_dev0))
out["steal_bit_identical"] = bool(np.array_equal(vol_fleet, vol_st))
out["steal_stolen"] = rep_st.stolen
out["steal_flagged"] = list(rep_st.flagged_devices)

# ---- a spare steals: all 16 steps queued on device 0, none on device 1 --
import repro.runtime.executor as exmod
from repro.runtime.planner import FleetSchedule
keep_partition = exmod.partition_steps
exmod.partition_steps = lambda steps, n: FleetSchedule(
    n_shards=n, queues=(tuple(range(len(steps))),) + ((),) * (n - 1),
    loads=(len(steps),) + (0,) * (n - 1))
try:
    vol_sp, rep_sp = fleet_run(FleetConfig(
        devices=tuple(jax.local_devices()[:2]), step_hook=slow_dev0))
finally:
    exmod.partition_steps = keep_partition
out["spare_bit_identical"] = bool(np.array_equal(vol_fleet, vol_sp))
out["spare_steps_by_device"] = list(rep_sp.steps_by_device)
out["spare_late_copies"] = rep_sp.late_copies
out["parity_late_copies"] = rep.late_copies

# ---- poison step: fails on EVERY device -> abort, never a partial volume -
def poison_step0(device, step):
    if step == 0:
        raise RuntimeError("injected poison step")

try:
    fleet_run(FleetConfig(step_hook=poison_step0, max_retries_per_step=2))
    out["poison_raised"] = False
except RuntimeError as e:
    out["poison_raised"] = True
    out["poison_msg"] = str(e)[:120]

# ---- serving layer: buckets place requests across the fleet --------------
svc = ReconService(max_inflight=2, devices="all")
h1 = svc.submit(projs, geom, tiling=(8, 8, geom.nz), proj_batch=8)
h2 = svc.submit(projs, geom, tiling=(8, 8, geom.nz), proj_batch=8)
v1, v2 = np.asarray(h1.result()), np.asarray(h2.result())
out["service_rel_err"] = float(np.max(np.abs(v1 - ref))) / scale
out["service_repeat_identical"] = bool(np.array_equal(v1, v2)
                                       and np.array_equal(v1, vol_fleet))
stats = svc.stats()
out["service_bucket_devices"] = stats.buckets[0].devices
out["service_requests"] = stats.requests
svc.close()

# ---- the benchmark cell's path vs the plain reference ---------------------
# subline_pl (interpret mode on the CPU), full-z (8, 8, nz) tiles, two
# proj_batch chunks, host volume, the first 4 devices: the path of the
# p9.fleet4 cell at the smoke size, checked as its runs are, at sampled
# voxels against bench/reference.py on seeded smooth projections
sys.path.insert(0, "bench")
import reference, traffic
import repro
from repro.core.geometry import CTGeometry
from repro.runtime import telemetry
from repro.runtime.executor import PlanExecutor

cell_geom = reference.geometry({"vol": 16, "det": 24, "n_proj": 16,
                                "sad": 1000.0, "sdd": 1536.0,
                                "extent": 256.0, "det_margin": 1.25})
seed = 2 ** 31 + 15
scan = np.asarray(traffic.make_projections(
    traffic.seed_key(seed, 0), cell_geom["n_proj"], cell_geom["nh"],
    cell_geom["nw"], 8))
ijk = traffic.sample_voxels(cell_geom, 512, seed)
cell_ref = reference.fdk_at(scan, cell_geom, ijk)
cell_opts = repro.ReconOptions(variant="subline_pl", tiling=(8, 8, 16),
                               proj_batch=8, out="host", schedule="step",
                               devices=4)

def cell_rmse():
    vol = np.asarray(repro.reconstruct(scan, CTGeometry(**cell_geom),
                                       method="fdk", options=cell_opts))
    got = np.array(vol[ijk[:, 2], ijk[:, 1], ijk[:, 0]], np.float64)
    return reference.rel_rmse(got, cell_ref)

# every jax.device_put made inside execute_fleet, in order with the step
# program calls: what it was given (a host array, or the devices of a
# jax.Array), its shape, its target, and whether a step program had been
# called before it
puts, calls = [], []
keep_put, keep_fleet = jax.device_put, PlanExecutor.execute_fleet
keep_prog = PlanExecutor._program
in_fleet = threading.Event()

def logged_put(x, device=None, *a, **k):
    if in_fleet.is_set():
        src = (sorted(d.id for d in x.devices())
               if isinstance(x, jax.Array) else "host")
        puts.append({"src": src, "dst": device.id, "shape": list(x.shape),
                     "after_step": bool(calls)})
    return keep_put(x, device, *a, **k)

late = []

def logged_fleet(self, *a, **k):
    in_fleet.set()
    try:
        return keep_fleet(self, *a, **k)
    finally:
        in_fleet.clear()
        late.append(self.last_fleet_report.late_copies)

def logged_prog(self, *a, **k):
    prog = keep_prog(self, *a, **k)
    def call(*args):
        calls.append(1)
        return prog(*args)
    return call

jax.device_put, PlanExecutor.execute_fleet = logged_put, logged_fleet
PlanExecutor._program = logged_prog
try:
    with telemetry.tracing():
        out["cell_rel_rmse"] = cell_rmse()
        evs = telemetry.events()
finally:
    jax.device_put, PlanExecutor.execute_fleet = keep_put, keep_fleet
    PlanExecutor._program = keep_prog
out["cell_puts"] = puts
out["cell_late_copies"] = late
out["cell_spans"] = {name: sorted(e["args"].get("device", -1) for e in evs
                                  if e["name"] == name)
                     for name in ("step.dispatch", "fleet.flush",
                                  "fleet.flush_wait", "fleet.replicate")}
out["cell_flush_bytes"] = sum(e["args"]["bytes"] for e in evs
                              if e["name"] == "fleet.flush")
out["cell_replicate_bytes"] = sorted(e["args"]["bytes"] for e in evs
                                     if e["name"] == "fleet.replicate")

# planted fault: the writes of the step at the volume's corner are dropped
keep_writes = PlanExecutor._step_writes
PlanExecutor._step_writes = staticmethod(
    lambda step, o: () if (step.i0, step.j0) == (0, 0)
    else keep_writes(step, o))
try:
    out["cell_dropped_step_rel_rmse"] = cell_rmse()
finally:
    PlanExecutor._step_writes = staticmethod(keep_writes)

print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def fleet_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))), env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    return json.loads(line[len("RESULT:"):])


def test_fleet_runs_on_eight_devices(fleet_results):
    assert fleet_results["n_devices"] == 8
    assert fleet_results["fleet_devices"] == 8


def test_fleet_matches_single_device(fleet_results):
    """16 steps sharded over 8 devices reconstruct the same volume as
    the single-device step-major walk (every step covered once)."""
    assert fleet_results["fleet_rel_err"] < 1e-5
    assert fleet_results["fleet_steps_covered"] == \
        fleet_results["fleet_steps"]


def test_fleet_on_tiles_that_do_not_divide_the_volume(fleet_results):
    """(5, 7, nz) boxes leave narrower edge steps: the fleet's volume
    equals the single-device step walk's bit for bit (one step program
    per box shape, the same on every device)."""
    assert fleet_results["odd_tiles_steps"] == 7 * 5   # 32/5 x 32/7
    assert fleet_results["odd_tiles_bit_identical"]


def test_fleet_with_a_padded_tail_batch(fleet_results):
    """6 views in batches of nb=5: the tail is padded with zero views to
    10, and the fleet's ``backproject`` equals the single-device step
    walk bit for bit."""
    assert fleet_results["tail_n_proj_padded"] == 10
    assert fleet_results["tail_bit_identical"]


def test_fleet_failover_bit_identical(fleet_results):
    """A device whose every step faults is retired after its strike
    budget; its steps re-run on survivors and the output is
    BIT-identical (disjoint boxes + identical per-step programs)."""
    assert fleet_results["failover_bit_identical"]
    assert 3 in fleet_results["failover_dead"]
    assert fleet_results["failover_retried"] >= 1
    assert fleet_results["failover_dev3_done"] == 0
    assert fleet_results["failover_steps_covered"] == \
        fleet_results["fleet_steps"]


def test_fleet_steals_from_straggler(fleet_results):
    """An idle device steals the straggling device's unclaimed steps;
    migration never changes the output."""
    assert fleet_results["steal_stolen"] >= 1
    assert fleet_results["steal_bit_identical"]


def test_fleet_poison_step_aborts(fleet_results):
    """A step failing on EVERY device exhausts max_retries_per_step and
    raises — a partial volume is never silently returned."""
    assert fleet_results["poison_raised"]
    assert "max_retries_per_step" in fleet_results.get("poison_msg", "")


def test_service_places_buckets_across_fleet(fleet_results):
    """ReconService(devices="all") routes bucket executors through
    execute_fleet: correct volumes, repeat-identical, and the bucket
    stats report the fleet width."""
    assert fleet_results["service_rel_err"] < 1e-5
    assert fleet_results["service_repeat_identical"]
    assert fleet_results["service_bucket_devices"] == 8
    assert fleet_results["service_requests"] == 2


def test_fleet_cell_path_matches_the_plain_reference(fleet_results):
    """The benchmark cell's path (sub-line kernel, full-z tiles, chunked
    filter, host volume, 4 devices) agrees with ``bench/reference.py``
    within the paper's 1e-5, and a step whose writes are dropped reads
    far above it."""
    assert fleet_results["cell_rel_rmse"] < 1e-5
    assert fleet_results["cell_dropped_step_rel_rmse"] > 1e-3


def test_fleet_spans_flush_and_replicate(fleet_results):
    """One ``fleet.flush`` and one ``fleet.flush_wait`` per step, on the
    step's device, and one ``fleet.replicate`` per device other than
    device 0 (which already holds the views) that took work."""
    spans = fleet_results["cell_spans"]
    steps = spans["step.dispatch"]
    assert len(steps) == 4
    assert spans["fleet.flush"] == spans["fleet.flush_wait"] == steps
    assert spans["fleet.replicate"] == sorted(set(steps) - {0})
    # the 16^3 float32 volume is flushed once; each copy carries the
    # 16 filtered views and their matrices
    assert fleet_results["cell_flush_bytes"] == 16 ** 3 * 4
    assert all(b > 16 * 24 * 24 * 4
               for b in fleet_results["cell_replicate_bytes"])


def test_fleet_step_inputs_leave_no_chip_once_steps_dispatch(fleet_results):
    """Inside ``execute_fleet`` on the cell's path (4 devices, one step
    each), the only device-to-device copies are the view and matrix
    replicas of devices 1 to 3, all issued before the first step program
    is called, and every step origin is put from a host array: no step
    waits for a copy that leaves a chip behind that chip's queued step.
    ``late_copies`` reads 0 there and in the 8-device parity run."""
    puts = fleet_results["cell_puts"]
    cross = [p for p in puts if p["src"] != "host" and p["src"] != [p["dst"]]]
    origins = [p for p in puts if p["shape"] == [3]]
    assert sorted(p["dst"] for p in cross) == [1, 1, 2, 2, 3, 3]
    assert all(len(p["shape"]) == 4 and not p["after_step"] for p in cross)
    assert len(origins) == 4
    assert all(p["src"] == "host" for p in origins)
    assert fleet_results["cell_late_copies"] == [0]
    assert fleet_results["parity_late_copies"] == 0


def test_fleet_spare_that_steals_counts_one_late_copy(fleet_results):
    """A device whose initial queue is empty replicates only once it
    steals, and that lazy copy is the one ``late_copies`` counts; the
    volume is bit-identical to the balanced run's."""
    assert fleet_results["spare_steps_by_device"][1] >= 1
    assert sum(fleet_results["spare_steps_by_device"]) == \
        fleet_results["fleet_steps"]
    assert fleet_results["spare_late_copies"] == 1
    assert fleet_results["spare_bit_identical"]
