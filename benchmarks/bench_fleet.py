"""Fleet scaling benchmark: single device vs the sharded step schedule.

Measures what ``PlanExecutor.execute_fleet`` buys on N forced XLA host
devices (``--xla_force_host_platform_device_count``): the step-major
schedule is LPT-partitioned into per-device queues, each device runs the
shared origin-traced fleet program over its steps, and the host volume
accumulates the disjoint boxes.

The measurement runs in a SUBPROCESS because the device count must be
fixed before jax initializes — the launching process (and anything it
imported) keeps the default single device. Emitted rows:

  fleet/single_device     the plain step-major walk (the baseline)
  fleet/fleet<N>dev       the same plan through execute_fleet
  fleet/failover          fleet with one device's steps forcibly
                          failed — the price of re-running them

Forced host devices SHARE the machine's cores, so the fleet "speedup"
on a CI box is a scheduling-overhead measurement, not a scaling claim —
the number that matters is that fleet wall stays within ~2x of single
(threads + retries are cheap), while real multi-socket hardware shards
actual compute. Never a gating number (the multidevice CI lane runs it
``|| warn``).

    PYTHONPATH=src python -m benchmarks.bench_fleet [--devices 8]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


from . import common

_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + sys.argv[1])
sys.path.insert(0, "src")
import numpy as np
import jax, jax.numpy as jnp
import time

from repro.compile_cache import enable_compile_cache
from repro.core import standard_geometry
from repro.core.fdk import _build_plan
from repro.runtime.executor import FleetConfig, PlanExecutor

n = int(sys.argv[2])
geom = standard_geometry(n=n, n_det=max(24, 3 * n // 2), n_proj=16)
rng = np.random.RandomState(0)
projs = jnp.asarray(rng.rand(geom.n_proj, geom.nh,
                             geom.nw).astype(np.float32))
kw = dict(nb=8, tiling=(n // 4, n // 4, geom.nz),
          memory_budget=None, proj_batch=8, out="host", schedule="step")
plan = _build_plan(geom, "algorithm1_mp", **kw)

def timed(ex):
    ex.warm()
    ex.reconstruct(projs)                       # per-device compiles
    t0 = time.perf_counter()
    ex.reconstruct(projs)
    return time.perf_counter() - t0

out = {"n_devices": len(jax.local_devices()), "n_steps": len(plan.steps)}
out["single_s"] = timed(PlanExecutor(geom, plan))

ex = PlanExecutor(geom, plan, fleet=FleetConfig())
out["fleet_s"] = timed(ex)
rep = ex.last_fleet_report
out["steps_by_device"] = list(rep.steps_by_device)

def fail_last(device, step):
    if device == out["n_devices"] - 1:
        raise RuntimeError("injected fault")

ex_fo = PlanExecutor(geom, plan, fleet=FleetConfig(step_hook=fail_last))
out["failover_s"] = timed(ex_fo)
out["failover_retried"] = ex_fo.last_fleet_report.retried

print("RESULT:" + json.dumps(out))
"""


def run(devices: int = 8, n: int = 48):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    # the child forces virtual host devices: pin it to the CPU so it
    # never contends for an accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(devices), str(n)],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(f"fleet bench subprocess failed:\n"
                           f"{proc.stderr[-3000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    r = json.loads(line[len("RESULT:"):])

    ratio = r["fleet_s"] / r["single_s"]
    common.emit("fleet/single_device", r["single_s"] * 1e6,
                f"steps={r['n_steps']}")
    common.emit(f"fleet/fleet{r['n_devices']}dev", r["fleet_s"] * 1e6,
                f"fleet_over_single={ratio:.2f}x")
    common.emit("fleet/failover", r["failover_s"] * 1e6,
                f"retried={r['failover_retried']} "
                f"over_fleet={r['failover_s'] / r['fleet_s']:.2f}x")
    print(f"# {r['n_steps']} steps over {r['n_devices']} forced host "
          f"devices: {r['steps_by_device']}")
    print(f"# fleet/single = {ratio:.2f}x on SHARED cores — overhead "
          f"measurement, not a scaling claim (see module docstring)")
    return r


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--n", type=int, default=48,
                    help="cubic volume edge (default 48)")
    args = ap.parse_args(argv)
    run(devices=args.devices, n=args.n)


if __name__ == "__main__":
    enable_compile_cache()
    main()
