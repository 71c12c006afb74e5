#!/usr/bin/env python3
"""Readings that a cell's limit is set from, at the cell's own size.

    python3 bench/calibrate.py --workload p5.fdk --seeds 101-112 \\
        --control-seeds 101-103 [--out readings.json]

For every seed, the scan ``(seed, 0)`` of the cell's generator goes
through the cell's request, as in a run, and is compared with the
reference at the cell's sampled voxels: the program's relative RMSE.
For every control seed the same is read of the control that the cell's
configuration names under ``control``: the program with
``program_options`` (its own reduced-precision path), or the reference
with its filtered views held in ``reference_store``. The control has to
read above the limit; the program, below. One process, so set-up is paid
once. Needs the chips the cell asks for; benchmark runs never run this.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def seed_list(text: str) -> list:
    """``"1-3,7"`` -> ``[1, 2, 3, 7]``."""
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def readings(cell, seeds, control_seeds) -> dict:
    """Relative RMSE of the program on ``seeds`` and of the control on
    ``control_seeds``, each against the reference."""
    import harness
    import reference
    import traffic
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    geom = reference.geometry(cell.config["scan"])
    request = harness.make_request(
        geom, harness.program_options(cell.config))
    ctl = cell.config["control"]
    ctl_request = (harness.make_request(geom, harness.program_options(
        cell.config, **ctl["program_options"]))
        if "program_options" in ctl else None)
    one = dict(cell.mix, pool=1)
    out = {"program": {}, "control": {}, "control_kind": ctl}
    for seed in sorted(set(seeds) | set(control_seeds)):
        t0 = time.perf_counter()
        scan = traffic.make_pool(geom, one, seed)[0]
        ijk = traffic.sample_voxels(geom, cell.mix["check_voxels"], seed)
        ref = reference.fdk_at(scan, geom, ijk)
        if seed in seeds:
            out["program"][seed] = reference.rel_rmse(
                harness.at(request(scan), ijk), ref)
        if seed in control_seeds:
            got = (harness.at(ctl_request(scan), ijk) if ctl_request else
                   reference.fdk_at(scan, geom, ijk, ctl["reference_store"]))
            out["control"][seed] = reference.rel_rmse(got, ref)
        print(f"seed={seed} program={out['program'].get(seed)!r} "
              f"control={out['control'].get(seed)!r} "
              f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    prog, ctrl = list(out["program"].values()), list(out["control"].values())
    out["lower"] = max(prog) if prog else None
    out["upper"] = min(ctrl) if ctrl else None
    out["limit"] = cell.config["limits"]["rel_rmse"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    import jax

    cell = harness.load_cell(ROOT, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chips, JAX "
              f"sees {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    out = readings(cell, args.seeds, args.control_seeds)
    out.update(workload=cell.name, device=devs[0].device_kind,
               count=len(devs))
    text = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
