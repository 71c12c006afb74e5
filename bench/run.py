#!/usr/bin/env python3
"""Chip benchmark of FDK reconstruction: one run of one cell.

    python3 bench/run.py --workload p5.fdk --seed 7 --seconds 10 --trace 0

Run from the root of a checkout (``BENCHMARK.json`` lists the cells).
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of the window. The last
line of standard output is one JSON object: ``correct``, ``attempted``
(scans in the window), ``failed`` (scans over the limit), ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``check`` (each
number compared with its limit, also the last line of standard error).

Exits nonzero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, or no ``repro`` package beside this directory,
and where a metric that ``BENCHMARK.json`` lists for the cell read
nothing (a program the trace reduction looks for by name did not run).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="with --trace 1, also copy the raw profiler "
                         "trace (.xplane.pb) to PATH")
    args = ap.parse_args(argv)

    # libtpu's own logs would go to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.trace:
        # the program's step spans land in the profiler trace too
        os.environ["REPRO_TRACE_XLA"] = "1"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness

    cell = harness.load_cell(ROOT, args.workload)
    try:
        import repro  # noqa: F401
    except ImportError as exc:
        print(f"bench: no repro package at {ROOT}/src ({exc})",
              file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX's devices are on "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 3
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 3
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, keep_trace=args.keep_trace)
    missing = harness.missing_metrics(cell, out, bool(args.trace))
    if missing:
        print(f"bench: {cell.name} lists {missing}, which read nothing in "
              f"this run", file=sys.stderr)
        return 4
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
