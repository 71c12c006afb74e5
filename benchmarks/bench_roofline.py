"""Paper Fig. 10 analogue: roofline placement of the top kernel.

The paper uses Intel Advisor on dual Gold-6140; here the roofline terms
come from an analytic arithmetic-intensity model of the kernels against
TPU v5e constants:

    AI(subline)  ~ flops / bytes
      flops/update ~ 8   (two mixes + weight + accumulate)
      bytes/update ~ (4 + 1/nb)*4 / reuse  — the paper's N_mem model

which places the kernel in the bandwidth-bound region, matching the
paper's observation that the optimized kernel sits between the L2 and L3
bandwidth ceilings on CPUs.
"""

from __future__ import annotations

from .common import emit

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def run():
    # analytic AI of the kernel family (per voxel update)
    for name, flops_per_update, bytes_per_update in [
        ("baseline", 18.0, (4 + 1.0) * 4),       # nb=1: vol rw each proj
        ("subline_nb8", 8.0, (4 + 1 / 8) * 4),
        ("subline_nb32", 8.0, (4 + 1 / 32) * 4),
        ("pallas_output_stationary", 8.0, 4.0 * 4),  # vol written once
    ]:
        ai = flops_per_update / bytes_per_update
        ridge = PEAK_FLOPS / HBM_BW
        bound = "memory" if ai < ridge else "compute"
        attainable = min(PEAK_FLOPS, ai * HBM_BW)
        emit(f"roofline/{name}", 0.0,
             f"AI={ai:.3f} bound={bound} "
             f"attainable_TFLOPs={attainable/1e12:.2f}")


def main():
    run()


if __name__ == "__main__":
    main()
