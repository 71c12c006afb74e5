"""Paper Fig. 11 analogue: cross-platform comparison.

The paper compares A64FX against P100/V100 GPUs (including host->device
transfer overhead, and P10 not fitting GPU memory). Without those devices
we reproduce the comparison as a bandwidth-limited MODEL — legitimate
because the paper itself establishes back-projection is bandwidth-bound:

    t(platform) ~ N_mem_bytes / effective_bw
    GUPS(platform) ~ updates / t

with published peak bandwidths, plus the PCIe transfer term for GPUs
(projections must cross the bus; the paper's Fig. 11 protocol). The
memory-capacity gate reproduces the paper's P10 observation.
"""

from __future__ import annotations

from repro.compile_cache import enable_compile_cache
from repro.configs.ct_paper import PROBLEMS

from .common import emit

PLATFORMS = {
    # name: (mem_bw GB/s, mem_capacity GB, pcie GB/s or None)
    "A64FX": (1024.0, 32.0, None),          # HBM2, host-resident
    "V100": (900.0, 16.0, 12.0),
    "P100": (732.0, 16.0, 12.0),
    "TPUv5e-chip": (819.0, 16.0, None),     # this repo's target
    "Gold6140x2": (250.0, 384.0, None),
}


def run(nb: int = 32):
    for prob in PROBLEMS:
        updates = prob.updates
        vol_bytes = prob.vol ** 3 * 4
        proj_bytes = prob.det ** 2 * prob.n_proj * 4
        # paper's N_mem model (bytes): (4 reads of proj + 1/nb vol) * 4B
        n_mem = (4 + 1 / nb) * updates * 4
        for name, (bw, cap, pcie) in PLATFORMS.items():
            need = (2 * vol_bytes + proj_bytes) / 1e9
            if need > cap:
                emit(f"xplat/{prob.label}/{name}", 0.0,
                     f"OOM need={need:.1f}GB cap={cap:.0f}GB")
                continue
            t = n_mem / (bw * 1e9)
            if pcie:
                t += proj_bytes / (pcie * 1e9)
            emit(f"xplat/{prob.label}/{name}", t * 1e6,
                 f"model_gups={updates / t / 1e9:.1f}")


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
