# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (benchmarks/common.py emit()).
#
#   Fig. 6  -> bench_batch        (nb sweep + N_mem model)
#   Fig. 7/8-> bench_variants     (optimization-ladder speedups)
#   Fig. 9  -> bench_scaling      (work scaling + dry-run device scaling)
#   Fig. 10 -> bench_roofline     (AI placement, analytic + dry-run)
#   Fig. 11 -> bench_crossplatform(bandwidth-model comparison)
#   Table 3 -> bench_problems     (P1.. problem matrix, CPU-scaled)
#   (ours)  -> bench_tiled        (tiled engine tile-shape sweep)
#   (ours)  -> bench_lm_substrate (assigned-arch substrate latencies)

from __future__ import annotations

import sys
import traceback

from repro.compile_cache import enable_compile_cache


def main() -> None:
    from . import (
        bench_batch,
        bench_crossplatform,
        bench_lm_substrate,
        bench_problems,
        bench_roofline,
        bench_scaling,
        bench_tiled,
        bench_variants,
    )

    suites = [
        ("variants(Fig7/8)", bench_variants.main),
        ("batch(Fig6)", bench_batch.main),
        ("problems(Table3)", bench_problems.main),
        ("scaling(Fig9)", bench_scaling.main),
        ("roofline(Fig10)", bench_roofline.main),
        ("crossplatform(Fig11)", bench_crossplatform.main),
        ("tiled(engine)", bench_tiled.main),
        ("lm_substrate", bench_lm_substrate.main),
    ]
    failed = 0
    for name, fn in suites:
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception:  # noqa: BLE001 — report and continue
            failed += 1
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
