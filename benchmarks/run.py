# One module per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   Fig. 5   -> fa_overhead            (FA-2 tile-update overhead, SU-FA cut)
#   Fig. 16/18a -> complexity_reduction (DLZS/SADS/SU-FA equivalent-adds)
#   Fig. 17a/18b -> topk_hit            (DLZS vs SLZS hit rate; acc<->RC)
#   Fig. 19/20/22a -> throughput        (dense vs STAR wall clock + traffic)
#   Fig. 23/24 -> spatial               (DRAttention/MRCA mesh simulation)
#   Table III -> roofline_table         (per-cell roofline from the dry-run)
#   (beyond-paper) -> serving           (paged KV cache vs dense slot cache:
#                                        TTFT, tok/s, KV footprint ratio)

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (complexity_reduction, fa_overhead,
                            roofline_table, serving, spatial, throughput,
                            topk_hit)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    modules = [fa_overhead, complexity_reduction, topk_hit, throughput,
               spatial, roofline_table, serving]
    failed = []
    for mod in modules:
        try:
            mod.run()
        except Exception:  # noqa: BLE001 — report per-table, keep going
            traceback.print_exc()
            failed.append(mod.__name__)
    try:
        throughput.run_kernels()
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        failed.append("throughput.run_kernels")
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
