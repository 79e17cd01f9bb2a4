"""Benchmark harness entry: one section per paper table/figure + roofline.

``PYTHONPATH=src python -m benchmarks.run [--quick]``
Each section prints CSV (name,value,... rows) followed by a ``#`` summary
line comparing against the paper's claim.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


SECTIONS = [
    ("table1_swap_order", "benchmarks.table1_swap_order",
     "Table 1: perplexity vs #swapped layers under 4 orderings"),
    ("fig4_tradeoff", "benchmarks.fig4_tradeoff",
     "Fig 4: latency-accuracy tradeoff across policies"),
    ("fig5_kvc", "benchmarks.fig5_kvc",
     "Fig 5: KV capacity elasticity under bursty trace"),
    ("fig6_throughput", "benchmarks.fig6_throughput",
     "Fig 6: throughput / saturation sweep"),
    ("fig7_tpot", "benchmarks.fig7_tpot",
     "Fig 7: TPOT distribution per policy"),
    ("swap_overhead", "benchmarks.swap_overhead",
     "§3.3: layer swap transfer overhead"),
    ("serving_bench", "benchmarks.serving_bench",
     "end-to-end: bursty trace, chunked prefill, morph on/off TTFT gate"),
    ("kernel_bench", "benchmarks.kernel_bench",
     "kernels: wNa16 GEMM + paged attention microbench"),
    ("roofline", "benchmarks.roofline",
     "§Roofline: three-term analysis from the dry-run artifacts"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    failures = []
    for name, mod, desc in SECTIONS:
        if only and name not in only:
            continue
        print(f"\n===== {name}: {desc} =====", flush=True)
        t0 = time.time()
        try:
            __import__(mod, fromlist=["main"]).main()
            print(f"# [{name}] done in {time.time()-t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001 — report and continue
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED sections: {failures}")
        sys.exit(1)
    print("\nall benchmark sections completed")


if __name__ == "__main__":
    main()
