"""Benchmark entrypoint: one benchmark per paper table/figure + the
roofline reader.  Prints CSV blocks per benchmark and writes JSON
artifacts under artifacts/bench/.

Budget: REPRO_BENCH_BUDGET = quick (default) | std | full.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table1,table2,fig2,overhead,"
                         "kernels,roofline")
    args = ap.parse_args()
    wanted = set(args.only.split(",")) if args.only else None

    from benchmarks import table1, table2, fig2, overhead, kernels_bench, \
        roofline

    benches = [("overhead", overhead.main), ("kernels", kernels_bench.main),
               ("table1", table1.main), ("table2", table2.main),
               ("fig2", fig2.main), ("roofline", roofline.main)]
    t_all = time.time()
    for name, fn in benches:
        if wanted and name not in wanted:
            continue
        t0 = time.time()
        print(f"\n#### bench:{name} ####")
        fn()
        print(f"#### bench:{name} done in {time.time()-t0:.1f}s ####")
    print(f"\nALL BENCHMARKS DONE in {time.time()-t_all:.1f}s")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
