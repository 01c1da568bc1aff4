"""Benchmark entrypoint: one benchmark per paper table/figure (Table I,
Table II, Fig. 2) plus the §III.B overhead measurement.  Prints CSV
blocks per benchmark and writes JSON artifacts under artifacts/bench/.

Budget: REPRO_BENCH_BUDGET = quick (default) | std | full.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table1,table2,fig2,overhead")
    args = ap.parse_args()
    wanted = set(args.only.split(",")) if args.only else None

    from benchmarks import table1, table2, fig2, overhead

    benches = [("overhead", overhead.main), ("table1", table1.main),
               ("table2", table2.main), ("fig2", fig2.main)]
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
