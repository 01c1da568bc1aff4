#!/usr/bin/env python3
"""Readings that set the limits of ``check.py``, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 3 --seconds 4

For each seed, a run of the cell as ``run.py`` makes it (weights,
calibration, warm-up, a window of the cell's own load, the check of a
sample of its answers), printing the program's compared numbers.  For
the first ``--control-seeds`` seeds it also prints the control's: the
fp8 reference (``bench/reference/fp8.py``), with the difficulty of the
images rounded to fp8 in bfloat16 arithmetic, put in the program's place
on the same sampled images.  Seeds after the
first reuse the first seed's compiled programs with new weights.

The lower reading of a number is the largest the program gives over the
seeds, the upper one the smallest the control gives; PERF.md records
both and the limit set between them.  The benchmark's own runs never
run this.  Needs a TPU, like ``run.py``.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = R.CACHE_DIR
    from bench import check, spec
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    print(json.dumps(R.device_info(cell.chips)))
    seeds = [int(s) for s in args.seeds.split(",")]
    system, rows = None, []
    for i, seed in enumerate(seeds):
        params, pool, system, tau = R.prepare(cell, seed, system)
        _, _, reqs = R.drive(system, cell.traffic, seed, pool, args.seconds)
        row = {"seed": seed, "unanswered": check.unanswered(reqs),
               "program": R.reference_numbers(cell, params, pool, reqs,
                                              seed, tau)}
        if i < args.control_seeds:
            row["control"] = R.reference_numbers(cell, params, pool, reqs,
                                                 seed, tau, control=True)
        print(json.dumps(row), flush=True)
        rows.append(row)
    system.close()
    for k in check.NUMBERS:
        low = max(r["program"][k] for r in rows)
        ctl = [r["control"][k] for r in rows if "control" in r]
        print(f"{k}: program max {low} over {len(rows)} seeds; control min "
              f"{min(ctl) if ctl else None} over {len(ctl)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
