#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_trace.py --workload resnet152.shallow \\
        --seed 7 --seconds 0.25 --out chiprun_out/trace.xplane.pb

A run of the cell as ``run.py`` prepares it, then ``--seconds`` of its
load under the profiler with ``run.py``'s options and the program's
spans on; the ``.xplane.pb`` is copied to ``--out`` and the window and
host spans (wall-clock ns), as ``trace_reduce.reduce`` takes them, to
``--out`` + ``.json``.  Needs a TPU.
"""
import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import run as R  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.25)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = R.CACHE_DIR
    import jax
    from bench import spec, trace_reduce
    from repro import obs
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    R.device_info(cell.chips)
    _, pool, system, _ = R.prepare(cell, args.seed)
    obs.configure(enabled=True)
    trace_dir = R.start_trace()
    t0, t1, _ = R.drive(system, cell.traffic, args.seed, pool, args.seconds)
    jax.profiler.stop_trace()
    system.close()
    path = trace_reduce.find(trace_dir)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(path, args.out)
    side = {"window": [R.wall(t0), R.wall(t1)],
            "host": [[R.wall(a), R.wall(b), n]
                     for a, b, n in R.host_activity(obs.get_tracer().spans())]}
    with open(args.out + ".json", "w") as f:
        json.dump(side, f)
    print(args.out, os.path.getsize(args.out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
