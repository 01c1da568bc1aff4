#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow across the window.

    python3 bench/sweep.py --workload resnet152.poisson --seed 1 \\
        --rates 60,80,100,120 --seconds 6

One process prepares the cell as ``run.py`` does, then offers each rate
in turn with the traffic file's arrival process and sizes, and prints
per rate the completed rate, the backlog (requests sent and not yet
answered) at each quarter of the window, and the latency percentiles.
The benchmark's own runs never run this; the rate it finds goes into
the traffic file as a number.  Needs a TPU.
"""
import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = R.CACHE_DIR
    import numpy as np
    from bench import load, spec
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    print(json.dumps(R.device_info(cell.chips)))
    _, pool, system, _ = R.prepare(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_rps=rate)
        reqs = load.plan_open(traffic, args.seed, args.seconds, len(pool))
        backlog, stop = [], threading.Event()

        def watch():
            while not stop.wait(args.seconds / 4):
                backlog.append(sum(r.sent is not None and r.done is None
                                   for r in reqs))

        t = threading.Thread(target=watch, daemon=True)
        t.start()
        t0, t1, _ = load.run_open(system.submit, reqs, pool, args.seconds,
                                  traffic["senders"])
        stop.set()
        t.join()
        done = [r for r in reqs if r.result is not None]
        lat = np.asarray([(r.done - t0 - r.due) * 1e3 for r in done])
        in_window = sum(1 for r in done if r.done <= t1)
        print(json.dumps({
            "rate_rps": rate, "offered": len(reqs),
            "completed_rps_in_window": in_window / args.seconds,
            "samples_per_s_in_window": sum(r.n for r in done
                                           if r.done <= t1) / args.seconds,
            "backlog_quarters": backlog[:4],
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95)),
            "unanswered": len(reqs) - len(done)}), flush=True)
        time.sleep(1.0)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
