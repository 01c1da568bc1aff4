#!/usr/bin/env python3
"""The benchmark's one command: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A run, all of it from ``--seed``:

1. turns JAX's persistent compilation cache on at ``<checkout>/.jax_cache``;
2. draws the configuration's weights on the device
   (``bench/reference/<family>.py`` ``init``) and builds the engine and
   the async server through the program's entry points (``sut.py``);
3. calibrates the exit thresholds to the traffic's exit shares on a set
   of images disjoint from the traffic, from the masked step's own
   confidences;
4. warms up every shape the window uses;
5. drives the traffic (``load.py``) for ``--seconds``; with ``--trace 1``
   under the JAX profiler, with the program's spans on;
6. compares a sample of the answers with the plain reference
   (``check.py``);
7. prints each compared number beside its limit on standard error, and
   as the last line of standard output one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
   or with ``--trace 1`` its per-layer ones, each read by
   ``bench/metrics/<name>.py``), ``device``, ``breakdown`` (traced runs)
   and, last, ``compared``.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for, or when the checkout lacks the program.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
#: the compile cache's one fixed place, inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: rows of answers compared with the reference per run
CHECK_ROWS = 384
#: images the traffic draws from, and the disjoint calibration set
POOL = 256
CALIBRATION = 256
#: seconds of the traffic's own load served before the window
WARM_S = 1.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: wall-clock ns minus monotonic ns, read once per process
WALL_OFFSET_NS = time.time_ns() - time.monotonic_ns()


class NoChip(Exception):
    pass


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCounter:
    """Backend compiles seen while ``active`` (JAX monitoring events)."""

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if self.active and name == COMPILE_EVENT:
            self.count += 1


def start_trace() -> str:
    """Start the JAX profiler into a new temporary directory, recording
    the device alone: host tracing (the runtime's events on every
    host-to-device copy) slows the host side of this server tenfold and
    more, so the host's activity comes from the program's spans."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return trace_dir


def prepare(cell, seed: int, system=None):
    """Weights, images, engine and server for ``seed``, calibrated and
    warm.  ``system``: an earlier seed's, whose compiled programs the new
    weights reuse."""
    import jax
    from bench import images, spec
    from bench.sut import System, make_weights
    cfg, traffic = cell.config, cell.traffic
    clock = [time.monotonic()]

    def lap(what):
        now = time.monotonic()
        print(f"setup {what} {now - clock[0]:.3f} s", file=sys.stderr)
        clock[0] = now

    params = jax.block_until_ready(
        make_weights(cfg, seed, spec.family(cfg, "reference")))
    lap("weights")
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    pool = images.clutter_images(jax.random.fold_in(key, 1), POOL,
                                 cfg["img_res"])
    calib = images.clutter_images(jax.random.fold_in(key, 2), CALIBRATION,
                                  cfg["img_res"])
    lap("images")
    if system is None:
        system = System(cfg, params)
    else:
        system.reset(params)
    tau = system.calibrate(calib, traffic["exit_shares"])
    lap("engine and calibration")
    system.warm_up(pool, traffic["sizes"])
    lap("warm-up")
    drive(system, traffic, seed ^ 0x5A5A5A5A, pool, WARM_S)
    lap("warm load")
    return params, pool, system, tau


def reference_numbers(cell, params, pool, reqs, seed, tau, control=False):
    """The compared numbers of a sample of ``reqs`` (empty when nothing
    was answered).  ``control``: those of the fp8 reference (and the
    difficulty of fp8 images) put in the program's place, instead of the
    served answers."""
    import numpy as np
    from bench import check, spec
    cfg = cell.config
    picked = check.sample(reqs, seed, CHECK_ROWS)
    if not picked:
        return {}
    family = spec.family(cfg, "reference")
    x = np.concatenate([r.images(pool) for r in picked])
    logits = check.reference_logits(family, cfg, params, x)
    alpha = check.reference_alpha(cfg, x)
    if control:
        a8 = check.reference_alpha(cfg, x, fp8=True)
        ex, pred, conf = check.decide(
            check.reference_logits(family, cfg, params, x, fp8=True),
            a8, tau, cfg["beta_diff"])
        served = {"exit_idx": ex, "pred": pred, "conf": conf, "alpha": a8}
    else:
        served = check.served_rows(picked)
    return check.compare(served, logits, alpha, tau, cfg["beta_diff"])


def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, out=sys.stderr):
    """One run of ``cell``; returns the result object.
    ``require_tpu=False`` lets a test drive every step on the CPU."""
    import gc

    import jax
    import numpy as np
    from bench import check, spec
    from bench.record import Run

    device = device_info(cell.chips) if require_tpu else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": cell.chips}
    print(f"setup process and JAX {time.monotonic() - T_START:.3f} s",
          file=sys.stderr)
    cfg, traffic = cell.config, cell.traffic
    compiles = CompileCounter()
    params, pool, system, tau = prepare(cell, seed)

    if trace:
        from repro import obs
        obs.configure(enabled=True, trace_capacity=1 << 18)
        trace_dir = start_trace()
    setup_s = time.monotonic() - T_START
    compiles.active = True
    t0, t1, reqs = drive(system, traffic, seed, pool, seconds)
    compiles.active = False
    spans = None
    if trace:
        jax.profiler.stop_trace()
        spans = obs.get_tracer().spans()
        obs.reset()
    system.close()
    device["memory_peak_bytes"] = int(max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell.chips]))
    misrouted = system.misrouted
    del system
    gc.collect()

    # -- after the window, the peak reading and the program's state
    numbers = reference_numbers(cell, params, pool, reqs, seed, tau)
    compared = {k: {"value": numbers.get(k), "limit": cell.limits[k]}
                for k in check.NUMBERS}
    compared["unanswered"] = {"value": check.unanswered(reqs), "limit": 0}
    compared["compiles_in_window"] = {"value": compiles.count, "limit": 0}
    compared["calibration_misrouted"] = {"value": misrouted, "limit": 0}
    correct = bool(numbers) and all(
        v["value"] is not None and v["value"] <= v["limit"]
        for v in compared.values() if v["limit"] is not None)

    run = Run(cell=cell, t0=t0, t1=t1, requests=reqs, setup_s=setup_s,
              spans=spans, device=device, tau=tau)
    breakdown = None
    if trace:
        from bench import trace_reduce
        run.trace = trace_reduce.reduce(
            trace_reduce.find(trace_dir), window=(wall(t0), wall(t1)),
            host=[(wall(a), wall(b), n) for a, b, n in host_activity(spans)])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    served = [r.result["exit_idx"] for r in reqs if r.result is not None]
    hist = np.bincount(np.concatenate(served) if served else [],
                       minlength=cfg["n_exits"])
    print(f"tau {tau.tolist()} served exit histogram {hist.tolist()} "
          f"setup_s {setup_s:.3f}", file=out)
    per_s = np.zeros(max(int(np.ceil(run.seconds)), 1), int)
    for r in run.answered_in_window():
        per_s[min(int(r.done - t0), len(per_s) - 1)] += r.n
    print(f"samples answered in each second of the window "
          f"{per_s.tolist()}", file=out)
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} limit {v['limit']}", file=out)
    result = {"correct": correct, "attempted": len(reqs),
              "failed": sum(r.result is None for r in reqs),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def wall(t_mono: float) -> int:
    """Wall-clock ns of a ``time.monotonic()`` reading (the trace's
    clock)."""
    return int(t_mono * 1e9) + WALL_OFFSET_NS


#: what the host is doing in a program span, by span name
HOST_ACTIVITY = {"admit": "admission (Eq. 8 estimate, copy to device)",
                 "compiled_step": "a bucket in flight (dispatch to answers)",
                 "queue_wait": "requests queued, no bucket dispatched"}


def host_activity(spans):
    """(start, end, activity) monotonic intervals from the program's
    spans, one per distinct interval."""
    seen = set()
    for s in spans or ():
        if s["name"] in HOST_ACTIVITY and s["dur"] > 0:
            seen.add((s["ts"], s["ts"] + s["dur"], HOST_ACTIVITY[s["name"]]))
    return sorted(seen)


def drive(system, traffic, seed, pool, seconds):
    from bench import load
    if traffic["loop"] == "closed":
        plans = load.plan_closed(traffic, seed, int(seconds * 64) + 64,
                                 len(pool))
        return load.run_closed(system.submit, plans, pool, seconds)
    reqs = load.plan_open(traffic, seed, seconds, len(pool))
    return load.run_open(system.submit, reqs, pool, seconds,
                         traffic["senders"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    try:
        from bench import spec
        cell = spec.load_cell(args.workload)
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except (ImportError, KeyError, OSError) as e:
        print(f"bench: cannot run {args.workload!r}: {e!r}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
