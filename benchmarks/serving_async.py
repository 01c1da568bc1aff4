"""Async-scheduler benchmark: open-loop load sweep of the
``repro.serving`` request scheduler vs per-request eager dispatch
(ISSUE 3 acceptance: the scheduler sustains >= 2x the throughput of
per-request dispatch at equal p95 latency).

Workload: an OPEN-LOOP request stream — arrival times are drawn up
front (Poisson, or bursty on/off Poisson with --bursty) and requests
are submitted at those times regardless of how the server keeps up, so
queueing delay shows up in the latency numbers instead of silently
throttling the load.  Two servers face identical streams:

* ``eager / request``  — the baseline a naive deployment runs: one
  ``engine.infer`` call per request, FIFO, synchronous.
* ``scheduler``        — ``AsyncDartServer``: difficulty-aware
  admission (Eq. 8 at enqueue), size-or-deadline bucket consolidation,
  one padded compiled dispatch per flushed bucket.

Before any timing, every scheduler output is checked identical to the
eager oracle (exit_idx/pred bit-equal, conf to float tolerance).

The sweep raises the offered rate from below the baseline's capacity to
several multiples of it; a rate is SUSTAINED when p95 latency stays
under --slo-ms.  The verdict compares the highest sustained achieved
throughput of each server.

Run:  PYTHONPATH=src python -m benchmarks.serving_async
      [--request 4] [--secs 2] [--slo-ms 200] [--steps 40] [--bursty]
"""
import argparse
import sys
import time

import numpy as np


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--request", type=int, default=4,
                    help="samples per request")
    ap.add_argument("--secs", type=float, default=2.0,
                    help="submission window per load point")
    ap.add_argument("--slo-ms", type=float, default=250.0,
                    help="p95 target defining 'sustained'")
    ap.add_argument("--steps", type=int, default=40,
                    help="brief training steps (policy realism)")
    ap.add_argument("--max-requests", type=int, default=400,
                    help="cap on requests per load point")
    ap.add_argument("--bursty", action="store_true",
                    help="on/off bursty arrivals instead of Poisson")
    ap.add_argument("--passes", type=int, default=2,
                    help="measurement passes per load point (best "
                         "counts; this container throttles in bursts)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI obs smoke: measure enabled-vs-disabled "
                         "observability overhead, validate the scraped "
                         "metrics file, write artifacts/perf/obs.json")
    return ap


ARGS = _parser().parse_args([])          # defaults; real argv under __main__
if __name__ == "__main__":
    ARGS = _parser().parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

import jax.numpy as jnp                                    # noqa: E402

from repro.core.routing import DartParams                  # noqa: E402
from repro.data.datasets import DatasetConfig, make_batch  # noqa: E402
from repro.engine import DartEngine                        # noqa: E402
from repro.serving import AsyncDartServer, SchedulerConfig  # noqa: E402
from benchmarks.common import train_model                  # noqa: E402

CIFAR = DatasetConfig(name="synth-cifar", n_train=2048, n_eval=2048)


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------
def arrival_times(rate, secs, rng, n_max, bursty=False):
    """Absolute arrival offsets (s).  Poisson, or on/off bursty (5x the
    rate 20% of the time, 0.5x otherwise — EENet-style traffic where a
    per-distribution exit budget matters)."""
    t, out = 0.0, []
    while t < secs and len(out) < n_max:
        r = rate
        if bursty:
            r = 5.0 * rate if (int(t * 2) % 5 == 0) else 0.5 * rate
        t += rng.exponential(1.0 / r)
        out.append(t)
    return np.asarray(out)


def make_requests(n, request, rng):
    """n request batches drawn (with reshuffling) from the eval split."""
    x, _ = make_batch(CIFAR, range(2048), split="eval")
    x = np.asarray(x)
    idx = rng.permutation(len(x))
    reqs = []
    for i in range(n):
        a = (i * request) % (len(x) - request)
        reqs.append(x[idx[a:a + request]])
    return reqs


# ---------------------------------------------------------------------------
# the two servers
# ---------------------------------------------------------------------------
def run_baseline(engine, requests, arrivals):
    """Per-request eager dispatch, FIFO: latency includes queueing."""
    lats = []
    t0 = time.perf_counter()
    for x, t_arr in zip(requests, arrivals):
        now = time.perf_counter() - t0
        if now < t_arr:
            time.sleep(t_arr - now)
        out = engine.infer(x, mode="masked", record=True)
        np.asarray(out["pred"])            # materialize
        lats.append((time.perf_counter() - t0 - t_arr) * 1e3)
    total = time.perf_counter() - t0
    return np.asarray(lats), len(requests) * requests[0].shape[0] / total


def run_scheduler(engine, requests, arrivals, slo_ms):
    srv = AsyncDartServer(engine, SchedulerConfig(
        max_batch=128, flush_ms=5.0, margin_ms=15.0, max_queue=512))
    t0 = time.perf_counter()
    futs = []
    for x, t_arr in zip(requests, arrivals):
        now = time.perf_counter() - t0
        if now < t_arr:
            time.sleep(t_arr - now)
            now = time.perf_counter() - t0
        # lag: how far the submission loop itself fell behind the
        # scheduled arrival — charged to the scheduler so both servers'
        # latencies are measured from the SAME clock (arrival), exactly
        # like run_baseline's perf_counter()-t0-t_arr.
        futs.append((srv.submit(x, deadline_ms=slo_ms),
                     max(0.0, now - t_arr)))
    outs = [(f.result(), lag) for f, lag in futs]
    total = time.perf_counter() - t0
    srv.close()
    lats = np.asarray([o["latency_ms"] + lag * 1e3 for o, lag in outs])
    return lats, len(requests) * requests[0].shape[0] / total, srv


def check_oracle(engine, oracle, requests):
    """Every scheduler output must match serving the request alone."""
    srv = AsyncDartServer(engine, SchedulerConfig(max_batch=128,
                                                  flush_ms=2.0))
    futs = [srv.submit(x) for x in requests]
    outs = [f.result(timeout=300) for f in futs]
    srv.close()
    for x, out in zip(requests, outs):
        ref = oracle.infer(x, mode="masked", record=False)
        np.testing.assert_array_equal(out["exit_idx"],
                                      np.asarray(ref["exit_idx"]))
        np.testing.assert_array_equal(out["pred"], np.asarray(ref["pred"]))
        np.testing.assert_allclose(out["conf"], np.asarray(ref["conf"]),
                                   rtol=2e-5, atol=2e-5)
    return len(outs)


# ---------------------------------------------------------------------------
def run(request=ARGS.request, secs=ARGS.secs, slo_ms=ARGS.slo_ms,
        steps=ARGS.steps, bursty=ARGS.bursty, seed=ARGS.seed,
        n_max=ARGS.max_requests):
    from repro.models.cnn_zoo import AlexNetConfig
    cfg = AlexNetConfig(img_res=32, n_classes=10,
                        channels=(16, 32, 48, 32, 32), fc_dims=(128, 64))
    tr = train_model(cfg, CIFAR, steps=steps, batch=64)
    dart = DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                      beta_diff=0.3)
    kw = dict(dart=dart, cum_costs=[0.3, 0.7, 1.0], adapt=True,
              update_every=10 ** 9)
    base_eng = DartEngine.from_config(cfg, tr.params, **kw)
    sched_eng = DartEngine.from_config(cfg, tr.params, **kw)
    oracle = DartEngine.from_config(cfg, tr.params, **kw)

    rng = np.random.RandomState(seed)
    # warm every compiled shape both servers will hit
    warm = make_requests(1, request, rng)[0]
    base_eng.infer(warm, mode="masked", record=False)
    for b in sched_eng.compactor.buckets:
        if b <= 128:
            sched_eng.infer(warm[:min(request, b)], mode="masked",
                            record=False, pad_to=b)
            oracle.infer(warm[:min(request, b)], mode="masked",
                         record=False, pad_to=b)

    n_checked = check_oracle(sched_eng, oracle,
                             make_requests(32, request, rng))
    print(f"oracle check: {n_checked} scheduler requests bit-identical "
          f"to per-request eager dispatch")

    # Thorough warmup of BOTH serving paths end to end (jit caches,
    # telemetry fold, thread pools) — this 2-core container needs it or
    # the first sweep points measure cold-path overhead, not serving.
    print("warming serving paths ...")
    warm_reqs = make_requests(128, request, rng)
    run_baseline(base_eng, warm_reqs, np.zeros(len(warm_reqs)))
    run_scheduler(sched_eng, warm_reqs, np.zeros(len(warm_reqs)), slo_ms)
    run_scheduler(sched_eng, warm_reqs[:48], np.arange(48) * 0.02, slo_ms)
    run_baseline(base_eng, warm_reqs[:48], np.arange(48) * 0.02)

    # baseline capacity: warm per-request service rate
    reqs = make_requests(64, request, rng)
    t0 = time.perf_counter()
    for x in reqs:
        np.asarray(base_eng.infer(x, mode="masked", record=True)["pred"])
    cap = 64 / (time.perf_counter() - t0)         # requests/s
    kind = "bursty" if bursty else "poisson"
    print(f"\nasync DART serving — {request}-sample requests, {kind} "
          f"arrivals, SLO p95<={slo_ms:.0f}ms, baseline capacity "
          f"~{cap:.0f} req/s")
    print(f"{'offered':>10} {'server':>12} {'achieved/s':>11} "
          f"{'p95 ms':>8} {'p99 ms':>8} {'miss%':>6} {'ok':>3}")

    time.sleep(3.0)                # let the container's CPU burst settle
    sustained = {"eager": 0.0, "sched": 0.0}
    ceiling = {"eager": 0.0, "sched": 0.0}
    rows = []
    # 1x is the baseline's knee; the finer 1.5-3.5x ladder brackets the
    # scheduler's (its capacity sits between 2x and 4x of eager's).
    for mult in (1.0, 1.5, 2.0, 2.5, 3.5):
        rate = mult * cap
        arr = arrival_times(rate, secs, np.random.RandomState(seed + 1),
                            n_max, bursty)
        reqs = make_requests(len(arr), request,
                             np.random.RandomState(seed + 2))
        for name in ("eager", "sched"):
            # best of --passes runs per point: this host throttles CPU
            # in bursts, and one bad window shouldn't decide the sweep
            best = None
            for _ in range(ARGS.passes):
                if name == "eager":
                    lats, tput = run_baseline(base_eng, reqs, arr)
                else:
                    lats, tput, _ = run_scheduler(sched_eng, reqs, arr,
                                                  slo_ms)
                p95, p99 = np.percentile(lats, [95, 99])
                miss = float(np.mean(lats > slo_ms))
                cand = (p95 > slo_ms, -tput, p95, p99, miss, tput)
                if best is None or cand < best:
                    best = cand
                time.sleep(1.0)
            bad, _, p95, p99, miss, tput = best
            ok = not bad
            if ok:
                sustained[name] = max(sustained[name], tput)
            ceiling[name] = max(ceiling[name], tput)
            rows.append({"offered": rate * request, "server": name,
                         "achieved": tput, "p95": p95, "p99": p99,
                         "sustained": ok})
            print(f"{rate * request:>10.0f} {name:>12} {tput:>11.0f} "
                  f"{p95:>8.1f} {p99:>8.1f} {100 * miss:>5.0f}% "
                  f"{'Y' if ok else 'n':>3}")

    st = sched_eng.stats()
    if "requests" in st:
        lm = st["requests"]["latency_ms"]
        print(f"scheduler EngineState telemetry: "
              f"{st['requests']['requests']} requests, p50/p95/p99 = "
              f"{lm['p50']:.1f}/{lm['p95']:.1f}/{lm['p99']:.1f} ms, "
              f"miss rate {100 * st['requests']['miss_rate']:.1f}%")
    # Acceptance: highest SLO-sustained throughput of each server.  If
    # eager never met the SLO, credit it its capacity CEILING (the best
    # throughput it reached at ANY latency) — an upper bound on what it
    # could sustain, so the comparison can only understate the speedup.
    denom = sustained["eager"] or ceiling["eager"]
    speedup = sustained["sched"] / max(denom, 1e-9)
    verdict = "PASS" if speedup >= 2.0 else "FAIL"
    note = "" if sustained["eager"] \
        else " (eager never met the SLO; using its capacity ceiling)"
    print(f"\nacceptance (scheduler >= 2x per-request eager dispatch at "
          f"equal p95): {sustained['sched']:.0f} vs {denom:.0f} "
          f"samples/s{note} -> {speedup:.2f}x -> {verdict}")
    return {"rows": rows, "speedup": speedup, "sustained": sustained,
            "ceiling": ceiling}


# ---------------------------------------------------------------------------
# obs smoke: enabled-vs-disabled overhead + metrics-file validation
# ---------------------------------------------------------------------------
#: metric families the scraped exposition must carry after a live run
#: (the PR 8 acceptance list: per-lane latency, exit-depth histograms,
#: DAES, recompile + dispatch-fallback counters)
REQUIRED_FAMILIES = (
    "dart_requests_total", "dart_requests_completed_total",
    "dart_request_latency_ms", "dart_exits_total", "dart_flushes_total",
    "dart_lane_daes", "dart_lane_speedup", "dart_lane_power_eff",
    "dart_engine_latency_ms", "dart_engine_exits_total",
    "dart_recompiles_total", "dart_kernel_dispatch_total",
    "dart_scheduler_events_total")


def run_obs_smoke(request=None, steps=10, passes=3, n_requests=96):
    """Closed-loop throughput with obs disabled vs enabled (exporter
    on), alternated per pass so the container's CPU-burst throttling
    hits both arms; ``obs.overhead`` = best-enabled / best-disabled
    throughput, gated at >= 0.95 by ``perf_iterate --check``."""
    import json
    import os

    import repro.obs as obs
    from repro.obs.metrics import parse_prometheus
    from repro.models.cnn_zoo import AlexNetConfig

    request = request or ARGS.request
    cfg = AlexNetConfig(img_res=32, n_classes=10,
                        channels=(16, 32, 48, 32, 32), fc_dims=(128, 64))
    tr = train_model(cfg, CIFAR, steps=steps, batch=64)
    dart = DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                      beta_diff=0.3)
    kw = dict(dart=dart, cum_costs=[0.3, 0.7, 1.0], adapt=True,
              update_every=10 ** 9)
    eng_off = DartEngine.from_config(cfg, tr.params, **kw)
    eng_on = DartEngine.from_config(cfg, tr.params, **kw)

    rng = np.random.RandomState(ARGS.seed)
    reqs = make_requests(n_requests, request, rng)
    arr = np.zeros(len(reqs))             # closed loop: submit at once
    outdir = "artifacts/perf"
    os.makedirs(outdir, exist_ok=True)
    prom = os.path.join(outdir, "metrics.prom")

    print("obs smoke: warming both serving paths ...")
    obs.reset()
    run_scheduler(eng_off, reqs, arr, ARGS.slo_ms)
    obs.configure(enabled=True, textfile=prom)
    run_scheduler(eng_on, reqs, arr, ARGS.slo_ms)
    obs.reset()

    best = {"off": 0.0, "on": 0.0}
    keep = None                            # last enabled server (weakref)
    for i in range(passes):
        obs.reset()
        _, t_off, _ = run_scheduler(eng_off, reqs, arr, ARGS.slo_ms)
        obs.configure(enabled=True, textfile=prom)
        _, t_on, keep = run_scheduler(eng_on, reqs, arr, ARGS.slo_ms)
        best["off"] = max(best["off"], t_off)
        best["on"] = max(best["on"], t_on)
        print(f"  pass {i + 1}/{passes}: disabled {t_off:.0f}/s  "
              f"enabled {t_on:.0f}/s")
        time.sleep(0.5)

    # scrape exactly what an external scraper would read, and validate
    obs.flush_textfile()
    with open(prom) as f:
        fams = parse_prometheus(f.read())
    missing = [f for f in REQUIRED_FAMILIES if f not in fams]
    n_recompiles = sum(
        v for name, _, v in fams.get(
            "dart_recompiles_total", {}).get("samples", ())
        if name == "dart_recompiles_total")
    metrics_valid = not missing and n_recompiles == 0
    del keep
    obs.reset()

    overhead = best["on"] / max(best["off"], 1e-9)
    out = {"overhead": overhead,
           "tput_disabled": best["off"], "tput_enabled": best["on"],
           "metrics_valid": bool(metrics_valid),
           "missing_families": missing,
           "recompiles": int(n_recompiles),
           "n_families": len(fams), "metrics_file": prom}
    with open(os.path.join(outdir, "obs.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(f"obs smoke: enabled/disabled throughput ratio "
          f"{overhead:.3f} ({best['on']:.0f}/{best['off']:.0f} "
          f"samples/s), metrics file "
          f"{'VALID' if metrics_valid else 'INVALID: ' + str(missing)}"
          f" ({len(fams)} families) -> {outdir}/obs.json")
    return 0 if metrics_valid else 1


if __name__ == "__main__":
    if ARGS.smoke:
        sys.exit(run_obs_smoke())
    r = run()
    sys.exit(0 if r["speedup"] >= 2.0 else 1)
