"""Cascade serving benchmark: open-loop load sweep of the
``repro.cascade`` difficulty-routed cascade vs serving EVERY request
through the biggest member alone (ISSUE 6 acceptance: the cascade
sustains more samples/s than biggest-member-only at equal p95).

Workload: the same open-loop Poisson stream as ``serving_async`` —
arrival times drawn up front, requests submitted on schedule regardless
of how the server keeps up.  Two servers face identical streams:

* ``big-only``  — ``AsyncDartServer`` over the biggest member: every
  request pays the big model (its own DART exits still apply, so this
  is the STRONG baseline, not full-depth static).
* ``cascade``   — ``AsyncDartServer`` over a :class:`CascadeEngine`:
  easy requests terminate in the small member, hard ones escalate and
  pay both.  The escalation threshold is set so roughly ``--esc`` of
  the stream escalates.

Before any timing, every cascade-server output is checked identical to
the per-request cascade oracle (member/exit_idx/pred bit-equal, conf to
float tolerance).  After the sweep the per-(member, class) DAES rows
from the serving telemetry are printed — the paper's Eq. 9 per lane.

The JSON result (``artifacts/perf/serving_cascade.json``) carries the
``speedup`` ratio the CI smoke gate tracks (``perf_iterate --check``).

Run:  PYTHONPATH=src python -m benchmarks.serving_cascade
      [--request 8] [--secs 2] [--slo-ms 400] [--steps 40] [--esc 0.25]
      [--smoke]
"""
import argparse
import json
import os
import sys
import time

import numpy as np


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--request", type=int, default=8,
                    help="samples per request")
    ap.add_argument("--secs", type=float, default=2.0,
                    help="submission window per load point")
    ap.add_argument("--slo-ms", type=float, default=400.0,
                    help="p95 target defining 'sustained'")
    ap.add_argument("--steps", type=int, default=40,
                    help="brief training steps (policy realism)")
    ap.add_argument("--esc", type=float, default=0.25,
                    help="target escalation fraction (sets theta)")
    ap.add_argument("--max-requests", type=int, default=300,
                    help="cap on requests per load point")
    ap.add_argument("--passes", type=int, default=2,
                    help="measurement passes per load point (best "
                         "counts; this container throttles in bursts)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI variant: untrained params, short "
                         "window, two load points")
    ap.add_argument("--seed", type=int, default=0)
    return ap


ARGS = _parser().parse_args([])          # defaults; real argv under __main__
if __name__ == "__main__":
    ARGS = _parser().parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402

from repro.cascade import CascadeEngine                    # noqa: E402
from repro.core.routing import DartParams                  # noqa: E402
from repro.data.datasets import DatasetConfig, make_batch  # noqa: E402
from repro.engine import DartEngine                        # noqa: E402
from repro.models.vit import ViTConfig, vit_init           # noqa: E402
from repro.parallel.sharding import unzip                  # noqa: E402
from repro.serving import AsyncDartServer, SchedulerConfig  # noqa: E402
from benchmarks.common import train_model                  # noqa: E402
from benchmarks.serving_async import arrival_times         # noqa: E402

OUT = "artifacts/perf"
CIFAR = DatasetConfig(name="synth-cifar", n_train=1024, n_eval=1024)

# ViT members: attention/MLP compute scales ~quadratically in d_model,
# so the capacity gap is real WALL-CLOCK on CPU (~4x/sample at batch
# 64), not just a parameter-count ratio — a conv pair this small would
# be dispatch-overhead-bound and the cascade could never win.
SMALL = ViTConfig(name="casc-small", img_res=32, patch=8, n_layers=2,
                  d_model=32, n_heads=2, d_ff=128, n_classes=10,
                  exit_layers=(0, 1))
BIG = ViTConfig(name="casc-big", img_res=32, patch=8, n_layers=8,
                d_model=160, n_heads=4, d_ff=640, n_classes=10,
                exit_layers=(2, 5))


def make_requests(n, request, rng):
    x, _ = make_batch(CIFAR, range(1024), split="eval")
    x = np.asarray(x)
    idx = rng.permutation(len(x))
    return [x[idx[(i * request) % (len(x) - request):][:request]]
            for i in range(n)]


def build_engines(steps):
    """Small + big members (shared data/policy shape) and the big-only
    baseline engine."""
    dart = DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                      beta_diff=0.3)
    kw = dict(dart=dart, cum_costs=[0.3, 0.7, 1.0], adapt=True,
              update_every=10 ** 9)
    params = {}
    for i, (name, cfg) in enumerate((("small", SMALL), ("big", BIG))):
        if steps:
            params[name] = train_model(cfg, CIFAR, steps=steps,
                                       batch=64).params
        else:                                  # smoke: untrained policy
            params[name], _ = unzip(vit_init(jax.random.key(i), cfg))
    small = DartEngine.from_config(SMALL, params["small"], **kw)
    big = DartEngine.from_config(BIG, params["big"], **kw)
    big_only = DartEngine.from_config(BIG, params["big"], **kw)
    return small, big, big_only


def pick_theta(small, x, esc_frac, beta_esc):
    """Escalation threshold hitting ~``esc_frac`` of the stream: the
    (1 - esc_frac) quantile of the small member's gate margin."""
    alpha = np.asarray(small._alpha(jnp.asarray(x)))
    out = small.infer(x, mode="masked", record=False, alpha=alpha)
    margin = np.asarray(out["conf"]) - beta_esc * alpha
    return float(np.quantile(margin, esc_frac))


def run_server(engine, requests, arrivals, slo_ms):
    """Open-loop submission against an AsyncDartServer (same lag
    accounting as benchmarks.serving_async)."""
    srv = AsyncDartServer(engine, SchedulerConfig(
        max_batch=64, flush_ms=10.0, margin_ms=30.0, max_queue=1024))
    t0 = time.perf_counter()
    futs = []
    for x, t_arr in zip(requests, arrivals):
        now = time.perf_counter() - t0
        if now < t_arr:
            time.sleep(t_arr - now)
            now = time.perf_counter() - t0
        futs.append((srv.submit(x, deadline_ms=slo_ms),
                     max(0.0, now - t_arr)))
    outs = [(f.result(), lag) for f, lag in futs]
    total = time.perf_counter() - t0
    st = srv.stats()
    srv.close()
    lats = np.asarray([o["latency_ms"] + lag * 1e3 for o, lag in outs])
    return lats, len(requests) * requests[0].shape[0] / total, st


def check_oracle(cascade, requests):
    """Every cascade-server output must match the per-request oracle."""
    srv = AsyncDartServer(cascade, SchedulerConfig(max_batch=64,
                                                   flush_ms=2.0))
    futs = [srv.submit(x) for x in requests]
    outs = [f.result(timeout=300) for f in futs]
    srv.close()
    for x, out in zip(requests, outs):
        ref = cascade.infer(x, mode="oracle")
        for k in ("pred", "exit_idx", "member"):
            np.testing.assert_array_equal(out[k], ref[k], err_msg=k)
        np.testing.assert_allclose(out["conf"], ref["conf"], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(out["macs"], ref["macs"], rtol=2e-5,
                                   atol=2e-5)
    return len(outs)


# ---------------------------------------------------------------------------
def run(request=None, secs=None, slo_ms=None, steps=None, esc=None,
        n_max=None, passes=None, seed=None, smoke=None):
    smoke = ARGS.smoke if smoke is None else smoke
    request = request or ARGS.request
    secs = secs or (1.0 if smoke else ARGS.secs)
    slo_ms = slo_ms or (1500.0 if smoke else ARGS.slo_ms)
    steps = (0 if smoke else ARGS.steps) if steps is None else steps
    esc = esc or ARGS.esc
    n_max = n_max or (64 if smoke else ARGS.max_requests)
    passes = passes or ARGS.passes
    seed = ARGS.seed if seed is None else seed

    small, big, big_only = build_engines(steps)
    rng = np.random.RandomState(seed)
    probe = np.concatenate(make_requests(32, request, rng))
    beta_esc = 0.1
    theta = pick_theta(small, probe, esc, beta_esc)
    cascade = CascadeEngine([small, big], theta=np.array([theta]),
                            beta_esc=beta_esc)
    print(f"member costs (param-count, big=1): "
          f"{np.round(cascade.member_costs, 3).tolist()}, "
          f"theta={theta:.3f} targeting ~{esc:.0%} escalation")

    n_checked = check_oracle(cascade, make_requests(16, request, rng))
    print(f"oracle check: {n_checked} cascade-server requests "
          f"bit-identical to the per-request cascade oracle")

    # Warm EVERY (member, bucket) compiled shape both servers can hit:
    # escalated remnants re-bucket at arbitrary power-of-two sizes, and
    # one mid-measurement XLA compile of the big member would decide a
    # load point by itself on this container.
    print("warming compiled buckets + serving paths ...")
    xw = probe[:64]
    for eng in (small, big, big_only):
        aw = np.asarray(eng._alpha(jnp.asarray(xw)))
        for b in eng.compactor.buckets:
            if b <= 64:
                n = min(len(xw), b)
                eng.infer(xw[:n], mode="masked", record=False, pad_to=b)
                eng.infer(xw[:n], mode="masked", record=False,
                          alpha=aw[:n], pad_to=b)
    warm = make_requests(48, request, rng)
    run_server(big_only, warm, np.zeros(len(warm)), slo_ms)
    run_server(cascade, warm, np.zeros(len(warm)), slo_ms)

    # big-only capacity anchors the sweep
    reqs = make_requests(48, request, rng)
    t0 = time.perf_counter()
    for x in reqs:
        np.asarray(big_only.infer(x, mode="masked", record=True)["pred"])
    cap = 48 / (time.perf_counter() - t0)          # requests/s
    print(f"\ncascade serving — {request}-sample requests, poisson "
          f"arrivals, SLO p95<={slo_ms:.0f}ms, big-only capacity "
          f"~{cap:.0f} req/s")
    print(f"{'offered':>10} {'server':>10} {'achieved/s':>11} "
          f"{'p95 ms':>8} {'p99 ms':>8} {'miss%':>6} {'ok':>3}")

    time.sleep(1.0 if smoke else 3.0)
    sustained = {"big": 0.0, "cascade": 0.0}
    ceiling = {"big": 0.0, "cascade": 0.0}
    rows, esc_rate, daes_rows = [], None, None
    mults = (2.0, 4.0, 6.0) if smoke else (1.0, 1.5, 2.0, 3.0, 4.0)
    for mult in mults:
        rate = mult * cap
        arr = arrival_times(rate, secs, np.random.RandomState(seed + 1),
                            n_max)
        reqs = make_requests(len(arr), request,
                             np.random.RandomState(seed + 2))
        for name, eng in (("big", big_only), ("cascade", cascade)):
            best = None
            for _ in range(passes):
                lats, tput, st = run_server(eng, reqs, arr, slo_ms)
                p95, p99 = np.percentile(lats, [95, 99])
                miss = float(np.mean(lats > slo_ms))
                cand = (p95 > slo_ms, -tput, p95, p99, miss, tput, st)
                if best is None or cand[:5] < best[:5]:
                    best = cand
                time.sleep(0.5 if smoke else 1.0)
            bad, _, p95, p99, miss, tput, st = best
            ok = not bad
            if ok:
                sustained[name] = max(sustained[name], tput)
            ceiling[name] = max(ceiling[name], tput)
            if name == "cascade":
                esc_rate = st["escalation_rate"]
                daes_rows = st["daes"]
            rows.append({"offered": rate * request, "server": name,
                         "achieved": tput, "p95": p95, "p99": p99,
                         "sustained": ok})
            print(f"{rate * request:>10.0f} {name:>10} {tput:>11.0f} "
                  f"{p95:>8.1f} {p99:>8.1f} {100 * miss:>5.0f}% "
                  f"{'Y' if ok else 'n':>3}")

    print(f"\ncascade escalation rate: "
          f"{[round(r, 3) for r in esc_rate]}")
    if daes_rows:
        print("per-(member, class) DAES (Eq. 9, macs energy model):")
        print(f"  {'lane':>10} {'n':>5} {'acc%':>6} {'speedup':>8} "
              f"{'powereff':>9} {'daes':>7}")
        for lane, r in daes_rows.items():
            print(f"  {str(lane):>10} {r['n']:>5} {r['acc_pct']:>6.1f} "
                  f"{r['speedup']:>8.2f} {r['power_eff']:>9.2f} "
                  f"{r['daes']:>7.2f}")

    # Acceptance: the cascade beats serving everything through the big
    # member at equal p95.  Ceiling fallbacks stay CONSERVATIVE for the
    # cascade: if big-only never met the SLO, its best-at-any-latency
    # throughput is the denominator (an upper bound on what it could
    # sustain); the cascade only falls back to its ceiling when NEITHER
    # server sustained (a pure throughput race).  If big-only sustained
    # and the cascade never did, the cascade fails honestly.
    denom = sustained["big"] or ceiling["big"]
    num = sustained["cascade"] or \
        (0.0 if sustained["big"] else ceiling["cascade"])
    speedup = num / max(denom, 1e-9)
    verdict = "PASS" if speedup > 1.0 else "FAIL"
    note = "" if sustained["big"] \
        else " (big-only never met the SLO; using its capacity ceiling)"
    print(f"\nacceptance (cascade > biggest-member-only at equal p95): "
          f"{num:.0f} vs {denom:.0f} samples/s{note} -> "
          f"{speedup:.2f}x -> {verdict}")
    result = {"rows": rows, "speedup": speedup, "sustained": sustained,
              "ceiling": ceiling, "escalation_rate": esc_rate,
              "member_costs": cascade.member_costs.tolist(),
              "daes": {str(k): v for k, v in (daes_rows or {}).items()},
              "smoke": bool(smoke), "request": request,
              "slo_ms": slo_ms}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "serving_cascade.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(f"result JSON -> {os.path.join(OUT, 'serving_cascade.json')}")
    return result


if __name__ == "__main__":
    r = run()
    sys.exit(0 if r["speedup"] > 1.0 else 1)
