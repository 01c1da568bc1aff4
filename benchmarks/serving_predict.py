"""Admission-time exit-depth prediction benchmark (ISSUE 9 acceptance:
the predictor-on server beats the predictor-off server on sustained
samples/s at equal p95, with per-lane DAES no worse).

Workload: the open-loop Poisson stream of ``serving_async`` /
``serving_cascade``.  Two servers face identical streams over the SAME
trained ViT and the SAME DART policy:

* ``off``  — ``AsyncDartServer`` with ``predict="off"``: the pre-ISSUE-9
  scheduler.  Every compacted dispatch runs every stage's exit head +
  Alg. 1 gate, including the leading gates this policy provably never
  fires.
* ``pred`` — ``predict="conservative"``: admission-time exit-depth
  prediction.  Each bucket carries the sound Eq. 19 head-skip bound
  (``min_exit``), so the ruled-out leading exit heads + gate host syncs
  never launch; requests are laned by predicted depth band and quoted
  an admission latency (predicted depth x per-stage service EMA).

The policy is chosen so the head-skip engages for real: with
``tau = (0.9, 0.9, 0.2)`` and ``beta_diff = 0.3`` the unclipped Eq. 19
threshold of gates 0-1 exceeds the softmax-max confidence bound for
every synth-cifar difficulty (alpha >= ~0.5 measured, rule-out needs
only alpha >= 1/3), so conservative mode skips two of four stages'
launches per bucket while decisions stay BIT-IDENTICAL — checked
against the per-request oracle before any timing.

A rate is SUSTAINED when p95 stays under ``--slo-ms``; the verdict
compares the highest sustained samples/s AND requires the completion-
weighted mean DAES (Eq. 9) of the predictor server to hold the
baseline's.  The JSON result (``artifacts/perf/serving_predict.json``)
carries the ``speedup`` ratio gated by ``perf_iterate --check``.

Run:  PYTHONPATH=src python -m benchmarks.serving_predict
      [--request 8] [--secs 2] [--slo-ms 400] [--steps 40] [--smoke]
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

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.core.routing import DartParams                   # noqa: E402
from repro.data.datasets import DatasetConfig, make_batch   # noqa: E402
from repro.engine import DartEngine                         # noqa: E402
from repro.models.vit import ViTConfig, vit_init            # noqa: E402
from repro.parallel.sharding import unzip                   # noqa: E402
from repro.serving import AsyncDartServer, SchedulerConfig  # noqa: E402
from benchmarks.common import train_model                   # noqa: E402
from benchmarks.serving_async import arrival_times          # noqa: E402

OUT = "artifacts/perf"
CIFAR = DatasetConfig(name="synth-cifar", n_train=1024, n_eval=1024)

# Four exit stages so the head-skip has room to pay: gates 0-1 are
# provably dead under TAU below, gate 2 carries the live early exits.
# d_model is sized so engine compute dominates per-bucket host
# overhead — on a dispatch-bound toy model the skip's win would
# drown in scheduler fixed costs whenever the CI host throttles.
CFG = ViTConfig(name="pred-bench", img_res=32, patch=8, n_layers=5,
                d_model=96, n_heads=4, d_ff=384, n_classes=10,
                exit_layers=(0, 1, 2))
TAU = (0.9, 0.9, 0.2)
CUM_COSTS = [0.2, 0.4, 0.6, 1.0]


def make_requests(n, request, rng):
    x, _ = make_batch(CIFAR, range(1024), split="eval")
    x = np.asarray(x)
    idx = rng.permutation(len(x))
    return [x[idx[(i * request) % (len(x) - request):][:request]]
            for i in range(n)]


def build_engine(steps, seed=0):
    if steps:
        params = train_model(CFG, CIFAR, steps=steps, batch=64).params
    else:                                     # smoke: untrained policy
        params, _ = unzip(vit_init(jax.random.key(seed), CFG))
    dart = DartParams(tau=jnp.asarray(TAU), coef=jnp.ones(len(TAU)),
                      beta_diff=0.3)
    return DartEngine.from_config(CFG, params, dart=dart,
                                  cum_costs=CUM_COSTS, adapt=True,
                                  update_every=10 ** 9)


def make_config(predict):
    return SchedulerConfig(max_batch=64, flush_ms=10.0, margin_ms=30.0,
                           max_queue=1024, mode="compacted",
                           predict=predict)


def run_stream(srv, requests, arrivals, slo_ms):
    """Open-loop submission against a PERSISTENT server (same lag
    accounting as serving_async).  The server lives across load points
    so the predictor's online state — learned depth bands, the stage
    service EMA behind the quotes — carries over, exactly as it would
    in a deployment."""
    t0 = time.perf_counter()
    futs = []
    for x, t_arr in zip(requests, arrivals):
        now = time.perf_counter() - t0
        if now < t_arr:
            time.sleep(t_arr - now)
            now = time.perf_counter() - t0
        futs.append((srv.submit(x, deadline_ms=slo_ms),
                     max(0.0, now - t_arr)))
    outs = [(f.result(timeout=600), lag) for f, lag in futs]
    total = time.perf_counter() - t0
    lats = np.asarray([o["latency_ms"] + lag * 1e3 for o, lag in outs])
    return lats, len(requests) * requests[0].shape[0] / total


def agg_daes(st):
    """Completion-weighted mean Eq. 9 DAES across lanes (the predictor
    splits lanes by depth band, so per-lane rows aren't comparable
    directly between the two servers)."""
    rows = st.get("daes") or {}
    n = sum(r["n"] for r in rows.values())
    if not n:
        return None
    return sum(r["daes"] * r["n"] for r in rows.values()) / n


def check_oracle(engine, oracle, requests):
    """Every predictor-on server output must match serving the request
    alone (conservative head-skip may not change one decision)."""
    with AsyncDartServer(engine, make_config("conservative")) as srv:
        futs = [srv.submit(x) for x in requests]
        outs = [f.result(timeout=300) for f in futs]
        n_skip = srv.predictor.stats()["skip_stages"]
    if not n_skip:
        raise AssertionError(
            "head-skip never engaged: the oracle check would not "
            "exercise the skip path (policy/difficulty mismatch?)")
    for x, out in zip(requests, outs):
        ref = oracle.infer(x, mode="compacted", record=False)
        for k in ("pred", "exit_idx"):
            np.testing.assert_array_equal(out[k], ref[k], err_msg=k)
        np.testing.assert_allclose(out["conf"], ref["conf"], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(out["macs"], ref["macs"], rtol=2e-5,
                                   atol=2e-5)
    return len(outs), n_skip


# ---------------------------------------------------------------------------
def run(request=None, secs=None, slo_ms=None, steps=None, n_max=None,
        passes=None, seed=None, smoke=None):
    smoke = ARGS.smoke if smoke is None else smoke
    request = request or ARGS.request
    secs = secs or (1.0 if smoke else ARGS.secs)
    # smoke SLO is deliberately loose: the verdict is then a pure
    # throughput race (every point sustains), so a near-SLO p95 on a
    # throttled 1-core runner can't disqualify the winning load point
    slo_ms = slo_ms or (2500.0 if smoke else ARGS.slo_ms)
    steps = (0 if smoke else ARGS.steps) if steps is None else steps
    n_max = n_max or (64 if smoke else ARGS.max_requests)
    passes = passes or (3 if smoke else ARGS.passes)
    seed = ARGS.seed if seed is None else seed

    engine = build_engine(steps, seed)
    oracle = DartEngine.from_config(
        CFG, engine.params,
        dart=DartParams(tau=jnp.asarray(TAU), coef=jnp.ones(len(TAU)),
                        beta_diff=0.3),
        cum_costs=CUM_COSTS, adapt=True, update_every=10 ** 9)
    rng = np.random.RandomState(seed)

    bound = engine.min_exit_bound(alpha_lo=0.4)
    print(f"policy tau={TAU}, beta_diff=0.3: sound head-skip bound at "
          f"alpha_lo=0.4 -> min_exit={bound} of {engine.n_exits} stages")

    n_checked, n_skip = check_oracle(engine, oracle,
                                     make_requests(16, request, rng))
    print(f"oracle check: {n_checked} predictor-on server requests "
          f"bit-identical to per-request inference "
          f"({n_skip} gates skipped during the check)")

    # Persistent servers: the predictor learns its depth bands (and the
    # planner its stage-time EMA) during warmup and KEEPS them for the
    # measured sweep — cold-band lane churn would otherwise compile new
    # bucket shapes mid-measurement.  Both arms share the engine, so
    # every compiled shape one arm pays for, the other reuses.
    servers = {"off": AsyncDartServer(engine, make_config("off")),
               "pred": AsyncDartServer(engine,
                                       make_config("conservative"))}
    print("warming compiled buckets, serving paths + predictor ...")
    for srv in servers.values():
        warm = make_requests(48, request, rng)
        run_stream(srv, warm, np.zeros(len(warm)), slo_ms)
        # a SPREAD warm stream too: trickled arrivals flush the small
        # buckets (and their post-exit compaction shapes)
        run_stream(srv, warm, np.linspace(0.0, 0.8, len(warm)), slo_ms)

    # per-request capacity anchors the sweep
    reqs = make_requests(48, request, rng)
    t0 = time.perf_counter()
    for x in reqs:
        np.asarray(engine.infer(x, mode="compacted", record=True)["pred"])
    cap = 48 / (time.perf_counter() - t0)          # requests/s
    print(f"\nexit-prediction serving — {request}-sample requests, "
          f"poisson arrivals, SLO p95<={slo_ms:.0f}ms, per-request "
          f"capacity ~{cap:.0f} req/s")
    print(f"{'offered':>10} {'server':>8} {'achieved/s':>11} "
          f"{'p95 ms':>8} {'p99 ms':>8} {'miss%':>6} {'ok':>3}")

    time.sleep(1.0 if smoke else 3.0)
    sustained = {"off": 0.0, "pred": 0.0}
    ceiling = {"off": 0.0, "pred": 0.0}
    rows, ratios = [], []
    mults = (2.5, 4.0, 6.0) if smoke else (1.0, 1.5, 2.0, 3.0, 4.0)
    for mult in mults:
        rate = mult * cap
        arr = arrival_times(rate, secs, np.random.RandomState(seed + 1),
                            n_max)
        reqs = make_requests(len(arr), request,
                             np.random.RandomState(seed + 2))
        # unmeasured compile pass first: each point's stream mix can
        # reach post-exit stage shapes no earlier point compiled, and
        # the arms share the engine's compile cache — whichever ran
        # first in a measured pair would pay XLA for both
        for name in ("off", "pred"):
            run_stream(servers[name], reqs, arr, slo_ms)
        best = {}
        # The two arms run back-to-back inside each pass (order
        # alternating), and the GATED verdict is the median of the
        # per-pair throughput ratios: this container throttles in
        # multi-second bursts, and a paired ratio over the identical
        # stream cancels drift a best-of comparison can't.
        for p in range(passes):
            pair = {}
            for name in (("off", "pred"), ("pred", "off"))[p % 2]:
                lats, tput = run_stream(servers[name], reqs, arr, slo_ms)
                p95, p99 = np.percentile(lats, [95, 99])
                miss = float(np.mean(lats > slo_ms))
                cand = (p95 > slo_ms, -tput, p95, p99, miss, tput)
                if name not in best or cand[:5] < best[name][:5]:
                    best[name] = cand
                pair[name] = tput
                time.sleep(0.5 if smoke else 1.0)
            ratios.append(pair["pred"] / max(pair["off"], 1e-9))
        for name in ("off", "pred"):
            bad, _, p95, p99, miss, tput = best[name]
            ok = not bad
            if ok:
                sustained[name] = max(sustained[name], tput)
            ceiling[name] = max(ceiling[name], tput)
            rows.append({"offered": rate * request, "server": name,
                         "achieved": tput, "p95": p95, "p99": p99,
                         "sustained": ok})
            print(f"{rate * request:>10.0f} {name:>8} {tput:>11.0f} "
                  f"{p95:>8.1f} {p99:>8.1f} {100 * miss:>5.0f}% "
                  f"{'Y' if ok else 'n':>3}")

    # both arms served the identical stream, so the completion-weighted
    # DAES over the whole sweep is directly comparable
    daes = {name: agg_daes(srv.stats()) for name, srv in servers.items()}
    pred_st = servers["pred"].stats()
    for srv in servers.values():
        srv.close()
    pr = pred_st["scheduler"]["predictor"]
    quote = pred_st["requests"].get("quote")
    print(f"\npredictor telemetry (whole sweep): "
          f"{pr['skip_stages']} gates skipped over {pr['skip_calls']} "
          f"buckets, band hit rate "
          f"{'n/a' if pr['hit_rate'] is None else round(pr['hit_rate'], 3)}")
    if quote:
        print(f"SLO quotes: {quote['quoted']} quoted, mean "
              f"{quote['mean_quote_ms']:.1f}ms, mean abs error "
              f"{quote['mean_abs_err_ms']:.1f}ms")

    # Acceptance: predictor-on beats predictor-off at equal p95.  The
    # gated ``speedup`` is the MEDIAN back-to-back pair ratio (every
    # pair served the identical stream seconds apart, so host drift
    # cancels); an SLO-failed pred arm caps it at 1.0 so a latency
    # blow-up can't hide behind a throughput win.  DAES must hold:
    # identical decisions => identical accuracy/macs, so this guards
    # the telemetry plumbing, not a routing tradeoff.
    speedup = float(np.median(ratios))
    if not sustained["pred"] and sustained["off"]:
        speedup = min(speedup, 1.0)
    daes_ok = (daes["off"] is None or daes["pred"] is None
               or daes["pred"] >= daes["off"] * 0.98)
    verdict = "PASS" if speedup > 1.0 and daes_ok else "FAIL"
    print(f"\nacceptance (prediction on > off at equal p95, DAES no "
          f"worse): median paired ratio over {len(ratios)} "
          f"back-to-back pairs -> {speedup:.2f}x "
          f"(best sustained {sustained['pred']:.0f} vs "
          f"{sustained['off']:.0f} samples/s), mean DAES "
          f"{daes['pred']} vs {daes['off']} -> {verdict}")
    result = {"rows": rows, "speedup": speedup,
              "pair_ratios": [round(r, 4) for r in ratios],
              "sustained": sustained, "ceiling": ceiling,
              "daes": {**daes, "ok": daes_ok},
              "predictor": pr, "quote": quote, "min_exit_bound": bound,
              "smoke": bool(smoke), "request": request, "slo_ms": slo_ms}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "serving_predict.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(f"result JSON -> {os.path.join(OUT, 'serving_predict.json')}")
    return result


if __name__ == "__main__":
    r = run()
    sys.exit(0 if r["speedup"] > 1.0 and r["daes"]["ok"] else 1)
