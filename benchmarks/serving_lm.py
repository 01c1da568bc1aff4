"""Batched-decode benchmark: the sharded bucketed LM decode session vs
eager per-request decode (ISSUE 4 acceptance: >= 1.5x tokens/s at equal
p95 on the CI host).

Workload: an OPEN-LOOP stream of decode requests — each request is ONE
prompt asking for ``--n-new`` greedy tokens; arrival times are drawn up
front (Poisson) and requests are submitted at those times regardless of
how the server keeps up, so queueing delay lands in the latency numbers
instead of silently throttling the load.  Two servers face identical
streams:

* ``eager / request`` — the pre-ISSUE-4 deployment: one eager
  ``LMDecodeEngine.generate`` call per request, FIFO, synchronous —
  every decode step dispatches its stage pieces as separate ops.
* ``session``         — ``engine.session()`` over a SHARDED
  ``LMDecodeEngine``: concurrent callers laned by (prompt_len, n_new),
  consolidated into one fused donated-cache compiled decode loop per
  flushed bucket.

Before any timing, every session output is checked bit-identical to the
per-request eager oracle (tokens + exit depths).

A rate is SUSTAINED when p95 latency stays under --slo-ms; the verdict
compares the highest sustained tokens/s of each server.  Results are
always written to ``artifacts/perf/serving_lm.json`` (the CI smoke job
uploads it).

``--continuous`` (ISSUE 7) runs a second sweep instead: the same
open-loop stream against

* ``session``    — the bucketed consolidation server above (the
  incumbent), and
* ``continuous`` — ``engine.session(continuous=True)``: slot-based
  continuous batching over the paged KV cache.  No flush barriers and
  no bucket padding; a request is admitted the moment a slot (and its
  KV pages) frees up, and rows at different cascade depths share every
  compiled decode launch.

Its verdict ratio (sustained continuous tokens/s over sustained
bucketed tokens/s) lands in ``artifacts/perf/serving_lm_cont.json`` as
``speedup`` and is gated in CI via ``benchmarks/baselines/smoke.json``
(baseline 1.0, tolerance 0.15).

Run:  PYTHONPATH=src python -m benchmarks.serving_lm
      [--n-new 12] [--secs 2] [--slo-ms 2000] [--steps 60] [--smoke]
      [--continuous]
"""
import argparse
import json
import os
import sys
import time

import numpy as np


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-new", type=int, default=12,
                    help="tokens generated per request")
    ap.add_argument("--prompt-len", type=int, default=9)
    ap.add_argument("--secs", type=float, default=2.0,
                    help="submission window per load point")
    ap.add_argument("--slo-ms", type=float, default=2000.0,
                    help="p95 target defining 'sustained'")
    ap.add_argument("--steps", type=int, default=60,
                    help="brief training steps (policy realism)")
    ap.add_argument("--max-requests", type=int, default=160,
                    help="cap on requests per load point")
    ap.add_argument("--passes", type=int, default=2,
                    help="measurement passes per load point (best "
                         "counts; this container throttles in bursts)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI variant: untrained params, short "
                         "window, two load points")
    ap.add_argument("--continuous", action="store_true",
                    help="sweep continuous slot-pool serving vs the "
                         "bucketed session (serving_lm_cont.json)")
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
from repro.engine import LMDecodeEngine                     # noqa: E402
from repro.launch.mesh import make_serving_mesh             # noqa: E402
from repro.models.transformer_lm import LMConfig, lm_init   # noqa: E402
from repro.parallel.sharding import unzip                   # noqa: E402
from repro.serving.loop import SchedulerConfig              # noqa: E402

CFG = LMConfig(name="lm-bench", n_layers=6, d_model=64, n_heads=4,
               n_kv_heads=2, d_ff=128, vocab=64, exit_layers=(1, 3),
               max_seq=64, remat=False)
BUCKETS = (1, 2, 4, 8, 16)
OUT = "artifacts/perf"


def train_params(steps, seed=0):
    if steps <= 0:
        return unzip(lm_init(jax.random.key(seed), CFG))[0]
    from repro.data.datasets import DatasetConfig
    from repro.runtime.trainer import Trainer, TrainConfig
    tr = Trainer(CFG, TrainConfig(batch_size=16, steps=steps, lr=5e-3),
                 DatasetConfig(name="tokens", n_train=1024),
                 data_kind="tokens")
    tr.run()
    return tr.params


def arrival_times(rate, secs, rng, n_max):
    t, out = 0.0, []
    while t < secs and len(out) < n_max:
        t += rng.exponential(1.0 / max(rate, 1e-9))
        out.append(t)
    return np.asarray(out)


def make_prompts(n, plen, rng):
    return rng.randint(0, CFG.vocab, (n, plen))


# ---------------------------------------------------------------------------
# the two servers
# ---------------------------------------------------------------------------
def run_eager(engine, prompts, arrivals, n_new):
    """Per-request eager decode, FIFO: latency includes queueing."""
    lats = []
    t0 = time.perf_counter()
    for i, t_arr in enumerate(arrivals):
        now = time.perf_counter() - t0
        if now < t_arr:
            time.sleep(t_arr - now)
        engine.generate(prompts[i:i + 1], n_new, mode="eager")
        lats.append((time.perf_counter() - t0 - t_arr) * 1e3)
    total = time.perf_counter() - t0
    return np.asarray(lats), len(arrivals) * n_new / total


def run_session(engine, prompts, arrivals, n_new, slo_ms):
    # margin_ms covers the service-time jitter of a full decode bucket
    # on a throttly CPU host: deadline'd requests are held until
    # deadline − service_EMA − margin, so a thin margin turns hold
    # jitter straight into SLO misses at light load.
    sess = engine.session(SchedulerConfig(
        max_batch=BUCKETS[-1], flush_ms=5.0, margin_ms=150.0,
        max_queue=4096, policy="reject"))
    t0 = time.perf_counter()
    futs = []
    for i, t_arr in enumerate(arrivals):
        now = time.perf_counter() - t0
        if now < t_arr:
            time.sleep(t_arr - now)
            now = time.perf_counter() - t0
        # lag: how far the submission loop fell behind the scheduled
        # arrival — charged to the session so both servers' latencies
        # are measured from the SAME clock (arrival).
        futs.append((sess.submit(prompts[i], n_new=n_new,
                                 deadline_ms=slo_ms),
                     max(0.0, now - t_arr)))
    outs = [(f.result(timeout=600), lag) for f, lag in futs]
    total = time.perf_counter() - t0
    sess.close()
    lats = np.asarray([o["latency_ms"] + lag * 1e3 for o, lag in outs])
    return lats, len(arrivals) * n_new / total


POOL = dict(n_slots=BUCKETS[-1], page_size=8)   # view_len == max_seq


def run_continuous(engine, prompts, arrivals, n_new, slo_ms):
    """Continuous slot-pool server on the same open-loop contract as
    ``run_session`` (lag charged to the server)."""
    sess = engine.session(SchedulerConfig(
        max_batch=BUCKETS[-1], flush_ms=5.0, margin_ms=150.0,
        max_queue=4096, policy="reject"), continuous=True, **POOL)
    t0 = time.perf_counter()
    futs = []
    for i, t_arr in enumerate(arrivals):
        now = time.perf_counter() - t0
        if now < t_arr:
            time.sleep(t_arr - now)
            now = time.perf_counter() - t0
        futs.append((sess.submit(prompts[i], n_new=n_new,
                                 deadline_ms=slo_ms),
                     max(0.0, now - t_arr)))
    outs = [(f.result(timeout=600), lag) for f, lag in futs]
    total = time.perf_counter() - t0
    sess.close()
    lats = np.asarray([o["latency_ms"] + lag * 1e3 for o, lag in outs])
    return lats, len(arrivals) * n_new / total


def check_oracle_cont(cont_eng, oracle, prompts, n_new):
    """Every continuous-session output must be bit-identical to the
    per-request eager path (tokens + exit depths) — the paged-KV slot
    pool may not change a single logit."""
    with cont_eng.session(SchedulerConfig(
            max_batch=BUCKETS[-1], flush_ms=2.0, max_queue=4096,
            policy="reject"), continuous=True, **POOL) as sess:
        futs = [sess.submit(p, n_new=n_new) for p in prompts]
        outs = [f.result(timeout=600) for f in futs]
    for p, out in zip(prompts, outs):
        ref_tok, ref_stg = oracle.generate(p[None], n_new, mode="eager")
        np.testing.assert_array_equal(out["tokens"], ref_tok)
        np.testing.assert_array_equal(out["stages"], ref_stg)
    return len(outs)


def check_oracle(sharded, oracle, prompts, n_new):
    """Every consolidated session output must match decoding the prompt
    alone through the eager per-stage path (tokens + exit depths)."""
    with sharded.session(SchedulerConfig(
            max_batch=BUCKETS[-1], flush_ms=2.0, max_queue=4096,
            policy="reject")) as sess:
        futs = [sess.submit(p, n_new=n_new) for p in prompts]
        outs = [f.result(timeout=600) for f in futs]
    for p, out in zip(prompts, outs):
        ref_tok, ref_stg = oracle.generate(p[None], n_new, mode="eager")
        np.testing.assert_array_equal(out["tokens"], ref_tok)
        np.testing.assert_array_equal(out["stages"], ref_stg)
    return len(outs)


# ---------------------------------------------------------------------------
def run(n_new=None, prompt_len=None, secs=None, slo_ms=None, steps=None,
        n_max=None, passes=None, seed=None, smoke=None):
    smoke = ARGS.smoke if smoke is None else smoke
    n_new = n_new or (8 if smoke else ARGS.n_new)
    prompt_len = prompt_len or ARGS.prompt_len
    secs = secs or (1.0 if smoke else ARGS.secs)
    slo_ms = slo_ms or ARGS.slo_ms
    steps = (0 if smoke else ARGS.steps) if steps is None else steps
    n_max = n_max or (48 if smoke else ARGS.max_requests)
    passes = passes or (1 if smoke else ARGS.passes)
    seed = ARGS.seed if seed is None else seed

    params = train_params(steps, seed)
    # thresholds low enough that the briefly-trained model actually
    # exits early on easy tokens — the sweep then measures the real
    # DART serving path (layer skipping + propagation), not just
    # full-depth decode
    dart = DartParams(tau=jnp.asarray([0.08, 0.1]), coef=jnp.ones(2),
                      beta_diff=0.15)
    eager_eng = LMDecodeEngine(CFG, params, dart, buckets=BUCKETS)
    shard_eng = LMDecodeEngine(CFG, params, dart, buckets=BUCKETS,
                               mesh=make_serving_mesh())
    oracle = LMDecodeEngine(CFG, params, dart, buckets=BUCKETS)

    rng = np.random.RandomState(seed)
    # warm every compiled shape both servers will hit: the session
    # consolidates 1..max_bucket prompts into one lane, the eager
    # baseline always decodes single requests
    warm = make_prompts(BUCKETS[-1], prompt_len, rng)
    eager_eng.generate(warm[:1], n_new, mode="eager")
    for b in BUCKETS:
        shard_eng.generate(warm[:b], n_new)

    n_checked = check_oracle(shard_eng, oracle,
                             make_prompts(16, prompt_len, rng), n_new)
    print(f"oracle check: {n_checked} consolidated session requests "
          f"bit-identical to per-request eager decode (tokens + exits)")

    # baseline capacity: warm per-request service rate
    reqs = make_prompts(12, prompt_len, rng)
    t0 = time.perf_counter()
    for i in range(len(reqs)):
        eager_eng.generate(reqs[i:i + 1], n_new, mode="eager")
    cap = len(reqs) / (time.perf_counter() - t0)          # requests/s
    print(f"\nLM decode serving — 1-prompt requests x {n_new} new tokens, "
          f"poisson arrivals, SLO p95<={slo_ms:.0f}ms, eager capacity "
          f"~{cap:.1f} req/s")
    print(f"{'offered tok/s':>13} {'server':>9} {'tok/s':>8} "
          f"{'p95 ms':>8} {'p99 ms':>8} {'ok':>3}")

    sustained = {"eager": 0.0, "sess": 0.0}
    ceiling = {"eager": 0.0, "sess": 0.0}
    rows = []
    # the higher points are where consolidation pays; the smoke sweep
    # still includes one so a throttled CI host can't flake the verdict
    mults = (1.5, 3.0, 5.0) if smoke else (1.0, 1.5, 2.5, 4.0, 6.0)
    for mult in mults:
        rate = mult * cap
        arr = arrival_times(rate, secs, np.random.RandomState(seed + 1),
                            n_max)
        prompts = make_prompts(len(arr), prompt_len,
                               np.random.RandomState(seed + 2))
        for name in ("eager", "sess"):
            best = None
            for _ in range(passes):
                if name == "eager":
                    lats, tput = run_eager(eager_eng, prompts, arr, n_new)
                else:
                    lats, tput = run_session(shard_eng, prompts, arr,
                                             n_new, slo_ms)
                p95, p99 = np.percentile(lats, [95, 99])
                cand = (p95 > slo_ms, -tput, p95, p99, tput)
                if best is None or cand < best:
                    best = cand
            bad, _, p95, p99, tput = best
            ok = not bad
            if ok:
                sustained[name] = max(sustained[name], tput)
            ceiling[name] = max(ceiling[name], tput)
            rows.append({"offered_tok_s": rate * n_new, "server": name,
                         "tokens_s": tput, "p95_ms": float(p95),
                         "p99_ms": float(p99), "sustained": ok})
            print(f"{rate * n_new:>13.0f} {name:>9} {tput:>8.0f} "
                  f"{p95:>8.0f} {p99:>8.0f} {'Y' if ok else 'n':>3}")

    st = shard_eng.stats()
    print(f"session engine telemetry: {st['served']} tokens served, "
          f"exit fractions {np.round(st['exit_frac'], 3).tolist()}, "
          f"{100 * st['layers_skipped'] / max(st['layers_skipped'] + st['layers_run'], 1):.0f}% "
          f"of full-depth layer compute avoided")
    # Acceptance: highest SLO-sustained tokens/s of each server.  If
    # eager never met the SLO, credit it its capacity CEILING — an
    # upper bound, so the comparison can only understate the speedup.
    denom = sustained["eager"] or ceiling["eager"]
    speedup = sustained["sess"] / max(denom, 1e-9)
    verdict = "PASS" if speedup >= 1.5 else "FAIL"
    note = "" if sustained["eager"] \
        else " (eager never met the SLO; using its capacity ceiling)"
    print(f"\nacceptance (sharded bucketed session >= 1.5x eager "
          f"per-request decode at equal p95): {sustained['sess']:.0f} vs "
          f"{denom:.0f} tokens/s{note} -> {speedup:.2f}x -> {verdict}")
    result = {"rows": rows, "speedup": speedup, "sustained": sustained,
              "ceiling": ceiling, "smoke": bool(smoke), "n_new": n_new,
              "slo_ms": slo_ms}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "serving_lm.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def run_cont(n_new=None, prompt_len=None, secs=None, slo_ms=None,
             steps=None, n_max=None, passes=None, seed=None, smoke=None):
    """ISSUE 7 sweep: continuous slot-pool serving vs the bucketed
    session on identical open-loop streams."""
    smoke = ARGS.smoke if smoke is None else smoke
    n_new = n_new or (8 if smoke else ARGS.n_new)
    prompt_len = prompt_len or ARGS.prompt_len
    secs = secs or (1.0 if smoke else ARGS.secs)
    slo_ms = slo_ms or ARGS.slo_ms
    steps = (0 if smoke else ARGS.steps) if steps is None else steps
    n_max = n_max or (48 if smoke else ARGS.max_requests)
    passes = passes or (2 if smoke else ARGS.passes)
    seed = ARGS.seed if seed is None else seed

    params = train_params(steps, seed)
    dart = DartParams(tau=jnp.asarray([0.08, 0.1]), coef=jnp.ones(2),
                      beta_diff=0.15)
    bucket_eng = LMDecodeEngine(CFG, params, dart, buckets=BUCKETS,
                                mesh=make_serving_mesh())
    cont_eng = LMDecodeEngine(CFG, params, dart, buckets=BUCKETS,
                              mesh=make_serving_mesh())
    oracle = LMDecodeEngine(CFG, params, dart, buckets=BUCKETS)

    rng = np.random.RandomState(seed)
    warm = make_prompts(BUCKETS[-1], prompt_len, rng)
    for b in BUCKETS:
        bucket_eng.generate(warm[:b], n_new)
    # warming the continuous server compiles its THREE programs total:
    # embed, decode step, and the (single) prefill shape of this sweep
    run_continuous(cont_eng, warm, np.zeros(len(warm)), n_new, slo_ms)

    n_checked = check_oracle_cont(cont_eng, oracle,
                                  make_prompts(16, prompt_len, rng),
                                  n_new)
    print(f"oracle check: {n_checked} continuous slot-pool requests "
          f"bit-identical to per-request eager decode (tokens + exits)")

    # shared load scale: warm per-request eager service rate
    reqs = make_prompts(12, prompt_len, rng)
    t0 = time.perf_counter()
    for i in range(len(reqs)):
        oracle.generate(reqs[i:i + 1], n_new, mode="eager")
    cap = len(reqs) / (time.perf_counter() - t0)          # requests/s
    print(f"\ncontinuous LM serving — 1-prompt requests x {n_new} new "
          f"tokens, poisson arrivals, SLO p95<={slo_ms:.0f}ms, eager "
          f"capacity ~{cap:.1f} req/s")
    print(f"{'offered tok/s':>13} {'server':>10} {'tok/s':>8} "
          f"{'p95 ms':>8} {'p99 ms':>8} {'ok':>3}")

    sustained = {"sess": 0.0, "cont": 0.0}
    ceiling = {"sess": 0.0, "cont": 0.0}
    rows = []
    mults = (1.5, 3.0, 5.0) if smoke else (1.0, 1.5, 2.5, 4.0, 6.0)
    for mult in mults:
        rate = mult * cap
        arr = arrival_times(rate, secs, np.random.RandomState(seed + 1),
                            n_max)
        prompts = make_prompts(len(arr), prompt_len,
                               np.random.RandomState(seed + 2))
        for name in ("sess", "cont"):
            best = None
            for _ in range(passes):
                if name == "sess":
                    lats, tput = run_session(bucket_eng, prompts, arr,
                                             n_new, slo_ms)
                else:
                    lats, tput = run_continuous(cont_eng, prompts, arr,
                                                n_new, slo_ms)
                p95, p99 = np.percentile(lats, [95, 99])
                cand = (p95 > slo_ms, -tput, p95, p99, tput)
                if best is None or cand < best:
                    best = cand
            bad, _, p95, p99, tput = best
            ok = not bad
            if ok:
                sustained[name] = max(sustained[name], tput)
            ceiling[name] = max(ceiling[name], tput)
            rows.append({"offered_tok_s": rate * n_new, "server": name,
                         "tokens_s": tput, "p95_ms": float(p95),
                         "p99_ms": float(p99), "sustained": ok})
            print(f"{rate * n_new:>13.0f} {name:>10} {tput:>8.0f} "
                  f"{p95:>8.0f} {p99:>8.0f} {'Y' if ok else 'n':>3}")

    st = cont_eng.stats()
    print(f"continuous engine telemetry: {st['served']} tokens served, "
          f"{st['continuous']['decode_steps']} pool steps, "
          f"pages peak {st['continuous']['pages_peak']}, "
          f"exit fractions {np.round(st['exit_frac'], 3).tolist()}")
    denom = sustained["sess"] or ceiling["sess"]
    speedup = sustained["cont"] / max(denom, 1e-9)
    # gate floor mirrors the committed baseline (1.0 - 15% tolerance):
    # continuous batching must at least HOLD the bucketed throughput;
    # its wins (no flush barrier, no padding, per-step reclamation)
    # show up as >1.0 on unthrottled hosts
    verdict = "PASS" if speedup >= 0.85 else "FAIL"
    note = "" if sustained["sess"] \
        else " (bucketed never met the SLO; using its ceiling)"
    print(f"\nacceptance (continuous slot-pool serving holds the "
          f"bucketed session's sustained tokens/s): "
          f"{sustained['cont']:.0f} vs {denom:.0f} tokens/s{note} -> "
          f"{speedup:.2f}x -> {verdict}")
    result = {"rows": rows, "speedup": speedup, "sustained": sustained,
              "ceiling": ceiling, "smoke": bool(smoke), "n_new": n_new,
              "slo_ms": slo_ms, "pool": POOL}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "serving_lm_cont.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


if __name__ == "__main__":
    if ARGS.continuous:
        r = run_cont()
        sys.exit(0 if r["speedup"] >= 0.85 else 1)
    r = run()
    sys.exit(0 if r["speedup"] >= 1.5 else 1)
