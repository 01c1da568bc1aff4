#!/usr/bin/env python3
"""Chip smoke test: DART serving through its normal entry points on TPU.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --chips 4      # four chips: sharded phase A only

Phase A, classifier serving: ``resnet-152`` at published width (224²,
1000 classes, bf16, exits after stages 0-2) built with
``DartEngine.from_config(..., mesh=make_serving_mesh())`` behind an
``AsyncDartServer`` that answers requests of mixed sizes 1-32.

Phase B, continuous LM decode: ``tinyllama-1.1b`` at published width
(22 layers, d=2048, GQA 32/4, V=32000, bf16) through
``LMDecodeEngine(...).session(continuous=True)``: 24 prompts of 16-24
new tokens on 8 slots, so slots and pages are reused mid-stream.

Weights are random, drawn from ``--seed``; nothing is read from disk.
Two choices make the exit gates meaningful on random weights:

* each exit head is rescaled so its logits have standard deviation
  ``LOGIT_STD`` (random init leaves every max-softmax confidence near
  1/V, so a gate boundary would be indistinguishable from any row);
* the base thresholds are quantiles of the exit confidences observed
  on a calibration pass, so that every exit takes a share of the rows.

Every answer is compared with the eager oracle.  A disagreement passes
only at a numerical boundary: a gate whose eager confidence lies within
``BOUNDARY`` of its Eq. 19 threshold τ', or an argmax whose two best
eager logits are within two bf16 ulps.  The logits are bf16, and near
a top logit of 20 one ulp is 0.125, which alone can move a confidence
by more than ``BOUNDARY``.  So phase A also passes a gate whose eager
confidence is within ``ulp_band`` of τ': the move that one ulp on every
logit makes.  In bf16 decode the eager path does not agree with itself
even that closely: decoding each prompt alone instead of in the batch
moves its confidences by a few hundredths on the chip.  Phase B holds
its rows to fixed limits set from that reading (``ORACLE_CONF_NOISE``,
``ORACLE_TOP_NOISE``), measures the movement again on every run
(``oracle_noise``) and fails when it exceeds them.  Each boundary row
is printed.  Every kernel on the path must resolve to the compiled
``pallas`` backend (designed XLA fallbacks are printed).

The last stdout line is one JSON object, ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": ...}}``.  Without a TPU, or
when a phase or a check fails, it reads ``"ok": false`` and the exit
code is nonzero; the script never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: target standard deviation of every exit head's logits
LOGIT_STD = 6.0
#: |conf - τ'| under which a gate decision may differ from the oracle
BOUNDARY = 1e-2
#: Eq. 19 difficulty sensitivity used by both phases
BETA_DIFF = 0.3
#: phase A: requests answered, each of a size drawn from 1-32
N_REQUESTS = 64
#: phase B: prompts decoded on ``N_SLOTS`` slots, each with a number of
#: new tokens drawn from ``NEW_TOKENS``.  More prompts than slots and
#: mixed lengths, so slots and pages are freed and reused mid-stream.
N_PROMPTS = 24
N_SLOTS = 8
NEW_TOKENS = (16, 20, 24)
#: phase B: how far the bf16 eager LM oracle may move against itself
#: when only the batch shape changes, in exit confidence and in top
#: logit.  Set from its reading on a v5e chip (confidence p99 0.064,
#: max 0.0835; top logit max 0.375, three ulps at 16-32); a run whose
#: own reading exceeds them fails.  A gate row passes within
#: ``ORACLE_CONF_NOISE`` of τ', an argmax row at a gap of at most
#: twice ``ORACLE_TOP_NOISE``.
ORACLE_CONF_NOISE = 0.1
ORACLE_TOP_NOISE = 0.375

CLASSIFIER_KERNELS = ("exit_gate", "difficulty")
LM_KERNELS = ("exit_head", "paged_gather")
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (all threads),
    read from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in _COMPILE_EVENTS:
            self.seconds += secs


@contextlib.contextmanager
def probe_confidence(engine):
    """Record what the eager oracle gates on: wraps ``engine._conf_fn``
    (the registered confidence functional the eager paths call on each
    exit's logits) and keeps, per call, the confidences, the gap between
    the two largest logits of every row, and the logits' standard
    deviation per leading (exit) index."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    calls = []
    orig = engine._conf_fn

    def recording(logits, **kw):
        conf = orig(logits, **kw)
        lf = logits.astype(jnp.float32)
        top2 = jax.lax.top_k(lf, 2)[0]
        calls.append({"conf": np.asarray(conf),
                      "top": np.asarray(top2[..., 0]),
                      "gap": np.asarray(top2[..., 0] - top2[..., 1]),
                      "std": np.asarray(jnp.std(lf, axis=(-2, -1)))})
        return conf

    engine._conf_fn = recording
    try:
        yield calls
    finally:
        engine._conf_fn = orig


def quantile_tau(conf, alpha, n_exits):
    """Base thresholds that split the rows evenly over the exits.

    ``conf`` (E, N) confidences at every exit, ``alpha`` (N,)
    difficulties.  With coef = 1, Eq. 19 fires gate s iff
    conf_s - β·α > τ_s (the clip to [0, 1] never changes that for a
    confidence in (0, 1]), so τ_s is taken halfway between two adjacent
    values of conf_s - β·α among the rows still alive at s."""
    import numpy as np
    alive = np.ones(conf.shape[1], bool)
    tau = []
    for s in range(n_exits - 1):
        score = conf[s] - BETA_DIFF * alpha
        vals = np.sort(score[alive])
        if len(vals) < 2:                  # nothing left to split
            tau.append(1.0)
            continue
        k = int(round(len(vals) * (1.0 - 1.0 / (n_exits - s))))
        k = min(max(k, 1), len(vals) - 1)
        t = 0.5 * (vals[k - 1] + vals[k])
        tau.append(t)
        alive &= ~(score > t)
    return np.asarray(tau, np.float32)


def near_tie(gap, top) -> bool:
    """The two best logits are within two bf16 ulps of the larger."""
    return float(gap) <= abs(float(top)) * 2.0 ** -7


def ulp_band(conf, top) -> float:
    """How far a max-softmax confidence ``conf`` moves when the top
    logit ``top`` moves one bf16 ulp against every other logit moving
    one ulp the other way (each gap to the top changes by two ulps)."""
    ulp = 2.0 ** (math.floor(math.log2(max(abs(float(top)), 1e-30))) - 7)
    k, p = math.exp(2.0 * ulp), float(conf)
    return max(p * k / (p * k + 1 - p) - p, p - p / (p + (1 - p) * k))


def peak_memory(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


# ---------------------------------------------------------------------------
# phase A: classifier serving
# ---------------------------------------------------------------------------

def _resnet_heads(params, cfg):
    """The exit heads of a ResNet in exit order (final head last)."""
    return [params["exit_heads"][str(s)] for s in cfg.exit_stages] \
        + [params["head"]]


def phase_classifier(cfg, *, seed: int, mesh,
                     n_requests: int = N_REQUESTS,
                     sizes=(1, 2, 5, 8, 13, 21, 32), buckets=(8, 32)):
    """Serve ``n_requests`` image requests through ``AsyncDartServer``
    over ``DartEngine.from_config(cfg, ..., mesh=mesh)`` and compare
    every answer with the eager oracle on one device.  Returns the
    phase report (see ``main`` for the checks applied to it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.routing import DartParams
    from repro.core.thresholds import adapt_thresholds
    from repro.data.datasets import DatasetConfig, make_batch
    from repro.engine import DartEngine
    from repro.models import get_family
    from repro.parallel.sharding import unzip
    from repro.serving import AsyncDartServer, SchedulerConfig

    big = max(buckets)
    n_exits = len(cfg.exit_stages) + 1
    data = DatasetConfig(img_res=cfg.img_res, seed=seed)
    params = unzip(get_family(cfg).init(jax.random.key(seed), cfg))[0]
    params = jax.device_put(params, jax.devices()[0])

    # -- calibration: logit scale, then thresholds (eager, one device)
    xc, _ = make_batch(data, range(10 ** 6, 10 ** 6 + big), split="eval")
    hold = DartParams(tau=jnp.ones(n_exits - 1), coef=jnp.ones(n_exits - 1),
                      beta_diff=BETA_DIFF)
    eager = DartEngine.from_config(cfg, params, dart=hold, adapt=False,
                                   buckets=buckets)
    with probe_confidence(eager) as calls:
        eager.infer(xc, mode="masked")
    for head, std in zip(_resnet_heads(params, cfg), calls[0]["std"]):
        for k in head:
            head[k] = head[k] * (LOGIT_STD / float(std))
    eager.params = params
    cal = eager.infer(xc, mode="masked")
    tau = quantile_tau(np.asarray(cal["conf_stack"]),
                       np.asarray(cal["alpha"]), n_exits)
    dart = DartParams(tau=jnp.asarray(tau), coef=jnp.ones(n_exits - 1),
                      beta_diff=BETA_DIFF)
    eager.state = eager.state.with_policy(tau=tau)

    # -- serving through the normal entry points
    rng = np.random.default_rng(seed)
    req_sizes = [int(n) for n in rng.choice(sizes, n_requests)]
    starts = np.concatenate([[0], np.cumsum(req_sizes)])
    xs, _ = make_batch(data, range(int(starts[-1])), split="eval")
    engine = DartEngine.from_config(cfg, params, dart=dart, mesh=mesh,
                                    adapt=False, buckets=buckets)
    server = AsyncDartServer(engine, SchedulerConfig(max_batch=big,
                                                     mode="masked"))
    futures = [server.submit(xs[a:z]) for a, z in zip(starts[:-1],
                                                      starts[1:])]
    results = [f.result(timeout=1200) for f in futures]
    server.close()
    stats = engine.stats()
    got_exit = np.concatenate([r["exit_idx"] for r in results])
    got_pred = np.concatenate([r["pred"] for r in results])

    # -- the eager oracle on the same samples, in max-bucket chunks
    want_exit, want_pred, conf, alpha, gap, top = [], [], [], [], [], []
    for a in range(0, len(xs), big):
        chunk = xs[a:a + big]
        with probe_confidence(eager) as calls:
            ref = eager.infer(chunk, mode="masked", pad_to=big)
        n = len(chunk)
        want_exit.append(np.asarray(ref["exit_idx"]))
        want_pred.append(np.asarray(ref["pred"]))
        conf.append(np.asarray(ref["conf_stack"]))
        alpha.append(np.asarray(ref["alpha"]))
        gap.append(calls[0]["gap"][:, :n])
        top.append(calls[0]["top"][:, :n])
    want_exit, want_pred = np.concatenate(want_exit), np.concatenate(want_pred)
    conf, alpha = np.concatenate(conf, 1), np.concatenate(alpha)
    gap, top = np.concatenate(gap, 1), np.concatenate(top, 1)
    eff = np.asarray(adapt_thresholds(jnp.asarray(tau), jnp.ones(n_exits - 1),
                                      jnp.asarray(alpha), BETA_DIFF))

    rows = []
    for i in np.nonzero((got_exit != want_exit) | (got_pred != want_pred))[0]:
        s = int(min(got_exit[i], want_exit[i]))
        if got_exit[i] != want_exit[i]:
            margin = abs(float(conf[s, i]) - float(eff[i, s]))
            band = max(BOUNDARY, ulp_band(conf[s, i], top[s, i]))
            rows.append({"sample": int(i), "kind": "gate", "stage": s,
                         "conf": float(conf[s, i]), "tau": float(eff[i, s]),
                         "top": float(top[s, i]), "band": band,
                         "ok": margin < band})
        else:
            rows.append({"sample": int(i), "kind": "argmax", "stage": s,
                         "gap": float(gap[s, i]),
                         "ok": near_tie(gap[s, i], top[s, i])})
    return {
        "phase": "A", "model": cfg.name, "requests": n_requests,
        "answered": len(results), "samples": int(len(xs)),
        "exit_hist": np.bincount(got_exit, minlength=n_exits).tolist(),
        "agree": int(len(xs) - len(rows)), "disagree": rows,
        "served_per_replica": np.asarray(
            stats["served_per_replica"]).tolist(),
        "tau": tau.tolist(),
    }


# ---------------------------------------------------------------------------
# phase B: continuous LM decode
# ---------------------------------------------------------------------------

def _eager_decode(engine, prompts, n_new, max_len, force=None):
    """The eager oracle, step by step (the loop of ``generate(mode=
    "eager")``), so each step's difficulty and each stage's gate inputs
    stay visible.  ``force`` (B, n_new): feed these tokens back instead
    of the decoded ones.  Returns tokens, stages (B, n_new) and per step
    the alpha and the probe records of every stage."""
    import numpy as np
    b, s0 = prompts.shape
    cache = engine.prefill(prompts[:, :-1], engine.init_cache(b, max_len))
    alpha = np.full((b,), 0.5, np.float32)
    toks = prompts[:, -1]
    out, stg, steps = [], [], []
    for t in range(n_new):
        with probe_confidence(engine) as calls:
            toks, stage, cache, alpha = engine.decode_step(
                toks, cache, s0 - 1 + t, alpha, record=False)
        if force is not None:
            toks = force[:, t]
        out.append(toks.copy())
        stg.append(stage.copy())
        steps.append((alpha.copy(), calls))
    return np.stack(out, 1), np.stack(stg, 1), steps


def oracle_noise(engine, prompts, n_new, max_len):
    """How far the eager oracle moves against itself when only the batch
    shape changes: ``prompts`` decoded together at a policy under which
    no early gate fires (so every stage sees every row), then each alone
    and fed the batch run's tokens.  Returns the |difference| of every
    stage's confidence and top logit, flattened.  bf16 rounding inside
    a differently shaped program moves a hidden state by an ulp here
    and there; the network carries that to the exit heads."""
    import numpy as np
    tokens, _, steps = _eager_decode(engine, prompts, n_new, max_len)
    dconf, dtop = [], []
    for r in range(len(prompts)):
        _, _, alone = _eager_decode(engine, prompts[r:r + 1], n_new,
                                    max_len, force=tokens[r:r + 1])
        for (_, batch_calls), (_, calls) in zip(steps, alone):
            for rb, ra in zip(batch_calls, calls):
                dconf.append(abs(float(rb["conf"][r]) - float(ra["conf"][0])))
                dtop.append(abs(float(rb["top"][r]) - float(ra["top"][0])))
    return np.asarray(dconf), np.asarray(dtop)


def _gate_inputs(engine, alpha, calls, row):
    """(conf, τ', gap, top) of ``row`` at every stage it reached in one
    eager decode step, rebuilt from the probe (each call holds the rows
    still alive at that stage, in order)."""
    import numpy as np
    from repro.core.thresholds import stage_threshold
    tau, coef, beta = engine._policy_host()
    active = np.arange(len(alpha))
    out = []
    for s, rec in enumerate(calls):
        final = s == len(engine.stages) - 1
        eff = np.ones(len(active), np.float32) * -1.0 if final else \
            np.asarray(stage_threshold(tau[s], coef[s], alpha[active], beta))
        j = int(np.nonzero(active == row)[0][0])
        out.append((float(rec["conf"][j]), float(eff[j]),
                    float(rec["gap"][j]), float(rec["top"][j])))
        fire = rec["conf"] > eff
        if row in active[fire] or final:
            break
        active = active[~fire]
    return out


def phase_lm(cfg, *, seed: int, n_prompts: int = N_PROMPTS,
             new_tokens=NEW_TOKENS, n_slots: int = N_SLOTS,
             prompt_len: int = 16, page_size: int = 8, noise=(8, 16)):
    """Decode ``n_prompts`` prompts, each of a number of new tokens drawn
    from ``new_tokens``, through the continuous session of an
    ``LMDecodeEngine`` on ``n_slots`` slots, and compare tokens and exit
    stages with the eager oracle.  The oracle decodes ``n_slots``
    prompts at a time: it compiles a program per count of surviving
    rows, so its batch bounds what it compiles.  ``noise`` (prompts,
    tokens): the share of the prompts on which ``oracle_noise`` is
    read.  Returns the phase report."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.routing import DartParams
    from repro.engine import LMDecodeEngine
    from repro.models.transformer_lm import lm_init
    from repro.parallel.sharding import unzip

    n_exits = len(cfg.exit_layers) + 1
    params = unzip(lm_init(jax.random.key(seed), cfg))[0]
    # rmsnorm'd rows have unit RMS, so the logit std is the table's
    # std times sqrt(d): rescale the (shared) unembed table to LOGIT_STD
    table = params["unembed"]
    params["unembed"] = table * (
        LOGIT_STD / (float(jnp.std(table.astype(jnp.float32)))
                     * cfg.d_model ** 0.5))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (n_prompts, prompt_len))
    n_new = [int(n) for n in rng.choice(new_tokens, n_prompts)]
    longest = max(n_new)
    max_len = prompt_len + longest

    # -- calibration: no gate fires at τ = 1, every stage sees every row
    hold = DartParams(tau=jnp.ones(n_exits - 1), coef=jnp.ones(n_exits - 1),
                      beta_diff=BETA_DIFF)
    engine = LMDecodeEngine(cfg, params, hold)
    chunks = range(0, n_prompts, n_slots)
    steps = [st for a in chunks for st in _eager_decode(
        engine, prompts[a:a + n_slots], longest, max_len)[2]]
    conf = np.concatenate([np.stack([c["conf"] for c in calls])
                           for _, calls in steps], 1)
    alpha = np.concatenate([a for a, _ in steps])
    tau = quantile_tau(conf, alpha, n_exits)
    n_noise, t_noise = noise
    dconf, dtop = oracle_noise(engine, prompts[:n_noise], t_noise,
                               prompt_len + t_noise)
    engine.state = engine.state.with_policy(tau=tau)

    # -- serving through the continuous session
    session = engine.session(continuous=True, n_slots=n_slots,
                             page_size=page_size, max_len=max_len)
    view_len = session.decoder.view_len
    futures = [session.submit(p[None], n_new=n)
               for p, n in zip(prompts, n_new)]
    results = [f.result(timeout=1200) for f in futures]
    session.close()
    got_tok = [np.asarray(r["tokens"])[0] for r in results]
    got_stg = [np.asarray(r["stages"])[0] for r in results]

    # -- the eager oracle at the continuous decoder's view length
    oracle = [_eager_decode(engine, prompts[a:a + n_slots], longest,
                            view_len) for a in chunks]
    rows = []
    agree = 0
    for r in range(n_prompts):
        want_tok, want_stg, steps = oracle[r // n_slots]
        j, n = r % n_slots, n_new[r]
        bad = np.nonzero((got_tok[r] != want_tok[j, :n])
                         | (got_stg[r] != want_stg[j, :n]))[0]
        if not len(bad):
            agree += n
            continue
        t = int(bad[0])              # later tokens follow another context
        agree += t
        alpha_t, calls = steps[t]
        gates = _gate_inputs(engine, alpha_t, calls, j)
        s = int(min(got_stg[r][t], want_stg[j, t]))
        conf_s, eff_s, gap_s, top_s = gates[s]
        if got_stg[r][t] != want_stg[j, t]:
            rows.append({"prompt": r, "token": t, "kind": "gate",
                         "stage": s, "conf": conf_s, "tau": eff_s,
                         "ok": abs(conf_s - eff_s) < ORACLE_CONF_NOISE})
        else:
            rows.append({"prompt": r, "token": t, "kind": "argmax",
                         "stage": s, "gap": gap_s,
                         "ok": near_tie(gap_s, top_s)
                         or gap_s <= 2 * ORACLE_TOP_NOISE})
    q = (0.5, 0.99, 1.0)
    stages = np.concatenate(got_stg)
    return {
        "phase": "B", "model": cfg.name, "requests": n_prompts,
        "answered": len(results), "slots": n_slots,
        "tokens": int(stages.size),
        "exit_hist": np.bincount(stages, minlength=n_exits).tolist(),
        "agree": agree, "disagree": rows, "tau": tau.tolist(),
        "oracle_noise": {"conf_p50_p99_max": np.quantile(dconf, q).tolist(),
                         "top_logit_p50_p99_max":
                             np.quantile(dtop, q).tolist()},
    }


# ---------------------------------------------------------------------------
# checks and entry point
# ---------------------------------------------------------------------------

def check_report(report, counts, kernels, *, expect="pallas",
                 allow_xla=False) -> list:
    """Problems with one phase report (empty list = pass)."""
    problems = []
    if report["answered"] != report["requests"]:
        problems.append(f"answered {report['answered']} of "
                        f"{report['requests']} requests")
    for k in kernels:
        if not counts.get((k, expect)):
            problems.append(f"{k} never resolved to {expect}")
    for (k, backend), n in counts.items():
        if backend == "pallas-interpret" and expect != backend:
            problems.append(f"{k} ran in interpret mode {n}x")
        if k in kernels and backend == "xla" and not allow_xla:
            problems.append(f"{k} fell back to xla {n}x")
    bad = [r for r in report["disagree"] if not r["ok"]]
    if bad:
        problems.append(f"{len(bad)} disagreements with the eager oracle "
                        "off any numerical boundary")
    if 0 in report["exit_hist"]:
        problems.append(f"an exit took no share: {report['exit_hist']}")
    noise = report.get("oracle_noise")
    if noise and (noise["conf_p50_p99_max"][-1] > ORACLE_CONF_NOISE
                  or noise["top_logit_p50_p99_max"][-1] > ORACLE_TOP_NOISE):
        problems.append(f"the eager oracle moved against itself beyond "
                        f"({ORACLE_CONF_NOISE}, {ORACLE_TOP_NOISE}): {noise}")
    if "served_per_replica" in report \
            and min(report["served_per_replica"]) <= 0:
        problems.append("a replica served nothing: "
                        f"{report['served_per_replica']}")
    return problems


def run_phase(name, fn, kernels, clock, *, allow_xla=False):
    """Run one phase with fresh dispatch counts; print its report."""
    import jax
    from repro.kernels import dispatch
    dispatch.reset_dispatch_counts()
    c0, t0 = clock.seconds, time.time()
    report = fn()
    counts = dispatch.dispatch_counts()
    report["compile_s"] = round(clock.seconds - c0, 3)
    report["wall_s"] = round(time.time() - t0, 3)
    report["dispatch_counts"] = {f"{k}/{b}": n
                                 for (k, b), n in sorted(counts.items())}
    report["peak_bytes"] = peak_memory(jax.devices())
    for row in report["disagree"]:
        print(f"{name} boundary row: {json.dumps(row)}")
    print(f"{name}: {json.dumps(report)}")
    return check_report(report, counts, kernels, allow_xla=allow_xla)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = None
    try:
        from repro.launch.cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        import jax
        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        if device["platform"] != "tpu":
            raise SystemExit(f"no TPU: JAX found {device['platform']}")
        if len(devs) < args.chips:
            raise SystemExit(f"--chips {args.chips} but {len(devs)} devices")
        from repro.configs import registry
        from repro.kernels import dispatch
        from repro.launch.mesh import make_serving_mesh
        if dispatch.forced_backend() is not None:
            raise SystemExit("a kernel backend is forced: "
                             f"{dispatch.forced_backend()}")
        print(f"device: {json.dumps(device)}")
        print(f"compile cache: {cache_dir}")
        clock = CompileClock()
        mesh = make_serving_mesh(args.chips)
        problems = run_phase(
            "phase A", lambda: phase_classifier(
                registry.get("resnet-152"), seed=args.seed, mesh=mesh),
            CLASSIFIER_KERNELS, clock, allow_xla=args.chips > 1)
        if args.chips == 1:
            problems += run_phase(
                "phase B", lambda: phase_lm(
                    registry.get("tinyllama-1.1b"), seed=args.seed),
                LM_KERNELS, clock)
        for p in problems:
            print(f"CHECK FAILED: {p}")
        ok = not problems
    except (Exception, SystemExit) as e:
        if not isinstance(e, SystemExit):
            traceback.print_exc()
        print(f"FAILED: {e}")
        ok = False
        code = 2 if device is None or device["platform"] != "tpu" else 1
    else:
        code = 0 if ok else 1
    print(json.dumps({"ok": ok, "device": device}))
    return code


if __name__ == "__main__":
    sys.exit(main())
