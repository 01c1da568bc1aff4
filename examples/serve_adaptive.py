"""Online-adaptation serving demo (paper §II.C) on the engine API.

A DartEngine session handles a request stream whose class mix SHIFTS
midway (deployment drift).  The adaptive manager — sliding-window stats,
temporal EMA (Eq. 13), class-aware updates from pseudo-labels (Eq. 14),
UCB1 strategy selection (Eq. 15) — retunes coefficients online; the
whole serving state (thresholds + window + counters) lives in ONE pytree
(``engine.state``) and is checkpointed atomically mid-stream.

Run:  PYTHONPATH=src python examples/serve_adaptive.py
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import dataclasses
import tempfile

import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core import adaptive as AD
from repro.core.routing import DartParams
from repro.data.datasets import DatasetConfig, make_batch
from repro.engine import DartEngine
from benchmarks.common import train_model

CIFAR = DatasetConfig(name="synth-cifar", n_train=2048, n_eval=4096)


def stream(phase, step, batch=32):
    """Phase 0: easy classes (0-4).  Phase 1: hard classes (5-9)."""
    base = step * batch * 2
    idx = [base + i * 2 + (0 if phase == 0 else 1) * 0 for i in range(batch)]
    idx = [i - (i % 10) + (i % 5) + (5 if phase else 0) for i in idx]
    return make_batch(CIFAR, idx, split="eval")


def main():
    tb = registry.paper_testbeds()
    cfg = dataclasses.replace(tb["alexnet"], channels=(16, 32, 48, 32, 32),
                              fc_dims=(128, 64))
    tr = train_model(cfg, CIFAR, steps=80, batch=32)
    acfg = AD.AdaptiveConfig(n_exits=3, n_classes=10, window=512,
                             ucb_enabled=True)
    engine = DartEngine.from_config(
        cfg, tr.params,
        dart=DartParams(tau=jnp.asarray([0.5, 0.55]), coef=jnp.ones(2),
                        beta_diff=0.3),
        adaptive_cfg=acfg, adapt=True, update_every=64)
    engine.measure_costs((32, 32, 3))
    engine.cum_costs = engine.cum_costs / engine.cum_costs[-1]

    print("phase,step,mean_exit,mean_macs,coef_mean,strategy")
    for phase in (0, 1):
        for step in range(12):
            x, y = stream(phase, step)
            out = engine.infer(x, mode="compacted")
            coef = float(np.mean(np.asarray(
                AD.effective_coef(engine.state.adaptive, acfg))))
            strategy = AD.STRATEGIES[
                int(engine.state.adaptive["active_strategy"])]
            print(f"{phase},{step},{out['exit_idx'].mean():.2f},"
                  f"{out['macs'].mean():.3f},{coef:.4f},{strategy}")
        if phase == 0:
            # checkpoint the FULL serving state mid-stream (one pytree)
            ckdir = tempfile.mkdtemp()
            engine.save_state(ckdir, step=0)
            seen = int(engine.state.adaptive["seen"])
            engine.restore_state(ckdir)
            assert int(engine.state.adaptive["seen"]) == seen
            print(f"# state checkpointed + restored at phase boundary "
                  f"(window seen={seen})")

    stats = engine.stats()
    print("\nexit counts:", stats["exit_counts"].tolist())
    print(f"served {stats['served']} requests, "
          f"mean normalized MACs {stats['mean_macs']:.3f} (static = 1.0)")

    # ------------------------------------------------------------------
    # Sharded serving: the same engine API, jit-end-to-end over a mesh.
    # Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 to see
    # real data parallelism; docs/serving.md explains the paths.
    # ------------------------------------------------------------------
    from repro.launch.mesh import make_serving_mesh

    sharded = DartEngine.from_config(
        cfg, tr.params, mesh=make_serving_mesh(),
        dart=engine.dart_params(coef=np.asarray(engine.state.coef)),
        adaptive_cfg=acfg, adapt=True, update_every=64,
        cum_costs=engine.cum_costs)
    for step in range(8):
        x, _ = stream(1, step)
        out = sharded.infer(x, mode="masked")      # ONE compiled step
    sstats = sharded.stats()
    print(f"\nsharded engine: {sstats['replicas']} replica(s), "
          f"served {sstats['served']} "
          f"(per replica {sstats['served_per_replica'].tolist()}), "
          f"one compiled step/request "
          f"(traces: {sorted(sharded.trace_counts)})")

    # ------------------------------------------------------------------
    # Async serving: callers submit INDIVIDUAL requests with deadlines;
    # the repro.serving scheduler estimates difficulty at admission
    # (Eq. 8), lanes requests by difficulty class, and flushes
    # consolidated buckets on size-or-deadline.  docs/serving.md
    # ("Async serving") explains the lifecycle.
    # ------------------------------------------------------------------
    from repro.serving import AsyncDartServer, SchedulerConfig

    with AsyncDartServer(sharded, SchedulerConfig(
            max_batch=32, flush_ms=10.0)) as server:
        futs = [server.submit(stream(1, s, batch=4)[0],
                              deadline_ms=5000.0,    # demo SLO: compile
                              priority=s % 2)        # time counts too
                for s in range(16)]
        outs = [f.result(timeout=600) for f in futs]
    astats = server.stats()
    sch = astats["scheduler"]
    print(f"async scheduler: {sch['submitted']} requests -> "
          f"{sch['flush_deadline'] + sch['flush_size'] + sch['flush_hold']}"
          f" consolidated flushes "
          f"(per-class exit-depth prior: "
          f"{[None if d is None else round(d, 2) for d in sch['depth_prior']]})")
    lm = astats["requests"]["latency_ms"]
    print(f"  latency p50/p95/p99 = {lm['p50']:.0f}/{lm['p95']:.0f}/"
          f"{lm['p99']:.0f} ms, deadline miss rate "
          f"{100 * astats['requests']['miss_rate']:.0f}%  "
          f"(folded into EngineState -> survives checkpoints)")
    print(f"  mean exit depth served: "
          f"{float(np.mean(np.concatenate([o['exit_idx'] for o in outs]))):.2f}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
