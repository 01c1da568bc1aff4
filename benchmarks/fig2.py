"""Fig. 2 reproduction: evolution of class-aware adaptive coefficients for
an easy (car=1), medium (cat=3), and hard (ship=8) class during streaming
deployment with pseudo-labels (paper §III.C).

Expected qualitative behaviour: easy-class coefficients drift DOWN (more
aggressive early exits), hard-class coefficients drift UP (conservative)."""
from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core import adaptive as AD
from repro.core.routing import DartParams
from repro.data.datasets import DatasetConfig, make_batch
from repro.engine import DartEngine
from benchmarks.common import SCALE, train_model

CIFAR = DatasetConfig(name="synth-cifar", img_res=32, channels=3,
                      n_train=4096, n_eval=4096)
CLASSES = {"car(easy)": 1, "cat(medium)": 3, "ship(hard)": 8}


def main(outdir="artifacts/bench"):
    os.makedirs(outdir, exist_ok=True)
    art = os.path.join(outdir, "fig2.json")
    if os.environ.get("REPRO_BENCH_REUSE") == "1" and os.path.exists(art):
        with open(art) as f:
            traj = json.load(f)
        ks = list(traj); n = len(traj[ks[0]])
        print("\n== Fig. 2 (from artifact) ==")
        print("step," + ",".join(ks))
        import numpy as np
        for i in np.linspace(0, n - 1, min(10, n)).astype(int):
            print(f"{i}," + ",".join(f"{traj[k][i]:.4f}" for k in ks))
        print("direction:", {k: f"{traj[k][0]:.3f}->{traj[k][-1]:.3f}"
                             for k in ks})
        return traj
    tb = registry.paper_testbeds()
    cfg = dataclasses.replace(tb["alexnet"], channels=(16, 32, 48, 32, 32),
                              fc_dims=(128, 64))
    tr = train_model(cfg, CIFAR, steps=150 * SCALE, batch=32)
    dart = DartParams(tau=jnp.asarray([0.55, 0.6]), coef=jnp.ones(2),
                      beta_diff=0.3)
    acfg = AD.AdaptiveConfig(n_exits=3, n_classes=10, window=512,
                             eta=0.02, a_target=0.85, ucb_enabled=False)
    engine = DartEngine.from_config(
        cfg, tr.params, dart=dart,
        adaptive_cfg=acfg, adapt=True, update_every=64)
    cum = engine.measure_costs((32, 32, 3))
    engine.cum_costs = cum / cum[-1]
    traj = {k: [] for k in CLASSES}
    steps = 40 * SCALE
    for step in range(steps):
        x, y = make_batch(CIFAR, range(step * 64, (step + 1) * 64),
                          split="eval")
        engine.infer(x, mode="compacted")
        coef = np.asarray(engine.state.adaptive["coef_class"])  # (10, E-1)
        for name, c in CLASSES.items():
            traj[name].append(float(coef[c].mean()))
    print("\n== Fig. 2 — class-aware coefficient evolution ==")
    print("step," + ",".join(CLASSES))
    idxs = np.linspace(0, steps - 1, min(10, steps)).astype(int)
    for i in idxs:
        print(f"{i}," + ",".join(f"{traj[k][i]:.4f}" for k in CLASSES))
    start = {k: traj[k][0] for k in CLASSES}
    end = {k: traj[k][-1] for k in CLASSES}
    print("direction:", {k: f"{start[k]:.3f}->{end[k]:.3f}"
                         for k in CLASSES})
    with open(os.path.join(outdir, "fig2.json"), "w") as f:
        json.dump(traj, f, indent=1)
    return traj


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
