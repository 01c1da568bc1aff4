"""Quickstart: the full DART pipeline through the `repro.engine` API.

The whole lifecycle is five lines:

    engine = DartEngine.from_config(cfg, params)   # wire up
    engine.calibrate(cal_data)                     # §II.B policy fit
    out = engine.infer(x, mode="compacted")        # Alg. 1 serving
    engine.update()                                # §II.C adaptation
    engine.stats()                                 # metering

This script: (1) trains a 3-exit AlexNet on synth-CIFAR with the Eq. 18
multi-exit loss, (2) runs the paper's Table I protocol (Static /
BranchyNet / RL-Agent / DART — all registered policy optimizers), and
(3) serves a few batches through the compacting engine.

Run:  PYTHONPATH=src python examples/quickstart.py
      (QUICKSTART_STEPS / QUICKSTART_EVAL shrink it for smoke tests)
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import dataclasses

import numpy as np

from repro.configs import registry
from repro.data.datasets import DatasetConfig, make_batch
from repro.engine import DartEngine
from benchmarks.common import evaluate_methods, print_rows, train_model

STEPS = int(os.environ.get("QUICKSTART_STEPS", 200))
N_EVAL = int(os.environ.get("QUICKSTART_EVAL", 512))
CIFAR = DatasetConfig(name="synth-cifar", n_train=2048, n_eval=2048)


def main():
    tb = registry.paper_testbeds()
    cfg = dataclasses.replace(tb["alexnet"], channels=(16, 32, 48, 32, 32),
                              fc_dims=(128, 64))
    print(f"training 3-exit AlexNet on synth-CIFAR ({STEPS} steps) ...")
    tr = train_model(cfg, CIFAR, steps=STEPS, batch=32)
    print(f"final train loss: {tr.history[-1]['loss']:.3f}")

    # -- Table I protocol (all four methods via the optimizer registry) --
    rows, diag = evaluate_methods(cfg, tr.params, CIFAR, n_eval=N_EVAL)
    print_rows("Quickstart — Table I protocol (synth-CIFAR)", rows)
    print(f"\nDART thresholds (Eq. 12/DP): "
          f"{np.round(diag['dart_tau'], 3).tolist()}")
    print(f"DART exit distribution: {diag['exit_dist']['dart']}")
    print(f"mean difficulty alpha: {diag['mean_alpha']:.3f} "
          f"(paper: CIFAR-10 ~0.85)")
    dart = rows[3]
    print(f"\nDART: {dart['speedup']:.2f}x speedup, "
          f"{dart['power_eff']:.2f}x power efficiency, "
          f"DAES {dart['daes']:.2f} (static {rows[0]['daes']:.2f})")

    # -- the 5-line serving session -------------------------------------
    engine = DartEngine.from_config(cfg, tr.params,
                                    cum_costs=diag["cum_macs"])
    engine.calibrate(engine.collect_calibration(CIFAR, n=256))
    x, _ = make_batch(CIFAR, range(64), split="eval")
    out = engine.infer(x, mode="compacted")
    stats = engine.stats()
    print(f"\nengine session: served {stats['served']} samples, "
          f"exit counts {stats['exit_counts'].tolist()}, "
          f"mean exit {out['exit_idx'].mean():.2f}, "
          f"mean MACs {out['macs'].mean()/1e6:.2f}M "
          f"(full depth {engine.cum_costs[-1]/1e6:.2f}M)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
