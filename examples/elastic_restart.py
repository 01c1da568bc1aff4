"""Fault-tolerance walkthrough: crash → restart → identical trajectory,
heartbeat failure detection, straggler shard reassignment, and serving-
state recovery (the engine's FULL session state — thresholds, §II.C
sliding window, UCB arms, counters — is ONE pytree, so a serving replica
restarts exactly where it died).

Run:  PYTHONPATH=src python examples/elastic_restart.py
"""
import tempfile
import time

import numpy as np

from repro.data.datasets import DatasetConfig, make_batch
from repro.engine import DartEngine
from repro.models.cnn_zoo import AlexNetConfig
from repro.runtime.fault import (HeartbeatMonitor, ShardPlan,
                                 StragglerPolicy,
                                 simulate_failure_and_recover)
from repro.runtime.trainer import Trainer, TrainConfig

DATA = DatasetConfig(name="synth-cifar", n_train=512)
MODEL = AlexNetConfig(img_res=32, n_classes=10,
                      channels=(8, 16, 24, 16, 16), fc_dims=(64, 32))


def main():
    # 1. crash-recovery determinism ---------------------------------------
    ck = tempfile.mkdtemp()
    tc = TrainConfig(batch_size=16, steps=20, lr=1e-3, ckpt_dir=ck,
                     ckpt_every=5, log_every=5, warmup=0)
    print("training to step 10, then 'crashing' ...")
    before, after, tr = simulate_failure_and_recover(
        MODEL, tc, fail_at=10, total_steps=20, data_cfg=DATA)
    print("pre-crash:", [(h["step"], round(h["loss"], 3)) for h in before])
    print("post-resume:", [(h["step"], round(h["loss"], 3)) for h in after])

    straight = Trainer(MODEL, TrainConfig(batch_size=16, steps=20, lr=1e-3,
                                          log_every=5, warmup=0), DATA)
    straight.run()
    import jax
    max_dev = max(float(np.max(np.abs(np.asarray(a, np.float64)
                                      - np.asarray(b, np.float64))))
                  for a, b in zip(jax.tree.leaves(straight.params),
                                  jax.tree.leaves(tr.params)))
    print(f"max param deviation vs never-crashed run: {max_dev:.2e} "
          f"(stateless data + atomic ckpt => deterministic recovery)")

    # 2. heartbeat failure detection --------------------------------------
    print("\nheartbeat monitor: worker w2 goes silent ...")
    dead = []
    mon = HeartbeatMonitor([f"w{i}" for i in range(4)], timeout_s=0.2,
                           on_failure=lambda w: dead.append(w))
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.6:
        for w in ("w0", "w1", "w3"):
            mon.beat(w)
        time.sleep(0.03)
    mon.close()
    print("detected dead workers:", dead)

    # 3. straggler mitigation ----------------------------------------------
    print("\nstraggler mitigation: re-slicing the slow worker's shard ...")
    plan = ShardPlan.even(["w0", "w1", "w2", "w3"], np.arange(64))
    pol = StragglerPolicy(factor=3.0)
    for _ in range(10):
        pol.record(0.1)
    slow = 0.45
    if pol.is_straggling(slow):
        plan = plan.reassign("w2")
    sizes = {w: len(ix) for w, ix in plan.assignments.items()}
    print("new shard sizes:", sizes, "(total",
          sum(sizes.values()), "— no data lost)")

    # 4. serving-state recovery -------------------------------------------
    print("\nserving replica crash: EngineState round-trips as one pytree")
    engine = DartEngine.from_config(MODEL, tr.params, adapt=True,
                                    update_every=16)
    x, _ = make_batch(DATA, range(48), split="eval")
    engine.infer(x, mode="compacted")
    ckdir = tempfile.mkdtemp()
    engine.save_state(ckdir, step=0)

    replica = DartEngine.from_config(MODEL, tr.params, adapt=True,
                                     update_every=16)
    replica.restore_state(ckdir)
    same = (int(replica.state.served) == int(engine.state.served)
            and int(replica.state.adaptive["seen"])
            == int(engine.state.adaptive["seen"]))
    a, b2 = engine.infer(x[:16], "compacted"), replica.infer(x[:16],
                                                            "compacted")
    print(f"counters restored: {same}; post-restore decisions identical: "
          f"{bool(np.array_equal(a['exit_idx'], b2['exit_idx']))}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
