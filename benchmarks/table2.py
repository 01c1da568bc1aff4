"""Table II reproduction: LeViT extensibility — Static vs DART accuracy,
MACs, time, speedup on the three LeViT variants (§II.D / §III.E)."""
from __future__ import annotations

import dataclasses
import json
import os


from repro.configs import registry
from repro.data.datasets import DatasetConfig
from repro.models.cnn_zoo import levit_macs
from benchmarks.common import SCALE, evaluate_methods, train_model

CIFAR = DatasetConfig(name="synth-cifar", img_res=32, channels=3,
                      n_train=4096, n_eval=2048)


def testbeds():
    tb = registry.paper_testbeds()
    beds = [("levit-128s", tb["levit-128s"], 120),
            ("levit-192", tb["levit-192"], 120),
            ("levit-256", tb["levit-256"], 120)]
    if SCALE == 1:
        beds = [(n, dataclasses.replace(
            c, dims=tuple(d // 4 for d in c.dims), depths=(1, 1, 2),
            key_dim=8), 150) for n, c, _ in beds]
    return beds


def main(outdir="artifacts/bench"):
    os.makedirs(outdir, exist_ok=True)
    art = os.path.join(outdir, "table2.json")
    if os.environ.get("REPRO_BENCH_REUSE") == "1" and os.path.exists(art):
        with open(art) as f:
            results = json.load(f)
        print("\n== Table II (from artifact) ==")
        print("model,method,acc_pct,macs_m,time_ms,speedup")
        for name, rec in results.items():
            for r in rec["rows"]:
                print(f"{name},{r['method']},{r['acc_pct']:.2f},"
                      f"{r['macs_m']:.2f},{r['time_ms']:.3f},"
                      f"{r['speedup']:.2f}")
        return results
    results = {}
    for name, cfg, steps in testbeds():
        tr = train_model(cfg, CIFAR, steps=steps * SCALE, batch=32)
        rows, diag = evaluate_methods(cfg, tr.params, CIFAR,
                                      n_eval=512 * min(SCALE, 4))
        static, dart = rows[0], rows[3]
        print(f"\n== Table II — {name} (analytic full MACs "
              f"{levit_macs(cfg)/1e6:.1f}M) ==")
        print("method,acc_pct,macs_m,time_ms,speedup")
        for r in (static, dart):
            print(f"{r['method']},{r['acc_pct']:.2f},{r['macs_m']:.2f},"
                  f"{r['time_ms']:.3f},{r['speedup']:.2f}")
        results[name] = {"rows": [static, dart], "diag": diag}
    with open(os.path.join(outdir, "table2.json"), "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
