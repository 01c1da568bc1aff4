"""Table I reproduction: Static / BranchyNet / RL-Agent / DART across
AlexNet (MNIST + CIFAR), ResNet-18 and VGG-16 (CIFAR), with DAES.

Synthetic stand-in datasets (offline container) — compare METHOD ORDERING
and efficiency ratios against the paper, not absolute accuracy
(DESIGN.md §1)."""
from __future__ import annotations

import dataclasses
import json
import os

from repro.configs import registry
from repro.data.datasets import DatasetConfig
from benchmarks.common import (SCALE, evaluate_methods, print_rows,
                               train_model)

MNIST = DatasetConfig(name="synth-mnist", img_res=28, channels=1,
                      n_train=4096, n_eval=2048)
CIFAR = DatasetConfig(name="synth-cifar", img_res=32, channels=3,
                      n_train=4096, n_eval=2048)


def testbeds():
    tb = registry.paper_testbeds()
    beds = [("alexnet-mnist", tb["alexnet-mnist"], MNIST, 150),
            ("alexnet-cifar", tb["alexnet"], CIFAR, 150),
            ("resnet18-cifar", tb["resnet-18"], CIFAR, 120),
            ("vgg16-cifar", tb["vgg16"], CIFAR, 100)]
    if SCALE == 1:   # quick: shrink the nets, keep the protocol
        slim = dataclasses.replace(tb["alexnet"],
                                   channels=(16, 32, 48, 32, 32),
                                   fc_dims=(128, 64))
        slim_m = dataclasses.replace(tb["alexnet-mnist"],
                                     channels=(16, 32, 48, 32, 32),
                                     fc_dims=(128, 64))
        rn = dataclasses.replace(tb["resnet-18"], width=16)
        vg = dataclasses.replace(
            tb["vgg16"], blocks=((16, 1), (32, 1), (64, 2), (96, 2),
                                 (96, 2)), fc_dim=128)
        beds = [("alexnet-mnist", slim_m, MNIST, 200),
                ("alexnet-cifar", slim, CIFAR, 200),
                ("resnet18-cifar", rn, CIFAR, 150),
                ("vgg16-cifar", vg, CIFAR, 150)]
    return beds


def main(outdir="artifacts/bench"):
    os.makedirs(outdir, exist_ok=True)
    art = os.path.join(outdir, "table1.json")
    if os.environ.get("REPRO_BENCH_REUSE") == "1" and os.path.exists(art):
        with open(art) as f:
            results = json.load(f)
        for name, rec in results.items():
            print_rows(f"Table I — {name} (from artifact)", rec["rows"])
            print(f"   dart exits: {rec['diag']['exit_dist']['dart']}  "
                  f"mean_alpha={rec['diag']['mean_alpha']:.3f}")
        return results
    results = {}
    for name, cfg, data, steps in testbeds():
        tr = train_model(cfg, data, steps=steps * SCALE, batch=32)
        rows, diag = evaluate_methods(cfg, tr.params, data,
                                      n_eval=512 * min(SCALE, 4))
        print_rows(f"Table I — {name}", rows)
        print(f"   dart exits: {diag['exit_dist']['dart']}  "
              f"mean_alpha={diag['mean_alpha']:.3f}")
        results[name] = {"rows": rows, "diag": diag}
    with open(os.path.join(outdir, "table1.json"), "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
