"""§III.B overhead comparison: DART's difficulty estimator vs a
RACENet-style class-aware adaptive-normalization MLP.

Paper's numbers: DART 78.9 KFLOPs; RACENet 716,912 extra params and
3.96 MFLOPs => 50.3× overhead.  We implement BOTH control mechanisms and
measure (a) analytic FLOPs, (b) XLA cost-analysis FLOPs, (c) wall time
per sample at batch 128 (the paper's measurement setup).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import difficulty as DIFF


def racenet_style_mlp_params(n_layers=8, feat_dims=(64, 192, 384, 256, 256,
                                                    1024, 512, 10),
                             hidden=128):
    """A RACENet-ish controller: one (feat -> hidden -> 2*feat) MLP per
    layer producing per-channel scale/shift (class-aware adaptive norm)."""
    key = jax.random.key(0)
    params = []
    for i, f in enumerate(feat_dims):
        k1, k2, key = jax.random.split(key, 3)
        params.append({
            "w1": jax.random.normal(k1, (f, hidden)) * 0.02,
            "b1": jnp.zeros(hidden),
            "w2": jax.random.normal(k2, (hidden, 2 * f)) * 0.02,
            "b2": jnp.zeros(2 * f),
        })
    return params


def racenet_flops(params):
    total = 0
    for p in params:
        f, h = p["w1"].shape
        total += 2 * f * h + 2 * h * (2 * f)
    return total


def racenet_apply(params, feats):
    outs = []
    for p, x in zip(params, feats):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        outs.append(h @ p["w2"] + p["b2"])
    return outs


def measure(fn, *args, iters=50):
    fn(*args)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(outdir="artifacts/bench"):
    os.makedirs(outdir, exist_ok=True)
    batch = 128
    img = jax.random.uniform(jax.random.key(1), (batch, 32, 32, 3))

    # DART difficulty estimator
    dart_flops = DIFF.estimator_flops(32, 32, 3)
    est = jax.jit(lambda x: DIFF.image_difficulty(x))
    t_dart = measure(est, img) / batch
    ca = jax.jit(DIFF.image_difficulty).lower(img).compile().cost_analysis()
    dart_xla = float(ca.get("flops", 0)) / batch

    # RACENet-style per-layer MLP controller
    params = racenet_style_mlp_params()
    n_params = sum(int(np.prod(v.shape)) for p in params
                   for v in p.values())
    feats = [jax.random.normal(jax.random.key(i), (batch, p["w1"].shape[0]))
             for i, p in enumerate(params)]
    race = jax.jit(lambda ps, fs: racenet_apply(ps, fs))
    t_race = measure(race, params, feats) / batch
    race_fl = racenet_flops(params)

    ratio = race_fl / dart_flops
    print("\n== §III.B control-mechanism overhead ==")
    print("mechanism,params,analytic_flops,xla_flops_per_sample,us_per_sample")
    print(f"DART-difficulty,0,{dart_flops},{dart_xla:.0f},{t_dart*1e6:.2f}")
    print(f"RACENet-style-MLP,{n_params},{race_fl},-,{t_race*1e6:.2f}")
    print(f"FLOPs ratio (RACENet/DART): {ratio:.1f}x  "
          f"(paper: 50.3x; paper DART=78.9K vs ours {dart_flops/1e3:.1f}K)")
    rec = {"dart_flops": dart_flops, "dart_xla_flops": dart_xla,
           "dart_us": t_dart * 1e6, "racenet_flops": race_fl,
           "racenet_params": n_params, "racenet_us": t_race * 1e6,
           "ratio": ratio}
    with open(os.path.join(outdir, "overhead.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
