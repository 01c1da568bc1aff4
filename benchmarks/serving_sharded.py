"""Sharded-serving benchmark: request-stream throughput of the
jit-end-to-end ShardedDartEngine vs single-device eager serving
(ISSUE 2 acceptance: >= 2x on the same host).

Workload: a stream of small request batches (online serving; default 8
samples/request).  Three ways to serve it:

* ``eager / request``    — the reference ``DartEngine``: one masked call
  per request; every call syncs the host (np outputs, eager routing +
  telemetry dispatch).
* ``sharded / request``  — ``ShardedDartEngine``: one compiled step per
  request.  Outputs stay on device, so consecutive donated-state steps
  pipeline — the host never blocks between requests.
* ``sharded / consolidated`` — the serving-scale mode: ``n_replicas``
  concurrent requests are consolidated into ONE compiled step (each
  replica serves one request); steps still pipeline.

Telemetry (exit counters + the §II.C window) is folded inside the
compiled step in all sharded rows, and decisions are asserted identical
to the eager oracle before timing.

NOTE on what the speedup measures: with fake CPU devices every replica
shares the host's cores, so consolidation pays off through larger fused
programs and removed per-request host round-trips, NOT extra FLOP/s.  On
a real multi-chip mesh the replicas add compute too, and the same
consolidation multiplies further.

Run:  PYTHONPATH=src python -m benchmarks.serving_sharded
      [--devices 8] [--request 8] [--secs 3] [--steps 40]
"""
import argparse
import os
import sys


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int,
                    default=int(os.environ.get("SERVING_BENCH_DEVICES", 8)))
    ap.add_argument("--request", type=int, default=8,
                    help="samples per request")
    ap.add_argument("--secs", type=float, default=3.0,
                    help="measurement window per engine")
    ap.add_argument("--steps", type=int, default=40,
                    help="brief training steps (policy realism, not "
                         "accuracy)")
    return ap


ARGS = _parser().parse_args([])          # defaults; real argv under __main__

if __name__ == "__main__":
    ARGS = _parser().parse_args()
    # Must precede the jax import (fake-device count is a process-level
    # flag); an already-exported XLA_FLAGS wins over --devices.
    flag = f"--xla_force_host_platform_device_count={ARGS.devices}"
    if os.environ.setdefault("XLA_FLAGS", flag) != flag:
        print(f"serving_sharded: XLA_FLAGS already set "
              f"({os.environ['XLA_FLAGS']!r}); --devices ignored",
              file=sys.stderr)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

import time                                                # noqa: E402

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
import numpy as np                                         # noqa: E402

from repro.core.routing import DartParams                  # noqa: E402
from repro.data.datasets import DatasetConfig, make_batch  # noqa: E402
from repro.engine import DartEngine                        # noqa: E402
from repro.launch.mesh import make_serving_mesh            # noqa: E402
from benchmarks.common import train_model                  # noqa: E402

CIFAR = DatasetConfig(name="synth-cifar", n_train=2048, n_eval=2048)


def serve_stream(engine, requests, secs, group=1):
    """Serve the request stream round-robin for ``secs``; ``group``
    consecutive requests are consolidated per call.  Returns samples/s
    (all submitted work forced to completion before the clock stops)."""
    batches = [np.concatenate(requests[i:i + group])
               for i in range(0, len(requests), group)]
    out = engine.infer(batches[0], mode="masked", record=True)  # warmup
    np.asarray(out["pred"])
    n, i, t0 = 0, 0, time.perf_counter()
    while time.perf_counter() - t0 < secs:
        out = engine.infer(batches[i % len(batches)], mode="masked",
                           record=True)
        n += batches[i % len(batches)].shape[0]
        i += 1
    np.asarray(out["pred"])            # drain the pipeline
    return n / (time.perf_counter() - t0)


def run(devices=ARGS.devices, request=ARGS.request, secs=ARGS.secs,
        steps=ARGS.steps):
    from repro.models.cnn_zoo import AlexNetConfig
    cfg = AlexNetConfig(img_res=32, n_classes=10,
                        channels=(16, 32, 48, 32, 32), fc_dims=(128, 64))
    tr = train_model(cfg, CIFAR, steps=steps, batch=64)
    dart = DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                      beta_diff=0.3)
    kw = dict(dart=dart, cum_costs=[0.3, 0.7, 1.0], adapt=True,
              update_every=10 ** 9)

    eager = DartEngine.from_config(cfg, tr.params, **kw)
    shard = DartEngine.from_config(cfg, tr.params,
                                   mesh=make_serving_mesh(), **kw)
    n_rep = shard.n_replicas

    requests = [np.asarray(make_batch(CIFAR, range(i * request,
                                                   (i + 1) * request),
                                      split="eval")[0])
                for i in range(2 * n_rep)]

    # decisions must agree before throughput numbers mean anything
    ref = eager.infer(requests[0], mode="masked", record=False)
    out = shard.infer(requests[0], mode="masked", record=False)
    np.testing.assert_array_equal(np.asarray(ref["exit_idx"]),
                                  np.asarray(out["exit_idx"]))

    rows = [
        ("eager / request", serve_stream(eager, requests, secs)),
        ("sharded / request", serve_stream(shard, requests, secs)),
        (f"sharded / consolidated x{n_rep}",
         serve_stream(shard, requests, secs, group=n_rep)),
    ]

    base = rows[0][1]
    print(f"\nsharded DART serving — {request}-sample requests, "
          f"{n_rep} replicas ({os.cpu_count()} cores), {secs:.0f}s/engine")
    print(f"{'engine':>28} {'samples/s':>12} {'speedup':>9}")
    for name, rate in rows:
        print(f"{name:>28} {rate:>12.0f} {rate / base:>8.2f}x")
    st = shard.stats()
    print(f"telemetry (compiled path): served={st['served']} "
          f"exit_frac={np.round(st['exit_frac'], 3).tolist()}")
    speedup = rows[-1][1] / base
    verdict = "PASS" if speedup >= 2.0 else "FAIL"
    print(f"\nacceptance (sharded consolidated >= 2x single-device eager): "
          f"{speedup:.2f}x -> {verdict}")
    return {"rows": rows, "speedup": speedup}


if __name__ == "__main__":
    r = run()
    sys.exit(0 if r["speedup"] >= 2.0 else 1)
