"""Kernel micro-benchmarks: the dispatch-routed fused kernels vs the
composed (multi-dispatch) XLA reference chains.

Two things are measured and written to ``artifacts/bench/``:

* ``kernels.json``  — per-shape rows: wall time of the composed
  reference chain, wall time of the ONE dispatch-routed fused call, and
  the structural HBM-traffic model that carries to TPU (the kernels are
  single-pass; the composed chain re-reads its operands).
* ``kernels_gate.json`` — the ISSUE 5 acceptance gate: the fused gate
  must be >= 1.3x the composed XLA reference chain on the host
  platform.  On platforms where the compiled Pallas backend is not
  available (this CPU container), the gate instead asserts that
  ``kernels.dispatch`` auto-selected the ``xla`` backend — interpret
  mode must never be what production traffic pays — and the measured
  numbers are recorded alongside.

``--smoke`` is the CI variant (fewer shapes/iters, same JSON artifacts,
exit code = gate result).  ``make bench-kernels`` runs the full sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import difficulty as DIFF
from repro.kernels import dispatch


def t_of(fn, *args, iters=30):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6   # us


# The chain the serving engines composed BEFORE the dispatch wiring:
# three separate dispatches over the same logits (softmax+max, argmax,
# compare) — each jitted on its own, like the eager per-stage path.
_conf_op = jax.jit(lambda lg: jnp.max(
    jax.nn.softmax(lg.astype(jnp.float32), axis=-1), axis=-1))
_pred_op = jax.jit(lambda lg: jnp.argmax(lg, axis=-1))
_fire_op = jax.jit(lambda conf, th: conf > th)


def _ref_chain(lg, th):
    conf = _conf_op(lg)
    pred = _pred_op(lg)
    return conf, pred, _fire_op(conf, th)


def bench_gate(shapes, iters):
    rows = []
    for (b, v) in shapes:
        lg = jax.random.normal(jax.random.key(1), (b, v))
        th = jnp.full((b,), 0.5)
        us_chain = t_of(_ref_chain, lg, th, iters=iters)
        us_fused = t_of(dispatch.exit_gate, lg, th, iters=iters)
        block_b = dispatch.gate_block_b(b, v)
        backend = dispatch.select_backend(
            "exit_gate", vmem_bytes=dispatch._gate_step_bytes(block_b, v))
        rows.append({
            "kernel": "exit_gate", "shape": f"{b}x{v}",
            "us_ref": us_chain, "us_fused": us_fused,
            "speedup": us_chain / max(us_fused, 1e-9),
            "backend": backend,
            "ref_bytes": 3 * b * v * 4,
            "kernel_bytes": b * v * 4 + b * 16,
        })
    return rows


def bench_difficulty(shapes, iters):
    rows = []
    ref = jax.jit(DIFF.image_difficulty)
    for (b, h, w, c) in shapes:
        img = jax.random.uniform(jax.random.key(0), (b, h, w, c))
        us_ref = t_of(ref, img, iters=iters)
        us_fused = t_of(dispatch.image_difficulty, img, iters=iters)
        backend = dispatch.select_backend(
            "difficulty",
            vmem_bytes=dispatch._difficulty_step_bytes(h, w, c))
        img_bytes = b * h * w * c * 4
        gray_bytes = b * h * w * 4
        ref_traffic = (img_bytes + gray_bytes            # grayscale
                       + 2 * (gray_bytes + gray_bytes)   # sobel x2
                       + img_bytes                       # variance
                       + gray_bytes + gray_bytes)        # laplacian
        rows.append({
            "kernel": "difficulty", "shape": f"{b}x{h}x{w}x{c}",
            "us_ref": us_ref, "us_fused": us_fused,
            "speedup": us_ref / max(us_fused, 1e-9), "backend": backend,
            "ref_bytes": ref_traffic,
            "kernel_bytes": img_bytes + b * 4 * 4,
        })
    return rows


def bench_exit_head(shapes, iters):
    from repro.kernels.exit_head.ref import ref_exit_head_gate
    rows = []
    ref = jax.jit(ref_exit_head_gate)
    for (b, d, v) in shapes:
        k1, k2, k3 = jax.random.split(jax.random.key(2), 3)
        h = jax.random.normal(k1, (b, d))
        scale = 1.0 + 0.1 * jax.random.normal(k2, (d,))
        tab = jax.random.normal(k3, (v, d))
        th = jnp.full((b,), 0.5)
        us_ref = t_of(ref, h, scale, tab, th, iters=iters)
        us_fused = t_of(dispatch.exit_head_gate, h, scale, tab, th,
                        iters=iters)
        block_v = dispatch.exit_head_block_v(v, d, b)
        backend = dispatch.select_backend(
            "exit_head",
            vmem_bytes=dispatch._head_step_bytes(block_v, d, b))
        rows.append({
            "kernel": "exit_head", "shape": f"{b}x{d}x{v}",
            "us_ref": us_ref, "us_fused": us_fused,
            "speedup": us_ref / max(us_fused, 1e-9), "backend": backend,
            # composed chain: (B, V) logits written once, read 3x;
            # the fused head writes 3 scalars per row instead
            "ref_bytes": 4 * b * v * 4,
            "kernel_bytes": b * 12,
        })
    return rows


def main(outdir="artifacts/bench", smoke=False):
    os.makedirs(outdir, exist_ok=True)
    iters = 10 if smoke else 30
    gate_shapes = [(128, 10), (64, 32000)] if smoke else \
        [(128, 10), (256, 1000), (64, 32000), (8, 129280)]
    diff_shapes = [(64, 32, 32, 3)] if smoke else \
        [(64, 32, 32, 3), (16, 224, 224, 3)]
    head_shapes = [(32, 64, 1024)] if smoke else \
        [(32, 64, 1024), (16, 256, 32000)]

    rows = (bench_gate(gate_shapes, iters)
            + bench_difficulty(diff_shapes, iters)
            + bench_exit_head(head_shapes, iters))
    print("kernel,shape,backend,us_ref_chain,us_fused,speedup,traffic_ratio")
    for r in rows:
        print(f"{r['kernel']},{r['shape']},{r['backend']},"
              f"{r['us_ref']:.1f},{r['us_fused']:.1f},"
              f"{r['speedup']:.2f},"
              f"{r['ref_bytes']/max(r['kernel_bytes'],1):.2f}")
    with open(os.path.join(outdir, "kernels.json"), "w") as f:
        json.dump(rows, f, indent=1)

    # ---- ISSUE 5 acceptance gate -------------------------------------
    gate_rows = [r for r in rows if r["kernel"] == "exit_gate"]
    backends = sorted({r["backend"] for r in gate_rows})
    pallas_rows = [r for r in gate_rows if r["backend"] == "pallas"]
    if jax.default_backend() == "tpu":
        # EVERY pallas-dispatched gate shape must clear 1.3x (a single
        # fast toy shape must not mask a regressed LM-vocab shape), and
        # at least one gate shape must actually dispatch to pallas.
        worst = min((r["speedup"] for r in pallas_rows), default=0.0)
        ok = bool(pallas_rows) and worst >= 1.3
        reason = (f"fused gate worst-shape speedup {worst:.2f}x over "
                  f"{len(pallas_rows)} pallas-dispatched shape(s) "
                  f"(require >= 1.3x on every one)")
    else:
        # no compiled pallas on this host: gate on dispatch never
        # auto-selecting interpret mode; speedups are recorded above
        ok = all(b == "xla" for b in backends)
        reason = (f"host platform {jax.default_backend()!r} has no "
                  f"compiled pallas backend; gating on auto-selection "
                  f"of 'xla' (got {backends}); measured fused-gate "
                  f"speedups recorded in kernels.json")
    gate = {"ok": bool(ok), "reason": reason,
            "gate_speedups": {r["shape"]: r["speedup"]
                              for r in gate_rows},
            "backends": backends, "platform": jax.default_backend(),
            "smoke": smoke}
    with open(os.path.join(outdir, "kernels_gate.json"), "w") as f:
        json.dump(gate, f, indent=1)
    print(f"\ngate: {'PASS' if ok else 'FAIL'} — {reason}")
    return rows, gate


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: fewer shapes/iters; exit code = "
                         "gate result")
    ap.add_argument("--outdir", default="artifacts/bench")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    _, gate = main(outdir=args.outdir, smoke=args.smoke)
    raise SystemExit(0 if gate["ok"] else 1)
