"""fp8 rounding, the benchmark's lower-precision control.

The served configurations compute in bfloat16; the step below that,
which a later change might be tempted to take, is fp8 (e4m3: 3
mantissa bits against bfloat16's 7).  ``fake_quant`` scales a tensor so
its largest magnitude per slice along ``keep`` (per sample or token for
activations, per output channel for weights) meets e4m3's largest
finite value, rounds it to e4m3 and scales it back, so the reference's
arithmetic sees exactly the values an fp8 matmul would.
"""
from __future__ import annotations

import jax.numpy as jnp

E4M3_MAX = 448.0


def fake_quant(x, keep):
    axes = tuple(a for a in range(x.ndim) if a not in keep)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
