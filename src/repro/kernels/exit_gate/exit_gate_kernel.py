"""Fused early-exit gate Pallas kernel (paper Alg. 1, lines 5–9).

For each sample, one VMEM pass over the exit-head logits computes:
  * ``conf``    — max softmax probability (DART's confidence)
  * ``entropy`` — Shannon entropy (BranchyNet's criterion, same pass)
  * ``pred``    — argmax class
  * ``fire``    — conf > τ' (the Eq. 19 difficulty-adapted threshold)

Why a kernel: for LM exits the row is the vocabulary (DeepSeek: 129 280
floats = 517 KB — comfortably VMEM-resident).  The naive composition
softmax→max→argmax→compare reads the logits from HBM three times and
materializes the (B, V) softmax; this kernel reads each row once and
writes 4 scalars, turning the gate from memory-bound to free.

Grid: (B / block_b,) with ``block_b`` rows per step.  TPU tiling: a
``(block_b, V)`` logits block needs ``block_b`` a multiple of 8 or the
whole batch, and the per-row results are ``(B, 1)`` columns (a
``(block_b, 1)`` block is legal for every such ``block_b``; a 1-D
``(block_b,)`` block is not).  ``kernels.dispatch.gate_block_b`` picks a
legal ``block_b``, and rows beyond the VMEM budget never reach this
kernel (dispatch routes them to the jnp reference).  Numerics: fp32
max-subtracted log-sum-exp, bitwise-stable argmax (first max index),
matching ref.py.

``interpret=None`` auto-resolves to interpret mode off-TPU so the raw
kernel stays runnable in tests on a CPU host; production callers go
through ``kernels.dispatch``, which only ever picks the compiled kernel
on TPU and the interpreter when explicitly forced.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(logits_ref, thresh_ref, conf_ref, ent_ref, pred_ref, fire_ref):
    rows = logits_ref[...].astype(jnp.float32)           # (block_b, V)
    v = rows.shape[-1]
    m = jnp.max(rows, axis=-1, keepdims=True)            # (block_b, 1)
    # first-argmax (ties to lowest index, matches jnp.argmax)
    iota = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    idx = jnp.min(jnp.where(rows == m, iota, v), axis=-1, keepdims=True)
    ex = jnp.exp(rows - m)
    s = jnp.sum(ex, axis=-1, keepdims=True)
    conf = 1.0 / s
    # H = log s − Σ (l−m)·exp(l−m) / s
    ent = jnp.log(s) - jnp.sum((rows - m) * ex, axis=-1, keepdims=True) / s
    conf_ref[...] = conf
    ent_ref[...] = ent
    pred_ref[...] = idx
    fire_ref[...] = (conf > thresh_ref[...]).astype(jnp.int32)


def exit_gate_pallas(logits, thresholds, *, block_b: int = 1,
                     interpret=None):
    """logits: (B, V); thresholds: (B,) effective τ' per sample.

    ``block_b`` rows per grid step (must divide B; on TPU also a
    multiple of 8 or B itself — ``dispatch.gate_block_b`` guarantees
    both).  Returns (conf (B,), entropy (B,), pred (B,) int32,
    fire (B,) int32)."""
    from repro.kernels.dispatch import resolve_interpret
    b, v = logits.shape
    if b % block_b:
        raise ValueError(f"block_b={block_b} does not divide batch {b}")
    col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    outs = pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ),
        grid=(b // block_b,),
        in_specs=[pl.BlockSpec((block_b, v), lambda i: (i, 0)), col],
        out_specs=(col, col, col, col),
        interpret=resolve_interpret(interpret),
    )(logits, thresholds.astype(jnp.float32).reshape(b, 1))
    return tuple(o[:, 0] for o in outs)
