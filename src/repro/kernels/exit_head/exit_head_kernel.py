"""Fused decode-time LM exit head (paper Alg. 1 lines 5–9, LM domain).

One kernel launch per (stage, decode step) computes, for every survivor
row, the WHOLE exit decision the compiled decode step used to compose
from four XLA ops:

    rmsnorm(h) @ unembed.T  →  max-softmax confidence  →  argmax token
                            →  conf > τ' (Eq. 19 threshold)

Why a kernel: the (B, V) logits are the largest decode-time tensor (a
DeepSeek-vocab row is 517 KB), and the composed chain writes them to
HBM once and reads them three times (softmax, argmax, compare).  This
kernel never materializes them: the grid walks the vocabulary in
``block_v`` steps, each step holds one ``(block_v, D)`` unembed block
and all B rows in VMEM, and an online (flash-style) softmax folds block
maxima/sums/argmaxes into ``(B, 1)`` VMEM scratch carried across the
vocab axis — the unembed table is read from HBM exactly once per call
and the only HBM writes are the three per-row results.

TPU tiling: the rows block is the whole ``(B, D)`` array, ``block_v``
is a multiple of 128 or the whole vocabulary (``dispatch.
exit_head_block_v``), and every per-row value is a ``(B, 1)`` column.

Numerics follow the ref chain: rmsnorm in fp32, the normalized rows
cast back to the model dtype, the block matmul accumulated in fp32 on
the MXU and rounded to the model dtype (the ref's einsum output dtype),
then fp32 softmax statistics.  Parity is allclose on confidences (the
online sum adds in a different order); argmax ties resolve to the
lowest index within AND across blocks, matching ``jnp.argmax``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(h_ref, scale_ref, tab_ref, th_ref, conf_ref, pred_ref,
            fire_ref, m_ref, s_ref, p_ref, *, eps, block_v, nv, dtype):
    j = pl.program_id(0)
    x = h_ref[...].astype(jnp.float32)                      # (B, D)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    hn = (x * scale_ref[...].astype(jnp.float32)).astype(h_ref.dtype)
    hn = hn.astype(dtype)
    logits = jax.lax.dot_general(
        hn, tab_ref[...].astype(dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (B, block_v)
    logits = logits.astype(dtype).astype(jnp.float32)
    bm = jnp.max(logits, axis=-1, keepdims=True)            # (B, 1)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    bidx = jnp.min(jnp.where(logits == bm, iota, block_v), axis=-1,
                   keepdims=True) + j * block_v
    bs = jnp.sum(jnp.exp(logits - bm), axis=-1, keepdims=True)

    @pl.when(j == 0)
    def _():
        m_ref[...] = bm
        s_ref[...] = bs
        p_ref[...] = bidx

    @pl.when(j > 0)
    def _():
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, bm)
        s_ref[...] = (s_ref[...] * jnp.exp(m_prev - m_new)
                      + bs * jnp.exp(bm - m_new))
        m_ref[...] = m_new
        # strictly-greater keeps the earliest block on ties (jnp.argmax)
        p_ref[...] = jnp.where(bm > m_prev, bidx, p_ref[...])

    @pl.when(j == nv - 1)
    def _():
        conf = 1.0 / s_ref[...]
        conf_ref[...] = conf
        pred_ref[...] = p_ref[...]
        fire_ref[...] = (conf > th_ref[...]).astype(jnp.int32)


def exit_head_gate_pallas(h, scale, table, thresholds, *,
                          eps: float = 1e-6, block_v: int | None = None,
                          interpret=None):
    """h (B, D), scale (D,), table (V, D), thresholds (B,).

    ``block_v`` must divide V (``dispatch.exit_head_block_v`` picks a
    VMEM-budgeted, lane-aligned divisor).  Returns (conf (B,) f32,
    pred (B,) i32, fire (B,) i32)."""
    from repro.kernels.dispatch import resolve_interpret
    b, d = h.shape
    v = table.shape[0]
    block_v = v if block_v is None else block_v
    if v % block_v:
        raise ValueError(f"block_v={block_v} does not divide vocab {v}")
    nv = v // block_v
    kern = functools.partial(_kernel, eps=eps, block_v=block_v, nv=nv,
                             dtype=jnp.promote_types(h.dtype, table.dtype))
    col = pl.BlockSpec((b, 1), lambda j: (0, 0))
    outs = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((b, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1), jnp.int32)),
        grid=(nv,),
        in_specs=[pl.BlockSpec((b, d), lambda j: (0, 0)),
                  pl.BlockSpec((1, d), lambda j: (0, 0)),
                  pl.BlockSpec((block_v, d), lambda j: (j, 0)),
                  col],
        out_specs=(col, col, col),
        scratch_shapes=[pltpu.VMEM((b, 1), jnp.float32),
                        pltpu.VMEM((b, 1), jnp.float32),
                        pltpu.VMEM((b, 1), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(h, scale.reshape(1, d), table,
      thresholds.astype(jnp.float32).reshape(b, 1))
    return tuple(o[:, 0] for o in outs)
