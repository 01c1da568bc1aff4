"""Fused DART difficulty-estimator Pallas kernel (paper §II.A, Eqs. 1–8).

One VMEM pass per image computes ALL difficulty statistics:
grayscale → Sobel Gx/Gy → edge count, |Laplacian| sum, per-channel
mean/variance, and the fused α — the kernel reads the image once (the
pure-jnp reference reads it five times: gray ×2, variance, and two
convolutions, each materializing HBM-sized intermediates).

TPU mapping: grid over the batch; each step holds one image in VMEM in
channels-major ``(C, H, W)`` layout, so W sits on the 128-wide lane
axis.  (The serving layout ``(H, W, C)`` would put C = 3 on the lanes
and pad every pixel 3 → 128 lanes: ~26 MB for one 224² f32 image, past
the scoped VMEM limit.)  The wrapper moves the channel axis before the
call.  All reductions run on the VPU; there is no MXU work — the kernel
is bandwidth-bound by design, which is why fusing the five passes into
one is the win.  The four statistics leave as lanes 0–3 of a
lane-dense ``(1, 128)`` row per image (TPU cannot store scalars to
VMEM).

Validated in interpret mode against ``ref.ref_components`` over a
shape/dtype sweep (tests/test_kernels.py) and compiled for v5e at
serving shapes (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: output row width: one lane tile; lanes 0-3 hold the four statistics
LANES = 128


def _kernel(img_ref, out_ref, *, tau_edge, var_scale, grad_scale, w1, w2, w3):
    img = img_ref[0].astype(jnp.float32)                 # (C, H, W)
    c, h, w = img.shape
    if c == 3:
        gray = 0.299 * img[0] + 0.587 * img[1] + 0.114 * img[2]
    else:
        gray = jnp.mean(img, axis=0)

    # ---- Eq. 5–6: per-channel spatial variance, averaged over channels
    sq = 0.0
    for ci in range(c):
        plane = img[ci]
        sq = sq + jnp.sum(jnp.square(plane - jnp.mean(plane)))
    a_var = 1.0 - jnp.exp(-(sq / (c * h * w)) / var_scale)

    # ---- shifted views for the two 3x3 stencils (valid region)
    tl = gray[0:h - 2, 0:w - 2]
    tc = gray[0:h - 2, 1:w - 1]
    tr = gray[0:h - 2, 2:w]
    ml = gray[1:h - 1, 0:w - 2]
    mc = gray[1:h - 1, 1:w - 1]
    mr = gray[1:h - 1, 2:w]
    bl = gray[2:h, 0:w - 2]
    bc = gray[2:h, 1:w - 1]
    br = gray[2:h, 2:w]

    # ---- Eqs. 1–4: Sobel magnitude > τ_edge
    gx = (tr + 2.0 * mr + br) - (tl + 2.0 * ml + bl)
    gy = (bl + 2.0 * bc + br) - (tl + 2.0 * tc + tr)
    mag = jnp.sqrt(gx * gx + gy * gy)
    a_edge = jnp.mean((mag > tau_edge).astype(jnp.float32))

    # ---- Eq. 7: mean |Laplacian|
    lap = tc + ml + mr + bc - 4.0 * mc
    a_grad = 1.0 - jnp.exp(-jnp.mean(jnp.abs(lap)) / grad_scale)

    # ---- Eq. 8 fusion
    alpha = jnp.clip(w1 * a_edge + w2 * a_var + w3 * a_grad, 0.0, 1.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    row = jnp.where(lane == 0, a_edge,
                    jnp.where(lane == 1, a_var,
                              jnp.where(lane == 2, a_grad,
                                        jnp.where(lane == 3, alpha, 0.0))))
    out_ref[0] = row


def difficulty_pallas(images, *, tau_edge=0.1, var_scale=0.05,
                      grad_scale=0.2, w1=0.4, w2=0.3, w3=0.3,
                      interpret=None):
    """images: (B, H, W, C) → (B, 4) = (α_edge, α_var, α_grad, α).

    ``interpret=None`` auto-resolves to interpret mode off-TPU (the raw
    kernel stays runnable in tests on a CPU host) and to the compiled
    Mosaic kernel on TPU; production traffic goes through
    ``kernels.dispatch``, which never auto-selects the interpreter.
    """
    from repro.kernels.dispatch import resolve_interpret
    b, h, w, c = images.shape
    kernel = functools.partial(_kernel, tau_edge=tau_edge,
                               var_scale=var_scale, grad_scale=grad_scale,
                               w1=w1, w2=w2, w3=w3)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, 1, LANES), jnp.float32),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, c, h, w), lambda i: (i, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0)),
        interpret=resolve_interpret(interpret),
    )(jnp.moveaxis(images, -1, 1))
    return out[:, 0, :4]
