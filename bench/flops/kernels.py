"""Operations and HBM bytes of the classifier path's two Pallas kernels,
per call, from their shapes.  Both are memory-bound by design: their
least time on a chip is their bytes over its HBM bandwidth.

* ``exit_gate``: one read of a (B, V) logits block and the (B,) Eq. 19
  thresholds; writes four (B,) f32/i32 columns.  Per logit: max,
  subtract, exp, sum, the argmax compare and select, and the entropy
  product and sum.
* ``difficulty``: one read of each (H, W, C) f32 image; writes one
  128-lane f32 row per image.  Per pixel: luma, the two Sobel stencils
  and magnitude, the threshold, the Laplacian, and the variance terms.
"""
from __future__ import annotations

GATE_OPS_PER_LOGIT = 8


def exit_gate(b: int, v: int, itemsize: int) -> tuple[int, int]:
    """(flops, bytes) of one gate call over ``b`` rows of ``v`` logits."""
    return GATE_OPS_PER_LOGIT * b * v, b * v * itemsize + 4 * b + 4 * 4 * b


def difficulty(b: int, h: int, w: int, c: int,
               itemsize: int = 4) -> tuple[int, int]:
    """(flops, bytes) of one estimator call over ``b`` images."""
    hv, wv = h - 2, w - 2
    per = (h * w * (2 * c - 1)            # luma
           + hv * wv * (2 * 2 * 9 + 3)     # Sobel x, y and magnitude
           + 2 * hv * wv                   # threshold and mean
           + 4 * h * w * c                 # variance
           + hv * wv * (2 * 9 + 2))        # Laplacian, |.| and mean
    return b * per, b * (h * w * c * itemsize + 128 * 4)
