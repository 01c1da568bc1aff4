"""Plain reference of DART's difficulty (Eqs. 1-8) and exit gate (Eq. 19,
Algorithm 1), written from the paper and never from the program.

Difficulty of an image in [0, 1] (H, W, C):

* edge density: the share of pixels whose Sobel gradient magnitude on
  the luma image exceeds ``tau_edge`` (valid 3x3 region);
* pixel variance: per-channel spatial variance, averaged over channels,
  squashed as 1 - exp(-v / var_scale);
* gradient complexity: mean |Laplacian| of the luma image (valid
  region), squashed as 1 - exp(-g / grad_scale);
* Eq. 8: alpha = clip(w1 * edge + w2 * variance + w3 * gradient, 0, 1).

Gate: exit s < E-1 fires when its max-softmax confidence exceeds
tau'_s = clip(c_s * tau_s + beta_diff * alpha, 0, 1); a sample leaves at
its first firing exit, and the final exit always takes it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LUMA = (0.299, 0.587, 0.114)


def _valid3x3(g, k):
    h, w = g.shape[1] - 2, g.shape[2] - 2
    return sum(k[i][j] * g[:, i:i + h, j:j + w]
               for i in range(3) for j in range(3) if k[i][j])


def difficulty(images, d, dtype=jnp.float32):
    """(B,) Eq. 8 difficulty; ``d`` holds tau_edge, var_scale,
    grad_scale and the weights w (3,).  ``dtype`` is the arithmetic's."""
    x = jnp.asarray(images).astype(dtype)
    c = x.shape[-1]
    gray = sum(LUMA[i] * x[..., i] for i in range(3)) if c == 3 \
        else jnp.mean(x, axis=-1)
    gx = _valid3x3(gray, ((-1, 0, 1), (-2, 0, 2), (-1, 0, 1)))
    gy = _valid3x3(gray, ((-1, -2, -1), (0, 0, 0), (1, 2, 1)))
    edge = jnp.mean((jnp.sqrt(gx * gx + gy * gy) > d["tau_edge"])
                    .astype(dtype), axis=(1, 2))
    mu = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = 1 - jnp.exp(-jnp.mean(jnp.square(x - mu), axis=(1, 2, 3))
                      / d["var_scale"])
    lap = _valid3x3(gray, ((0, 1, 0), (1, -4, 1), (0, 1, 0)))
    grad = 1 - jnp.exp(-jnp.mean(jnp.abs(lap), axis=(1, 2))
                       / d["grad_scale"])
    w1, w2, w3 = d["w"]
    return jnp.clip(w1 * edge + w2 * var + w3 * grad, 0, 1).astype(
        jnp.float32)


def confidence(logits):
    """Max softmax probability over the last axis, float32."""
    return jnp.max(jax.nn.softmax(jnp.asarray(logits, jnp.float32), -1), -1)


def thresholds(tau, coef, alpha, beta_diff):
    """(B, E-1) Eq. 19 thresholds, on the host."""
    t = np.asarray(coef, np.float64) * np.asarray(tau, np.float64)
    return np.clip(t[None, :] + beta_diff * np.asarray(alpha)[:, None],
                   0.0, 1.0)


def select_exit(conf, eff):
    """First exit whose confidence beats its threshold; conf (E, B),
    eff (B, E-1).  The final exit takes what no gate took."""
    fires = np.asarray(conf)[:-1].T > eff
    fires = np.concatenate([fires, np.ones((fires.shape[0], 1), bool)], 1)
    return np.argmax(fires, axis=1)
