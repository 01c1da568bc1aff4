"""Synthetic class-clutter images, made on the device from a key.

A copy of the semantics of the repository's ``synth-cifar`` generator,
drawn at any resolution in one jitted call: class c of 10 has an
oriented sinusoid texture (frequency 2 + 2 (c mod 5), angle 36 c deg), a
disc, square or triangle of a class colour, a background tint, then a
class-dependent clutter level that sets a Poisson number of small
blended squares and Gaussian pixel noise.  Clutter is what makes the
Eq. 8 difficulty vary from image to image.  Pixels lie in [0, 1].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CLASS_NOISE = (0.16, 0.05, 0.14, 0.12, 0.16, 0.18, 0.13, 0.15, 0.26, 0.2)
MAX_BLOBS = 12


def _image(key, label, res):
    ks = jax.random.split(key, 8)
    yy, xx = jnp.meshgrid(jnp.arange(res) / res, jnp.arange(res) / res,
                          indexing="ij")
    freq = 2.0 + (label % 5) * 2.0
    angle = label * 36.0 * jnp.pi / 180.0
    tex = 0.5 + 0.5 * jnp.sin(2 * jnp.pi * freq * (xx * jnp.cos(angle)
                                                   + yy * jnp.sin(angle)))
    cy, cx = 0.5 + jax.random.uniform(ks[0], (2,), minval=-0.15, maxval=0.15)
    r = jax.random.uniform(ks[1], minval=0.2, maxval=0.35)
    disc = (yy - cy) ** 2 + (xx - cx) ** 2 < r ** 2
    square = (jnp.abs(yy - cy) < r) & (jnp.abs(xx - cx) < r)
    tri = (yy - cy + r > 0) & (jnp.abs(xx - cx) < (yy - cy + r) / 2)
    mask = jnp.select([label % 3 == 0, label % 3 == 1], [disc, square], tri)
    base = jnp.stack([(label * 37) % 255, (label * 91 + 60) % 255,
                      (label * 151 + 120) % 255]) / 255.0
    bg = jax.random.uniform(ks[2], (3,), minval=0.2, maxval=0.8)
    img = jnp.where(mask[..., None], base * (0.5 + 0.5 * tex)[..., None],
                    bg * (0.6 + 0.4 * tex)[..., None])
    noise = jnp.asarray(CLASS_NOISE)[label] \
        * jax.random.uniform(ks[3], minval=0.5, maxval=1.5)
    n_blobs = jax.random.poisson(ks[4], noise * 12)
    centers = jax.random.randint(ks[5], (MAX_BLOBS, 2), 0, res)
    radii = jax.random.randint(ks[6], (MAX_BLOBS,), 2, 6)
    colors = jax.random.uniform(ks[7], (MAX_BLOBS, 3))
    iy, ix = jnp.arange(res)[:, None], jnp.arange(res)[None, :]

    def blend(k, img):
        (by, bx), br = centers[k], radii[k]
        blob = (iy >= by - br) & (iy < by + br) & (ix >= bx - br) \
            & (ix < bx + br) & (k < n_blobs)
        return jnp.where(blob[..., None], 0.5 * img + 0.5 * colors[k], img)

    img = jax.lax.fori_loop(0, MAX_BLOBS, blend, img)
    pix = jax.random.normal(jax.random.fold_in(key, 1), img.shape)
    return jnp.clip(img + noise * pix * 0.5, 0.0, 1.0)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _images(key, n, res):
    labels = jnp.arange(n) % 10
    return jax.vmap(_image, in_axes=(0, 0, None))(
        jax.random.split(key, n), labels, res)


def clutter_images(key, n: int, res: int) -> np.ndarray:
    """(n, res, res, 3) float32 images on the host."""
    return np.asarray(_images(key, n, res))
