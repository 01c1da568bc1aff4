"""Plain float32 reference of a Vision Transformer with early exits.

ViT-H/14 (Dosovitskiy et al., arXiv:2010.11929): 14x14 patches embedded
by a strided convolution, learned position embeddings, then pre-norm
encoder blocks, x + MHA(LN(x)) and x + MLP(LN(x)).  An exit after layer
l is Eq. 16 of the DART paper, MLP(LayerNorm(GlobalPool(tokens))) with
a hidden width of half the model width; the final head is
Linear(LayerNorm(GlobalPool(tokens))).

Departures from the paper, each one the served program's own:

* no class token: every head pools the tokens by their mean;
* GELU is the tanh approximation (``jax.nn.gelu``'s default);
* the query projection has a bias, key and value projections have none;
* layer norm eps 1e-6.

The parameter tree has the served program's layout, so one tree feeds
both; this module draws it from a key (``init``) and never imports the
program.  ``forward`` returns float32 logits at every exit and runs at
``jax.default_matmul_precision("highest")``; with ``fp8=True`` every
matmul takes fp8-rounded operands instead (the benchmark's
lower-precision control, see ``fp8.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.draw import Normal, realise
from bench.reference.fp8 import fake_quant

LN_EPS = 1e-6


def _ln(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + LN_EPS) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def _einsum(spec, x, w, w_keep, fp8):
    """x's last axis is contracted; ``w_keep`` are w's output axes."""
    w = w.astype(x.dtype)
    if fp8:
        x = fake_quant(x, keep=tuple(range(x.ndim - 1)))
        w = fake_quant(w, keep=w_keep)
    return jnp.einsum(spec, x, w)


def _linear(p, x, fp8=False):
    return _einsum("...d,df->...f", x, p["w"], (1,), fp8) \
        + p["b"].astype(x.dtype)


def _attn(p, x, fp8):
    f = x.dtype
    q = _einsum("bsd,dhk->bshk", x, p["wq"], (1, 2), fp8) \
        + p["bq"].astype(f)
    k = _einsum("bsd,dhk->bshk", x, p["wk"], (1, 2), fp8)
    v = _einsum("bsd,dhk->bshk", x, p["wv"], (1, 2), fp8)
    if fp8:
        q, k, v = (fake_quant(t, keep=(0, 1, 2)) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    w = jax.nn.softmax(s, axis=-1)
    if fp8:
        w = fake_quant(w, keep=(0, 1, 2))
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    return _einsum("bshk,hkd->bsd", o, p["wo"], (2,), fp8) \
        + p["bo"].astype(f)


def _block(p, x, fp8):
    x = x + _attn(p["attn"], _ln(p["norm1"], x), fp8)
    h = jax.nn.gelu(_linear(p["mlp"]["up"], _ln(p["norm2"], x), fp8))
    return x + _linear(p["mlp"]["down"], h, fp8)


def embed(params, images, cfg, fp8=False):
    x = images.astype(jnp.float32)
    w = params["patch"]["proj"]["w"].astype(x.dtype)
    if fp8:
        x, w = fake_quant(x, keep=(0,)), fake_quant(w, keep=(3,))
    pp = cfg["patch"]
    y = lax.conv_general_dilated(
        x, w, (pp, pp), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = y + params["patch"]["proj"]["b"].astype(x.dtype)
    b, h, w, c = y.shape
    return y.reshape(b, h * w, c) + params["pos"].astype(x.dtype)


def exit_head(p, tokens, fp8=False):
    h = _ln(p["norm"], jnp.mean(tokens, axis=1))
    return _linear(p["fc2"], jax.nn.gelu(_linear(p["fc1"], h, fp8)), fp8)


def final_head(params, tokens, fp8=False):
    return _linear(params["head"],
                   _ln(params["final_norm"], jnp.mean(tokens, axis=1)), fp8)


def forward(params, images, cfg, fp8=False):
    """(E, B, n_classes) float32 logits, exits in order, final last."""
    with jax.default_matmul_precision("highest"):
        x = embed(params, images, cfg, fp8)
        logits = []
        for i, p in enumerate(params["blocks"]):
            x = _block(p, x, fp8)
            if i in cfg["exit_layers"]:
                logits.append(exit_head(params["exit_heads"][str(i)], x,
                                        fp8))
        logits.append(final_head(params, x, fp8))
        return jnp.stack(logits)


# -- weights from a key -------------------------------------------------------

def _ln_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def _lin_init(din, dout, std, dtype):
    return {"w": Normal((din, dout), std), "b": jnp.zeros((dout,), dtype)}


def init(key, cfg, dtype=jnp.bfloat16):
    """Random weights in the served layout (``draw.py``): projections N(0, 0.02²), the
    patch convolution He-normal, position embeddings N(0, 0.02²).  Each
    head's output layer is drawn with the standard deviation that gives
    its logits a standard deviation of ``cfg["logit_std"]``, from the RMS
    of that layer's input (``cfg["exit_feature_rms"]``)."""
    d, f, nh = cfg["d_model"], cfg["d_ff"], cfg["n_heads"]
    hd = d // nh
    hidden = max(16, int(d * cfg["exit_mlp_ratio"]))
    pp, cin = cfg["patch"], cfg["in_channels"]
    n_tok = (cfg["img_res"] // pp) ** 2
    params = {
        "patch": {"proj": {"w": Normal((pp, pp, cin, d),
                                       (2.0 / (pp * pp * cin)) ** 0.5),
                           "b": jnp.zeros((d,), dtype)}},
        "pos": Normal((n_tok, d), 0.02),
    }
    blocks = []
    for _ in range(cfg["n_layers"]):
        blocks.append({
            "norm1": _ln_init(d, dtype),
            "attn": {"wq": Normal((d, nh, hd), 0.02),
                     "wk": Normal((d, nh, hd), 0.02),
                     "wv": Normal((d, nh, hd), 0.02),
                     "wo": Normal((nh, hd, d), 0.02),
                     "bq": jnp.zeros((nh, hd), dtype),
                     "bo": jnp.zeros((d,), dtype)},
            "norm2": _ln_init(d, dtype),
            "mlp": {"up": _lin_init(d, f, 0.02, dtype),
                    "down": _lin_init(f, d, 0.02, dtype)},
        })
    params["blocks"] = blocks
    rms = cfg["exit_feature_rms"]
    std = [cfg["logit_std"] / (r * (hidden if i < len(rms) - 1 else d) ** 0.5)
           for i, r in enumerate(rms)]
    params["exit_heads"] = {
        str(layer): {"norm": _ln_init(d, dtype),
                     "fc1": _lin_init(d, hidden, 0.02, dtype),
                     "fc2": _lin_init(hidden, cfg["n_classes"], std[i],
                                      dtype)}
        for i, layer in enumerate(cfg["exit_layers"])}
    params["final_norm"] = _ln_init(d, dtype)
    params["head"] = _lin_init(d, cfg["n_classes"], std[-1], dtype)
    return realise(key, params, dtype)
