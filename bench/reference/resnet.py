"""Plain float32 reference of a bottleneck ResNet with early exits.

ResNet-152 (He et al., arXiv:1512.03385): a 7x7/2 stem convolution,
batch norm, ReLU and a 3x3/2 max pool, then four stages of bottleneck
blocks (1x1, 3x3, 1x1 with a 4x expansion; the first block of stages
1-3 strides by 2 in its 3x3 convolution and its 1x1 projection).  An
exit after stage s is global average pooling and one linear layer; the
final head is the same after the last stage.

Departures from the paper, each one the served program's own:

* every convolution and the max pool pad "SAME" (the 7x7/2 stem pads
  2 before and 3 after at 224, where the paper's code pads 3 and 3);
* batch norm runs with stored statistics, eps 1e-5;
* ``small_input`` (the CIFAR-style test size): a 3x3/1 stem and no pool.

The parameter tree has the served program's layout, so one tree feeds
both; this module draws it from a key (``init``) and never imports the
program.  ``forward`` returns float32 logits at every exit and runs at
``jax.default_matmul_precision("highest")``; with ``fp8=True`` every
convolution and matmul takes fp8-rounded operands instead (the
benchmark's lower-precision control, see ``fp8.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.draw import Normal, realise
from bench.reference.fp8 import fake_quant

BN_EPS = 1e-5


def _conv(x, w, stride, fp8=False):
    w = w.astype(x.dtype)
    if fp8:
        x, w = fake_quant(x, keep=(0,)), fake_quant(w, keep=(3,))
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(p, x):
    return ((x - p["mean"]) * lax.rsqrt(p["var"] + BN_EPS)
            * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype))


def _block(p, x, stride, fp8):
    h = jax.nn.relu(_bn(p["bn1"], _conv(x, p["conv1"]["w"], 1, fp8)))
    h = jax.nn.relu(_bn(p["bn2"], _conv(h, p["conv2"]["w"], stride, fp8)))
    h = _bn(p["bn3"], _conv(h, p["conv3"]["w"], 1, fp8))
    idn = x
    if "down_conv" in p:
        idn = _bn(p["down_bn"], _conv(x, p["down_conv"]["w"], stride, fp8))
    return jax.nn.relu(h + idn)


def _linear(p, x, fp8=False):
    w = p["w"].astype(x.dtype)
    if fp8:
        x, w = fake_quant(x, keep=(0,)), fake_quant(w, keep=(1,))
    return x @ w + p["b"].astype(x.dtype)


def features(params, images, cfg, fp8=False):
    """Global-average-pooled features after each stage, float32."""
    x = images.astype(jnp.float32)
    small = cfg.get("small_input", False)
    x = jax.nn.relu(_bn(params["stem"]["bn"], _conv(
        x, params["stem"]["conv"]["w"], 1 if small else 2, fp8)))
    if not small:
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    out = []
    for s, blocks in enumerate(params["stages"]):
        for b, p in enumerate(blocks):
            x = _block(p, x, 2 if (b == 0 and s > 0) else 1, fp8)
        out.append(jnp.mean(x, axis=(1, 2)))
    return out


def forward(params, images, cfg, fp8=False):
    """(E, B, n_classes) float32 logits, exits in order, final last."""
    with jax.default_matmul_precision("highest"):
        feats = features(params, images, cfg, fp8)
        last = len(cfg["depths"]) - 1
        logits = [_linear(params["exit_heads"][str(s)], feats[s], fp8)
                  for s in cfg["exit_stages"] if s != last]
        logits.append(_linear(params["head"], feats[last], fp8))
        return jnp.stack(logits)


# -- weights from a key -------------------------------------------------------

def _bn_init(c, dtype, scale=1.0):
    return {"scale": jnp.full((c,), scale, dtype),
            "bias": jnp.zeros((c,), dtype),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def _conv_init(kh, kw, cin, cout):
    return {"w": Normal((kh, kw, cin, cout), (2.0 / (kh * kw * cin)) ** 0.5)}


def init(key, cfg, dtype=jnp.bfloat16):
    """Random weights in the served layout (``draw.py``).

    Convolutions are He-normal.  The last batch norm of every residual
    branch scales by ``cfg["branch_scale"]``, so the residual stream
    grows slowly through 50 blocks, as a trained network's statistics
    keep it.  Exit head s is drawn with the standard deviation that
    gives its logits a standard deviation of ``cfg["logit_std"]``, from
    the feature RMS measured at that exit (``cfg["exit_feature_rms"]``).
    """
    w = cfg["width"]
    k = 3 if cfg.get("small_input", False) else 7
    params = {"stem": {"conv": _conv_init(k, k, cfg["in_channels"], w),
                       "bn": _bn_init(w, dtype)}}
    stages, cin = [], w
    for s, depth in enumerate(cfg["depths"]):
        planes = w * 2 ** s
        blocks = []
        for b in range(depth):
            stride = 2 if (b == 0 and s > 0) else 1
            p = {"conv1": _conv_init(1, 1, cin, planes),
                 "bn1": _bn_init(planes, dtype),
                 "conv2": _conv_init(3, 3, planes, planes),
                 "bn2": _bn_init(planes, dtype),
                 "conv3": _conv_init(1, 1, planes, 4 * planes),
                 "bn3": _bn_init(4 * planes, dtype, cfg["branch_scale"])}
            if stride != 1 or cin != 4 * planes:
                p["down_conv"] = _conv_init(1, 1, cin, 4 * planes)
                p["down_bn"] = _bn_init(4 * planes, dtype)
            blocks.append(p)
            cin = 4 * planes
        stages.append(blocks)
    params["stages"] = stages

    def head(s):
        c = w * 2 ** s * 4
        std = cfg["logit_std"] / (cfg["exit_feature_rms"][
            exit_position(cfg, s)] * c ** 0.5)
        return {"w": Normal((c, cfg["n_classes"]), std),
                "b": jnp.zeros((cfg["n_classes"],), dtype)}

    last = len(cfg["depths"]) - 1
    params["exit_heads"] = {str(s): head(s)
                            for s in cfg["exit_stages"] if s != last}
    params["head"] = head(last)
    return realise(key, params, dtype)


def exit_position(cfg, stage):
    """Index of the exit after ``stage`` (the final head is last)."""
    early = [s for s in cfg["exit_stages"] if s != len(cfg["depths"]) - 1]
    return early.index(stage) if stage in early else len(early)
