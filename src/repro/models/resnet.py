"""ResNet (bottleneck) with BranchyNet-style early exits.

Assigned arch ``resnet-152`` (depths 3-8-36-3) plus the paper's ResNet-18
testbed (basic blocks, depths 2-2-2-2).  Exits sit after each stage
(GAP -> Linear heads); staged interface for the DART serving engine.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.batchnorm import bn_init, bn_apply


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    name: str
    depths: tuple[int, ...] = (3, 8, 36, 3)
    width: int = 64
    block: str = "bottleneck"              # "bottleneck" | "basic"
    img_res: int = 224
    n_classes: int = 1000
    in_channels: int = 3
    exit_stages: tuple[int, ...] = (0, 1, 2)   # early exits after these stages
    small_input: bool = False              # CIFAR-style stem (3x3, no pool)
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    @property
    def expansion(self) -> int:
        return 4 if self.block == "bottleneck" else 1

    @property
    def n_exits(self) -> int:
        return len(self.exit_stages) + 1


def _block_init(key, cin, planes, cfg, stride):
    dt = cfg.param_dtype
    e = cfg.expansion
    if cfg.block == "bottleneck":
        p = {
            "conv1": L.conv_init(L.rng(key, "c1"), 1, 1, cin, planes, dt,
                                 bias=False),
            "bn1": bn_init(planes, dt),
            "conv2": L.conv_init(L.rng(key, "c2"), 3, 3, planes, planes, dt,
                                 bias=False),
            "bn2": bn_init(planes, dt),
            "conv3": L.conv_init(L.rng(key, "c3"), 1, 1, planes, planes * e,
                                 dt, bias=False),
            "bn3": bn_init(planes * e, dt),
        }
    else:
        p = {
            "conv1": L.conv_init(L.rng(key, "c1"), 3, 3, cin, planes, dt,
                                 bias=False),
            "bn1": bn_init(planes, dt),
            "conv2": L.conv_init(L.rng(key, "c2"), 3, 3, planes, planes, dt,
                                 bias=False),
            "bn2": bn_init(planes, dt),
        }
    if stride != 1 or cin != planes * e:
        p["down_conv"] = L.conv_init(L.rng(key, "dc"), 1, 1, cin, planes * e,
                                     dt, bias=False)
        p["down_bn"] = bn_init(planes * e, dt)
    return p


def _block_apply(p, x, cfg, stride, *, train, updates, name):
    idn = x
    if cfg.block == "bottleneck":
        h = jax.nn.relu(bn_apply(p["bn1"], L.conv2d(p["conv1"], x),
                                 train=train, updates=updates,
                                 name=f"{name}/bn1"))
        h = jax.nn.relu(bn_apply(p["bn2"], L.conv2d(p["conv2"], h,
                                                    stride=stride),
                                 train=train, updates=updates,
                                 name=f"{name}/bn2"))
        h = bn_apply(p["bn3"], L.conv2d(p["conv3"], h), train=train,
                     updates=updates, name=f"{name}/bn3")
    else:
        h = jax.nn.relu(bn_apply(p["bn1"], L.conv2d(p["conv1"], x,
                                                    stride=stride),
                                 train=train, updates=updates,
                                 name=f"{name}/bn1"))
        h = bn_apply(p["bn2"], L.conv2d(p["conv2"], h), train=train,
                     updates=updates, name=f"{name}/bn2")
    if "down_conv" in p:
        idn = bn_apply(p["down_bn"], L.conv2d(p["down_conv"], x,
                                              stride=stride),
                       train=train, updates=updates, name=f"{name}/down_bn")
    return jax.nn.relu(h + idn)


def resnet_init(key, cfg: ResNetConfig):
    dt = cfg.param_dtype
    e = cfg.expansion
    stem_out = cfg.width
    if cfg.small_input:
        stem = {"conv": L.conv_init(L.rng(key, "stem"), 3, 3, cfg.in_channels,
                                    stem_out, dt, bias=False),
                "bn": bn_init(stem_out, dt)}
    else:
        stem = {"conv": L.conv_init(L.rng(key, "stem"), 7, 7, cfg.in_channels,
                                    stem_out, dt, bias=False),
                "bn": bn_init(stem_out, dt)}
    stages = []
    cin = stem_out
    for s, depth in enumerate(cfg.depths):
        planes = cfg.width * (2 ** s)
        blocks = []
        for b in range(depth):
            stride = 2 if (b == 0 and s > 0) else 1
            blocks.append(_block_init(L.rng(key, f"s{s}b{b}"), cin, planes,
                                      cfg, stride))
            cin = planes * e
        stages.append(blocks)
    heads = {}
    for s in cfg.exit_stages:
        cdim = cfg.width * (2 ** s) * e
        heads[str(s)] = L.linear_init(L.rng(key, f"exit{s}"), cdim,
                                      cfg.n_classes, dt,
                                      axes=("embed", "classes"))
    return {
        "stem": stem,
        "stages": stages,
        "head": L.linear_init(L.rng(key, "head"),
                              cfg.width * (2 ** (len(cfg.depths) - 1)) * e,
                              cfg.n_classes, dt, axes=("embed", "classes")),
        "exit_heads": heads,
    }


# -- staged interface -------------------------------------------------------

def apply_stem(params, images, cfg: ResNetConfig, *, train=False,
               updates=None):
    x = images.astype(cfg.compute_dtype)
    stride = 1 if cfg.small_input else 2
    x = jax.nn.relu(bn_apply(params["stem"]["bn"],
                             L.conv2d(params["stem"]["conv"], x,
                                      stride=stride),
                             train=train, updates=updates, name="stem/bn"))
    if not cfg.small_input:
        x = L.max_pool(x, 3, 2)
    return x


def apply_stage(params, x, stage: int, cfg: ResNetConfig, *, train=False,
                updates=None):
    for b, bp in enumerate(params["stages"][stage]):
        stride = 2 if (b == 0 and stage > 0) else 1
        x = _block_apply(bp, x, cfg, stride, train=train, updates=updates,
                         name=f"stages/{stage}/{b}")
    return x


def apply_exit(params, x, stage: int, cfg: ResNetConfig):
    h = L.global_avg_pool(x)
    if stage == len(cfg.depths) - 1:
        return L.linear(params["head"], h)
    return L.linear(params["exit_heads"][str(stage)], h)


def num_stages(cfg: ResNetConfig) -> int:
    return len(cfg.depths)


def resnet_forward(params, images, cfg: ResNetConfig, *, mesh=None,
                   train=False):
    updates: dict = {}
    with jax.named_scope("stem"):
        x = apply_stem(params, images, cfg, train=train, updates=updates)
    logits = []
    for s in range(num_stages(cfg)):
        with jax.named_scope(f"stage{s}"):
            x = apply_stage(params, x, s, cfg, train=train,
                            updates=updates)
        if s in cfg.exit_stages or s == num_stages(cfg) - 1:
            with jax.named_scope(f"exit{s}"):
                logits.append(apply_exit(params, x, s, cfg))
    return {"exit_logits": jnp.stack(logits), "bn_updates": updates}


def resnet_forward_flops(cfg: ResNetConfig, batch: int) -> int:
    """Analytic conv MACs*2 (approximate: ignores bias/norm)."""
    res = cfg.img_res // (1 if cfg.small_input else 4)
    fl = 0
    cin = cfg.width
    stem_res = cfg.img_res // (1 if cfg.small_input else 2)
    fl += 2 * (7 * 7 if not cfg.small_input else 9) * cfg.in_channels \
        * cfg.width * stem_res * stem_res
    e = cfg.expansion
    for s, depth in enumerate(cfg.depths):
        planes = cfg.width * (2 ** s)
        if s > 0:
            res //= 2
        for b in range(depth):
            c_in = cin if b == 0 else planes * e
            if cfg.block == "bottleneck":
                fl += 2 * res * res * (c_in * planes + 9 * planes * planes
                                       + planes * planes * e)
                if b == 0:
                    fl += 2 * res * res * c_in * planes * e
            else:
                fl += 2 * res * res * (9 * c_in * planes
                                       + 9 * planes * planes)
                if b == 0 and s > 0:
                    fl += 2 * res * res * c_in * planes
        cin = planes * e
    return int(batch * fl)
