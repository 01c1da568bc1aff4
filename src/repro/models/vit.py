"""Vision Transformer with early-exit heads (paper §II.D mapping).

Exit heads follow Eq. 16: ``ExitBlock_ViT(T) = MLP(LayerNorm(GlobalPool(T)))``.
The final head uses the same global-average-pool convention.

Covers assigned archs ``vit-s16`` and ``vit-h14`` (and their reduced smoke
variants).  Implements the generic *staged* vision-classifier interface
used by the DART serving engine (``repro.engine``):

  ``num_stages(cfg)``, ``apply_stem``, ``apply_stage``, ``apply_exit``.

Stages are groups of encoder blocks split at exit boundaries.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.parallel.sharding import Param


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    name: str
    img_res: int
    patch: int
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    n_classes: int = 1000
    in_channels: int = 3
    exit_layers: tuple[int, ...] = ()
    exit_mlp_ratio: float = 0.5       # hidden dim of the Eq.16 exit MLP
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    remat: bool = False

    @property
    def n_tokens(self) -> int:
        return (self.img_res // self.patch) ** 2

    @property
    def n_exits(self) -> int:
        return len(self.exit_layers) + 1

    @property
    def stage_bounds(self) -> tuple[int, ...]:
        """Layer index (exclusive) ending each stage; final stage = n_layers."""
        return tuple(i + 1 for i in self.exit_layers) + (self.n_layers,)


def _block_init(key, cfg: ViTConfig):
    dt = cfg.param_dtype
    return {
        "norm1": L.layernorm_init(cfg.d_model, dt),
        "attn": L.mha_init(L.rng(key, "attn"), cfg.d_model, cfg.n_heads, dt),
        "norm2": L.layernorm_init(cfg.d_model, dt),
        "mlp": L.mlp_init(L.rng(key, "mlp"), cfg.d_model, cfg.d_ff, dt),
    }


def exit_head_init(key, d_model, n_classes, hidden, dt):
    """Paper Eq. 16: MLP(LayerNorm(GlobalPool(T)))."""
    return {
        "norm": L.layernorm_init(d_model, dt),
        "fc1": L.linear_init(L.rng(key, "fc1"), d_model, hidden, dt,
                             axes=("embed", "mlp")),
        "fc2": L.linear_init(L.rng(key, "fc2"), hidden, n_classes, dt,
                             axes=("mlp", "classes")),
    }


def exit_head_apply(p, tokens):
    """tokens: (B, N, D) or pooled (B, D)."""
    h = tokens if tokens.ndim == 2 else L.global_avg_pool(tokens)
    h = L.layernorm(p["norm"], h)
    return L.linear(p["fc2"], jax.nn.gelu(L.linear(p["fc1"], h)))


def vit_init(key, cfg: ViTConfig):
    dt = cfg.param_dtype
    hidden = max(16, int(cfg.d_model * cfg.exit_mlp_ratio))
    p = {
        "patch": L.patch_embed_init(L.rng(key, "patch"), cfg.patch,
                                    cfg.in_channels, cfg.d_model, dt),
        "pos": Param(L.trunc_normal(L.rng(key, "pos"),
                                    (cfg.n_tokens, cfg.d_model), dt),
                     ("seq", "embed")),
        "blocks": [_block_init(L.rng(key, f"b{i}"), cfg)
                   for i in range(cfg.n_layers)],
        "final_norm": L.layernorm_init(cfg.d_model, dt),
        "head": L.linear_init(L.rng(key, "head"), cfg.d_model, cfg.n_classes,
                              dt, axes=("embed", "classes")),
        "exit_heads": {str(i): exit_head_init(L.rng(key, f"exit{i}"),
                                              cfg.d_model, cfg.n_classes,
                                              hidden, dt)
                       for i in cfg.exit_layers},
    }
    return p


def _block_apply(p, x):
    x = x + L.mha_apply(p["attn"], L.layernorm(p["norm1"], x))
    x = x + L.mlp(p["mlp"], L.layernorm(p["norm2"], x))
    return x


# -- staged interface -------------------------------------------------------

def apply_stem(params, images, cfg: ViTConfig):
    x = L.patch_embed(params["patch"], images.astype(cfg.compute_dtype),
                      cfg.patch)
    return x + params["pos"].astype(cfg.compute_dtype)


def apply_stage(params, x, stage: int, cfg: ViTConfig):
    start = 0 if stage == 0 else cfg.stage_bounds[stage - 1]
    end = cfg.stage_bounds[stage]
    blk = jax.checkpoint(_block_apply) if cfg.remat else _block_apply
    for i in range(start, end):
        x = blk(params["blocks"][i], x)
    return x


def apply_exit(params, x, stage: int, cfg: ViTConfig):
    """Logits at the exit ending ``stage`` (last stage = final head)."""
    if stage == len(cfg.stage_bounds) - 1:
        h = L.layernorm(params["final_norm"], L.global_avg_pool(x))
        return L.linear(params["head"], h)
    layer = cfg.exit_layers[stage]
    return exit_head_apply(params["exit_heads"][str(layer)], x)


def num_stages(cfg: ViTConfig) -> int:
    return len(cfg.stage_bounds)


def vit_forward(params, images, cfg: ViTConfig, *, mesh=None, train=False):
    """All-exits forward (training / masked serving).

    Returns {"exit_logits": (n_exits, B, n_classes)}."""
    with jax.named_scope("stem"):
        x = apply_stem(params, images, cfg)
    logits = []
    for s in range(num_stages(cfg)):
        with jax.named_scope(f"stage{s}"):
            x = apply_stage(params, x, s, cfg)
        with jax.named_scope(f"exit{s}"):
            logits.append(apply_exit(params, x, s, cfg))
    return {"exit_logits": jnp.stack(logits)}


def vit_forward_flops(cfg: ViTConfig, batch: int) -> int:
    n, d, f = cfg.n_tokens, cfg.d_model, cfg.d_ff
    per_block = 2 * n * d * d * 4 + 2 * 2 * n * n * d + 2 * n * d * f * 2
    stem = 2 * n * d * (cfg.patch ** 2 * cfg.in_channels)
    exits = cfg.n_exits * 2 * d * cfg.n_classes
    return int(batch * (stem + cfg.n_layers * per_block + exits))
