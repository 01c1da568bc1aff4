"""Operations of a Vision Transformer with Eq. 16 exit heads, per image.

FLOPs are 2 x multiply-accumulates of the patch embedding, the four
attention projections, the two attention products and the MLP, and of
the exit heads' linear layers; layer norms, softmax and GELU are left
out (about 1% of ViT-H/14's work).
"""
from __future__ import annotations


def _hidden(cfg) -> int:
    return max(16, int(cfg["d_model"] * cfg["exit_mlp_ratio"]))


def layer_flops(cfg) -> int:
    n = (cfg["img_res"] // cfg["patch"]) ** 2
    d, f = cfg["d_model"], cfg["d_ff"]
    return 2 * (4 * n * d * d + 2 * n * n * d + 2 * n * d * f)


def embed_flops(cfg) -> int:
    n = (cfg["img_res"] // cfg["patch"]) ** 2
    return 2 * n * cfg["d_model"] * cfg["patch"] ** 2 * cfg["in_channels"]


def exit_layer(cfg) -> list[int]:
    """The last layer before each exit, the final head last."""
    return list(cfg["exit_layers"]) + [cfg["n_layers"] - 1]


def head_flops(cfg) -> list[int]:
    d, c, h = cfg["d_model"], cfg["n_classes"], _hidden(cfg)
    return [2 * (d * h + h * c)] * len(cfg["exit_layers"]) + [2 * d * c]


def exit_flops(cfg) -> list[int]:
    """Useful FLOPs of a sample that leaves at each exit: the embedding,
    the layers up to that exit and every head up to and including it."""
    hd = head_flops(cfg)
    return [embed_flops(cfg) + (l + 1) * layer_flops(cfg) + sum(hd[:e + 1])
            for e, l in enumerate(exit_layer(cfg))]


def step_flops(cfg) -> int:
    """FLOPs the masked step computes per row: every layer and head."""
    return embed_flops(cfg) + cfg["n_layers"] * layer_flops(cfg) \
        + sum(head_flops(cfg))
