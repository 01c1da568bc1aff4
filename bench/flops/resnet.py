"""Operations of a bottleneck ResNet, per image, from its configuration.

FLOPs are 2 x multiply-accumulates of the convolutions and linear
layers; batch norm, ReLU, pooling and the residual adds are left out,
as is usual (they are under 1% of ResNet-152's work).  Each 1x1
convolution runs at the resolution of its input: the first block of
stages 1-3 strides in its 3x3 convolution, so its first 1x1 runs at
the stage's input resolution.
"""
from __future__ import annotations


def stage_flops(cfg) -> list[int]:
    """FLOPs of the stem (counted in stage 0) and of each stage."""
    small = cfg.get("small_input", False)          # 3x3/1 stem, no pool
    res = cfg["img_res"] // (1 if small else 2)    # stem output
    k = 3 if small else 7
    stem = 2 * k * k * cfg["in_channels"] * cfg["width"] * res * res
    res //= 1 if small else 2                      # max pool
    cin, out = cfg["width"], []
    for s, depth in enumerate(cfg["depths"]):
        planes = cfg["width"] * 2 ** s
        fl = 0
        for b in range(depth):
            res_in = res
            if b == 0 and s > 0:
                res //= 2
            c = cin if b == 0 else 4 * planes
            fl += 2 * (res_in * res_in * c * planes        # 1x1
                       + res * res * 9 * planes * planes   # 3x3, strided
                       + res * res * planes * 4 * planes)  # 1x1 expand
            if b == 0:                                     # projection
                fl += 2 * res * res * c * 4 * planes
        cin = 4 * planes
        out.append(fl + (stem if s == 0 else 0))
    return out


def exit_stage(cfg) -> list[int]:
    """The stage each exit follows, in exit order, the final head last."""
    last = len(cfg["depths"]) - 1
    return [s for s in cfg["exit_stages"] if s != last] + [last]


def head_flops(cfg) -> list[int]:
    """FLOPs of each exit head in exit order, the final head last."""
    return [2 * cfg["width"] * 2 ** s * 4 * cfg["n_classes"]
            for s in exit_stage(cfg)]


def exit_flops(cfg) -> list[int]:
    """Useful FLOPs of a sample that leaves at each exit: the backbone up
    to that exit's stage and every head up to and including it."""
    st, hd = stage_flops(cfg), head_flops(cfg)
    return [sum(st[:s + 1]) + sum(hd[:e + 1])
            for e, s in enumerate(exit_stage(cfg))]


def step_flops(cfg) -> int:
    """FLOPs the masked step computes per row: every stage and head."""
    return sum(stage_flops(cfg)) + sum(head_flops(cfg))
