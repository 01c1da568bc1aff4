"""Tiny configurations and a throwaway checkout for the CPU tests."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

#: ResNet and ViT cut to a size the CPU runs in seconds; the served
#: configurations' structure, float32
TINY = {
    "tiny_resnet": {
        "name": "tiny-resnet", "family": "resnet",
        "program_config": "repro.models.resnet:ResNetConfig",
        "depths": [1, 1, 1, 1], "width": 8, "block": "bottleneck",
        "img_res": 32, "in_channels": 3, "n_classes": 10,
        "exit_stages": [0, 1, 2], "n_exits": 4, "branch_scale": 0.2,
        "exit_feature_rms": [0.5, 0.5, 0.5, 0.5]},
    "tiny_vit": {
        "name": "tiny-vit", "family": "vit",
        "program_config": "repro.models.vit:ViTConfig",
        "img_res": 32, "patch": 8, "n_layers": 4, "d_model": 64,
        "n_heads": 4, "d_ff": 128, "in_channels": 3, "n_classes": 10,
        "exit_layers": [0, 1, 2], "exit_mlp_ratio": 0.5, "remat": False,
        "n_exits": 4, "exit_feature_rms": [0.4, 0.4, 0.4, 1.0]},
}
#: float32 end to end, so sound runs read about 1e-6 and the limits are
#: 1e-3; the fp8 control reads 1e-2 and more
COMMON = {"dtype": "float32", "buckets": [4, 8], "max_batch": 8,
          "adapt": False, "beta_diff": 0.3, "logit_std": 6.0,
          "difficulty": {"tau_edge": 0.1, "var_scale": 0.05,
                         "grad_scale": 0.2, "w": [0.4, 0.3, 0.3]}}
LIMITS = {"pred_gap": 1e-3, "conf_err": 1e-3, "conf_err_mean": 1e-3,
          "gate_violation": 1e-3, "alpha_err": 1e-3}
#: a small closed loop over the tiny configurations' buckets
TINY_TRAFFIC = {"loop": "closed", "clients": 4, "sizes": [1, 3, 8],
                "exit_shares": [0.4, 0.3, 0.2, 0.1]}


def tiny_config(name: str) -> dict:
    return {**TINY[name], **COMMON}


def write_limits(root: str, cell: str):
    with open(os.path.join(root, "bench", "limits", f"{cell}.json"),
              "w") as f:
        json.dump(LIMITS, f)


def make_root(tmp_path, cells):
    """A checkout holding ``BENCHMARK.json`` with ``cells`` ((name,
    config, traffic) triples over the tiny configurations) and a copy of
    the benchmark's traffic mixes and metric readers."""
    root = tmp_path / "checkout"
    for sub in ("traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), root / "bench" / sub)
    (root / "bench" / "traffic" / "tiny_closed.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (root / "bench" / "configs").mkdir(parents=True)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        metric.pop("workloads", None)
    bench["configs"] = []
    for config in {cfg for _, cfg, _ in cells}:
        path = f"bench/configs/{config}.json"
        (root / path).write_text(json.dumps(tiny_config(config)))
        bench["configs"].append({"name": config, "file": path})
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1}
                          for n, c, t in cells]
    (root / "bench" / "limits").mkdir()
    for n, _, _ in cells:
        write_limits(str(root), n)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)
