"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces (artifacts/dryrun/<arch>__<shape>__<mesh>.json):
  * ``memory_analysis``  — per-device argument/output/temp bytes (fits?)
  * ``cost_analysis``    — per-device HLO FLOPs and bytes accessed
  * ``collectives``      — per-class counts and per-device bytes parsed
                           from the compiled (post-SPMD) HLO
  * ``model_flops``      — analytic MODEL_FLOPS (6ND-style) for §Roofline
  * sharding downgrades, compile time

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch vit-s16 --shape cls_224
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
"""
import argparse
import json
import os
import re
import time
import traceback

import jax

from repro.configs import registry
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_mod

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")
# bytes-on-wire multiplier per op result (ring algorithm accounting)
WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}

_DTYPE_BYTES = {"pred": 0.125, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _result_bytes(hlo_line: str) -> float:
    """Sum the byte size of the result type(s) on an HLO op line."""
    lhs = hlo_line.split(" = ", 1)
    if len(lhs) != 2:
        return 0.0
    # result type is at the start of the RHS, possibly a tuple
    rhs = lhs[1]
    op_pos = min((rhs.find(k) for k in COLLECTIVE_KINDS if k in rhs),
                 default=-1)
    head = rhs[:op_pos] if op_pos > 0 else rhs.split("(")[0]
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(head):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str, bf16_model: bool = False) -> dict:
    """Per-class collective counts/bytes from the post-SPMD HLO.

    CPU-backend caveat (documented in EXPERIMENTS.md §Dry-run): XLA:CPU
    legalizes bf16 compute to f32, so collectives that would be bf16 on
    TPU appear as f32 here.  For bf16 models we additionally report
    ``total_bytes_bf16corr`` = f32-typed collective bytes × 0.5 (verified
    against the bf16 StableHLO dot types)."""
    stats = {k: {"count": 0, "bytes": 0.0} for k in COLLECTIVE_KINDS}
    corr_total = 0.0
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith("//") or " = " not in s:
            continue
        for kind in COLLECTIVE_KINDS:
            # match `all-reduce(` / `all-reduce-start(`; skip `-done` (the
            # async pair would double-count the same transfer)
            if re.search(rf"(?<![\w-]){kind}(?:-start)?\(", s):
                by = _result_bytes(s) * WIRE_FACTOR[kind]
                stats[kind]["count"] += 1
                stats[kind]["bytes"] += by
                is_f32 = " f32[" in s or "(f32[" in s
                corr_total += by * (0.5 if (bf16_model and is_f32) else 1.0)
                break
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    stats["total_bytes_bf16corr"] = corr_total
    return stats


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             outdir: str = "artifacts/dryrun", reduced=False,
             keep_hlo=False, step_builder=None,
             variant: str = "baseline") -> dict:
    sp = next(s for s in registry.shapes(arch) if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    bundle = (step_builder or steps_mod.build)(arch, sp, mesh,
                                               reduced=reduced,
                                               variant=variant)
    lowered = jax.jit(bundle.step,
                      in_shardings=bundle.in_shardings).lower(*bundle.inputs)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    bf16_model = bundle.meta.get("bf16", True) and not reduced
    coll = parse_collectives(hlo, bf16_model=bf16_model)
    n_dev = mesh.devices.size

    # segment-scan cells: XLA costs each scan body once; compile a
    # single-layer probe and extrapolate the missing layer instances.
    probe_fn = bundle.meta.pop("probe", None)
    scan_corr = None
    if probe_fn is not None:
        pb = probe_fn()
        plow = jax.jit(pb.step, in_shardings=pb.in_shardings).lower(
            *pb.inputs)
        pcomp = plow.compile()
        pca = pcomp.cost_analysis()
        pcoll = parse_collectives(pcomp.as_text(), bf16_model=bf16_model)
        extra = (bundle.meta["scan_layers_total"]
                 - bundle.meta["scan_body_instances"])
        scan_corr = {
            "probe_flops_per_device": float(pca.get("flops", 0.0)),
            "probe_bytes_per_device": float(pca.get("bytes accessed", 0.0)),
            "probe_collective_bytes": pcoll["total_bytes_bf16corr"],
            "extrapolated_layers": extra,
        }
        ca = dict(ca)
        ca["flops"] = float(ca.get("flops", 0.0)) \
            + scan_corr["probe_flops_per_device"] * extra
        ca["bytes accessed"] = float(ca.get("bytes accessed", 0.0)) \
            + scan_corr["probe_bytes_per_device"] * extra
        for key in ("total_bytes", "total_bytes_bf16corr"):
            coll[key] = coll[key] + pcoll[key] * extra

    rec = {
        "arch": arch, "shape": sp.name, "kind": bundle.meta.get("kind"),
        "mesh": mesh_name, "devices": n_dev,
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        "model_flops_global": int(bundle.model_flops),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "collectives": coll,
        "downgrades": [f"{d.path} dim{d.dim} {d.logical}->{d.wanted}"
                       for d in bundle.downgrades],
        "scan_correction": scan_corr,
        "meta": bundle.meta,
    }
    rec["variant"] = variant
    os.makedirs(outdir, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant}"
    fn = os.path.join(outdir,
                      f"{arch}__{sp.name}__{mesh_name}{suffix}.json")
    with open(fn, "w") as f:
        json.dump(rec, f, indent=1)
    if keep_hlo:
        with open(fn.replace(".json", ".hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--outdir", default="artifacts/dryrun")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    pods = {"single": [False], "multi": [True],
            "both": [False, True]}[args.multi_pod]
    cells = registry.cells() if args.all else [
        (args.arch, next(s for s in registry.shapes(args.arch)
                         if s.name == args.shape))]
    failures = []
    for arch, sp in cells:
        for mp in pods:
            mesh_name = '2x16x16' if mp else '16x16'
            tag = f"{arch} × {sp.name} × {mesh_name}"
            if args.skip_existing:
                suffix = "" if args.variant == "baseline" \
                    else f"__{args.variant}"
                fn = os.path.join(args.outdir,
                                  f"{arch}__{sp.name}__{mesh_name}{suffix}.json")
                if os.path.exists(fn):
                    print(f"SKIP {tag} (artifact exists)")
                    continue
            try:
                rec = run_cell(arch, sp.name, multi_pod=mp,
                               outdir=args.outdir, reduced=args.reduced,
                               keep_hlo=args.keep_hlo,
                               variant=args.variant)
                print(f"OK   {tag}: compile {rec['compile_s']}s, "
                      f"flops/dev {rec['flops_per_device']:.3e}, "
                      f"temp {rec['memory']['temp_bytes']/2**30:.2f} GiB, "
                      f"coll {rec['collectives']['total_bytes']/2**30:.3f} GiB")
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"FAIL {tag}: {e!r}")
                traceback.print_exc(limit=3)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    # the production meshes need 512 host devices: set before the first
    # backend use, by this command only (importers keep their own flags)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
