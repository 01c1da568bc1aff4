"""Dry-run machinery tests: HLO collective parsing + reduced-config cells
compiling on the REAL production meshes (512 fake devices, subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.dryrun import parse_collectives, _result_bytes


def test_result_bytes_parsing():
    line = ("%all-reduce.5 = f32[16,512,2048]{2,1,0} "
            "all-reduce(f32[16,512,2048]{2,1,0} %x), replica_groups={}")
    assert _result_bytes(line) == 16 * 512 * 2048 * 4


def test_result_bytes_tuple():
    line = ("%ar = (bf16[8,4]{1,0}, bf16[8,4]{1,0}) all-reduce(%a, %b), "
            "replica_groups={}")
    assert _result_bytes(line) == 2 * 8 * 4 * 2


def test_parse_collectives_classes_and_wire_factor():
    hlo = """
  %ag = bf16[64,128]{1,0} all-gather(bf16[4,128]{1,0} %p), dims={0}
  %ar.1 = f32[32]{0} all-reduce(f32[32]{0} %x), to_apply=%sum
  %rs = f32[4,8]{1,0} reduce-scatter(f32[64,8]{1,0} %y), dims={0}
  %a2a = bf16[16,16]{1,0} all-to-all(bf16[16,16]{1,0} %z), dims={0}
  %cp-start = bf16[8]{0} collective-permute-start(bf16[8]{0} %w)
  %cp-done = bf16[8]{0} collective-permute-done(%cp-start)
"""
    st = parse_collectives(hlo)
    assert st["all-gather"]["count"] == 1
    assert st["all-gather"]["bytes"] == 64 * 128 * 2
    assert st["all-reduce"]["bytes"] == 32 * 4 * 2.0       # wire factor 2
    assert st["reduce-scatter"]["count"] == 1
    assert st["all-to-all"]["count"] == 1
    assert st["collective-permute"]["count"] == 1          # -done skipped
    # bf16 correction halves only the f32 entries
    st2 = parse_collectives(hlo, bf16_model=True)
    f32_bytes = 32 * 4 * 2.0 + 4 * 8 * 4
    assert st2["total_bytes_bf16corr"] == pytest.approx(
        st2["total_bytes"] - f32_bytes / 2)


CELL_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.path.insert(0, %r)
    from repro.launch.dryrun import run_cell
    arch, shape, outdir = sys.argv[1], sys.argv[2], sys.argv[3]
    rec = run_cell(arch, shape, multi_pod=(sys.argv[4] == "multi"),
                   outdir=outdir, reduced=True)
    assert rec["flops_per_device"] > 0
    assert rec["memory"]["temp_bytes"] >= 0
    print("CELL_OK", json.dumps({k: rec[k] for k in
                                 ("arch", "shape", "mesh", "devices")}))
""" % os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.mark.parametrize("arch,shape,pod", [
    ("vit-s16", "serve_b128", "single"),
    ("tinyllama-1.1b", "decode_32k", "multi"),
    ("granite-moe-3b-a800m", "train_4k", "single"),
    ("dit-s2", "gen_fast", "multi"),
])
def test_reduced_cell_compiles_on_production_mesh(arch, shape, pod,
                                                  tmp_path):
    """REDUCED configs through the REAL 256/512-device dry-run path —
    exercises mesh building, sharding resolution, lower+compile, and
    artifact writing without the full-config compile times."""
    r = subprocess.run(
        [sys.executable, "-c", CELL_SCRIPT, arch, shape, str(tmp_path),
         pod], capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "CELL_OK" in r.stdout
    arts = list(os.listdir(tmp_path))
    assert len(arts) == 1
    with open(os.path.join(tmp_path, arts[0])) as f:
        rec = json.load(f)
    assert rec["devices"] == (512 if pod == "multi" else 256)
    assert "collectives" in rec
