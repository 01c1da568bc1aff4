"""Every cell of the real ``BENCHMARK.json`` resolves to the files a run
reads, and the ViT-H/14 configuration is the program's ViT-H/14, so a
slip in an entry or a configuration fails here rather than on the chip."""
import json
import os

import pytest

from bench import check, spec
from bench.sut import program_config

with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
with open(os.path.join(spec.BENCH_DIR, "configs", "vith14.json")) as _f:
    VITH14 = json.load(_f)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = spec.load_cell(name)
    for kind in ("reference", "flops"):
        spec.family(cell.config, kind)
    limits = os.path.join(spec.BENCH_DIR, "limits", f"{name}.json")
    assert os.path.isfile(limits)
    assert set(check.NUMBERS) <= set(cell.limits)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert os.path.isfile(os.path.join(spec.BENCH_DIR, "metrics",
                                           f"{m['name']}.py")), m["name"]
        assert callable(spec.reader(m["name"]))


def test_vith14_is_the_programs_vit_h14():
    from repro.configs import registry
    got, want = program_config(VITH14), registry.get("vit-h14")
    for field in ("n_layers", "d_model", "n_heads", "d_ff", "patch",
                  "img_res", "n_classes", "exit_layers", "exit_mlp_ratio"):
        assert getattr(got, field) == getattr(want, field), field
    assert got.n_exits == VITH14["n_exits"]


def test_vith14_flops_match_the_paper():
    """333.3 GFLOP an image (arXiv:2010.11929's ViT-H/14 at 224², 256
    patch tokens), the exits after layers 8/16/24 at a quarter, a half
    and three quarters of it."""
    flops = spec.family(VITH14, "flops")
    step = flops.step_flops(VITH14)
    assert step == pytest.approx(333.3e9, rel=1e-3)
    shares = [e / step for e in flops.exit_flops(VITH14)]
    assert shares[:3] == pytest.approx([0.25, 0.50, 0.75], abs=0.01)
    assert shares[3] == pytest.approx(1.0)
