"""The harness end to end on the CPU at the tiny sizes (the look for a
chip skipped): a cell added as new files alone, the exit-mix
calibration, and the fp8 control coming out not correct."""
import hashlib
import io
import json
import os

import numpy as np
import pytest

from bench.tests.tiny import make_root, tiny_config, write_limits

from bench import run as R
from bench import spec

NEW_METRIC = '''"""Requests answered in the window."""


def read(run):
    return len(run.answered_in_window())
'''


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_a_cell_added_as_files_alone_runs(tmp_path):
    root = make_root(tmp_path, [("tiny.closed", "tiny_resnet",
                                 "tiny_closed")])
    before = _digests(root)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny_vit.json"), "w") as f:
        json.dump(tiny_config("tiny_vit"), f)
    with open(os.path.join(b, "traffic", "tiny_open.json"), "w") as f:
        json.dump({"loop": "open", "arrival": "poisson", "rate_rps": 60.0,
                   "arrival_seed": 1, "senders": 2, "sizes": [1, 3, 8],
                   "exit_shares": [0.4, 0.3, 0.2, 0.1]}, f)
    with open(os.path.join(b, "metrics", "answered_requests.py"), "w") as f:
        f.write(NEW_METRIC)
    write_limits(root, "tiny_vit.open")
    bench_json = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(bench_json))
    bench["configs"].append({"name": "tiny_vit",
                             "file": "bench/configs/tiny_vit.json"})
    bench["workloads"].append({"name": "tiny_vit.open", "config": "tiny_vit",
                               "traffic": "tiny_open", "chips": 1})
    bench["end_to_end"].append({"name": "answered_requests", "unit": "req",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["tiny_vit.open"]})
    json.dump(bench, open(bench_json, "w"))
    after = _digests(root)
    assert all(after[k] == v for k, v in before.items()
               if k != "BENCHMARK.json")

    cell = spec.load_cell("tiny_vit.open", root)
    assert cell.config["family"] == "vit" and cell.traffic["loop"] == "open"
    log = io.StringIO()
    result = R.run_cell(cell, 2**33 + 5, 1.5, False, require_tpu=False,
                        out=log)
    assert result["correct"], log.getvalue()
    assert result["metrics"]["answered_requests"]["value"] > 10
    assert "setup_s" in result["metrics"]
    assert list(result)[-1] == "compared"
    assert "compared pred_gap" in log.getvalue()


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("tiny"),
                     [("tiny.closed", "tiny_resnet", "tiny_closed")])
    return spec.load_cell("tiny.closed", root)


def test_calibration_hits_the_exit_shares(tiny_cell):
    _, pool, system, _ = R.prepare(tiny_cell, 21)
    try:
        exits = system._serve_all(pool)["exit_idx"]
    finally:
        system.close()
    got = np.bincount(exits, minlength=4) / len(exits)
    np.testing.assert_allclose(got, tiny_cell.traffic["exit_shares"],
                               atol=0.08)


def test_the_fp8_control_is_not_correct(tiny_cell):
    """The control, the fp8 reference put in the program's place, fails
    a limit that sound runs pass, on the sampled answers of a run."""
    params, pool, system, tau = R.prepare(tiny_cell, 8)
    try:
        _, _, reqs = R.drive(system, tiny_cell.traffic, 8, pool, 1.0)
    finally:
        system.close()
    limits = tiny_cell.limits
    program = R.reference_numbers(tiny_cell, params, pool, reqs, 8, tau)
    control = R.reference_numbers(tiny_cell, params, pool, reqs, 8, tau,
                                  control=True)
    assert all(program[k] <= limits[k] for k in limits), program
    assert any(control[k] > limits[k] for k in limits), control
