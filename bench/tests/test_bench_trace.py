"""``trace_reduce`` on a small trace recorded on a TPU v5e chip
(``record_trace.py``: resnet152.shallow, seed 7, a 0.25 s window; kept
gzipped, with the window and the program's host spans beside it)."""
import gzip
import json
import os
import shutil

import numpy as np
import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: the window and the program's host spans, wall-clock ns
SIDE = json.load(open(os.path.join(DATA, "resnet152_shallow.json")))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "t.xplane.pb")
    with gzip.open(os.path.join(DATA, "resnet152_shallow.xplane.pb.gz")) \
            as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def summary(trace):
    return trace_reduce.reduce(trace, SIDE["window"],
                               [tuple(h) for h in SIDE["host"]])


def _device_ops(trace):
    """The window and every XLA Ops interval of the TPU plane, in trace
    time, read independently."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace)
    env = next(p for p in pd.planes if p.name == "Task Environment")
    base = int(dict(env.stats)["profile_start_time"])
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    w0, w1 = (t - base for t in SIDE["window"])
    return (w0, w1), [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in ops.events]


def test_busy_time_is_the_union_of_device_ops(summary, trace):
    (w0, w1), ops = _device_ops(trace)
    assert summary.window_s == pytest.approx((w1 - w0) / 1e9)
    # rasterise at 100 ns: a bin is busy when any op covers its centre
    bins = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for s, e, _ in ops:
        a, b = (max(s, w0) - w0) / 100, (min(e, w1) - w0) / 100
        if b > a:
            bins[int(np.ceil(a - 0.5)):int(np.ceil(b - 0.5))] = True
    assert summary.busy_s == pytest.approx(bins.sum() * 1e-7, rel=0.01)
    assert 0 < summary.busy_s < summary.window_s


def test_kernels_and_programs_are_found(summary):
    gates = [k for k in summary.kernels if k.name == "_exit_gate_impl"]
    diff = [k for k in summary.kernels if k.name == "_difficulty_impl"]
    steps = summary.modules["jit_step"]
    assert steps and gates and diff
    # the masked step gates each of its four exits once
    assert abs(len(gates) - 4 * len(steps)) <= 8
    for k in gates:
        (dt, (b, v), size), _ = k.operands
        assert dt == "bf16" and size == 2 and v == 1000 and b in (8, 32)
    for k in diff:
        dt, (b, c, h, w), size = k.operands[0]
        assert dt == "f32" and (c, h, w) == (3, 224, 224) and 1 <= b <= 32
    assert all(k.seconds > 0 for k in summary.kernels)


def test_breakdown_is_bounded_and_sorted(summary):
    bd = summary.breakdown()
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(bd[key]) <= 10
        secs = [s for _, s in bd[key]]
        assert secs == sorted(secs, reverse=True)
    assert any(name.startswith("jit_step:") for name, _ in bd["device_ops"])
    idle = summary.window_s - summary.busy_s
    assert sum(s for _, s in bd["idle_gaps"]) <= idle + 1e-9
    assert all(name for name, _ in bd["idle_gaps"])


def test_kernel_of_parses_an_instruction():
    text = ('%_exit_gate_impl.3 = (f32[32,1]{1,0}, f32[32,1]{1,0}) '
            'custom-call(%l.1, %copy.1), '
            'custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={bf16[32,1000]{1,0}, '
            'f32[32,1]{1,0}}, frontend_attributes={kernel_metadata={}}')
    name, ops = trace_reduce.kernel_of(text)
    assert name == "_exit_gate_impl"
    assert ops == [("bf16", (32, 1000), 2), ("f32", (32, 1), 4)]
    assert trace_reduce.kernel_of("%fusion.1 = f32[8] fusion(...)") is None
