"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read, with nothing but ``jax.profiler.ProfileData``.

* The window is given on the wall clock, as ``run.py`` records it
  (host tracing would slow the host it measures tenfold, so the trace
  holds the device alone); everything is clipped to it.  Event times
  count from the ``Task Environment`` plane's ``profile_start_time``.
* A device is a ``/device:TPU:<n>`` plane.  Its ``XLA Ops`` line holds
  one event per HLO instruction run, named by the instruction's text;
  its ``XLA Modules`` line one event per program run, named
  ``<jit name>(<fingerprint>)``.
* Busy time is the union of a device's ``XLA Ops`` intervals; idle gaps
  are the holes in that union.  The longest are each attributed to the
  host activity that overlaps them most, from the given host spans (the
  program's own, on the wall clock).
* A kernel is a ``tpu_custom_call`` instruction; its name is the
  instruction's (``_exit_gate_impl``, ``_difficulty_impl``) and its
  operand shapes come from ``operand_layout_constraints``.

Times are in seconds; a summary is averaged over the devices traced.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: idle gaps attributed per summary
TOP_GAPS = 10
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8}


@dataclasses.dataclass
class Kernel:
    name: str                 # e.g. "_exit_gate_impl"
    operands: list            # [(dtype, dims, itemsize)]
    seconds: float


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # per device, averaged
    modules: dict                       # jit name -> [seconds per run]
    kernels: list                       # Kernel, every device
    ops: dict                           # op label -> seconds, every device
    gaps: list                          # (seconds, host activity), longest

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[name, s] for s, name in gaps]}


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def kernel_of(op_text: str):
    """(name, operands) of a ``tpu_custom_call`` instruction, else None."""
    if 'custom_call_target="tpu_custom_call"' not in op_text:
        return None
    name = op_text.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]
    key = "operand_layout_constraints={"
    start = op_text.find(key)
    body, depth = "", 1
    for ch in op_text[start + len(key):] if start >= 0 else "":
        depth += (ch == "{") - (ch == "}")
        if not depth:
            break
        body += ch
    shapes = _SHAPE.findall(body)
    return name, [(dt, tuple(int(d) for d in dims.split(",") if d),
                   _ITEMSIZE.get(dt, 4)) for dt, dims in shapes]


def op_label(module: str, op_text: str) -> str:
    """A stable label for summing device time: the program, and the
    instruction's name without its number (the compiler names fusions
    after what they fuse, e.g. ``convolution_add_fusion``)."""
    head = op_text.split(" = ", 1)[0].lstrip("%")
    return f"{module}:{re.sub(r'[.]\d+$', '', head)}"


def _union(intervals):
    """Merged (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce(path: str, window, host=()) -> Summary:
    """``window``: (start, end) in wall-clock ns; ``host``: (start, end,
    name) spans of host activity in wall-clock ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    env = [p for p in planes if p.name == "Task Environment"]
    if not env:
        raise ValueError(f"no profile start time in {path}")
    base = int(dict(env[0].stats)["profile_start_time"])
    host_events = [(s - base, e - base, n) for s, e, n in host]
    w0, w1 = window[0] - base, window[1] - base
    devices = [p for p in planes if re.match(r"/device:TPU:\d+$", p.name)]
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    modules, kernels, ops, holes, busy = {}, [], {}, [], 0.0
    for dev in devices:
        lines = {ln.name: ln for ln in dev.lines}
        spans = []
        for ev in lines["XLA Modules"].events if "XLA Modules" in lines \
                else ():
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if e > s:
                name = ev.name.split("(", 1)[0]
                modules.setdefault(name, []).append((e - s) / 1e9)
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                              name))
        spans.sort()
        busy_iv = []
        k = 0
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if e <= s:
                continue
            busy_iv.append((s, e))
            while k + 1 < len(spans) and spans[k + 1][0] <= ev.start_ns:
                k += 1
            module = spans[k][2] if spans and spans[k][0] <= ev.start_ns \
                <= spans[k][1] else "?"
            text = ev.name
            label = op_label(module, text)
            ops[label] = ops.get(label, 0.0) + (e - s) / 1e9
            kern = kernel_of(text)
            if kern is not None:
                kernels.append(Kernel(kern[0], kern[1], (e - s) / 1e9))
        merged = _union(busy_iv)
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        holes += [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                  if g1 > g0]
    holes = sorted(holes, key=lambda g: g[0] - g[1])[:TOP_GAPS]
    gaps = [((g1 - g0) / 1e9, _host_activity(host_events, g0, g1))
            for g0, g1 in holes]
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy / len(devices),
                   modules=modules, kernels=kernels, ops=ops, gaps=gaps)


def _host_activity(events, g0, g1) -> str:
    """The host event that overlaps [g0, g1] most; the shortest wins a
    tie.  'host idle' when none does."""
    best, key = "host idle", (0, 0)
    for s, e, name in events:
        ov = min(e, g1) - max(s, g0)
        if ov > 0 and (ov, -(e - s)) > key:
            best, key = name, (ov, -(e - s))
    return best
