"""What one run recorded, as the metric readers see it.

A reader (``bench/metrics/<name>.py``) gets a :class:`Run` and returns a
number, or None where the run holds nothing for it to read.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Run:
    cell: object                 # spec.Cell
    t0: float                    # window, on time.monotonic()
    t1: float
    requests: list               # load.Request, every one sent
    setup_s: float
    device: dict
    tau: np.ndarray
    spans: list | None = None    # repro.obs spans (traced runs)
    trace: object = None         # trace_reduce.Summary (traced runs)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def answered_in_window(self) -> list:
        """Requests answered while the window was open."""
        return [r for r in self.requests if r.result is not None
                and self.t0 <= r.done <= self.t1]

    def latencies_ms(self) -> np.ndarray:
        """Latency of every request due in the window, from when it was
        due; a request never answered reads infinite."""
        return np.asarray([(r.done - self.t0 - r.due) * 1e3
                           if r.result is not None else np.inf
                           for r in self.requests if r.due < self.seconds])

    def spans_named(self, name: str) -> list:
        """The program's spans of one name that began in the window."""
        return [s for s in self.spans or ()
                if s["name"] == name and self.t0 <= s["ts"] <= self.t1]

    @property
    def flops(self):
        from bench import spec
        return spec.family(self.cell.config, "flops")

    @property
    def peaks(self) -> dict:
        from bench import spec
        return spec.peaks(self.device["kind"])


def p95(values) -> float | None:
    """95th percentile, or None for an empty sample or one whose 95th
    percentile is a request that never came."""
    v = np.asarray(values, float)
    if not len(v):
        return None
    q = float(np.percentile(v, 95))
    return q if np.isfinite(q) else None
