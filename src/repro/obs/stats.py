"""repro.obs.stats — the ONE stats() assembly shared by every engine.

``DartEngine``, ``ShardedDartEngine`` and ``LMDecodeEngine`` used to
each hand-build the same summary dict (served / exit_counts /
exit_frac / total_macs / mean_macs / requests) and the three copies had
started to drift.  They now all call:

    tel = ST.telemetry_totals(self.state, sharded=...)   # ONE reduction
    out = OBS_STATS.engine_summary(tel)                  # ONE key set
    ...engine-specific extras...
    # ONE percentile implementation, over the host request window:
    return OBS_STATS.attach_requests(out, self.request_window)

so key naming cannot drift again, and the obs adapters (which join the
tracer's host-side spans against exactly these reductions) read one
canonical shape.
"""
from __future__ import annotations

import numpy as np

__all__ = ["engine_summary", "attach_requests"]

#: keys every engine's stats() is guaranteed to carry
SUMMARY_KEYS = ("served", "exit_counts", "exit_frac", "total_macs",
                "mean_macs")


def engine_summary(telemetry: dict) -> dict:
    """Canonical serving summary from reduced telemetry totals (the
    output of :func:`repro.engine.state.telemetry_totals`)."""
    served = int(telemetry["served"])
    counts = np.asarray(telemetry["exit_counts"])
    total_macs = float(telemetry["total_macs"])
    return {"served": served,
            "exit_counts": counts,
            "exit_frac": counts / max(served, 1),
            "total_macs": total_macs,
            "mean_macs": total_macs / max(served, 1)}


def attach_requests(out: dict, window) -> dict:
    """Attach the request window's percentiles/miss-rate block (if any
    requests were recorded; ``window`` is the engine's
    :class:`repro.engine.state.RequestWindow`) — the single percentile
    implementation is :func:`repro.engine.state.latency_percentiles`."""
    req = window.stats()
    if req["requests"]:
        out["requests"] = req
    return out
