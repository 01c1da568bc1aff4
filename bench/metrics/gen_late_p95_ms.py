"""How late the load generator sent requests: 95th percentile of send
time minus due time over the window's requests."""
from bench.record import p95


def read(run):
    return p95([(r.sent - run.t0 - r.due) * 1e3 for r in run.requests
                if r.sent is not None])
