"""95th percentile of the scheduler's queue wait (the program's
``queue_wait`` spans, submit to dispatch) of requests submitted in the
window."""
from bench.record import p95


def read(run):
    waits = [s["dur"] * 1e3 for s in run.spans_named("queue_wait")]
    return p95(waits) if waits else None
