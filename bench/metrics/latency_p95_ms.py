"""95th percentile of request latency, from when each request was due to
its answer, over every request due in the window (one never answered
reads infinite)."""
from bench.record import p95


def read(run):
    return p95(run.latencies_ms())
