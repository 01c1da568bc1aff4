"""Samples dispatched over the capacity of the buckets they were padded
to, from the program's ``bucket`` spans (one per flush) in the window."""


def read(run):
    buckets = sorted(run.cell.config["buckets"])
    flushes = [s["n_samples"] for s in run.spans_named("bucket")]
    if not flushes:
        return None
    cap = sum(next((b for b in buckets if b >= n), n) for n in flushes)
    return 100.0 * sum(flushes) / cap
