"""Time per bucket, in ms, the dispatcher spent blocked in device-to-host
reads (its ``sync`` spans) in the window."""
from bench import dispatcher


def read(run):
    return dispatcher.per_bucket(
        run, 1e3 * sum(s["dur"] for s in dispatcher.syncs(run)))
