"""The dispatcher's own host time per bucket, in ms: every phase but
``wait`` (select, gather, put, launch, fetch, fold, resolve) less the
device-to-host reads (``sync``) nested in them, summed over the window
and divided by the buckets dispatched in it."""
from bench import dispatcher


def read(run):
    busy = sum(s["dur"] for s in dispatcher.phases(run)) \
        - sum(s["dur"] for s in dispatcher.syncs(run))
    return dispatcher.per_bucket(run, 1e3 * busy)
