"""The dispatcher's ``wait`` time in the window (nothing ready to flush
or to finish) over the buckets dispatched in it, in ms; 0 where it never
waited."""
from bench import dispatcher


def read(run):
    return dispatcher.per_bucket(
        run, 1e3 * sum(s["dur"] for s in dispatcher.phases(run, ("wait",))))
