"""The async dispatcher's time in a run's window, from the program's
``repro.obs`` spans.

The dispatcher thread's loop is tiled by exclusive phase spans (top
level on their thread, ``parent`` None): ``wait`` for work, then
``select``, ``gather``, ``put``, ``launch`` of a bucket and ``fetch``,
``fold``, ``resolve`` of a finished one.  Every blocking device-to-host
read is a ``sync`` span, child of the phase it blocks (or of ``admit``
on a client thread, which these sums leave out).  A program without
these spans (before they existed) gives empty lists, and each reader
then None.
"""

#: the phases that do work for a bucket (``wait`` is the rest)
WORK = ("select", "gather", "put", "launch", "fetch", "fold", "resolve")


def phases(run, names=WORK) -> list:
    """Top-level spans of ``names`` that began in the window."""
    return [s for name in names for s in run.spans_named(name)
            if s.get("parent", "") is None]


def syncs(run) -> list:
    """The dispatcher's ``sync`` spans in the window."""
    return [s for s in run.spans_named("sync") if s.get("parent") in WORK]


def buckets(run) -> int:
    """Buckets dispatched in the window."""
    return len(run.spans_named("bucket"))


def per_bucket(run, total):
    """``total`` over the buckets dispatched in the window; None where
    the run has no dispatcher phases or no bucket."""
    n = buckets(run)
    if not n or not phases(run):
        return None
    return total / n
