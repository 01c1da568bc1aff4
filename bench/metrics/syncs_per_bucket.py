"""Blocking device-to-host reads the dispatcher made per bucket (its
``sync`` spans, by the bucket id they carry), over the buckets dispatched
in the window that it had finished when the spans were read."""
from bench import dispatcher


def read(run):
    bids = {s.get("bid") for s in run.spans_named("bucket")} - {None}
    per = {}
    for s in run.spans or ():
        if s["name"] == "sync" and s.get("parent") in dispatcher.WORK \
                and s.get("bid") in bids:
            per[s["bid"]] = per.get(s["bid"], 0) + 1
    if not per:
        return dispatcher.per_bucket(run, 0.0)
    return sum(per.values()) / len(per)
