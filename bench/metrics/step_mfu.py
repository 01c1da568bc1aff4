"""The masked step's share of the chip's peak: the FLOPs it computes (every
row of each bucket it ran, every stage and head, from
``bench/flops/<family>.py``) over its device time in the trace.  The
rows are the buckets of the program's ``bucket`` spans in the window."""


def read(run):
    if run.trace is None:
        return None
    seconds = sum(run.trace.modules.get("jit_step") or [])
    buckets = sorted(run.cell.config["buckets"])
    rows = sum(next((b for b in buckets if b >= s["n_samples"]),
                    s["n_samples"]) for s in run.spans_named("bucket"))
    if not seconds or not rows:
        return None
    flops = rows * run.flops.step_flops(run.cell.config)
    return 100.0 * flops / seconds / run.peaks["bf16_flops_per_s"]
