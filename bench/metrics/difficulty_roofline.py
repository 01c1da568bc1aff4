"""The difficulty kernel's share of its roofline: its least time on the
chip, from the bytes and operations of each call's (B, C, H, W) images
(``bench/flops/kernels.py``; bound by HBM bandwidth), over its device
time in the trace."""
from bench.flops import kernels


def read(run):
    calls = [k for k in (run.trace.kernels if run.trace else ())
             if k.name == "_difficulty_impl"]
    if not calls:
        return None
    peak = run.peaks
    least = 0.0
    for k in calls:
        _, (b, c, h, w), itemsize = k.operands[0]
        flops, nbytes = kernels.difficulty(b, h, w, c, itemsize)
        least += max(flops / peak["bf16_flops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / sum(k.seconds for k in calls)
