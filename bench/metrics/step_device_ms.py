"""Device time of one run of the masked step (the program ``jit_step``),
averaged over its runs in the traced window."""
import numpy as np


def read(run):
    runs = (run.trace.modules.get("jit_step") or []) if run.trace else []
    return float(np.mean(runs)) * 1e3 if runs else None
