"""Mean exit index of the samples answered in the window (0 = first
exit)."""
import numpy as np


def read(run):
    done = run.answered_in_window()
    if not done:
        return None
    return float(np.mean(np.concatenate([r.result["exit_idx"]
                                         for r in done])))
