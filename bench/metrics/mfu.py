"""Useful work's share of the chip's peak: each sample answered in the
window counts the FLOPs it needed to reach its exit
(``bench/flops/<family>.py``), over the window and the peak of
``bench/peaks.json``.  A step that stops computing exited rows raises
it; the masked step's all-rows work is ``step_mfu``."""
import numpy as np


def read(run):
    done = run.answered_in_window()
    if not done:
        return None
    per_exit = np.asarray(run.flops.exit_flops(run.cell.config), float)
    useful = sum(per_exit[r.result["exit_idx"]].sum() for r in done)
    return 100.0 * useful / run.seconds / run.peaks["bf16_flops_per_s"]
