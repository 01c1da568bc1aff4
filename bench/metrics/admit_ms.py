"""Mean duration, in ms, of the program's ``admit`` spans in the window:
a request's admission on its client's thread (the copy of its images to
the device, the Eq. 8 difficulty program, the read of alpha, the queue
push)."""
import numpy as np


def read(run):
    spans = run.spans_named("admit")
    if not spans:
        return None
    return 1e3 * float(np.mean([s["dur"] for s in spans]))
