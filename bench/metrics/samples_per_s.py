"""Samples answered while the window was open, over its length."""


def read(run):
    return sum(r.n for r in run.answered_in_window()) / run.seconds
