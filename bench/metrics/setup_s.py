"""Process start to the first timed request: weights, calibration,
warm-up and, in a cold checkout, compilation."""


def read(run):
    return run.setup_s
