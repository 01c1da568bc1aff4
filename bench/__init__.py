"""DART serving benchmark: one command (run.py), data files found by name
(configs/, traffic/, metrics/), and the yardstick kept apart from the
program (reference/, flops/, peaks.json, trace_reduce.py, check.py)."""
