"""Operation and byte counts kept with the benchmark: one module per
model family (``exit_flops``, ``step_flops``) and ``kernels`` for the
Pallas kernels."""
