"""Device time per batch in the step's ``writeback`` stage: W = c.W_prev + Z^T.alpha: the one scatter into the 2^18 weights.
``benchmark/stage_times.py``: every nanosecond of the profile's ``XLA Ops``
line goes to the innermost operation covering it, an operation's stage is
the first ``jax.named_scope`` name on its op-name path, and the eight
``stage_ms.*`` sum to ``step_device_ms``."""

from benchmark import stage_times

read = stage_times.reader("writeback")
