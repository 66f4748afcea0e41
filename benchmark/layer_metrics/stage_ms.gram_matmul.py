"""Device time per batch in the step's ``gram_matmul`` stage: G itself, on whichever plane ran, and the numeric block added to it.
``benchmark/stage_times.py``: every nanosecond of the profile's ``XLA Ops``
line goes to the innermost operation covering it, an operation's stage is
the first ``jax.named_scope`` name on its op-name path, and the eight
``stage_ms.*`` sum to ``step_device_ms``."""

from benchmark import stage_times

read = stage_times.reader("gram_matmul")
