"""Device time per batch in the step's ``gram_count`` stage: the plane gate and the count matrix: the one-hot build on the s8/bf16 planes; zero, scatter-densify and reshape on the exact plane.
``benchmark/stage_times.py``: every nanosecond of the profile's ``XLA Ops``
line goes to the innermost operation covering it, an operation's stage is
the first ``jax.named_scope`` name on its op-name path, and the eight
``stage_ms.*`` sum to ``step_device_ms``."""

from benchmark import stage_times

read = stage_times.reader("gram_count")
