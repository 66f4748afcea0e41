"""Device time per batch in the step's ``repad`` stage: the device-side re-pad of the ragged wire (ops/ragged.py: the gather into [B, L] and the ASCII fold).
``benchmark/stage_times.py``: every nanosecond of the profile's ``XLA Ops``
line goes to the innermost operation covering it, an operation's stage is
the first ``jax.named_scope`` name on its op-name path, and the eight
``stage_ms.*`` sum to ``step_device_ms``."""

from benchmark import stage_times

read = stage_times.reader("repad")
