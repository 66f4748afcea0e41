"""Device time per batch with a collective of the mesh step in flight, on
the chip with the most of it: the union of the intervals of the ``XLA Ops``
events whose op-name path holds the program's ``collective`` scope
(``benchmark/collectives.py``; an asynchronous pair counts from its start
to its done), over the batches ``step_device_ms`` divides by. A PART of
``step_device_ms`` and of the ``stage_ms.*`` that hold the collectives, not
beside them. It is transfer AND waiting: the chip that reaches a collective
first sits in it until the last arrives (``chip_step_skew``). None where
the profile holds no event under the scope (one chip, or a program from
before the scope)."""

from benchmark import collectives

read = collectives.ms_per_batch
