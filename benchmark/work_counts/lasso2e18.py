"""Work one train step of the hashed Lasso learner takes AT MOST, per chip
(``lasso2e18``: MLlib's ``L1Updater``, whose iterations cannot run in the
Gram basis — ``twtml_tpu/models/sgd.py`` ``primal_basis``): a batch that
never meets the convergence tolerance and runs all ``numIterations`` rounds.

Bytes: the one-hot densify writes the ``[B, F]`` count matrix once and each
round reads it once (``u = C·w`` and ``∇ = Cᵀr`` from one pass over a block
of rows while it is on chip), at the width of the narrowest plane the
program has (s8, one byte an element; the benchmark's mix takes the bf16
plane by rung 2 of the gate), plus the packed wire:
``(1 + numIterations)·B·F`` + wire. The ``[F]`` weights and gradient (1 MiB
each, resident on chip across a pass) and the ``[B]`` vectors are left out.
At B = 2048, F = 2^18 and 50 rounds that is 27.4 GB => 33.4 ms of HBM,
memory-bound. FLOPs: a multiply and an add an element in each of the two
contractions, ``4·B·F`` a round — 0.1 TFLOP a batch, counted against the
bf16 peak and never binding (the contractions are matrix-VECTOR products:
they run on the vector unit).

This is NOT what the deployment needs, so ``step_roofline`` does not list
the cell: MLlib's loop BREAKS at convergence, the rounds a batch needs
follow its data (~9 of 50 on the benchmark's mix), and ``work`` is given
the configuration alone and cannot see them. A program that exits the loop
early would read ``step_roofline`` over 100% against this count. The
cell's roofline reader is ``layer_metrics/primal_pass_hbm_share.py``, which
counts the rounds the program says it ran before the freeze
(``needed_bytes``); a ``benchmark`` issue that hands ``work`` the run's
counters can put the cell on ``step_roofline`` with that count (PERF.md
section 7 row 27). A later PR that iterates on the batch's ACTIVE columns only reads less than
``B·F`` a round and makes both counts stale: it needs a ``benchmark``
issue first.
"""


def work(config: dict, chips: int, wire_bytes_per_batch: float) -> dict:
    m = config["model"]
    b = float(config["batch_rows"])
    f = float(m["numTextFeatures"])
    rounds = float(m["numIterations"])
    flops = 4.0 * b * f * rounds / chips
    nbytes = ((1.0 + rounds) * b * f + wire_bytes_per_batch) / chips
    return {"flops": flops, "bytes": nbytes, "peak": "bf16_flops"}
