"""Shared learner contracts.

A streaming learner holds device-resident state (weights in HBM — unlike the
reference, which re-serializes driver weights into every batch closure,
LinearRegression.scala:57) and exposes one fused, jit-compiled
predict-then-train step per micro-batch: the incoming batch is scored with the
*pre-update* weights (progressive validation, the reference's explicit
ordering at LinearRegression.scala:85-86), per-batch statistics are reduced
on device, and the SGD iterations run inside the same XLA program.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp


class StepOutput(NamedTuple):
    """Device results of one micro-batch step. ``predictions`` keeps the full
    padded [B] vector (with ``mask`` deciding validity) so telemetry can ship
    the real-vs-pred series like the reference does to Lightning
    (SessionStats.scala:31-33); the scalars are the dashboard stats.

    ``quality`` (ISSUE 8, ``--modelWatch``) is the in-step model/data
    quality vector (ops/quality.QUALITY_FIELDS) — [Q] per batch, [M, Q]
    stacked on the tenant plane. It is a
    telemetry side channel riding the existing one-fetch-per-tick
    StepOutput transfer; ``None`` (an empty pytree — the default, and the
    ``--modelWatch off`` state) keeps the step program structurally
    identical to the pre-quality program.

    ``primal`` (``--l1Reg``: MLlib's ``L1Updater``, models/sgd.py
    ``primal_basis``) is ``[2]`` int32 for that learner alone — the
    iterations that ran before the converged-freeze, and the text weights
    that are exactly zero after the step — and ``None`` for every other,
    whose output pytree and program stay what they were."""

    predictions: jnp.ndarray  # [B] rounded predictions (pre-update weights)
    count: jnp.ndarray  # scalar — valid rows in this batch (global if psum)
    mse: jnp.ndarray  # scalar — mean((y - round(ŷ))²) over valid rows
    real_stdev: jnp.ndarray  # scalar — population stdev of labels
    pred_stdev: jnp.ndarray  # scalar — population stdev of rounded preds
    quality: Optional[jnp.ndarray] = None  # [QUALITY_WIDTH] side channel
    primal: Optional[jnp.ndarray] = None  # [2] int32: iterations, zero weights
