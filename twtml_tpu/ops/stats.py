"""Masked on-device batch statistics.

The reference computes per-batch count / MSE / stdev(real) / stdev(pred) as
separate RDD jobs with driver-side collects (LinearRegression.scala:56-65,
61-62 — its scalability cliff per SURVEY.md §2.5). Here all statistics are
fused into the training step and come back as a handful of scalars in the
step output; padding rows are excluded by the mask. ``RDD.stdev`` is the
population stdev (divide by n), reproduced here.

Every reduction takes an optional ``axis_name`` so the same code runs
single-device (jit) and data-parallel (shard_map with a psum over ICI).

Every reduction runs over the LAST axis, the rows: ``x`` may lead with a
model axis (``[M, B]``: M arms on the same rows, parallel/sharding.py)
against a ``[B]`` mask, and then each statistic of ``x`` leads with M and
each psum carries the M of them at once. A ``[B]`` input traces to the
expression it always did.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _maybe_psum(x, axis_name):
    if not axis_name:
        return x
    # the mesh steps' collectives carry one scope name, whatever stage
    # holds them (parallel/sharding.py)
    with jax.named_scope("collective"):
        return jax.lax.psum(x, axis_name)


def masked_sum(x, mask, axis_name=None):
    return _maybe_psum(jnp.sum(x * mask, axis=-1), axis_name)


def masked_count(mask, axis_name=None):
    return _maybe_psum(jnp.sum(mask, axis=-1), axis_name)


def masked_mean(x, mask, axis_name=None):
    n = masked_count(mask, axis_name)
    return masked_sum(x, mask, axis_name) / jnp.maximum(n, 1.0)


def masked_stdev(x, mask, axis_name=None):
    """Population standard deviation over valid rows (Spark RDD.stdev)."""
    mean = masked_mean(x, mask, axis_name)
    var = masked_mean(x * x, mask, axis_name) - mean * mean
    return jnp.sqrt(jnp.maximum(var, 0.0))


def batch_stats(labels, rounded_preds, mask, axis_name=None):
    """count, mse(y, rounded ŷ), stdev(y), stdev(ŷ) — the five dashboard
    numbers minus the cumulative count (kept by the driver, reference
    accumulator at LinearRegression.scala:51,60)."""
    count = masked_count(mask, axis_name)
    err = (labels - rounded_preds) * mask
    mse = masked_sum(err * err, jnp.ones_like(mask), axis_name) / jnp.maximum(count, 1.0)
    return {
        "count": count,
        "mse": mse,
        "real_stdev": masked_stdev(labels, mask, axis_name),
        "pred_stdev": masked_stdev(rounded_preds, mask, axis_name),
    }
