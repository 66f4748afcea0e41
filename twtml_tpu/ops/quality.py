"""In-step model/data quality vector — the device half of the model
observability plane (ISSUE 8).

One fixed, small ``[QUALITY_WIDTH]`` f32 vector computed INSIDE the existing
fused predict-then-train step and appended as a new leaf of ``StepOutput``,
so it rides the ONE ``device_get`` per tick the pipeline already makes (no
added fetch; what the extra reductions cost on the chip is not measured —
PERF.md). Everything here is observation-only: no value feeds
back into the weights, the predictions, or the reported stats — the parity
law stands, and with the quality leaf disabled the step program is
structurally the pre-ISSUE-8 program (the leaf is ``None``, an empty
pytree).

Signals (layout pinned by ``QUALITY_FIELDS``; telemetry/modelwatch.py keys
off the names, tests key off the indices):

- ``weight_norm`` / ``update_norm``: ‖w_new‖₂ and ‖w_new − w_prev‖₂ — the
  EWMA inputs for the host-side loss-trend/step-health detectors;
- ``grad_norm``: L2 norm of the masked pre-update residual — the gradient
  in the dual (Gram) basis (run_dual_loop's ∂/∂α at iteration 1), the one
  gradient quantity every layout (dense, scatter, Gram) exposes without an
  extra pass over the 2^18 feature space;
- prediction / label / residual first+second moments (masked, population
  variance like ops/stats);
- per-column moments of the 4 dense numeric features (the drift detector's
  feature-shift inputs);
- ``bucket_occupancy`` / ``bucket_top_share``: a folded
  ``QUALITY_NBINS``-bin histogram of the hashed token mass — occupancy is
  the fraction of folded bins touched, top_share the largest bin's mass
  share (a collision/skew proxy for the hash-bucket space; computed as
  ``QUALITY_NBINS`` fused masked reductions, never a scatter — XLA
  serializes a [B·L]-update scatter, lawcheck TW004);
- ``gram_plane``: which plane ``ops/gram.text_gram``'s gate took for this
  batch's G — 0 exact, 1 bf16, 2 s8; −1 where the step is not in the Gram
  basis. The gate's own index, handed through; nothing recomputes it.

Every reduction takes the optional ``axis_name`` so the same code runs
single-device and data-parallel (psum over the mesh — all outputs are then
axis-invariant, which is also what shard_map's replicated-output check
requires).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .stats import _maybe_psum

# folded-histogram width: small enough that the one-hot reductions stay
# trivially cheap at bench shapes (B·L ~ 10^5–10^6 tokens), wide enough
# that occupancy/top-share move when the token distribution does
QUALITY_NBINS = 32

# the 4 dense numeric features (features/batch.NUM_NUMBER_FEATURES) —
# asserted at trace time below so the field layout can never silently skew
NUM_NUMERIC = 4

QUALITY_FIELDS = (
    "weight_norm",
    "update_norm",
    "grad_norm",
    "pred_mean",
    "pred_var",
    "label_mean",
    "label_var",
    "resid_mean",
    "resid_var",
    "num_mean_0",
    "num_mean_1",
    "num_mean_2",
    "num_mean_3",
    "num_var_0",
    "num_var_1",
    "num_var_2",
    "num_var_3",
    "bucket_occupancy",
    "bucket_top_share",
    "gram_plane",
)
QUALITY_WIDTH = len(QUALITY_FIELDS)
QUALITY_INDEX = {name: i for i, name in enumerate(QUALITY_FIELDS)}


def _tree_sq_sum(tree) -> jnp.ndarray:
    return sum(
        jnp.sum(leaf.astype(jnp.float32) ** 2)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def quality_vector(
    w_prev,
    w_new,
    *,
    residual,
    preds,
    labels,
    mask,
    numeric,
    token_idx,
    token_val,
    gram_plane=None,
    axis_name: str | None = None,
    weight_sq=None,
    update_sq=None,
) -> jnp.ndarray:
    """The ``[QUALITY_WIDTH]`` f32 quality vector for one micro-batch.

    ``residual`` is the masked pre-update residual (``residual_fn(raw, y) ·
    mask``); ``preds`` the reported (post-rounding) predictions; ``mask``
    the valid-row mask; all row-dimensioned inputs are shard-LOCAL under a
    data axis — the psums here make every output global, exactly like
    ``ops/stats.batch_stats``. Weights are replicated over any data axis,
    so their norms need no collective; a layout that shards the WEIGHTS
    (the feature-sharded step, parallel/sharding.py) hands in ``weight_sq``
    = ‖w_new‖² and ``update_sq`` = ‖w_new − w_prev‖² already reduced over
    its model axis. ``gram_plane`` is ``text_gram``'s plane index
    (axis-invariant already), None outside the Gram basis.

    What depends on the MODEL — ``residual``, ``preds``, ``weight_sq``,
    ``update_sq`` — may lead with a model axis (``[M, B]`` rows, ``[M]``
    norms: M arms on the same rows, parallel/sharding.py): every row
    reduction runs over the LAST axis, each psum carries the M sums at
    once, what depends on the batch alone (label and numeric moments, the
    bucket histogram) is computed once, and the result is ``[M,
    QUALITY_WIDTH]``. Without the axis the expression is what it always
    was."""
    f32 = jnp.float32
    m = mask.astype(f32)
    n = _maybe_psum(jnp.sum(m), axis_name)
    denom = jnp.maximum(n, 1.0)

    w_sq = _tree_sq_sum(w_new) if weight_sq is None else weight_sq
    upd_sq = sum(
        jnp.sum((a.astype(f32) - b.astype(f32)) ** 2)
        for a, b in zip(
            jax.tree_util.tree_leaves(w_new), jax.tree_util.tree_leaves(w_prev)
        )
    ) if update_sq is None else update_sq
    grad_sq = _maybe_psum(
        jnp.sum(residual.astype(f32) ** 2, axis=-1), axis_name
    )

    def moments(x):
        x = x.astype(f32)
        mean = _maybe_psum(jnp.sum(x * m, axis=-1), axis_name) / denom
        var = (
            _maybe_psum(jnp.sum(x * x * m, axis=-1), axis_name) / denom
            - mean * mean
        )
        return mean, jnp.maximum(var, 0.0)

    pred_mean, pred_var = moments(preds)
    label_mean, label_var = moments(labels)
    resid_mean, resid_var = moments(labels.astype(f32) - preds.astype(f32))

    if numeric.shape[1] != NUM_NUMERIC:
        raise ValueError(
            f"quality_vector pins {NUM_NUMERIC} numeric columns "
            f"(QUALITY_FIELDS layout); got {numeric.shape[1]}"
        )
    num = numeric.astype(f32)
    num_mean = _maybe_psum(jnp.sum(num * m[:, None], axis=0), axis_name) / denom
    num_sq = (
        _maybe_psum(jnp.sum(num * num * m[:, None], axis=0), axis_name) / denom
    )
    num_var = jnp.maximum(num_sq - num_mean * num_mean, 0.0)

    # folded hash-bucket histogram: QUALITY_NBINS masked reductions (each a
    # fused pass over the token buffer) — no [N, NBINS] one-hot intermediate
    # and no scatter; padding tokens carry zero token_val and padded rows
    # are masked, so only real token mass lands in the bins
    folded = jnp.bitwise_and(
        token_idx.reshape(-1).astype(jnp.int32), QUALITY_NBINS - 1
    )
    tv = (token_val.astype(f32) * m[:, None]).reshape(-1)
    bins = jnp.stack(
        [
            jnp.sum(jnp.where(folded == b, tv, 0.0))
            for b in range(QUALITY_NBINS)
        ]
    )
    bins = _maybe_psum(bins, axis_name)
    total = jnp.sum(bins)
    occupancy = jnp.mean((bins > 0).astype(f32))
    top_share = jnp.max(bins) / jnp.maximum(total, 1.0)

    fields = (
        [
            jnp.sqrt(w_sq),
            jnp.sqrt(upd_sq),
            jnp.sqrt(grad_sq),
            pred_mean,
            pred_var,
            label_mean,
            label_var,
            resid_mean,
            resid_var,
        ]
        + [num_mean[i] for i in range(NUM_NUMERIC)]
        + [num_var[i] for i in range(NUM_NUMERIC)]
        + [occupancy, top_share]
        + [jnp.asarray(-1.0 if gram_plane is None else gram_plane, f32)]
    )
    # scalars all, or some leading with the model axis: one [..., Q] vector
    return jnp.stack(jnp.broadcast_arrays(*fields), axis=-1).astype(f32)
