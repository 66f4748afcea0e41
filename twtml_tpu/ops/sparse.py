"""Sparse↔dense feature assembly on device.

The reference's feature vector is an MLlib SparseVector: hashed-bigram text
dims followed by 4 dense numeric dims (MllibHelper.scala:73-82). On TPU there
are two regimes:

- **dense path** (small numTextFeatures, e.g. the default 1004 total): scatter
  the padded (idx, val) pairs into a dense [B, F] matrix once per batch, then
  every SGD iteration is a [B,F]×[F] matmul on the MXU — the whole
  numIterations loop stays compute-dense.
- **sparse path** (numTextFeatures = 2^18, BASELINE config #4): the dense
  matrix would be ~1GB of mostly zeros; instead predictions gather weight
  entries (w[token_idx]·token_val) and gradients scatter-add residuals with
  one ``segment_sum`` per iteration. That is the scatter loop
  (``use_gram=False``) and the predict-only serving program; the Gram basis
  (ops/gram.py, the default at 2^18) contracts with its count matrix
  instead and keeps these kernels as its differential references.

Token pairs arrive either host-hashed (features/hashing.py, native/) or are
computed in-program from raw code units (ops/text_hash.py — the default
wire format); both feed these same kernels.

Padded token slots carry (idx=0, val=0.0) so they contribute nothing to
either path.
"""

from __future__ import annotations

import jax.numpy as jnp


def densify_text(token_idx, token_val, num_text_features):
    """[B, L] (idx, val) pairs → dense [B, F_text] term-frequency matrix."""
    b = token_idx.shape[0]
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], token_idx.shape)
    dense = jnp.zeros((b, num_text_features), dtype=token_val.dtype)
    return dense.at[rows, token_idx].add(token_val)  # lawcheck: disable=TW004 -- dense-model densify for small F_text; the 2^18 config routes to ops/gram.py (the measured cliff is the [B,2^18] scatter)


def sparse_text_dot(w_text, token_idx, token_val):
    """Σ_j w_text[idx_j]·val_j per row — the text half of the sparse dot.
    Shared by the single-device sparse path and the feature-sharded path
    (which calls it on slice-local indices with out-of-slice values zeroed,
    then psums partial dots over the model axis)."""
    gathered = jnp.take(w_text, token_idx, axis=0)  # [B, L]
    return jnp.sum(gathered * token_val, axis=1)  # [B]


def sparse_predict(w_text, w_num, token_idx, token_val, numeric):
    """ŷ = Σ_j w_text[idx_j]·val_j + numeric·w_num, no dense materialization.
    Equivalent to SparseVector dot (MLlib predict, LinearRegression.scala:57)."""
    return sparse_text_dot(w_text, token_idx, token_val) + numeric @ w_num


def sparse_grad_text(token_idx, token_val, residual, num_text_features):
    """∇_w_text Σ_i r_i·x_i = scatter-add of r_i·val_ij at idx_ij — the
    sparse half of the least-squares gradient (sum, not yet averaged)."""
    contrib = token_val * residual[:, None]  # [B, L]
    flat_idx = token_idx.reshape(-1)
    flat_contrib = contrib.reshape(-1)
    return jnp.zeros((num_text_features,), dtype=token_val.dtype).at[flat_idx].add(  # lawcheck: disable=TW004 -- the pre-Gram reference scatter: ground truth for the gram differential tests; use_gram routes the 2^18 config around it
        flat_contrib
    )
