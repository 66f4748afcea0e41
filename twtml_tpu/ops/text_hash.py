"""On-device char-bigram HashingTF — featurization moved into the XLA program.

The host featurizer (features/hashing.py, native/fasthash.cpp) hashes bigram
strings on the CPU and ships (idx, count) pairs. That is host work per
tweet, while the hash itself is trivially vectorizable: MLlib's HashingTF index for a 2-char term is
``nonNegativeMod(javaStringHashCode(term), F)`` and Java ``String.hashCode``
of a 2-unit string is just ``31*c1 + c2`` over its UTF-16 code units
(max 31*65535 + 65535 < 2^31 — no wraparound, always non-negative). So the
wire format can be the padded code units themselves (uint16 — smaller than
the (idx, val) pairs) and the hash runs on device as two shifted loads, a
multiply-add, and a mod, fused by XLA into the same program as the SGD step.

Duplicate bigrams need no host-side aggregation: the learner's scatter-add
(`densify_text` / `sparse_grad_text`) turns per-occurrence 1.0 values into
exactly HashingTF's term-frequency counts, and the gather-dot predict path is
linear so occurrences sum identically.

Semantics matched to features/hashing.py (the ground truth, itself matched to
MllibHelper.scala:42-56 + Scala ``text.sliding(2)``):
- length ≥ 2: units [u0..u_{n-1}] → n−1 bigram terms, term j hashing to
  ``(31*u_j + u_{j+1}) % F``;
- length == 1: ``sliding(2)`` yields the whole 1-char string as the single
  window, hashing to ``u_0 % F``;
- length == 0: no terms (padding rows ride this case).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("hash")  # device stage name (models/sgd.py STAGE_SCOPES)
def hash_bigrams_device(units, length, num_features: int, dtype=jnp.float32):
    """[B, L] uint16 code units + [B] lengths → ([B, L-1] idx, [B, L-1] val).

    Padded unit slots (beyond each row's length) produce val 0.0 and idx 0,
    so the output plugs straight into `densify_text`/`sparse_predict`/
    `sparse_grad_text` in place of host-hashed token pairs.
    """
    u = units.astype(jnp.int32)
    c1, c2 = u[:, :-1], u[:, 1:]
    h = 31 * c1 + c2
    # sliding(2) on a single-unit string yields that string itself: the
    # row's one term hashes to u0 (Java hashCode of a 1-char string).
    h = h.at[:, 0].set(jnp.where(length == 1, u[:, 0], h[:, 0]))  # lawcheck: disable=TW004 -- fixed single-column update (static index 0), not a data-indexed scatter
    n_terms = jnp.where(length == 1, 1, jnp.maximum(length - 1, 0))
    valid = jnp.arange(h.shape[1], dtype=length.dtype)[None, :] < n_terms[:, None]
    token_idx = jnp.where(valid, h % num_features, 0)
    token_val = valid.astype(dtype)
    return token_idx, token_val
