"""Streaming k-means with exponential forgetting.

TPU-native equivalent of MLlib's ``StreamingKMeans``/``StreamingKMeansModel``
as the reference's experimental entry configures it (KMeans.scala:69-73:
setK(3).setHalfLife(5, "batches").setRandomCenters(2, 0.0); manual per-batch
``latestModel.update(scaledData, decayFactor, timeUnit)`` at KMeans.scala:105).

MLlib update rule, reproduced inside one jit program:
  discount = decayFactor                  (timeUnit = batches)
           = decayFactor^numPoints        (timeUnit = points)
  n_j ← n_j·discount
  c_j ← (c_j·n_j + Σ_{x→j} x) / (n_j + count_j)
  n_j ← n_j + count_j
plus the dying-cluster rule: when the smallest cluster weight falls below
1e-8× the largest, the largest is split in two (±1e-14 perturbation) and the
smallest is replaced.

Assignment uses a [B,k] distance matrix and a one-hot matmul for the per-center
sums — k is small, B is the batch, both land on the MXU.

Data-parallel on a device mesh (``mesh=`` arg): batch rows are sharded over
the ``data`` axis and the per-center sums/counts/num_points become ``psum``s
over ICI — the same treeAggregate→psum translation as the SGD models
(parallel/sharding.py); centers/weights stay replicated, so the decay and
dying-cluster arithmetic is computed identically on every shard.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

BATCHES = "batches"
POINTS = "points"


def _sq_dists(points, centers):
    """[B,k] squared distances via the expanded form — one [B,D]×[D,k]
    matmul (MXU) instead of a [B,k,D] broadcast."""
    return (
        jnp.sum(points * points, axis=1, keepdims=True)
        - 2.0 * points @ centers.T
        + jnp.sum(centers * centers, axis=1)[None, :]
    )


def _update_step(centers, weights, points, mask, decay_factor, time_unit,
                 axis_name=None):
    """One streaming k-means batch update. centers [k,D], weights [k],
    points [B,D], mask [B]. Under shard_map, ``axis_name`` globalizes the
    batch reductions with psum; everything downstream of them is replicated
    arithmetic."""
    k = centers.shape[0]
    assign = jnp.argmin(_sq_dists(points, centers), axis=1)  # [B]
    onehot = jax.nn.one_hot(assign, k, dtype=points.dtype) * mask[:, None]  # [B,k]
    counts = jnp.sum(onehot, axis=0)  # [k]
    sums = onehot.T @ points  # [k, D]

    num_points = jnp.sum(mask)
    if axis_name:
        counts = lax.psum(counts, axis_name)
        sums = lax.psum(sums, axis_name)
        num_points = lax.psum(num_points, axis_name)
    if time_unit == BATCHES:
        discount = jnp.asarray(decay_factor, points.dtype)
    else:
        discount = jnp.asarray(decay_factor, points.dtype) ** num_points
    # an all-padding batch must be a STATE NO-OP: single-host callers skip
    # empty batches before update (apps/kmeans.py, KMeans.scala semantics),
    # but multi-host lockstep DISPATCHES them for collective alignment
    # (streaming/context.py) — no decay, no dying-cluster split
    discount = jnp.where(num_points > 0, discount, 1.0)

    n = weights * discount
    denom = jnp.maximum(n + counts, 1e-16)
    new_centers = (centers * n[:, None] + sums) / denom[:, None]
    # centers with no mass and no history keep their position
    new_centers = jnp.where((n + counts)[:, None] > 0, new_centers, centers)
    new_weights = n + counts

    # dying-cluster rule (MLlib StreamingKMeansModel.update tail)
    largest = jnp.argmax(new_weights)
    smallest = jnp.argmin(new_weights)
    max_w = new_weights[largest]
    min_w = new_weights[smallest]
    dying = (min_w < 1e-8 * max_w) & (num_points > 0)

    half = (max_w + min_w) / 2.0
    c_large = new_centers[largest]
    p = 1e-14 * jnp.maximum(jnp.abs(c_large), 1.0)
    split_centers = new_centers.at[largest].set(c_large + p).at[smallest].set(c_large - p)  # lawcheck: disable=TW004 -- 2-row update over K centers (tiny domain), the MLlib dying-cluster rule
    split_weights = new_weights.at[largest].set(half).at[smallest].set(half)  # lawcheck: disable=TW004 -- 2-row update over K centers (tiny domain), the MLlib dying-cluster rule

    new_centers = jnp.where(dying, split_centers, new_centers)
    new_weights = jnp.where(dying, split_weights, new_weights)
    return new_centers, new_weights, assign


class StreamingKMeans:
    def __init__(
        self,
        k: int = 2,
        decay_factor: float = 1.0,
        time_unit: str = BATCHES,
        mesh=None,
    ):
        self.k = k
        self.decay_factor = decay_factor
        self.time_unit = time_unit
        self.mesh = mesh
        self.num_data = 1 if mesh is None else mesh.shape[mesh.axis_names[0]]
        self.centers: jnp.ndarray | None = None
        self.cluster_weights: jnp.ndarray | None = None
        self._step = None
        self._step_config: tuple | None = None

    def _get_step(self):
        """(Re)build the jitted update when builder methods changed config."""
        cfg = (self.decay_factor, self.time_unit)
        if self._step is None or self._step_config != cfg:
            from functools import partial

            if self.mesh is None:
                self._step = jax.jit(
                    partial(_update_step, decay_factor=cfg[0], time_unit=cfg[1])
                )
            else:
                data_axis = self.mesh.axis_names[0]
                body = partial(
                    _update_step,
                    decay_factor=cfg[0], time_unit=cfg[1], axis_name=data_axis,
                )
                self._step = jax.jit(jax.shard_map(
                    body,
                    mesh=self.mesh,
                    # centers/weights replicated; rows sharded over 'data'
                    in_specs=(P(), P(), P(data_axis, None), P(data_axis)),
                    out_specs=(P(), P(), P(data_axis)),
                ))
            self._step_config = cfg
        return self._step

    # -- MLlib builder surface (KMeans.scala:69-73) --------------------------
    def set_k(self, k: int) -> "StreamingKMeans":
        self.k = k
        return self

    def set_decay_factor(self, a: float) -> "StreamingKMeans":
        self.decay_factor = a
        return self

    def set_half_life(self, half_life: float, time_unit: str) -> "StreamingKMeans":
        """decayFactor = exp(ln(0.5)/halfLife) — MLlib setHalfLife."""
        self.decay_factor = math.exp(math.log(0.5) / half_life)
        self.time_unit = time_unit
        return self

    def set_random_centers(
        self, dim: int, weight: float, seed: int = 0
    ) -> "StreamingKMeans":
        key = jax.random.PRNGKey(seed)
        self.centers = jax.random.normal(key, (self.k, dim), dtype=jnp.float32)
        self.cluster_weights = jnp.full((self.k,), weight, dtype=jnp.float32)
        return self

    def set_initial_centers(self, centers, weights) -> "StreamingKMeans":
        self.centers = jnp.asarray(centers, dtype=jnp.float32)
        self.cluster_weights = jnp.asarray(weights, dtype=jnp.float32)
        return self

    # -- streaming update ----------------------------------------------------
    def update(self, points, mask=None) -> np.ndarray:
        """One batch update; returns per-point cluster assignments."""
        points = jnp.asarray(points, dtype=jnp.float32)
        if mask is None:
            mask = jnp.ones((points.shape[0],), dtype=jnp.float32)
        else:
            mask = jnp.asarray(mask, dtype=jnp.float32)
        if self.centers is None:
            raise ValueError("call set_random_centers or set_initial_centers first")
        if points.shape[0] % self.num_data:
            raise ValueError(
                f"batch rows {points.shape[0]} not divisible by data shards "
                f"{self.num_data}; pad rows to a multiple of the mesh's data axis"
            )
        self.centers, self.cluster_weights, assign = self._get_step()(
            self.centers, self.cluster_weights, points, mask
        )
        if (
            isinstance(assign, jax.Array)
            and not assign.is_fully_addressable
        ):
            # multi-host mesh: each host gets ITS rows' assignments (the
            # rows it contributed — process-aligned data axis), in global
            # row order; per-row telemetry never crosses hosts
            from ..parallel.distributed import local_rows

            return local_rows(assign)
        return np.asarray(assign)

    def predict(self, points) -> np.ndarray:
        points = jnp.asarray(points, dtype=jnp.float32)
        return np.asarray(jnp.argmin(_sq_dists(points, self.centers), axis=1))

    @property
    def latest_centers(self) -> np.ndarray:
        return np.asarray(self.centers)
