"""The jitted predict-only program over a device-resident snapshot.

Parity by CONSTRUCTION, not by re-implementation: the predict program is the
fused train step built with ``num_iterations=0`` (``models/sgd.py
make_sgd_train_step``). The reference's predict-then-train ordering means the
train step's reported predictions are computed with PRE-update weights
(LinearRegression.scala:85-86) by exactly the prologue this program runs —
wire unpack, ragged re-pad + ASCII fold, device bigram hash, raw margin,
``prediction_fn``, HALF_UP rounding — and a zero-iteration ``fori_loop``
leaves the weights untouched (XLA drops the dead update). Serve-path
predictions are therefore BIT-identical to what the train step would report
for the same snapshot and batch (tests/test_serving.py asserts it), and every
future change to the prediction semantics lands on both paths at once.

``use_gram=False`` always: the Gram build (config #4's [B, B] matmul) exists
for the ITERATIONS, which serving never runs — with the scatter formulation
chosen and zero iterations, the whole training half is dead code and the
compiled program is predict + stats only. ``quality=False`` likewise (the
model-watch side channel is a training telemetry surface).

The tenant stack (PR 7, ``[M, F+4]``) serves through the same trick:
``TenantStackModel`` with zero-iteration steps — ONE ``lax.map``-mapped
program for all M tenants, host-side ``tenant_route_keys`` routing, one
stacked fetch; ``predictions_for`` re-orders the ``[M, B]`` output back to
original request rows via the recomputed deterministic route (the
aggregate_tenant_output rule).
"""

from __future__ import annotations

import numpy as np

from ..utils import get_logger

log = get_logger("serving.engine")


class PredictEngine:
    """Snapshot-resident predict program with the model surface
    ``apps/common.FetchPipeline`` drives (``step``/``pack_for_wire``/
    ``accepts_packed`` delegate to the underlying zero-iteration model).

    ``model_cls`` supplies the reference gradient-family knobs
    (``residual_fn``/``prediction_fn``/``round_predictions`` — linear by
    default, logistic serves the sentiment family); ``num_tenants`` > 1
    builds the stacked tenant program instead."""

    def __init__(
        self,
        num_text_features: int = 1000,
        num_tenants: int = 1,
        tenant_key: str = "hash",
        dtype=None,
        model_cls=None,
        use_sparse: "bool | None" = None,
    ) -> None:
        import jax.numpy as jnp

        from ..models.linear import StreamingLinearRegressionWithSGD

        model_cls = model_cls or StreamingLinearRegressionWithSGD
        dtype = jnp.float32 if dtype is None else dtype
        self.num_text_features = num_text_features
        self.num_tenants = int(num_tenants)
        if self.num_tenants > 1:
            from ..parallel.tenants import TenantStackModel

            self.model = TenantStackModel(
                self.num_tenants,
                num_text_features=num_text_features,
                num_iterations=0,  # predict-only: the whole update is dead
                dtype=dtype,
                residual_fn=model_cls.residual_fn,
                prediction_fn=model_cls.prediction_fn,
                round_predictions=model_cls.round_predictions,
                use_sparse=use_sparse,
                use_gram=False,  # G exists for iterations serving never runs
                tenant_key=tenant_key,
                quality=False,
            )
        else:
            self.model = model_cls(
                num_text_features=num_text_features,
                num_iterations=0,
                dtype=dtype,
                use_sparse=use_sparse,
                use_gram=False,
                quality=False,
            )
        self.snapshot_step = -1

    @classmethod
    def from_conf(cls, conf, num_tenants: int = 1, model_cls=None):
        import jax.numpy as jnp

        return cls(
            num_text_features=conf.numTextFeatures,
            num_tenants=num_tenants,
            tenant_key=getattr(conf, "tenantKey", "hash"),
            dtype=jnp.dtype(getattr(conf, "dtype", "float32")),
            model_cls=model_cls,
        )

    # -- snapshot state ------------------------------------------------------
    def set_snapshot(self, snapshot) -> None:
        """Install a snapshot's weights device-side. The zero-iteration step
        never changes them, so the device copy IS the snapshot until the
        next swap; callers swap only between dispatches (serving/plane.py),
        which is what makes the swap tear-free."""
        weights = np.asarray(snapshot.weights)
        want = 2 if self.num_tenants > 1 else 1
        if weights.ndim != want:
            raise ValueError(
                f"snapshot weights ndim {weights.ndim} does not fit a "
                f"{self.num_tenants}-tenant predict program"
            )
        self.model.set_initial_weights(weights)
        self.snapshot_step = int(snapshot.step)

    # -- FetchPipeline model surface ----------------------------------------
    @property
    def accepts_packed(self) -> bool:
        return bool(getattr(self.model, "accepts_packed", False))

    def step(self, wire):
        return self.model.step(wire)

    def pack_for_wire(self, batch):
        packer = getattr(self.model, "pack_for_wire", None)
        if packer is not None:
            return packer(batch)
        from ..features.batch import pack_batch

        return pack_batch(batch)

    # -- result extraction ---------------------------------------------------
    def predictions_for(self, host_out, batch) -> np.ndarray:
        """The fetched StepOutput's predictions re-ordered to the ORIGINAL
        batch rows, valid rows only ([n] float array). Single-model output
        is already row-ordered; the tenant stack's [M, rung] per-tenant-order
        output re-orders through the recomputed deterministic route exactly
        like ``aggregate_tenant_output`` (routing is host-side metadata —
        PARITY.md)."""
        mask = np.asarray(batch.mask) > 0
        if self.num_tenants == 1:
            return np.asarray(host_out.predictions)[mask]
        from ..features.batch import gather_tenant_predictions

        return gather_tenant_predictions(
            host_out.predictions, batch, self.model.route_ids(batch),
            self.num_tenants,
        )[mask]

    def tenant_row_counts(self, batch) -> "np.ndarray | None":
        """[M] valid-row counts this batch routed per tenant (None on the
        single-model plane) — the per-tenant query telemetry, recomputed
        host-side from the same deterministic route as the wire."""
        if self.num_tenants == 1:
            return None
        ids = np.asarray(self.model.route_ids(batch))
        valid = np.asarray(batch.mask) > 0
        return np.bincount(
            ids[valid], minlength=self.num_tenants
        ).astype(np.int64)
