"""Pallas TPU kernel: the fused dense streaming-SGD hot loop (reference
implementation; see the honest status note at the bottom).

The per-batch compute core (SURVEY.md §3.3 — numIterations of
predict→gradient→update on a [B, F] design matrix) runs as ONE pallas program
with the design matrix resident in VMEM for the entire loop: X is loaded from
HBM once, then every iteration's MXU products and VPU vector updates hit
on-chip memory only, instead of re-streaming X from HBM per iteration.

Design (the parts that make it actually lower on a real v5e — the round-1
version OOM'd scoped VMEM at the flagship 2048×1024 shape because the
``X^T r`` contraction materialized a second f32 copy of X):

- **Both orientations ship as inputs.** The kernel receives ``X`` [B, F] and
  ``XT`` [F, B] so the forward (``X·w``) and gradient (``X^T·r``) products are
  both canonical ``(((1,), (0,)), ((), ()))`` matvecs — no in-kernel
  transpose, no relayout copy. The enclosing jit builds ``XT`` with XLA.
- **bf16 storage, f32 accumulation.** X/XT live in VMEM as bfloat16 (half the
  footprint; both fit in ~8 MB at 2048×1024), every dot accumulates in f32
  (``preferred_element_type``). For this workload the text half of X holds
  small integer bigram counts — exact in bf16 — so the only storage error is
  on the 4 scaled numeric features; ``w``/``r`` are cast to bf16 per product,
  giving ~1e-4 relative weight error vs the f32 XLA path (tests pin it).
- **No mask ref.** Padded batches zero their padding rows (features/batch.py
  zeroes X rows and labels), so ``r = X·w − y`` is already 0 there; the
  selected count arrives as one SMEM scalar. This trims ~1 MB of
  lane-padded [B, 1] vectors — the difference between fitting and OOM.
- The iteration loop is a ``lax.fori_loop`` inside the kernel (sequential on
  one core — exactly the dependency chain SGD imposes anyway) with the same
  MLlib semantics as models/sgd.py ``sgd_inner_loop``: 1-indexed stepSize/√i,
  L2 pre-scale, zero-count skip, convergence tolerance with converged-freeze.

STATUS: nothing calls this kernel (round 1's ``use_pallas`` flag is gone):
at 2048x1024 the whole 50-iteration loop is a small fraction of a batch's
end-to-end time for BOTH the XLA-compiled loop and this kernel. It stays as
tested, hardware-lowerable reference code for the VMEM-resident pattern,
with semantics pinned against the XLA path (tests/test_pallas_sgd.py in
interpret mode, its only caller: ROADMAP D7). A
caller states ``interpret`` explicitly — the kernel never decides by itself
to run interpreted, so a chip run can not silently measure the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sgd_kernel(
    count_ref, x_ref, xt_ref, y_ref, w0_ref, wout_ref, preds_ref,
    *, num_iterations: int, step_size: float, l2_reg: float,
    convergence_tol: float,
):
    X = x_ref[:]    # [B, F] bf16 — stays in VMEM across the whole loop
    XT = xt_ref[:]  # [F, B] bf16
    y = y_ref[:]    # [B, 1] f32, already masked (padding rows are 0)
    w0 = w0_ref[:]  # [F, 1] f32
    count = count_ref[0]
    denom = jnp.maximum(count, 1.0)

    def matvec(w):  # [B, 1] f32
        return lax.dot_general(
            X, w.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def gradvec(r):  # [F, 1] f32
        return lax.dot_general(
            XT, r.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # predictions with pre-update weights (predict-then-train)
    preds_ref[:] = matvec(w0)

    def body(i, carry):
        w, converged = carry
        it = i + 1
        residual = matvec(w) - y  # padding rows: zero X row, zero y → 0
        grad = gradvec(residual) / denom
        eta = step_size / jnp.sqrt(jnp.float32(it))
        w_new = w * (1.0 - eta * l2_reg) - eta * grad
        w_new = jnp.where(count > 0, w_new, w)
        if convergence_tol > 0:
            delta = jnp.sqrt(jnp.sum((w_new - w) ** 2))
            norm_new = jnp.sqrt(jnp.sum(w_new * w_new))
            conv_now = (count > 0) & (
                delta < convergence_tol * jnp.maximum(norm_new, 1.0)
            )
        else:
            conv_now = False
        w_out = jnp.where(converged, w, w_new)
        return w_out, jnp.logical_or(converged, conv_now)

    w_final, _ = lax.fori_loop(
        0, num_iterations, body, (w0, jnp.array(False))
    )
    wout_ref[:] = w_final


# Scoped-VMEM model, calibrated against the Mosaic compiler's own accounting
# on v5e (hardware limit 16 MB): X+XT in bf16, the [·, 1] f32 vectors tiling
# to a full 128-lane stripe each (~512 B/row), plus the compiler's measured
# fixed overhead — at 2048×1024 Mosaic reports ~15.83 MB vs 14.7 MB for the
# first two terms, so the model carries that ~1.25 MB slack explicitly. The
# gate must track REAL usage: the round-1 kernel shipped a budget that
# approved shapes which then OOM'd at compile time on hardware.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024
_MOSAIC_OVERHEAD_BYTES = 1_310_720  # ~1.25 MB measured at the flagship shape


def _vmem_estimate(batch_rows: int, f_padded: int) -> int:
    matrix = 2 * batch_rows * f_padded * 2  # X + XT, bf16
    # ~6 lane-padded [rows, 1] f32 stripes (y, w, preds, residual, grad, tmp)
    vectors = 6 * max(batch_rows, f_padded) * 512
    return matrix + vectors + _MOSAIC_OVERHEAD_BYTES


def padded_lanes(num_features: int) -> int:
    """The kernel's own padding rule — single source of truth for callers."""
    return -(-num_features // 128) * 128


def supports(
    *, batch_rows: int, num_features: int, mini_batch_fraction: float, dtype
) -> bool:
    f_padded = padded_lanes(num_features)
    backend = jax.default_backend()
    return (
        backend in ("tpu", "cpu")  # cpu: interpret=True only; others can't lower
        and mini_batch_fraction >= 1.0
        and dtype == jnp.float32
        and batch_rows % 8 == 0
        and _vmem_estimate(batch_rows, f_padded) <= VMEM_LIMIT_BYTES
    )


@functools.cache
def _build(batch_rows, f_padded, num_iterations, step_size, l2_reg,
           convergence_tol, interpret):
    kernel = functools.partial(
        _sgd_kernel,
        num_iterations=num_iterations,
        step_size=step_size,
        l2_reg=l2_reg,
        convergence_tol=convergence_tol,
    )
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((f_padded, 1), jnp.float32),  # weights
            jax.ShapeDtypeStruct((batch_rows, 1), jnp.float32),  # raw preds
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # count
            pl.BlockSpec(memory_space=pltpu.VMEM),  # X (bf16)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # XT (bf16)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # y (masked)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # w0
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )


def fused_dense_sgd(
    x_dense,
    labels,
    mask,
    weights,
    *,
    num_iterations: int,
    step_size: float,
    l2_reg: float = 0.0,
    convergence_tol: float = 0.001,
    interpret: bool,
):
    """Run the fused loop on a dense [B, F] batch. ``weights`` is the flat
    [F] vector; F is padded to a lane multiple internally. Rows with
    mask == 0 MUST have zeroed features and labels (features/batch.py
    guarantees this for real batches; the call masks labels defensively).
    ``interpret`` is the caller's explicit choice: True runs the Pallas
    interpreter (any backend; what the CPU tests use), False compiles with
    Mosaic for the TPU. Returns (new_weights [F], raw_predictions [B])."""
    b, f = x_dense.shape
    f_padded = padded_lanes(f)
    if f_padded != f:
        x_dense = jnp.pad(x_dense, ((0, 0), (0, f_padded - f)))
        weights = jnp.pad(weights, (0, f_padded - f))
    mask = mask.astype(jnp.float32)
    # where, not multiply: garbage in masked rows may be NaN/Inf, and
    # NaN * 0 is NaN — it would poison every weight through the gradient
    x_dense = jnp.where(mask[:, None] > 0, x_dense, 0.0).astype(jnp.bfloat16)
    call = _build(
        b, f_padded, num_iterations, float(step_size), float(l2_reg),
        float(convergence_tol), bool(interpret),
    )
    w_out, preds = call(
        jnp.sum(mask).reshape(1),
        x_dense,
        x_dense.T,
        jnp.where(mask > 0, labels.astype(jnp.float32), 0.0)[:, None],
        weights.astype(jnp.float32)[:, None],
    )
    return w_out[:f, 0], preds[:, 0]
