"""Pallas TPU kernel: one PRIMAL iteration's pass over the count matrix —
``u = C·w``, the residual, ``∇ = Cᵀr`` — in ONE streamed read of C.

MLlib's ``L1Updater`` thresholds every coordinate of the weights, so the
iterate leaves ``span{w_prev, rows of C}`` and the Gram (dual) basis of
ops/gram.py has nothing to offer it (models/sgd.py ``primal_basis``): each
of the ``numIterations`` rounds needs both contractions with the batch's
``[B, k_hi, k_lo]`` count matrix itself. As two XLA fusions that is two
reads of C an iteration (``CountPlane.dot`` + ``.tdot``); here the rows go
by in blocks of ``ROWS`` (8 rows of bf16 C at 2^18 dims are 4 MiB)
and a block is used twice while it is resident in VMEM:

    u_blk = Σ_(hi,lo) f32(C_blk)·w              (phase 1, VPU)
    r_blk = residual_fn(u_blk + base_blk, labels_blk)·sel_blk
    ∇    += Σ_rows f32(C_blk)·r_blk             (phase 2, VPU)

``w`` and ``∇`` (``[k_hi, k_lo]`` f32, 1 MiB each at 2^18 dims) stay on
chip across the whole grid: ``w`` is an input every step maps to the same
block, ``∇`` the output every step maps to the same block (the grid axis
is ``arbitrary``: sequential, the accumulator written back once). ``base``
is what the caller adds to the text margin before the residual (the
numeric features' ``numeric·w_num``), ``sel`` the iteration's row
selection (the mask, or mask x Bernoulli sample). Nothing is rounded: C's
element is converted to f32 in registers (the counts are small integers,
exact on every plane), ``w`` and ``r`` are f32, every sum accumulates in
f32 — the products ``CountPlane.dot`` / ``.tdot`` form, summed in another
order (tile by tile; tests/test_l1_updater.py holds the two together).

Both phases walk the block in ``[rows, tile_h, tile_l]`` tiles so the
working set stays in vector registers: phase 1 keeps one f32 accumulator
tile a row and reduces it to the row's ``u`` once, at the block's end;
phase 2 loads a tile of ``∇`` once for all the block's rows. The per-row
vectors (``base``, ``labels``, ``sel`` in, ``r`` out) travel as
``[B / ROWS, ROWS, 128]`` arrays, a row's value repeated along
the lanes: one ``(8, 128)`` tile a block, and a ``[1, 1]`` slice of it is
the row's scalar in vector form (the residual function — a difference, or
a sigmoid — runs on the vector unit).

``interpret`` is the caller's explicit choice, as ops/pallas_sgd.py has it:
``CountPlane.primal_pass`` decides by the platform the step is LOWERED for
(``lax.platform_dependent``), so a chip run can not measure the interpreter,
and a test off the chip runs this same body, in the chip's own tiling,
through it.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROWS = 8  # rows a block: 4 MiB of bf16 C at 2^18 dims
# the narrowest tile each plane's type loads whole: (sublanes, lanes)
MIN_SUBLANES = {1: 32, 2: 16, 4: 8}


def tile_shape(dtype, k_hi: int, k_lo: int) -> tuple:
    """The ``[tile_h, tile_l]`` the phases walk a ``[k_hi, k_lo]`` row in:
    one native tile of C's type where the row holds whole ones, else the
    whole axis (matrices smaller than a tile: test sizes)."""
    th = MIN_SUBLANES[jnp.dtype(dtype).itemsize]
    return (th if k_hi % th == 0 else k_hi,
            LANES if k_lo % LANES == 0 else k_lo)


def _kernel(c_ref, w_ref, base_ref, y_ref, sel_ref, grad_ref, r_ref, *,
            residual_fn: Callable, tile: tuple):
    k_hi, k_lo = w_ref.shape
    th, tl = tile
    # a loop step is one tile ROW: ``th`` sublanes by all of ``k_lo``, its
    # tiles taken one after another (1.444 ms a pass at 2^18 dims where a
    # loop step a tile reads 1.845: PERF.md section 5)
    lanes = [pl.ds(k * tl, tl) for k in range(k_lo // tl)]

    @pl.when(pl.program_id(0) == 0)
    def _():
        grad_ref[...] = jnp.zeros_like(grad_ref)

    def sublanes(t):
        return pl.ds(pl.multiple_of(t * th, th), th)

    # phase 1: the block's rows of u, one accumulator tile a row
    def margin(t, acc):
        hs = sublanes(t)
        for ls in lanes:
            x = c_ref[:, hs, ls].astype(jnp.float32)
            acc = acc + x * w_ref[hs, ls][None]
        return acc

    acc = lax.fori_loop(
        0, k_hi // th, margin, jnp.zeros((ROWS, th, tl), jnp.float32)
    )
    residual = []
    for i in range(ROWS):
        row = slice(i, i + 1)
        u = jnp.sum(acc[i], keepdims=True)  # [1, 1]
        # the row's scalars, repeated along the lanes: [1, 128]
        r = residual_fn(
            u + base_ref[0, row, :], y_ref[0, row, :]
        ) * sel_ref[0, row, :]
        r_ref[0, row, :] = r
        # over the sublanes only (Mosaic has no one-step broadcast of a
        # [1, 1] over both); a tile wider than 128 lanes is a test's
        across = r[:, :tl] if tl <= LANES else r[:, :1]
        residual.append(jnp.broadcast_to(across, (th, tl)))

    # phase 2: ∇ += C_blkᵀ r_blk, a tile of ∇ loaded once for all rows
    def gradient(t, carry):
        hs = sublanes(t)
        for ls in lanes:
            g = grad_ref[hs, ls]
            for i in range(ROWS):
                g = g + c_ref[i, hs, ls].astype(jnp.float32) * residual[i]
            grad_ref[hs, ls] = g
        return carry

    lax.fori_loop(0, k_hi // th, gradient, 0)


@functools.cache
def _build(shape, dtype, residual_fn, interpret):
    b, k_hi, k_lo = shape
    kernel = functools.partial(
        _kernel, residual_fn=residual_fn, tile=tile_shape(dtype, k_hi, k_lo)
    )
    per_row = pl.BlockSpec((1, ROWS, LANES), lambda i: (i, 0, 0))
    whole = pl.BlockSpec((k_hi, k_lo), lambda i: (0, 0))
    block_bytes = ROWS * k_hi * k_lo * jnp.dtype(dtype).itemsize
    return pl.pallas_call(
        kernel,
        grid=(b // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, k_hi, k_lo), lambda i: (i, 0, 0)),
            whole, per_row, per_row, per_row,
        ],
        out_specs=(whole, per_row),
        out_shape=(
            jax.ShapeDtypeStruct((k_hi, k_lo), jnp.float32),
            jax.ShapeDtypeStruct((b // ROWS, ROWS, LANES), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two blocks of C in flight, w, ∇ and the per-row tiles
            vmem_limit_bytes=2 * block_bytes + 8 * k_hi * k_lo * 4 + (4 << 20),
        ),
        name="primal_pass",
        interpret=interpret,
    )


def primal_pass(c, w, base, labels, sel, *, residual_fn: Callable,
                interpret: bool):
    """``(∇ [k_hi, k_lo], r [B])`` of one iteration: ``r =
    residual_fn(C·w + base, labels)·sel`` and ``∇ = Cᵀr``, from one read
    of ``c`` (``[B, k_hi, k_lo]``, any plane's type, B a multiple of
    ``ROWS``). ``w`` has C's trailing shape; ``base``, ``labels`` and
    ``sel`` are ``[B]`` f32."""
    b = c.shape[0]
    if b % ROWS:
        raise ValueError(f"primal_pass: {b} rows are not blocks of {ROWS}")

    def lanes(v):
        return jnp.broadcast_to(
            v.astype(jnp.float32).reshape(b // ROWS, ROWS, 1),
            (b // ROWS, ROWS, LANES),
        )

    grad, r = _build(
        c.shape, jnp.dtype(c.dtype), residual_fn, bool(interpret)
    )(c, w.astype(jnp.float32), lanes(base), lanes(labels), lanes(sel))
    return grad, r[:, :, 0].reshape(b)
