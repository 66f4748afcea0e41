"""Gram-domain (dual) SGD — the sparse inner loop re-expressed as MXU matmuls.

The 2^18-dim sparse regime (BASELINE config #4) was device-bound in r1/r2:
every one of the ``numIterations`` (50) rounds of MLlib's GradientDescent
loop (SURVEY.md §3.3) did a [B·L]-wide gather plus a scatter-add into the
2^18-entry weight vector — XLA lowers those to serialized scatter updates,
~100 ms/step on a v5e chip.

The fix is algebra, not a kernel. Within one micro-batch the design matrix
``Z = [X_text | numeric]`` is FIXED across all iterations; the loop only ever
needs ``Z·W`` (predictions) and ``Zᵀ·r`` (gradient). Re-parameterize the
weight trajectory in the span the updates actually live in:

    W_i = c_i · W_prev + Zᵀ · α_i          (c_0 = 1, α_0 = 0)

Then with ``u = Z·W_prev`` ([B], the batch's pre-update predictions) and
``G = Z·Zᵀ`` ([B,B], the Gram matrix):

    Z·W_i     = c_i·u + G·α_i              — a [B,B]×[B] matvec
    update    : c ← c·(1−ηλ);  α ← α·(1−ηλ) − η·(sel·r)/denom
    ‖W_a−W_b‖² = Δc²·‖W_prev‖² + 2·Δc·(u·Δα) + Δαᵀ·G·Δα

so MLlib's exact update rule — √-decay step, SquaredL2Updater pre-scale,
Bernoulli sampling, zero-sample skip, convergence freeze — runs unchanged
through ``sgd_inner_loop`` on the tiny dual state {c, α}, and the 2^18
feature space is touched only through ONE matrix, built once per batch:
the dense count matrix C of the text block (Z = [C | numeric]), ``B`` rows
of ``F`` features held as the ``[B, k_hi, k_lo]`` its build writes (below;
``CountPlane`` has why it is never reshaped to ``[B, F]``).
The step contracts with it three times — ``u_text = C·w_text`` for the
pre-update predictions (reduced in the epilogue of the product that writes
C: no read of it), ``G_text = C·Cᵀ`` on the MXU, and
``Δw_text = Cᵀ·α`` at write-back — each at most one streamed read of C,
whatever the row length L (a mesh step, which contracts only ITS rows
against all of them, has C built as two arrays — its rows, the rest — and
slices nothing: ``text_gram``); no ``[B, L]`` gather from the ``[F]`` weights
and no ``[B, L]`` scatter into them remains in the Gram basis (``ops/sparse.py``
keeps both for the scatter loop, for serving, and as the references of the
differential tests). The residual function enters only elementwise on
``Z·W``, so the same dual loop serves the logistic learner. Nothing here is
approximate: it is the same recursion in a different basis (floating-point
summation order differs; differential tests in tests/test_gram_sgd.py pin
both paths together).

The two vector contractions are multiply-and-reduce fusions in f32:
``Σ_f f32(C[b, f])·w[f]`` and ``Σ_b f32(C[b, f])·α[b]`` (``f`` running
over ``(hi, lo)``, ``w`` and the result taking C's shape), with C converted
element by element in registers (never as an f32 copy of a bf16 / s8
matrix) and ``w``, ``α`` left in f32. C's entries are the exact counts on
every plane (the gate's proof below), so ``C[b, f]·w[f]`` is the product
the gather formed after summing a row's duplicates; only the order of an
f32 sum differs. A default-precision ``dot`` would round ``w`` or ``α`` to
bf16 on the TPU and is not used (PERF.md §5 has the measurement against
the three-term MXU form).

Even the G build avoids scatters. XLA serializes a [B·L]-update scatter
into [B, 2^18] update by update (its cost on this machine: not measured,
PERF.md), so the dense count matrix is instead built as a batched
MXU matmul over a two-level split of the feature index, ``f = hi·K + lo``:

    C[b, hi, lo] = Σ_l val[b,l] · 1[hi_l = hi] · 1[lo_l = lo]
                 = (OHhiᵀ · diag(val) · OHlo)[hi, lo]       per row b

i.e. one ``[B, √F, L] × [B, L, √F]`` batched matmul (~0.07 TFLOP at
B=2048, F=2^18 — 3% of the G matmul itself), with 0/1 one-hot operands
that are exact in bf16 and f32 accumulation, so counts come out exact.
That ``[B, k_hi, k_lo]`` IS the count matrix from here on: G contracts it
over ``(hi, lo)`` on both operands, ``u`` and the write-back sum over the
same axes (``CountPlane``).

Exactness is gated at runtime, never assumed. ``text_gram`` picks one of
three planes from what it observes in the batch's [B, L] (idx, val) pairs —
never from the counts — and hands the index it took out with G:

  2  s8    integral values, every row's ABSOLUTE token mass ≤ 127;
  1  bf16  integral, bf16-representable values and either
           rung 1: every row's absolute mass ≤ 255 (one pass over the
                   values; every 20–140-unit text passes here), or
           rung 2, evaluated only when rung 1 fails: every (row, feature)
                   absolute mass ≤ 256 and every row's absolute mass
                   ≤ 65,536 (a row-wise sort of the pairs along L, a
                   cumulative sum and its difference at run boundaries);
  0  exact anything else — fractional values, one feature carrying more
           than 256 of a row's mass, a row of mass above 65,536: f32
           scatter densify + ``Precision.HIGHEST`` matmul.

Why the bf16 plane's G is the exact plane's, integer for integer:
(1) values: each is an integer bf16 holds, so the one-hot operands are
exact; (2) counts: a count is an integer whose magnitude — and every
partial sum of the one-hot build's f32 accumulation — is at most the
ABSOLUTE mass its row puts on that feature, ≤ 256, and bf16 holds every
integer up to 256 (absolute mass, because with mixed signs a plain sum can
hide a partial sum beyond that); a row's LENGTH does not enter: a 280-unit
tweet has 279 bigrams and would have to repeat one hashed bigram 257 times
to need the exact plane; (3) G: with every |count| ≤ 256,
Σ_f |c_af·c_bf| ≤ 256 · row_mass ≤ 256 · 65,536 = 2²⁴, so every partial
sum of the bf16×bf16→f32 product, in any order, is an integer of magnitude
≤ 2²⁴ and f32 holds it. Pad slots (idx 0, val 0.0) and the tokens a
feature-sharded caller clips to its slice edge with their value zeroed
carry no mass, so they cannot trip a gate that sums |val|.

Across feature slices (``feature_axis``: the 2-D step of
parallel/sharding.py, each shard holding ``F / m`` of the features) the
proof is per count matrix for (1) and (2) — a feature lives in exactly one
slice, so a shard's counts are the whole row's counts on its features —
and needs one more line for (3): G is the psum over the slices of their
partial Gs, each an integer matrix, and Σ over ALL slices of
Σ_f |c_af·c_bf| ≤ 256 · (GLOBAL row mass), so that psum is exact in f32
while the global row mass is ≤ 65,536 — a bound no shard can see in its
own slice. So the gate's inputs are reduced over ``feature_axis`` before
any rung is decided: the ``[B]`` row masses by a psum (each shard's is its
slice's share), ``integral`` and bf16-representable by a pmin (a value is
out of every slice but one, where it is zeroed), and rung 2's verdict — a
max over (row, feature), each pair in one slice — by a pmin of each
shard's own. Every rung then reads the whole row's figures, every shard
takes the SAME plane, and the index handed out is that one plane.

The s8 plane tightens the same ladder: row absolute mass ≤ 127 (true of
texts up to 128 units — per-occurrence 1.0 values) makes every count an
integer in [−127, 127], EXACT in int8, so both matmuls run s8×s8→s32 on
the MXU (twice the bf16 peak on v5e by specification, half the
count-matrix bytes) with integer accumulation: bit-exact, no rounding at
all.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .sparse import densify_text

# Above this dense-counts footprint (B·F·4 bytes) the Gram build would not
# fit comfortably in HBM next to the program's other buffers; the learner
# falls back to the per-iteration gather/scatter loop. 4 GB leaves >10 GB
# headroom on a 16 GB v5e chip for G, the bf16 planes, and the weights.
GRAM_DENSE_BYTES_LIMIT = 4 << 30

# Below this iteration count the Gram build (one densify scatter + one
# matmul) costs about as much as just running the scatter loop.
GRAM_MIN_ITERATIONS = 4


def fits_gram(batch_rows: int, f_text: int, num_iterations: int) -> bool:
    """Static-shape gate: use the Gram path when the dense counts matrix
    fits the HBM budget and there are enough iterations to amortize it.
    All inputs are trace-time constants, so this never recompiles."""
    return (
        num_iterations >= GRAM_MIN_ITERATIONS
        and batch_rows * f_text * 4 <= GRAM_DENSE_BYTES_LIMIT
    )


def _split_feature_index(token_idx, f_text: int):
    """The two-level split ``f = hi·k_lo + lo`` both one-hot count builders
    share — ONE definition so the planes cannot drift on feature layout."""
    lo_bits = (max(f_text - 1, 1).bit_length() + 1) // 2
    k_lo = 1 << lo_bits
    k_hi = -(-f_text // k_lo)
    return token_idx // k_lo, token_idx % k_lo, k_hi, k_lo


_ONEHOT_DIMS = (((1,), (1,)), ((0,), (0,)))  # contract over l, batch over b


def onehot_counts(token_idx, token_val, f_text: int, dtype=jnp.bfloat16):
    """[B, L] (idx, val) pairs → dense ``[B, k_hi, k_lo]`` ``dtype`` counts
    with NO scatter: the two-level one-hot batched matmul of the module
    docstring, feature ``f`` at ``[b, f // k_lo, f % k_lo]``. Accumulation
    is f32 regardless of ``dtype``; the output cast fuses into the matmul
    epilogue, so the bf16 default halves the write (and the downstream G
    matmul's read) vs an f32 count matrix.

    The result is NOT reshaped to ``[B, F]``: every reader contracts it over
    ``(hi, lo)`` as built (``CountPlane``). Where ``f_text < k_hi·k_lo`` the
    positions past ``f_text`` are zero, every index being ``< f_text``."""
    hi, lo, k_hi, k_lo = _split_feature_index(token_idx, f_text)
    oh_hi = (hi[:, :, None] == jnp.arange(k_hi, dtype=hi.dtype)).astype(
        jnp.bfloat16
    ) * token_val[:, :, None].astype(jnp.bfloat16)
    oh_lo = (lo[:, :, None] == jnp.arange(k_lo, dtype=lo.dtype)).astype(jnp.bfloat16)
    return lax.dot_general(
        oh_hi,
        oh_lo,
        _ONEHOT_DIMS,
        preferred_element_type=jnp.float32,
    ).astype(dtype)


def onehot_counts_int8(token_idx, token_val, f_text: int):
    """The int8 twin of ``onehot_counts``: [B, L] (idx, val) pairs → dense
    ``[B, k_hi, k_lo]`` int8 counts via the same two-level one-hot batched
    matmul, with s8 operands and s32 accumulation — integer-exact whenever
    the caller's gate holds (integral values, per-row absolute mass ≤ 127,
    so every count and every partial sum is an integer within range)."""
    hi, lo, k_hi, k_lo = _split_feature_index(token_idx, f_text)
    val_i8 = token_val.astype(jnp.int8)
    oh_hi = jnp.where(
        hi[:, :, None] == jnp.arange(k_hi, dtype=hi.dtype),
        val_i8[:, :, None],
        jnp.int8(0),
    )
    oh_lo = (lo[:, :, None] == jnp.arange(k_lo, dtype=lo.dtype)).astype(jnp.int8)
    return lax.dot_general(
        oh_hi,
        oh_lo,
        _ONEHOT_DIMS,
        preferred_element_type=jnp.int32,
    ).astype(jnp.int8)  # counts ≤ row mass ≤ 127: the narrowing is exact


def text_gram(
    token_idx,
    token_val,
    f_text: int,
    row_start=None,
    rows: int = 0,
    int8_plane: bool = True,
    feature_axis: str | None = None,
    *,
    body,
):
    """What ``body`` makes of the batch's count matrix, and the plane it was
    built on: ``(body's result, plane)``. With ``body=CountPlane.gram`` that
    is the text-feature Gram block G = X·Xᵀ ([B,B] f32), or the row panel
    ``X[row_start:row_start+rows]·Xᵀ`` ([rows, B]) when ``rows`` > 0 — the
    building block sharded layouts use (each shard computes its row panel
    and/or its feature slice's partial G, then all-gathers/psums) — and
    ``plane`` the int32 index the switch took: 2 s8, 1 bf16, 0 exact (the
    gate is computed here, once per step; ops/quality.py carries it out).

    A row panel is never CUT from a count matrix. With ``rows`` > 0 the
    ``[B, L]`` pairs are rolled by ``−row_start`` along the rows (8 MB at
    the cells' size, once, outside the switch) and the branch taken builds
    two arrays from static slices of them: the caller's ``rows`` rows and
    the ``B − rows`` after them, wrapping (``branch`` below has why).
    ``CountPlane`` contracts the first as built and rolls nothing larger
    than the ``[rows, B]`` panel back to the batch's order. The gate reads
    the pairs as given, so its collectives carry what they always did.
    With ``rows`` = 0 (every one-device caller) there is one array and no
    roll: the program is the one this function always traced.

    ``body(counts)`` is all the switch's branch does with the count matrix
    it built: it is called INSIDE the branch of the plane taken with that
    plane's ``CountPlane``, and its result (any pytree whose types do not
    depend on the plane) is what comes out. The train steps pass the whole
    Gram basis — ``counts.dot`` for ``u``, ``counts.gram()``, the dual
    loop, ``counts.tdot`` for the write-back — so that every contraction
    reads the ONE C of the batch (one array, or two under a row panel),
    live from its build to the write-back in the shape and layout the
    build wrote, and nothing typed by the plane has to leave the switch.
    Under a mesh the body's collectives run inside the branch: every shard
    enters the same one, because the index is reduced over every axis it
    could differ on before the switch.

    The gate ladder and its proof are the module docstring's. Every rung
    reads the [B, L] token pairs, never the counts: row absolute
    mass ≤ 127 ⇒ s8 (one s8×s8→s32 MXU matmul, bit-exact); ≤ 255 ⇒ bf16;
    a batch with a longer row (a 257–280-unit text) reaches rung 2, which
    sorts each row's pairs and takes the bf16 plane while no (row, feature)
    absolute mass exceeds 256 and no row's exceeds 65,536. Anything else —
    fractional values, a feature repeated past 256 within one row — takes
    the exact fallback: f32 scatter densify + full-f32
    (``Precision.HIGHEST``) matmul.

    ``feature_axis`` names the mesh axis the caller sliced the FEATURES
    over (``f_text`` is then the slice's width, the pairs hold only this
    slice's mass): the row masses (psum), the two value flags (pmin) and
    rung 2's verdict (pmin) are reduced over it, three small collectives
    under the ``collective`` scope, so that every shard takes the same
    plane on the whole row's figures (module docstring). G stays this
    slice's PARTIAL product; the caller psums it.
    """
    # device stage names (models/sgd.py STAGE_SCOPES): the three planes
    # share ``gram_count`` (the plane gate and the count matrix) and
    # ``gram_matmul``; which plane ran is the index handed out with G
    with jax.named_scope("gram_count"):
        val_f = token_val.astype(jnp.float32)
        integral = jnp.all(val_f == jnp.round(val_f))
        # ABSOLUTE mass: a plain sum would be unsound for mixed-sign values
        # (cancellation can hide a partial sum above the bf16 range)
        row_mass = jnp.sum(jnp.abs(val_f), axis=1)
        if feature_axis:
            # the whole row's mass, not this slice's (module docstring)
            with jax.named_scope("collective"):
                row_mass = lax.psum(row_mass, feature_axis)
        max_row_mass = jnp.max(row_mass)
        representable = jnp.all(
            val_f.astype(jnp.bfloat16).astype(jnp.float32) == val_f
        )
        if feature_axis:
            with jax.named_scope("collective"):
                integral, representable = lax.pmin(
                    jnp.stack([integral, representable]).astype(jnp.int32),
                    feature_axis,
                ).astype(bool)
        vals_bf16 = integral & representable
        # rung 1: row mass ≤ 255 bounds every count of the row with it
        rung1 = vals_bf16 & (max_row_mass <= 255.0)
        # row absolute mass ≤ 127 tightens every bound to the int8 range:
        # each |value| ≤ 127 (s8 operand) and each |count| ≤ 127 (s8 count
        # matrix)
        vals_ok_i8 = integral & (max_row_mass <= 127.0)

        def feature_mass_ok(i, v):
            """Rung 2: max over (row, feature) of Σ|val| ≤ 256, on the
            sorted [B, L] pairs. Integral values and row mass ≤ 65,536 <
            2²⁴ (the cond's predicate) keep the f32 cumsum exact; |val| ≥ 0
            keeps it monotone, so ``cummax`` carries each run's starting
            level to the run's end."""
            s_idx, s_abs = lax.sort(
                (i, jnp.abs(v)), dimension=1, num_keys=1, is_stable=False
            )
            level = jnp.cumsum(s_abs, axis=1)
            run_start = jnp.concatenate(
                [jnp.ones_like(s_idx[:, :1], bool), s_idx[:, 1:] != s_idx[:, :-1]],
                axis=1,
            )
            before_run = lax.cummax(
                jnp.where(run_start, level - s_abs, 0.0), axis=1
            )
            return jnp.max(level - before_run) <= 256.0

        def not_reached(i, v):
            # under shard_map both branches must vary over the same axes
            axes = tuple(sorted(jax.typeof(i).vma | jax.typeof(v).vma))
            no = jnp.zeros((), bool)
            return lax.pcast(no, axes, to="varying") if axes else no

        # 65,536 = 2²⁴ ÷ 256 bounds G's own f32 accumulation once a row's
        # mass is no longer bounded by 255 (module docstring, part 3)
        rung2 = lax.cond(
            vals_bf16 & ~rung1 & (max_row_mass <= 65536.0),
            feature_mass_ok,
            not_reached,
            token_idx,
            val_f,
        )
        if feature_axis:
            # every shard took the same branch (the predicate reads reduced
            # figures); a (row, feature) pair lives in one slice, so the
            # global maximum passes iff every slice's does
            with jax.named_scope("collective"):
                rung2 = lax.pmin(
                    rung2.astype(jnp.int32), feature_axis
                ).astype(bool)
        vals_ok = rung1 | rung2

        if rows:
            # this shard's rows first: a roll of the [B, L] PAIRS, so that
            # each branch builds its own rows' counts and the rest's as two
            # arrays and nothing of C's size is ever sliced (``branch``)
            token_idx, val_f = (
                jnp.roll(x, -row_start, axis=0) for x in (token_idx, val_f)
            )

    def branch(build, **product):
        def run(i, v):
            """The plane's count matrix, and ``body`` on it. Under a row
            panel it is BUILT as two arrays, the caller's own rows and the
            rest: the build is a batched product with the row as its batch
            dim, so the two cost what one costs, and the G product, ``dot``
            and ``tdot`` read the own rows' array as built. Rows sliced out
            of ONE C are a second array of the panel's size written every
            batch on the TPU: the G product contracts two dims and takes no
            dynamic slice as a fused operand (PERF.md §6, PR 54). The panel
            keeps the G MATMUL's FLOPs — and the bytes ``tdot`` streams —
            at 1/shards in sharded builds; the count build itself is
            deliberately replicated per shard — the right operand needs all
            B_global rows anyway, and all-gathering shard-local count
            builds would move [B_global, F_local] bf16 (~0.5 GB at the 2^18
            operating point) to save a build worth ~3% of the G matmul."""
            with jax.named_scope("gram_count"):
                # each array exact in the plane's type
                if rows:
                    c_own = build(i[:rows], v[:rows], f_text)
                    c_rest = build(i[rows:], v[rows:], f_text)
                else:
                    c_own, c_rest = build(i, v, f_text), None
            return body(CountPlane(c_own, c_rest, row_start, f_text, **product))

        return run

    idx = vals_ok.astype(jnp.int32)
    branches = [
        # f32 scatter densify, [B, F]
        branch(densify_text, precision=lax.Precision.HIGHEST),
        branch(onehot_counts, preferred_element_type=jnp.float32),
    ]
    if int8_plane:
        idx = idx + vals_ok_i8.astype(jnp.int32)  # i8-ok ⊆ bf16-ok: 0/1/2
        branches.append(
            branch(onehot_counts_int8, preferred_element_type=jnp.int32)
        )
    return lax.switch(idx, branches, token_idx, val_f), idx


class CountPlane:
    """One plane's dense count matrix inside its branch of ``text_gram``'s
    switch, as the three contractions the Gram basis runs with it (and the
    primal learner's one pass an iteration, ``primal_pass``). C is
    f32, bf16 or s8 by the plane and keeps, from its build to its last
    reader, the shape the build wrote: ``[B, k_hi, k_lo]`` from the one-hot
    builders (feature ``f`` at ``[hi, lo] = divmod(f, k_lo)``), ``[B, F]``
    from the exact plane's densify. It is never reshaped to ``[B, F]``:
    the builders write it tiled over ``(hi, lo)``, a ``[B, F]`` view is
    tiled over ``(b, f)``, and wherever a row slice sits between that
    reshape and its readers — every mesh step — the TPU's compiler makes
    the reshape a physical copy of all of C (2 GiB read + 2 GiB written a
    batch at ``hash2e20``; PERF.md §6, PR 30). So every contraction runs
    over ALL trailing axes of C as given, and the ``[F]`` vectors take C's
    shape instead: ``w`` is zero-padded to ``k_hi·k_lo`` going in and the
    write-back cropped to ``f_text`` coming out (C is zero past ``f_text``,
    every index being below it: the same sums).

    C comes as the arrays ``text_gram`` built: ``c_own``, the caller's
    rows — on one device every row, and then ``c_rest`` is ``None`` and
    each method traces to the one-array expression — and under a row panel
    ``c_rest``, the rows after them in rolled order (the batch's row
    ``row_start + rows + j``, wrapping, at ``j``). ``dot`` and ``tdot`` are
    this shard's and read ``c_own`` alone; ``gram`` reads both and is the
    one place ``row_start`` is used. No array of C's type is sliced,
    concatenated or rolled (on the TPU each would be a second array of
    that size written a batch: PERF.md §6, PR 54). Every result is f32.

    ``dot`` and ``tdot`` are multiply-and-reduce fusions with f32 operands
    (module docstring): C's element is converted in registers, ``w`` and
    ``alpha`` are never rounded, the accumulation is f32. Neither carries a
    stage name: the caller scopes ``dot`` under ``predict`` and ``tdot``
    under ``writeback`` (models/sgd.py ``STAGE_SCOPES``).

    Both take a leading MODEL axis (``w`` ``[M, F]`` → ``[M, rows]``,
    ``alpha`` ``[M, rows]`` → ``[M, F]``: M models on the same rows,
    models/sgd.py ``arms``), decided by the operand's ``ndim`` and nothing
    else; a 1-D operand traces to the expression it always did. The 2-D
    form is M SIBLING reductions, each the 1-D one, stacked — not one
    ``[M, …]`` reduction. The TPU's compiler fuses the siblings into one
    operation that reads C once (for ``dot`` the epilogue of the product
    that writes C: no read at all), whereas for one reduction with M
    leading it writes an f32 copy of C beside the plane's own (compiled for
    a v5e in PR 50: 2 GiB a batch at 2^18 dims, what PR 28 found of any
    f32 C); and each model's sum stays the single model's own."""

    # tests set this to run the primal pass's KERNEL, interpreted, inside a
    # whole step off the chip (``primal_pass``); no entry point does
    kernel_off_chip = False

    def __init__(self, c_own, c_rest, row_start, f_text: int, **product):
        self.c_own = c_own  # the caller's rows; on one device all of C
        self.c_rest = c_rest  # the rows after them, in rolled order, or None
        self._row_start = row_start  # where the own rows sit in the batch
        self._f_text = f_text
        self._product = product  # the G product's precision / result type
        self._features = tuple(range(1, c_own.ndim))

    def dot(self, w):
        """``rows(C)·w`` → ``[rows]``: the text half of ``u = Z·W_prev``
        for this shard's rows (a partial over its features under a
        feature axis; the caller psums). It reads the own rows' array
        alone and sits in the epilogue of the product that writes it: no
        read of C.

        ``w`` of shape ``[M, F]`` gives ``[M, rows]`` (class docstring):
        all M reductions ride that one epilogue."""
        if w.ndim == 2:
            return jnp.stack([self.dot(w_m) for w_m in w])
        shape = self.c_own.shape[1:]
        w = jnp.pad(w, (0, math.prod(shape) - w.shape[0])).reshape(shape)
        return jnp.sum(
            self.c_own.astype(jnp.float32) * w[None], axis=self._features
        )

    def tdot(self, alpha):
        """``rows(C)ᵀ·alpha`` → ``[F]``: the text half of ``Zᵀα`` from this
        shard's rows (the caller psums over the row shards). Duplicate
        (row, feature) occurrences are already summed in C, as the
        ``sparse_grad_text`` scatter summed them.

        ``alpha`` of shape ``[M, rows]`` gives ``[M, F]`` (class docstring):
        ONE pass over the panel for all M."""
        if alpha.ndim == 2:
            return jnp.stack([self.tdot(a_m) for a_m in alpha])
        panel = self.c_own.astype(jnp.float32)
        delta = jnp.sum(panel * jnp.expand_dims(alpha, self._features), axis=0)
        return delta.reshape(-1)[: self._f_text]

    def primal_pass(self, w, *, base, labels, sel, residual_fn):
        """One PRIMAL iteration's pass over C (models/sgd.py
        ``primal_basis``: MLlib's ``L1Updater``, whose iterate leaves the
        dual basis): ``r = residual_fn(C·w + base, labels)·sel`` and ``∇ =
        Cᵀr`` → ``(∇ [F], r [rows])``, both f32. ``w`` is the ``[F]`` text
        weights, ``base`` what the caller adds to the text margin (the
        numeric features' share), ``sel`` the round's row selection.

        On the planes the one-hot builders write (``[B, k_hi, k_lo]``, bf16
        or s8) and a TPU that is ONE streamed read of C: the Pallas kernel
        of ops/primal_pass.py takes the rows in blocks and runs both
        contractions while a block is on chip, ``w`` and ``∇`` resident in
        C's own ``[k_hi, k_lo]`` shape (``w`` zero-padded going in, ``∇``
        cropped coming out, as ``dot`` and ``tdot`` do). Anywhere else —
        the exact plane's ``[B, F]`` f32 densify (a batch no cell's text
        reaches), a batch that is not whole blocks of the kernel's 8 rows
        (a ``--batchBucket`` that is no multiple of 8), and every platform
        but the TPU — it is ``dot`` then ``tdot``: two reads. Which of the two a 3-D plane takes is decided
        where the step is LOWERED (``lax.platform_dependent``), never by
        asking which backend happens to be jax's default: a chip run cannot
        measure the fallback, and a CPU run does not crawl through the
        kernel's interpreter (6x the two fusions' time at 2^18 dims; the
        kernel's own tests run it interpreted, at sizes that allow it, and
        ``kernel_off_chip`` puts it into a whole step for them). The
        kernel's sums are ``dot``'s and ``tdot``'s own products in another
        order (tile by tile), to f32 rounding. One device only: it reads
        ``c_own`` as ALL of C."""
        if self.c_rest is not None:
            raise ValueError(
                "CountPlane.primal_pass under a row panel: the primal "
                "iteration has no mesh form (a [F] psum an iteration)"
            )

        def two_reads(w, base, labels, sel):
            r = residual_fn(self.dot(w) + base, labels) * sel
            return self.tdot(r), r

        from .primal_pass import ROWS, primal_pass

        if self.c_own.ndim != 3 or self.c_own.shape[0] % ROWS:
            return two_reads(w, base, labels, sel)

        shape = self.c_own.shape[1:]

        def one_read(interpret):
            def run(w, base, labels, sel):
                w = jnp.pad(w, (0, math.prod(shape) - w.shape[0]))
                grad, r = primal_pass(
                    self.c_own, w.reshape(shape), base, labels, sel,
                    residual_fn=residual_fn, interpret=interpret,
                )
                return grad.reshape(-1)[: self._f_text], r
            return run

        return lax.platform_dependent(
            w, base, labels, sel, tpu=one_read(False),
            default=one_read(True) if self.kernel_off_chip else two_reads,
        )

    def gram(self):
        """``rows(C)·Cᵀ`` → ``[rows, B]`` f32 on the MXU, exact on every
        plane (module docstring): one product contracting every feature
        axis of both operands — or, under a row panel, two, the own rows
        against themselves and against the rest, side by side and rolled
        back so that the columns are in the batch's order. Every entry is
        the integer sum the one product gave it."""
        with jax.named_scope("gram_matmul"):
            # s8 plane: |G| ≤ (Σ|c_a|)·max|c_b| ≤ 127² < 2²⁴, the s32 →
            # f32 casts are exact
            g = self._against(self.c_own)
            if self.c_rest is None:
                return g
            g = jnp.concatenate([g, self._against(self.c_rest)], axis=1)
            return jnp.roll(g, self._row_start, axis=1)

    def _against(self, c):
        """``rows(C)·cᵀ`` → ``[rows, rows of c]`` f32."""
        return lax.dot_general(
            self.c_own,
            c,
            ((self._features, self._features), ((), ())),
            **self._product,
        ).astype(jnp.float32)


@jax.named_scope("gram_matmul")
def add_numeric_block(g_text, numeric, dtype=jnp.float32):
    """G = g_text + N·Nᵀ, cast to the dual loop's dtype — the one place the
    numeric features enter G (shared by every layout so precision handling
    cannot drift between them)."""
    num = numeric.astype(jnp.float32)
    return (g_text + num @ num.T).astype(dtype)


def gram_matrix(
    token_idx,
    token_val,
    numeric,
    f_text: int,
    dtype=jnp.float32,
    int8_plane: bool = True,
):
    """G = Z·Zᵀ ([B,B] ``dtype``) for Z = [text counts | numeric features]."""
    return add_numeric_block(
        text_gram(
            token_idx, token_val, f_text, int8_plane=int8_plane,
            body=CountPlane.gram,
        )[0],
        numeric,
        dtype,
    )


def dual_norm_sq(p_prev, u, g):
    """‖W_a − W_b‖² evaluated in the dual basis — the ``norm_sq`` hook for
    ``sgd_inner_loop`` (convergence tolerance), given ``p_prev = ‖W_prev‖²``,
    ``u = Z·W_prev`` and the Gram matrix ``g``."""

    def norm_sq(a, b):
        dc = a["c"] - b["c"]
        da = a["alpha"] - b["alpha"]
        return dc * dc * p_prev + 2.0 * dc * jnp.dot(u, da) + jnp.dot(da, g @ da)

    return norm_sq
