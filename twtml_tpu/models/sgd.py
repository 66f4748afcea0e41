"""Fused streaming-SGD step builder — the compute core of the framework.

This is the TPU re-expression of MLlib's ``GradientDescent.runMiniBatchSGD``
driven by ``StreamingLinearRegressionWithSGD.trainOn`` (the reference's hot
loop, SURVEY.md §3.3): per micro-batch, ``numIterations`` rounds of
  sample(miniBatchFraction) → gradient → reduce → w ← w − stepSize/√i · ∇
with the treeAggregate reduction replaced by an in-program ``psum`` over the
``data`` mesh axis when running sharded, and the whole loop compiled as one
XLA program (``lax.fori_loop``) so weights never leave HBM.

MLlib semantics preserved:
- per-iteration learning rate stepSize/√i, 1-indexed (SimpleUpdater);
- L2: w scaled by (1 − η·λ) before the gradient step (SquaredL2Updater) when
  l2_reg > 0 (the reference runs regParam 0; BASELINE config #4 adds L2);
- L1: the gradient step, then every coordinate soft-thresholded by η·λ
  (L1Updater, LassoWithSGD's) when l1_reg > 0 — the one updater that is
  not linear in w, so its iterations run in the feature space itself
  (``primal_basis`` below), never in the Gram basis;
- Bernoulli mini-batch sampling per iteration, seeded by iteration number
  (MLlib uses seed 42+i) — deterministic replay;
- convergence tolerance on successive weight vectors:
  ‖w_{i} − w_{i−1}‖₂ < tol · max(‖w_i‖₂, 1), early-stop;
- an iteration that samples zero points leaves weights unchanged;
- predictions for the batch are computed with pre-update weights
  (predict-then-train, LinearRegression.scala:85-86).

Two feature regimes (see ops/sparse.py): dense [B,F]×[F] MXU matmuls for
small models, gather/scatter for 2^18-dim hashed features.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..features.batch import (
    NUM_NUMBER_FEATURES,
    FeatureBatch,
    PackedBatch,
    RaggedUnitBatch,
    UnitBatch,
    unpack_batch,
)
from ..ops.gram import (
    add_numeric_block,
    dual_norm_sq,
    fits_gram,
    text_gram,
)
from ..ops.quality import quality_vector
from ..ops.ragged import ragged_repad
from ..ops.sparse import densify_text, sparse_grad_text, sparse_predict
from ..ops.stats import batch_stats
from ..ops.text_hash import hash_bigrams_device
from ..utils.rounding import jnp_round_half_up
from .base import StepOutput

# Above this text-feature count the dense [B, F] design matrix stops paying
# for itself and the gather/scatter path wins (2^18 dims ≈ 1 GB dense at B=1k).
DENSE_TEXT_FEATURE_LIMIT = 8192

MLLIB_SAMPLING_SEED = 42  # GradientDescent samples with seed 42+i

# The device stages of the train step, by name: ``jax.named_scope``s here
# and in ops/{ragged,text_hash,gram}.py, so a profile sums kernels by stage
# and not by HLO text that any edit renumbers (benchmark/stage_times.py
# reads them; PERF.md §3). Metadata only: the compiled program is the same
# with or without them. ``predict`` is the raw margin and the batch stats
# with the pre-update weights (in the Gram basis ``u = C·w``, inside the
# plane's branch; the gather elsewhere); ``gram_count`` the plane gate and
# the count matrix, ``gram_matmul`` G itself, ``writeback`` ``Cᵀα``, on
# whichever plane.
STAGE_SCOPES = (
    "unpack", "repad", "hash", "predict", "gram_count", "gram_matmul",
    "dual_loop", "writeback", "quality",
)


def sgd_inner_loop(
    weights,
    *,
    num_iterations: int,
    step_size: float,
    mini_batch_fraction: float,
    l2_reg: float,
    convergence_tol: float,
    mask,
    sample_key,
    grad_and_count: Callable,
    norm_sq: Callable | None = None,
    vary_axis: str | None = None,
    l1_reg: float = 0.0,
    count_iterations: bool = False,
):
    """The MLlib GradientDescent iteration loop over an arbitrary weight
    pytree — the ONE place the parity-critical semantics live (1-indexed
    eta = stepSize/√i, the updater's rule, Bernoulli sampling,
    zero-sample skip, convergence test on successive weight vectors,
    converged-freeze). Both the single-device step below and the
    feature-sharded step (parallel/sharding.py) drive it.

    The update rule is the run's ONE updater (``mllib.optimization.
    Updater``): ``SimpleUpdater`` / ``SquaredL2Updater`` — ``w·(1 − η·λ₂) −
    η·∇/n``, the first being the second at ``l2_reg`` 0 — or, with
    ``l1_reg`` > 0 (a Python number: it picks the traced body),
    ``L1Updater.compute`` to the letter: ``w' = w − η·∇/n``, then
    ``sign(w')·max(|w'| − η·λ₁, 0)`` on EVERY leaf and coordinate (MLlib
    thresholds the whole vector, the numeric weights too). With ``l1_reg``
    0 the traced body is the one this loop always had.
    ``count_iterations`` returns ``(weights, iterations)``: the rounds
    that ran before the converged-freeze (MLlib's loop breaks there), an
    int32 scalar carried beside the flag; False (every dual and scatter
    caller) leaves the carry, hence the program, as it was.

    ``grad_and_count(w, sel)`` must return (gradient-sum pytree, selected
    count), already globally reduced across any mesh axes. ``norm_sq(a, b)``
    returns the global ‖a−b‖² for convergence (default: local sum over
    leaves; sharded layouts pass a psum-ing version). ``vary_axis`` marks
    the loop carry as varying over a manual mesh axis — required when the
    body consumes axis-varying values (e.g. an all-gathered batch) whose
    varying-ness would otherwise mismatch the constant-initialized carry.
    """
    dtype = jax.tree_util.tree_leaves(weights)[0].dtype

    if l1_reg > 0:
        def update(eta, denom):
            def rule(wl, gl):  # L1Updater.compute
                stepped = wl - eta * gl / denom
                return jnp.sign(stepped) * jnp.maximum(
                    jnp.abs(stepped) - eta * l1_reg, 0.0
                )
            return rule
    else:
        def update(eta, denom):  # SimpleUpdater / SquaredL2Updater
            return lambda wl, gl: wl * (1.0 - eta * l2_reg) - eta * gl / denom

    if norm_sq is None:
        def norm_sq(a, b):
            return sum(
                jnp.sum((la - lb) ** 2)
                for la, lb in zip(
                    jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
                )
            )

    def body(i, carry):
        w, converged, *ran = carry
        it = i + 1  # MLlib iterations are 1-indexed
        if mini_batch_fraction < 1.0:
            sel = mask * jax.random.bernoulli(
                jax.random.fold_in(sample_key, it), mini_batch_fraction, mask.shape
            ).astype(dtype)
        else:
            sel = mask
        grad_sum, count = grad_and_count(w, sel)
        denom = jnp.maximum(count, 1.0)
        eta = step_size / jnp.sqrt(jnp.asarray(it, dtype))
        w_new = jax.tree_util.tree_map(update(eta, denom), w, grad_sum)
        # zero sampled points → no update (MLlib warns and skips)
        w_new = jax.tree_util.tree_map(
            lambda nl, wl: jnp.where(count > 0, nl, wl), w_new, w
        )
        if convergence_tol > 0:
            delta = jnp.sqrt(norm_sq(w_new, w))
            norm_new = jnp.sqrt(
                norm_sq(w_new, jax.tree_util.tree_map(jnp.zeros_like, w_new))
            )
            # a zero-sample iteration is a skip, not convergence
            conv_now = (count > 0) & (
                delta < convergence_tol * jnp.maximum(norm_new, 1.0)
            )
        else:
            conv_now = jnp.array(False)
        w_out = jax.tree_util.tree_map(
            lambda wl, nl: jnp.where(converged, wl, nl), w, w_new
        )
        # a round that starts unfrozen ran (MLlib breaks AFTER the round
        # that converged)
        ran = [n + (~converged).astype(jnp.int32) for n in ran]
        return (w_out, converged | conv_now, *ran)

    converged0 = jnp.array(False)
    if vary_axis:
        weights = jax.tree_util.tree_map(
            lambda x: lax.pcast(x, vary_axis, to="varying"), weights
        )
    # A loop carry must enter varying over every manual mesh axis it leaves
    # varying over. The converged flag enters varying over the axes EVERY
    # weights leaf varies over: ``vary_axis`` once they are cast, and e.g.
    # 'model' with tenants sharded over it (each shard's tenants converge
    # on their own; no psum makes the flag invariant). Over such an axis
    # the flag cannot widen any weights carry, so the cast is always safe;
    # an axis only SOME leaves vary over (feature-sharded text vs numeric)
    # is the caller's to reduce in ``norm_sq``. Outside shard_map the set
    # is empty, nothing is cast, and the single-device program is unchanged.
    flag_axes = tuple(sorted(frozenset.intersection(*(
        jax.typeof(leaf).vma for leaf in jax.tree_util.tree_leaves(weights)
    ))))
    if flag_axes:
        converged0 = lax.pcast(converged0, flag_axes, to="varying")
    ran0 = (jnp.zeros((), jnp.int32),) if count_iterations else ()
    w_final, _, *ran = lax.fori_loop(
        0, num_iterations, body, (weights, converged0, *ran0)
    )
    return (w_final, ran[0]) if count_iterations else w_final


def run_dual_loop(
    *,
    u,
    g,
    labels,
    mask,
    dtype,
    residual_fn: Callable,
    num_iterations: int,
    step_size: float,
    mini_batch_fraction: float,
    l2_reg: float,
    convergence_tol: float,
    p_prev,
    vary_axis: str | None = None,
):
    """MLlib's iteration loop in the Gram (dual) basis — the ONE dual-state
    driver both the single-device sparse step (``_gram_sgd`` below) and the
    feature-sharded step (parallel/sharding.py) call, so the parity-critical
    construction (state init, grad shape, sampling key, convergence norm)
    cannot de-synchronize between layouts.

    All row-dimensioned inputs (``u = Z·W_prev``, ``labels``, ``mask``, and
    G's rows) are GLOBAL; under a mesh the loop runs replicated on every
    shard — it is [B]-sized, and collective-free. Sampling draws ONE global
    mask with the unfolded MLlib key, bit-matching the single-device
    trajectory (the scatter loop's per-shard folded keys only match it
    statistically — ``sampling_key`` docstring). Returns the dual state
    {'c', 'alpha'}: W_new = c·W_prev + Zᵀα (write-back is layout-specific).
    """

    def grad_and_count(w, sel):
        raw = w["c"] * u + g @ w["alpha"]
        residual = residual_fn(raw, labels) * sel
        return {"c": jnp.zeros((), dtype), "alpha": residual}, jnp.sum(sel)

    with jax.named_scope("dual_loop"):
        return sgd_inner_loop(
            {"c": jnp.ones((), dtype), "alpha": jnp.zeros(labels.shape, dtype)},
            num_iterations=num_iterations,
            step_size=step_size,
            mini_batch_fraction=mini_batch_fraction,
            l2_reg=l2_reg,
            convergence_tol=convergence_tol,
            mask=mask,
            sample_key=sampling_key(None, mini_batch_fraction),
            grad_and_count=grad_and_count,
            norm_sq=dual_norm_sq(p_prev, u, g),
            vary_axis=vary_axis,
        )


def dual_scale_and_alpha(dual, axis_name: str, rows: int):
    """This shard's slice of the dual state for a sharded write-back:
    (c, α_local). The psum-mean of c turns the identical-everywhere scale
    into a statically-invariant value (shard_map's replicated-output check),
    and slicing α to local rows keeps the write-back contraction 1/shards.
    The M arms' stacked state (``c`` ``[M]``, ``alpha`` ``[M, B]``:
    ``arms_dual_half`` on a mesh) is cut on its row axis, the last, and its
    scales ride the ONE psum."""
    alpha = dual["alpha"]
    alpha_local = lax.dynamic_slice_in_dim(
        alpha, lax.axis_index(axis_name) * rows, rows, axis=alpha.ndim - 1
    )
    with jax.named_scope("collective"):
        c = lax.psum(dual["c"], axis_name) / lax.axis_size(axis_name)
    return c, alpha_local


def arms_dual_half(
    counts,
    *,
    w_text,
    w_num,
    numeric,
    mapped,
    dual: Callable,
    dtype,
    sum_features: Callable = lambda part: part,
    all_rows: Callable = lambda raw: raw,
    own_rows: Callable = lambda duals: (duals["c"], duals["alpha"]),
    sum_rows: Callable = lambda delta: delta,
):
    """The per-arm half of the Gram basis for M arms on the SAME rows
    (``--tenantKey all``), inside the branch of the plane taken, on its ONE
    count matrix ``counts`` — the ONE place it exists: the single-device
    step below and the feature-sharded step (parallel/sharding.py) both
    call it, after they built G their own way.

    ``u = C·[w_1…w_M]`` for all arms from one expression at the branch's
    top level (scope ``predict``: the count build's epilogue takes it; the
    numeric half is the single model's own matvec, an arm); the dual loop
    ``lax.map``ped over the arms under the scope ``arm_map`` —
    ``dual(*mapped_m, u_m)`` is one arm's ``run_dual_loop`` under its own
    step size and L2, G shared through the closure, and ``mapped`` what it
    takes per arm besides ``u``; then the write-back ``c_m·W_m + Zᵀα_m``
    for all arms in ONE pass over C (scope ``writeback``). Nothing under
    ``arm_map`` is also under ``predict`` or ``writeback``: the readers of
    the benchmark count on it.

    The four hooks are the reductions a mesh needs, identities on one
    device: ``sum_features`` the ``[M, rows]`` text partials over the
    feature shards, ``all_rows`` this shard's ``[M, rows]`` of ``u`` to
    every row (ONE array for all arms), ``own_rows`` the mapped duals to
    ``(c [M], α [M, rows])`` of this shard's rows
    (``dual_scale_and_alpha``), ``sum_rows`` a write-back delta over the
    row shards — each ONE collective with an ``[M, ·]`` payload, none
    inside the map. ``w_text`` is ``[M, F]`` (this shard's slice of it),
    ``w_num`` ``[M, 4]``, ``numeric`` this shard's rows. Returns ``((new
    text weights, new numeric weights), this shard's rows of u)``."""
    with jax.named_scope("predict"):
        raw = (
            sum_features(counts.dot(w_text))
            + jnp.stack([numeric @ w for w in w_num])
        ).astype(dtype)
        u = all_rows(raw)
    with jax.named_scope("arm_map"):
        # each arm's own loop and converged-freeze, G shared
        duals = lax.map(lambda args: dual(*args), (*mapped, u))
    with jax.named_scope("writeback"):
        # W_new = c·W_prev + Zᵀα for all arms: ONE read of C
        c, alpha = own_rows(duals)
        c = c[:, None]
        delta_text = sum_rows(counts.tdot(alpha))
        delta_num = sum_rows(jnp.stack([numeric.T @ a for a in alpha]))
        return (w_text * c + delta_text, w_num * c + delta_num), raw


def sampling_key(axis_name: str | None, mini_batch_fraction: float):
    """MLlib-compatible sampling key (seed 42, GradientDescent's 42+i), with
    the data-shard index folded in under shard_map so shards draw independent
    masks. Sampled subsets therefore differ between mesh layouts (as they do
    between Spark partitionings) but are statistically equivalent;
    fraction=1.0 (the default) is exact."""
    key = jax.random.PRNGKey(MLLIB_SAMPLING_SEED)
    if axis_name and mini_batch_fraction < 1.0:
        key = jax.random.fold_in(key, lax.axis_index(axis_name))
    return key


def make_sgd_train_step(
    *,
    num_text_features: int,
    num_iterations: int,
    step_size: float,
    mini_batch_fraction: float = 1.0,
    l2_reg: float = 0.0,
    convergence_tol: float = 0.001,
    residual_fn: Callable | None = None,
    prediction_fn: Callable | None = None,
    axis_name: str | None = None,
    use_sparse: bool | None = None,
    round_predictions: bool = True,
    use_gram: bool | None = None,
    quality: bool = False,
    arms: bool = False,
    l1_reg: float = 0.0,
):
    """Build the fused (weights, batch) → (new_weights, StepOutput) step.

    ``residual_fn(raw, label)`` is the per-example gradient multiplier
    (identity diff for least-squares; σ(raw) − y for logistic), and
    ``prediction_fn(raw)`` maps the raw margin to the reported prediction.
    The returned function is pure and jit/shard_map-composable; wrap with
    ``jax.jit(..., donate_argnums=0)`` to keep weights HBM-resident.

    The inner loop is always the XLA-compiled ``sgd_inner_loop``. A
    VMEM-resident pallas variant exists as reference code
    (ops/pallas_sgd.py, semantics pinned by tests) but is deliberately NOT a
    knob here: nothing calls it. On the v5e it compiles and runs (PERF.md
    §5 has the one isolated-loop timing); whether the inner loop is where
    a batch's time goes is not measured, so ROADMAP D7 decides its fate.

    In the sparse regime the iterations run in the dual (Gram) basis by
    default (ops/gram.py): one MXU matmul builds G = Z·Zᵀ per batch and the
    loop never touches the 2^18 feature space (the per-iteration
    gather/scatter formulation it replaced is gone; no comparison exists on
    this machine). The whole basis runs inside the branch of the plane the
    gate takes, on that plane's ONE count matrix C, built first: the
    pre-update raw margin ``u = C·w_text + numeric·w_num`` (``raw``,
    ``preds``, the batch stats and the quality vector read this ``u``), G,
    the dual loop, and the write-back ``c·W_prev + [Cᵀα | numericᵀα]`` —
    two streamed reads of C where a ``[B, L]`` gather from and a ``[B, L]``
    scatter into the weights used to be (``sparse_predict`` /
    ``sparse_grad_text`` remain the scatter loop's, serving's, and the
    differential tests' references). With a data axis the
    batch is all-gathered once (G needs cross-shard row products), each
    shard contracts its row panel of C — its rows of ``u`` (all-gathered),
    its panel of G (matmul FLOPs scale 1/shards; one all-gather replicates
    G), its share of ``Cᵀα`` (psum) — and the tiny dual loop runs
    replicated with NO per-iteration collectives — versus one gradient psum
    per iteration (50/batch) in the scatter loop. The collectives are the
    ones the gather/scatter form ran (one all-gather each of the batch's
    arrays, of ``u`` and of the G panels; one psum each of the two
    write-back deltas and of ``c``; the plane pmin): none added, none
    resized. ``use_gram`` False forces the scatter loop
    (the differential baseline); None picks Gram whenever it applies (f32
    weights, dense counts within HBM budget — ops/gram.py ``fits_gram``).

    ``quality`` (ISSUE 8) appends the in-step quality vector
    (ops/quality.py) as ``StepOutput.quality`` — weight/update/gradient
    norms and data moments computed inside this same XLA program, riding
    the existing one-fetch StepOutput. Observation-only: weights,
    predictions, and the five reference stats are bit-identical with it on
    or off, and ``False`` (the default / ``--modelWatch off``) leaves the
    output pytree — hence the compiled program — structurally the
    pre-quality program (the leaf is None).

    ``arms`` (``--tenantKey all``: a champion and its challengers on the
    SAME rows) builds the step of M models that share their batch:
    ``weights`` is ``[M, F+4]``, ``step_size`` and ``l2_reg`` are ``[M]``
    (arm m's own recipe), and every leaf of the output leads with M. What
    does not depend on the model runs ONCE — unpack, re-pad, hash and, in
    the Gram basis, the count matrix C and G = C·Cᵀ — and so does every
    READ of C: ``u = C·[w_1…w_M]`` comes out of the count build for all
    arms at once (``CountPlane.dot`` on ``[M, F]``: the build's epilogue,
    as the single model's) and ``Cᵀ·[α_1…α_M]`` is ONE pass
    (``CountPlane.tdot`` on ``[M, B]``), both at the top level of the
    plane's branch. Only the dual loop is ``lax.map``ped over the arms,
    under the scope ``arm_map`` (G shared, arm m's step size and L2, its
    own converged-freeze), and after the switch the quality vector; the
    arms' batch stats are mapped under ``predict`` alone. Arm m IS this
    step built without ``arms`` under arm m's recipe — every sum it runs is
    the single model's own expression, a sibling of the other arms' — to
    float32 rounding in the Gram basis (where a compiler may fuse siblings
    and order a sum otherwise: PARITY.md, "the arm law") and bit for bit
    outside it, where the featurized batch is shared and the whole loop is
    mapped (tests/test_tenant_grid.py). This builder's ``arms`` step is the
    one-device one: there is no ``axis_name`` (data-only mesh) form of it.
    Across chips the arms run on the mesh WITH a model axis — the
    feature-sharded step (parallel/sharding.py ``arms``), which calls the
    same per-arm half, ``arms_dual_half``, with its reductions handed in.

    ``l1_reg`` > 0 (``--l1Reg``: MLlib's ``L1Updater``) is the learner whose
    iterations CANNOT run in the dual basis: the soft threshold acts on
    every coordinate, after which ``w_t`` is no longer in ``span{w_prev,
    rows of C}`` and there is no ``{c, α}``. Inside the same branch of the
    same gate, on the same ONE count matrix, ``primal_basis`` stands in
    ``dual_basis``'s place: the pre-update margin under ``predict`` as
    above (the count build's epilogue), then ``sgd_inner_loop`` over the
    pytree ``(w_text [F], w_num [4])`` under the scope ``primal_loop``,
    every round of which reads C once (``CountPlane.primal_pass``: ``u =
    C·w``, the residual and ``∇ = Cᵀr`` while a block of rows is on chip;
    scope ``primal_pass``). No G, no ``gram_matmul``, no ``writeback``:
    the loop's carry IS the new weights. ``primal_loop`` is a scope of
    ``arm_map``'s kind, not a tenth of ``STAGE_SCOPES`` (the benchmark's
    stage readers put it under ``other``; its own readers carry their
    reduction). The step's output then carries ``StepOutput.primal``:
    the rounds that ran before the freeze and the count of text weights
    that are exactly zero after the step. Outside ``fits_gram`` (no dense
    C) the same updater runs through the scatter loop. One device and one
    model: a data axis would need a ``[F]`` psum an iteration and the arms'
    per-arm half is dual by construction — both are refused here and, in
    a sentence, at the entry point (apps/common.build_model). With
    ``l1_reg`` 0 the output pytree, hence every standing program, is what
    it was.
    """
    if l1_reg and not arms and l2_reg:
        raise ValueError(
            "l1_reg with l2_reg: MLlib's GradientDescent runs ONE updater "
            "(L1Updater or SquaredL2Updater), not an elastic net"
        )
    if l1_reg and (arms or axis_name):
        raise ValueError(
            "l1_reg (MLlib's L1Updater) runs on one device, one model: "
            + ("the arms' per-arm half (arms_dual_half) is the DUAL half by "
               "construction and the soft threshold has no dual form"
               if arms else
               "under a data axis every iteration would psum a [F] gradient "
               "(50 a batch) and that pass has no form yet")
        )
    if arms and axis_name:
        raise ValueError(
            "arms on the same rows under a data axis alone: this builder's "
            "arms step runs on one device, and the mesh that runs them has "
            "a model axis (--modelShards; parallel/sharding.py). The "
            "data-only mesh has no form of the per-arm half"
        )
    f_text = num_text_features
    sparse = f_text > DENSE_TEXT_FEATURE_LIMIT if use_sparse is None else use_sparse
    residual_fn = residual_fn or (lambda raw, label: raw - label)
    prediction_fn = prediction_fn or (lambda raw: raw)

    def _predict_raw(weights, batch: FeatureBatch, x_dense):
        if sparse:
            return sparse_predict(
                weights[:f_text],
                weights[f_text:],
                batch.token_idx,
                batch.token_val,
                batch.numeric.astype(weights.dtype),
            )
        return x_dense @ weights

    def _grad_sum(batch: FeatureBatch, x_dense, residual):
        if sparse:
            g_text = sparse_grad_text(
                batch.token_idx, batch.token_val, residual, f_text
            )
            g_num = residual @ batch.numeric.astype(residual.dtype)
            return jnp.concatenate([g_text, g_num])
        return x_dense.T @ residual

    def _gram_sgd(weights, row_args, local_numeric):
        """The sparse step in the dual basis, all of it inside the branch
        of the plane ``text_gram``'s gate takes, on that plane's ONE count
        matrix C: ``u = C·w_text + numeric·w_num`` (the pre-update raw
        margin), G (row panels sharded under a data axis), the shared
        ``run_dual_loop``, and the write-back ``Cᵀα`` — locally, or this
        shard's rows + psum under a data axis (which both shrinks the
        contraction 1/shards and gives the replicated-weights output the
        statically-invariant form shard_map requires).

        With ``arms`` (``weights`` ``[M, F+4]``) C and G are built ONCE and
        C is READ once by each contraction (``arms_dual_half``, the per-arm
        half this step shares with the feature-sharded one): ``u`` for all
        arms out of the count build, the dual loop mapped over
        ``(w_m, η_m, λ_m, u_m)`` under the scope ``arm_map``, then the
        write-back for all arms in one pass.

        ``row_args`` are GLOBAL (the caller all-gathers the batch under a
        data axis); ``local_numeric`` is this shard's rows. Returns
        (new weights, this shard's rows of ``u``, plane index, and under
        ``l1_reg`` the rounds the primal loop ran, else None)."""
        token_idx, token_val, numeric, mask, labels = row_args
        dtype = weights.dtype
        rows = local_numeric.shape[0] if axis_name else 0

        # the single model's halves, sliced outside the switch (the arms'
        # ``[M, F+4]`` stack is sliced in the branch, under ``predict``)
        whole = None if arms else (weights[:f_text], weights[f_text:])

        def dual_basis(counts):
            def gram():
                g_text = counts.gram()
                if axis_name:
                    # [B_local, B_global] panel: the G matmul's FLOPs scale
                    # 1/shards (the count build replicates per shard — see
                    # ops/gram.text_gram's ``branch``)
                    g_text = lax.all_gather(
                        g_text, axis_name, axis=0, tiled=True
                    )
                return add_numeric_block(g_text, numeric, dtype)

            def dual(w, u, g, eta, lam):
                with jax.named_scope("dual_loop"):
                    p_prev = jnp.sum(w * w)  # its convergence norm
                # u and G are built in f32 (the accumulation type); the dual
                # loop runs in the weights dtype so the fori_loop carry stays
                # type-stable for low-precision weights. f64 weights never
                # reach here (the auto gate is f32-only — the bf16-plane G
                # build would silently downgrade f64).
                return run_dual_loop(
                    u=u,
                    g=g,
                    labels=labels,
                    mask=mask,
                    dtype=dtype,
                    residual_fn=residual_fn,
                    num_iterations=num_iterations,
                    step_size=eta,
                    mini_batch_fraction=mini_batch_fraction,
                    l2_reg=lam,
                    convergence_tol=convergence_tol,
                    p_prev=p_prev,
                    vary_axis=axis_name,
                )

            if arms:
                g = gram()  # ONE count matrix and ONE G for all M arms
                with jax.named_scope("predict"):
                    w_text, w_num = weights[:, :f_text], weights[:, f_text:]
                halves, raw = arms_dual_half(
                    counts,
                    w_text=w_text,
                    w_num=w_num,
                    numeric=local_numeric,
                    mapped=(weights, step_size, l2_reg),
                    dual=lambda w, eta, lam, u: dual(w, u, g, eta, lam),
                    dtype=dtype,
                )
                with jax.named_scope("writeback"):
                    w_new = jnp.concatenate(halves, axis=1).astype(dtype)
                return w_new, raw

            w_text, w_num = whole
            with jax.named_scope("predict"):
                # this shard's rows of u = Z·W_prev: C·w rides the count
                # build's epilogue (ops/gram.CountPlane.dot)
                raw = (counts.dot(w_text) + local_numeric @ w_num).astype(dtype)
                u = raw
                if axis_name:
                    u = lax.all_gather(raw, axis_name, axis=0, tiled=True)
            dual_state = dual(weights, u, gram(), step_size, l2_reg)
            with jax.named_scope("writeback"):
                # W_new = c·W_prev + Zᵀα: the other read of C
                c, alpha = dual_state["c"], dual_state["alpha"]
                if axis_name:  # this shard's rows of α, then one psum each
                    c, alpha = dual_scale_and_alpha(dual_state, axis_name, rows)
                delta_text = counts.tdot(alpha)
                delta_num = local_numeric.T @ alpha
                if axis_name:
                    delta_text = lax.psum(delta_text, axis_name)
                    delta_num = lax.psum(delta_num, axis_name)
                w_new = jnp.concatenate(
                    [w_text * c + delta_text, w_num * c + delta_num]
                ).astype(dtype)
            return w_new, raw

        def primal_basis(counts):
            """``dual_basis``'s stand-in under ``l1_reg``: MLlib's loop on
            the weights themselves, C read once a round."""
            w_text, w_num = whole
            with jax.named_scope("predict"):
                # the pre-update margin, as the dual basis takes it
                raw = (counts.dot(w_text) + local_numeric @ w_num).astype(dtype)

            def grad_and_count(w, sel):
                w_text, w_num = w
                with jax.named_scope("primal_pass"):
                    grad_text, residual = counts.primal_pass(
                        w_text,
                        base=numeric @ w_num,
                        labels=labels,
                        sel=sel,
                        residual_fn=residual_fn,
                    )
                return (grad_text, numeric.T @ residual), jnp.sum(sel)

            with jax.named_scope("primal_loop"):
                halves, ran = sgd_inner_loop(
                    whole,
                    num_iterations=num_iterations,
                    step_size=step_size,
                    mini_batch_fraction=mini_batch_fraction,
                    l2_reg=0.0,
                    l1_reg=l1_reg,
                    convergence_tol=convergence_tol,
                    mask=mask,
                    sample_key=sampling_key(None, mini_batch_fraction),
                    grad_and_count=grad_and_count,
                    count_iterations=True,
                )
                w_new = jnp.concatenate(halves).astype(dtype)
            return w_new, raw, ran

        (w_new, raw, *ran), plane = text_gram(
            token_idx,
            token_val,
            f_text,
            row_start=lax.axis_index(axis_name) * rows if axis_name else None,
            rows=rows,
            body=primal_basis if l1_reg else dual_basis,
        )
        if axis_name:
            # every shard gated the same global rows: pmin only makes the
            # index statically invariant, like ``c`` in dual_scale_and_alpha
            plane = lax.pmin(plane, axis_name)
        return w_new, raw, plane, (ran[0] if ran else None)

    def train_step(weights, batch: FeatureBatch | UnitBatch | PackedBatch):
        dtype = weights.dtype
        if isinstance(batch, PackedBatch):
            # one-buffer wire format: reinterpret in-place (features/batch.py
            # PackedBatch — bit-identical arrays, transfer-count 5 → 1)
            with jax.named_scope("unpack"):
                batch = unpack_batch(batch.buffer, batch.layout)
        if isinstance(batch, RaggedUnitBatch):
            # ragged wire: the units arrive concatenated (no per-row pad
            # bytes on the transport); ops/ragged.py rebuilds the padded
            # [B, L] + ASCII fold on device — bit-identical units either way
            buf, lens = ragged_repad(
                batch.units, batch.offsets, batch.row_len, batch.mask.shape[0]
            )
            batch = UnitBatch(
                buf, lens, batch.numeric, batch.label, batch.mask
            )
        if isinstance(batch, UnitBatch):
            # on-device featurization: hash the raw code units inside this
            # same XLA program (ops/text_hash.py); per-occurrence 1.0 values
            # scatter/gather to the identical features host hashing ships
            token_idx, token_val = hash_bigrams_device(
                batch.units, batch.length, f_text, dtype
            )
            batch = FeatureBatch(
                token_idx, token_val, batch.numeric, batch.label, batch.mask
            )
        # tokens arrive in a compact wire dtype (batch.compact_tokens);
        # upcast once on device before any gather/scatter
        with jax.named_scope("unpack"):
            batch = batch._replace(
                token_idx=batch.token_idx.astype(jnp.int32),
                token_val=batch.token_val.astype(dtype),
            )
            mask = batch.mask.astype(dtype)
            labels = batch.label.astype(dtype)
        x_dense = None
        if not sparse:
            x_dense = jnp.concatenate(
                [
                    densify_text(batch.token_idx, batch.token_val, f_text),
                    batch.numeric.astype(dtype),
                ],
                axis=1,
            )

        b_global = batch.mask.shape[0] * (lax.axis_size(axis_name) if axis_name else 1)
        gram = (
            sparse
            and dtype == jnp.float32  # see dtype note in _gram_sgd
            and fits_gram(b_global, f_text, num_iterations)
            if use_gram is None
            else use_gram
        )

        # ---- predict + stats with pre-update weights --------------------
        w_new = raw = plane = ran = None
        if gram:
            # the Gram basis: the count matrix is built FIRST and the raw
            # margin u = Z·W_prev, G, the dual loop and the write-back all
            # read it inside the plane's branch (_gram_sgd)
            numeric = batch.numeric.astype(dtype)
            row_args = (batch.token_idx, batch.token_val, numeric, mask, labels)
            if axis_name:
                # ONE all-gather of the batch (and one of u, in the
                # branch); the loop runs replicated and collective-free
                # (vs a gradient psum per iteration below)
                row_args = tuple(
                    lax.all_gather(a, axis_name, axis=0, tiled=True)
                    for a in row_args
                )
            w_new, raw, plane, ran = _gram_sgd(weights, row_args, numeric)

        def observe(raw):
            """One model's reported predictions and the five batch stats
            of them, from its pre-update raw margin."""
            preds = prediction_fn(raw)
            if round_predictions:
                preds = jnp_round_half_up(preds)
            return preds, batch_stats(labels, preds, mask, axis_name)

        def quality_of(weights, w_new, raw, preds, gram_plane=None):
            # the ISSUE-8 side channel against the post-update weights;
            # None (plane off) keeps the output pytree the HEAD program's
            if not quality:
                return None
            with jax.named_scope("quality"):
                return quality_vector(
                    weights, w_new,
                    residual=residual_fn(raw, labels) * mask,
                    preds=preds, labels=labels, mask=mask,
                    numeric=batch.numeric, token_idx=batch.token_idx,
                    token_val=batch.token_val, gram_plane=gram_plane,
                    axis_name=axis_name,
                )

        def primal_of(w_new, ran):
            """``StepOutput.primal`` under ``l1_reg``: the rounds that ran
            and the text weights that are exactly zero; None (the leaf of
            every other learner) keeps the output pytree as it was."""
            if not l1_reg:
                return None
            return jnp.stack([
                ran, jnp.sum(w_new[:f_text] == 0, dtype=jnp.int32)
            ])

        def finish(weights, eta, lam, w_new=None, raw=None):
            """One model's stats with its pre-update weights and, outside
            the Gram basis, its iterations: everything of the step that is
            per MODEL once the batch is featurized."""
            with jax.named_scope("predict"):
                if not gram:
                    raw = _predict_raw(weights, batch, x_dense)
                preds, stats = observe(raw)

            if gram:
                return w_new, StepOutput(
                    predictions=preds,
                    quality=quality_of(weights, w_new, raw, preds, plane),
                    primal=primal_of(w_new, ran),
                    **stats,
                )

            # ---- numIterations of mini-batch SGD (the scatter loop) -----
            def grad_and_count(w, sel):
                residual = residual_fn(_predict_raw(w, batch, x_dense), labels) * sel
                grad_sum = _grad_sum(batch, x_dense, residual)
                count = jnp.sum(sel)
                if axis_name:
                    grad_sum = lax.psum(grad_sum, axis_name)
                    count = lax.psum(count, axis_name)
                return grad_sum, count

            w_final = sgd_inner_loop(
                weights,
                num_iterations=num_iterations,
                step_size=eta,
                mini_batch_fraction=mini_batch_fraction,
                l2_reg=lam,
                l1_reg=l1_reg,
                convergence_tol=convergence_tol,
                mask=mask,
                sample_key=sampling_key(axis_name, mini_batch_fraction),
                grad_and_count=grad_and_count,
                count_iterations=bool(l1_reg),
            )
            scatter_ran = None
            if l1_reg:  # the same updater outside fits_gram (no dense C)
                w_final, scatter_ran = w_final
            return w_final, StepOutput(
                predictions=preds,
                quality=quality_of(weights, w_final, raw, preds),
                primal=primal_of(w_final, scatter_ran),
                **stats,
            )

        if not arms:
            return finish(weights, step_size, l2_reg, w_new, raw)
        # M arms on the SAME rows: the featurized batch above is shared
        if gram:
            # C, G, u = C·[w_1…w_M] and the write-back came out of the
            # plane's branch for all arms at once (_gram_sgd). An arm's
            # stats are the single model's own reductions of its row of u,
            # mapped but NOT under ``arm_map`` (a reader of the benchmark
            # takes ``arm_map`` + ``predict`` for a pass over C); the
            # quality vector, per arm, stays there
            with jax.named_scope("predict"):
                preds, stats = lax.map(observe, raw)
            vectors = None
            if quality:
                with jax.named_scope("arm_map"):
                    vectors = lax.map(
                        lambda args: quality_of(*args, plane),
                        (weights, w_new, raw, preds),
                    )
            return w_new, StepOutput(predictions=preds, quality=vectors, **stats)
        # outside the Gram basis the whole loop is mapped, each arm through
        # the single model's own program (lax.map: the parity law)
        with jax.named_scope("arm_map"):
            return lax.map(
                lambda args: finish(*args), (weights, step_size, l2_reg)
            )

    return train_step


def zero_weights(num_text_features: int, dtype=jnp.float32):
    """MLlib initial weights: zeros(numFeatures) (LinearRegression.scala:32)."""
    return jnp.zeros((num_text_features + NUM_NUMBER_FEATURES,), dtype=dtype)


class StreamingSGDModel:
    """Shared surface of the streaming SGD learners (linear/logistic):
    device-resident weight state, fused jit step with donated weights, conf
    plumbing, and DStream-style ``train_on`` registration. Subclasses set the
    three gradient knobs (``residual_fn``, ``prediction_fn``,
    ``round_predictions``) and a default step size."""

    residual_fn = None  # least-squares when None
    prediction_fn = None  # identity when None
    round_predictions = True
    default_step_size = 0.1
    # single-device steps unpack the one-buffer wire in-program; sharded
    # models don't (a packed buffer has no row sharding), so the app-side
    # pack opt-in keys off this capability (apps/common.py)
    accepts_packed = True

    def __init__(
        self,
        num_text_features: int = 1000,
        num_iterations: int = 50,
        step_size: float | None = None,
        mini_batch_fraction: float = 1.0,
        l2_reg: float = 0.0,
        convergence_tol: float = 0.001,
        dtype=jnp.float32,
        use_sparse: bool | None = None,
        use_gram: bool | None = None,
        quality: bool = False,
        l1_reg: float = 0.0,
    ) -> None:
        self.num_text_features = num_text_features
        self.dtype = dtype
        self._weights = zero_weights(num_text_features, dtype)
        step = make_sgd_train_step(
            num_text_features=num_text_features,
            num_iterations=num_iterations,
            step_size=self.default_step_size if step_size is None else step_size,
            mini_batch_fraction=mini_batch_fraction,
            l2_reg=l2_reg,
            convergence_tol=convergence_tol,
            residual_fn=type(self).residual_fn,
            prediction_fn=type(self).prediction_fn,
            round_predictions=self.round_predictions,
            use_sparse=use_sparse,
            use_gram=use_gram,  # None=auto; False is the scatter-loop escape hatch
            quality=quality,  # --modelWatch: the in-step quality side channel
            l1_reg=l1_reg,  # --l1Reg: MLlib's L1Updater, the primal basis
        )
        # donate weights: the update happens in-place in HBM
        self._step = jax.jit(step, donate_argnums=0)

    @classmethod
    def from_conf(cls, conf, **overrides):
        kwargs = dict(
            num_text_features=conf.numTextFeatures,
            num_iterations=conf.numIterations,
            step_size=conf.stepSize,
            mini_batch_fraction=conf.miniBatchFraction,
            l2_reg=conf.l2Reg,
            l1_reg=float(getattr(conf, "l1Reg", 0.0) or 0.0),
            convergence_tol=conf.convergenceTol,
            dtype=jnp.dtype(conf.dtype),
            quality=getattr(conf, "modelWatch", "off") == "on",
        )
        kwargs.update(overrides)
        return cls(**kwargs)

    def set_initial_weights(self, weights) -> "StreamingSGDModel":
        self._weights = jnp.asarray(weights, dtype=self.dtype)
        return self

    def reset(self) -> "StreamingSGDModel":
        """Back to MLlib's initial state: zero weights (LinearRegression.scala:32)."""
        self._weights = zero_weights(self.num_text_features, self.dtype)
        return self

    @property
    def latest_weights(self):
        import numpy as np

        return np.asarray(self._weights)

    def step(self, batch: FeatureBatch | UnitBatch | PackedBatch) -> StepOutput:
        """Fused predict-then-train on one micro-batch; advances the model.

        Accepts the one-buffer wire format too (``pack_batch``) — bit-
        identical unpack inside the jit step. On the lean RAGGED wire the
        packed form is the shipped default (one put per batch; the app
        paths pack via the fetch pipeline, apps/common.py); on the padded
        wire it stays an opt-in."""
        self._weights, out = self._step(self._weights, batch)
        return out

    def train_on(self, stream) -> None:
        """Register the fused step as a stream output (DStream.trainOn analog;
        the reference registers stats first, then training —
        LinearRegression.scala:53,86 — the fused step preserves that order
        internally)."""
        stream.foreach_batch(lambda batch, _time: self.step(batch))
