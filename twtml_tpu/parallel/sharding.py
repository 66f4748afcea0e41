"""Sharded training — shard_map + psum over the device mesh.

This is the TPU-native replacement for the reference's distributed runtime:
MLlib's per-iteration ``treeAggregate`` of gradients to the driver
(SURVEY.md §3.3) becomes an in-program ``psum`` over the mesh's ``data`` axis,
and the driver→executor weight broadcast disappears entirely — weights are
device-resident (replicated over ``data``, optionally sharded over ``model``).

Two layouts:

- **data-parallel** (model_axis=None): weights replicated, batch rows sharded;
  reuses the single-device fused step (models/sgd.py) with ``axis_name`` so
  gradient/stat reductions turn into ICI collectives. This is BASELINE
  config #5 (4-way sharded stream + gradient allreduce).
- **feature-sharded** (2D mesh, ``--modelShards M``): the hashed
  text-feature axis of the weights is sharded over ``model`` — for a hashed
  width whose dense count matrix one chip cannot hold inside
  ``ops/gram.fits_gram``'s gate (2^20 dims at batches of 2048: BASELINE
  config #4's learner at spark.mllib HashingTF's default width, on config
  #5's four chips), so that each shard's SLICE passes the gate and the step
  stays in the Gram basis. Each shard counts only the tokens hashing into
  its slice (its slice's count matrix C serves the predict partial, the
  partial G panel and the write-back alike; the scatter loop gathers /
  scatter-adds the same tokens), with a ``psum`` over ``model`` reassembling
  predictions and the partial G panels — a sharded-embedding pattern, not a
  translation of any reference code (the reference caps at 1000 dims in one
  JVM). The body carries the single-device step's stage names (models/sgd.py
  ``STAGE_SCOPES``), every collective under a ``collective`` scope inside
  the stage it serves, and the single-device step's quality vector.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..features.batch import (
    NUM_NUMBER_FEATURES,
    FeatureBatch,
    PackedBatch,
    RaggedUnitBatch,
    UnitBatch,
    align_ragged_shards,
    pack_ragged_sharded,
    unpack_batch,
)
from ..models.base import StepOutput
from ..models.sgd import (
    arms_dual_half,
    dual_scale_and_alpha,
    make_sgd_train_step,
    run_dual_loop,
    sampling_key,
    sgd_inner_loop,
)
from ..ops.gram import add_numeric_block, fits_gram, text_gram
from ..ops.quality import quality_vector
from ..ops.ragged import ragged_repad
from ..ops.sparse import sparse_grad_text, sparse_text_dot
# ``_psum``: lax.psum under the ``collective`` scope — the device time of the
# mesh steps' collectives is summed by that name, whatever stage holds them
# (benchmark/layer_metrics/collective_ms_per_batch.py)
from ..ops.stats import _maybe_psum as _psum, batch_stats
from ..ops.text_hash import hash_bigrams_device
from ..utils.rounding import jnp_round_half_up


def batch_pspecs(data_axis: str = "data") -> FeatureBatch:
    """PartitionSpecs sharding a FeatureBatch's rows across ``data``."""
    return FeatureBatch(
        token_idx=P(data_axis, None),
        token_val=P(data_axis, None),
        numeric=P(data_axis, None),
        label=P(data_axis),
        mask=P(data_axis),
    )


def unit_batch_pspecs(data_axis: str = "data") -> UnitBatch:
    """PartitionSpecs sharding a UnitBatch's rows across ``data`` (the
    on-device-featurization wire format, ops/text_hash.py)."""
    return UnitBatch(
        units=P(data_axis, None),
        length=P(data_axis),
        numeric=P(data_axis, None),
        label=P(data_axis),
        mask=P(data_axis),
    )


def _pspecs_for(batch_cls, data_axis: str):
    if batch_cls is RaggedUnitBatch:
        # one P(data) prefix-spec: every ragged leaf (units sub-buffers,
        # segment-relative offsets, rows) shards its leading dim — the
        # shard-aligned layout makes them all divisible by the data axis
        return P(data_axis)
    if batch_cls is PackedBatch:
        # the per-shard packed buffer (pack_ragged_sharded): S equal shard
        # segments, so P(data) hands each device exactly its rows' bytes
        return P(data_axis)
    return (
        unit_batch_pspecs(data_axis)
        if batch_cls is UnitBatch
        else batch_pspecs(data_axis)
    )


def _stacked(spec_tree):
    """Prepend an unsharded leading axis to every PartitionSpec — the specs
    for the tenant stack's [M, ...] leaves (parallel/tenants.py)."""
    return jax.tree_util.tree_map(
        lambda s: P(*((None,) + tuple(s))),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_batch(batch: FeatureBatch | UnitBatch | RaggedUnitBatch, mesh):
    """Place a host batch onto the mesh with row sharding (explicit
    device_put so repeated steps don't re-infer layouts). The tenant
    stack's [M, ...] leaves (detected by the mask rank) shard their row
    axis the same way with M unsharded. A RaggedUnitBatch is
    shard-ALIGNED first (``align_ragged_shards`` — a host memcpy unless the
    featurizer already aligned it), after which every leaf row-shards over
    ``data`` like the padded wire; a STACKED ragged batch must already be
    aligned per batch (alignment is a flat-batch operation — the tenant
    plane aligns its parts before stacking, parallel/tenants.py)."""
    data_axis = mesh.axis_names[0]
    if isinstance(batch, RaggedUnitBatch):
        num_data = mesh.shape[data_axis]
        stacked = batch.mask.ndim == 2
        if batch.num_shards != num_data:
            if stacked:
                raise ValueError(
                    "stacked ragged batches must be shard-aligned per "
                    "batch before stacking (align_ragged_shards)"
                )
            batch = align_ragged_shards(batch, num_data)
        spec = P(None, data_axis) if stacked else P(data_axis)
        sharding = NamedSharding(mesh, spec)
        return RaggedUnitBatch(
            *(jax.device_put(a, sharding) for a in (
                batch.units, batch.offsets, batch.numeric, batch.label,
                batch.mask,
            )),
            row_len=batch.row_len,
            num_shards=batch.num_shards,
        )
    specs = _pspecs_for(type(batch), data_axis)
    if batch.mask.ndim == 2:  # stacked: [K, B] mask
        specs = _stacked(specs)
    return type(batch)(*(
        jax.device_put(arr, NamedSharding(mesh, spec))
        for arr, spec in zip(batch, specs)
    ))


def _all_gather(x, axis, rows_axis: int = 0):
    """Tiled ``lax.all_gather`` of the rows (``rows_axis`` of ``x``), under
    the ``collective`` scope."""
    with jax.named_scope("collective"):
        return lax.all_gather(x, axis, axis=rows_axis, tiled=True)


def _make_feature_sharded_step(
    *,
    f_text: int,
    f_text_local: int,
    num_iterations: int,
    step_size: float,
    mini_batch_fraction: float,
    l2_reg: float,
    convergence_tol: float,
    residual_fn: Callable | None,
    prediction_fn: Callable | None,
    round_predictions: bool,
    data_axis: str,
    model_axis: str,
    use_gram: bool | None = None,
    quality: bool = False,
    arms: bool = False,
):
    """Per-shard body for the 2D (data × model) mesh. Weights arrive as a
    {'text': [f_text_local], 'num': [4]} pytree; token indices are global and
    each shard contributes only the tokens landing in its slice.

    ``arms`` (``--tenantKey all --modelShards m``: a champion and its
    challengers on the SAME rows, at a width one chip cannot hold) is the
    step of M models that share their batch: the weights lead with the arm
    axis, {'text': [M, f_text_local], 'num': [M, 4]}, ``step_size`` and
    ``l2_reg`` are ``[M]``, every leaf of the output leads with M. What
    does not depend on the model is the single model's step, untouched and
    run ONCE: re-pad, hash, the batch all-gather, the plane gate and its
    reductions, the slice's count matrix C, its G panel with the panel psum
    and the G all-gather. The per-arm half is ``models/sgd.arms_dual_half``
    (the one the single-device ``arms`` step calls) with this mesh's
    reductions handed in, so C is read once for ``u`` of all arms (the
    build's epilogue) and once for their write-back, the M dual loops run
    replicated under ``arm_map`` with no collective inside a loop, and the
    program issues the SAME NUMBER of collectives as without ``arms``, the
    per-arm ones carrying ``[M, ·]`` payloads: the ``u`` partials' psum
    over ``model`` and all-gather over ``data`` (``[M, B_local]``, one
    array), ‖w_m‖² (``[M]``, one psum), the scale ``c`` (``[M]``), the two
    write-back deltas (``[M, f_text_local]`` and ``[M, 4]``), and the batch
    statistics and quality sums (ops/stats.py, ops/quality.py: each psum
    takes the arm axis along). Arm m is this step without ``arms`` under arm m's
    recipe, to float32 rounding (PARITY.md, "the arm law"). The Gram basis
    only: a size outside it is refused when the step is traced.

    The inner loop runs in the Gram (dual) basis whenever it applies (f32
    weights, per-shard dense counts within HBM budget — ops/gram.py): one
    all-gather of the batch over ``data``, each shard's feature slice
    contributes its partial G row panel (psum over ``model``), one
    all-gather over ``data`` replicates G, and the [B]-sized dual loop runs
    replicated with ZERO per-iteration collectives — versus one predict
    psum over ``model`` plus one gradient psum over ``data`` per iteration
    (2·numIterations collectives/batch) in the scatter formulation. The
    write-back stays slice-local (this shard's rows × its feature slice)
    with one psum over ``data``. Everything that reads the slice's count
    matrix C runs inside the branch of the plane ``text_gram``'s gate takes
    (PR 28): the predict partial ``rows(C)·w_slice`` → ``[B_local]`` in
    place of the ``sparse_text_dot`` gather — reduced in the epilogue of
    the product that writes ``rows(C)``, so it reads nothing (PR 30) — the
    G panel ``rows(C)·Cᵀ``, the dual loop, and the write-back delta
    ``rows(C)ᵀ·α_local`` → ``[f_text_local]`` in place of the
    ``sparse_grad_text`` scatter. ``rows(C)`` is an array of its own: the
    slice's count matrix is BUILT as this data shard's rows and the rest,
    two arrays in the ``[·, k_hi, k_lo]`` the build writes, and the panel
    is two products side by side — nothing is cut from C, which on the
    TPU was a second array of the panel's size written every batch (PR 54;
    ops/gram.text_gram, CountPlane). The collective inventory below is
    UNCHANGED by that: the same psums and all-gathers, of the same sizes,
    under the same scopes — those from the predict psum to the write-back
    psum now sit inside the branch, which every shard enters together
    (the plane index is reduced over ``model`` by the gate and computed
    from all-gathered rows on every ``data`` shard).

    What is reduced over which axis, once a batch (Gram basis):

    - over ``model``: the predict partials ``[B_local]`` (psum); the plane
      gate's inputs inside ``text_gram`` — the ``[B]`` row masses (psum),
      the two value flags (pmin) and rung 2's verdict (pmin) — so every
      shard takes the SAME plane on the whole row's figures and the psum of
      the integer partial panels is exact in f32 (ops/gram.py docstring);
      the partial G panels ``[B_local, B]`` (psum); ‖W_prev‖² and the
      quality vector's two weight norms (psum of each slice's share);
    - over ``data``: the batch's rows — hashed pairs, numeric, label, mask
      and ``u`` (all-gather); the G panels (all-gather); the slice-local
      write-back deltas ``[f_text_local]`` and ``[4]`` (psum); the batch
      statistics and the quality vector's row sums (psum); and two scalars
      that are equal on every data shard already, the dual scale ``c``
      (psum-mean) and the plane index (pmin), reduced only to make them
      statically invariant.

    Stage names are the single-device step's (models/sgd.py
    ``STAGE_SCOPES``); each collective sits inside the stage it serves,
    under a further scope ``collective``: the predict psum under
    ``predict``, the batch all-gather under ``hash`` (it assembles the
    hashed pairs of all rows) and ``predict`` (the row vectors), the gate's
    reductions under ``gram_count``, the panel psum and the G all-gather
    under ``gram_matmul``, ‖W_prev‖² under ``dual_loop`` (before the loop,
    never inside it), the write-back psum under ``writeback``."""
    residual_fn = residual_fn or (lambda raw, label: raw - label)
    prediction_fn = prediction_fn or (lambda raw: raw)

    def step(weights, batch: FeatureBatch | UnitBatch | RaggedUnitBatch):
        w_text, w_num = weights["text"], weights["num"]
        dtype = w_text.dtype
        if isinstance(batch, RaggedUnitBatch):
            # ragged wire, shard-local arrays: re-pad + fold on device
            # (ops/ragged.py), then hash like the padded units wire below
            buf, lens = ragged_repad(
                batch.units, batch.offsets, batch.row_len,
                batch.mask.shape[0],
            )
            batch = UnitBatch(buf, lens, batch.numeric, batch.label, batch.mask)
        if isinstance(batch, UnitBatch):
            # on-device featurization: each data shard hashes its own rows'
            # code units to GLOBAL indices, then slices per model shard below
            g_idx, token_val = hash_bigrams_device(
                batch.units, batch.length, f_text, dtype
            )
        else:
            # compact wire dtype (batch.compact_tokens) → int32 index math
            with jax.named_scope("unpack"):
                g_idx = batch.token_idx.astype(jnp.int32)
                token_val = batch.token_val.astype(dtype)
        with jax.named_scope("unpack"):
            mask = batch.mask.astype(dtype)
            labels = batch.label.astype(dtype)
            numeric = batch.numeric.astype(dtype)
        lo = lax.axis_index(model_axis) * f_text_local

        def to_slice(idx, val):
            """Global (idx, val) pairs → this shard's slice: indices
            relative to it, values zeroed outside it."""
            rel = idx - lo
            inside = ((rel >= 0) & (rel < f_text_local)).astype(dtype)
            return jnp.clip(rel, 0, f_text_local - 1), val * inside

        def norm_sq_of(text, num):
            # text slices live on the model axis; num is replicated there
            # (over the LAST axis: with arms ``[M, ·]`` in, ``[M]`` out)
            return _psum(
                jnp.sum(text * text, axis=-1), model_axis
            ) + jnp.sum(num * num, axis=-1)

        # ---- Gram (dual) basis when it applies (see docstring) ----------
        b_local = mask.shape[0]
        b_global = b_local * lax.axis_size(data_axis)
        gram = (
            dtype == jnp.float32
            and fits_gram(b_global, f_text_local, num_iterations)
            if use_gram is None
            else use_gram
        )
        if gram:
            with jax.named_scope("hash"):
                idx_g = _all_gather(g_idx, data_axis)
                val_g = _all_gather(token_val, data_axis)
            with jax.named_scope("predict"):
                num_g, lab_g, mask_g = (
                    _all_gather(a, data_axis) for a in (numeric, labels, mask)
                )
            with jax.named_scope("gram_count"):
                rel_g, local_val_g = to_slice(idx_g, val_g)

            def dual_basis(counts):
                """Everything that reads the slice's count matrix, inside
                the branch of the plane taken (ops/gram.text_gram): this
                shard's rows of u, its partial G panel, the dual loop and
                the slice-local write-back."""
                with jax.named_scope("predict"):
                    # [B_local], over this slice: this shard's rows of
                    # C·w, out of the epilogue of the build that writes them
                    part = counts.dot(w_text)
                    raw = (_psum(part, model_axis) + numeric @ w_num).astype(
                        dtype
                    )
                    u = _all_gather(raw, data_axis)
                # [B_local, B_global] partial over this feature slice
                panel = counts.gram()
                with jax.named_scope("gram_matmul"):
                    g_mat = _all_gather(_psum(panel, model_axis), data_axis)
                g_mat = add_numeric_block(g_mat, num_g, dtype)
                with jax.named_scope("dual_loop"):
                    p_prev = norm_sq_of(w_text, w_num)  # its convergence norm
                dual = run_dual_loop(
                    u=u,
                    g=g_mat,
                    labels=lab_g,
                    mask=mask_g,
                    dtype=dtype,
                    residual_fn=residual_fn,
                    num_iterations=num_iterations,
                    step_size=step_size,
                    mini_batch_fraction=mini_batch_fraction,
                    l2_reg=l2_reg,
                    convergence_tol=convergence_tol,
                    p_prev=p_prev,
                    vary_axis=data_axis,
                )
                with jax.named_scope("writeback"):
                    # psum-mean of the (identical-everywhere) scale + psum
                    # of the slice-local write-back (this shard's rows of
                    # C, transposed, times its rows of α): statically
                    # invariant over ``data``
                    c, alpha_local = dual_scale_and_alpha(
                        dual, data_axis, b_local
                    )
                    w_final = {
                        "text": (
                            w_text * c
                            + _psum(counts.tdot(alpha_local), data_axis)
                        ).astype(dtype),
                        "num": w_num * c
                        + _psum(numeric.T @ alpha_local, data_axis),
                    }
                return w_final, raw

            def arms_basis(counts):
                """``dual_basis`` for M arms on the same rows: the G panel
                and its two collectives as above, ONCE; then the per-arm
                half (models/sgd.arms_dual_half) with this mesh's
                reductions, each one collective for all arms."""
                panel = counts.gram()
                with jax.named_scope("gram_matmul"):
                    g_mat = _all_gather(_psum(panel, model_axis), data_axis)
                g_mat = add_numeric_block(g_mat, num_g, dtype)
                with jax.named_scope("dual_loop"):
                    # ‖w_m‖² of every arm in ONE psum, before the map
                    p_prev = norm_sq_of(w_text, w_num)

                def dual(p, eta, lam, u):
                    return run_dual_loop(
                        u=u,
                        g=g_mat,
                        labels=lab_g,
                        mask=mask_g,
                        dtype=dtype,
                        residual_fn=residual_fn,
                        num_iterations=num_iterations,
                        step_size=eta,
                        mini_batch_fraction=mini_batch_fraction,
                        l2_reg=lam,
                        convergence_tol=convergence_tol,
                        p_prev=p,
                        vary_axis=data_axis,
                    )

                (new_text, new_num), raw = arms_dual_half(
                    counts,
                    w_text=w_text,
                    w_num=w_num,
                    numeric=numeric,
                    mapped=(p_prev, step_size, l2_reg),
                    dual=dual,
                    dtype=dtype,
                    sum_features=lambda part: _psum(part, model_axis),
                    all_rows=lambda raw: _all_gather(raw, data_axis, 1),
                    own_rows=lambda duals: dual_scale_and_alpha(
                        duals, data_axis, b_local
                    ),
                    sum_rows=lambda delta: _psum(delta, data_axis),
                )
                return {"text": new_text.astype(dtype), "num": new_num}, raw

            (w_final, raw), plane = text_gram(
                rel_g,
                local_val_g,
                f_text_local,
                row_start=lax.axis_index(data_axis) * b_local,
                rows=b_local,
                feature_axis=model_axis,
                body=arms_basis if arms else dual_basis,
            )
            # every data shard gated the same gathered rows: the pmin only
            # makes the index statically invariant (models/sgd.py)
            with jax.named_scope("gram_matmul"), jax.named_scope("collective"):
                plane = lax.pmin(plane, data_axis)
        elif arms:
            raise ValueError(
                "arms on a mesh run in the Gram basis only (float32 "
                "weights, a slice's count matrix inside ops/gram.fits_gram)"
            )
        else:
            with jax.named_scope("predict"):
                rel, local_val = to_slice(g_idx, token_val)

        def predict(w):
            part = sparse_text_dot(w["text"], rel, local_val)
            return _psum(part, model_axis) + numeric @ w["num"]

        # ---- predict + stats with pre-update weights --------------------
        with jax.named_scope("predict"):
            if not gram:
                raw = predict(weights)
            preds = prediction_fn(raw)
            if round_predictions:
                preds = jnp_round_half_up(preds)
            # with arms ``preds`` is [M, B_local]: an arm's stats are the
            # single model's own reductions of its row, and each psum takes
            # the M of them along (ops/stats.py: one collective, no loop)
            stats = batch_stats(labels, preds, mask, data_axis)
            if arms:
                stats = {
                    k: jnp.broadcast_to(v, preds.shape[:1])
                    for k, v in stats.items()
                }

        def _quality(w_new, gram_plane=None):
            # the ISSUE-8 side channel (models/sgd.py ``quality_of``): rows
            # reduce over ``data``, the text weights' norms over ``model``.
            # With arms it is per arm as on one device, under ``arm_map``:
            # its sums lead with the arm axis (ops/quality.py), what the
            # batch alone decides runs once
            if not quality:
                return None
            scope = "arm_map/quality" if arms else "quality"
            with jax.named_scope(scope):
                new_t, new_n, old_t, old_n = (
                    a.astype(jnp.float32)
                    for a in (w_new["text"], w_new["num"], w_text, w_num)
                )
                return quality_vector(
                    weights, w_new,
                    residual=residual_fn(raw, labels) * mask,
                    preds=preds, labels=labels, mask=mask,
                    numeric=batch.numeric, token_idx=g_idx,
                    token_val=token_val, gram_plane=gram_plane,
                    axis_name=data_axis,
                    weight_sq=norm_sq_of(new_t, new_n),
                    update_sq=norm_sq_of(new_t - old_t, new_n - old_n),
                )

        if gram:
            return w_final, StepOutput(
                predictions=preds, quality=_quality(w_final, plane), **stats
            )

        # ---- the shared MLlib iteration loop over the sharded pytree ----
        def grad_and_count(w, sel):
            residual = residual_fn(predict(w), labels) * sel
            g_text = _psum(
                sparse_grad_text(rel, local_val, residual, f_text_local), data_axis
            )
            g_num = _psum(residual @ numeric, data_axis)
            count = _psum(jnp.sum(sel), data_axis)
            return {"text": g_text, "num": g_num}, count

        def norm_sq(a, b):
            return norm_sq_of(a["text"] - b["text"], a["num"] - b["num"])

        w_final = sgd_inner_loop(
            {"text": w_text, "num": w_num},
            num_iterations=num_iterations,
            step_size=step_size,
            mini_batch_fraction=mini_batch_fraction,
            l2_reg=l2_reg,
            convergence_tol=convergence_tol,
            mask=mask,
            sample_key=sampling_key(data_axis, mini_batch_fraction),
            grad_and_count=grad_and_count,
            norm_sq=norm_sq,
        )
        return w_final, StepOutput(
            predictions=preds, quality=_quality(w_final), **stats
        )

    return step


class ParallelSGDModel:
    """Mesh-sharded streaming SGD learner with the same step surface as the
    single-device models (models/sgd.py StreamingSGDModel).

    ``arms=(step sizes, L2 strengths)``, one of each per arm, makes it the
    mesh model of a champion and its challengers on the SAME rows
    (``--tenantKey all --modelShards m``): M recipes of the one learner on
    the 2-D mesh, the weights {'text': [M, F], 'num': [M, 4]} with the
    feature axis sharded over ``model`` and the arm axis leading, one
    ``[M, ...]`` StepOutput a batch. It then carries the tenant plane's
    surface too (``num_tenants``, ``tenant_key`` = ``all``,
    ``shared_rows``, ``wire_pack``), so the delivery chain, the tenant
    frames and the stacked ``[M, F+4]`` checkpoint treat it as
    ``parallel/tenants.TenantStackModel`` under that key; the wire is the
    single mesh model's. It needs the model axis: a data-only mesh has no
    form of the per-arm half and is refused."""

    def __init__(
        self,
        mesh,
        num_text_features: int = 1000,
        num_iterations: int = 50,
        step_size: float = 0.005,
        mini_batch_fraction: float = 1.0,
        l2_reg: float = 0.0,
        convergence_tol: float = 0.001,
        dtype=jnp.float32,
        residual_fn: Callable | None = None,
        prediction_fn: Callable | None = None,
        round_predictions: bool = True,
        use_sparse: bool | None = None,
        use_gram: bool | None = None,
        quality: bool = False,
        arms=None,
    ) -> None:
        self.mesh = mesh
        self.num_text_features = num_text_features
        self.dtype = dtype
        axes = mesh.axis_names
        self.data_axis = axes[0]
        self.model_axis = axes[1] if len(axes) > 1 else None
        self.num_data = mesh.shape[self.data_axis]
        # sharding of the last dispatched wire buffer (device_span)
        self._wire_sharding = None
        out_pred_spec = P(self.data_axis)
        scalar = P()
        self.quality = quality
        # M arms on the same rows: every weights leaf and every output leaf
        # leads with the (unsharded) arm axis
        stack = ()
        if arms is not None:
            if self.model_axis is None:
                raise ValueError(
                    "--tenantKey all: the data-only mesh has no form of the "
                    "per-arm half; the arms' mesh has a model axis "
                    "(--modelShards), or use --master local[1]"
                )
            step_size, l2_reg = (np.asarray(v, dtype) for v in arms)
            if step_size.ndim != 1 or step_size.shape != l2_reg.shape:
                raise ValueError(
                    "arms takes one step size and one L2 strength an arm, "
                    f"got shapes {step_size.shape} and {l2_reg.shape}"
                )
            # the tenant plane's surface under --tenantKey all (class
            # docstring): set on an arms model only, so a reader that asks
            # ``getattr(model, "num_tenants", 0)`` still tells the planes
            # apart
            self.num_tenants = int(step_size.shape[0])
            self.tenant_key, self.shared_rows = "all", True
            self.wire_pack = "stacked"
            stack = (self.num_tenants,)
            out_pred_spec = P(None, self.data_axis)

        if self.model_axis is None:
            step = make_sgd_train_step(
                num_text_features=num_text_features,
                num_iterations=num_iterations,
                step_size=step_size,
                mini_batch_fraction=mini_batch_fraction,
                l2_reg=l2_reg,
                convergence_tol=convergence_tol,
                residual_fn=residual_fn,
                prediction_fn=prediction_fn,
                round_predictions=round_predictions,
                axis_name=self.data_axis,
                use_sparse=use_sparse,
                use_gram=use_gram,
                quality=quality,
            )
            self._weights = jnp.zeros(
                (num_text_features + NUM_NUMBER_FEATURES,), dtype
            )
            w_spec = P()
        else:
            num_model = mesh.shape[self.model_axis]
            if num_text_features % num_model:
                raise ValueError(
                    f"numTextFeatures={num_text_features} not divisible by "
                    f"model-axis size {num_model}"
                )
            step = _make_feature_sharded_step(
                f_text=num_text_features,
                f_text_local=num_text_features // num_model,
                num_iterations=num_iterations,
                step_size=step_size,
                mini_batch_fraction=mini_batch_fraction,
                l2_reg=l2_reg,
                convergence_tol=convergence_tol,
                residual_fn=residual_fn,
                prediction_fn=prediction_fn,
                round_predictions=round_predictions,
                data_axis=self.data_axis,
                model_axis=self.model_axis,
                use_gram=use_gram,
                quality=quality,
                arms=arms is not None,
            )
            w_spec = {
                "text": P(*(None,) * len(stack), self.model_axis), "num": P(),
            }
            self._weights = {
                "text": jax.device_put(
                    jnp.zeros(stack + (num_text_features,), dtype),
                    NamedSharding(mesh, w_spec["text"]),
                ),
                "num": jnp.zeros(stack + (NUM_NUMBER_FEATURES,), dtype),
            }

        # the shard_map is built lazily per wire format (FeatureBatch and
        # UnitBatch differ in pytree structure, hence in in_specs); a stream
        # uses one format throughout, so this stays one compiled program
        self._step_body = step
        self._w_spec = w_spec
        self._out_specs = (
            w_spec,
            StepOutput(
                predictions=out_pred_spec,
                count=scalar,
                mse=scalar,
                real_stdev=scalar,
                pred_stdev=scalar,
                # the quality vector is psum-global (axis-invariant), hence
                # replicated like the scalar stats; None when the plane is
                # off keeps the spec tree structurally the HEAD tree
                quality=scalar if quality else None,
            ),
        )
        # compiled programs, keyed by batch class
        self._sharded: dict[object, Callable] = {}

    def _step_for(self, batch_cls) -> Callable:
        fn = self._sharded.get(batch_cls)
        if fn is None:
            inner = self._step_body

            # ONE function name, so one module name (``jit_sharded_train_
            # step``) on the device plane and in the ``compile`` spans for
            # every bucket and wire form of both layouts
            def sharded_train_step(weights, batch):
                if isinstance(batch, PackedBatch):
                    # per-shard packed ragged wire: each device's local
                    # slice is ONE shard segment; rebuild the shard-local
                    # batch in-program (zero-copy bitcasts) and run the
                    # ordinary per-shard body
                    with jax.named_scope("unpack"):
                        batch = unpack_batch(batch.buffer, batch.layout)
                return inner(weights, batch)

            sharded = jax.shard_map(
                sharded_train_step,
                mesh=self.mesh,
                in_specs=(self._w_spec, _pspecs_for(batch_cls, self.data_axis)),
                out_specs=self._out_specs,
            )
            fn = jax.jit(sharded, donate_argnums=0)
            self._sharded[batch_cls] = fn
        return fn

    @classmethod
    def from_conf(cls, conf, mesh, **overrides):
        kwargs = dict(
            num_text_features=conf.numTextFeatures,
            num_iterations=conf.numIterations,
            step_size=conf.stepSize,
            mini_batch_fraction=conf.miniBatchFraction,
            l2_reg=conf.l2Reg,
            convergence_tol=conf.convergenceTol,
            dtype=jnp.dtype(conf.dtype),
            quality=getattr(conf, "modelWatch", "off") == "on",
        )
        kwargs.update(overrides)
        return cls(mesh, **kwargs)

    @staticmethod
    def _to_host(arr) -> np.ndarray:
        """Global array → host numpy, gathering across processes when this
        process doesn't address every shard (a multi-host mesh whose model
        axis crosses process boundaries) — required for checkpointing and
        telemetry of feature-sharded weights on pods."""
        if (
            isinstance(arr, jax.Array)
            and not arr.is_fully_addressable
            and not arr.is_fully_replicated  # replicated: local copy suffices
        ):
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
        return np.asarray(arr)

    def device_span(self) -> dict:
        """How many devices the weights and the last dispatched packed
        wire buffer really occupy, read off their shardings (host-side
        metadata, no fetch). The run record carries it, so N shards on one
        device can never pass for an N-device run. ``batch`` is 0 until a
        packed batch was dispatched."""
        wire = self._wire_sharding
        return {
            "weights": min(
                len(leaf.sharding.device_set)
                for leaf in jax.tree_util.tree_leaves(self._weights)
            ),
            "batch": len(wire.device_set) if wire is not None else 0,
        }

    def mesh_layout(self) -> dict:
        """The mesh as the ``mesh_layout`` trace instant carries it
        (telemetry/trace.py): axis sizes, hashed features a model shard
        holds, devices."""
        num_model = self.mesh.shape[self.model_axis] if self.model_axis else 1
        return {
            "data": self.num_data,
            "model": num_model,
            "f_text_local": self.num_text_features // num_model,
            "devices": self.mesh.size,
        }

    def mesh_arms(self, rows: int) -> "dict | None":
        """What a chip ships a batch FOR THE ARMS, as the ``mesh_arms``
        trace instant carries it (``rows``: the batch's pinned row count, 0
        where it is not pinned): the ``u`` all-gather over ``data`` hands
        every chip the other data shards' ``[M, rows/d]`` f32 rows of it,
        and the write-back psum over ``data`` moves the slice's ``[M, F/m]``
        and ``[M, 4]`` f32 deltas. None on a model without arms."""
        m = getattr(self, "num_tenants", 0)
        if not m:
            return None
        layout = self.mesh_layout()
        d = layout["data"]
        return {
            "arms": m, "data": d, "model": layout["model"],
            "u_gather_bytes": m * (rows // d) * (d - 1) * 4,
            "delta_psum_bytes": m * (
                layout["f_text_local"] + NUM_NUMBER_FEATURES) * 4,
        }

    @property
    def latest_weights(self) -> np.ndarray:
        """``[F+4]``; with arms the stacked ``[M, F+4]``, one checkpointable
        array for all of them (the tenant plane's layout)."""
        if isinstance(self._weights, dict):
            return np.concatenate(
                [self._to_host(self._weights["text"]),
                 self._to_host(self._weights["num"])],
                axis=-1,
            )
        return self._to_host(self._weights)

    def set_initial_weights(self, weights) -> "ParallelSGDModel":
        """Takes what ``latest_weights`` gives. An arms model also takes one
        flat ``[F+4]`` vector for every arm (the sentinel's zeros-reset),
        as ``TenantStackModel`` does."""
        weights = np.asarray(weights, dtype=self.dtype)
        m = getattr(self, "num_tenants", 0)
        if m and weights.ndim == 1:
            weights = np.broadcast_to(weights, (m,) + weights.shape).copy()
        if m and weights.shape[0] != m:
            raise ValueError(
                f"stacked weights lead with {weights.shape[0]} tenants; "
                f"this plane has {m}"
            )
        if isinstance(self._weights, dict):
            ft = self.num_text_features
            text = weights[..., :ft]
            sharding = NamedSharding(self.mesh, self._w_spec["text"])
            # make_array_from_callback, not device_put: checkpoint restore
            # must also work when the model axis spans processes and this
            # process does not address every shard (the allgather mirror of
            # _to_host) — each process materializes only its local slices
            self._weights = {
                "text": jax.make_array_from_callback(
                    text.shape, sharding, lambda idx: text[idx]
                ),
                "num": jnp.asarray(weights[..., ft:]),
            }
        else:
            self._weights = jnp.asarray(weights)
        return self

    def _check_rows(self, rows: int) -> None:
        if rows % self.num_data:
            raise ValueError(
                f"batch rows {rows} not divisible by data shards "
                f"{self.num_data}; set --batchBucket to a multiple of the "
                f"mesh's data axis"
            )

    # the shard-aligned ragged wire also ships PACKED — one buffer laid out
    # per shard (pack_ragged_sharded); the app-side pack opt-in keys off
    # this capability (apps/common.py)
    accepts_packed = True
    # compressed units wire (r15, --wireCodec): set by the app driver when
    # the codec is effective — the mesh packs below compress each shard
    # segment into a shared bucket (single-process mesh: this process
    # picks the bucket freely; the MULTI-HOST model keeps the raw wire —
    # a cross-host agreed compressed bucket would need a new collective)
    wire_codec = ""

    def prepare(self, batch):
        """Host-side shard alignment WITHOUT device placement (the first
        half of ``pack_for_wire``)."""
        if (
            isinstance(batch, RaggedUnitBatch)
            and batch.num_shards != self.num_data
        ):
            return align_ragged_shards(batch, self.num_data)
        return batch

    def pack_for_wire(self, batch) -> PackedBatch:
        """The mesh form of the one-buffer ragged wire: shard-align, then
        pack per shard and place with row sharding (each device receives
        exactly its shard segment's bytes)."""
        if not isinstance(batch, RaggedUnitBatch):
            raise TypeError(
                "pack_for_wire is the ragged wire's mesh pack; padded "
                "batches shard as plain arrays"
            )
        pb = pack_ragged_sharded(
            self.prepare(batch), codec=self.wire_codec or None
        )
        # the host buffer's arena lease rides to the dispatch pipeline,
        # which retires it once the step's fetch delivers (apps/common.py)
        return PackedBatch(
            jax.device_put(
                pb.buffer, NamedSharding(self.mesh, P(self.data_axis))
            ),
            pb.layout,
        )._with_lease(pb._lease)

    def _packed_rows(self, pb: PackedBatch) -> int:
        """Global row count recorded in a RaggedShardSegments layout."""
        if pb.layout[0] != "RaggedShardSegments":
            raise ValueError(
                "mesh models take the per-shard packed layout "
                "(pack_for_wire), not the flat pack_batch buffer"
            )
        s = pb.layout[2][1]
        if s != self.num_data:
            raise ValueError(
                f"packed buffer is laid out for {s} shards; this mesh's "
                f"data axis is {self.num_data}"
            )
        return pb.layout[1][4][0][0] * s  # per-shard mask rows × shards

    def step(
        self, batch: FeatureBatch | UnitBatch | RaggedUnitBatch | PackedBatch
    ) -> StepOutput:
        if isinstance(batch, PackedBatch):
            self._check_rows(self._packed_rows(batch))
            if not isinstance(batch.buffer, jax.Array):
                batch = PackedBatch(
                    jax.device_put(
                        batch.buffer,
                        NamedSharding(self.mesh, P(self.data_axis)),
                    ),
                    batch.layout,
                )
            self._wire_sharding = batch.buffer.sharding
        else:
            self._check_rows(batch.mask.shape[0])
            if (
                isinstance(batch, RaggedUnitBatch)
                and batch.num_shards != self.num_data
            ):
                # host ragged batch straight from a featurizer: re-lay into
                # per-shard segments + place (a no-op for pre-aligned
                # batches, e.g. the multi-host global assembly)
                batch = shard_batch(batch, self.mesh)
        self._weights, out = self._step_for(type(batch))(self._weights, batch)
        return out

    def train_on(self, stream) -> None:
        stream.foreach_batch(lambda batch, _time: self.step(batch))
