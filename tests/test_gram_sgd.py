"""Differential tests: the Gram-domain (dual) sparse SGD loop (ops/gram.py)
against the per-iteration gather/scatter formulation — the two are the same
recursion in different bases, so multi-step weight trajectories must agree to
float tolerance across every parity-critical semantic: √-decay step sizes,
SquaredL2Updater pre-scale (including entries the batch never touches),
Bernoulli mini-batch sampling, convergence freeze, zero-sample skip, and the
logistic residual. ``gram_matrix`` itself is pinned against the dense
densify-matmul reference, including the cond-gated two-plane split for
counts > 255 and non-integral token values."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from twtml_tpu.features.batch import NUM_NUMBER_FEATURES, FeatureBatch, UnitBatch
from twtml_tpu.models.logistic import StreamingLogisticRegressionWithSGD
from twtml_tpu.models.sgd import make_sgd_train_step, zero_weights
from twtml_tpu.ops import gram as gram_ops
from twtml_tpu.ops.gram import fits_gram, gram_matrix
from twtml_tpu.ops.sparse import densify_text


def text_gram(*args, **kwargs):
    """``(G, plane)``: the switch with G alone as its body."""
    return gram_ops.text_gram(*args, body=gram_ops.CountPlane.gram, **kwargs)


F_TEXT = 512  # small enough for fast CPU tests; forced sparse via use_sparse


def random_batch(rng, b=24, l=12, f_text=F_TEXT, label_scale=50.0):
    token_idx = rng.integers(0, f_text, size=(b, l)).astype(np.int32)
    token_val = rng.integers(1, 4, size=(b, l)).astype(np.float32)
    # padded token slots: idx 0, val 0 (the batch contract)
    token_val[:, l - 2 :] = 0.0
    token_idx[:, l - 2 :] = 0
    numeric = rng.normal(size=(b, NUM_NUMBER_FEATURES)).astype(np.float32) * 0.1
    label = rng.uniform(0, label_scale, size=(b,)).astype(np.float32)
    mask = np.ones((b,), np.float32)
    mask[b - 3 :] = 0.0  # padding rows
    token_val[b - 3 :] = 0.0
    numeric[b - 3 :] = 0.0
    label[b - 3 :] = 0.0
    return FeatureBatch(token_idx, token_val, numeric, label, mask)


def run_chain(step, batches, w0):
    w = jnp.asarray(w0)
    outs = []
    for b in batches:
        w, out = step(w, b)
        outs.append(out)
    return np.asarray(w), outs


def both_paths(batches, w0, **kw):
    kw.setdefault("num_text_features", F_TEXT)
    kw.setdefault("use_sparse", True)
    kw.setdefault("num_iterations", 25)
    kw.setdefault("step_size", 0.05)
    scatter = make_sgd_train_step(use_gram=False, **kw)
    gram = make_sgd_train_step(use_gram=True, **kw)
    w_s, out_s = run_chain(scatter, batches, w0)
    w_g, out_g = run_chain(gram, batches, w0)
    return (w_s, out_s), (w_g, out_g)


def assert_trajectories_match(res_s, res_g, rtol=2e-4, atol=2e-4):
    (w_s, out_s), (w_g, out_g) = res_s, res_g
    scale = max(1.0, float(np.max(np.abs(w_s))))
    np.testing.assert_allclose(w_g, w_s, rtol=rtol, atol=atol * scale)
    for a, b in zip(out_s, out_g):
        # predictions are pre-update in both paths — identical math
        np.testing.assert_allclose(
            np.asarray(b.predictions), np.asarray(a.predictions), rtol=1e-5, atol=1e-4
        )
        np.testing.assert_allclose(float(b.mse), float(a.mse), rtol=1e-4, atol=1e-3)


def test_gram_matrix_matches_dense_reference():
    rng = np.random.default_rng(0)
    batch = random_batch(rng)
    dense = np.asarray(
        densify_text(jnp.asarray(batch.token_idx), jnp.asarray(batch.token_val), F_TEXT)
    )
    z = np.concatenate([dense, batch.numeric], axis=1)
    ref = z @ z.T
    got = np.asarray(
        gram_matrix(
            jnp.asarray(batch.token_idx),
            jnp.asarray(batch.token_val),
            jnp.asarray(batch.numeric),
            F_TEXT,
        )
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)


def test_gram_matrix_two_plane_split_counts_above_255():
    rng = np.random.default_rng(1)
    batch = random_batch(rng)
    token_val = batch.token_val.copy()
    token_idx = batch.token_idx.copy()
    token_idx[0, :5] = 7  # duplicate feature occurrences...
    token_val[0, :5] = 100.0  # ...summing to 500 > 255: bf16-inexact count
    dense = np.asarray(densify_text(jnp.asarray(token_idx), jnp.asarray(token_val), F_TEXT))
    z = np.concatenate([dense, batch.numeric], axis=1)
    ref = z @ z.T
    got = np.asarray(
        gram_matrix(
            jnp.asarray(token_idx),
            jnp.asarray(token_val),
            jnp.asarray(batch.numeric),
            F_TEXT,
        )
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-2)


def test_gram_matrix_int8_plane_is_bit_exact():
    """Row absolute mass ≤ 127 rides the s8×s8→s32 plane: integer
    accumulation end-to-end, so the text block must equal the dense integer
    reference EXACTLY (not allclose — the int8 plane does no rounding)."""
    rng = np.random.default_rng(20)
    batch = random_batch(rng)  # vals in {1,2,3}, L=12 ⇒ mass ≤ 36 ≤ 127
    assert np.all(np.sum(np.abs(batch.token_val), axis=1) <= 127.0)
    dense = np.asarray(
        densify_text(jnp.asarray(batch.token_idx), jnp.asarray(batch.token_val), F_TEXT)
    )
    ref = dense @ dense.T
    got = np.asarray(
        text_gram(jnp.asarray(batch.token_idx), jnp.asarray(batch.token_val), F_TEXT)[0]
    )
    np.testing.assert_array_equal(got, ref)


def test_gram_matrix_int8_gate_mixed_sign_boundary():
    """Mixed-sign rows at the gate edge: absolute mass exactly 127 rides the
    int8 plane (bit-exact, array_equal); mass 128 falls to the bf16 plane
    (still correct — counts here are small, so bf16 is exact too; the test
    that actually DISTINGUISHES the planes at the boundary is
    test_gram_matrix_int8_gate_count_wrap_boundary's sign witness)."""
    for vals, exact in [([60.0, -60.0, 7.0, 0.0], True),
                        ([64.0, -57.0, 7.0, 0.0], False)]:
        token_idx = np.array([[3, 3, 9, 11]], np.int32)
        token_val = np.array([vals], np.float32)
        dense = np.asarray(
            densify_text(jnp.asarray(token_idx), jnp.asarray(token_val), F_TEXT)
        )
        ref = dense @ dense.T
        got = np.asarray(text_gram(jnp.asarray(token_idx), jnp.asarray(token_val), F_TEXT)[0])
        if exact:
            np.testing.assert_array_equal(got, ref)
        else:
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-2)


def test_gram_matrix_int8_gate_count_wrap_boundary():
    """A per-feature count at the int8 edge, witnessed through an
    OFF-DIAGONAL entry (squares hide a ±wrap: (−128)² = 128²). Two rows
    share feature 7; row0's count is 127 (int8-exact, must be array-equal)
    or 128 (would wrap to −128 if the gate admitted it — G[0,1] flips sign,
    so a gate loosened to ≤128, or a wrong narrowing dtype, fails here)."""
    for count, exact in [(127.0, True), (128.0, False)]:
        token_idx = np.array([[7, 0], [7, 0]], np.int32)
        token_val = np.array([[count, 0.0], [1.0, 0.0]], np.float32)
        got = np.asarray(
            text_gram(jnp.asarray(token_idx), jnp.asarray(token_val), F_TEXT)[0]
        )
        expected = np.array([[count * count, count], [count, 1.0]], np.float32)
        if exact:
            np.testing.assert_array_equal(got, expected)
        else:
            np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-2)
        assert got[0, 1] > 0.0  # the wrap witness: sign must not flip


def test_gram_matrix_int8_plane_disabled_still_matches():
    """int8_plane=False rebuilds the r3 two-plane program (the bench A/B
    baseline) and stays on the reference."""
    rng = np.random.default_rng(21)
    batch = random_batch(rng)
    dense = np.asarray(
        densify_text(jnp.asarray(batch.token_idx), jnp.asarray(batch.token_val), F_TEXT)
    )
    ref = dense @ dense.T
    got = np.asarray(
        text_gram(
            jnp.asarray(batch.token_idx),
            jnp.asarray(batch.token_val),
            F_TEXT,
            int8_plane=False,
        )[0]
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)


def test_gram_matrix_fractional_values():
    rng = np.random.default_rng(2)
    batch = random_batch(rng)
    token_val = batch.token_val * 0.37  # non-integral: one bf16 plane can't hold it
    dense = np.asarray(densify_text(jnp.asarray(batch.token_idx), jnp.asarray(token_val), F_TEXT))
    z = np.concatenate([dense, batch.numeric], axis=1)
    ref = z @ z.T
    got = np.asarray(
        gram_matrix(
            jnp.asarray(batch.token_idx),
            jnp.asarray(token_val),
            jnp.asarray(batch.numeric),
            F_TEXT,
        )
    )
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-2)


def test_multi_batch_trajectory_matches_scatter():
    rng = np.random.default_rng(3)
    batches = [random_batch(rng) for _ in range(4)]
    w0 = zero_weights(F_TEXT)
    res = both_paths(batches, w0)
    assert_trajectories_match(*res)


def test_l2_scales_untouched_weights_identically():
    """W_prev entries the batch never references must shrink by the exact
    per-iteration (1 − η·λ) product — the lazy c-scale of the dual basis
    against the scatter loop's explicit full-vector scaling."""
    rng = np.random.default_rng(4)
    # tokens confined to [0, 64): features ≥ 64 are untouched by every batch
    batches = []
    for _ in range(3):
        b = random_batch(rng)
        batches.append(b._replace(token_idx=(b.token_idx % 64).astype(np.int32)))
    w0 = rng.normal(size=(F_TEXT + NUM_NUMBER_FEATURES,)).astype(np.float32)
    res_s, res_g = both_paths(batches, w0, l2_reg=0.05, convergence_tol=0.0)
    assert_trajectories_match(res_s, res_g)
    # untouched entries did change (the L2 shrink really applied)...
    w_s = res_s[0]
    assert not np.allclose(w_s[64:F_TEXT], w0[64:F_TEXT])
    # ...multiplicatively, by the same factor everywhere
    ratio = w_s[64:F_TEXT] / w0[64:F_TEXT]
    np.testing.assert_allclose(ratio, ratio[0], rtol=1e-5)


def test_mini_batch_sampling_matches():
    rng = np.random.default_rng(5)
    batches = [random_batch(rng) for _ in range(3)]
    res = both_paths(batches, zero_weights(F_TEXT), mini_batch_fraction=0.5)
    assert_trajectories_match(*res)


def test_convergence_freeze_matches():
    """A tight tolerance freezes both formulations at the same iteration;
    trajectories (and therefore the frozen weights) agree."""
    rng = np.random.default_rng(6)
    batches = [random_batch(rng, label_scale=1.0)]
    res = both_paths(
        batches, zero_weights(F_TEXT), convergence_tol=0.05, num_iterations=50
    )
    assert_trajectories_match(*res)


def test_zero_valid_batch_is_identity():
    rng = np.random.default_rng(7)
    b = random_batch(rng)
    empty = b._replace(mask=np.zeros_like(b.mask))
    w0 = rng.normal(size=(F_TEXT + NUM_NUMBER_FEATURES,)).astype(np.float32)
    step = make_sgd_train_step(
        num_text_features=F_TEXT, use_sparse=True, use_gram=True,
        num_iterations=10, step_size=0.05, l2_reg=0.1,
    )
    w1, _ = step(jnp.asarray(w0), empty)
    np.testing.assert_allclose(np.asarray(w1), w0, rtol=1e-6, atol=0)


def test_logistic_residual_matches():
    rng = np.random.default_rng(8)
    batches = []
    for _ in range(3):
        b = random_batch(rng)
        batches.append(b._replace(label=(b.label > 25).astype(np.float32) * b.mask))
    cls = StreamingLogisticRegressionWithSGD
    res = both_paths(
        batches,
        zero_weights(F_TEXT),
        residual_fn=cls.residual_fn,
        prediction_fn=cls.prediction_fn,
        round_predictions=cls.round_predictions,
        step_size=0.5,
    )
    assert_trajectories_match(*res)


def test_unit_batch_rides_gram_path():
    """UnitBatch → on-device hash → Gram loop equals the same UnitBatch
    through the scatter loop (hash runs in both programs identically)."""
    rng = np.random.default_rng(9)
    texts = ["tpu stream %d" % i for i in range(8)]
    units = np.zeros((8, 16), np.uint16)
    length = np.zeros((8,), np.int32)
    for i, t in enumerate(texts):
        enc = np.frombuffer(t.encode("utf-16-le"), np.uint16)
        units[i, : len(enc)] = enc
        length[i] = len(enc)
    batch = UnitBatch(  # jnp arrays: the step runs unjitted in this test
        jnp.asarray(units),
        jnp.asarray(length),
        rng.normal(size=(8, NUM_NUMBER_FEATURES)).astype(np.float32) * 0.1,
        rng.uniform(0, 50, size=(8,)).astype(np.float32),
        np.ones((8,), np.float32),
    )
    res = both_paths([batch], zero_weights(F_TEXT))
    assert_trajectories_match(*res)


def test_gram_matrix_mixed_sign_values_stay_exact():
    """Row-sum cancellation must not fool the bf16-exactness gate: mixed-sign
    integral values whose sum is small but whose per-feature count magnitude
    exceeds 255 must take the exact fallback."""
    token_idx = np.array([[7, 7, 9, 0]], np.int32)
    token_val = np.array([[150.0, 151.0, -200.0, 0.0]], np.float32)
    numeric = np.zeros((1, NUM_NUMBER_FEATURES), np.float32)
    got = np.asarray(
        gram_matrix(
            jnp.asarray(token_idx),
            jnp.asarray(token_val),
            jnp.asarray(numeric),
            F_TEXT,
        )
    )
    # exact: 301² + 200² = 130601
    np.testing.assert_allclose(got[0, 0], 301.0**2 + 200.0**2, rtol=1e-6)


def test_bfloat16_weights_run_the_gram_loop():
    """Explicit use_gram with bf16 weights must trace (type-stable fori_loop
    carry) and track the bf16 scatter path."""
    rng = np.random.default_rng(11)
    batches = [random_batch(rng) for _ in range(2)]
    w0 = zero_weights(F_TEXT, dtype=jnp.bfloat16)
    (w_s, _), (w_g, _) = both_paths(batches, w0)
    np.testing.assert_allclose(
        np.asarray(w_g, np.float32), np.asarray(w_s, np.float32),
        rtol=0.1, atol=0.1,  # bf16 trajectories diverge fast; same ballpark
    )


def test_auto_gate_is_f32_only():
    """The default path must not auto-select Gram for non-f32 weights (the
    bf16-plane G build would silently change f64 semantics)."""
    rng = np.random.default_rng(12)
    b = random_batch(rng)
    step = make_sgd_train_step(
        num_text_features=F_TEXT, use_sparse=True,
        num_iterations=5, step_size=0.05,
    )
    # bf16 weights trace and run through the (auto-selected) scatter loop
    w0 = zero_weights(F_TEXT, dtype=jnp.bfloat16)
    w1, _ = step(jnp.asarray(w0), b)
    assert w1.dtype == jnp.bfloat16


def test_feature_sharded_gram_sampling_matches_single_device():
    """2D (data × model) mesh with fraction < 1: the gram path's one global
    mask must bit-match the single-device gram trajectory."""
    import jax

    from twtml_tpu.parallel import ParallelSGDModel, make_mesh
    from twtml_tpu.parallel.sharding import shard_batch

    rng = np.random.default_rng(15)
    batches = [random_batch(rng, b=32) for _ in range(2)]
    single = make_sgd_train_step(
        num_text_features=F_TEXT, use_sparse=True, use_gram=True,
        num_iterations=20, step_size=0.05, mini_batch_fraction=0.5, l2_reg=0.01,
    )
    w_ref, _ = run_chain(single, batches, zero_weights(F_TEXT))

    mesh = make_mesh(num_data=2, num_model=4)
    model = ParallelSGDModel(
        mesh, num_text_features=F_TEXT, num_iterations=20, step_size=0.05,
        mini_batch_fraction=0.5, l2_reg=0.01, use_gram=True,
    )
    for b in batches:
        model.step(shard_batch(b, mesh))
    np.testing.assert_allclose(model.latest_weights, w_ref, rtol=2e-4, atol=2e-4)


def test_feature_sharded_gram_vs_scatter():
    """Same 2D mesh, gram vs scatter formulations agree (fraction=1 so the
    sampling layouts coincide)."""
    import jax

    from twtml_tpu.parallel import ParallelSGDModel, make_mesh
    from twtml_tpu.parallel.sharding import shard_batch

    rng = np.random.default_rng(16)
    batches = [random_batch(rng, b=32) for _ in range(2)]
    mesh = make_mesh(num_data=2, num_model=4)
    kw = dict(
        num_text_features=F_TEXT, num_iterations=15, step_size=0.05, l2_reg=0.02
    )
    m_gram = ParallelSGDModel(mesh, use_gram=True, **kw)
    m_scat = ParallelSGDModel(mesh, use_gram=False, **kw)
    for b in batches:
        sb = shard_batch(b, mesh)
        og, os_ = m_gram.step(sb), m_scat.step(sb)
        np.testing.assert_allclose(float(og.mse), float(os_.mse), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(
        m_gram.latest_weights, m_scat.latest_weights, rtol=2e-4, atol=2e-4
    )


def test_full_scale_2e18_gram_matches_scatter():
    """Both formulations at the REAL feature width (2^18) through the
    default wire format (units → device hash): the full-scale shapes the
    bench runs, pinned to each other."""
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.sources import SyntheticSource

    statuses = list(
        SyntheticSource(total=48, seed=3, base_ms=1785320000000).produce()
    )
    feat = Featurizer(num_text_features=2**18, now_ms=1785320000000)
    batch = feat.featurize_batch_units(statuses, row_bucket=48, pre_filtered=True)
    kw = dict(
        num_text_features=2**18, num_iterations=5, step_size=0.005, l2_reg=0.1
    )
    w0 = zero_weights(2**18)
    scatter = make_sgd_train_step(use_gram=False, **kw)
    gram = make_sgd_train_step(use_gram=True, **kw)
    w_s, out_s = jax.jit(scatter)(w0, batch)
    w_g, out_g = jax.jit(gram)(w0, batch)
    assert float(out_g.mse) == float(out_s.mse)
    np.testing.assert_allclose(np.asarray(w_g), np.asarray(w_s), rtol=1e-4, atol=1e-7)


def test_randomized_config_sweep_matches_scatter():
    """Property-style sweep: random knob combinations (step size, L2,
    sampling fraction, convergence tol, iterations, batch/token shapes,
    value ranges) — every one must keep the two formulations together.
    Seeded, so a failure names its config and reproduces exactly."""
    rng = np.random.default_rng(2026)
    for trial in range(6):
        knobs = dict(
            num_iterations=int(rng.integers(4, 30)),
            step_size=float(rng.choice([0.005, 0.05, 0.2])),
            l2_reg=float(rng.choice([0.0, 0.01, 0.1])),
            mini_batch_fraction=float(rng.choice([1.0, 0.7, 0.4])),
            convergence_tol=float(rng.choice([0.0, 0.001, 0.05])),
        )
        b = int(rng.integers(8, 40))
        l = int(rng.integers(4, 20))
        batches = [
            random_batch(rng, b=b, l=l, label_scale=float(rng.choice([5.0, 500.0])))
            for _ in range(2)
        ]
        w0 = (rng.normal(size=(F_TEXT + NUM_NUMBER_FEATURES,)) * 0.1).astype(
            np.float32
        )
        try:
            res = both_paths(batches, w0, **knobs)
            assert_trajectories_match(*res)
        except AssertionError as exc:  # name the failing config
            raise AssertionError(f"trial {trial} knobs={knobs} b={b} l={l}: {exc}")


def test_auto_gate_picks_gram_only_when_it_fits():
    assert fits_gram(2048, 2**18, 50)
    assert not fits_gram(2048, 2**18, 2)  # too few iterations to amortize
    assert not fits_gram(1 << 20, 2**18, 50)  # dense counts exceed HBM budget


def test_data_axis_gram_matches_single_device():
    """Row-sharded Gram (all-gathered batch, sharded G row panels, replicated
    dual loop) must reproduce the single-device trajectory: same global
    batch, same unfolded sampling key — the collectives are the only
    difference."""
    import jax

    from twtml_tpu.parallel import ParallelSGDModel, make_mesh
    from twtml_tpu.parallel.sharding import shard_batch

    rng = np.random.default_rng(13)
    batches = [random_batch(rng, b=32) for _ in range(3)]

    single = make_sgd_train_step(
        num_text_features=F_TEXT, use_sparse=True, use_gram=True,
        num_iterations=25, step_size=0.05, l2_reg=0.01,
    )
    w_ref, outs_ref = run_chain(single, batches, zero_weights(F_TEXT))

    mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
    model = ParallelSGDModel(
        mesh, num_text_features=F_TEXT, num_iterations=25,
        step_size=0.05, l2_reg=0.01, use_sparse=True,
    )
    outs = [model.step(shard_batch(b, mesh)) for b in batches]
    np.testing.assert_allclose(
        model.latest_weights, w_ref, rtol=2e-4, atol=2e-4
    )
    for a, b in zip(outs_ref, outs):
        np.testing.assert_allclose(float(b.mse), float(a.mse), rtol=1e-4, atol=1e-3)


def test_data_axis_gram_sampling_matches_single_device():
    """fraction < 1: the gram data-axis path draws ONE global mask with the
    unfolded key, so it must bit-match the single-device gram trajectory
    (the scatter loop's per-shard folded keys only match statistically)."""
    import jax

    from twtml_tpu.parallel import ParallelSGDModel, make_mesh
    from twtml_tpu.parallel.sharding import shard_batch

    rng = np.random.default_rng(14)
    batches = [random_batch(rng, b=32) for _ in range(2)]
    single = make_sgd_train_step(
        num_text_features=F_TEXT, use_sparse=True, use_gram=True,
        num_iterations=20, step_size=0.05, mini_batch_fraction=0.5,
    )
    w_ref, _ = run_chain(single, batches, zero_weights(F_TEXT))

    mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
    model = ParallelSGDModel(
        mesh, num_text_features=F_TEXT, num_iterations=20,
        step_size=0.05, mini_batch_fraction=0.5, use_sparse=True,
    )
    for b in batches:
        model.step(shard_batch(b, mesh))
    np.testing.assert_allclose(model.latest_weights, w_ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# PR 25: the bf16 gate per (row, feature) — the plane is the index
# ``text_gram`` hands out with G (0 exact, 1 bf16, 2 s8)

GATE_L = 512  # the row length a 280-unit text is padded to


def _gate_rows(first_idx, first_val, short_rows=3):
    """Row 0 as given (padded to GATE_L with the batch contract's
    ``(idx 0, val 0.0)`` slots) among short rows of 20 unit tokens."""
    rng = np.random.default_rng(25)
    idx = np.zeros((1 + short_rows, GATE_L), np.int32)
    val = np.zeros((1 + short_rows, GATE_L), np.float32)
    idx[0, : len(first_idx)] = first_idx
    val[0, : len(first_val)] = first_val
    for r in range(1, 1 + short_rows):
        idx[r, :20] = rng.integers(1, F_TEXT, size=20)
        val[r, :20] = 1.0
    return idx, val


def _gate_case(name):
    """(token_idx, token_val, plane the gate must take)."""
    ones = lambda n: np.ones(n, np.float32)
    if name == "row_of_279_distinct_bigrams":   # a 280-unit tweet
        return *_gate_rows(np.arange(1, 280), ones(279)), 1
    if name == "count_256_on_one_feature":
        return *_gate_rows(np.r_[np.full(256, 7), np.arange(8, 28)], ones(276)), 1
    if name == "count_257_on_one_feature":
        return *_gate_rows(np.r_[np.full(257, 7), np.arange(8, 28)], ones(277)), 0
    if name == "pads_have_multiplicity_not_mass":
        # 20 real tokens and 492 pad slots at index 0, in a batch of such
        # rows: nothing reaches rung 1's limit, the s8 plane stands
        return *_gate_rows(np.arange(1, 21), ones(20)), 2
    if name == "pads_beside_a_long_row":
        # rung 2 is reached (row 0) and must not count the short rows' 492
        # repeats of (0, 0.0), nor row 0's own 233
        return *_gate_rows(np.arange(0, 279), ones(279)), 1
    if name == "mixed_signs_on_one_feature":
        # +200 −100: the count is 100, the absolute mass 300
        return *_gate_rows(
            np.r_[7, 7, np.arange(8, 108)], np.r_[200.0, -100.0, ones(100)]), 0
    if name == "row_mass_above_65536":
        # 257 distinct features of count 256: every count passes, G does not
        return *_gate_rows(np.arange(1, 258), np.full(257, 256.0, np.float32)), 0
    if name == "fractional_values":
        return *_gate_rows(np.arange(1, 280), np.full(279, 0.5, np.float32)), 0
    raise KeyError(name)


GATE_CASES = [
    "row_of_279_distinct_bigrams", "count_256_on_one_feature",
    "count_257_on_one_feature", "pads_have_multiplicity_not_mass",
    "pads_beside_a_long_row", "mixed_signs_on_one_feature",
    "row_mass_above_65536", "fractional_values",
]


def _dense64(token_idx, token_val):
    dense = np.zeros((token_idx.shape[0], F_TEXT), np.float64)
    for r in range(token_idx.shape[0]):
        np.add.at(dense[r], token_idx[r], token_val[r].astype(np.float64))
    return dense @ dense.T


@pytest.mark.parametrize("name", GATE_CASES)
def test_gate_takes_the_plane_its_proof_covers(name):
    """Per (row, feature) absolute mass ≤ 256 and row mass ≤ 65,536 ⇒ the
    bf16 plane, whose G is then the exact plane's and the float64
    reference's integer for integer; anything the proof does not cover ⇒
    the exact plane."""
    from jax import lax

    token_idx, token_val, want = _gate_case(name)
    g, plane = jax.jit(lambda i, v: text_gram(i, v, F_TEXT))(token_idx, token_val)
    assert int(plane) == want
    ref = _dense64(token_idx, token_val)
    if want >= 1:
        np.testing.assert_array_equal(np.asarray(g, np.float64), ref)
        c = densify_text(jnp.asarray(token_idx), jnp.asarray(token_val), F_TEXT)
        exact = jnp.matmul(c, c.T, precision=lax.Precision.HIGHEST)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(exact))
    else:
        np.testing.assert_allclose(np.asarray(g, np.float64), ref, rtol=1e-6)


def test_gate_count_257_is_not_rounded_to_256():
    """What the gate is for: bf16 holds 256 and not 257, so a count of 257
    on the bf16 plane would give G[0,0] = 256² + … — the exact plane gives
    257² + 20, to the unit."""
    from twtml_tpu.ops.gram import onehot_counts

    token_idx, token_val, _ = _gate_case("count_257_on_one_feature")
    g, plane = text_gram(jnp.asarray(token_idx), jnp.asarray(token_val), F_TEXT)
    assert int(plane) == 0 and float(g[0, 0]) == 257.0**2 + 20.0
    c = onehot_counts(jnp.asarray(token_idx), jnp.asarray(token_val), F_TEXT)
    # feature 7 of row 0 in the [B, k_hi, k_lo] the build writes: the
    # rounding the gate keeps out
    assert float(c[(0, *divmod(7, c.shape[2]))]) == 256.0


def test_gate_without_the_int8_plane_takes_bf16_where_s8_would_do():
    token_idx, token_val, _ = _gate_case("pads_have_multiplicity_not_mass")
    _g, plane = text_gram(
        jnp.asarray(token_idx), jnp.asarray(token_val), F_TEXT, int8_plane=False
    )
    assert int(plane) == 1


def test_gate_on_a_feature_slice_ignores_clipped_zeroed_tokens(monkeypatch):
    """The 2-D sharded caller clips out-of-slice tokens to its slice's edge
    with their value zeroed: hundreds of repeats of one index that carry no
    mass. Row 0 holds 300 distinct unit features in slice 0 and 300 in
    slice 2 of four: on those two model shards the slice's row mass is 300
    and 300 clipped tokens share one index, on the other two every token
    of the row is clipped. The gate reads the WHOLE row's figures (reduced
    over ``model``, PR 27: mass 600, every count 1), so every shard takes
    the bf16 plane by rung 2 — none the exact one, none s8 on its own
    slice's lighter share — and the weights are the single-device step's."""
    from twtml_tpu.parallel import ParallelSGDModel, make_mesh
    from twtml_tpu.parallel import sharding
    from twtml_tpu.parallel.sharding import shard_batch

    f_text, b, l = 2048, 8, 640
    rng = np.random.default_rng(26)
    token_idx = np.zeros((b, l), np.int32)
    token_val = np.zeros((b, l), np.float32)
    token_idx[0, :600] = np.r_[np.arange(0, 300), np.arange(1024, 1324)]
    token_val[0, :600] = 1.0
    for r in range(1, b):
        token_idx[r, :20] = rng.integers(0, f_text, size=20)
        token_val[r, :20] = 1.0
    batch = FeatureBatch(
        token_idx, token_val,
        rng.normal(size=(b, NUM_NUMBER_FEATURES)).astype(np.float32) * 0.1,
        rng.uniform(0, 50, size=(b,)).astype(np.float32),
        np.ones((b,), np.float32),
    )
    kw = dict(num_text_features=f_text, num_iterations=10, step_size=0.001)
    single = make_sgd_train_step(use_sparse=True, use_gram=True, quality=True, **kw)
    w_ref, out = single(zero_weights(f_text), batch)
    assert float(out.quality[-1]) == 1.0   # the whole row: mass 600, counts 1

    planes = []
    real = sharding.text_gram

    def watched(*args, **kwargs):
        g, plane = real(*args, **kwargs)
        jax.debug.callback(lambda p: planes.append(int(p)), plane)
        return g, plane

    monkeypatch.setattr(sharding, "text_gram", watched)
    mesh = make_mesh(num_data=2, num_model=4)
    model = ParallelSGDModel(mesh, use_gram=True, **kw)
    model.step(shard_batch(batch, mesh))
    jax.effects_barrier()
    np.testing.assert_allclose(
        model.latest_weights, np.asarray(w_ref), rtol=2e-4, atol=2e-4
    )
    assert planes == [1] * 8   # every model shard, on both data shards


# ---------------------------------------------------------------------------
# PR 28: inside the Gram basis the text half of u = Z·W_prev and of Zᵀα are
# contractions with the plane's count matrix (ops/gram.CountPlane), not a
# gather from / a scatter into the [F] weights. The gather and the scatter
# (ops/sparse.py) are the references.

def _contraction_batch(rng, plane: str, b=32, l=12, f_text=F_TEXT):
    """Duplicate tokens within a row, mixed-sign values, pad slots
    ``(0, 0.0)``, a masked row, and tokens on both sides of every slice edge
    a 4-way model axis cuts (a feature-sharded step clips them to its slice
    with their value zeroed)."""
    idx = rng.integers(0, f_text, size=(b, l)).astype(np.int32)
    if plane == "exact":  # fractional values: only the f32 plane holds them
        val = rng.normal(size=(b, l)).astype(np.float32)
    elif plane == "bf16":  # integral, row mass > 127: not the s8 plane's
        val = rng.integers(1, 4, size=(b, l)).astype(np.float32)
        val[:, 0] = 130.0
    else:  # s8: integral, every row's absolute mass ≤ 127
        val = rng.integers(1, 4, size=(b, l)).astype(np.float32)
    val[:, 1] *= -1.0  # mixed signs
    idx[:, 2] = idx[:, 3]  # a feature twice in a row…
    idx[:, 4] = idx[:, 3]  # …and a third time
    edge = f_text // 4
    idx[:, 5] = rng.choice([edge - 1, edge, 2 * edge - 1, 2 * edge,
                            f_text - 1, 0], size=b)
    idx[:, l - 2:] = 0  # pad slots
    val[:, l - 2:] = 0.0
    numeric = rng.normal(size=(b, NUM_NUMBER_FEATURES)).astype(np.float32) * 0.1
    label = rng.uniform(0, 50.0, size=(b,)).astype(np.float32)
    mask = np.ones((b,), np.float32)
    mask[5] = 0.0  # a masked row that still carries tokens
    return FeatureBatch(idx, val, numeric, label, mask)


def _rel_l1(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sum(np.abs(got - want)) / np.sum(np.abs(want)))


def _round_bf16(x):
    return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))


_PLANE_INDEX = {"exact": 0, "bf16": 1, "s8": 2}


@pytest.mark.parametrize("plane, layout, weights", [
    (plane, layout, "normal")
    for plane in ("exact", "bf16", "s8") for layout in ("single", "data", "2x2")
] + [("bf16", layout, "low_bits") for layout in ("single", "data", "2x2")])
def test_gram_step_contracts_with_counts_like_gather_and_scatter(
    plane, layout, weights
):
    """The step's ``u`` against ``sparse_predict`` and its new weights
    against ``w·c + sparse_grad_text(…, α)`` (c, α from the shared dual loop
    on the reference's own u and G), at f32 tolerance: 1e-6 relative L1.
    ``low_bits``: weights of the form 1 + k·2⁻²⁰, whose whole signal sits
    under bf16's 8 bits — a contraction that rounded ``w`` (or ``α``) to
    bf16 is shown, on the same data, to miss the tolerance by ≥ 100x."""
    from twtml_tpu.models.sgd import run_dual_loop
    from twtml_tpu.ops.sparse import sparse_grad_text, sparse_predict
    from twtml_tpu.parallel import ParallelSGDModel, make_mesh
    from twtml_tpu.parallel.sharding import shard_batch

    rng = np.random.default_rng(2800 + 10 * _PLANE_INDEX[plane])
    batch = _contraction_batch(rng, plane)
    if weights == "low_bits":
        w0 = 1.0 + rng.integers(1, 256, size=F_TEXT + 4) * 2.0 ** -20
    else:
        w0 = rng.normal(size=F_TEXT + 4)
    w0 = w0.astype(np.float32)
    kw = dict(num_text_features=F_TEXT, num_iterations=8, step_size=0.02,
              l2_reg=0.1, use_gram=True, quality=True)

    if layout == "single":
        step = jax.jit(make_sgd_train_step(
            use_sparse=True, round_predictions=False, **kw))
        w_new, out = step(jnp.asarray(w0), batch)
        w_new = np.asarray(w_new)
    else:
        mesh = (make_mesh(num_data=4) if layout == "data"
                else make_mesh(num_data=2, num_model=2))
        model = ParallelSGDModel(
            mesh, use_sparse=True, round_predictions=False, **kw)
        model.set_initial_weights(w0)
        out = model.step(shard_batch(batch, mesh))
        w_new = model.latest_weights
    assert int(np.asarray(out.quality)[-1]) == _PLANE_INDEX[plane]

    # ---- the references: gather, scatter -------------------------------
    idx, val = jnp.asarray(batch.token_idx), jnp.asarray(batch.token_val)
    numeric = jnp.asarray(batch.numeric)
    w_text, w_num = jnp.asarray(w0[:F_TEXT]), jnp.asarray(w0[F_TEXT:])
    u_ref = sparse_predict(w_text, w_num, idx, val, numeric)
    assert _rel_l1(out.predictions, u_ref) <= 1e-6
    dual = run_dual_loop(
        u=u_ref, g=gram_matrix(idx, val, numeric, F_TEXT),
        labels=jnp.asarray(batch.label), mask=jnp.asarray(batch.mask),
        dtype=jnp.float32, residual_fn=lambda raw, label: raw - label,
        num_iterations=8, step_size=0.02, mini_batch_fraction=1.0,
        l2_reg=0.1, convergence_tol=0.001, p_prev=jnp.sum(w0 * w0),
    )
    c, alpha = dual["c"], dual["alpha"]

    def written_back(alpha):
        return np.concatenate([
            w_text * c + sparse_grad_text(idx, val, alpha, F_TEXT),
            w_num * c + numeric.T @ alpha,
        ])

    w_ref = written_back(alpha)
    # the update alone (w_new − c·w), so that weights the batch never
    # touches do not pad the denominator
    scaled = np.asarray(w0 * c)
    assert _rel_l1(w_new - scaled, w_ref - scaled) <= 1e-6
    assert _rel_l1(w_new, w_ref) <= 1e-6

    if weights == "low_bits":
        u_rounded = sparse_predict(
            jnp.asarray(_round_bf16(w_text)), w_num, idx, val, numeric)
        assert _rel_l1(u_rounded, u_ref) >= 1e-4
        w_rounded = written_back(jnp.asarray(_round_bf16(alpha)))
        assert _rel_l1(w_rounded - scaled, w_ref - scaled) >= 1e-4


# ---------------------------------------------------------------------------
# PR 30: the count matrix keeps the ``[B, k_hi, k_lo]`` its build writes and
# ``CountPlane`` contracts it over ``(hi, lo)``. The 2-D form it replaces —
# ``C.reshape(B, k_hi·k_lo)[:, :f_text]``, a matmul with ``Cᵀ``, row-wise
# and column-wise multiply-and-reduce — is the reference.

@pytest.mark.parametrize("rows", [0, 8])
@pytest.mark.parametrize("plane", ["exact", "bf16", "s8"])
@pytest.mark.parametrize("f_text, split", [
    (1 << 10, (32, 32)),   # square split
    (1 << 11, (32, 64)),   # k_lo = 2·k_hi: hash2e20's 2^19 slice
    (1000, (32, 32)),      # f_text < k_hi·k_lo: w zero-padded, delta cropped
])
def test_count_plane_contracts_the_built_shape_like_the_2d_form(
    f_text, split, plane, rows
):
    from jax import lax

    rng = np.random.default_rng(3000 + f_text + _PLANE_INDEX[plane])
    batch = _contraction_batch(rng, plane, f_text=f_text)
    idx, val = jnp.asarray(batch.token_idx), jnp.asarray(batch.token_val)
    b = idx.shape[0]
    w = jnp.asarray(rng.normal(size=f_text).astype(np.float32))
    alpha = jnp.asarray(rng.normal(size=rows or b).astype(np.float32))
    start = 16 if rows else None  # the third of four row shards

    width = split[0] * split[1]
    built = []  # (dtype, shape) of each plane's C, in the switch's order

    def body(counts):
        # PR 54: under a row panel C is BUILT as two arrays, the own rows
        # and the rest in rolled order (the rows after them, wrapping)
        parts = [counts.c_own] + ([counts.c_rest] if rows else [])
        assert (counts.c_rest is None) == (not rows)
        built.append([(part.dtype, part.shape) for part in parts])
        flat = jnp.concatenate(
            [part.astype(jnp.float32).reshape(part.shape[0], -1)
             for part in parts])
        flat = jnp.pad(flat, ((0, 0), (0, width - flat.shape[1])))
        return flat, counts.gram(), counts.dot(w), counts.tdot(alpha)

    (flat, g, u, delta), took = jax.jit(lambda i, v, r: gram_ops.text_gram(
        i, v, f_text, row_start=r, rows=rows, body=body))(idx, val, start)
    assert int(took) == _PLANE_INDEX[plane]
    dtype = {"exact": jnp.float32, "bf16": jnp.bfloat16, "s8": jnp.int8}[plane]
    features = (f_text,) if plane == "exact" else split
    assert built[_PLANE_INDEX[plane]] == [
        (dtype, (n, *features)) for n in ([rows, b - rows] if rows else [b])]

    # the 2-D form: C is the exact counts, and zero past f_text (the two
    # builds un-rolled to the batch's order first)
    flat = np.roll(np.asarray(flat), start or 0, axis=0)
    assert not flat[:, f_text:].any()
    c2 = jnp.asarray(flat[:, :f_text]).astype(dtype)
    np.testing.assert_array_equal(
        np.asarray(c2, np.float32), np.asarray(densify_text(idx, val, f_text)))
    panel = c2[start:start + rows] if rows else c2
    product = {
        "exact": dict(precision=lax.Precision.HIGHEST),
        "bf16": dict(preferred_element_type=jnp.float32),
        "s8": dict(preferred_element_type=jnp.int32),
    }[plane]
    g_ref = np.asarray(jnp.matmul(panel, c2.T, **product), np.float32)
    assert g.dtype == jnp.float32 and g.shape == (rows or b, b)
    if plane == "exact":  # an f32 sum of fractions, in another order
        np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-5, atol=1e-5)
    else:  # integers within f32: bit for bit
        np.testing.assert_array_equal(np.asarray(g), g_ref)
    panel = panel.astype(jnp.float32)
    assert u.shape == (rows or b,) and delta.shape == (f_text,)
    assert _rel_l1(u, jnp.sum(panel * w[None, :], axis=1)) <= 1e-6
    assert _rel_l1(delta, jnp.sum(panel * alpha[:, None], axis=0)) <= 1e-6


# ---------------------------------------------------------------------------
# PR 50: ``CountPlane.dot`` / ``.tdot`` take a leading MODEL axis (M arms on
# the same rows read C once a batch, models/sgd.py ``arms``). What they do
# follows from the operand's ``ndim`` and nothing else: a 1-D operand traces
# to the expression every other step has always run.

def _plane_of(plane: str, batch, f_text: int):
    """The plane's C as its builder writes it, as a ``CountPlane`` on one
    device (no rest, no row start)."""
    idx, val = jnp.asarray(batch.token_idx), jnp.asarray(batch.token_val)
    c = {
        "exact": lambda: densify_text(idx, val, f_text),
        "bf16": lambda: gram_ops.onehot_counts(idx, val, f_text),
        "s8": lambda: gram_ops.onehot_counts_int8(idx, val, f_text),
    }[plane]()
    return c, lambda c: gram_ops.CountPlane(c, None, None, f_text)


def _parents_dot(c, w):
    """``CountPlane.dot`` as PR 30 left it, written out."""
    shape = c.shape[1:]
    w = jnp.pad(w, (0, int(np.prod(shape)) - w.shape[0])).reshape(shape)
    return jnp.sum(c.astype(jnp.float32) * w[None],
                   axis=tuple(range(1, c.ndim)))


def _parents_tdot(f_text):
    """``CountPlane.tdot`` as PR 30 left it, written out."""
    def tdot(c, alpha):
        panel = c.astype(jnp.float32)
        delta = jnp.sum(
            panel * jnp.expand_dims(alpha, tuple(range(1, c.ndim))), axis=0)
        return delta.reshape(-1)[:f_text]
    return tdot


@pytest.mark.parametrize("which", ["dot", "tdot"])
@pytest.mark.parametrize("plane", ["exact", "bf16", "s8"])
def test_count_plane_contracts_all_models_at_once_like_each_alone(
    plane, which
):
    """The ``[M, …]`` call against the M 1-D calls, each compiled as a
    program of its own: BIT FOR BIT on the CPU backend, on every plane (the
    2-D form is M sibling reductions, each the 1-D expression, so nothing
    but the compiler's fusion could reorder a sum; here it does not). And
    the 1-D call's jaxpr is the parent's expression's, character for
    character: the six cells that run one model keep their programs."""
    f_text = 1000  # < k_hi·k_lo: w zero-padded going in, delta cropped
    rng = np.random.default_rng(5000 + _PLANE_INDEX[plane])
    batch = _contraction_batch(rng, plane, f_text=f_text)
    c, plane_of = _plane_of(plane, batch, f_text)
    m, b = 4, c.shape[0]
    operand = jnp.asarray(rng.normal(
        size=(m, f_text if which == "dot" else b)).astype(np.float32))

    def contract(c, x):
        return getattr(plane_of(c), which)(x)

    together = jax.jit(contract)(c, operand)
    assert together.dtype == jnp.float32
    assert together.shape == (m, b if which == "dot" else f_text)
    alone = jax.jit(contract)
    for k in range(m):
        assert np.asarray(together[k]).tobytes() == np.asarray(
            alone(c, operand[k])).tobytes(), (plane, which, k)
    parents = _parents_dot if which == "dot" else _parents_tdot(f_text)
    assert str(jax.make_jaxpr(contract)(c, operand[0])) == str(
        jax.make_jaxpr(parents)(c, operand[0]))
    # and it is the contraction: against the f64 sum of the same products
    flat = np.asarray(c, np.float64).reshape(b, -1)[:, :f_text]
    x = np.asarray(operand, np.float64)
    want = x @ flat.T if which == "dot" else x @ flat
    assert _rel_l1(together, want) <= 1e-6
