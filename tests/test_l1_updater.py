"""MLlib's ``L1Updater`` (``--l1Reg``; PR 55, configuration ``lasso2e18``):
the one learner whose iterations cannot run in the Gram (dual) basis, at
sizes a CPU holds.

(a) the update rule, in ``sgd_inner_loop``, to the letter: the gradient
    step then the soft threshold on EVERY leaf; a weight that crosses zero,
    a weight pinned at zero that a later batch revives; the zero-sample
    skip, the convergence test, the converged-freeze and the count of
    rounds that ran;
(b) the fused pass (``ops/primal_pass.py``, interpreted) against
    ``CountPlane.dot`` + ``.tdot`` on all three planes, in the tiling the
    chip runs and in the interpreter's whole-row tiling;
(c) the program against its plain reference
    (``benchmark/reference/lasso_sgd.py``: NumPy, float64, shares nothing)
    on seeded streams, F = 4,096 and 2^18 in rows of 8, on all three planes,
    with the XLA pass a CPU takes and with the kernel in the loop; the
    reference's bf16 control, the L2 updater, the threshold left off the
    numeric weights and 49 iterations are each NOT it;
(d) ``--l1Reg 0`` lowers every standing step to the parent's program;
(e) the flag's parse, its refusals and their sentences;
(f) checkpoint → ``load_servable`` → resume, bit for bit;
(g) the ``primal`` instant, the gauges and ``weights_zero_share``.

The harness-level cases of the cell are ``benchmark/tests/test_lasso2e18.py``.
"""

from __future__ import annotations

import hashlib
import json
import re

import numpy as np
import pytest

from benchmark import gen
from benchmark import spans as span_files
from benchmark.reference import lasso_sgd as ref
from test_tenant_deployment import _generator, _run_app, _stream, _weights
from twtml_tpu.config import ConfArguments
from twtml_tpu.telemetry import metrics as _metrics

L1 = ["--l2Reg", "0.0", "--l1Reg", "0.1"]
MODEL = {"numTextFeatures": 4096, "numIterations": 50, "stepSize": 0.005,
         "l1Reg": 0.1, "convergenceTol": 0.001}


@pytest.fixture(autouse=True)
def _fresh_registry():
    _metrics.reset_for_tests()
    yield
    _metrics.reset_for_tests()


def _dev(w, r) -> float:
    return float(np.abs(w - r).sum() / np.abs(r).sum())


# ---------------------------------------------------------------------------
# (a) the update rule

def _mllib_l1(w, grad_sum, count, it, step, lam):
    """``L1Updater.compute`` in float64, as the Scala source states it."""
    eta = step / np.sqrt(it)
    stepped = w - eta * grad_sum / max(count, 1.0)
    return np.sign(stepped) * np.maximum(0.0, np.abs(stepped) - eta * lam)


def test_the_rule_is_l1updaters_on_every_leaf_and_crossing_zero_lands_on_it():
    """Fixed gradient, 6 rounds, a pytree of a text-like and a numeric-like
    leaf: each round is ``w' = w − η_t·∇/n`` then the soft threshold by
    ``η_t·λ``, ``η_t = stepSize/√t`` — on BOTH leaves. Leaf 0 starts at
    +0.3 under a positive gradient: it steps through zero's band and is
    pinned at exactly 0.0, not carried to the other side."""
    import jax.numpy as jnp

    from twtml_tpu.models.sgd import sgd_inner_loop

    g = (np.array([0.65, -0.5, 0.0, 0.02]), np.array([0.3, -0.01]))
    w0 = (np.array([0.3, 0.0, 0.0, 0.05]), np.array([0.2, 0.0]))

    def run(rounds):
        return sgd_inner_loop(
            tuple(jnp.asarray(a, jnp.float32) for a in w0),
            num_iterations=rounds, step_size=0.5, mini_batch_fraction=1.0,
            l2_reg=0.0, l1_reg=0.1, convergence_tol=0.0, mask=jnp.ones(4),
            sample_key=None, count_iterations=True,
            grad_and_count=lambda w, sel: (
                tuple(jnp.asarray(a * 4.0, jnp.float32) for a in g),
                jnp.sum(sel)),
        )

    w, ran = run(6)
    want = [a.copy() for a in w0]
    for it in range(1, 7):
        want = [_mllib_l1(a, b * 4.0, 4.0, it, 0.5, 0.1)
                for a, b in zip(want, g)]
    assert int(ran) == 6
    for got, ref_leaf in zip(w, want):
        np.testing.assert_allclose(np.asarray(got), ref_leaf, atol=2e-7)
    # round 1 takes 0.3 to 0.3 − 0.5·0.65 = −0.025, inside the band of
    # η·λ = 0.05 around zero: it lands ON zero, exactly
    first, _ = run(1)
    assert float(first[0][0]) == 0.0
    # a zero weight under a zero gradient stays exactly zero, and so does
    # one under a gradient smaller than λ (|η∇| < ηλ); under a larger one
    # it leaves
    assert float(w[0][2]) == 0.0 and float(w[1][1]) == 0.0
    assert float(w[0][1]) > 0.0
    # the numeric-like leaf was thresholded too: the plain step alone
    # would leave 0.2 − Σ ηₜ·0.3
    plain = 0.2 - sum(0.5 / np.sqrt(t) * 0.3 for t in range(1, 7))
    assert abs(float(w[1][0]) - plain) > 0.05


def test_zero_sample_rounds_skip_and_the_freeze_holds_and_is_counted():
    """A round whose selected count is 0 leaves the weights as they are (no
    shrink either) and is not convergence; after the round that meets the
    tolerance nothing moves and ``iterations`` stops counting."""
    import jax.numpy as jnp

    from twtml_tpu.models.sgd import sgd_inner_loop

    w0 = jnp.asarray([1.0, -2.0, 0.5], jnp.float32)
    kw = dict(num_iterations=50, step_size=0.1, mini_batch_fraction=1.0,
              l2_reg=0.0, l1_reg=0.1, sample_key=None, count_iterations=True)
    w, ran = sgd_inner_loop(
        w0, convergence_tol=0.001, mask=jnp.zeros(4),
        grad_and_count=lambda w, sel: (w * 3.0, jnp.sum(sel)), **kw)
    assert np.asarray(w).tobytes() == np.asarray(w0).tobytes()
    assert int(ran) == 50           # skipped rounds ran; none converged

    # a gradient that pulls w to 0: the steps shrink below tol·max(‖w‖, 1)
    def pull(w, sel):
        return w * jnp.sum(sel), jnp.sum(sel)

    w, ran = sgd_inner_loop(w0, convergence_tol=0.01, mask=jnp.ones(4),
                            grad_and_count=pull, **kw)
    want, stopped = np.asarray(w0, np.float64), None
    for it in range(1, 51):
        new = _mllib_l1(want, want * 4.0, 4.0, it, 0.1, 0.1)
        done = np.linalg.norm(new - want) < 0.01 * max(np.linalg.norm(new), 1)
        want = new
        if done:
            stopped = it
            break
    assert stopped is not None and 1 < stopped < 50
    assert int(ran) == stopped
    np.testing.assert_allclose(np.asarray(w), want, atol=1e-6)


def test_l1_zero_is_the_loop_it_always_was_and_the_count_is_opt_in():
    """``l1_reg=0`` traces the SimpleUpdater / SquaredL2Updater body — the
    jaxpr of a call that never heard of ``l1_reg`` — and the carry has no
    counter unless asked for."""
    import jax
    import jax.numpy as jnp

    from twtml_tpu.models.sgd import sgd_inner_loop

    kw = dict(num_iterations=5, step_size=0.1, mini_batch_fraction=1.0,
              l2_reg=0.1, convergence_tol=0.001, mask=jnp.ones(4),
              sample_key=None,
              grad_and_count=lambda w, sel: (w * 2.0, jnp.sum(sel)))
    plain = jax.make_jaxpr(lambda w: sgd_inner_loop(w, **kw))(jnp.ones(3))
    zero = jax.make_jaxpr(
        lambda w: sgd_inner_loop(w, l1_reg=0.0, **kw))(jnp.ones(3))
    assert str(plain) == str(zero)
    assert "sign" not in str(plain)
    lasso = jax.make_jaxpr(
        lambda w: sgd_inner_loop(w, l1_reg=0.1, **dict(kw, l2_reg=0.0))
    )(jnp.ones(3))
    assert "sign" in str(lasso)


# ---------------------------------------------------------------------------
# (b) the fused pass against dot + tdot

def _counts(plane: str, rows: int, f_text: int, seed: int):
    """(token_idx, token_val) that ``text_gram``'s gate sends to ``plane``."""
    rng = np.random.default_rng(seed)
    width = 24
    idx = rng.integers(0, f_text, (rows, width)).astype(np.int32)
    val = np.ones((rows, width), np.float32)
    if plane == "bf16":      # a row of mass 24 + 140 > 127, values integral
        val[0, 0] = 141.0
    elif plane == "exact":   # a fractional value
        val[0, 0] = 0.5
    return idx, val


@pytest.mark.parametrize("residual", ["least_squares", "logistic"])
@pytest.mark.parametrize("plane, index", [("s8", 2), ("bf16", 1), ("exact", 0)])
def test_the_pass_is_dot_then_tdot_on_every_plane(plane, index, residual,
                                                  monkeypatch):
    """``CountPlane.primal_pass`` with the kernel forced in (interpreted:
    the exact plane's 2-D densify has no kernel and is ``dot`` + ``tdot``
    itself, bit for bit) against the two XLA contractions on the same C:
    the same f32 products summed tile by tile instead of row by row, so
    equal to f32 rounding of a sum of ~24 terms — 4e-6 of the largest
    entry — and bit for bit in ``r`` wherever the row sums agree."""
    import jax
    import jax.numpy as jnp

    from twtml_tpu.ops import gram

    monkeypatch.setattr(gram.CountPlane, "kernel_off_chip", True)
    rows, f_text = 16, 4096
    idx, val = _counts(plane, rows, f_text, 5)
    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.normal(size=f_text), jnp.float32)
    base = jnp.asarray(rng.normal(size=rows), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, rows), jnp.float32)
    sel = jnp.asarray(rng.integers(0, 2, rows), jnp.float32)
    fn = (lambda raw, y: raw - y) if residual == "least_squares" else (
        lambda raw, y: jax.nn.sigmoid(raw) - y)

    def body(counts):
        fused = counts.primal_pass(w, base=base, labels=labels, sel=sel,
                                   residual_fn=fn)
        r = fn(counts.dot(w) + base, labels) * sel
        return fused, (counts.tdot(r), r)

    ((grad, r), (grad_xla, r_xla)), took = jax.jit(
        lambda i, v: gram.text_gram(i, v, f_text, body=body))(idx, val)
    assert int(took) == index
    assert grad.shape == (f_text,) and r.shape == (rows,)
    scale = float(jnp.max(jnp.abs(grad_xla)))
    if plane == "exact":
        assert np.asarray(grad).tobytes() == np.asarray(grad_xla).tobytes()
        assert np.asarray(r).tobytes() == np.asarray(r_xla).tobytes()
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_xla),
                               rtol=4e-6, atol=4e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_xla),
                               rtol=0, atol=4e-6 * scale)


@pytest.mark.parametrize("dtype, shape, tile", [
    ("bfloat16", (64, 256), (16, 128)),
    ("int8", (64, 256), (32, 128)),
    ("float32", (64, 256), (8, 128)),
    ("bfloat16", (8, 64), (8, 64)),     # smaller than a tile: the whole axes
])
def test_the_kernel_in_the_chips_tiling_is_the_plain_sums(dtype, shape, tile):
    """The kernel body as the chip runs it — native ``(sublanes, 128)``
    tiles of C's type, a tile row a loop step, blocks of 8 rows, several
    blocks so that ``∇`` accumulates across the grid — interpreted, against
    plain sums. The blocking is the module's own: there is nothing to pass."""
    import jax.numpy as jnp

    from twtml_tpu.ops.primal_pass import primal_pass, tile_shape

    rng = np.random.default_rng(1)
    b, (k_hi, k_lo) = 32, shape
    assert tile_shape(jnp.dtype(dtype), k_hi, k_lo) == tile
    if shape == (64, 256):      # the cell's own [512, 512]: the same tile
        assert tile_shape(jnp.dtype(dtype), 512, 512) == tile
    c = jnp.asarray(rng.integers(-3, 4, (b, k_hi, k_lo))).astype(dtype)
    w = jnp.asarray(rng.normal(size=(k_hi, k_lo)), jnp.float32)
    base, y = (jnp.asarray(rng.normal(size=b), jnp.float32) for _ in "ab")
    sel = jnp.asarray(rng.integers(0, 2, b), jnp.float32)
    grad, r = primal_pass(c, w, base, y, sel, interpret=True,
                          residual_fn=lambda raw, lab: raw - lab)
    x = np.asarray(c, np.float64)
    r_want = (np.einsum("bhl,hl->b", x, np.asarray(w, np.float64))
              + np.asarray(base) - np.asarray(y)) * np.asarray(sel)
    grad_want = np.einsum("bhl,b->hl", x, r_want)
    np.testing.assert_allclose(np.asarray(r), r_want, rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(np.asarray(grad), grad_want, rtol=0,
                               atol=2e-6 * np.abs(grad_want).max())
    assert not np.asarray(r)[np.asarray(sel) == 0].any()
    with pytest.raises(ValueError, match="not blocks of 8"):
        primal_pass(c[:12], w, base[:12], y[:12], sel[:12], interpret=True,
                    residual_fn=lambda raw, lab: raw - lab)


def test_the_pass_refuses_a_row_panel():
    import jax.numpy as jnp

    from twtml_tpu.ops.gram import CountPlane

    c = jnp.zeros((4, 8, 8), jnp.bfloat16)
    with pytest.raises(ValueError, match="no mesh form"):
        CountPlane(c, c, 0, 64).primal_pass(
            jnp.zeros(64), base=jnp.zeros(4), labels=jnp.zeros(4),
            sel=jnp.ones(4), residual_fn=lambda a, b: a - b)


# ---------------------------------------------------------------------------
# (c) the program against its plain reference

def _plane_generator(plane: str, rows: int, batches: int) -> dict:
    """The mix's generator at a tiny size, its texts cut to what sends every
    batch to ``plane``: at most 120 units for s8; the mix's own 20–280 for
    bf16 (a row over 255 bigrams fails rung 1, rung 2 passes); and for the
    exact plane one tweet a batch of ONE character 270 times (a bigram 269
    times in a row: rung 2 fails)."""
    g = _generator(rows, batches)
    if plane == "s8":
        g.update(text_units_max=120, text_units_mean=70, text_units_sd=30)
    elif plane == "exact":
        g.update(text_units_min=258, text_units_mean=270, text_units_sd=8)
        g["runs"] = {"every_blocks": 1, "lines_per_block": 1,
                     "min_units": 258, "chars": ["k"]}
    return g


def _featurized(g: dict, chunk, rows: int, batches: int):
    from twtml_tpu.features.featurizer import Featurizer, Status

    feat = Featurizer(now_ms=g["now_ms"])
    statuses = [Status.from_json(json.loads(line)) for line in chunk.lines]
    return [feat.featurize_batch_ragged(
        statuses[b * rows:(b + 1) * rows], row_bucket=rows, pre_filtered=True)
        for b in range(batches)]


def _train(batches, f_text, **kw):
    import jax

    from twtml_tpu.models import StreamingLinearRegressionWithSGD

    model = StreamingLinearRegressionWithSGD(
        num_text_features=f_text, num_iterations=50, step_size=0.005,
        use_sparse=True, quality=True, **kw)
    outs = [jax.device_get(model.step(rb)) for rb in batches]
    return np.asarray(model.latest_weights, np.float64), outs


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("plane, index, f_text, rows", [
    ("s8", 2, 4096, 64), ("bf16", 1, 4096, 64), ("exact", 0, 4096, 64),
    ("bf16", 1, 1 << 18, 8),
])
def test_the_program_is_the_reference_on_every_plane(
        plane, index, f_text, rows, kernel, monkeypatch):
    """Three batches of a seeded stream through the step the app runs
    (ragged wire, device hash, the plane's branch, ``primal_basis``) against
    ``lasso_sgd`` in float64: the weights to 5e-6 of their L1 norm (float32
    against float64 over 150 thresholded rounds), exactly the same zeros
    but for weights within float32 rounding of the threshold, and every
    batch's mse. With ``kernel`` the Pallas pass runs interpreted inside
    the loop, where a CPU otherwise takes ``dot`` + ``tdot``."""
    from twtml_tpu.ops import gram
    from twtml_tpu.ops.quality import QUALITY_INDEX

    if kernel and plane == "exact":
        pytest.skip("the exact plane's [B, F] densify has no kernel")
    monkeypatch.setattr(gram.CountPlane, "kernel_off_chip", kernel)
    batches, seed = 3, 23
    g = _plane_generator(plane, rows, batches)
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0,
                           rows * batches)
    w, outs = _train(_featurized(g, chunk, rows, batches), f_text, l1_reg=0.1)
    learner, stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches,
        model=dict(MODEL, numTextFeatures=f_text), generator=g)
    assert [int(o.quality[QUALITY_INDEX["gram_plane"]]) for o in outs] == (
        [index] * batches)
    assert _dev(w, learner.w) < 5e-6
    # the four numeric weights: at the reference's hand scaling a gradient
    # step is ~1e-6 a round against a threshold of 5e-4, so L1Updater pins
    # them at EXACTLY zero — a program that left the threshold off them
    # (MLlib thresholds the whole vector) would hold ~1e-4 there, which the
    # cell's weights_dev cannot see (benchmark/tests/test_lasso2e18.py)
    assert (w[-4:] == 0).all() and (learner.w[-4:] == 0).all()
    for out, s in zip(outs, stats):
        assert abs(float(out.mse) - s["mse"]) <= 2e-4 * s["mse"] + 1
    # the iterations the step counted are the reference's
    assert [int(o.primal[0]) for o in outs] == learner.ran
    # zeros: the count the step fetched is the weights' own, and the
    # reference's but for weights a rounding away from the threshold
    zeros = int((w[:f_text] == 0).sum())
    assert int(outs[-1].primal[1]) == zeros
    ref_zeros = int((learner.w[:f_text] == 0).sum())
    assert abs(zeros - ref_zeros) <= 2
    assert 0 < zeros < f_text


@pytest.mark.parametrize("what, kw, model", [
    ("the L2 updater at the same strength", dict(l2_reg=0.1), {}),
    ("49 iterations", dict(l1_reg=0.1),
     {"numIterations": 51}),   # the reference moved instead: same gap
])
def test_another_updater_or_another_round_count_is_not_the_reference(
        what, kw, model):
    rows, batches, seed = 64, 3, 23
    g = _plane_generator("bf16", rows, batches)
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0,
                           rows * batches)
    w, _outs = _train(_featurized(g, chunk, rows, batches), 4096, **kw)
    learner, _stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches,
        model=dict(MODEL, **model), generator=g)
    assert _dev(w, learner.w) > 1e-4, what


def test_the_reference_thresholds_the_numeric_weights_and_its_control_fails():
    """The bf16 control is not the reference by the weights; neither is a
    copy of the reference whose threshold skips the four numeric weights
    (MLlib thresholds the whole vector)."""
    rows, batches, seed = 64, 3, 23
    g = _plane_generator("bf16", rows, batches)
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0,
                           rows * batches)
    kw = dict(batch_rows=rows, n_batches=batches, model=MODEL, generator=g)
    learner, _ = ref.train_on_chunks([chunk], **kw)
    control, _ = ref.train_on_chunks([chunk], precision="bf16", **kw)
    assert _dev(control.w, learner.w) > 5e-6

    whole = ref.soft_threshold

    def text_only(w, shrinkage):
        out = whole(w, shrinkage)
        out[-4:] = w[-4:]
        return out

    ref.soft_threshold = text_only
    try:
        skipped, _ = ref.train_on_chunks([chunk], **kw)
    finally:
        ref.soft_threshold = whole
    assert np.abs(skipped.w[-4:] - learner.w[-4:]).max() > 0
    assert (learner.w[-4:] == 0).any() or _dev(skipped.w, learner.w) > 0


def test_a_weight_pinned_at_zero_is_revived_by_a_later_batch():
    """Two batches on disjoint columns: after batch 1 the columns only
    batch 2 holds are exactly zero (never informed), after batch 2 they are
    not — and batch 1's own columns, which batch 2 no longer informs, only
    shrink (the threshold keeps acting on them with a zero gradient)."""
    import jax.numpy as jnp

    from twtml_tpu.features.batch import FeatureBatch
    from twtml_tpu.models import StreamingLinearRegressionWithSGD

    f_text, rows = 4096, 8
    rng = np.random.default_rng(3)

    def batch(lo, hi):
        idx = rng.integers(lo, hi, (rows, 12)).astype(np.int32)
        return FeatureBatch(
            jnp.asarray(idx), jnp.ones((rows, 12), jnp.float32),
            jnp.zeros((rows, 4), jnp.float32),
            jnp.asarray(rng.integers(100, 1000, rows), jnp.float32),
            jnp.ones(rows, jnp.float32))

    model = StreamingLinearRegressionWithSGD(
        num_text_features=f_text, num_iterations=50, step_size=0.005,
        l1_reg=0.1, use_sparse=True)
    first, second = batch(0, 2048), batch(2048, 4096)
    model.step(first)
    w1 = np.asarray(model.latest_weights)
    assert (w1[2048:4096] == 0).all() and (w1[:2048] != 0).any()
    model.step(second)
    w2 = np.asarray(model.latest_weights)
    assert (w2[2048:4096] != 0).any()
    live = w1[:2048] != 0
    assert (np.abs(w2[:2048][live]) < np.abs(w1[:2048][live])).all()
    assert (w2[:2048][~live] == 0).all()


# ---------------------------------------------------------------------------
# (d) --l1Reg 0: every standing step is the parent's program

# SHA-256 of the StableHLO text (no debug info: metadata aside) of the
# standing programs as the PARENT of PR 55 lowered them on this
# installation — recorded from a ``git archive`` of the parent commit, 8
# rows of 16 units (the two mesh steps on four virtual CPU devices). A PR that changes a standing step on purpose records
# them anew; this PR may not have moved one instruction.
_PARENT_PROGRAMS = {
    "single": "e2054e43e4ade5b8",
    "scatter_loop": "1e8492c8987ce2df",
    "arms": "c6438e18b786f2f3",
    "tenants": "b810228e406cd680",
    "mesh_2x2": "3bb6d965e6958d61",
    "mesh_4x1": "7af3f9893b62d11d",
}


def _standing_programs():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from test_step_scopes import _lowered, _one_device_programs
    from twtml_tpu.features.batch import RaggedUnitBatch

    one = _one_device_programs()

    def on_mesh(shape, body, w_spec, weights_of):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from twtml_tpu.models.base import StepOutput

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape),
                    ("data", "model")[:len(shape)])

        def sds(dims, dtype, *spec):
            return jax.ShapeDtypeStruct(
                dims, dtype, sharding=NamedSharding(mesh, P(*spec)))

        rows, shards = 8, mesh.shape["data"]
        wire = RaggedUnitBatch(
            sds((64,), jnp.uint16, "data"),
            sds((rows + shards,), jnp.int32, "data"),
            sds((rows, 4), jnp.float32, "data", None),
            sds((rows,), jnp.float32, "data"),
            sds((rows,), jnp.float32, "data"),
            row_len=16, num_shards=shards)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(w_spec, P("data")),
            out_specs=(w_spec, StepOutput(
                predictions=P("data"), count=P(), mse=P(), real_stdev=P(),
                pred_stdev=P(), quality=P())),
        ), donate_argnums=0).lower(weights_of(sds), wire)

    f_mesh = 1 << 14
    recipe = dict(num_iterations=50, step_size=0.005, l2_reg=0.1,
                  quality=True)

    def mesh_2x2():
        from jax.sharding import PartitionSpec as P

        from twtml_tpu.parallel.sharding import _make_feature_sharded_step

        body = _make_feature_sharded_step(
            f_text=f_mesh, f_text_local=f_mesh // 2, mini_batch_fraction=1.0,
            convergence_tol=0.001, residual_fn=None, prediction_fn=None,
            round_predictions=True, data_axis="data", model_axis="model",
            **recipe)
        return on_mesh(
            (2, 2), body, {"text": P("model"), "num": P()},
            lambda sds: {"text": sds((f_mesh,), jnp.float32, "model"),
                         "num": sds((4,), jnp.float32)})

    def mesh_4x1():
        from jax.sharding import PartitionSpec as P

        from twtml_tpu.models.sgd import make_sgd_train_step

        body = make_sgd_train_step(
            num_text_features=f_mesh, axis_name="data", **recipe)
        return on_mesh((4,), body, P(),
                       lambda sds: sds((f_mesh + 4,), jnp.float32))

    return {
        "single": one["single"],
        "scatter_loop": lambda: _lowered("packed", use_gram=False,
                                         quality=True),
        "arms": one["arms"], "tenants": one["tenants"],
        "mesh_2x2": mesh_2x2, "mesh_4x1": mesh_4x1,
    }


def program_digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


@pytest.mark.parametrize("program", sorted(_PARENT_PROGRAMS))
def test_without_l1_every_standing_step_lowers_to_the_parents_program(program):
    assert program_digest(_standing_programs()[program]()) == (
        _PARENT_PROGRAMS[program])


def test_with_l1_the_step_has_no_g_no_dual_loop_and_no_writeback():
    """The primal program's scopes: the nine less ``gram_matmul``,
    ``dual_loop`` and ``writeback``, plus ``primal_loop`` with
    ``primal_pass`` inside it; nothing under ``primal_loop`` is also under
    a stage name (the stage readers would take it for that stage)."""
    import jax
    import jax.numpy as jnp

    from test_step_scopes import F_TEXT, _wire
    from twtml_tpu.models.sgd import STAGE_SCOPES, make_sgd_train_step

    step = make_sgd_train_step(
        num_text_features=F_TEXT, num_iterations=50, step_size=0.005,
        l1_reg=0.1, quality=True)
    text = jax.jit(step, donate_argnums=0).lower(
        jnp.zeros(F_TEXT + 4, jnp.float32), _wire("packed", 8, 16)
    ).as_text(debug_info=True)
    # the loop's body is outlined (a ``closed_call`` under
    # ``primal_loop/while/body``), so its own operations are named from the
    # body's root: ``primal_pass/...``; the compiler inlines the call and
    # joins the two (``…/primal_loop/while/body/closed_call/primal_pass/…``
    # is what a profile of the chip shows)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    paths = [n.split("/") for n in names]
    parts = {p for path in paths for p in path}
    assert {"primal_loop", "primal_pass"} <= parts
    assert not {"gram_matmul", "dual_loop", "writeback"} & parts
    assert set(STAGE_SCOPES) - parts == {
        "gram_matmul", "dual_loop", "writeback"}
    assert any(p[-4:] == ["primal_loop", "while", "body", "closed_call"]
               for p in paths)
    inside = [p for p in paths if "primal_loop" in p or p[0] == "primal_pass"]
    assert not [p for p in inside if set(p) & set(STAGE_SCOPES)]


# ---------------------------------------------------------------------------
# (e) the flag, its refusals and their sentences

def _parse(*flags):
    return ConfArguments().parse([
        "--backend", "cpu", "--numTextFeatures", "4096", *flags])


def test_the_flag_parses_and_defaults_to_zero():
    assert _parse().l1Reg == 0.0 and _parse().updater() == "simple"
    assert _parse("--l2Reg", "0.1").updater() == "l2"
    conf = _parse("--l1Reg", "0.25")
    assert conf.l1Reg == 0.25 and conf.updater() == "l1"
    assert "--l1Reg <float>" in ConfArguments().usage
    from twtml_tpu.models import StreamingLinearRegressionWithSGD

    assert StreamingLinearRegressionWithSGD.from_conf(conf) is not None


@pytest.mark.parametrize("flags, said", [
    (["--l1Reg", "0.1", "--l2Reg", "0.1"], "ONE updater"),
    (["--l2Reg", "0.1", "--l1Reg", "0.1"], "an elastic net is neither"),
    (["--l1Reg", "0.1", "--tenants", "2", "--tenantL2Reg", "0,0.1"],
     "ONE updater"),
])
def test_both_strengths_are_refused_at_parse(flags, said):
    with pytest.raises(SystemExit) as stop:
        _parse(*flags)
    assert said in str(stop.value.code)


@pytest.mark.parametrize("flags, said", [
    (["--tenants", "4", "--tenantKey", "all", "--master", "local[1]"],
     "DUAL half"),
    (["--tenants", "4", "--master", "local[1]"],
     "partitioned tenant plane"),
    (["--tenants", "4", "--tenantKey", "lang", "--master", "local[1]"],
     "partitioned tenant plane"),
    (["--master", "local[4]", "--modelShards", "2"], "under a model axis"),
    (["--master", "local[4]"], "psum a [numTextFeatures] gradient"),
])
def test_the_app_refuses_where_the_pass_has_no_form(flags, said):
    from twtml_tpu.apps.common import L1_REFUSALS, build_model

    with pytest.raises(SystemExit) as stop:
        build_model(_parse("--l1Reg", "0.1", *flags))
    assert said in str(stop.value.code)
    assert str(stop.value.code) in L1_REFUSALS.values()


def test_the_step_builder_refuses_arms_and_a_data_axis():
    from twtml_tpu.models.sgd import make_sgd_train_step

    kw = dict(num_text_features=4096, num_iterations=50, step_size=0.005,
              l1_reg=0.1)
    with pytest.raises(ValueError, match="DUAL half"):
        make_sgd_train_step(arms=True, **kw)
    with pytest.raises(ValueError, match="psum a \\[F\\] gradient"):
        make_sgd_train_step(axis_name="data", **kw)
    with pytest.raises(ValueError, match="ONE updater"):
        make_sgd_train_step(l2_reg=0.1, **kw)


# ---------------------------------------------------------------------------
# (f) through the app: reference, checkpoint, resume; (g) telemetry

def test_through_the_app_against_the_reference_with_a_resume_bit_for_bit(
        tmp_path, monkeypatch):
    """The normal path (block ingest, ragged wire, FetchPipeline, verified
    checkpoint) at 2^16 dims, where the step takes the plane's branch: four
    batches in one run against the reference; and two batches, a stop, then
    a second run of the same command line that resumes from the checkpoint
    ``load_servable`` verifies and trains the last two — the same ``[F+4]``
    vector, byte for byte."""
    rows, batches = 64, 4
    g, chunk, path = _stream(tmp_path, rows, batches, 7)
    wide = ["--numTextFeatures", "65536", *L1]
    totals, printed = _run_app(monkeypatch, path, str(tmp_path / "whole"),
                               rows, batches, wide)
    assert totals["batches"] == batches
    w = _weights(str(tmp_path / "whole"))
    learner, stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches,
        model=dict(MODEL, numTextFeatures=65536), generator=g)
    assert w.shape == learner.w.shape == (65536 + 4,)
    assert _dev(w, learner.w) < 5e-6
    for p, s in zip(printed, stats):
        assert abs(p["mse"] - s["mse"]) <= 2e-4 * s["mse"] + 1

    halves = str(tmp_path / "halves")
    _run_app(monkeypatch, path, halves, rows, 2, wide)
    first = _weights(halves)
    assert first.tobytes() != w.tobytes()
    again, _ = _run_app(monkeypatch, path, halves, rows, batches, wide)
    assert again["batches"] == batches      # two restored, two trained
    assert _weights(halves).astype(np.float32).tobytes() == (
        w.astype(np.float32).tobytes())


def test_the_primal_instant_the_gauges_and_the_share(tmp_path, monkeypatch):
    """One ``primal`` instant a delivered batch — ``batch``, ``iterations``,
    ``zero_weights``, ``plane`` — and the registry's gauges, which the
    eighth update's ``Metrics`` frame (``/api/metrics``) ships as they
    stand: ``model.weights_zero_share`` is the last batch's zeros over F."""
    rows, batches = 64, 3
    _g, _chunk, path = _stream(tmp_path, rows, batches, 7)
    spans = str(tmp_path / "spans.json")
    ckpt = str(tmp_path / "ck")
    _run_app(monkeypatch, path, ckpt, rows, batches,
             ["--numTextFeatures", "65536", "--trace", spans, *L1])
    seen = [ev["args"] for ev in span_files.load_events(spans)
            if ev.get("ph") == "i" and ev.get("name") == "primal"]
    assert [a["batch"] for a in seen] == [1, 2, 3]
    assert all(set(a) == {"batch", "iterations", "zero_weights", "plane"}
               for a in seen)
    assert all(1 <= a["iterations"] <= 50 and a["plane"] == 1 for a in seen)
    w = _weights(ckpt)
    assert seen[-1]["zero_weights"] == int((w[:65536] == 0).sum())
    gauges = _metrics.get_registry().snapshot()["gauges"]
    assert gauges["model.primal_iterations"] == seen[-1]["iterations"]
    assert gauges["model.weights_zero_share"] == pytest.approx(
        seen[-1]["zero_weights"] / 65536, abs=1e-6)
    assert 0.5 < gauges["model.weights_zero_share"] < 1.0


def test_without_l1_there_is_no_instant_and_no_leaf(tmp_path, monkeypatch):
    rows, batches = 64, 2
    _g, _chunk, path = _stream(tmp_path, rows, batches, 7)
    spans = str(tmp_path / "spans.json")
    _run_app(monkeypatch, path, str(tmp_path / "ck"), rows, batches,
             ["--trace", spans])
    assert not [ev for ev in span_files.load_events(spans)
                if ev.get("name") == "primal"]
    assert "model.weights_zero_share" not in (
        _metrics.get_registry().snapshot()["gauges"])
