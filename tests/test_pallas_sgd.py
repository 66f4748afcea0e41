"""Pallas fused-SGD reference kernel (interpret mode on the CPU harness):
the VMEM-resident loop must track the XLA sgd_inner_loop path within the
documented bf16-storage tolerance, honor the zeroed-padding contract, and
gate itself to configurations that actually fit scoped VMEM on hardware
(the round-1 kernel OOM'd on a real v5e at the flagship shape; the budget
model now reflects measured usage — see ops/pallas_sgd.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from twtml_tpu.features.batch import FeatureBatch
from twtml_tpu.models.sgd import make_sgd_train_step, zero_weights
from twtml_tpu.ops import pallas_sgd
from twtml_tpu.ops.sparse import densify_text

RNG = np.random.default_rng(11)
F_TEXT = 60  # + 4 numeric = 64 → pads to 128 lanes


def make_batch(n=14, pad_to=16, tokens=6):
    token_idx = RNG.integers(0, F_TEXT, size=(pad_to, tokens)).astype(np.int32)
    token_val = RNG.integers(1, 3, size=(pad_to, tokens)).astype(np.float32)
    numeric = (RNG.normal(size=(pad_to, 4)) * 0.1).astype(np.float32)
    label = RNG.uniform(50, 900, size=(pad_to,)).astype(np.float32)
    mask = np.zeros((pad_to,), dtype=np.float32)
    mask[:n] = 1.0
    token_idx[n:] = 0
    token_val[n:] = 0
    numeric[n:] = 0
    label[n:] = 0
    return FeatureBatch(token_idx, token_val, numeric, label, mask)


def dense_design(batch):
    x_text = densify_text(
        jnp.asarray(batch.token_idx), jnp.asarray(batch.token_val), F_TEXT
    )
    return jnp.concatenate(
        [x_text, jnp.asarray(batch.numeric, dtype=jnp.float32)], axis=1
    )


def xla_reference(batch, **kw):
    step = jax.jit(
        make_sgd_train_step(
            num_text_features=F_TEXT,
            num_iterations=kw.pop("num_iterations", 30),
            step_size=kw.pop("step_size", 0.005),
            round_predictions=False,
            **kw,
        )
    )
    return step(zero_weights(F_TEXT), batch)


@pytest.mark.parametrize("kw", [
    {},
    {"l2_reg": 0.1},
    {"num_iterations": 5},
    {"convergence_tol": 0.5},  # converges early; the freeze must match
])
def test_matches_xla_loop(kw):
    batch = make_batch()
    w_ref, out_ref = xla_reference(batch, **dict(kw))
    w_pal, preds = pallas_sgd.fused_dense_sgd(
        dense_design(batch),
        jnp.asarray(batch.label),
        jnp.asarray(batch.mask),
        zero_weights(F_TEXT),
        num_iterations=kw.get("num_iterations", 30),
        step_size=0.005,
        l2_reg=kw.get("l2_reg", 0.0),
        convergence_tol=kw.get("convergence_tol", 0.001),
        interpret=True,
    )
    # bf16 storage of the design matrix: integer bigram counts are exact,
    # the scaled numerics round — the documented ~1e-3 relative envelope
    np.testing.assert_allclose(w_pal, w_ref, rtol=2e-3, atol=2e-3)
    valid = batch.mask.astype(bool)
    np.testing.assert_allclose(
        np.asarray(preds)[valid],
        np.asarray(out_ref.predictions)[valid],
        rtol=2e-3, atol=2e-3,
    )


def test_padding_rows_do_not_leak():
    """The kernel has no mask ref: zeroed padding rows must contribute
    nothing. Same data, different pad_to → identical weights."""
    small = make_batch(n=14, pad_to=16)
    large = FeatureBatch(*(
        np.concatenate([np.asarray(f), np.zeros((16,) + f.shape[1:], f.dtype)])
        for f in small
    ))
    kw = dict(num_iterations=10, step_size=0.005, interpret=True)
    w_a, _ = pallas_sgd.fused_dense_sgd(
        dense_design(small), jnp.asarray(small.label), jnp.asarray(small.mask),
        zero_weights(F_TEXT), **kw)
    w_b, _ = pallas_sgd.fused_dense_sgd(
        dense_design(large), jnp.asarray(large.label), jnp.asarray(large.mask),
        zero_weights(F_TEXT), **kw)
    np.testing.assert_allclose(w_a, w_b, rtol=1e-6, atol=1e-7)


def test_masked_rows_zeroed_defensively():
    """Even if a caller hands unzeroed garbage in masked rows, the call
    masks features and labels before the kernel sees them."""
    batch = make_batch(n=14, pad_to=16)
    x = np.asarray(dense_design(batch))
    x_dirty = x.copy()
    x_dirty[14:] = np.nan  # NaN garbage: multiply-masking would poison all
    label_dirty = np.asarray(batch.label).copy()
    label_dirty[14:] = np.inf
    kw = dict(num_iterations=10, step_size=0.005, interpret=True)
    w_clean, _ = pallas_sgd.fused_dense_sgd(
        jnp.asarray(x), jnp.asarray(batch.label), jnp.asarray(batch.mask),
        zero_weights(F_TEXT), **kw)
    w_dirty, _ = pallas_sgd.fused_dense_sgd(
        jnp.asarray(x_dirty), jnp.asarray(label_dirty), jnp.asarray(batch.mask),
        zero_weights(F_TEXT), **kw)
    np.testing.assert_allclose(w_clean, w_dirty, rtol=1e-6, atol=1e-7)


def test_empty_batch_no_update():
    batch = make_batch(n=0)
    w, preds = pallas_sgd.fused_dense_sgd(
        dense_design(batch), jnp.asarray(batch.label), jnp.asarray(batch.mask),
        zero_weights(F_TEXT), num_iterations=10, step_size=0.005,
        interpret=True)
    assert np.all(np.asarray(w) == 0.0)
    np.testing.assert_allclose(np.asarray(preds), 0.0, atol=1e-7)


def test_interpret_is_the_callers_explicit_choice():
    """No caller gets interpret mode without asking for it: the keyword is
    required, so a chip run can never silently measure the interpreter."""
    batch = make_batch()
    with pytest.raises(TypeError, match="interpret"):
        pallas_sgd.fused_dense_sgd(
            dense_design(batch), jnp.asarray(batch.label),
            jnp.asarray(batch.mask), zero_weights(F_TEXT),
            num_iterations=1, step_size=0.005)


def test_supports_gating():
    assert pallas_sgd.padded_lanes(100) == 128
    assert pallas_sgd.padded_lanes(128) == 128
    assert pallas_sgd.supports(
        batch_rows=16, num_features=128, mini_batch_fraction=1.0,
        dtype=jnp.float32,
    )
    # the flagship operating point must fit the measured VMEM model
    assert pallas_sgd.supports(
        batch_rows=2048, num_features=1004, mini_batch_fraction=1.0,
        dtype=jnp.float32,
    )
    assert not pallas_sgd.supports(  # sampling unsupported
        batch_rows=16, num_features=128, mini_batch_fraction=0.5,
        dtype=jnp.float32,
    )
    assert not pallas_sgd.supports(  # over the scoped-VMEM budget
        batch_rows=4096, num_features=2**14, mini_batch_fraction=1.0,
        dtype=jnp.float32,
    )
    assert not pallas_sgd.supports(  # f32 weights only
        batch_rows=16, num_features=128, mini_batch_fraction=1.0,
        dtype=jnp.bfloat16,
    )


def test_vmem_estimate_is_the_gate():
    """The flagship shape must clear the scoped-VMEM limit with the matrix
    bytes accounted at bf16 plus vector-stripe overhead."""
    est = pallas_sgd._vmem_estimate(2048, 1024)
    assert 2 * 2048 * 1024 * 2 < est <= pallas_sgd.VMEM_LIMIT_BYTES
