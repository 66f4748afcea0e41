"""Mesh-sharded ragged wire (VERDICT r3 #2): the shard-aligned ragged
layout (features/batch.align_ragged_shards + ops/ragged.ragged_repad) must
train BIT-IDENTICALLY to the single-device ragged wire — and the ragged
wire itself is already pinned bit-identical to the padded ground truth
(tests/test_ragged_wire.py), so equality here closes mesh == padded.

Covers: host re-layout roundtrip, the data-parallel mesh, the 2D
(data × model) feature-sharded mesh, the unaligned-single-device aliasing
(an aligned batch stepped WITHOUT a mesh), and the pinned unit bucket the
multi-host lockstep tick agrees on."""

import numpy as np
import pytest

import jax

from twtml_tpu.features.batch import (
    RAGGED_UNIT_MULTIPLE,
    RaggedUnitBatch,
    align_ragged_shards,
)
from twtml_tpu.features.featurizer import Featurizer
from twtml_tpu.models import StreamingLinearRegressionWithSGD
from twtml_tpu.parallel import ParallelSGDModel, make_mesh
from twtml_tpu.parallel.sharding import shard_batch
from twtml_tpu.streaming.sources import SyntheticSource


def synthetic(n=96, seed=13):
    return list(
        SyntheticSource(total=n, seed=seed, base_ms=1785320000000).produce()
    )


def ragged_chunks(statuses, rows=32, **feat_kw):
    feat = Featurizer(now_ms=1785320000000, **feat_kw)
    return [
        feat.featurize_batch_ragged(
            statuses[i : i + rows], row_bucket=rows, unit_bucket=64
        )
        for i in range(0, len(statuses), rows)
    ]


def test_align_roundtrip_repad_identical():
    """Alignment is a pure re-layout: the on-device re-pad of the aligned
    buffer equals the re-pad of the flat buffer, row for row."""
    from twtml_tpu.ops.ragged import ragged_repad

    for rb in ragged_chunks(synthetic()):
        flat_buf, flat_len = ragged_repad(
            rb.units, rb.offsets, rb.row_len, rb.mask.shape[0]
        )
        for s in (2, 4, 8):
            ab = align_ragged_shards(rb, s)
            assert ab.num_shards == s
            assert ab.units.shape[0] % s == 0
            a_buf, a_len = ragged_repad(
                ab.units, ab.offsets, ab.row_len, ab.mask.shape[0]
            )
            np.testing.assert_array_equal(np.asarray(a_buf), np.asarray(flat_buf))
            np.testing.assert_array_equal(np.asarray(a_len), np.asarray(flat_len))


def test_align_rejects_bad_shapes():
    rb = ragged_chunks(synthetic(n=32))[0]
    with pytest.raises(ValueError, match="not divisible"):
        align_ragged_shards(rb, 5)
    ab = align_ragged_shards(rb, 4)
    with pytest.raises(ValueError, match="already shard-aligned"):
        align_ragged_shards(ab, 8)
    with pytest.raises(ValueError, match="exceed the pinned bucket"):
        # every real row's units can't fit a 0-unit... use a tiny non-multiple
        align_ragged_shards(rb, 4, unit_bucket=1)


def test_pinned_unit_bucket_shapes():
    """The multi-host path pins the per-shard sub-buffer capacity so every
    process compiles one program; the pinned layout must still re-pad
    identically."""
    from twtml_tpu.ops.ragged import ragged_repad

    rb = ragged_chunks(synthetic(n=32))[0]
    ab = align_ragged_shards(rb, 2, unit_bucket=2 * RAGGED_UNIT_MULTIPLE)
    assert ab.units.shape == (2 * 2 * RAGGED_UNIT_MULTIPLE,)
    a_buf, _ = ragged_repad(ab.units, ab.offsets, ab.row_len, ab.mask.shape[0])
    f_buf, _ = ragged_repad(rb.units, rb.offsets, rb.row_len, rb.mask.shape[0])
    np.testing.assert_array_equal(np.asarray(a_buf), np.asarray(f_buf))


def test_prealigned_batch_grows_to_pinned_bucket():
    """The one-data-shard-per-process topology: a FLAT batch is trivially
    aligned to 1 shard, and the multi-host agreed bucket can exceed this
    host's buffer — align must PAD UP (tail zeros; segment-relative
    offsets untouched), not raise (r4 review finding)."""
    from twtml_tpu.ops.ragged import ragged_repad

    rb = ragged_chunks(synthetic(n=32))[0]
    assert rb.num_shards == 1 and rb.units.shape[0] == RAGGED_UNIT_MULTIPLE
    grown = align_ragged_shards(rb, 1, unit_bucket=2 * RAGGED_UNIT_MULTIPLE)
    assert grown.units.shape == (2 * RAGGED_UNIT_MULTIPLE,)
    np.testing.assert_array_equal(grown.offsets, rb.offsets)
    g_buf, _ = ragged_repad(
        grown.units, grown.offsets, grown.row_len, grown.mask.shape[0]
    )
    f_buf, _ = ragged_repad(rb.units, rb.offsets, rb.row_len, rb.mask.shape[0])
    np.testing.assert_array_equal(np.asarray(g_buf), np.asarray(f_buf))
    # shrinking below the current buffer is still an error
    with pytest.raises(ValueError, match="cannot\n?\\s*shrink"):
        align_ragged_shards(grown, 1, unit_bucket=RAGGED_UNIT_MULTIPLE)


def test_aligned_batch_single_device_matches_flat():
    """An aligned batch stepped WITHOUT a mesh (num_shards > 1, no axis)
    must train identically to the flat ragged batch — the segment-aware
    repad path."""
    chunks = ragged_chunks(synthetic())
    flat = StreamingLinearRegressionWithSGD(num_iterations=5)
    aligned = StreamingLinearRegressionWithSGD(num_iterations=5)
    for rb in chunks:
        out_f = flat.step(rb)
        out_a = aligned.step(align_ragged_shards(rb, 4))
        for a, b in zip(out_f, out_a):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(flat.latest_weights, aligned.latest_weights)


def padded_chunks(statuses, rows=32, **feat_kw):
    feat = Featurizer(now_ms=1785320000000, **feat_kw)
    return [
        feat.featurize_batch_units(
            statuses[i : i + rows], row_bucket=rows, unit_bucket=64
        )
        for i in range(0, len(statuses), rows)
    ]


def test_data_mesh_ragged_bit_matches_padded_mesh():
    """4-way data-parallel mesh: the ragged wire must train BIT-identically
    to the padded wire on the SAME mesh (same collectives; only the wire
    differs — the exact parity law every fast path carries). Plus a
    float-tolerance check against single-device (summation order differs
    across psum shards, as with the padded wire)."""
    statuses = synthetic()
    r_chunks = ragged_chunks(statuses)
    p_chunks = padded_chunks(statuses)
    mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
    m_ragged = ParallelSGDModel(mesh, num_iterations=5, step_size=0.1)
    m_padded = ParallelSGDModel(mesh, num_iterations=5, step_size=0.1)
    single = StreamingLinearRegressionWithSGD(num_iterations=5, step_size=0.1)
    for rb, pb in zip(r_chunks, p_chunks):
        out_r = m_ragged.step(shard_batch(rb, mesh))
        out_p = m_padded.step(shard_batch(pb, mesh))
        single.step(rb)
        for a, b in zip(out_r, out_p):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        m_ragged.latest_weights, m_padded.latest_weights
    )
    np.testing.assert_allclose(
        m_ragged.latest_weights, single.latest_weights, rtol=1e-4, atol=1e-5
    )


def test_2d_mesh_ragged_bit_matches_padded_mesh():
    """The (data=2, model=4) feature-sharded mesh accepts the ragged wire
    and bit-matches the padded wire on the same mesh — the long-context
    layout no longer falls back to the padded wire (the r3 regression
    VERDICT #2 named)."""
    f_text = 512
    statuses = synthetic()
    r_chunks = ragged_chunks(statuses, num_text_features=f_text)
    p_chunks = padded_chunks(statuses, num_text_features=f_text)
    mesh = make_mesh(num_data=2, num_model=4)
    kw = dict(num_text_features=f_text, num_iterations=5, step_size=0.1)
    m_ragged = ParallelSGDModel(mesh, **kw)
    m_padded = ParallelSGDModel(mesh, **kw)
    for rb, pb in zip(r_chunks, p_chunks):
        out_r = m_ragged.step(shard_batch(rb, mesh))
        out_p = m_padded.step(shard_batch(pb, mesh))
        for a, b in zip(out_r, out_p):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        m_ragged.latest_weights, m_padded.latest_weights
    )


def test_shard_batch_reuses_prealigned():
    """shard_batch must not re-align an already-aligned batch (the
    featurizer/multi-host path aligns at build time)."""
    rb = ragged_chunks(synthetic(n=32))[0]
    mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
    ab = align_ragged_shards(rb, 4)
    sb = shard_batch(ab, mesh)
    assert sb.num_shards == 4
    np.testing.assert_array_equal(np.asarray(sb.units), np.asarray(ab.units))


# -- degenerate shard segments (ISSUE 3 satellite) ---------------------------
# The lockstep all-padding-batch contract (streaming/context._lockstep_loop:
# dry shards dispatch all-padding batches every tick) means the sharded
# one-buffer wire MUST round-trip shards that hold no rows at all, and
# shards that hold exactly one tweet — the boundary cases of the
# segment-relative offset layout (and of its uint16-delta encoding).


def _sparse_ragged(n_real, rows=32, seed=21):
    """A ragged batch whose last shards are pure padding: only the first
    ``n_real`` rows are real (featurizer pads the rest)."""
    feat = Featurizer(now_ms=1785320000000)
    return feat.featurize_batch_ragged(
        synthetic(n=n_real, seed=seed), row_bucket=rows, unit_bucket=64,
        pre_filtered=True,
    )


@pytest.mark.parametrize("n_real", [3, 1, 32])
def test_degenerate_shards_roundtrip_one_buffer_wire(n_real):
    """All-padding shards (n_real=3 → shards 1-3 empty; n_real=1 → a
    single-tweet shard plus three empty ones) must round-trip the sharded
    one-buffer wire bit-identically — packed sharded AND coalesced group,
    narrow and int32 offsets."""
    from twtml_tpu.features.batch import (
        pack_ragged_group,
        pack_ragged_sharded,
        unpack_batch,
    )

    rb = _sparse_ragged(n_real)
    aligned = align_ragged_shards(rb, 4)
    assert rb.num_valid == n_real
    for narrow in (None, False):
        pk = pack_ragged_sharded(aligned, narrow_offsets=narrow)
        back = unpack_batch(pk.buffer, pk.layout)
        for f in ("units", "offsets", "numeric", "label", "mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(back, f)), np.asarray(getattr(aligned, f))
            )
        assert back.num_shards == 4
        pg = pack_ragged_group([aligned], narrow_offsets=narrow)
        gback = unpack_batch(pg.buffer, pg.layout)
        for f in ("units", "offsets", "numeric", "label", "mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(gback, f))[0],
                np.asarray(getattr(aligned, f)),
            )


@pytest.mark.parametrize("n_real", [3, 1])
def test_degenerate_shards_train_identically_on_mesh(n_real):
    """The mesh step over the one-buffer wire with empty/single-tweet
    shards equals the flat single-device ragged step — the app-level form
    of the lockstep all-padding contract."""
    rb = _sparse_ragged(n_real)
    ref = StreamingLinearRegressionWithSGD(num_iterations=5, step_size=0.05)
    out_ref = ref.step(rb)

    mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
    m = ParallelSGDModel(mesh, num_iterations=5, step_size=0.05)
    out_pk = m.step(m.pack_for_wire(rb))
    assert float(out_pk.count) == float(out_ref.count) == n_real
    np.testing.assert_array_equal(
        np.asarray(out_pk.predictions), np.asarray(out_ref.predictions)
    )
    np.testing.assert_array_equal(m.latest_weights, ref.latest_weights)
