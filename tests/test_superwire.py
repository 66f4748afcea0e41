"""Lean wire v2 (ISSUE 3): the coalesced one-buffer tenant wire
(``pack_ragged_group``) must unpack BYTE-IDENTICAL to the stacked wire
(``stack_batches``), and the narrow uint16-delta offset wire to the
shipped packed-ragged path — flat AND per-shard layouts, K ∈
{1, 4, 8} — with the int32 offset fallback metadata-gated exactly like the
uint8/uint16 units switch (rows longer than the uint16 delta range trip
it). The wire may change transfer count and sideband bytes, never math."""

import numpy as np
import pytest

import jax

from twtml_tpu.features.batch import (
    OFFSET_DELTA_MAX,
    RaggedUnitBatch,
    offsets_narrow,
    pack_batch,
    pack_ragged_group,
    pack_ragged_sharded,
    ragged_wire_arrays,
    stack_batches,
    unpack_batch,
    wire_composition,
    wire_nbytes,
)
from twtml_tpu.features.featurizer import Featurizer
from twtml_tpu.models import StreamingLinearRegressionWithSGD
from twtml_tpu.streaming.sources import SyntheticSource


def ragged_batches(n=4, rows=16, unit_bucket=512):
    """n same-signature ragged batches (one compiled program's worth —
    what the tenant split emits)."""
    statuses = list(
        SyntheticSource(total=n * rows, seed=3, base_ms=1785320000000).produce()
    )
    feat = Featurizer(now_ms=1785320000000)
    return [
        feat.featurize_batch_ragged(
            statuses[i * rows : (i + 1) * rows], row_bucket=rows,
            unit_bucket=unit_bucket, pre_filtered=True,
        )
        for i in range(n)
    ]


def wide_ragged_batch(rows=8, row_len=32, seed=5):
    """Hand-built NON-ASCII (uint16 units) ragged batch — the wide-units
    wire composed with the narrow-offsets wire."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, row_len, size=rows)
    offsets = np.zeros(rows + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    units = rng.integers(0x100, 0x3FF, size=int(lens.sum())).astype(np.uint16)
    flat, offs = ragged_wire_arrays(units, offsets, rows, rows, narrow=False)
    return RaggedUnitBatch(
        flat, offs,
        rng.normal(size=(rows, 4)).astype(np.float32),
        rng.uniform(0, 100, size=(rows,)).astype(np.float32),
        np.ones((rows,), np.float32),
        row_len=row_len,
    )


def long_row_batch(rows=4, long_len=OFFSET_DELTA_MAX + 2):
    """One row longer than the uint16 delta range: the static row_len
    bucket exceeds 65,535, so the metadata gate keeps the int32 offsets."""
    from twtml_tpu.features.batch import _bucket

    rng = np.random.default_rng(7)
    lens = np.array([8, long_len, 4, 6][:rows])
    offsets = np.zeros(rows + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    units = rng.integers(97, 123, size=int(lens.sum())).astype(np.uint8)
    flat, offs = ragged_wire_arrays(units, offsets, rows, rows, narrow=True)
    return RaggedUnitBatch(
        flat, offs,
        rng.normal(size=(rows, 4)).astype(np.float32),
        rng.uniform(0, 100, size=(rows,)).astype(np.float32),
        np.ones((rows,), np.float32),
        row_len=_bucket(long_len),
    )


# -- coalesced group wire: the wire law the tenant stack relies on ----------
# ``_unpack_ragged_group(pack_ragged_group(bs))`` IS ``stack_batches(bs)``,
# leaf for leaf and bit for bit — on the host (the whole group back), inside
# a jit program (one segment: the single-device tenant program), and per
# shard (each P(data) slice of the shard-major buffer: the mesh tenant
# program's shard_map-local view).

_FIELDS = ("units", "offsets", "numeric", "label", "mask")


def _assert_leaves_equal(got, want):
    assert got.row_len == want.row_len
    for f in _FIELDS:
        g, w = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert g.dtype == w.dtype and g.shape == w.shape, f
        assert g.tobytes() == w.tobytes(), f


def _assert_group_wire_law(batches, num_shards):
    """Host, in-jit and per-shard unpack of the group pack against the
    stacked wire of the same (shard-aligned) batches."""
    from twtml_tpu.features.batch import align_ragged_shards

    if num_shards > 1:
        batches = [align_ragged_shards(b, num_shards) for b in batches]
    want = stack_batches(batches)
    pg = pack_ragged_group(batches)
    assert pg.layout[0] == "RaggedGroupSegments"
    assert pg.layout[2][1:3] == (num_shards, len(batches))
    _assert_leaves_equal(unpack_batch(pg.buffer, pg.layout), want)

    unpack = jax.jit(lambda buf: unpack_batch(buf, pg.layout))
    seg = pg.buffer.shape[0] // num_shards
    for s in range(num_shards):
        local = unpack(pg.buffer[s * seg : (s + 1) * seg])
        assert local.num_shards == 1
        for f in _FIELDS:
            full = np.asarray(getattr(want, f))
            d = full.shape[1] // num_shards
            w = full[:, s * d : (s + 1) * d]
            g = np.asarray(getattr(local, f))
            assert g.dtype == w.dtype and g.shape == w.shape, (f, s)
            assert g.tobytes() == np.ascontiguousarray(w).tobytes(), (f, s)


@pytest.mark.parametrize("k", [1, 4, 8])
def test_group_wire_matches_stacked_single_device(k):
    _assert_group_wire_law(ragged_batches(n=k), num_shards=1)


@pytest.mark.parametrize("k", [1, 4, 8])
def test_group_wire_matches_stacked_mesh(k):
    _assert_group_wire_law(ragged_batches(n=k, rows=32), num_shards=4)


def test_group_wire_2d_mesh_matches_stacked():
    """A (2 data x 2 model) mesh slices the buffer over its data axis
    only: two shard segments."""
    _assert_group_wire_law(ragged_batches(n=4, rows=32), num_shards=2)


def test_group_wire_wide_units():
    """Non-ASCII (uint16) units compose with the group wire and the narrow
    offset wire."""
    batches = [wide_ragged_batch(seed=s) for s in (5, 6, 7, 8)]
    pg = pack_ragged_group(batches)
    assert pg.layout[2][3] == "u16delta"  # narrow offsets despite wide units
    assert np.asarray(stack_batches(batches).units).dtype == np.uint16
    _assert_group_wire_law(batches, num_shards=1)


# -- narrow offset wire: encode gate + fallback ------------------------------

def test_narrow_offset_wire_flat_bit_identical():
    rb = ragged_batches(n=1)[0]
    narrow = pack_batch(rb)  # auto: row_len ≤ 65,535 → u16delta
    wide = pack_batch(rb, narrow_offsets=False)
    assert narrow.layout[2][2] == "u16delta"
    assert wide.layout[2][2] == "i32"
    assert narrow.buffer.nbytes < wide.buffer.nbytes
    for pk in (narrow, wide):
        back = unpack_batch(pk.buffer, pk.layout)
        for f in ("units", "offsets", "numeric", "label", "mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(back, f)), np.asarray(getattr(rb, f))
            )
        assert back.offsets.dtype == np.int32
    # and through the jit step: bitwise-identical outputs either way
    m_n = StreamingLinearRegressionWithSGD(num_iterations=5)
    m_w = StreamingLinearRegressionWithSGD(num_iterations=5)
    out_n, out_w = m_n.step(narrow), m_w.step(wide)
    for fa, fb in zip(out_n, out_w):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    np.testing.assert_array_equal(m_n.latest_weights, m_w.latest_weights)


def test_narrow_offset_wire_sharded_bit_identical():
    from twtml_tpu.features.batch import align_ragged_shards

    rb = ragged_batches(n=1, rows=32)[0]
    aligned = align_ragged_shards(rb, 4)
    for mode, marker in ((None, "u16delta"), (False, "i32")):
        pk = (
            pack_ragged_sharded(aligned)
            if mode is None
            else pack_ragged_sharded(aligned, narrow_offsets=False)
        )
        assert pk.layout[2][2] == marker
        back = unpack_batch(pk.buffer, pk.layout)
        for f in ("units", "offsets", "numeric", "label", "mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(back, f)), np.asarray(getattr(aligned, f))
            )


def test_long_row_trips_int32_fallback():
    """A row longer than 65,535 units pushes the static row_len bucket past
    the uint16 delta range: the metadata gate keeps the int32 offsets (no
    silent wrap), and the wire still trains bit-identically."""
    rb = long_row_batch()
    assert not offsets_narrow(rb.row_len)
    pk = pack_batch(rb)
    assert pk.layout[2][2] == "i32"  # the auto gate chose the fallback
    # forcing the narrow wire on an out-of-range batch raises, never wraps
    with pytest.raises(ValueError, match="uint16-delta"):
        pack_batch(rb, narrow_offsets=True)
    with pytest.raises(ValueError, match="uint16-delta"):
        pack_ragged_group([rb], narrow_offsets=True)
    # group wire inherits the fallback from the same gate
    pg = pack_ragged_group([rb])
    assert pg.layout[2][3] == "i32"
    back = unpack_batch(pk.buffer, pk.layout)
    for f in ("units", "offsets", "numeric", "label", "mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(back, f)), np.asarray(getattr(rb, f))
        )
    m_plain = StreamingLinearRegressionWithSGD(num_iterations=3)
    m_pack = StreamingLinearRegressionWithSGD(num_iterations=3)
    out_a, out_b = m_plain.step(rb), m_pack.step(pk)
    for fa, fb in zip(out_a, out_b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    np.testing.assert_array_equal(
        m_plain.latest_weights, m_pack.latest_weights
    )


def test_group_pack_rejects_mixed_signatures():
    a = ragged_batches(n=1, rows=16)[0]
    b = ragged_batches(n=1, rows=32)[0]
    with pytest.raises(ValueError, match="share one wire signature"):
        pack_ragged_group([a, b])
    with pytest.raises(ValueError, match="empty group"):
        pack_ragged_group([])


# -- wire composition metrics (satellite) ------------------------------------

def test_wire_composition_sums_to_wire_nbytes():
    rb = ragged_batches(n=1, rows=32)[0]
    from twtml_tpu.features.batch import align_ragged_shards

    forms = [
        rb,
        pack_batch(rb),
        pack_ragged_sharded(align_ragged_shards(rb, 4)),
        pack_ragged_group(ragged_batches(n=4, rows=32)),
    ]
    for batch in forms:
        comp = wire_composition(batch)
        assert set(comp) == {"units", "offsets", "sideband"}
        assert sum(comp.values()) == wire_nbytes(batch)
    # the narrow wire's offsets are measurably smaller than the int32 wire
    narrow = wire_composition(pack_batch(rb))["offsets"]
    wide = wire_composition(pack_batch(rb, narrow_offsets=False))["offsets"]
    assert narrow < wide


def test_record_metrics_sets_wire_split_gauges():
    from twtml_tpu.streaming.context import FeatureStream
    from twtml_tpu.telemetry import metrics as _metrics

    _metrics.reset_for_tests()
    try:
        rb = ragged_batches(n=1)[0]
        FeatureStream._record_metrics(rb)
        snap = _metrics.get_registry().snapshot()
        comp = wire_composition(rb)
        assert snap["gauges"]["wire.units_bytes"] == comp["units"]
        assert snap["gauges"]["wire.offsets_bytes"] == comp["offsets"]
        assert snap["gauges"]["wire.sideband_bytes"] == comp["sideband"]
        assert snap["counters"]["wire.bytes"] == wire_nbytes(rb)
    finally:
        _metrics.reset_for_tests()
