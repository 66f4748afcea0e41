"""Differential suite for the compressed ragged units wire
(``--wireCodec dict`` — features/wirecodec.py host codec,
ops/ragged.units_from_codes in-jit decode, the codec-aware packed layouts
in features/batch.py).

The parity law: decoded units must be BYTE-identical to the uncompressed
wire on every path — flat pack, shard segments, the coalesced group wire,
the mesh-sharded program — and a model fed the codec wire must produce
bitwise-identical trajectories to one fed the raw wire. The codec changes
wire representation only, never semantics. Fallbacks (uint16 non-ASCII
units, incompressible batches) must ship the raw layout, not fail.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from twtml_tpu.features import wirecodec as wc
from twtml_tpu.features.batch import (
    RaggedUnitBatch,
    align_ragged_shards,
    pack_batch,
    pack_ragged_group,
    pack_ragged_sharded,
    stack_batches,
    unpack_batch,
    wire_composition,
)
from twtml_tpu.features.featurizer import Featurizer
from twtml_tpu.models import StreamingLinearRegressionWithSGD
from twtml_tpu.streaming.sources import SyntheticSource

NOW = 1785320000000


def synthetic(n=128, seed=7):
    return list(SyntheticSource(total=n, seed=seed, base_ms=NOW).produce())


def ragged_batch(statuses, rows=64, unit_bucket=0):
    feat = Featurizer(now_ms=NOW)
    return feat.featurize_batch_ragged(
        statuses, row_bucket=rows, unit_bucket=unit_bucket, pre_filtered=True
    )


def assert_ragged_equal(a: RaggedUnitBatch, b: RaggedUnitBatch):
    for f in ("units", "offsets", "numeric", "label", "mask"):
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype, f
        np.testing.assert_array_equal(x, y, err_msg=f)
    assert (a.row_len, a.num_shards) == (b.row_len, b.num_shards)


# ---------------------------------------------------------------------------
# codec core: encoder ground truth, C parity, decode twins


def fuzz_buffers(rounds=40, seed=0):
    rng = np.random.default_rng(seed)
    out = [
        np.zeros((0,), np.uint8),
        np.zeros((1,), np.uint8),
        np.zeros((4096,), np.uint8),
        np.frombuffer(
            b"the quick brown fox jumps over https://t.co/Ab12 again and "
            b"again because english text is what the dictionary is for ",
            np.uint8,
        ),
    ]
    for _ in range(rounds):
        n = int(rng.integers(0, 2048))
        out.append(rng.integers(0, 128, n).astype(np.uint8))
        # runs of dictionary-hit pairs at adversarial alignments
        out.append(
            np.frombuffer((b"e " * int(rng.integers(1, 64)))[1:], np.uint8)
        )
    return out


def test_host_roundtrip_fuzz():
    for i, buf in enumerate(fuzz_buffers()):
        codes = wc.encode_np(buf)
        # literals stay < 128, codes >= 128, never longer than the input
        assert codes.shape[0] <= max(buf.shape[0], 1)
        out = wc.decode_np(codes, buf.shape[0])
        np.testing.assert_array_equal(out, buf, err_msg=f"buffer {i}")


def test_c_encoder_matches_numpy_ground_truth():
    from twtml_tpu.features import native

    if not native.available():
        pytest.skip("no native library on this host")
    for i, buf in enumerate(fuzz_buffers(rounds=60, seed=1)):
        np.testing.assert_array_equal(
            wc.encode(buf), wc.encode_np(buf), err_msg=f"buffer {i}"
        )


def test_greedy_is_maximal_munch():
    """The vectorized run-parity encode must equal the sequential greedy
    definition — checked against a literal Python reference loop."""
    lut = wc.pair_lut()

    def reference(buf):
        out, i, n = [], 0, buf.shape[0]
        while i < n:
            if i + 1 < n:
                c = lut[(int(buf[i]) << 8) | int(buf[i + 1])]
                if c != 0xFF:
                    out.append(wc.CODE_BASE + int(c))
                    i += 2
                    continue
            out.append(int(buf[i]))
            i += 1
        return np.array(out, np.uint8).reshape(-1)

    for buf in fuzz_buffers(rounds=25, seed=2):
        np.testing.assert_array_equal(wc.encode_np(buf), reference(buf))


def test_jit_decode_matches_host_twin():
    from twtml_tpu.ops.ragged import units_from_codes

    for buf in fuzz_buffers(rounds=10, seed=3):
        if buf.shape[0] == 0:
            continue
        codes = wc.encode_np(buf)
        dev = jax.jit(
            lambda c, n=buf.shape[0]: units_from_codes(c, n)
        )(jnp.asarray(codes))
        np.testing.assert_array_equal(np.asarray(dev), buf)


def test_dictionary_is_frozen_shape():
    lut, table = wc.pair_lut(), wc.decode_table()
    assert lut.shape == (65536,) and lut.dtype == np.uint8
    assert table.shape == (wc.CODE_BASE, 2) and table.dtype == np.uint8
    # every dictionary pair is ASCII and round-trips through the LUT
    hits = np.nonzero(lut != 0xFF)[0]
    assert hits.shape[0] == wc.CODE_BASE
    assert int(lut[0]) == 0  # the zero pair is entry 0 (the bucket tail)


# ---------------------------------------------------------------------------
# packed layouts: byte parity on every path


def both_unpacks(pb):
    """(host unpack, in-jit unpack) of one packed wire."""
    host = unpack_batch(pb.buffer, pb.layout)
    dev = jax.jit(
        lambda buf: tuple(
            getattr(unpack_batch(buf, pb.layout), f)
            for f in ("units", "offsets", "numeric", "label", "mask")
        )
    )(jnp.asarray(pb.buffer))
    return host, dev


def test_pack_batch_codec_byte_parity():
    rb = ragged_batch(synthetic())
    assert rb.units.dtype == np.uint8
    raw = pack_batch(rb)
    coded = pack_batch(rb, codec="dict")
    assert coded.buffer.nbytes < raw.buffer.nbytes
    host, dev = both_unpacks(coded)
    assert_ragged_equal(host, rb)
    for f, arr in zip(("units", "offsets", "numeric", "label", "mask"), dev):
        got = np.asarray(arr)
        want = np.asarray(getattr(rb, f))
        assert np.dtype(got.dtype) == np.dtype(want.dtype), f
        np.testing.assert_array_equal(got, want, err_msg=f)


def test_pack_sharded_codec_byte_parity():
    rb = ragged_batch(synthetic())
    for s in (1, 2, 4):
        al = align_ragged_shards(rb, s)
        raw = pack_ragged_sharded(al)
        coded = pack_ragged_sharded(al, codec="dict")
        assert coded.buffer.nbytes <= raw.buffer.nbytes
        assert_ragged_equal(unpack_batch(coded.buffer, coded.layout), al)
        # the device-side unpack sees ONE shard segment (the shard_map
        # local slice): decode each slice and reassemble
        per_seg = coded.buffer.shape[0] // s
        al_units = np.asarray(al.units).reshape(s, -1)
        for seg in range(s):
            sl = coded.buffer[seg * per_seg : (seg + 1) * per_seg]
            local = jax.jit(
                lambda buf: unpack_batch(buf, coded.layout).units
            )(jnp.asarray(sl))
            np.testing.assert_array_equal(np.asarray(local), al_units[seg])


def test_pack_group_codec_byte_parity():
    statuses = synthetic(192)
    parts = [
        ragged_batch(statuses[i * 64 : (i + 1) * 64], rows=64, unit_bucket=64)
        for i in range(3)
    ]
    if len({(p.units.shape, p.row_len) for p in parts}) != 1:
        pytest.skip("synthetic batches landed in different unit buckets")
    stacked = stack_batches(parts)
    raw = pack_ragged_group(parts)
    coded = pack_ragged_group(parts, codec="dict")
    assert coded.buffer.nbytes < raw.buffer.nbytes
    assert_ragged_equal(unpack_batch(coded.buffer, coded.layout), stacked)
    dev = jax.jit(lambda buf: unpack_batch(buf, coded.layout).units)(
        jnp.asarray(coded.buffer)
    )
    np.testing.assert_array_equal(np.asarray(dev), np.asarray(stacked.units))


def test_uint16_units_ship_raw():
    """Non-ASCII-widened (uint16) units are ineligible — the metadata
    gate, like the int32 offset fallback: the layout records no codec."""
    statuses = synthetic()
    for s in statuses:
        if s.retweeted_status is not None:
            s.retweeted_status.text = "héllo wörld " + s.retweeted_status.text
    rb = ragged_batch(statuses)
    assert rb.units.dtype == np.uint16
    coded = pack_batch(rb, codec="dict")
    from twtml_tpu.features.batch import _layout_codec

    assert _layout_codec(coded.layout) is None
    assert_ragged_equal(unpack_batch(coded.buffer, coded.layout), rb)


def test_incompressible_batch_ships_raw():
    """A units buffer with ~no dictionary hits must keep the raw layout
    (the bucketed encoding would not shrink the wire)."""
    rng = np.random.default_rng(5)
    n, b = 4096, 32
    units = rng.integers(1, 128, n).astype(np.uint8)
    # kill accidental pair hits so the stream is truly incompressible
    lut = wc.pair_lut()
    hit = lut[(units[:-1].astype(np.uint16) << 8) | units[1:]] != 0xFF
    while hit.any():
        units[np.nonzero(hit)[0]] = rng.integers(1, 128, int(hit.sum()))
        hit = lut[(units[:-1].astype(np.uint16) << 8) | units[1:]] != 0xFF
    offsets = np.linspace(0, n, b + 1).astype(np.int32)
    rb = RaggedUnitBatch(
        units, offsets,
        np.zeros((b, 4), np.float32), np.zeros((b,), np.float32),
        np.ones((b,), np.float32), row_len=256,
    )
    coded = pack_batch(rb, codec="dict")
    from twtml_tpu.features.batch import _layout_codec

    assert _layout_codec(coded.layout) is None
    assert_ragged_equal(unpack_batch(coded.buffer, coded.layout), rb)


def test_empty_and_tiny_batches():
    """All-padding and single-row batches ride the codec like any other —
    the zero tail is the dictionary's entry 0 and compresses 2x."""
    feat = Featurizer(now_ms=NOW)
    empty = feat.featurize_batch_ragged([], row_bucket=32)
    one = ragged_batch(synthetic(4)[:1], rows=32)
    for rb in (empty, one):
        coded = pack_batch(rb, codec="dict")
        host, _dev = both_unpacks(coded)
        assert_ragged_equal(host, rb)


def test_oversized_rows_roundtrip():
    statuses = synthetic(16)
    for s in statuses:
        if s.retweeted_status is not None:
            s.retweeted_status.text = (
                s.retweeted_status.text + " padding words" * 200
            )
    rb = ragged_batch(statuses, rows=16)
    coded = pack_batch(rb, codec="dict")
    host, _ = both_unpacks(coded)
    assert_ragged_equal(host, rb)


def test_pack_fuzz_seeded():
    """Seeded fuzz over synthetic streams × shard counts × codec on/off:
    the unpacked view must always equal the pre-pack batch."""
    for seed in (11, 23, 47):
        rb = ragged_batch(synthetic(96, seed=seed), rows=32)
        for s in (1, 2, 4):
            al = align_ragged_shards(rb, s)
            pb = pack_ragged_sharded(al, codec="dict")
            assert_ragged_equal(unpack_batch(pb.buffer, pb.layout), al)


# ---------------------------------------------------------------------------
# model-level parity: the codec wire may never change the math


def test_model_trajectory_bitwise_identical():
    statuses = synthetic(192, seed=3)
    chunks = [statuses[i : i + 64] for i in range(0, 192, 64)]
    batches = [ragged_batch(c, rows=64, unit_bucket=64) for c in chunks]
    m_raw = StreamingLinearRegressionWithSGD(num_iterations=5, step_size=0.1)
    m_codec = StreamingLinearRegressionWithSGD(
        num_iterations=5, step_size=0.1
    )
    for b in batches:
        out_raw = m_raw.step(pack_batch(b))
        out_codec = m_codec.step(pack_batch(b, codec="dict"))
        for a, c in zip(out_raw, out_codec):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_array_equal(
        m_raw.latest_weights, m_codec.latest_weights
    )


def test_mesh_sharded_model_bitwise_identical():
    """4-way data mesh: the codec-packed per-shard wire trains
    bit-identically to the raw packed wire (the shard_map body decodes
    its own segment in-program)."""
    from twtml_tpu.parallel import ParallelSGDModel, make_mesh

    statuses = synthetic(128, seed=9)
    chunks = [statuses[i : i + 64] for i in range(0, 128, 64)]
    batches = [ragged_batch(c, rows=64, unit_bucket=64) for c in chunks]
    mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
    m_raw = ParallelSGDModel(mesh, num_iterations=5, step_size=0.1)
    m_codec = ParallelSGDModel(mesh, num_iterations=5, step_size=0.1)
    m_codec.wire_codec = "dict"
    for b in batches:
        out_raw = m_raw.step(m_raw.pack_for_wire(b))
        out_codec = m_codec.step(m_codec.pack_for_wire(b))
        for a, c in zip(out_raw, out_codec):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_array_equal(
        m_raw.latest_weights, m_codec.latest_weights
    )


def test_pipeline_codec_wire_bitwise_identical():
    """``--wireCodec dict`` as the apps run it — FetchPipeline packing each
    batch through the codec — delivers the same per-batch stats and leaves
    the same weights, bit for bit, as the raw packed wire."""
    from twtml_tpu.apps.common import FetchPipeline
    from twtml_tpu.telemetry import metrics as _metrics

    statuses = synthetic(192, seed=21)
    batches = [
        ragged_batch(statuses[i : i + 64], rows=64, unit_bucket=64)
        for i in range(0, 192, 64)
    ]

    def run(codec):
        _metrics.reset_for_tests()
        model = StreamingLinearRegressionWithSGD(
            num_iterations=5, step_size=0.1
        )
        seen = []
        pipe = FetchPipeline(
            model,
            lambda out, b, t, at_boundary: seen.append(
                [np.asarray(a).tobytes() for a in out if a is not None]
            ),
            depth=8, pack=True, wire_codec=codec,
        )
        for i, b in enumerate(batches):
            pipe.on_batch(b, float(i))
        pipe.flush()
        gauges = _metrics.get_registry().snapshot()["gauges"]
        return model.latest_weights, seen, gauges

    w_raw, seen_raw, g_raw = run("")
    w_codec, seen_codec, g_codec = run("dict")
    _metrics.reset_for_tests()
    assert "wire.codec_ratio" not in g_raw
    assert g_codec["wire.codec_ratio"] > 1.0  # the codec really engaged
    assert seen_codec == seen_raw and len(seen_raw) == 3
    np.testing.assert_array_equal(w_codec, w_raw)


def test_tenant_group_wire_bitwise_identical():
    """The coalesced M-tenant wire with the codec on == codec off, bit for
    bit (stats and weights)."""
    from twtml_tpu.parallel.tenants import TenantStackModel

    statuses = synthetic(128, seed=31)
    batch = ragged_batch(statuses, rows=128)
    m_raw = TenantStackModel(3, wire_pack="group", num_iterations=5)
    m_codec = TenantStackModel(
        3, wire_pack="group", wire_codec="dict", num_iterations=5
    )
    out_raw = m_raw.step(batch)
    out_codec = m_codec.step(batch)
    for a, c in zip(out_raw, out_codec):
        if a is None:
            assert c is None
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_array_equal(
        np.asarray(m_raw.latest_weights), np.asarray(m_codec.latest_weights)
    )


# ---------------------------------------------------------------------------
# telemetry + config surface


def test_wire_composition_reports_compressed_split():
    rb = ragged_batch(synthetic())
    raw_comp = wire_composition(pack_batch(rb))
    coded_comp = wire_composition(pack_batch(rb, codec="dict"))
    # "units" stays the RAW bytes (agrees with the unpacked view)...
    assert coded_comp["units"] == raw_comp["units"]
    assert coded_comp["offsets"] == raw_comp["offsets"]
    assert coded_comp["sideband"] == raw_comp["sideband"]
    # ...and the physical wire is the compressed size
    assert 0 < coded_comp["units_compressed"] < coded_comp["units"]
    assert "units_compressed" not in raw_comp


def test_codec_gauges_and_fallback_counter():
    from twtml_tpu.apps.common import _record_wire_codec
    from twtml_tpu.telemetry import metrics as _metrics

    reg = _metrics.get_registry()
    rb = ragged_batch(synthetic())
    before = reg.counter("wire.codec_fallbacks").value
    _record_wire_codec(pack_batch(rb, codec="dict"), "dict")
    assert reg.gauge("wire.codec_ratio").value > 1.0
    assert reg.gauge("wire.units_compressed_bytes").value > 0
    assert reg.counter("wire.codec_fallbacks").value == before
    # a raw wire that REQUESTED the codec counts as a fallback
    _record_wire_codec(pack_batch(rb), "dict")
    assert reg.counter("wire.codec_fallbacks").value == before + 1
    assert reg.gauge("wire.codec_ratio").value == 1.0


def test_config_flag_resolution():
    from twtml_tpu.config import ConfArguments

    conf = ConfArguments().parse(["--seconds", "0"])
    assert conf.wireCodec == "auto"
    assert conf.effective_wire_codec() == "off"  # auto = off, on-chip verdict pending
    conf = ConfArguments().parse(["--seconds", "0", "--wireCodec", "dict"])
    assert conf.effective_wire_codec() == "dict"
    # dict + superbatch resolves the coalesced group wire
    assert conf.effective_wire_pack() == "group"
    # explicit stacked contradicts the codec — loud, not silent
    conf = ConfArguments().parse(
        ["--seconds", "0", "--wireCodec", "dict", "--wirePack", "stacked"]
    )
    with pytest.raises(ValueError, match="stacked contradicts"):
        conf.effective_wire_pack()
    # the codec needs the ragged raw-units wire
    conf = ConfArguments().parse(["--wireCodec", "dict", "--hashOn", "host"])
    with pytest.raises(ValueError, match="ragged"):
        conf.effective_wire_codec()
