"""Per-shard packed ragged wire (r5): ``pack_ragged_sharded`` lays a
shard-aligned RaggedUnitBatch into ONE buffer whose S equal segments are the
shards, so the mesh data axis shards the single buffer and each device
rebuilds its local batch in-program — one-buffer packing extended to
every layout. Parity bar: bit-identical weights vs both the
unpacked ragged wire and the padded units wire on the same mesh."""

import jax
import numpy as np
import pytest

from twtml_tpu.features.batch import (
    RaggedUnitBatch,
    align_ragged_shards,
    pack_ragged_sharded,
    unpack_batch,
)
from twtml_tpu.features.featurizer import Featurizer
from twtml_tpu.parallel import ParallelSGDModel, make_mesh
from twtml_tpu.parallel.sharding import shard_batch
from twtml_tpu.streaming.sources import SyntheticSource


def _ragged_batch(rows=32, f_text=None, seed=3):
    statuses = list(
        SyntheticSource(total=rows, seed=seed, base_ms=1785320000000).produce()
    )
    feat = Featurizer(now_ms=1785320000000, **(
        {"num_text_features": f_text} if f_text else {}
    ))
    return feat.featurize_batch_ragged(
        statuses, row_bucket=rows, pre_filtered=True
    ), feat, statuses


def test_pack_unpack_roundtrip_host():
    rb, _, _ = _ragged_batch()
    aligned = align_ragged_shards(rb, 4)
    pb = pack_ragged_sharded(aligned)
    back = unpack_batch(pb.buffer, pb.layout)
    assert isinstance(back, RaggedUnitBatch)
    assert back.num_shards == 4 and back.row_len == aligned.row_len
    for f in ("units", "offsets", "numeric", "label", "mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(back, f)), np.asarray(getattr(aligned, f))
        )
    assert pb.num_valid == aligned.num_valid


def test_pack_single_shard_alignment_is_legal():
    # 1-device meshes and the one-shard-per-process topology pack s=1
    rb, _, _ = _ragged_batch(rows=16)
    pb = pack_ragged_sharded(rb)
    back = unpack_batch(pb.buffer, pb.layout)
    np.testing.assert_array_equal(
        np.asarray(back.units), np.asarray(rb.units)
    )
    assert back.num_shards == 1


def test_layout_records_global_shards():
    rb, _, _ = _ragged_batch(rows=16)
    aligned = align_ragged_shards(rb, 2)
    pb = pack_ragged_sharded(aligned, num_shards_out=4)
    assert pb.layout[2][1] == 4


@pytest.mark.parametrize(
    "mesh_kw", [dict(num_data=4), dict(num_data=2, num_model=2)]
)
def test_mesh_packed_step_bit_matches_unpacked(mesh_kw):
    rb, feat, statuses = _ragged_batch(rows=32)
    unit = feat.featurize_batch_units(statuses, row_bucket=32, pre_filtered=True)
    mesh = make_mesh(devices=jax.devices()[:4], **mesh_kw)

    packed = ParallelSGDModel(mesh, num_iterations=5, step_size=0.005)
    plain = ParallelSGDModel(mesh, num_iterations=5, step_size=0.005)
    padded = ParallelSGDModel(mesh, num_iterations=5, step_size=0.005)

    out_p = packed.step(packed.pack_for_wire(rb))
    out_r = plain.step(shard_batch(rb, mesh))
    out_u = padded.step(unit)

    assert float(out_p.count) == float(out_r.count) == float(out_u.count)
    np.testing.assert_array_equal(
        np.asarray(out_p.predictions), np.asarray(out_r.predictions)
    )
    np.testing.assert_array_equal(packed.latest_weights, plain.latest_weights)
    np.testing.assert_array_equal(packed.latest_weights, padded.latest_weights)


def test_mesh_pack_one_device_mesh():
    rb, _, _ = _ragged_batch(rows=16)
    mesh = make_mesh(num_data=1, devices=jax.devices()[:1])
    m = ParallelSGDModel(mesh, num_iterations=5, step_size=0.005)
    out = m.step(m.pack_for_wire(rb))
    assert float(out.count) == rb.num_valid


def test_mesh_rejects_flat_pack():
    from twtml_tpu.features.batch import pack_batch

    rb, _, _ = _ragged_batch(rows=16)
    mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
    m = ParallelSGDModel(mesh, num_iterations=5, step_size=0.005)
    with pytest.raises(ValueError, match="per-shard packed layout"):
        m.step(pack_batch(rb))


def test_mesh_rejects_mismatched_shard_layout():
    rb, _, _ = _ragged_batch(rows=32)
    mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
    m = ParallelSGDModel(mesh, num_iterations=5, step_size=0.005)
    pb = pack_ragged_sharded(align_ragged_shards(rb, 2))
    with pytest.raises(ValueError, match="laid out for 2 shards"):
        m.step(pb)
