"""One-pass wire assembly + pooled buffer arena (ISSUE 14, r17).

The fused native emitter (native/wireassemble.cpp via
features/assemble.py) must be BYTE-IDENTICAL — buffer and layout — to the
numpy pack pipeline (features/batch.py, the ground truth) on every wire
form × codec state × fallback, and trained trajectories must be
bitwise-equal with the assembler on vs off. The arena
(features/arena.py) changes who owns the bytes, never the bytes: leases
ride the dispatch pipelines and retire on fetch delivery (discard on
abort), with the accounting asserted here. The stale-library degrade
seam mirrors PR 6's: a real .so without ``wire_assemble`` loads, flags
once, and every pack keeps flowing through numpy.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from twtml_tpu.features import arena as arena_mod  # noqa: E402
from twtml_tpu.features import assemble, native  # noqa: E402
from twtml_tpu.features.batch import (  # noqa: E402
    OFFSET_DELTA_MAX,
    RaggedUnitBatch,
    align_ragged_shards,
    pack_batch,
    pack_ragged_group,
    pack_ragged_sharded,
    ragged_wire_arrays,
    unpack_batch,
)
from twtml_tpu.features.featurizer import Featurizer  # noqa: E402
from twtml_tpu.models import StreamingLinearRegressionWithSGD  # noqa: E402
from twtml_tpu.streaming.sources import SyntheticSource  # noqa: E402

needs_native = pytest.mark.skipif(
    not native.assemble_available(),
    reason="native wire assembler unavailable (no g++?)",
)


# ---------------------------------------------------------------------------
# builders


def hand_batch(
    b=32, seed=1, wide=False, incompressible=False, row_len=96
):
    """Hand-built ragged batch: ASCII tweet-like text by default; ``wide``
    adds one non-ASCII row (the uint16-widened wire); ``incompressible``
    uses uniform random bytes (the codec's raw fallback)."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(b - 3):
        n = int(rng.integers(1, row_len))
        if incompressible:
            rows.append(rng.integers(0, 128, n).astype(np.uint16))
        else:
            text = np.frombuffer(
                b"the streaming fox https://t.co/ab jumps again and ",
                np.uint8,
            )
            rows.append(text[np.arange(n) % len(text)].astype(np.uint16))
    if wide and rows:
        rows[0] = np.concatenate(
            [rows[0], np.array([0x3042], np.uint16)]
        )
    units = (
        np.concatenate(rows) if rows else np.zeros(0, np.uint16)
    )
    offsets = np.zeros(len(rows) + 1, np.int64)
    np.cumsum([len(r) for r in rows], out=offsets[1:])
    flat, offs = ragged_wire_arrays(
        units, offsets, len(rows), b, narrow=not wide
    )
    numeric = rng.normal(size=(b, 4)).astype(np.float32)
    label = rng.uniform(0, 50, size=(b,)).astype(np.float32)
    mask = np.zeros(b, np.float32)
    mask[: len(rows)] = 1.0
    return RaggedUnitBatch(
        flat, offs, numeric, label, mask, row_len=row_len
    )


def signature_variants(al, k):
    """k same-signature copies differing only in sideband values."""
    return [
        RaggedUnitBatch(
            al.units.copy(), al.offsets.copy(), al.numeric + j,
            al.label + j, al.mask.copy(),
            row_len=al.row_len, num_shards=al.num_shards,
        )
        for j in range(k)
    ]


def featurized_batches(n=4, rows=16, unit_bucket=512):
    statuses = list(SyntheticSource(
        total=n * rows, seed=3, base_ms=1785320000000
    ).produce())
    feat = Featurizer(now_ms=1785320000000)
    return [
        feat.featurize_batch_ragged(
            statuses[i * rows : (i + 1) * rows], row_bucket=rows,
            unit_bucket=unit_bucket, pre_filtered=True,
        )
        for i in range(n)
    ]


def assert_same_packed(got, ref, tag=""):
    assert got.layout == ref.layout, (tag, got.layout, ref.layout)
    np.testing.assert_array_equal(
        np.asarray(got.buffer), np.asarray(ref.buffer), err_msg=tag
    )


def both_modes(fn):
    with assemble.forced("off"):
        ref = fn()
    with assemble.forced("on"):
        got = fn()
    return got, ref


# ---------------------------------------------------------------------------
# byte parity: every layout × codec × fallback


@needs_native
@pytest.mark.parametrize("codec", [None, "dict"])
@pytest.mark.parametrize("wide", [False, True])
@pytest.mark.parametrize("incompressible", [False, True])
def test_flat_pack_byte_parity(codec, wide, incompressible):
    rb = hand_batch(wide=wide, incompressible=incompressible)
    got, ref = both_modes(lambda: pack_batch(rb, codec=codec))
    assert_same_packed(got, ref, "flat")
    # and the fast path actually ran (not a silent permanent fallback)
    assert got._lease is not None


@needs_native
@pytest.mark.parametrize("codec", [None, "dict"])
@pytest.mark.parametrize("s", [1, 2, 4])
def test_sharded_pack_byte_parity(codec, s):
    al = align_ragged_shards(hand_batch(), s)
    got, ref = both_modes(
        lambda: pack_ragged_sharded(al, codec=codec)
    )
    assert_same_packed(got, ref, f"sharded s={s}")


@needs_native
@pytest.mark.parametrize("codec", [None, "dict"])
@pytest.mark.parametrize("s,k", [(1, 1), (1, 3), (2, 1), (2, 3)])
def test_group_pack_byte_parity(codec, s, k):
    parts = signature_variants(
        align_ragged_shards(hand_batch(), s), k
    )
    got, ref = both_modes(
        lambda: pack_ragged_group(parts, codec=codec)
    )
    assert_same_packed(got, ref, f"group s={s} k={k}")


@needs_native
@pytest.mark.parametrize("narrow", [None, False])
def test_offset_modes_byte_parity(narrow):
    rb = hand_batch()
    got, ref = both_modes(
        lambda: pack_batch(rb, narrow_offsets=narrow)
    )
    assert_same_packed(got, ref, f"narrow={narrow}")
    al = align_ragged_shards(rb, 2)
    got, ref = both_modes(
        lambda: pack_ragged_sharded(al, narrow_offsets=narrow)
    )
    assert_same_packed(got, ref)


@needs_native
def test_featurized_group_byte_parity():
    batches = featurized_batches(n=4)
    got, ref = both_modes(lambda: pack_ragged_group(batches))
    assert_same_packed(got, ref, "featurized group")


@needs_native
def test_long_row_int32_fallback_parity():
    """row_len past the uint16 delta range: the metadata gate keeps the
    int32 offset wire in BOTH paths (auto narrow resolves to off)."""
    from twtml_tpu.features.batch import _bucket

    lens = np.array([8, OFFSET_DELTA_MAX + 2, 4, 6])
    offsets = np.zeros(5, np.int64)
    np.cumsum(lens, out=offsets[1:])
    units = np.random.default_rng(7).integers(
        97, 123, size=int(lens.sum())
    ).astype(np.uint16)
    flat, offs = ragged_wire_arrays(units, offsets, 4, 4, narrow=True)
    rb = RaggedUnitBatch(
        flat, offs,
        np.zeros((4, 4), np.float32), np.zeros(4, np.float32),
        np.ones(4, np.float32), row_len=_bucket(OFFSET_DELTA_MAX + 2),
    )
    got, ref = both_modes(lambda: pack_batch(rb))
    assert got.layout[2][2] == "i32"
    assert_same_packed(got, ref, "long-row i32")
    # forcing the narrow wire past the gate raises in both modes (the
    # native path refuses and routes to the numpy error)
    for mode in ("off", "on"):
        with assemble.forced(mode):
            with pytest.raises(ValueError):
                pack_batch(rb, narrow_offsets=True)


@needs_native
def test_forced_codec_bucket_parity_and_overflow():
    """The multi-host agreed bucket: parity when it covers, the canonical
    ValueError (from the ground truth) when it under-covers — in both
    modes."""
    from twtml_tpu.features.wirecodec import encode, encoded_bucket

    al = align_ragged_shards(hand_batch(), 2)
    segs = np.asarray(al.units).reshape(2, -1)
    max_enc = max(encode(r).shape[0] for r in segs)
    bucket = encoded_bucket(max_enc) + 1024
    got, ref = both_modes(
        lambda: pack_ragged_sharded(
            al, codec="dict", codec_bucket=bucket
        )
    )
    assert_same_packed(got, ref, "forced bucket")
    if max_enc > 1:
        under = max(1, max_enc - 1)
        for mode in ("off", "on"):
            with assemble.forced(mode):
                with pytest.raises(ValueError):
                    pack_ragged_sharded(
                        al, codec="dict", codec_bucket=under
                    )


@needs_native
def test_unpack_round_trip_host_and_jit():
    import jax

    parts = signature_variants(
        align_ragged_shards(hand_batch(), 1), 3
    )
    with assemble.forced("on"):
        pb = pack_ragged_group(parts, codec="dict")
    host = unpack_batch(pb.buffer, pb.layout)
    with assemble.forced("off"):
        ref = unpack_batch(
            pack_ragged_group(parts, codec="dict").buffer, pb.layout
        )
    for f in ("units", "offsets", "numeric", "label", "mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(host, f)), np.asarray(getattr(ref, f))
        )
    dev = jax.jit(lambda buf: unpack_batch(buf, pb.layout).units)(
        pb.buffer
    )
    np.testing.assert_array_equal(np.asarray(dev), np.asarray(host.units))


# ---------------------------------------------------------------------------
# trajectory parity: assembler on vs off trains bitwise-equal weights


@needs_native
def test_trajectory_bitwise_single_device():
    batches = featurized_batches(n=6)
    finals = {}
    for mode in ("off", "on"):
        with assemble.forced(mode):
            m = StreamingLinearRegressionWithSGD(num_iterations=5)
            for b in batches:
                m.step(pack_batch(b))
            finals[mode] = np.asarray(m.latest_weights)
    np.testing.assert_array_equal(finals["off"], finals["on"])


@needs_native
def test_trajectory_bitwise_mesh():
    import jax

    from twtml_tpu.parallel import ParallelSGDModel, make_mesh

    batches = featurized_batches(n=4, rows=32)
    finals = {}
    for mode in ("off", "on"):
        with assemble.forced(mode):
            mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
            m = ParallelSGDModel(mesh, num_iterations=5, step_size=0.05)
            for b in batches:
                m.step(m.pack_for_wire(b))
            finals[mode] = np.asarray(m.latest_weights)
    np.testing.assert_array_equal(finals["off"], finals["on"])


@needs_native
def test_trajectory_bitwise_tenant_stack():
    from twtml_tpu.parallel import TenantStackModel

    batches = featurized_batches(n=4, rows=32)
    finals = {}
    for mode in ("off", "on"):
        with assemble.forced(mode):
            mt = TenantStackModel(
                3, num_iterations=5, step_size=0.1, wire_pack="group"
            )
            for b in batches:
                mt.step(b)
            finals[mode] = np.asarray(mt.latest_weights)
    np.testing.assert_array_equal(finals["off"], finals["on"])


# ---------------------------------------------------------------------------
# arena accounting


def test_arena_lease_retire_recycles():
    a = arena_mod.WireArena()
    l1 = a.lease(4096)
    buf1 = l1.buf
    assert a.stats()["in_use"] == 1
    l1.retire()
    assert a.stats() == {
        "in_use": 0, "free_buffers": 1, "free_bytes": 4096,
    }
    l2 = a.lease(4096)
    assert l2.buf is buf1  # recycled, not reallocated
    # retire is idempotent
    l2.retire()
    l2.retire()
    assert a.stats()["in_use"] == 0
    assert a.stats()["free_buffers"] == 1


def test_arena_discard_never_recycles():
    a = arena_mod.WireArena()
    le = a.lease(2048)
    le.discard()
    assert a.stats() == {
        "in_use": 0, "free_buffers": 0, "free_bytes": 0,
    }


def test_arena_pool_cap_bounds_free_bytes():
    a = arena_mod.WireArena(max_pool_bytes=8192)
    leases = [a.lease(4096) for _ in range(4)]
    for le in leases:
        le.retire()
    assert a.stats()["free_bytes"] <= 8192


def test_arena_disabled_is_fresh_alloc_control():
    a = arena_mod.WireArena()
    a.enabled = False
    le = a.lease(1024)
    le.retire()
    assert a.stats()["free_buffers"] == 0  # nothing pooled
    l2 = a.lease(1024)
    assert l2.buf is not le.buf


def test_pack_attaches_lease_and_counts():
    from twtml_tpu.telemetry import metrics as _metrics

    arena_mod.get_arena().reset_for_tests()
    reg = _metrics.get_registry()
    before = reg.counter("wire.arena_misses").snapshot()
    rb = hand_batch()
    pb = pack_batch(rb)
    assert pb._lease is not None
    assert pb._lease.buf.nbytes >= pb.buffer.nbytes
    assert reg.counter("wire.arena_misses").snapshot() > before
    pb._lease.retire()
    pb2 = pack_batch(rb)
    # identical signature → the retired buffer is the recycled one
    assert pb2._lease.buf is pb._lease.buf
    pb2._lease.retire()


# ---------------------------------------------------------------------------
# pipeline integration: leases retire on delivery, discard on abort


class _EchoModel:
    """Step = identity-ish dispatch; fetch-side device_get of plain numpy
    is a no-op — enough to drive the pipelines' accounting."""

    accepts_packed = True

    def step(self, wire):
        return {"mse": np.float32(1.0)}


def _ragged_stream(n=5):
    return [hand_batch(seed=10 + i) for i in range(n)]


def test_fetch_pipeline_retires_leases_on_delivery():
    from twtml_tpu.apps.common import FetchPipeline

    arena_mod.get_arena().reset_for_tests()
    got = []
    pipe = FetchPipeline(
        _EchoModel(), lambda out, b, t, at_boundary: got.append(out),
        depth=3, pack=True,
    )
    for i, b in enumerate(_ragged_stream()):
        pipe.on_batch(b, float(i))
    pipe.flush()
    assert len(got) == 5
    st = arena_mod.get_arena().stats()
    assert st["in_use"] == 0  # every lease retired on delivery
    assert st["free_buffers"] >= 1  # and recycled through the pool


def test_one_delivery_a_round_keeps_the_arena_at_one_buffer():
    """PR 39: the round delivers what was done at its count BEFORE it packs,
    so in the host-paced steady state (each result done one round after its
    dispatch) the lease of the batch just delivered is back in the pool
    when the next wire is packed: ``wire.arena_misses`` stays at the one
    buffer of the first round and every later pack recycles."""
    import threading

    from twtml_tpu.apps.common import FetchPipeline
    from twtml_tpu.telemetry import metrics as _metrics

    class _Lagged(_EchoModel):
        def __init__(self):
            self.gates = []

        def step(self, wire):
            self.gates.append(threading.Event())
            return len(self.gates) - 1

        def fetch_output(self, out):
            assert self.gates[out].wait(30)
            return out

    _metrics.reset_for_tests()
    arena_mod.get_arena().reset_for_tests()
    model, got = _Lagged(), []
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: got.append(out),
        depth=8, pack=True,
    )
    misses = _metrics.get_registry().counter("wire.arena_misses")
    batch, warm = hand_batch(seed=10), None
    for i in range(24):
        pipe.on_batch(batch, float(i))
        assert got == list(range(i))       # one delivery a round, one late
        model.gates[i].set()               # done before the next count
        next(e[0] for e in pipe._pending if e[1] == i).result(timeout=30)
        if i == 3:
            warm = misses.snapshot()
    assert arena_mod.get_arena().stats()["in_use"] == 1
    assert misses.snapshot() == warm == 1  # the first round's, recycled since
    pipe.flush()
    assert got == list(range(24))
    assert arena_mod.get_arena().stats()["in_use"] == 0


def test_fetch_pipeline_discards_leases_on_abort(monkeypatch):
    from twtml_tpu.apps.common import FetchAbort, FetchPipeline

    arena_mod.get_arena().reset_for_tests()
    # deterministic: no opportunistic early emit — all three stay pending
    pipe = FetchPipeline(
        _EchoModel(), lambda *a, **k: None, depth=8, pack=True,
        deterministic=True,
    )
    for i, b in enumerate(_ragged_stream(3)):
        pipe.on_batch(b, float(i))
    assert arena_mod.get_arena().stats()["in_use"] == 3

    def boom(future, reissue):
        raise FetchAbort("wedged")

    monkeypatch.setattr(pipe._watchdog, "await_result", boom)
    pipe.flush()  # drops pending outputs, discards (never pools) leases
    st = arena_mod.get_arena().stats()
    assert st["in_use"] == 0
    assert st["free_buffers"] == 0  # abort path: no reuse


def test_tenant_group_wire_leases_retire():
    """The coalesced M-tenant wire (``pack_ragged_group`` behind
    ``TenantStackModel.pack_for_wire``) leases ONE buffer per dispatch;
    the pipeline retires it to the pool on delivery."""
    from twtml_tpu.apps.common import FetchPipeline
    from twtml_tpu.parallel import TenantStackModel

    arena_mod.get_arena().reset_for_tests()
    got = []
    pipe = FetchPipeline(
        TenantStackModel(2, num_iterations=2, wire_pack="group"),
        lambda out, b, t, at_boundary: got.append(t),
        depth=3, pack=True, deterministic=True,
    )
    for j, b in enumerate(featurized_batches(n=5)):
        pipe.on_batch(b, float(j))
        assert arena_mod.get_arena().stats()["in_use"] >= 1
    pipe.flush()
    assert got == [0.0, 1.0, 2.0, 3.0, 4.0]
    st = arena_mod.get_arena().stats()
    assert st["in_use"] == 0
    assert st["free_buffers"] >= 1


# ---------------------------------------------------------------------------
# the stale-library degrade seam


def test_bind_assemble_flags_missing_symbol_and_counts(monkeypatch):
    from twtml_tpu.telemetry import metrics as _metrics

    class _NoAssemble:
        def __getattr__(self, name):
            raise AttributeError(name)

    _metrics.reset_for_tests()
    monkeypatch.setattr(native, "_assemble_missing", False)
    with pytest.raises(AttributeError):
        native._bind_assemble(_NoAssemble(), strict=True)
    native._bind_assemble(_NoAssemble(), strict=False)
    assert native._assemble_missing
    assert _metrics.get_registry().counter(
        "native.assemble_degraded"
    ).snapshot() == 1
    monkeypatch.setattr(native, "_assemble_missing", False)


def test_assemble_missing_degrades_to_numpy(monkeypatch):
    monkeypatch.setattr(native, "_assemble_missing", True)
    assert not native.assemble_available()
    assert not assemble.available()
    rb = hand_batch()
    with assemble.forced("on"):  # even explicit on degrades, never dies
        pb = pack_batch(rb)
    monkeypatch.setattr(native, "_assemble_missing", False)
    with assemble.forced("off"):
        ref = pack_batch(rb)
    assert_same_packed(pb, ref, "degraded")


def test_stale_library_without_assemble_symbol_loads_degraded(tmp_path):
    """End-to-end seam: a REAL .so carrying every pre-r17 symbol but not
    ``wire_assemble`` loads with strict=False, flags the degrade, and
    keeps the old symbols callable — no ctypes AttributeError
    mid-stream."""
    src = tmp_path / "stale.cpp"
    src.write_text(
        """
#include <cstdint>
extern "C" {
int32_t fasthash_batch(uint16_t*, int64_t*, int32_t, int32_t, int32_t,
                       int32_t*, float*, int32_t*, int32_t) { return 0; }
int32_t pad_units_batch(uint16_t*, int64_t*, int32_t, int32_t, int32_t,
                        int32_t, uint16_t*, int32_t*) { return 0; }
int32_t pad_units_batch_u8(uint16_t*, int64_t*, int32_t, int32_t, int32_t,
                           int32_t, uint8_t*, int32_t*) { return 0; }
void lexicon_score_batch(uint16_t*, int64_t*, int32_t, uint16_t*, int64_t*,
                         int32_t*, int32_t, uint16_t*, int64_t*, int32_t*,
                         int32_t, int32_t*, uint8_t*) {}
int64_t parse_tweet_block(const char*, int64_t, int64_t, int64_t, int64_t,
                          int64_t, int64_t*, uint16_t*, int64_t*, uint8_t*,
                          int64_t* c, int64_t* b) { *c = 0; *b = 0; return 0; }
int64_t parse_tweet_block_wire(const char*, int64_t, int64_t, int64_t,
                               int64_t, int64_t, int64_t*, uint8_t*,
                               uint16_t*, int64_t*, uint8_t*, int64_t* c,
                               int64_t* b, int64_t* n, int64_t* w) {
  *c = 0; *b = 0; *n = 1; *w = 0; return 0; }
int64_t digram_encode(const uint8_t*, int64_t, const uint8_t*, uint8_t*,
                      int64_t) { return 0; }
}
""",
        encoding="utf-8",
    )
    so = tmp_path / "stale.so"
    subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-o", str(so), str(src)],
        check=True, capture_output=True,
    )
    saved = native._assemble_missing
    try:
        with pytest.raises(AttributeError):
            native._load(str(so), strict=True)
        lib = native._load(str(so), strict=False)
        assert native._assemble_missing
        assert lib.digram_encode is not None  # old symbols still bound
    finally:
        native._assemble_missing = saved
        # every degrade flag, not just ours: the degraded _load also
        # flagged the r18 featurize symbol this stale lib lacks
        native.rebind_flags()


# ---------------------------------------------------------------------------
# mode plumbing


def test_configure_validates_and_env_default():
    with pytest.raises(ValueError):
        assemble.configure("maybe")
    prev = assemble.mode()
    assemble.configure("off")
    assert not assemble.available()
    assemble.configure(prev)


@needs_native
def test_assembled_counter_increments():
    from twtml_tpu.telemetry import metrics as _metrics

    reg = _metrics.get_registry()
    before = reg.counter("wire.assembled_native").snapshot()
    with assemble.forced("on"):
        pack_batch(hand_batch())
    assert reg.counter("wire.assembled_native").snapshot() == before + 1
