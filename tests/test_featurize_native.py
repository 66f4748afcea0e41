"""One-pass native featurize (ISSUE 15, r18).

The fused C emitter (native/featurize.cpp via
features/featurize_native.py) must produce batches BIT-IDENTICAL — every
array, every dtype, the row_len aux — to the Python/numpy ground truth
in features/featurizer.py on both ingest paths, across the Unicode edge
cases the wire formats care about (astral pairs, lone surrogates,
length-changing lowercasing, accent mode), every labeler variant, and
the empty batch; trained-weight trajectories must be bitwise-equal with
the featurizer on vs off (single device, 4-way mesh, tenant stack). The
arena lease riding the batch retires exactly once — on fetch delivery
through the dispatch pipelines (chained with the wire lease), or via
the GC ``discard`` backstop for batches that never dispatch. The
stale-library degrade seam mirrors r6/r15/r17's: a real .so without
``featurize_wire`` loads, flags once, and featurize keeps flowing
through Python.
"""

from __future__ import annotations

import gc
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from twtml_tpu.features import arena as arena_mod  # noqa: E402
from twtml_tpu.features import featurize_native as ffz  # noqa: E402
from twtml_tpu.features import native  # noqa: E402
from twtml_tpu.features.batch import pack_batch  # noqa: E402
from twtml_tpu.features.blocks import ParsedBlock  # noqa: E402
from twtml_tpu.features.featurizer import Featurizer, Status  # noqa: E402
from twtml_tpu.streaming.sources import SyntheticSource  # noqa: E402

needs_native = pytest.mark.skipif(
    not native.featurize_available(),
    reason="native featurize emitter unavailable (no g++?)",
)

NOW = 1785320000000


# ---------------------------------------------------------------------------
# builders


def synthetic(n=256):
    return list(SyntheticSource(total=n, seed=3, base_ms=NOW).produce())


def rt(text, count=500, **extra) -> Status:
    fields = dict(
        followers_count=1234, favourites_count=77, friends_count=450,
        created_at_ms=NOW - 86_400_000,
    )
    fields.update(extra)
    return Status(
        text="RT", retweet_count=1,
        retweeted_status=Status(
            text=text, retweet_count=count, **fields
        ),
    )


def unicode_corpus() -> list[Status]:
    """Every Unicode shape the wire formats special-case, plus filter
    variety (non-retweets, out-of-interval counts)."""
    return [
        rt("plain ascii tweet with CAPS and a link https://t.co/x"),
        rt("astral emoji \U0001f98a pair rides two UTF-16 units"),
        rt("lone surrogate \ud83e stays a unit like the JVM"),
        rt("İstanbul lowercases to MORE units (i + combining dot)"),
        rt("café naïve résumé — accents"),
        rt(""),  # empty original text
        rt("boundary low", count=100),
        rt("boundary high", count=1000),
        rt("dropped: below interval", count=99),
        rt("dropped: above interval", count=1001),
        Status(text="not a retweet at all"),
        rt("big numbers", followers_count=2**40,
           favourites_count=10**15, created_at_ms=0),
    ]


def assert_same_batch(ref, got, tag=""):
    for f in ("units", "offsets", "numeric", "label", "mask"):
        a, b = getattr(ref, f), getattr(got, f)
        assert a.dtype == b.dtype, (tag, f, a.dtype, b.dtype)
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{tag}.{f}"
        )
    assert ref.row_len == got.row_len, (tag, ref.row_len, got.row_len)


def both_modes(fn):
    with ffz.forced("off"):
        ref = fn()
    with ffz.forced("on"):
        got = fn()
    return ref, got


def block_from(statuses) -> ParsedBlock:
    """Parse the statuses' JSONL through the native wire parser."""
    import json


    data = (
        "\n".join(json.dumps(s.to_json()) for s in statuses) + "\n"
    ).encode("utf-8")
    parsed = native.parse_tweet_block_wire(data, 0, 10**9)
    assert parsed is not None
    return ParsedBlock(*parsed[:4])


# ---------------------------------------------------------------------------
# object-path bit parity


@needs_native
@pytest.mark.parametrize("row_bucket", [0, 64])
@pytest.mark.parametrize("pre_filtered", [False, True])
def test_object_parity_synthetic(row_bucket, pre_filtered):
    feat = Featurizer(now_ms=NOW)
    sts = synthetic(200)
    ref, got = both_modes(
        lambda: feat.featurize_batch_ragged(
            sts, row_bucket=row_bucket, pre_filtered=pre_filtered
        )
    )
    assert_same_batch(ref, got, "synthetic")
    assert got.num_valid == 200


@needs_native
def test_object_parity_unicode_edges():
    feat = Featurizer(now_ms=NOW)
    ref, got = both_modes(
        lambda: feat.featurize_batch_ragged(unicode_corpus(), row_bucket=16)
    )
    assert_same_batch(ref, got, "unicode")
    # the corpus mixes ASCII and non-ASCII rows: the wide wire must ship
    assert ref.units.dtype == np.uint16


@needs_native
def test_object_parity_all_ascii_narrow_wire():
    feat = Featurizer(now_ms=NOW)
    sts = [rt("pure ascii %d" % i) for i in range(10)]
    ref, got = both_modes(
        lambda: feat.featurize_batch_ragged(sts, row_bucket=16)
    )
    assert_same_batch(ref, got, "ascii")
    assert ref.units.dtype == np.uint8  # the narrow wire, both modes


@needs_native
def test_object_parity_empty_batch():
    feat = Featurizer(now_ms=NOW)
    ref, got = both_modes(
        lambda: feat.featurize_batch_ragged([], row_bucket=32)
    )
    assert_same_batch(ref, got, "empty")
    assert got.num_valid == 0


@needs_native
def test_object_parity_accent_mode():
    feat = Featurizer(now_ms=NOW, normalize_accents=True)
    ref, got = both_modes(
        lambda: feat.featurize_batch_ragged(unicode_corpus(), row_bucket=16)
    )
    assert_same_batch(ref, got, "accents")


@needs_native
def test_object_parity_label_fn_variants():
    corpus = synthetic(64) + unicode_corpus()
    # per-status label_fn
    f1 = Featurizer(
        now_ms=NOW,
        label_fn=lambda s: s.retweeted_status.followers_count * 0.25,
    )
    ref, got = both_modes(
        lambda: f1.featurize_batch_ragged(corpus, row_bucket=128)
    )
    assert_same_batch(ref, got, "label_fn")
    # batched labeler (encoded= contract included)
    from twtml_tpu.features.sentiment import sentiment_label, sentiment_labels

    f2 = Featurizer(
        now_ms=NOW, label_fn=sentiment_label, batch_label_fn=sentiment_labels
    )
    ref, got = both_modes(
        lambda: f2.featurize_batch_ragged(corpus, row_bucket=128)
    )
    assert_same_batch(ref, got, "batch_label_fn")
    assert np.asarray(ref.label)[: ref.num_valid].any()  # labels are live


@needs_native
def test_object_parity_subclassed_filtrate():
    class OddFilter(Featurizer):
        def filtrate(self, s):
            return s.is_retweet and (
                s.retweeted_status.retweet_count % 2 == 0
            )

    feat = OddFilter(now_ms=NOW)
    sts = [rt("tweet %d" % i, count=100 + i) for i in range(30)]
    ref, got = both_modes(
        lambda: feat.featurize_batch_ragged(sts, row_bucket=32)
    )
    assert_same_batch(ref, got, "subclass")
    assert got.num_valid == 15  # the subclass filter actually applied


# ---------------------------------------------------------------------------
# block-path bit parity


@needs_native
def test_block_parity_ascii_common_case():
    feat = Featurizer(now_ms=NOW)
    block = block_from([rt("block ascii row %d" % i) for i in range(40)])
    assert block.units.dtype == np.uint8
    ref, got = both_modes(
        lambda: feat.featurize_parsed_block(block, row_bucket=64, ragged=True)
    )
    assert_same_batch(ref, got, "block-ascii")
    assert got.units.dtype == np.uint8


@needs_native
def test_block_parity_uint16_legacy_parser_units():
    """A legacy (ParsedBlock-parser) block carries uint16 units even when
    every row is ASCII — the fused path must downcast identically."""
    feat = Featurizer(now_ms=NOW)
    blk = block_from([rt("legacy width row %d" % i) for i in range(12)])
    wide = ParsedBlock(
        blk.numeric, blk.units.astype(np.uint16), blk.offsets, blk.ascii
    )
    ref, got = both_modes(
        lambda: feat.featurize_parsed_block(wide, row_bucket=16, ragged=True)
    )
    assert_same_batch(ref, got, "block-u16")
    assert got.units.dtype == np.uint8  # ascii-flagged → narrow wire


@needs_native
def test_block_nonascii_and_accent_rows_fall_back_identically():
    feat = Featurizer(now_ms=NOW)
    block = block_from(
        [rt("ascii row"), rt("unicode İ row \U0001f98a")] * 4
    )
    ref, got = both_modes(
        lambda: feat.featurize_parsed_block(block, row_bucket=16, ragged=True)
    )
    assert_same_batch(ref, got, "block-nonascii")
    feat2 = Featurizer(now_ms=NOW, normalize_accents=True)
    ref, got = both_modes(
        lambda: feat2.featurize_parsed_block(
            block, row_bucket=16, ragged=True
        )
    )
    assert_same_batch(ref, got, "block-accents")


@needs_native
def test_block_parity_unit_label_fn():
    from twtml_tpu.features.sentiment import sentiment_labels_from_units

    feat = Featurizer(now_ms=NOW, unit_label_fn=sentiment_labels_from_units)
    block = block_from(
        [rt("good happy great row"), rt("bad awful terrible row")] * 5
    )
    ref, got = both_modes(
        lambda: feat.featurize_parsed_block(block, row_bucket=16, ragged=True)
    )
    assert_same_batch(ref, got, "block-unit-labels")
    lab = np.asarray(got.label)[: got.num_valid]
    assert lab.any()  # the lexicon labels applied (not the count column)


@needs_native
def test_block_parity_empty_block():
    from twtml_tpu.features.blocks import empty_block

    feat = Featurizer(now_ms=NOW)
    ref, got = both_modes(
        lambda: feat.featurize_parsed_block(
            empty_block(), row_bucket=8, ragged=True
        )
    )
    assert_same_batch(ref, got, "block-empty")


@needs_native
def test_block_packed_wire_byte_parity():
    """featurize → pack: the packed wire (the bytes that get uploaded) is
    byte-identical with the fused featurize on vs off."""
    feat = Featurizer(now_ms=NOW)
    block = block_from([rt("packed row %d" % i) for i in range(32)])
    ref, got = both_modes(
        lambda: feat.featurize_parsed_block(
            block, row_bucket=32, ragged=True, pack=True
        )
    )
    assert ref.layout == got.layout
    np.testing.assert_array_equal(
        np.asarray(ref.buffer), np.asarray(got.buffer)
    )


@needs_native
@pytest.mark.parametrize("codec", [None, "dict"])
@pytest.mark.parametrize("form", ["flat", "sharded", "group"])
def test_packed_wire_parity_every_form(form, codec):
    """featurize on vs off → every packed wire form × codec: the bytes
    that get uploaded are identical (flat pack, shard-aligned pack,
    coalesced group pack)."""
    from twtml_tpu.features.batch import (
        align_ragged_shards, pack_ragged_group, pack_ragged_sharded,
    )

    feat = Featurizer(now_ms=NOW)
    sts = synthetic(128)

    def build(mode):
        with ffz.forced(mode):
            batches = [
                feat.featurize_batch_ragged(
                    sts[i : i + 32], row_bucket=32, unit_bucket=256,
                    pre_filtered=True,
                )
                for i in range(0, 128, 32)
            ]
        if form == "flat":
            return pack_batch(batches[0], codec=codec)
        if form == "sharded":
            return pack_ragged_sharded(
                align_ragged_shards(batches[0], 2), codec=codec
            )
        return pack_ragged_group(batches, codec=codec)

    ref, got = build("off"), build("on")
    assert ref.layout == got.layout
    np.testing.assert_array_equal(
        np.asarray(ref.buffer), np.asarray(got.buffer)
    )


# ---------------------------------------------------------------------------
# trajectory parity: trained weights bitwise-equal on vs off


def _featurized(feat, n=6, rows=32, mode="off"):
    sts = synthetic(n * rows)
    with ffz.forced(mode):
        return [
            feat.featurize_batch_ragged(
                sts[i * rows : (i + 1) * rows], row_bucket=rows,
                pre_filtered=True,
            )
            for i in range(n)
        ]


@needs_native
def test_trajectory_bitwise_single_device():
    from twtml_tpu.models import StreamingLinearRegressionWithSGD

    feat = Featurizer(now_ms=NOW)
    finals = {}
    for mode in ("off", "on"):
        m = StreamingLinearRegressionWithSGD(num_iterations=5)
        for b in _featurized(feat, mode=mode):
            m.step(pack_batch(b))
        finals[mode] = np.asarray(m.latest_weights)
    np.testing.assert_array_equal(finals["off"], finals["on"])


@needs_native
def test_trajectory_bitwise_mesh():
    import jax

    from twtml_tpu.parallel import ParallelSGDModel, make_mesh

    feat = Featurizer(now_ms=NOW)
    finals = {}
    for mode in ("off", "on"):
        mesh = make_mesh(num_data=4, devices=jax.devices()[:4])
        m = ParallelSGDModel(mesh, num_iterations=5, step_size=0.05)
        for b in _featurized(feat, n=4, mode=mode):
            m.step(m.pack_for_wire(b))
        finals[mode] = np.asarray(m.latest_weights)
    np.testing.assert_array_equal(finals["off"], finals["on"])


@needs_native
def test_trajectory_bitwise_tenant_stack():
    from twtml_tpu.parallel import TenantStackModel

    feat = Featurizer(now_ms=NOW)
    finals = {}
    for mode in ("off", "on"):
        mt = TenantStackModel(
            3, num_iterations=5, step_size=0.1, wire_pack="group"
        )
        for b in _featurized(feat, n=4, mode=mode):
            mt.step(b)
        finals[mode] = np.asarray(mt.latest_weights)
    np.testing.assert_array_equal(finals["off"], finals["on"])


# ---------------------------------------------------------------------------
# arena lease accounting


@pytest.fixture()
def private_arena(monkeypatch):
    """A fresh arena swapped in for the process-global one: the suite
    runs with --featurizeNative auto (= on), so batches from OTHER
    tests hold leases on the global arena and their GC finalizers fire
    at unpredictable points — absolute accounting assertions need an
    arena only this test's leases touch (old leases keep a reference to
    the arena THEY came from, so strays never land here)."""
    fresh = arena_mod.WireArena()
    monkeypatch.setattr(arena_mod, "_arena", fresh)
    return fresh


@needs_native
def test_featurize_leases_retire_on_pipeline_delivery(private_arena):
    """The featurize lease chains with the wire lease at the dispatch
    site and retires on fetch delivery — arena accounting returns to
    zero outstanding after the pipeline drains."""
    from twtml_tpu.apps.common import FetchPipeline

    class _EchoModel:
        accepts_packed = True

        def step(self, wire):
            return {"mse": np.float32(1.0)}

    feat = Featurizer(now_ms=NOW)
    delivered = []
    pipe = FetchPipeline(
        _EchoModel(), lambda out, b, t, at_boundary: delivered.append(b),
        depth=4,
    )
    with ffz.forced("on"):
        sts = synthetic(5 * 16)
        for i in range(5):
            b = feat.featurize_batch_ragged(
                sts[i * 16 : (i + 1) * 16], row_bucket=16,
                pre_filtered=True,
            )
            assert b._lease is not None
            pipe.on_batch(b, float(i))
        pipe.flush()
    assert len(delivered) == 5
    assert private_arena.stats()["in_use"] == 0


@needs_native
def test_featurize_lease_gc_backstop_discards(private_arena):
    """A featurized batch that never reaches a dispatch site releases
    its lease through the GC finalizer: accounting exact, buffer NOT
    pooled (discard — views extracted from the batch can never alias a
    recycled buffer)."""
    feat = Featurizer(now_ms=NOW)
    with ffz.forced("on"):
        b = feat.featurize_batch_ragged(synthetic(16), row_bucket=16)
    assert b._lease is not None
    assert private_arena.stats()["in_use"] == 1
    del b
    gc.collect()
    stats = private_arena.stats()
    assert stats["in_use"] == 0
    assert stats["free_buffers"] == 0  # discarded, never pooled


@needs_native
def test_featurize_lease_recycles_across_batches(private_arena):
    """Delivery-retired featurize buffers are POOLED: the second batch
    of the same signature reuses the first one's buffer."""
    feat = Featurizer(now_ms=NOW)
    sts = synthetic(32)
    with ffz.forced("on"):
        b1 = feat.featurize_batch_ragged(sts[:16], row_bucket=16,
                                         pre_filtered=True)
        buf1 = b1._lease.buf
        b1._lease.retire()
        b2 = feat.featurize_batch_ragged(sts[16:], row_bucket=16,
                                         pre_filtered=True)
        assert b2._lease.buf is buf1
        b2._lease.retire()


def test_chain_leases_combinator():
    from twtml_tpu.features.arena import LeaseChain, chain_leases

    a = arena_mod.WireArena()
    l1, l2 = a.lease(64), a.lease(128)
    assert chain_leases(None, None) is None
    assert chain_leases(l1, None) is l1
    assert chain_leases(l1, l1) is l1  # identity-deduplicated
    chain = chain_leases(l1, l2)
    assert isinstance(chain, LeaseChain)
    assert chain.buf is l1.buf  # primary buffer exposed
    chain.retire()
    assert a.stats()["in_use"] == 0
    assert a.stats()["free_buffers"] == 2
    # discard path: idempotent with the retire above
    chain.discard()
    assert a.stats()["free_buffers"] == 2


# ---------------------------------------------------------------------------
# zero added fetches: the sub-stage gauges are host clocks only


@needs_native
def test_substage_gauges_add_zero_fetches(monkeypatch):
    import jax

    from twtml_tpu.features.featurizer import Featurizer as F
    from twtml_tpu.streaming.context import FeatureStream
    from twtml_tpu.telemetry import metrics as _metrics

    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    feat = F(now_ms=NOW)
    stream = FeatureStream(feat, row_bucket=16, device_hash=True,
                           ragged=True)
    with ffz.forced("on"):
        stream._featurize(synthetic(16))
    assert calls["n"] == 0  # featurize + gauges never fetch
    reg = _metrics.get_registry()
    snap = reg.snapshot()["gauges"]
    for name in ("featurize.encode_ms", "featurize.wire_build_ms"):
        assert name in snap, snap.keys()


# ---------------------------------------------------------------------------
# mode plumbing + degrade seam


def test_configure_validates():
    with pytest.raises(ValueError):
        ffz.configure("maybe")
    prev = ffz.mode()
    ffz.configure("off")
    assert not ffz.available()
    ffz.configure(prev)


def test_conf_flag_roundtrip():
    from twtml_tpu.config import ConfArguments

    conf = ConfArguments().parse(["--featurizeNative", "off"])
    assert conf.featurizeNative == "off"
    with pytest.raises(SystemExit):
        ConfArguments().parse(["--featurizeNative", "sometimes"])


def test_bind_featurize_flags_missing_symbol_and_counts(monkeypatch):
    from twtml_tpu.telemetry import metrics as _metrics

    class _NoFeaturize:
        def __getattr__(self, name):
            raise AttributeError(name)

    _metrics.reset_for_tests()
    monkeypatch.setattr(native, "_featurize_missing", False)
    with pytest.raises(AttributeError):
        native._bind_featurize(_NoFeaturize(), strict=True)
    native._bind_featurize(_NoFeaturize(), strict=False)
    assert native._featurize_missing
    assert _metrics.get_registry().counter(
        "native.featurize_degraded"
    ).snapshot() == 1
    monkeypatch.setattr(native, "_featurize_missing", False)


def test_featurize_missing_degrades_to_python(monkeypatch):
    monkeypatch.setattr(native, "_featurize_missing", True)
    assert not native.featurize_available()
    assert not ffz.available()
    feat = Featurizer(now_ms=NOW)
    with ffz.forced("on"):  # even explicit on degrades, never dies
        got = feat.featurize_batch_ragged(synthetic(16), row_bucket=16)
    monkeypatch.setattr(native, "_featurize_missing", False)
    with ffz.forced("off"):
        ref = feat.featurize_batch_ragged(synthetic(16), row_bucket=16)
    assert_same_batch(ref, got, "degraded")
    assert getattr(got, "_lease", None) is None  # python path: no lease


def test_stale_library_without_featurize_symbol_loads_degraded(tmp_path):
    """End-to-end seam: a REAL .so carrying every pre-r18 symbol but not
    ``featurize_wire`` loads with strict=False, flags the degrade, and
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
int64_t wire_assemble(const void* const*, const int32_t* const*,
                      const float* const*, const float* const*,
                      const float* const*, int64_t, int64_t, int64_t,
                      int64_t, int64_t, int64_t, const uint8_t*, int64_t,
                      uint8_t*, int64_t*, uint8_t*, int64_t,
                      int64_t* e) { *e = 0; return 0; }
}
""",
        encoding="utf-8",
    )
    so = tmp_path / "stale.so"
    subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-o", str(so), str(src)],
        check=True, capture_output=True,
    )
    saved = native._featurize_missing
    try:
        with pytest.raises(AttributeError):
            native._load(str(so), strict=True)
        lib = native._load(str(so), strict=False)
        assert native._featurize_missing
        assert lib.wire_assemble is not None  # old symbols still bound
    finally:
        native._featurize_missing = saved
        # every degrade flag, not just ours (see test_blockwire's seam
        # test: a partial restore leaves sibling fast paths off)
        native.rebind_flags()


@needs_native
def test_fused_counter_increments():
    from twtml_tpu.telemetry import metrics as _metrics

    reg = _metrics.get_registry()
    before = reg.counter("featurize.fused_native").snapshot()
    feat = Featurizer(now_ms=NOW)
    with ffz.forced("on"):
        b = feat.featurize_batch_ragged(synthetic(16), row_bucket=16)
    assert reg.counter("featurize.fused_native").snapshot() == before + 1
    b._lease.retire()
