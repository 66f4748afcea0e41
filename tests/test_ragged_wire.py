"""Ragged units wire (features/batch.RaggedUnitBatch): the concatenated
units + offsets wire must produce BIT-IDENTICAL training to the padded
UnitBatch wire — the device-side re-pad + ASCII fold replaces the
host-side pad copy exactly. Parity law: features/hashing.py / the padded
Status path is ground truth; every fast path carries differential tests."""

import numpy as np
import pytest

from twtml_tpu.features.batch import RAGGED_UNIT_MULTIPLE, RaggedUnitBatch
from twtml_tpu.features.featurizer import Featurizer, Status
from twtml_tpu.models import (
    StreamingLinearRegressionWithSGD,
    StreamingLogisticRegressionWithSGD,
)
from twtml_tpu.streaming.sources import SyntheticSource


def rt(text, label=500):
    return Status(
        text="RT",
        retweeted_status=Status(text=text, retweet_count=label,
                                followers_count=1234),
    )


def synthetic(n=96, seed=13):
    return list(
        SyntheticSource(total=n, seed=seed, base_ms=1785320000000).produce()
    )


def assert_identical_training(statuses, model_cls=StreamingLinearRegressionWithSGD,
                              rows=32, feat_kw=None, model_kw=None):
    feat = Featurizer(now_ms=1785320000000, **(feat_kw or {}))
    chunks = [statuses[i : i + rows] for i in range(0, len(statuses), rows)]

    padded_model = model_cls(num_iterations=5, **(model_kw or {}))
    ragged_model = model_cls(num_iterations=5, **(model_kw or {}))
    for chunk in chunks:
        pb = feat.featurize_batch_units(chunk, row_bucket=rows, unit_bucket=64)
        rb = feat.featurize_batch_ragged(chunk, row_bucket=rows, unit_bucket=64)
        out_p = padded_model.step(pb)
        out_r = ragged_model.step(rb)
        for field_p, field_r in zip(out_p, out_r):
            np.testing.assert_array_equal(
                np.asarray(field_p), np.asarray(field_r)
            )
    np.testing.assert_array_equal(
        padded_model.latest_weights, ragged_model.latest_weights
    )


def test_ragged_matches_padded_synthetic_stream():
    assert_identical_training(synthetic())


def test_ragged_matches_padded_logistic():
    assert_identical_training(
        synthetic(), model_cls=StreamingLogisticRegressionWithSGD
    )


def test_ragged_matches_padded_unicode_and_edge_rows():
    statuses = [
        rt("MiXeD CaSe ASCII tweet!"),
        rt("ünïcode ÉMOJI \U0001f600 tweet"),  # astral char: 2 units
        rt("x"),  # single-unit row: the sliding(2) special case
        rt("ÀÈÌ UPPER with accents"),
        rt("plain lower ascii"),
    ] * 7
    assert_identical_training(statuses, rows=8)
    assert_identical_training(
        statuses, rows=8, feat_kw={"normalize_accents": True}
    )


def test_ragged_wire_shape_and_narrowing():
    feat = Featurizer(now_ms=0)
    rb = feat.featurize_batch_ragged(
        [rt("hello world")] * 10, row_bucket=16, unit_bucket=32
    )
    assert isinstance(rb, RaggedUnitBatch)
    assert rb.units.dtype == np.uint8  # all-ASCII narrow wire
    assert rb.units.shape == (RAGGED_UNIT_MULTIPLE,)
    assert rb.offsets.shape == (17,)
    assert rb.row_len == 32
    assert rb.num_valid == 10
    # non-ASCII rows keep the full uint16 schema
    rb16 = feat.featurize_batch_ragged([rt("héllo")] * 4, row_bucket=8)
    assert rb16.units.dtype == np.uint16


def test_ragged_empty_batch():
    feat = Featurizer(now_ms=0)
    rb = feat.featurize_batch_ragged([], row_bucket=8, unit_bucket=16)
    model = StreamingLinearRegressionWithSGD(num_iterations=5)
    out = model.step(rb)
    assert float(out.count) == 0.0
    np.testing.assert_array_equal(
        model.latest_weights, np.zeros_like(model.latest_weights)
    )


def test_ragged_2e18_gram_config():
    """The ragged wire through the 2^18 Gram-domain config (BASELINE #4) —
    the config whose throughput the wire work targets."""
    statuses = synthetic(n=64)
    assert_identical_training(
        statuses, rows=32,
        feat_kw={"num_text_features": 2**18},
        model_kw={"num_text_features": 2**18, "l2_reg": 0.1},
    )


@pytest.mark.parametrize("total", [3, 40])
def test_ragged_unit_bucket_growth(total):
    """Unpinned unit bucket: the rebuilt row length grows per batch like the
    padded wire's (same _bucket policy), so mixed streams stay consistent."""
    feat = Featurizer(now_ms=0)
    text = "a" * total
    rb = feat.featurize_batch_ragged([rt(text)], row_bucket=4)
    pb = feat.featurize_batch_units([rt(text)], row_bucket=4)
    assert rb.row_len == pb.units.shape[1]


def test_linear_app_ragged_identical_stats(tmp_path, capsys):
    """--wire ragged through the REAL flagship app prints the identical
    per-batch stats lines and totals as --wire padded."""
    import json

    import jax

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.config import ConfArguments

    jax.devices()  # lock the conftest backend before local[1]

    path = tmp_path / "tweets.jsonl"
    with open(path, "w") as fh:
        for s in synthetic(n=5 * 16, seed=21):
            fh.write(json.dumps(s.to_json()) + "\n")

    def run(wire):
        conf = ConfArguments().parse([
            "--source", "replay", "--replayFile", str(path),
            "--seconds", "0", "--backend", "cpu",
            "--batchBucket", "16", "--tokenBucket", "64",
            "--master", "local[1]", "--wire", wire,
        ])
        capsys.readouterr()
        totals = app.run(conf)
        lines = [
            ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("count:")
        ]
        return totals, lines

    totals_p, lines_p = run("padded")
    totals_r, lines_r = run("ragged")
    # stream_seconds is wall-clock (r4, for the suite's startup split)
    totals_p.pop("stream_seconds", None); totals_r.pop("stream_seconds", None)
    assert totals_r == totals_p
    assert lines_r == lines_p
    assert len(lines_p) >= 5


def test_ragged_flag_gates():
    """The loud incompatibility gate that remains (host hashing), and the
    r4 capability the r3 mesh gate gave way to: build_model accepts the
    ragged wire on a mesh (shard-aligned segments,
    tests/test_ragged_sharded.py)."""
    from twtml_tpu.apps.common import build_model, build_source
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.parallel import ParallelSGDModel

    import jax

    jax.devices()

    base = ["--wire", "ragged", "--source", "synthetic"]
    model, row_multiple = build_model(ConfArguments().parse(base))
    assert isinstance(model, ParallelSGDModel)  # 8-device mesh, no gate
    assert row_multiple == 8
    with pytest.raises(SystemExit):
        build_source(ConfArguments().parse(base + ["--hashOn", "host"]))


def test_ragged_block_ingest_matches_padded(tmp_path):
    """The ragged wire from COLUMNAR BLOCKS (the native data loader's
    format — no pad copy at all: the block already holds concatenated
    units + offsets) trains bit-identically to the padded block path."""
    import json

    from twtml_tpu.features.blocks import iter_row_chunks
    from twtml_tpu.streaming.sources import BlockReplayFileSource

    path = tmp_path / "tweets.jsonl"
    statuses = synthetic(n=96, seed=31)
    # a couple of non-ASCII rows exercise the redo/uint16 path
    statuses[3] = rt("ünïcode BLOCK tweet É")
    statuses[40] = rt("MiXeD Ascii ROW")
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(s.to_json()) + "\n")

    feat = Featurizer(now_ms=1785320000000)
    blocks = list(BlockReplayFileSource(str(path)).produce())
    chunks = list(iter_row_chunks(blocks, 32))

    padded_model = StreamingLinearRegressionWithSGD(num_iterations=5)
    ragged_model = StreamingLinearRegressionWithSGD(num_iterations=5)
    for chunk in chunks:
        pb = feat.featurize_parsed_block(chunk, row_bucket=32, unit_bucket=64)
        rb = feat.featurize_parsed_block(
            chunk, row_bucket=32, unit_bucket=64, ragged=True
        )
        assert isinstance(rb, RaggedUnitBatch)
        out_p = padded_model.step(pb)
        out_r = ragged_model.step(rb)
        for field_p, field_r in zip(out_p, out_r):
            np.testing.assert_array_equal(
                np.asarray(field_p), np.asarray(field_r)
            )
    np.testing.assert_array_equal(
        padded_model.latest_weights, ragged_model.latest_weights
    )


def test_linear_app_block_ragged_identical_stats(tmp_path, capsys):
    """--ingest block --wire ragged through the real app: identical stats
    to the padded block run."""
    import json

    import jax

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.config import ConfArguments

    jax.devices()

    path = tmp_path / "tweets.jsonl"
    with open(path, "w") as fh:
        for s in synthetic(n=5 * 16, seed=23):
            fh.write(json.dumps(s.to_json()) + "\n")

    def run(wire):
        conf = ConfArguments().parse([
            "--source", "replay", "--replayFile", str(path),
            "--seconds", "0", "--backend", "cpu", "--ingest", "block",
            "--batchBucket", "16", "--tokenBucket", "64",
            "--master", "local[1]", "--wire", wire,
        ])
        capsys.readouterr()
        totals = app.run(conf)
        return totals, [
            ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("count:")
        ]

    totals_p, lines_p = run("padded")
    totals_r, lines_r = run("ragged")
    # stream_seconds is wall-clock (r4, for the suite's startup split)
    totals_p.pop("stream_seconds", None); totals_r.pop("stream_seconds", None)
    assert totals_r == totals_p
    assert lines_r == lines_p
    # the small file arrives as ONE parsed block (a block item overshoots
    # the row cap by design), so one batch carries all rows
    assert len(lines_p) >= 1 and totals_p["count"] == 80


def test_ragged_matches_padded_logistic_sentiment_labels():
    """Config #3's exact shape: the logistic learner with C-lexicon
    sentiment labels (batch_label_fn reusing the featurizer's encode pass)
    through the ragged wire — bit-identical to the padded wire."""
    from twtml_tpu.features.sentiment import sentiment_label, sentiment_labels

    assert_identical_training(
        synthetic(n=96, seed=41),
        model_cls=StreamingLogisticRegressionWithSGD,
        feat_kw={
            "label_fn": sentiment_label,
            "batch_label_fn": sentiment_labels,
        },
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ragged_fuzz_random_unicode(seed):
    """Seeded fuzz: random texts across codepoint planes — ASCII, Latin-1,
    CJK, astral (surrogate pairs), EMPTY strings, single chars, and long
    rows — must train bit-identically through both wires."""
    rng = np.random.default_rng(seed)
    pools = [
        lambda: chr(rng.integers(32, 127)),          # ASCII
        lambda: chr(rng.integers(0xC0, 0x17F)),      # Latin accents
        lambda: chr(rng.integers(0x4E00, 0x4F00)),   # CJK
        lambda: chr(rng.integers(0x1F300, 0x1F3FF)),  # astral emoji
    ]
    statuses = []
    for _ in range(64):
        kind = rng.integers(0, 8)
        if kind == 0:
            text = ""  # empty text row
        elif kind == 1:
            text = pools[rng.integers(0, 4)]()  # single char
        else:
            n_chars = int(rng.integers(2, 60))
            text = "".join(
                pools[rng.integers(0, 4)]() for _ in range(n_chars)
            )
        statuses.append(rt(text, label=int(rng.integers(100, 1001))))
    assert_identical_training(statuses, rows=16)


def test_ragged_stack_rejects_mixed_alignment():
    """The stacked tenant wire (``stack_batches``) refuses ragged parts
    whose shard alignment differs — a stacked batch cannot be re-aligned."""
    from twtml_tpu.features.batch import align_ragged_shards, stack_batches

    statuses = synthetic(64)
    feat = Featurizer(now_ms=1785320000000)
    a, b = (
        feat.featurize_batch_ragged(
            statuses[i : i + 32], row_bucket=32, pre_filtered=True
        )
        for i in (0, 32)
    )
    with pytest.raises(ValueError, match="different row_len or shard"):
        stack_batches([a, align_ragged_shards(b, 2)])


# ---------------------------------------------------------------------------
# PR 33: the device re-pad moves whole 128-lane rows of the units buffer and
# shifts (ops/ragged.py). Held to a plain NumPy re-pad, not to another jax
# program.


def _numpy_repad(units, starts, lens, row_len):
    out = np.zeros((len(starts), row_len), np.int32)
    for b, (s, n) in enumerate(zip(starts, lens)):
        row = units[s:s + n].astype(np.int32)
        out[b, :n] = np.where((row >= 65) & (row <= 90), row + 32, row)
    return out


def _segment_lengths(rng, row_len):
    """One segment's row lengths: 128 rows of one unit (consecutive starts:
    every residue mod 128), an empty row, a full row, random rows — and a
    total that is NOT a multiple of 128."""
    lens = [1] * 128 + [0, row_len, 0, row_len]
    lens += list(rng.integers(0, row_len + 1, 24))
    lens.append(row_len)  # the row that ends the sub-buffer
    if sum(lens) % 128 == 0:
        lens[-2] += 1 if lens[-2] < row_len else -1
    return np.array(lens, np.int64)


@pytest.mark.parametrize("segments, deltas", [
    (1, False), (2, False), (4, False), (1, True),
], ids=["plain", "aligned2", "aligned4", "deltas"])
@pytest.mark.parametrize("row_len", [16, 256, 512])
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
def test_device_repad_matches_numpy(dtype, row_len, segments, deltas):
    """``ragged_repad`` against NumPy on every shape of the wire: uint8 and
    uint16 units; the plain ``[B + 1]`` offsets, the shard-aligned layout of
    2 and 4 segments and the narrow ``deltas`` wire (one segment: on the
    aligned layout its caller decodes it, ``offsets_from_deltas``); starts at every residue
    mod 128; rows of length 0 and L; junk (never zeros) after every row; a
    last row that ends exactly at N with N no multiple of 128 (the static
    pad) and, second, the same rows in a sub-buffer rounded up to the
    wire's 4096 (the exact view)."""
    import jax

    from twtml_tpu.ops.ragged import ragged_repad

    rng = np.random.default_rng(row_len + segments)
    base = _segment_lengths(rng, row_len)
    # every segment holds the same multiset of lengths in another order, so
    # the sub-buffers are equally long, as align_ragged_shards makes them
    seg_lens = [np.roll(base, 7 * s) for s in range(segments)]
    total = int(base.sum())
    assert total % 128
    repad = jax.jit(ragged_repad, static_argnums=(2, 3, 4))
    for sub in (total, -(-total // RAGGED_UNIT_MULTIPLE) * RAGGED_UNIT_MULTIPLE):
        units = rng.integers(
            1, np.iinfo(dtype).max, sub * segments).astype(dtype)
        units[::5] = rng.integers(60, 95, len(units[::5]))  # around A-Z
        rel = [np.concatenate([[0], np.cumsum(ln)]) for ln in seg_lens]
        starts = np.concatenate(
            [r[:-1] + s * sub for s, r in enumerate(rel)])
        lens = np.concatenate(seg_lens)
        rows = len(lens)
        wire = (lens.astype(np.uint16) if deltas
                else np.concatenate(rel).astype(np.int32))
        buf, got_lens = repad(units, wire, row_len, rows, deltas)
        assert buf.dtype == np.int32 and buf.shape == (rows, row_len)
        np.testing.assert_array_equal(np.asarray(got_lens), lens)
        np.testing.assert_array_equal(
            np.asarray(buf), _numpy_repad(units, starts, lens, row_len))
