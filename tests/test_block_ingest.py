"""Native block ingest (native/tweetjson.cpp + features/blocks.py) parity.

The C data-loader must produce byte-identical batches to the Python
ground-truth path (json.loads → Status → filtrate → featurize): same kept
rows, same UTF-16 units (escapes, emoji, surrogates), same numerics and
timestamps. Every test compares against the object path end to end.
"""

import json
import os

import numpy as np
import pytest

from twtml_tpu.features import Featurizer, Status
from twtml_tpu.features.blocks import merge_blocks
from twtml_tpu.streaming.sources import BlockReplayFileSource

DATA = os.path.join(os.path.dirname(__file__), "data", "tweets.jsonl")


def _object_path_batch(path, feat, **kw):
    with open(path, encoding="utf-8") as fh:
        statuses = [Status.from_json(json.loads(l)) for l in fh if l.strip()]
    return feat.featurize_batch_units(statuses, **kw)


def _block_path_batch(path, feat, block_bytes=1 << 20, **kw):
    src = BlockReplayFileSource(path, block_bytes=block_bytes)
    blocks = list(src.produce())
    assert blocks, "no blocks produced"
    return feat.featurize_parsed_block(merge_blocks(blocks), **kw)


def _assert_batches_equal(a, b):
    assert type(a) is type(b)
    np.testing.assert_array_equal(a.units, b.units)
    np.testing.assert_array_equal(a.length, b.length)
    np.testing.assert_allclose(a.numeric, b.numeric, rtol=1e-6)
    np.testing.assert_array_equal(a.label, b.label)
    np.testing.assert_array_equal(a.mask, b.mask)


@pytest.fixture()
def feat():
    return Featurizer(now_ms=1785320000000)


def test_fixture_file_parity(feat):
    obj = _object_path_batch(DATA, feat, row_bucket=16, unit_bucket=128)
    blk = _block_path_batch(DATA, feat, row_bucket=16, unit_bucket=128)
    _assert_batches_equal(obj, blk)


def test_fixture_file_parity_python_fallback(feat, monkeypatch):
    from twtml_tpu.features import native

    monkeypatch.setattr(native, "parse_tweet_block", lambda *a, **k: None)
    obj = _object_path_batch(DATA, feat, row_bucket=16, unit_bucket=128)
    blk = _block_path_batch(DATA, feat, row_bucket=16, unit_bucket=128)
    _assert_batches_equal(obj, blk)


def test_tiny_blocks_carry_across_chunk_boundaries(feat):
    """block_bytes far smaller than a line forces the consumed/carry logic."""
    obj = _object_path_batch(DATA, feat, row_bucket=16, unit_bucket=128)
    blk = _block_path_batch(
        DATA, feat, block_bytes=64, row_bucket=16, unit_bucket=128
    )
    _assert_batches_equal(obj, blk)


ADVERSARIAL = [
    # escapes incl. \uXXXX and an escaped surrogate pair (emoji)
    {"text": "RT", "retweeted_status": {
        "text": "line\\none \"q\" tab\\t \\u00e9 \\ud83d\\ude00 end",
        "retweet_count": 150,
        "user": {"followers_count": 1, "favourites_count": 2, "friends_count": 3},
        "timestamp_ms": "1785310000000"}},
    # raw UTF-8 emoji + CJK, extra nested structures to skip
    {"text": "RT", "extended_entities": {"media": [{"sizes": {"h": 1}}]},
     "retweeted_status": {
        "text": "火 🔥 test",
        "retweet_count": 999,
        "entities": {"urls": [{"indices": [0, 1]}], "hashtags": []},
        "user": {"followers_count": 7, "favourites_count": 0,
                 "friends_count": 9, "description": "nested \"quotes\" {\\n}"},
        "created_at": "Wed Aug 27 13:08:45 +0000 2008"}},
    # boundary values: counts exactly at the [100, 1000] edges
    {"text": "RT", "retweeted_status": {"text": "low edge", "retweet_count": 100,
        "user": {"followers_count": 0, "favourites_count": 0, "friends_count": 0},
        "timestamp_ms": "1785300000000"}},
    {"text": "RT", "retweeted_status": {"text": "high edge", "retweet_count": 1000,
        "user": {"followers_count": 0, "favourites_count": 0, "friends_count": 0},
        "timestamp_ms": "1785300000000"}},
    # filtered out: not a retweet / out of range / null retweeted_status
    {"text": "plain tweet", "retweet_count": 500},
    {"text": "RT", "retweeted_status": {"text": "too hot", "retweet_count": 99999,
        "user": {}}},
    {"text": "RT", "retweeted_status": None},
    # numbers as floats, negative, booleans and nulls in skipped fields
    {"text": "RT", "truncated": False, "coordinates": None,
     "retweeted_status": {"text": "float counts", "retweet_count": 250.0,
        "user": {"followers_count": 123.9, "favourites_count": -1,
                 "friends_count": 0}, "timestamp_ms": 1785311111111}},
    # empty text
    {"text": "RT", "retweeted_status": {"text": "", "retweet_count": 500,
        "user": {"followers_count": 5, "favourites_count": 5, "friends_count": 5},
        "timestamp_ms": "1785312222222"}},
]


def test_adversarial_json_parity(feat, tmp_path):
    path = tmp_path / "adversarial.jsonl"
    path.write_text(
        "\n".join(json.dumps(o) for o in ADVERSARIAL) + "\n", encoding="utf-8"
    )
    obj = _object_path_batch(str(path), feat, row_bucket=8, unit_bucket=64)
    blk = _block_path_batch(str(path), feat, row_bucket=8, unit_bucket=64)
    assert obj.num_valid == 6  # 4 escape/utf8/boundary + float counts + empty
    _assert_batches_equal(obj, blk)


def test_created_at_string_matches_python(feat, tmp_path):
    """The C fixed-format date parse must agree with Python's strptime."""
    path = tmp_path / "dates.jsonl"
    obj = {"text": "RT", "retweeted_status": {
        "text": "dated", "retweet_count": 300,
        "user": {"followers_count": 1, "favourites_count": 1, "friends_count": 1},
        "created_at": "Mon Feb 29 23:59:59 +0130 2016"}}
    path.write_text(json.dumps(obj) + "\n", encoding="utf-8")
    o = _object_path_batch(str(path), feat, row_bucket=8)
    b = _block_path_batch(str(path), feat, row_bucket=8)
    _assert_batches_equal(o, b)
    assert o.numeric[0, 3] != 0  # age feature actually derived from the date


def test_malformed_lines_skipped(feat, tmp_path):
    path = tmp_path / "bad.jsonl"
    good = {"text": "RT", "retweeted_status": {"text": "ok", "retweet_count": 500,
            "user": {"followers_count": 1, "favourites_count": 1,
                     "friends_count": 1}, "timestamp_ms": "1785313333333"}}
    path.write_text(
        json.dumps(good) + "\n" + "{not json}\n" + json.dumps(good) + "\n",
        encoding="utf-8",
    )
    blk = _block_path_batch(str(path), feat, row_bucket=8)
    assert blk.num_valid == 2


def test_linear_app_block_ingest_matches_object(tmp_path, capsys):
    """End to end through the CLI run(): --ingest block == --ingest object."""
    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.config import ConfArguments

    outputs = {}
    for ingest in ("object", "block"):
        conf = ConfArguments().parse([
            "--source", "replay", "--replayFile", DATA, "--ingest", ingest,
            "--lightning", "http://127.0.0.1:9", "--twtweb", "http://127.0.0.1:9",
            "--backend", "cpu",
        ])
        app.run(conf, max_batches=1)
        outputs[ingest] = [
            l for l in capsys.readouterr().out.splitlines()
            if l.startswith("count:")
        ]
    assert outputs["block"] == outputs["object"]
    assert outputs["block"], "no stats lines captured"


def test_full_text_extended_tweets_parity(feat, tmp_path):
    """Extended-tweet archives store the body in full_text (no text key)."""
    path = tmp_path / "extended.jsonl"
    objs = [
        {"text": "RT", "retweeted_status": {
            "full_text": "the entire extended tweet body, uncut",
            "retweet_count": 400,
            "user": {"followers_count": 2, "favourites_count": 2,
                     "friends_count": 2}, "timestamp_ms": "1785314444444"}},
        # empty text falls through to full_text, like Status.from_json
        {"text": "RT", "retweeted_status": {
            "text": "", "full_text": "fallback body", "retweet_count": 500,
            "user": {"followers_count": 1, "favourites_count": 1,
                     "friends_count": 1}, "timestamp_ms": "1785315555555"}},
        # text wins over full_text when non-empty
        {"text": "RT", "retweeted_status": {
            "text": "short form", "full_text": "long form", "retweet_count": 600,
            "user": {"followers_count": 1, "favourites_count": 1,
                     "friends_count": 1}, "timestamp_ms": "1785316666666"}},
    ]
    path.write_text("\n".join(json.dumps(o) for o in objs) + "\n", "utf-8")
    obj = _object_path_batch(str(path), feat, row_bucket=8, unit_bucket=64)
    blk = _block_path_batch(str(path), feat, row_bucket=8, unit_bucket=64)
    assert obj.num_valid == 3
    _assert_batches_equal(obj, blk)


def test_missing_retweet_count_with_zero_begin(tmp_path):
    """Absent retweet_count coerces to 0 in BOTH paths (Status.from_json
    semantics), so numRetweetBegin=0 keeps the row in both modes."""
    feat0 = Featurizer(now_ms=1785320000000, num_retweet_begin=0)
    path = tmp_path / "nocount.jsonl"
    obj = {"text": "RT", "retweeted_status": {
        "text": "countless", "user": {"followers_count": 1,
        "favourites_count": 1, "friends_count": 1},
        "timestamp_ms": "1785317777777"}}
    path.write_text(json.dumps(obj) + "\n", "utf-8")
    o = _object_path_batch(str(path), feat0, row_bucket=8)
    src = BlockReplayFileSource(str(path), num_retweet_begin=0)
    blocks = list(src.produce())
    b = feat0.featurize_parsed_block(merge_blocks(blocks), row_bucket=8)
    assert o.num_valid == 1
    _assert_batches_equal(o, b)


def test_py_fallback_skips_non_object_json(feat, tmp_path, monkeypatch):
    """Valid JSON that isn't a tweet object must skip, not crash, in the
    Python fallback — matching the C parser's bad-line contract."""
    from twtml_tpu.features import native

    monkeypatch.setattr(native, "parse_tweet_block", lambda *a, **k: None)
    path = tmp_path / "nonobj.jsonl"
    good = {"text": "RT", "retweeted_status": {"text": "ok", "retweet_count": 500,
            "user": {"followers_count": 1, "favourites_count": 1,
                     "friends_count": 1}, "timestamp_ms": "1785318888888"}}
    path.write_text(
        "[1, 2]\n" + json.dumps(good) + "\n\"str\"\n5\n" + json.dumps(good) + "\n",
        encoding="utf-8",
    )
    blk = _block_path_batch(str(path), feat, row_bucket=8)
    assert blk.num_valid == 2


GOOD_LINE = {"text": "RT", "retweeted_status": {"text": "ok", "retweet_count": 500,
             "user": {"followers_count": 1, "favourites_count": 1,
                      "friends_count": 1}, "timestamp_ms": "1785313333333"}}


def _both_paths(path, feat, monkeypatch):
    """(C-path batch, Python-fallback batch) over the same file."""
    from twtml_tpu.features import native

    c = _block_path_batch(str(path), feat, row_bucket=8, unit_bucket=8192)
    with monkeypatch.context() as m:
        m.setattr(native, "parse_tweet_block", lambda *a, **k: None)
        py = _block_path_batch(str(path), feat, row_bucket=8, unit_bucket=8192)
    return c, py


def test_oversized_text_drops_line_both_paths(feat, tmp_path, monkeypatch):
    """ADVICE r1: a retweeted status whose text exceeds the wire-format
    bound (4096 UTF-16 units) is a counted bad line in the C parser AND the
    Python fallback — pinned, documented divergence from object ingest."""
    from twtml_tpu.features.native import MAX_TEXT_UNITS

    over = {"text": "RT", "retweeted_status": {
        "text": "a" * (MAX_TEXT_UNITS + 1), "retweet_count": 500,
        "user": {"followers_count": 1, "favourites_count": 1,
                 "friends_count": 1}}}
    # oversized full_text drops even when a small text would win
    over_full = {"text": "RT", "retweeted_status": {
        "text": "tiny", "full_text": "b" * (MAX_TEXT_UNITS + 100),
        "retweet_count": 500, "user": {"followers_count": 1,
        "favourites_count": 1, "friends_count": 1}}}
    at_bound = {"text": "RT", "retweeted_status": {
        "text": "c" * MAX_TEXT_UNITS, "retweet_count": 500,
        "user": {"followers_count": 1, "favourites_count": 1,
                 "friends_count": 1}, "timestamp_ms": "1785313333333"}}
    path = tmp_path / "oversized.jsonl"
    # duplicate "text" keys: the C scanner caps EVERY occurrence, so an
    # oversized first text drops the line even though dict-wise the small
    # last duplicate wins — the fallback pins the same any-occurrence rule
    dup_text = (
        '{"text": "RT", "retweeted_status": {"text": "'
        + "d" * 4097
        + '", "text": "small wins", "retweet_count": 500, '
        '"user": {"followers_count": 1}}}'
    )
    # duplicate retweeted_status keys: the C parser scans (and caps) the
    # FIRST occurrence too, while dict-wise only the clean last one survives
    dup_rt = (
        '{"text": "RT", "retweeted_status": {"text": "'
        + "e" * 4097
        + '", "retweet_count": 500}, "retweeted_status": {"text": "clean", '
        '"retweet_count": 500, "user": {"followers_count": 1}}}'
    )
    path.write_text(
        "\n".join([json.dumps(o) for o in
                   (GOOD_LINE, over, over_full, at_bound)]
                  + [dup_text, dup_rt, json.dumps(GOOD_LINE)]) + "\n",
        encoding="utf-8",
    )
    c, py = _both_paths(path, feat, monkeypatch)
    # kept: good, at-bound (exactly 4096 units), good — dropped: the two over
    assert c.num_valid == py.num_valid == 3
    _assert_batches_equal(c, py)
    assert int(max(c.length)) == 4096  # the at-bound row kept in full


def test_invalid_utf8_drops_line_both_paths(feat, tmp_path, monkeypatch):
    """ADVICE r1: overlong UTF-8 encodings are malformed in Python's utf-8
    codec (which json.loads(bytes) rides), so the C parser must reject them
    too — but UTF-8-encoded SURROGATES are KEPT by json.loads (it decodes
    bytes with errors='surrogatepass'), so both block paths keep those rows
    as lone UTF-16 units, matching the JVM view (features/hashing.py)."""
    good = json.dumps(GOOD_LINE).encode("utf-8")
    # overlong '/' (0xC0 0xAF) inside the rt text
    overlong = (b'{"text": "RT", "retweeted_status": {"text": "x\xc0\xafy", '
                b'"retweet_count": 500, "user": {"followers_count": 1}}}')
    # overlong NUL (0xC0 0x80) — the classic modified-UTF-8 case
    overlong_nul = (b'{"text": "RT", "retweeted_status": {"text": "x\xc0\x80y", '
                    b'"retweet_count": 500, "user": {"followers_count": 1}}}')
    # out-of-range code point U+110000 (0xF4 0x90 0x80 0x80)
    too_big = (b'{"text": "RT", "retweeted_status": {"text": "x\xf4\x90\x80\x80y", '
               b'"retweet_count": 500, "user": {"followers_count": 1}}}')
    # raw UTF-8-encoded surrogate U+D800 (0xED 0xA0 0x80): KEPT, like json
    surrogate = (b'{"text": "RT", "retweeted_status": {"text": "x\xed\xa0\x80y", '
                 b'"retweet_count": 500, "user": {"followers_count": 1}}}')
    # escaped lone surrogate: valid JSON, kept, exercises the
    # surrogatepass encode in the fallback's encode_texts
    escaped = (b'{"text": "RT", "retweeted_status": {"text": "x\\ud800y", '
               b'"retweet_count": 500, "user": {"followers_count": 1}}}')
    path = tmp_path / "badutf8.jsonl"
    path.write_bytes(
        good + b"\n" + overlong + b"\n" + surrogate + b"\n" + escaped + b"\n"
        + overlong_nul + b"\n" + too_big + b"\n" + good + b"\n"
    )
    c, py = _both_paths(path, feat, monkeypatch)
    # kept: good, raw-surrogate, escaped-surrogate, good
    assert c.num_valid == py.num_valid == 4
    _assert_batches_equal(c, py)
    # both surrogate rows carry the lone 0xD800 unit, not a replacement char
    assert (np.asarray(c.units) == 0xD800).sum() == 2


def test_iter_row_chunks_preserves_rows(feat):
    """The micro-batch slicer (blocks.py iter_row_chunks) must regroup
    arbitrary block boundaries into exact row chunks with identical data."""
    from twtml_tpu.features.blocks import iter_row_chunks, slice_block

    src = BlockReplayFileSource(DATA, block_bytes=256)  # many tiny blocks
    blocks = list(src.produce())
    whole = merge_blocks(blocks)
    for rows in (1, 2, 3, whole.rows, whole.rows + 5):
        chunks = list(iter_row_chunks(iter(blocks), rows))
        assert [c.rows for c in chunks[:-1]] == [rows] * (len(chunks) - 1)
        assert sum(c.rows for c in chunks) == whole.rows
        re = merge_blocks(chunks)
        np.testing.assert_array_equal(re.numeric, whole.numeric)
        np.testing.assert_array_equal(re.units, whole.units)
        np.testing.assert_array_equal(re.offsets, whole.offsets)
        np.testing.assert_array_equal(re.ascii, whole.ascii)
    # slice_block round-trip
    mid = slice_block(whole, 2, 5)
    assert mid.rows == 3
    np.testing.assert_array_equal(mid.numeric, whole.numeric[2:5])
    np.testing.assert_array_equal(
        mid.units, whole.units[whole.offsets[2] : whole.offsets[5]]
    )


def test_merge_blocks_empty_returns_zero_row_block():
    """ADVICE r1: merge_blocks([]) must not crash (a replay file where no
    line passes the filter)."""
    from twtml_tpu.features.blocks import ParsedBlock

    block = merge_blocks([])
    assert isinstance(block, ParsedBlock)
    assert block.rows == 0
    assert block.offsets.tolist() == [0]


def test_block_ingest_rejected_outside_linear_app(tmp_path):
    from twtml_tpu.apps.linear_regression import build_source
    from twtml_tpu.config import ConfArguments

    conf = ConfArguments().parse(
        ["--source", "replay", "--replayFile", DATA, "--ingest", "block"]
    )
    with pytest.raises(SystemExit):
        build_source(conf)  # kmeans/logistic call without allow_block
    assert build_source(conf, allow_block=True) is not None


def test_block_ingest_rejects_host_hashing():
    from twtml_tpu.apps.linear_regression import build_source
    from twtml_tpu.config import ConfArguments

    conf = ConfArguments().parse([
        "--source", "replay", "--replayFile", DATA,
        "--ingest", "block", "--hashOn", "host",
    ])
    with pytest.raises(SystemExit):
        build_source(conf, allow_block=True)


def test_non_numeric_timestamp_keeps_row(feat, tmp_path):
    """A quoted non-numeric timestamp_ms must not desync the parser: the
    row survives with created_ms falling back (parity with Status's
    tolerant _parse_created_at_ms)."""
    path = tmp_path / "badnum.jsonl"
    obj = {"text": "RT", "retweeted_status": {
        "text": "odd timestamp", "retweet_count": 500,
        "user": {"followers_count": 1, "favourites_count": 1,
                 "friends_count": 1}, "timestamp_ms": "not a number"}}
    path.write_text(json.dumps(obj) + "\n", encoding="utf-8")
    o = _object_path_batch(str(path), feat, row_bucket=8)
    b = _block_path_batch(str(path), feat, row_bucket=8)
    assert o.num_valid == b.num_valid == 1
    _assert_batches_equal(o, b)


def test_deeply_nested_json_is_a_bad_line_not_a_crash(feat, tmp_path):
    """~100k nested brackets are well-formed JSON but must not smash the C
    stack — counted bad, stream continues."""
    path = tmp_path / "deep.jsonl"
    good = {"text": "RT", "retweeted_status": {"text": "ok", "retweet_count": 500,
            "user": {"followers_count": 1, "favourites_count": 1,
                     "friends_count": 1}, "timestamp_ms": "1785313333333"}}
    deep = '{"x": ' + "[" * 100000 + "]" * 100000 + "}"
    path.write_text(
        json.dumps(good) + "\n" + deep + "\n" + json.dumps(good) + "\n",
        encoding="utf-8",
    )
    blk = _block_path_batch(str(path), feat, row_bucket=8)
    assert blk.num_valid == 2


@pytest.mark.parametrize("ensure_ascii", [True, False])
def test_fuzzed_unicode_parity(feat, tmp_path, ensure_ascii):
    """Seeded fuzz: random unicode texts (BMP, astral, quotes, escapes,
    controls) serialized with and without \\uXXXX escaping must parse
    identically to the Python path."""
    import random

    rng = random.Random(20260730 + int(ensure_ascii))
    alphabet = (
        [chr(c) for c in range(0x20, 0x7F)]  # printable ASCII incl. " and \\
        + ["\n", "\t", "\r", "\b", "\f"]
        + [chr(rng.randrange(0xA0, 0x2FFF)) for _ in range(40)]  # BMP
        + ["é", "你", "İ", "ẞ"]  # é, 你, İ, ẞ
        + [chr(rng.randrange(0x10000, 0x10400)) for _ in range(10)]  # astral
        + ["\U0001f600", "\U0001f525"]
    )
    def shuffled(d: dict) -> dict:
        items = list(d.items())
        rng.shuffle(items)
        return {
            k: shuffled(v) if isinstance(v, dict) else v for k, v in items
        }

    objs = []
    for i in range(200):
        text = "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 60)))
        objs.append(shuffled({
            "text": "RT wrap",
            "junk": {"nested": [i, None, True, {"deep": [text]}]},
            f"unknown_{rng.randrange(10)}": rng.choice([None, True, 1.5, "s"]),
            "retweeted_status": {
                "text": text,
                "retweet_count": rng.randrange(0, 2000),
                "extra": {"a": [rng.randrange(9)]},
                "user": {
                    "followers_count": rng.randrange(0, 10**9),
                    "favourites_count": rng.randrange(0, 10**6),
                    "friends_count": rng.randrange(0, 10**5),
                    "screen_name": "user_" + str(i),
                },
                "timestamp_ms": str(rng.randrange(10**12, 2 * 10**12)),
            },
        }))
    path = tmp_path / f"fuzz_{ensure_ascii}.jsonl"
    path.write_text(
        "\n".join(json.dumps(o, ensure_ascii=ensure_ascii) for o in objs) + "\n",
        encoding="utf-8",
    )
    obj_b = _object_path_batch(str(path), feat, row_bucket=256, unit_bucket=128)
    blk_b = _block_path_batch(str(path), feat, row_bucket=256, unit_bucket=128)
    assert obj_b.num_valid > 20  # the filter keeps a healthy sample
    _assert_batches_equal(obj_b, blk_b)


def test_logistic_app_block_ingest_matches_object(capsys):
    """The logistic app's block path (unit_label_fn sentiment) must produce
    the same per-batch stats as its object path."""
    from twtml_tpu.apps import logistic_regression as app
    from twtml_tpu.config import ConfArguments

    outputs = {}
    for ingest in ("object", "block"):
        conf = ConfArguments().parse([
            "--source", "replay", "--replayFile", DATA, "--ingest", ingest,
            "--lightning", "http://127.0.0.1:9", "--twtweb", "http://127.0.0.1:9",
            "--backend", "cpu",
        ])
        app.run(conf, max_batches=1)
        outputs[ingest] = [
            l for l in capsys.readouterr().out.splitlines()
            if l.startswith("count:")
        ]
    assert outputs["block"] == outputs["object"]
    assert outputs["block"], "no stats lines captured"


def test_unit_label_fn_parity_on_blocks(feat):
    """sentiment_labels_from_units over a parsed block == per-status
    sentiment labels over the same tweets."""
    import numpy as np

    from twtml_tpu.features.sentiment import (
        sentiment_label,
        sentiment_labels_from_units,
    )

    src = BlockReplayFileSource(DATA)
    block = merge_blocks(list(src.produce()))
    with open(DATA, encoding="utf-8") as fh:
        statuses = [Status.from_json(json.loads(l)) for l in fh if l.strip()]
    kept = [s for s in statuses if feat.filtrate(s)]
    want = np.array([sentiment_label(s) for s in kept], np.float32)
    got = sentiment_labels_from_units(block.units, block.offsets)
    np.testing.assert_array_equal(got, want)


def test_unit_labels_use_original_units_under_accent_normalization(tmp_path):
    """normalize_accents must never leak into labels: stripping 'bàd'→'bad'
    would change a lexicon hit. Labels come from the ORIGINAL units."""
    import numpy as np

    from twtml_tpu.features.sentiment import (
        sentiment_label,
        sentiment_labels_from_units,
    )

    path = tmp_path / "accented.jsonl"
    obj = {"text": "RT", "retweeted_status": {
        "text": "this is bàd news", "retweet_count": 500,
        "user": {"followers_count": 1, "favourites_count": 1,
                 "friends_count": 1}, "timestamp_ms": "1785313333333"}}
    path.write_text(json.dumps(obj) + "\n", encoding="utf-8")
    feat = Featurizer(
        now_ms=1785320000000,
        normalize_accents=True,
        unit_label_fn=sentiment_labels_from_units,
    )
    src = BlockReplayFileSource(str(path))
    batch = feat.featurize_parsed_block(merge_blocks(list(src.produce())))
    with open(path, encoding="utf-8") as fh:
        status = Status.from_json(json.loads(fh.readline()))
    assert batch.label[0] == sentiment_label(status) == 1.0  # 'bàd' ≠ 'bad'


def test_kmeans_app_block_ingest_matches_object(capsys):
    """k-means block path (numeric-column featurization, NO interval
    filter) must print the same per-batch centers as the object path."""
    from twtml_tpu.apps import kmeans as app
    from twtml_tpu.config import ConfArguments

    outputs = {}
    for ingest in ("object", "block"):
        conf = ConfArguments().parse([
            "--source", "replay", "--replayFile", DATA, "--ingest", ingest,
            "--lightning", "http://127.0.0.1:9", "--twtweb", "http://127.0.0.1:9",
            "--backend", "cpu",
        ])
        app.run(conf, max_batches=1, wall_clock=False)
        outputs[ingest] = [
            l for l in capsys.readouterr().out.splitlines()
            if l.startswith("count:")
        ]
    assert outputs["block"] == outputs["object"]
    assert outputs["block"], "no stats lines captured"


def test_warmup_compile_is_a_semantic_noop(capsys):
    """Pinning both buckets pre-compiles the step on an all-padding batch:
    weights stay at zeros and the subsequent real run is unchanged."""
    import numpy as np

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD

    from twtml_tpu.streaming.context import FeatureStream

    conf = ConfArguments().parse(["--batchBucket", "8", "--tokenBucket", "64"])
    feat = Featurizer(now_ms=1785320000000)
    model = StreamingLinearRegressionWithSGD(num_iterations=5)
    stream = FeatureStream(
        feat, row_bucket=conf.batchBucket, token_bucket=conf.tokenBucket,
        device_hash=True,
    )
    app.warmup_compile(stream, model)
    assert np.abs(model.latest_weights).sum() == 0.0  # no-op for the learner

    conf2 = ConfArguments().parse([
        "--source", "replay", "--replayFile", DATA,
        "--batchBucket", "8", "--tokenBucket", "64",
        "--lightning", "http://127.0.0.1:9", "--twtweb", "http://127.0.0.1:9",
        "--backend", "cpu",
    ])
    app.run(conf2, max_batches=1)
    lines = [
        l for l in capsys.readouterr().out.splitlines() if l.startswith("count:")
    ]
    assert lines == ["count: 6  batch: 6  mse: 481105.0  stdev (real, pred): (346, 0)"]


def test_empty_warmup_batch_matches_block_batch_shape(feat):
    """The shape contract warmup relies on in block mode: with the same
    pinned buckets, featurize_batch_units([]) (what featurize_empty emits)
    and featurize_parsed_block (what the stream emits) compile the SAME
    jit program — identical pytree structure, shapes, and dtypes. The units
    wire dtype is per-batch (uint8 for byte-ranged batches, uint16
    otherwise); the warmup's uint8 batch plus its uint16-widened twin (what
    apps/common.warmup_compile steps) must cover every real batch."""
    import jax

    src = BlockReplayFileSource(DATA)
    real = feat.featurize_parsed_block(
        merge_blocks(list(src.produce())), row_bucket=16, unit_bucket=128
    )
    warm = feat.featurize_batch_units([], row_bucket=16, unit_bucket=128)
    assert jax.tree_util.tree_structure(warm) == jax.tree_util.tree_structure(real)
    assert warm.units.dtype == np.uint8  # the canonical warm batch
    assert real.units.dtype in (np.uint8, np.uint16)
    for w, r in zip(warm, real):
        assert w.shape == r.shape
        if w is not warm.units:
            assert w.dtype == r.dtype


def test_fault_injection_counts_tweets_in_blocks():
    """--faultEvery counts TWEETS for block sources too (a block is ~2000
    rows; counting items would make faults thousands of times rarer), and a
    threshold crossed INSIDE a stream's only block still fires — the
    crossing block is lost in flight, like a dropped socket."""
    from twtml_tpu.streaming.faults import FaultInjectingSource, InjectedFault

    def drain(block_bytes):
        src = FaultInjectingSource(
            BlockReplayFileSource(DATA, block_bytes=block_bytes),
            crash_every=3,  # fixture has 6 kept retweets
            max_crashes=1,
        )
        rows, crashed = 0, False
        it = src.produce()
        while True:
            try:
                rows += next(it).rows
            except InjectedFault:
                crashed = True
                break
            except StopIteration:
                break
        return rows, crashed

    # single block holding all 6 tweets: the threshold is inside it
    rows, crashed = drain(1 << 20)
    assert crashed and rows == 0
    # several small blocks: crash still keyed to the tweet count
    rows, crashed = drain(256)
    assert crashed and rows < 6


def test_byte_range_sharding_partitions_rows_exactly(feat):
    """r5 (VERDICT r4 #4): shard_index/shard_count split the file by byte
    range, line-aligned — every kept row lands in exactly one shard and the
    shards' concatenation equals the unsharded parse (each host reads only
    ~1/N of the bytes)."""
    whole = merge_blocks(list(BlockReplayFileSource(DATA).produce()))
    for n in (2, 3, 4):
        shard_blocks = [
            list(BlockReplayFileSource(
                DATA, shard_index=i, shard_count=n, block_bytes=512
            ).produce())
            for i in range(n)
        ]
        merged = merge_blocks([b for blocks in shard_blocks for b in blocks])
        np.testing.assert_array_equal(merged.numeric, whole.numeric)
        np.testing.assert_array_equal(merged.units, whole.units)
        np.testing.assert_array_equal(merged.offsets, whole.offsets)
        np.testing.assert_array_equal(merged.ascii, whole.ascii)


def test_drain_splits_overshooting_blocks():
    """A ParsedBlock bigger than the drain cap splits AT the cap with the
    remainder put back (r5) — capped drains are exactly bucket-sized, which
    multi-host lockstep requires and which pins single-host block batch
    shapes too."""
    from twtml_tpu.streaming.context import StreamingContext
    from twtml_tpu.streaming.sources import QueueSource

    src = BlockReplayFileSource(DATA)
    big = merge_blocks(list(src.produce()))
    assert big.rows >= 4

    ssc = StreamingContext(batch_interval=0)
    ssc.raw_stream(QueueSource(), row_bucket=2)
    ssc._queue.put(big)
    drained = ssc._drain(2)
    assert sum(b.rows for b in drained) == 2
    # remainder is back at the queue FRONT, in order
    rest = ssc._drain(0)
    merged = merge_blocks(drained + [b for b in rest])
    np.testing.assert_array_equal(merged.numeric, big.numeric)
    np.testing.assert_array_equal(merged.units, big.units)


def test_drain_hands_over_one_merged_block():
    """PR 33: a drain that takes several parsed blocks merges them ONCE at
    the seam, so the lineage stamp, the journal's record and featurize all
    read one block (each used to merge the list again): the drained list
    holds a single block equal to the merge, the overshoot still split at
    the cap and left at the queue's front."""
    from twtml_tpu.features.blocks import slice_block
    from twtml_tpu.streaming.context import StreamingContext
    from twtml_tpu.streaming.sources import QueueSource

    big = merge_blocks(list(BlockReplayFileSource(DATA).produce()))
    assert big.rows >= 5
    ssc = StreamingContext(batch_interval=0)
    ssc.raw_stream(QueueSource(), row_bucket=4)
    for lo, hi in ((0, 1), (1, 3), (3, big.rows)):
        ssc._queue.put(slice_block(big, lo, hi))
    (head,) = ssc._drain(4)
    for got, want in zip(head, slice_block(big, 0, 4), strict=True):
        np.testing.assert_array_equal(got, want)
    (rest,) = ssc._drain(0)
    for got, want in zip(rest, slice_block(big, 4, big.rows), strict=True):
        np.testing.assert_array_equal(got, want)
