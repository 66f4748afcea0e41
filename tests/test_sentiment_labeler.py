"""The host labeler of the logistic learner (``features/sentiment.py`` over
``native/fasthash.cpp lexicon_score_batch``) keeps ONE rule: its labels
equal ``sentiment_label`` on the decoded text row for row — the C scan on
every code point, on the mix's own rows, on uint8 and uint16 blocks, object
path and block path alike — and the Python loop it replaced is paid for only
where there is no C library, under a span that says so.
"""

import json
import re

import numpy as np
import pytest

from twtml_tpu.features import native, sentiment
from twtml_tpu.features.featurizer import Status
from twtml_tpu.features.sentiment import (
    sentiment_label,
    sentiment_labels,
    sentiment_labels_from_units,
    sentiment_score,
)
from twtml_tpu.telemetry import trace

pytestmark = pytest.mark.skipif(
    not native.available(), reason="needs the native library")


def _units(texts):
    enc = [np.frombuffer(t.encode("utf-16-le", "surrogatepass"), np.uint16)
           for t in texts]
    off = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([e.size for e in enc], out=off[1:])
    return (np.concatenate(enc) if enc else np.zeros(0, np.uint16)), off


def _truth(texts):
    return np.array([1.0 if sentiment_score(t) >= 0 else 0.0 for t in texts],
                    np.float32)


def test_only_two_code_points_over_127_lower_case_into_the_token_class():
    """What the C scan rests on: over all of Unicode only U+0130 (to ``i`` +
    U+0307) and U+212A (to ``k``) reach ``[a-z']`` through ``str.lower``."""
    word = re.compile(r"[a-z']")
    hits = [c for c in range(128, 0x110000) if word.search(chr(c).lower())]
    assert hits == [0x0130, 0x212A]
    assert "İ".lower() == "i̇" and "K".lower() == "k"


# the code point BESIDE two negative words and INSIDE a positive one: as a
# separator the row scores bad, hate, good = -1 (label 0); as a letter it
# joins the negative words to itself (U+212A: "kbad", "hatek": +1), as `i` +
# a break it spoils one ("hatei": 0), and a scan that skipped it would find
# a second "good" (0): each a label 1
TEMPLATE = "{0}bad hate{0} good go{0}od"


@pytest.mark.parametrize("lo,hi", [(128, 0x4000), (0x4000, 0x10000),
                                   (0x10000, 0x90000), (0x90000, 0x110000)])
def test_c_scan_equals_the_rule_on_every_code_point(lo, hi):
    """Every code point >= 128 — surrogate halves alone (0xD800-0xDFFF),
    astral ones as their pairs, combining marks, U+0130, U+212A — placed
    inside and beside lexicon words."""
    texts = [TEMPLATE.format(chr(c)) for c in range(lo, hi)]
    got = sentiment_labels_from_units(*_units(texts))
    want = _truth(texts)
    bad = np.flatnonzero(got != want)
    assert bad.size == 0, [hex(lo + int(i)) for i in bad[:8]]
    if lo == 128:   # the template does tell the classes apart
        assert want[0x212B - lo] == want[0x0307 - lo] == 0.0
        assert want[0x212A - lo] == want[0x0130 - lo] == 1.0


CASES = [
    "BADİ", "İbad", "İ", "sucK", "sucKs hate love",
    "K", "i̇ bad", "baḋ", "̇bad", "bád",
    "\U0001F600bad", "bad\U0001F600good", "\ud83dbad\ude00 hate",
    "\ude00\ud83d bad", "café terrible", "ΣΙΓΜΑ bad",
    "", "'", "x" * 500 + " bad", "bad" * 20, "don't hate, it's the best",
    "GOOD BAD HATE", "useless！", "ＢＡＤ bad",
]


def test_c_scan_equals_the_rule_on_hand_picked_rows():
    got = sentiment_labels_from_units(*_units(CASES))
    np.testing.assert_array_equal(got, _truth(CASES))
    # pre-lowered units (the object path hands the featurizer's own encode)
    low = [t.lower() for t in CASES]
    np.testing.assert_array_equal(
        sentiment_labels_from_units(*_units(low)), _truth(CASES))


@pytest.mark.parametrize("seed", [11, 3200000171])
def test_rows_of_the_mix_object_path_and_block_path(seed):
    """Random rows of ``trimmed-kept-280-lex`` (30% non-ASCII): block path
    = object path = the rule = the plain reference's own copy of it."""
    from benchmark import gen, manifest
    from benchmark.reference import logistic_sgd

    g = manifest.load_json(
        manifest.traffic_path("trimmed-kept-280-lex"))["generator"]
    texts = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0, 2048).text
    want = _truth(texts)
    assert 0.05 < float(np.mean(want == 0)) < 0.30
    np.testing.assert_array_equal(
        logistic_sgd.labels_of(texts, g["lexicon"]), want)
    np.testing.assert_array_equal(
        sentiment_labels_from_units(*_units(texts)), want)
    statuses = [Status(text="RT", retweeted_status=Status(
        text=t, retweet_count=200)) for t in texts]
    np.testing.assert_array_equal(sentiment_labels(statuses), want)
    assert [sentiment_label(s) for s in statuses[:64]] == list(want[:64])
    # the reference's lists ARE the program's (the mix carries them as data)
    assert set(g["lexicon"]["positive"]) == sentiment.POSITIVE
    assert set(g["lexicon"]["negative"]) == sentiment.NEGATIVE


def test_uint8_block_is_read_in_place_and_scores_as_uint16(monkeypatch):
    """The narrow wire's uint8 units go to the C scan as they are: no
    widened copy of the block."""
    texts = ["this is BAD, really TERRIBLE stuff", "good vibes only", "",
             "win-win fail/fail", "goodness gracious", "it's the WORST"] * 50
    units, off = _units(texts)
    narrow = units.astype(np.uint8)
    seen = []
    real = native.lexicon_scores

    def spy(encoded, *a, **kw):
        seen.append(encoded[0])
        return real(encoded, *a, **kw)

    monkeypatch.setattr(native, "lexicon_scores", spy)
    got = sentiment_labels_from_units(narrow, off)
    assert seen[0] is narrow
    np.testing.assert_array_equal(got, _truth(texts))
    np.testing.assert_array_equal(got, sentiment_labels_from_units(units, off))
    # bytes 128-255 of a narrow block are separators like any unit >= 128
    odd = np.frombuffer(b"bad\xe9good\xffhate", np.uint8)
    assert sentiment_labels_from_units(
        odd, np.array([0, odd.size], np.int64))[0] == 0.0


class _Recorder:
    enabled = True

    def __init__(self):
        self.events = []

    def complete(self, name, t0, dur, **args):
        self.events.append((name, args))


def test_fallback_pays_python_only_without_the_library(monkeypatch):
    units, off = _units(CASES)
    rec = _Recorder()
    monkeypatch.setattr(trace, "_active", rec)
    sentiment_labels_from_units(units, off)
    assert rec.events == []          # the C scan took every row
    monkeypatch.setattr(native, "lexicon_scores", lambda *a, **k: None)
    got = sentiment_labels_from_units(units, off)
    np.testing.assert_array_equal(got, _truth(CASES))
    assert rec.events == [("label_fallback", {"rows": len(CASES)})]
    # tracing off: ONE enabled check, nothing written, same labels
    off_tracer = trace._NullTrace()
    monkeypatch.setattr(
        off_tracer, "complete",
        lambda *a, **k: pytest.fail("a span was written with tracing off"),
        raising=False)
    monkeypatch.setattr(trace, "_active", off_tracer)
    np.testing.assert_array_equal(
        sentiment_labels_from_units(units, off), _truth(CASES))


def test_label_is_a_substage_of_its_own_and_costs_one_clock_read(tmp_path):
    """``featurize.label`` is taken OUT of ``featurize.numeric`` at both
    call sites of the block path (the one-pass native fill and the NumPy
    ground truth) and carries the rows and the bytes read; a learner whose
    label is a parsed field has no such sub-stage."""
    from twtml_tpu.features import featurize_native as ffz
    from twtml_tpu.features.blocks import merge_blocks
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.sources import BlockReplayFileSource

    user = {"followers_count": 5, "favourites_count": 1, "friends_count": 2}
    path = tmp_path / "t.jsonl"
    path.write_text("".join(json.dumps({
        "text": "RT " + t, "retweet_count": 0, "lang": "en", "user": user,
        "timestamp_ms": "1785320000000",
        "retweeted_status": {"text": t, "retweet_count": 200, "lang": "en",
                             "timestamp_ms": "1785310000000", "user": user},
    }) + "\n" for t in ("good vibes only", "café terrible", "this is bad")),
        encoding="utf-8")
    block = merge_blocks(list(BlockReplayFileSource(str(path)).produce()))
    assert block.rows == 3
    units_bytes = int(block.offsets[3] - block.offsets[0]) * block.units.dtype.itemsize
    for mode in ("on", "off"):
        for label_fn in (sentiment_labels_from_units, None):
            feat = Featurizer(now_ms=1785320000000, unit_label_fn=label_fn)
            with ffz.forced(mode):
                batch = feat.featurize_parsed_block(block, ragged=True)
            names = [s[0] for s in feat.last_substages]
            if label_fn is None:
                assert "label" not in names and not feat.last_substage_args
                continue
            assert names.count("label") == 1 and "numeric" in names
            assert feat.last_substage_args == {
                "label": {"rows": 3, "bytes": units_bytes}}
            np.testing.assert_array_equal(batch.label[:3], [1.0, 0.0, 0.0])
