"""Featurizer golden tests (reference semantics: MllibHelper.scala:42-95).

Fixture tweets in tests/data/tweets.jsonl cover: in-range retweets, out-of-range
(3 and 50000), boundary values (100, 1000 — inclusive per
MllibHelper.scala:84-87), non-retweets, emoji/accents, and timestamp_ms parsing.
"""

import json
import os

import numpy as np
import pytest

from twtml_tpu.features import Featurizer, Status
from twtml_tpu.features.hashing import hashing_tf_counts, char_bigrams

DATA = os.path.join(os.path.dirname(__file__), "data", "tweets.jsonl")


@pytest.fixture()
def statuses():
    with open(DATA, encoding="utf-8") as fh:
        return [Status.from_json(json.loads(line)) for line in fh if line.strip()]


@pytest.fixture()
def feat():
    return Featurizer(now_ms=1785320000000)  # fixed clock for determinism


def test_filtrate(statuses, feat):
    kept = [s for s in statuses if feat.filtrate(s)]
    # in range: 250, 500, 100 (boundary), 1000 (boundary), 777, 980
    assert [s.retweeted_status.retweet_count for s in kept] == [250, 500, 100, 1000, 777, 980]


def test_filtrate_rejects_non_retweets(statuses, feat):
    plain = [s for s in statuses if not s.is_retweet]
    assert len(plain) == 2
    assert all(not feat.filtrate(s) for s in plain)


def test_label_is_original_retweet_count(statuses, feat):
    s = statuses[0]
    _, _, label = feat.featurize(s)
    assert label == 250.0


def test_text_features_hash_original_lowercased(statuses, feat):
    s = statuses[0]  # original text: "Breaking news from the summit today!"
    counts = feat.featurize_text(s)
    expected = hashing_tf_counts(
        char_bigrams("breaking news from the summit today!"), 1000
    )
    assert counts == expected
    # Never hashes the RT-wrapper text.
    wrapper = hashing_tf_counts(
        char_bigrams("rt @alice: breaking news from the summit today!"), 1000
    )
    assert counts != wrapper


def test_numeric_feature_scaling(statuses, feat):
    s = statuses[0]
    nums = feat.featurize_numbers(s)
    orig = s.retweeted_status
    assert nums[0] == pytest.approx(50000 * 1e-12)
    assert nums[1] == pytest.approx(1200 * 1e-12)
    assert nums[2] == pytest.approx(900 * 1e-12)
    age_ms = 1785320000000 - orig.created_at_ms
    assert age_ms > 0
    assert nums[3] == pytest.approx(age_ms * 1e-14, rel=1e-6)


def test_timestamp_ms_parsing(statuses):
    s = statuses[7]
    assert s.retweeted_status.created_at_ms == 1785315612000


def test_created_at_parsing(statuses):
    # "Mon Jul 27 09:00:00 +0000 2026"
    assert statuses[0].retweeted_status.created_at_ms == 1785142800000


_PLAIN = Status(text="plain words", retweet_count=3, followers_count=40,
                favourites_count=5, friends_count=6,
                created_at_ms=1785142800000, lang="en", id=1001)


@pytest.mark.parametrize("status", [
    _PLAIN,
    Status(text="RT plain words", created_at_ms=1785315612000, lang="en",
           retweeted_status=_PLAIN),
    Status(text="RT 東京の天気 café", created_at_ms=1785315612000, lang="ja",
           retweeted_status=Status(text="東京の天気 café", retweet_count=700,
                                   followers_count=12345, lang="ja")),
], ids=["plain", "retweet", "retweet_non_ascii"])
def test_to_json_is_read_back_by_from_json(status):
    """A status written as a stream's line parses back to itself, through
    the JSON text a replay file or a socket carries."""
    line = json.dumps(status.to_json())
    assert Status.from_json(json.loads(line)) == status


def test_num_text_features_takes_effect():
    """The reference's reset() shadows its own fields (MllibHelper.scala:27-29)
    so --numTextFeatures never reaches the hasher; ours must apply it."""
    big = Featurizer(num_text_features=2**18, now_ms=0)
    s = Status(retweeted_status=Status(text="Deep learning on TPUs", retweet_count=500))
    counts = big.featurize_text(s)
    assert all(0 <= idx < 2**18 for idx in counts)
    assert big.num_features == 2**18 + 4


def test_featurize_batch_padding(statuses, feat):
    batch = feat.featurize_batch(statuses)
    assert batch.num_valid == 6
    # padded to power-of-two bucket
    assert batch.token_idx.shape[0] == 8
    assert batch.token_idx.shape == batch.token_val.shape
    assert batch.numeric.shape == (8, 4)
    assert batch.mask.tolist() == [1, 1, 1, 1, 1, 1, 0, 0]
    # padded token slots are zero-valued so scatter-adds are no-ops
    assert batch.token_val[batch.mask == 0].sum() == 0


def test_accent_normalization_optional():
    s = Status(retweeted_status=Status(text="café", retweet_count=500))
    raw = Featurizer(now_ms=0).featurize_text(s)
    norm = Featurizer(now_ms=0, normalize_accents=True).featurize_text(s)
    expected_norm = hashing_tf_counts(char_bigrams("cafe"), 1000)
    assert norm == expected_norm
    assert raw == hashing_tf_counts(char_bigrams("café"), 1000)
    assert raw != norm

def test_compact_wire_dtypes(statuses, feat):
    """Default 1004-dim schema travels int16 indices + uint16 counts; the
    wire dtype is a schema decision (stable across batches), not data-sniffed
    (host→device transfer is the streaming hot loop's bottleneck)."""
    batch = feat.featurize_batch(statuses)
    assert batch.token_idx.dtype == np.int16
    assert batch.token_val.dtype == np.uint16
    # an empty batch keeps the exact same dtypes — one compiled program
    empty = feat.featurize_batch([])
    assert empty.token_idx.dtype == np.int16
    assert empty.token_val.dtype == np.uint16


def test_compact_wire_dtypes_large_feature_space(statuses):
    """2^18-dim hashing keeps int32 indices (int16 can't address them)."""
    feat = Featurizer(num_text_features=2**18, now_ms=0)
    batch = feat.featurize_batch(statuses)
    assert batch.token_idx.dtype == np.int32
    assert batch.token_val.dtype == np.uint16


def test_compact_wire_dtypes_lossless(statuses, feat):
    """Compact batch decodes to the identical sparse features as the
    python ground-truth path."""
    batch = feat.featurize_batch(statuses)
    kept = [s for s in statuses if feat.filtrate(s)]
    for i, s in enumerate(kept):
        expected = feat.featurize_text(s)
        got = {
            int(ix): float(v)
            for ix, v in zip(batch.token_idx[i], batch.token_val[i])
            if v
        }
        assert got == expected


def test_pad_feature_batch_non_count_values_stay_float():
    """A generic caller with real-valued token_val (counts=False default)
    keeps float32 on the wire — never downcast by data coincidence."""
    from twtml_tpu.features.batch import pad_feature_batch

    rows = [({1: 2.0, 3: 1.0}, np.zeros(4, np.float32), 5.0)]  # integral...
    batch = pad_feature_batch(rows, num_features=1004)
    assert batch.token_val.dtype == np.float32  # ...but schema says no counts
    assert batch.token_idx.dtype == np.int16  # indices still compact

def test_compact_tokens_misdeclared_schema_raises():
    """Out-of-range indices or counts fail loudly instead of silently
    wrapping (int16) or switching wire dtype mid-stream (float32)."""
    from twtml_tpu.features.batch import compact_tokens

    idx = np.array([[1, 40000]], dtype=np.int32)
    val = np.array([[1.0, 1.0]], dtype=np.float32)
    with pytest.raises(ValueError):
        compact_tokens(idx, val, 1000, counts=True)
    big = np.array([[70000.0]], dtype=np.float32)
    with pytest.raises(ValueError):
        compact_tokens(np.array([[1]], np.int32), big, 1000, counts=True)

def test_compact_tokens_rejects_fractional_and_negative():
    """counts=True values must survive the uint16 round-trip exactly:
    TF-IDF-style fractional weights, negatives, and negative indices all
    raise instead of silently truncating/wrapping."""
    from twtml_tpu.features.batch import compact_tokens

    ok_idx = np.array([[1, 2]], dtype=np.int32)
    for bad in ([[0.7, 1.0]], [[-1.0, 1.0]]):
        with pytest.raises(ValueError):
            compact_tokens(
                ok_idx, np.array(bad, dtype=np.float32), 1000, counts=True
            )
    with pytest.raises(ValueError):
        compact_tokens(
            np.array([[-5, 2]], dtype=np.int32),
            np.array([[1.0, 1.0]], dtype=np.float32),
            1000,
            counts=True,
        )
