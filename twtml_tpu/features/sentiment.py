"""Tiny lexicon sentiment labeler for the streaming logistic model.

BASELINE config #3 is "StreamingLogisticRegressionWithSGD (binary sentiment)
on the same stream" — the reference repo has no sentiment code, so the label
definition is ours: 1.0 when the original tweet's text contains at least as
many positive-lexicon words as negative ones, else 0.0. Deterministic,
dependency-free, and cheap enough for the hot path; swap ``label`` for a real
classifier's output if one is available.
"""

from __future__ import annotations

import re
import time

from ..telemetry import trace as _trace
from .featurizer import Status

POSITIVE = frozenset(
    """good great awesome amazing love happy excellent fantastic wonderful best
    beautiful fun win winning cool nice brilliant perfect thanks thank glad
    excited super sweet favorite favourite enjoy enjoyed impressive stunning
    delightful positive success successful""".split()
)

NEGATIVE = frozenset(
    """bad terrible awful hate sad horrible worst ugly fail failing broken
    angry annoying disappointing disappointed poor boring gross nasty sucks
    suck wrong problem problems negative disaster painful worse useless""".split()
)

_WORD = re.compile(r"[a-z']+")


def sentiment_score(text: str) -> int:
    """#positive − #negative lexicon hits over lowercased word tokens."""
    words = _WORD.findall(text.lower())
    return sum(w in POSITIVE for w in words) - sum(w in NEGATIVE for w in words)


def sentiment_label(status: Status) -> float:
    """Binary label from the ORIGINAL tweet's text (featurization also reads
    the original, MllibHelper.scala:42-44)."""
    return 1.0 if sentiment_score(status.retweeted_status.text) >= 0 else 0.0


def _pack_lexicon(words: frozenset) -> tuple:
    """Lexicon as (concatenated UTF-16 units, offsets, Java hashCodes) for
    the C scorer (native/fasthash.cpp lexicon_score_batch)."""
    import numpy as np

    from .hashing import java_string_hashcode

    ws = sorted(words)
    # what the C scorer's table holds: 64 words a list, 31 units a word
    assert len(ws) <= 64 and max(map(len, ws)) <= 31, "lexicon too large"
    units = np.concatenate([
        np.frombuffer(w.encode("utf-16-le"), np.uint16) for w in ws
    ])
    off = np.zeros(len(ws) + 1, np.int64)
    np.cumsum([len(w) for w in ws], out=off[1:])
    hashes = np.array([java_string_hashcode(w) for w in ws], np.int32)
    return units, off, hashes


_POS_PACKED = _pack_lexicon(POSITIVE)
_NEG_PACKED = _pack_lexicon(NEGATIVE)


def _labels_from_scores(score, n: int, text_of) -> "np.ndarray":
    """The ONE rule both labelers share: 1.0 where the row's lexicon score
    is >= 0. ``score`` is the C scan's (exact for every row, non-ASCII
    included); None — no C library — sends each of the ``n`` rows through
    the per-row Python rule, ``text_of(i)`` giving row i's text (a
    ``label_fallback`` span under ``--trace`` says how many rows paid)."""
    import numpy as np

    if score is not None:
        return (score >= 0).astype(np.float32)
    t0 = time.perf_counter()
    labels = np.fromiter(
        (1.0 if sentiment_score(text_of(i)) >= 0 else 0.0 for i in range(n)),
        np.float32, n,
    )
    tr = _trace.get()
    if tr.enabled:
        tr.complete("label_fallback", t0, time.perf_counter() - t0, rows=n)
    return labels


def sentiment_labels(statuses: list, encoded=None) -> "np.ndarray":
    """Batched ``sentiment_label`` over the ORIGINAL texts — C hot path
    (one scan over UTF-16 units, exact for non-ASCII rows too), per-row
    Python rule when the library is unavailable.

    ``encoded``: optionally the featurizer's already-computed
    (units, offsets) of the originals' (lowercased) texts — skips a second
    encode pass; the C scorer's folds (A-Z, U+0130, U+212A) are idempotent
    on pre-lowered rows."""
    import numpy as np

    from . import native

    n = len(statuses)
    if not n:
        return np.zeros((0,), np.float32)
    score = None
    if native.available():
        if encoded is None:
            encoded = native.encode_texts(
                [s.retweeted_status.text for s in statuses]
            )
        score = native.lexicon_scores(encoded, n, _POS_PACKED, _NEG_PACKED)
    return _labels_from_scores(
        score, n, lambda i: statuses[i].retweeted_status.text
    )


def sentiment_labels_from_units(units, offsets) -> "np.ndarray":
    """Batched labels straight from ragged UTF-16 units — the block-ingest
    path's labeler (no Status objects exist there). One C scan over every
    row, uint16 units or the narrow wire's uint8 read in place; without the
    library each row decodes and scores in Python (pre-lowered units score
    identically: sentiment_score lowercases idempotently)."""
    import numpy as np

    from . import native

    n = offsets.size - 1
    if n <= 0:
        return np.zeros((0,), np.float32)
    score = native.lexicon_scores(
        (units, offsets), n, _POS_PACKED, _NEG_PACKED
    )
    return _labels_from_scores(
        score, n,
        lambda i: units[offsets[i] : offsets[i + 1]]
        .astype(np.uint16, copy=False)
        .tobytes()
        .decode("utf-16-le", "surrogatepass"),
    )
