"""Tweet filter + feature assembly (reference: MllibHelper.scala:11-96).

Semantics preserved exactly:
- filter: only retweets whose original's retweetCount lies in
  [numRetweetBegin, numRetweetEnd] pass (MllibHelper.scala:89-95);
- text features: lowercase the *original* tweet's text, split into character
  bigrams, hash with HashingTF into numTextFeatures dims
  (MllibHelper.scala:42-56);
- numeric features: followers/favourites/friends counts scaled by 1e-12 and
  tweet age in milliseconds scaled by 1e-14 (MllibHelper.scala:58-71);
- label: the original tweet's retweetCount (MllibHelper.scala:81).

Deliberate divergences from reference quirks (SURVEY.md §2.5), both fixed
here because they are plain bugs there:
- ``reset`` actually applies numTextFeatures (the reference shadows its own
  fields with local vars, MllibHelper.scala:27-29, so the hasher stays at
  1000 dims no matter the flag);
- accent normalization is still OFF by default for hash parity with the
  reference (which computes ``noAccentText`` and then ignores it,
  MllibHelper.scala:49-54), but can be enabled via ``normalize_accents=True``.
"""

from __future__ import annotations

import datetime
import functools
import inspect
import itertools
import operator
import time
import unicodedata
from dataclasses import dataclass, field
from email.utils import parsedate_to_datetime
from typing import Any, Callable

import numpy as np

from .batch import (
    NUM_NUMBER_FEATURES,
    FeatureBatch,
    UnitBatch,
    compact_tokens,
    pad_feature_batch,
)
from .hashing import char_bigrams, hashing_tf_counts

# One C-level pass over the originals for every numeric column + the label
# (lambda-per-column fromiter costs ~25% more in the hot path).
_NUMERIC_COLS = operator.attrgetter(
    "followers_count", "favourites_count", "friends_count",
    "created_at_ms", "retweet_count",
)
# single-attribute getters for the r18 one-traversal gather: list(map(...))
# runs the extraction at C speed, so the only Python-bytecode loop left on
# the object featurize path is the filter itself
_RS_GET = operator.attrgetter("retweeted_status")
_TEXT_GET = operator.attrgetter("text")

# hand-scaling constants of the reference (MllibHelper.scala:64-67)
COUNT_SCALE = 1e-12  # followers / favourites / friends
AGE_SCALE = 1e-14  # tweet age in milliseconds


@functools.lru_cache(maxsize=32)
def _accepts_encoded(fn) -> bool:
    """Whether a batched labeler declares an ``encoded=`` keyword (the
    opt-in contract for reusing the featurizer's UTF-16 encode pass)."""
    try:
        return "encoded" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def _pad_ragged_units(
    units: np.ndarray,
    offsets: np.ndarray,
    lengths: np.ndarray,
    n: int,
    b: int,
    lu: int,
    narrow: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Ragged UTF-16 units → ([b, lu] buffer, [b] int32 lengths) with ASCII
    case folded — C row-copy fast path, numpy gather fallback. Shared by
    both UnitBatch builders (Status lists and columnar blocks).

    ``narrow=True`` ships the buffer as uint8 — the half-width wire format
    for batches every caller-known-ASCII row fits (the overwhelmingly common
    case). The units buffer is the largest tensor on the host→device wire,
    so this halves the dominant wire tensor with ZERO extra data passes: the flag comes from
    metadata both builders already have (parser ascii flags / isascii), the
    narrow write happens inside the same C pad copy, and the device hash
    upcasts to int32 either way (ops/text_hash.py) — identical features. A
    stream mixing both dtypes compiles at most one extra program
    (apps/common.warmup_compile warms both)."""
    from . import native

    if units.dtype == np.uint8:
        # narrow-wire block units (zero-copy parser) on the PADDED wire:
        # the C pad copy reads uint16 — widen once (the padded wire is not
        # the wire parser's target; apps gate it to the ragged wire)
        units = units.astype(np.uint16)

    padded = (
        native.pad_units((units, offsets), n, b, lu, ascii_lower=True,
                         narrow=narrow)
        if n
        else None
    )
    if padded is not None:
        return padded
    buf = np.zeros((b, lu), dtype=np.uint16)
    length = np.zeros((b,), dtype=np.int32)
    if n:
        cols = np.arange(lu, dtype=np.int64)[None, :]
        valid = cols < lengths[:, None]
        pos = offsets[:-1, None] + cols
        buf[:n][valid] = units[pos[valid]]
        length[:n] = lengths
        upper = (buf >= 65) & (buf <= 90)
        buf[upper] += 32
    if narrow:
        buf = buf.astype(np.uint8)
    return buf, length


def _parse_created_at_ms(value: Any) -> int:
    """Twitter timestamps: epoch ms int, ``timestamp_ms`` string, or the
    classic ``Wed Aug 27 13:08:45 +0000 2008`` format."""
    if value is None:
        return 0
    if isinstance(value, (int, float)):
        return int(value)
    s = str(value)
    if s.isdigit():
        return int(s)
    try:
        # Twitter's format is close enough to RFC 2822 for this parser once
        # the weekday/month tokens are in the expected order (datetime is a
        # module-scope import: this fallback sits on the hot created_at
        # path of object ingest, where a per-call import taxed every tweet)
        dt = datetime.datetime.strptime(s, "%a %b %d %H:%M:%S %z %Y")
        return int(dt.timestamp() * 1000)
    except ValueError:
        try:
            return int(parsedate_to_datetime(s).timestamp() * 1000)
        except Exception:  # lawcheck: disable=TW005 -- reference parse semantics: an unparsable created_at is 0, the Status-path ground truth (parity law: don't fix reference quirks)
            return 0


def _strip_accents(text: str) -> str:
    """NFD-decompose and drop combining marks (the reference computes this
    and then ignores it — MllibHelper.scala:49-54; opt-in here)."""
    return "".join(
        ch
        for ch in unicodedata.normalize("NFD", text)
        if unicodedata.category(ch) != "Mn"
    )


@dataclass(slots=True)
class Status:
    """Minimal tweet model covering the Twitter4j Status surface the
    reference reads (getRetweetedStatus/getText/getUser/getCreatedAt/
    getRetweetCount — MllibHelper.scala:42-95)."""

    text: str = ""
    retweet_count: int = 0
    followers_count: int = 0
    favourites_count: int = 0
    friends_count: int = 0
    created_at_ms: int = 0
    retweeted_status: "Status | None" = None
    lang: str = ""
    # the tweet's snowflake id (getId) — the live multi-host intake shard
    # key (streaming/sources.IdShardedSource); 0 when absent (synthetic/
    # replay fixtures without ids)
    id: int = 0

    @property
    def is_retweet(self) -> bool:
        return self.retweeted_status is not None

    @classmethod
    def from_json(cls, obj: dict[str, Any]) -> "Status":
        """Parse a (standard-API) tweet JSON object, including the nested
        ``retweeted_status``."""
        user = obj.get("user") or {}
        rs = obj.get("retweeted_status")
        return cls(
            text=obj.get("text") or obj.get("full_text") or "",
            retweet_count=int(obj.get("retweet_count") or 0),
            followers_count=int(user.get("followers_count") or 0),
            favourites_count=int(user.get("favourites_count") or 0),
            friends_count=int(user.get("friends_count") or 0),
            created_at_ms=_parse_created_at_ms(
                obj.get("timestamp_ms") or obj.get("created_at")
            ),
            retweeted_status=cls.from_json(rs) if rs else None,
            lang=obj.get("lang") or "",
            id=int(obj.get("id") or 0),
        )

    def to_json(self) -> dict[str, Any]:
        """The wire-format tweet JSON object ``from_json`` reads back
        (recursive on retweets). A stream's line always names a language,
        so an unset ``lang`` is written as ``"en"``."""
        d: dict[str, Any] = {
            "text": self.text,
            "retweet_count": self.retweet_count,
            "user": {
                "followers_count": self.followers_count,
                "favourites_count": self.favourites_count,
                "friends_count": self.friends_count,
            },
            "timestamp_ms": str(self.created_at_ms),
            "lang": self.lang or "en",
        }
        if self.id:
            d["id"] = self.id
        if self.retweeted_status is not None:
            d["retweeted_status"] = self.retweeted_status.to_json()
        return d


@dataclass
class Featurizer:
    """Configured featurizer. Unlike the reference's mutable singleton
    (``MllibHelper`` object), this is an explicit value you construct from
    config — no global mutable state, safe to use from multiple streams."""

    num_text_features: int = 1000  # MllibHelper.scala:17
    num_retweet_begin: int = 100  # MllibHelper.scala:15
    num_retweet_end: int = 1000  # MllibHelper.scala:16
    normalize_accents: bool = False  # reference computes-and-drops, §2.5
    now_ms: int | None = None  # fixed clock for deterministic replay; None=wall
    label_fn: "Callable[[Status], float] | None" = None  # default: retweetCount
    # optional batched form of label_fn (same semantics, one call per batch)
    # for hot paths — e.g. features/sentiment.py sentiment_labels
    batch_label_fn: "Callable[[list[Status]], np.ndarray] | None" = None
    # optional labeler over ragged UTF-16 units for the block-ingest path,
    # where no Status objects exist — e.g. sentiment_labels_from_units.
    # NOTE: narrow-wire blocks (zero-copy parser) carry uint8 units —
    # labelers must accept either dtype (values are code units either way;
    # sentiment_labels_from_units upcasts internally)
    unit_label_fn: "Callable[[np.ndarray, np.ndarray], np.ndarray] | None" = None
    num_number_features: int = field(default=NUM_NUMBER_FEATURES, init=False)
    # per-call featurize sub-stage clock [(name, t0, seconds)] — read by
    # FeatureStream._featurize after each call (telemetry side-channel:
    # ``featurize.{encode,numeric,wire_build}_ms`` gauges + nested trace
    # spans, so the straggler ladder can name WHICH half of featurize
    # gates a host). Three perf_counter reads per BATCH, never per tweet.
    last_substages: list = field(default_factory=list, init=False, repr=False)
    # what a sub-stage's trace span carries beside its time, by name (the
    # labeler's rows and bytes read): {name: {arg: value}}
    last_substage_args: dict = field(default_factory=dict, init=False, repr=False)

    @classmethod
    def from_conf(cls, conf) -> "Featurizer":
        """Equivalent of MllibHelper.reset(conf) (MllibHelper.scala:22-32),
        except the knobs actually take effect (see module docstring).

        ``TWTML_NOW_MS`` (env) pins the age-feature clock — the
        deterministic-replay hook app-level differential tests use to
        compare a real app run against a library-built ground truth (the
        age feature otherwise reads the wall clock, as the reference's
        ``new Date()`` does — MllibHelper.scala:73)."""
        import os as _os

        now_env = _os.environ.get("TWTML_NOW_MS", "")
        return cls(
            num_text_features=conf.numTextFeatures,
            num_retweet_begin=conf.numRetweetBegin,
            num_retweet_end=conf.numRetweetEnd,
            now_ms=int(now_env) if now_env else None,
        )

    @property
    def num_features(self) -> int:
        return self.num_text_features + self.num_number_features

    # -- filter (MllibHelper.scala:84-95) -----------------------------------
    def retweet_interval(self, status: Status) -> bool:
        n = status.retweeted_status.retweet_count
        return self.num_retweet_begin <= n <= self.num_retweet_end

    def filtrate(self, status: Status) -> bool:
        return status.is_retweet and self.retweet_interval(status)

    # -- featurize (MllibHelper.scala:42-82) ---------------------------------
    def featurize_text(self, status: Status) -> dict[int, float]:
        text = status.retweeted_status.text.lower()
        if self.normalize_accents:
            text = _strip_accents(text)
        return hashing_tf_counts(char_bigrams(text), self.num_text_features)

    def unit_len(self, status: Status) -> int:
        """UTF-16 unit count the wire formats will carry for this status's
        text — the same original-tweet/lower/accent handling as
        ``featurize_batch_units``/``featurize_text``, kept HERE so the
        over-long-row probe (multi-host lockstep overflow handling,
        streaming/context.py) can never drift from the canonical encoding.
        Unmeasurable rows count as over-long."""
        try:
            text = status.retweeted_status.text.lower()
            if self.normalize_accents:
                text = _strip_accents(text)
            return len(text.encode("utf-16-le", "surrogatepass")) // 2
        except Exception:  # lawcheck: disable=TW005 -- documented degrade (docstring above): an unmeasurable row counts as over-long so lockstep overflow handling drops it instead of desyncing
            return 1 << 30

    def featurize_numbers(self, status: Status) -> np.ndarray:
        original = status.retweeted_status
        now = self.now_ms if self.now_ms is not None else int(time.time() * 1000)
        time_left = now - original.created_at_ms
        return np.array(
            [
                original.followers_count * COUNT_SCALE,
                original.favourites_count * COUNT_SCALE,
                original.friends_count * COUNT_SCALE,
                time_left * AGE_SCALE,
            ],
            dtype=np.float32,
        )

    def featurize(self, status: Status) -> tuple[dict[int, float], np.ndarray, float]:
        """Sparse text counts + dense numerics + label, the host-side half of
        the LabeledPoint assembly; the device half (scatter into a dense or
        sharded vector) lives in ops/sparse.py."""
        label = (
            float(status.retweeted_status.retweet_count)
            if self.label_fn is None
            else float(self.label_fn(status))
        )
        return (self.featurize_text(status), self.featurize_numbers(status), label)

    def featurize_batch(
        self,
        statuses: list[Status],
        row_bucket: int = 0,
        token_bucket: int = 0,
        pre_filtered: bool = False,
        row_multiple: int = 1,
    ) -> FeatureBatch:
        """Filter + featurize + pad a micro-batch of tweets.

        Hot path: text hashing runs in the C++ extension (native/fasthash.cpp)
        writing straight into the padded buffers, and the numeric/label
        columns are assembled vectorized — the Python per-tweet path remains
        as semantic ground truth and fallback."""
        keep = statuses if pre_filtered else [s for s in statuses if self.filtrate(s)]
        fast = self._featurize_batch_native(keep, row_bucket, token_bucket, row_multiple)
        if fast is not None:
            return fast
        if self.batch_label_fn is not None:
            # featurize() consults label_fn only; the batched labeler must
            # apply on this fallback path too (else labels silently revert).
            # Features first with whatever label featurize produces cheaply,
            # then one batched labeling pass (never both per-status AND
            # batched — that would double the labeling cost here)
            rows = [
                (self.featurize_text(s), self.featurize_numbers(s), 0.0)
                for s in keep
            ]
            labels = self.batch_label_fn(keep)
            rows = [
                (text, nums, float(lab))
                for (text, nums, _), lab in zip(rows, labels)
            ]
        else:
            rows = [self.featurize(s) for s in keep]
        # token_val here is always hashing_tf_counts output — counts by
        # construction (label_fn customizes labels, never token values)
        return pad_feature_batch(
            rows, row_bucket=row_bucket, token_bucket=token_bucket,
            row_multiple=row_multiple, num_features=self.num_text_features,
            counts=True,
        )

    def _featurize_batch_native(
        self, keep: list[Status], row_bucket: int, token_bucket: int,
        row_multiple: int = 1,
    ) -> FeatureBatch | None:
        from . import native
        from .batch import _bucket, pad_row_count

        if self.normalize_accents:
            return None  # python path handles the uncommon configuration
            # (accent stripping changes the hashed units themselves)
        if not native.available():
            return None
        n = len(keep)
        originals = [s.retweeted_status for s in keep]
        texts = [o.text.lower() for o in originals]
        encoded = native.encode_texts(texts)
        # distinct bigrams per tweet can't exceed its UTF-16 unit count − 1
        # (bigrams window over code units, like the JVM — astral chars count
        # twice), so this token bucket only needs a retry in the pathological
        # >1024-distinct-terms case where the C side signals fallback
        lengths = np.diff(encoded[1])
        max_tok = int(np.maximum(lengths - 1, 1).max()) if n else 1
        b = pad_row_count(n, row_bucket, row_multiple)
        lt = (
            token_bucket
            if token_bucket >= max_tok and token_bucket > 0
            else _bucket(max_tok)
        )
        token_idx = np.zeros((b, lt), dtype=np.int32)
        token_val = np.zeros((b, lt), dtype=np.float32)
        ntok = native.hash_texts(
            texts, self.num_text_features, token_idx, token_val, encoded=encoded
        )
        if ntok is None:
            return None

        numeric, label, mask = self._numeric_label_mask(
            keep, originals, b, encoded=encoded
        )
        token_idx, token_val = compact_tokens(
            token_idx, token_val, self.num_text_features, counts=True,
            validate=False,  # C hasher output is in-range by construction
        )
        return FeatureBatch(token_idx, token_val, numeric, label, mask)

    def _sub(self, name: str, t0: float) -> float:
        """Record one featurize sub-stage span; returns the stage end
        time (the next stage's t0)."""
        t1 = time.perf_counter()
        self.last_substages.append((name, t0, t1 - t0))
        return t1

    def _label_units(self, label: np.ndarray, block, t0: float) -> float:
        """``unit_label_fn`` over the block's ORIGINAL raw units into
        ``label[:rows]``, as a sub-stage of its own (``featurize.label``:
        host work that grows with the units read, not with the rows)."""
        n = block.rows
        label[:n] = self.unit_label_fn(block.units, block.offsets)
        self.last_substage_args["label"] = {
            "rows": n,
            "bytes": int(block.offsets[n] - block.offsets[0])
            * block.units.dtype.itemsize,
        }
        return self._sub("label", t0)

    def _apply_label_fns(self, label: np.ndarray, keep, encoded) -> bool:
        """Apply a configured custom labeler over ``label[:n]`` — the ONE
        definition of the label_fn/batch_label_fn precedence both the
        numpy ground truth and the fused native path share. Returns False
        when no custom labeler is set (the default label is the numeric
        columns' retweet count, filled by whichever path ran)."""
        n = len(keep)
        if self.batch_label_fn is not None:
            if encoded is not None and _accepts_encoded(self.batch_label_fn):
                label[:n] = self.batch_label_fn(keep, encoded=encoded)
            else:
                label[:n] = self.batch_label_fn(keep)
            return True
        if self.label_fn is not None:
            label[:n] = [self.label_fn(s) for s in keep]
            return True
        return False

    def _numeric_label_mask(
        self, keep, originals, b: int, encoded=None, cols=None
    ):
        """Padded numeric/label/mask columns. ``cols``: the float64 [n, 5]
        numeric columns already gathered by ``_gather_rows`` (one Python
        traversal, r18); None falls back to the attrgetter pass over
        ``originals``. ``encoded``: the batch's already-computed (units,
        offsets) of the originals' (lowercased) texts, offered to a
        batched labeler that accepts it — avoids a second encode pass on
        the hot path."""
        n = len(keep)
        numeric = np.zeros((b, NUM_NUMBER_FEATURES), dtype=np.float32)
        label = np.zeros((b,), dtype=np.float32)
        mask = np.zeros((b,), dtype=np.float32)
        if not n:
            return numeric, label, mask
        now = self.now_ms if self.now_ms is not None else int(time.time() * 1000)
        if cols is None:
            cols = np.fromiter(
                itertools.chain.from_iterable(map(_NUMERIC_COLS, originals)),
                np.float64, n * 5,
            ).reshape(n, 5)
        numeric[:n, :3] = cols[:, :3] * COUNT_SCALE
        numeric[:n, 3] = (now - cols[:, 3]) * AGE_SCALE
        if not self._apply_label_fns(label, keep, encoded):
            label[:n] = cols[:, 4]
        mask[:n] = 1.0
        return numeric, label, mask

    def _gather_rows(self, statuses: list[Status], pre_filtered: bool):
        """ONE Python-level traversal of the Status objects (r18): the
        filter is the only remaining Python-bytecode loop; texts and the
        five numeric columns then extract from the kept originals at C
        speed (``list(map(attrgetter))`` / ``np.fromiter``). The object
        ingest path previously paid four separate per-tweet Python
        traversals (the filtrate comprehension with two method calls per
        row, the originals comprehension, the isascii/lower loop, the
        attrgetter fromiter) — that was most of the featurize stage.

        Returns (keep, texts, cols float64 [n, 5] in _NUMERIC_COLS
        order). ``keep`` is the kept Status objects when a custom
        labeler will need them; with no labeler configured it is the
        kept ORIGINALS — only its length is read downstream, and
        skipping the second per-row append is measurable. Texts are the
        originals' RAW texts — per-text lower()/accent handling stays in
        ``_encode_batch_texts``. ``filtrate``/``retweet_interval`` are
        inlined only when not overridden (a subclassed filter keeps its
        exact semantics at one method call per row); the inlined compare
        is the same Python-int comparison the ground truth makes."""
        inline = (
            type(self).filtrate is Featurizer.filtrate
            and type(self).retweet_interval is Featurizer.retweet_interval
        )
        need_statuses = (
            self.label_fn is not None or self.batch_label_fn is not None
        )
        if pre_filtered:
            keep: list = statuses
            rts = list(map(_RS_GET, statuses))
        elif inline and not need_statuses:
            nb, ne = self.num_retweet_begin, self.num_retweet_end
            rts = []
            ra = rts.append
            for s in statuses:
                rs = s.retweeted_status
                if rs is not None and nb <= rs.retweet_count <= ne:
                    ra(rs)
            keep = rts  # length-only sentinel (no labeler reads it)
        else:
            nb, ne = self.num_retweet_begin, self.num_retweet_end
            keep = []
            rts = []
            ka, ra = keep.append, rts.append
            if inline:
                for s in statuses:
                    rs = s.retweeted_status
                    if rs is not None and nb <= rs.retweet_count <= ne:
                        ka(s)
                        ra(rs)
            else:
                for s in statuses:
                    if self.filtrate(s):
                        ka(s)
                        ra(s.retweeted_status)
        n = len(rts)
        texts = list(map(_TEXT_GET, rts))
        # float64 conversion from the Python ints in one C pass — the
        # exact conversion the pre-r18 fromiter ground truth performed
        # (the parity law's numeric columns)
        cols = np.fromiter(
            itertools.chain.from_iterable(map(_NUMERIC_COLS, rts)),
            np.float64, n * 5,
        ).reshape(n, 5)
        return keep, texts, cols

    def _encode_batch_texts(self, statuses: list[Status], pre_filtered: bool):
        """Shared filter + UTF-16 encode for the unit-wire builders
        (padded ``featurize_batch_units`` and ragged
        ``featurize_batch_ragged``): returns
        (keep, cols, units, offsets, all_ascii) — ``cols`` the float64
        [n, 5] numeric columns from the same single Status traversal
        (``_gather_rows``)."""
        from . import native

        keep, texts, cols = self._gather_rows(statuses, pre_filtered)
        if self.normalize_accents:
            texts = [_strip_accents(t.lower()) for t in texts]
            all_ascii = all(t.isascii() for t in texts)
            units, offsets = native.encode_texts(texts)
            return keep, cols, units, offsets, all_ascii
        # case-folding strategy: texts with non-ASCII chars need Python's
        # Unicode lower(); pure-ASCII texts (the common case) are folded
        # for free later — during the pad copy (padded wire) or on device
        # (ragged wire); re-folding the pre-lowered rows' ASCII range is
        # idempotent. The ascii probe is ONE C scan of the joined batch
        # text, and on the all-ASCII batch the probe's join IS the encode
        # join (one unit per char — the same split encode_texts computes)
        joined = "".join(texts)
        if not joined.isascii():
            texts = [t if t.isascii() else t.lower() for t in texts]
            units, offsets = native.encode_texts(texts)
            return keep, cols, units, offsets, False
        units = np.frombuffer(
            joined.encode("utf-16-le", "surrogatepass"), dtype=np.uint16
        )
        n = len(texts)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(
            np.fromiter(map(len, texts), np.int64, n), out=offsets[1:]
        )
        if units.size == 0:
            units = np.zeros(1, dtype=np.uint16)
        return keep, cols, units, offsets, True

    @staticmethod
    def _row_len_bucket(max_len: int, unit_bucket: int) -> int:
        """The padded row length L for a given max row length — the ONE
        bucket policy both unit wires and the fused native path share.
        L ≥ 2 so the device's [:, :-1]/[:, 1:] bigram windows are
        non-empty."""
        from .batch import _bucket

        return (
            unit_bucket
            if unit_bucket >= max(max_len, 2) and unit_bucket > 0
            else _bucket(max(max_len, 2))
        )

    @staticmethod
    def _unit_batch_shape(
        n: int, lengths, row_bucket: int, unit_bucket: int, row_multiple: int
    ) -> tuple[int, int]:
        """The ONE (padded rows, padded row length) policy for both unit
        wires — padded and ragged MUST agree on compile shapes or the
        bit-identical-features contract drifts."""
        from .batch import pad_row_count

        max_len = int(lengths.max()) if n else 0
        b = pad_row_count(n, row_bucket, row_multiple)
        return b, Featurizer._row_len_bucket(max_len, unit_bucket)

    def featurize_batch_ragged(
        self,
        statuses: list[Status],
        row_bucket: int = 0,
        unit_bucket: int = 0,
        pre_filtered: bool = False,
        row_multiple: int = 1,
        pack: bool = False,
    ):
        """Filter + encode a micro-batch for the RAGGED device wire
        (features/batch.RaggedUnitBatch): the units ship concatenated
        (Σlengths, rounded to RAGGED_UNIT_MULTIPLE) instead of padded
        (B·L_bucket) — the learner re-pads by lane rows and case-folds
        ASCII inside the jit step, producing features bit-identical to the
        padded paths (differential tests in tests/test_ragged_wire.py).
        ``unit_bucket`` still pins the REBUILT row length L (compile-shape
        discipline); only the wire stops paying for padding."""
        from .batch import RaggedUnitBatch, pad_row_count, ragged_wire_arrays

        self.last_substages, self.last_substage_args = [], {}
        t0 = time.perf_counter()
        keep, cols, units, offsets, all_ascii = (
            self._encode_batch_texts(statuses, pre_filtered)
        )
        t0 = self._sub("encode", t0)
        n = len(keep)
        b = pad_row_count(n, row_bucket, row_multiple)
        enc = (units, offsets) if not self.normalize_accents else None
        # one-pass native fast path (r18, --featurizeNative): ONE C sweep
        # emits the final ragged-wire arrays — flat units (narrow uint8
        # iff every row is ASCII, the same metadata gate as the padded
        # wire), padded int32 offsets, scaled f32 numeric/label/mask —
        # into one arena lease; None falls through to the ground truth
        from . import featurize_native as _ffz

        fast = _ffz.try_fill(
            units, offsets, cols, _ffz.object_col_order(), n, b,
            narrow=all_ascii,
            now_ms=(
                self.now_ms if self.now_ms is not None
                else int(time.time() * 1000)
            ),
        )
        if fast is not None:
            flat, offs, numeric, label, mask, max_len, lease = fast
            t0 = self._sub("wire_build", t0)
            if n:
                self._apply_label_fns(label, keep, enc)
            self._sub("numeric", t0)
            batch = RaggedUnitBatch(
                flat, offs, numeric, label, mask,
                row_len=self._row_len_bucket(max_len, unit_bucket),
            )
            _ffz.attach_lease(batch, lease)
        else:
            lengths = np.diff(offsets).astype(np.int32)
            lu = self._row_len_bucket(
                int(lengths.max()) if n else 0, unit_bucket
            )
            flat, offs = ragged_wire_arrays(
                units, offsets, n, b, narrow=all_ascii
            )
            t0 = self._sub("wire_build", t0)
            numeric, label, mask = self._numeric_label_mask(
                keep, None, b, encoded=enc, cols=cols
            )
            self._sub("numeric", t0)
            batch = RaggedUnitBatch(
                flat, offs, numeric, label, mask, row_len=lu
            )
        if pack:
            # one-buffer wire (one transfer instead of five) for callers
            # that feed the model directly; apps keep the unpacked batch for
            # their handlers and pack at the model boundary (FetchPipeline)
            from .batch import pack_batch

            return pack_batch(batch)
        return batch

    def featurize_batch_units(
        self,
        statuses: list[Status],
        row_bucket: int = 0,
        unit_bucket: int = 0,
        pre_filtered: bool = False,
        row_multiple: int = 1,
    ) -> UnitBatch:
        """Filter + encode + pad a micro-batch for ON-DEVICE featurization.

        The text half is shipped as raw UTF-16 code units (lowercased — case
        folding is genuinely host work; hashing is not) and the learner
        hashes bigrams inside its jit step (ops/text_hash.py), producing
        features bit-identical to `featurize_batch`'s. Host cost per batch
        drops to one encode + one vectorized pad — no per-bigram work at all.
        """
        self.last_substages, self.last_substage_args = [], {}
        t0 = time.perf_counter()
        keep, cols, units, offsets, all_ascii = (
            self._encode_batch_texts(statuses, pre_filtered)
        )
        t0 = self._sub("encode", t0)
        n = len(keep)
        lengths = np.diff(offsets).astype(np.int32)
        b, lu = self._unit_batch_shape(
            n, lengths, row_bucket, unit_bucket, row_multiple
        )
        buf, length = _pad_ragged_units(
            units, offsets, lengths, n, b, lu, narrow=all_ascii
        )
        t0 = self._sub("wire_build", t0)
        # the encode is reusable by a batched labeler only when it reflects
        # the plain lowercased text (accent stripping changes the tokens)
        enc = (units, offsets) if not self.normalize_accents else None
        numeric, label, mask = self._numeric_label_mask(
            keep, None, b, encoded=enc, cols=cols
        )
        self._sub("numeric", t0)
        return UnitBatch(buf, length, numeric, label, mask)

    def featurize_parsed_block(
        self,
        block,
        row_bucket: int = 0,
        unit_bucket: int = 0,
        row_multiple: int = 1,
        ragged: bool = False,
        pack: bool = False,
    ):
        """Columnar block (features/blocks.py, rows already filtered by the
        native parser) → UnitBatch, with zero per-tweet Python work in the
        common case: numeric scaling is vectorized and text goes straight to
        the C pad (ASCII case folded there). Only rows containing non-ASCII
        units — or every row under ``normalize_accents`` — pay a Python
        lower()/normalize round-trip. Custom labels: set ``unit_label_fn``
        (labels from the ORIGINAL raw units, e.g. the lexicon sentiment
        scorer); the Status-based ``label_fn``/``batch_label_fn`` need the
        object ingest path and are rejected here."""
        from . import native
        from .blocks import (
            COL_CREATED_MS,
            COL_FAVOURITES,
            COL_FOLLOWERS,
            COL_FRIENDS,
            COL_LABEL,
        )

        if self.unit_label_fn is None and (
            self.label_fn is not None or self.batch_label_fn is not None
        ):
            raise ValueError(
                "featurize_parsed_block labels come from unit_label_fn "
                "(Status-based label_fn/batch_label_fn need the object "
                "ingest path)"
            )
        self.last_substages, self.last_substage_args = [], {}
        t0 = time.perf_counter()
        n = block.rows
        # one-pass native fast path (r18, --featurizeNative): in the
        # common case — ragged wire, every row parser-ASCII-flagged (so
        # no Unicode redo round-trip exists), no accent stripping — ONE C
        # sweep emits the final wire arrays from the parser's columns
        # (int64 → float64 scale, bit-matching the astype ground truth)
        # into one arena lease, and the stage runs no numpy passes at all
        if (
            ragged
            and not self.normalize_accents
            and (n == 0 or not bool((np.asarray(block.ascii) == 0).any()))
        ):
            from . import featurize_native as _ffz
            from .batch import (
                RaggedUnitBatch as _RB,
                pack_batch as _pack_batch,
                pad_row_count as _pad_row_count,
            )

            t0 = self._sub("encode", t0)  # the ascii probe IS the text prep
            b = _pad_row_count(n, row_bucket, row_multiple)
            fast = _ffz.try_fill(
                block.units, block.offsets, block.numeric,
                _ffz.block_col_order(), n, b, narrow=True,
                now_ms=(
                    self.now_ms if self.now_ms is not None
                    else int(time.time() * 1000)
                ),
            )
            if fast is not None:
                flat, offs, numeric, label, mask, max_len, lease = fast
                t0 = self._sub("wire_build", t0)
                if n and self.unit_label_fn is not None:
                    # labels from the ORIGINAL raw units, like the ground
                    # truth below
                    t0 = self._label_units(label, block, t0)
                self._sub("numeric", t0)
                batch = _RB(
                    flat, offs, numeric, label, mask,
                    row_len=self._row_len_bucket(max_len, unit_bucket),
                )
                _ffz.attach_lease(batch, lease)
                return _pack_batch(batch) if pack else batch
        units, offsets = block.units, block.offsets.copy()
        redo = (
            np.arange(n)
            if self.normalize_accents
            else np.nonzero(block.ascii == 0)[0]
        )
        if n and redo.size and units.dtype == np.uint8:
            # narrow-wire block (the zero-copy parser emits uint8 units
            # when every row is ASCII, so redo is normally empty here) that
            # still needs the per-row Unicode round-trip — only under
            # normalize_accents: widen once for the utf-16 decode below
            units = units.astype(np.uint16)
        if n and redo.size:
            # per-row Unicode round-trip for the rows that need it. The
            # common case (lower() preserves length) writes in place —
            # O(redo rows), not O(all rows); only a length-CHANGING mapping
            # (e.g. İ → i̇) forces a ragged reassembly, and then only the
            # changed rows pay Python-level work
            new_units = units.copy()
            new_lens = np.diff(block.offsets)
            resized: dict[int, np.ndarray] = {}
            for i in redo:
                raw = units[block.offsets[i] : block.offsets[i + 1]]
                text = raw.tobytes().decode("utf-16-le", "surrogatepass").lower()
                if self.normalize_accents:
                    text = _strip_accents(text)
                enc = np.frombuffer(
                    text.encode("utf-16-le", "surrogatepass"), dtype=np.uint16
                )
                if enc.size == raw.size:
                    new_units[block.offsets[i] : block.offsets[i + 1]] = enc
                else:
                    resized[int(i)] = enc
                    new_lens[i] = enc.size
            if resized:
                pieces = [
                    resized.get(
                        i, new_units[block.offsets[i] : block.offsets[i + 1]]
                    )
                    for i in range(n)
                ]
                units = np.concatenate(pieces) if pieces else np.zeros(1, np.uint16)
                np.cumsum(new_lens, out=offsets[1:])
            else:
                units = new_units
        t0 = self._sub("encode", t0)
        lengths = np.diff(offsets).astype(np.int32)
        b, lu = self._unit_batch_shape(
            n, lengths, row_bucket, unit_bucket, row_multiple
        )
        # narrow wire iff every row is parser-ASCII-flagged: redo rows are
        # exactly the non-ASCII ones (normalize_accents marks all rows redo,
        # so it conservatively keeps the wide wire) — metadata, never sniffed
        narrow = n == 0 or redo.size == 0

        now = self.now_ms if self.now_ms is not None else int(time.time() * 1000)
        numeric = np.zeros((b, NUM_NUMBER_FEATURES), dtype=np.float32)
        label = np.zeros((b,), dtype=np.float32)
        mask = np.zeros((b,), dtype=np.float32)
        if n:
            cols64 = block.numeric.astype(np.float64)
            numeric[:n, 0] = cols64[:, COL_FOLLOWERS] * COUNT_SCALE
            numeric[:n, 1] = cols64[:, COL_FAVOURITES] * COUNT_SCALE
            numeric[:n, 2] = cols64[:, COL_FRIENDS] * COUNT_SCALE
            numeric[:n, 3] = (now - cols64[:, COL_CREATED_MS]) * AGE_SCALE
            mask[:n] = 1.0
            if self.unit_label_fn is None:
                label[:n] = cols64[:, COL_LABEL]
        t0 = self._sub("numeric", t0)
        if n and self.unit_label_fn is not None:
            # labels from the ORIGINAL raw units (pre-lower/normalize:
            # the object path labels over the original text too, and
            # normalize_accents must never leak into labels — stripping
            # 'bàd'→'bad' would change a lexicon hit)
            t0 = self._label_units(label, block, t0)
        if ragged:
            # the block ALREADY holds concatenated units + offsets — the
            # ragged wire ships them as-is (no pad copy at all); the jit
            # step re-pads by lane rows + device ASCII fold, features
            # bit-identical to the padded path (tests/test_ragged_wire.py)
            from .batch import RaggedUnitBatch, pack_batch, ragged_wire_arrays

            flat, offs = ragged_wire_arrays(units, offsets, n, b, narrow=narrow)
            batch = RaggedUnitBatch(
                flat, offs, numeric, label, mask, row_len=lu
            )
            self._sub("wire_build", t0)
            return pack_batch(batch) if pack else batch
        buf, length = _pad_ragged_units(
            units, offsets, lengths, n, b, lu, narrow=narrow
        )
        self._sub("wire_build", t0)
        return UnitBatch(buf, length, numeric, label, mask)
