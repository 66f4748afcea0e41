"""Stream sources — the receiver layer.

The reference's only receiver is ``TwitterUtils.createStream`` (a Twitter4j
long-lived socket pinned to one executor, LinearRegression.scala:44;
SURVEY.md §2.4.4 "receiver parallelism = 1"). Here a source is a small
supervised producer thread pushing parsed ``Status`` objects into the
micro-batcher's queue:

- ``ReplayFileSource`` — deterministic replay of a tweets .jsonl fixture
  (the BASELINE configs' replayed-tweet source), optionally rate-paced;
- ``SyntheticSource`` — parameterized synthetic tweet generator with a known
  ground-truth linear relationship (for parity tests and benchmarks);
- ``QueueSource`` — push-from-test source;
- the live ``TwitterSource`` lives in twitter.py (gated on credentials).

Supervision: a crashed producer thread is restarted with exponential backoff
(``max_restarts``), the upgrade over Spark's receiver defaults the survey
calls for (SURVEY.md §5.3).
"""

from __future__ import annotations

import json
import queue
import random
import threading
import time
from typing import Callable, Iterator

from ..features.featurizer import Status
from ..telemetry import sideband as _sideband
from ..utils import get_logger

log = get_logger("streaming.sources")

# lazily-bound faults module (faults.py imports Source from here, so a
# module-scope import back would be circular); cached so the per-emit hot
# path pays one global read + one is-None check when chaos is off
_faults_mod = None


def _burst_extra() -> int:
    global _faults_mod
    if _faults_mod is None:
        from . import faults

        _faults_mod = faults
    if _faults_mod._CHAOS is None:
        return 0
    return _faults_mod.burst_extra()


def _record_event_lag(created_at_ms: int) -> None:
    """Ingest event-time lag gauge (ISSUE 16 satellite): arrival wall-clock
    minus the tweet's own ``created_at_ms`` — the gap the paced replay
    branch has computed (and dropped) since r1. Lazy metrics import keeps
    the sources module import-light; the clock goes through the
    ``TWTML_NOW_MS`` seam so tests pin it."""
    if created_at_ms <= 0:
        return
    from ..telemetry import metrics as _metrics
    from ..utils.clock import now_ms

    _metrics.get_registry().gauge("ingest.event_time_lag_ms").set(
        float(max(0, now_ms() - int(created_at_ms)))
    )


def _maybe_corrupt(data: bytes) -> bytes:
    global _faults_mod
    if _faults_mod is None:
        from . import faults

        _faults_mod = faults
    if _faults_mod._CHAOS is None:
        return data
    return _faults_mod.maybe_corrupt_block(data)


def _count_parse_drops(n: int) -> None:
    """Malformed/garbage lines the block parser skipped — registry state
    (``ingest.rows_dropped_parse``) instead of log-only, so wire damage is
    visible on /api/metrics next to the other ingest-loss counters."""
    from ..telemetry import metrics as _metrics

    _metrics.get_registry().counter("ingest.rows_dropped_parse").inc(n)


class Source:
    """Base: override ``produce`` (a generator of Status) — the harness turns
    it into a supervised thread feeding ``emit``."""

    name = "source"

    def __init__(self, max_restarts: int = 3, restart_backoff: float = 1.0):
        self._emit: Callable[[Status], None] | None = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._exhausted = threading.Event()
        self.max_restarts = max_restarts
        self.restart_backoff = restart_backoff

    def produce(self) -> Iterator[Status]:  # pragma: no cover - abstract
        raise NotImplementedError

    def start(self, emit: Callable[[Status], None]) -> None:
        self._emit = emit
        self._stop.clear()
        self._exhausted.clear()
        self._thread = threading.Thread(
            target=self._run_supervised, name=f"twtml-source-{self.name}", daemon=True
        )
        self._thread.start()

    def _run_supervised(self) -> None:
        restarts = 0
        while not self._stop.is_set():
            emitted_any = False
            try:
                for status in self.produce():
                    if self._stop.is_set():
                        return
                    self._emit(status)
                    emitted_any = True
                    extra = _burst_extra()  # --chaos source.burst rate spike
                    for _ in range(extra):
                        self._emit(status)
                self._exhausted.set()
                return  # clean end of stream
            except Exception as exc:
                if emitted_any:
                    # a run that produced data was a healthy (re)connection:
                    # max_restarts bounds CONSECUTIVE failures and the
                    # backoff ladder restarts from the bottom (the Twitter
                    # reconnect rules reset on successful connection; a
                    # receiver that streamed for hours must not die on its
                    # 4th lifetime disconnect)
                    restarts = 0
                restarts += 1
                if restarts > self.max_restarts:
                    log.exception("source %s died permanently", self.name)
                    self._exhausted.set()
                    return
                backoff = self._backoff(exc, restarts)
                # a flapping stream must be VISIBLE, not a silent retry
                # loop buried in logs: restarts are first-class registry
                # state (total + per source name) for /api/metrics
                from ..telemetry import metrics as _metrics

                reg = _metrics.get_registry()
                reg.counter("source.restarts").inc()
                reg.counter(f"source.{self.name}.restarts").inc()
                log.exception(
                    "source %s crashed; restart %d/%d in %.1fs",
                    self.name, restarts, self.max_restarts, backoff,
                )
                if self._stop.wait(backoff):
                    return

    # restart backoff ceiling; class-level so a subclass (or a test) can
    # tighten it without re-deriving the ladder
    BACKOFF_CAP_S = 30.0

    def _backoff(self, exc: Exception, restarts: int) -> float:
        """Seconds to sleep before restart ``restarts`` (1-based) after
        ``exc``. Default: exponential from ``restart_backoff``, JITTERED
        (uniform in [0.5x, 1x] of the ladder value — N restarting shards
        of one dead upstream must not reconnect in phase) and capped at
        ``BACKOFF_CAP_S``. Subclasses override for error-class-aware
        policies (the live Twitter receiver distinguishes rate-limit vs
        HTTP vs transport failures, twitter.py). The exponent is capped
        too: restarts can reach the millions in unbounded chaos runs and
        2**n overflows."""
        del exc
        base = min(
            self.restart_backoff * (2 ** min(restarts - 1, 12)),
            self.BACKOFF_CAP_S,
        )
        return base * (0.5 + 0.5 * random.random())

    # how long stop() waits for the producer thread; class-level so tests
    # can shrink it without monkeypatching join()
    JOIN_TIMEOUT_S = 5.0

    def stop(self) -> None:
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=self.JOIN_TIMEOUT_S)
            if thread.is_alive():
                # a silent timed-out join here used to make stuck shutdowns
                # invisible — name the wedged thread so the operator can
                # see WHICH producer is blocked (daemon threads die with
                # the process, so shutdown still completes)
                log.warning(
                    "source %s did not stop: producer thread %r still "
                    "running %.1fs after the stop request (wedged in a "
                    "blocking call?); proceeding with shutdown",
                    self.name, thread.name, self.JOIN_TIMEOUT_S,
                )

    @property
    def exhausted(self) -> bool:
        return self._exhausted.is_set()


class ReplayFileSource(Source):
    """Replay a .jsonl file of tweet objects. ``speed`` = 0 replays as fast
    as possible; otherwise tweets are paced at ``speed`` × realtime using the
    inter-tweet gaps in their timestamps (missing timestamps → 10ms gap)."""

    name = "replay"

    def __init__(self, path: str, speed: float = 0.0, loop: bool = False, **kw):
        super().__init__(**kw)
        self.path = path
        self.speed = speed
        self.loop = loop

    # tweets per aggregated ``parse`` span: per-line spans would swamp the
    # trace at the C parser's rate, so the source thread batches
    # its parse time into one complete event per this many lines
    PARSE_SPAN_EVERY = 1024

    def produce(self) -> Iterator[Status]:
        from ..telemetry import trace as _trace

        while True:
            prev_ms: int | None = None
            tr = _trace.get()
            t_parse, n_parse = 0.0, 0
            n_lag = 0
            with open(self.path, encoding="utf-8") as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    if tr.enabled:
                        t0 = time.perf_counter()
                        status = Status.from_json(json.loads(line))
                        t_parse += time.perf_counter() - t0
                        n_parse += 1
                        if n_parse >= self.PARSE_SPAN_EVERY:
                            tr.complete(
                                "parse", time.perf_counter() - t_parse,
                                t_parse, items=n_parse,
                            )
                            _sideband.record_stage("parse", t_parse)
                            t_parse, n_parse = 0.0, 0
                    else:
                        # per-line timing stays trace-gated: two clock
                        # reads per tweet would tax the parser
                        # — the sideband's parse attribution on
                        # OBJECT ingest therefore needs --trace (the block
                        # parser below always contributes)
                        status = Status.from_json(json.loads(line))
                    if self.speed > 0:
                        gap_ms = 10.0
                        if prev_ms and status.created_at_ms > prev_ms:
                            gap_ms = status.created_at_ms - prev_ms
                        prev_ms = status.created_at_ms or prev_ms
                        # paced replays record per status: the pacing wait
                        # dwarfs one clock read
                        _record_event_lag(status.created_at_ms)
                        if self._stop.wait(gap_ms / 1000.0 / self.speed):
                            return
                    else:
                        # as-fast-as-possible replays sample every
                        # PARSE_SPAN_EVERY statuses — per-tweet clock reads
                        # would tax the C parser
                        n_lag += 1
                        if n_lag >= self.PARSE_SPAN_EVERY:
                            n_lag = 0
                            _record_event_lag(status.created_at_ms)
                    yield status
            if n_parse:
                tr.complete(
                    "parse", time.perf_counter() - t_parse, t_parse,
                    items=n_parse,
                )
                _sideband.record_stage("parse", t_parse)
            if not self.loop:
                return


class BlockParserMixin:
    """The bytes → ParsedBlock stage both block sources share (file replay
    below and the live ``BlockTwitterSource``, twitter.py): the native C
    parser with the pure-Python ground-truth fallback. Consumers set
    ``begin``/``end`` (the retweet-interval filter) and ``copy``.

    ``wire=True`` parses through the zero-copy wire emitter
    (``native.parse_tweet_block_wire``): one C pass from raw bytes to the
    ragged wire's unit representation — blocks then carry **uint8** units
    whenever every kept row is ASCII (the narrow wire dtype, decided by the
    parser's per-row metadata, so the featurizer's downcast pass
    disappears). Kept rows and every emitted array are byte-identical to
    the legacy parser (tests/test_blockwire.py); only bad-line COUNTS may
    undercount on keyless malformed lines (the prescreen skips whole-line
    validation there — native/tweetjson.cpp banner). Degrades in order:
    wire emitter → legacy C parser (stale library without the symbol,
    counted + warned once by features/native.py) → Python ground truth."""

    begin: int
    end: int
    copy: bool = True
    wire: bool = False

    def parse_buffer(self, data: bytes) -> "list":
        """Parse a whole byte buffer (must end at a line boundary) into
        ParsedBlocks, looping over the parser's capacity bounds so an
        oversized buffer cannot drop its tail."""
        blocks = []
        while data.strip():
            if not data.endswith(b"\n"):
                data += b"\n"
            block, rest = self._parse(data)
            if block is not None and block.rows:
                blocks.append(block)
            if not rest or rest == data:
                break
            data = rest
        return blocks

    def _parse(self, data: bytes):
        """(ParsedBlock | None, carry bytes) for one buffered chunk —
        instrumented as the ``parse`` stage (one real span per chunk; the
        block path parses MB-scale buffers, so per-chunk spans are cheap).
        The parse rate and byte volume are first-class registry state
        (``ingest.parse_tweets_per_s`` gauge, ``ingest.parse_bytes``
        counter): the bottleneck ladder's parse rung is readable off
        /api/metrics without a bench run, and the PR 5 straggler ladder's
        ``parse`` attribution keeps riding the same ``record_stage`` clock
        whichever parser (wire / legacy / Python) ran."""
        from ..telemetry import metrics as _metrics
        from ..telemetry import trace as _trace

        tr = _trace.get()
        t0 = time.perf_counter()
        if not tr.enabled:
            out = self._parse_impl(data)
            dt = time.perf_counter() - t0
            _sideband.record_stage("parse", dt)
            self._record_parse_metrics(_metrics, len(data), out[0], dt)
            return out
        with tr.span("parse", bytes=len(data)) as sp:
            block, rest = self._parse_impl(data)
            if block is not None:
                sp.add(rows=int(block.rows))
        dt = time.perf_counter() - t0
        _sideband.record_stage("parse", dt)
        self._record_parse_metrics(_metrics, len(data), block, dt)
        return block, rest

    @staticmethod
    def _record_parse_metrics(_metrics, nbytes: int, block, dt: float) -> None:
        reg = _metrics.get_registry()
        reg.counter("ingest.parse_bytes").inc(nbytes)
        if block is not None and dt > 0:
            reg.gauge("ingest.parse_tweets_per_s").set(
                round(block.rows / dt, 1)
            )

    def _parse_impl(self, data: bytes):
        from ..features import native
        from ..features.blocks import ParsedBlock

        # --chaos source.garbage: damage the buffer BEFORE the parser —
        # the skip-and-count contract below is what absorbs it
        data = _maybe_corrupt(data)
        out = (
            native.parse_tweet_block_wire(
                data, self.begin, self.end, copy=self.copy
            )
            if self.wire
            else None
        )
        if out is None:
            out = native.parse_tweet_block(
                data, self.begin, self.end, copy=self.copy
            )
        if out is not None:
            numeric, units, offsets, ascii_flags, consumed, bad = out
            if bad:
                log.warning("block parser skipped %d malformed lines", bad)
                _count_parse_drops(bad)
            return (
                ParsedBlock(numeric, units, offsets, ascii_flags),
                data[consumed:],
            )
        return self._py_parse(data)

    def _py_parse(self, data: bytes):
        """Ground-truth fallback: json.loads + Status per line."""
        import numpy as np

        from ..features.blocks import ParsedBlock
        from ..features.native import MAX_TEXT_UNITS, encode_texts

        # the C parser's documented wire-format bound (kMaxTextUnits,
        # native/tweetjson.cpp): a retweeted status with ANY "text"/
        # "full_text" occurrence (duplicate JSON keys included — the C
        # scanner caps every occurrence, while plain dicts keep only the
        # last) over the unit bound makes the whole line a counted bad
        # line — pinned here so both block paths agree on adversarial
        # input (the object-ingest Status path keeps such rows)
        class _Obj(dict):
            oversized = False  # a DIRECT text/full_text value too big
            rt_oversized = False  # ANY retweeted_status value oversized

        def _pairs_hook(pairs):
            d = _Obj(pairs)
            for k, v in pairs:
                if (
                    k in ("text", "full_text")
                    and isinstance(v, str)
                    and len(v.encode("utf-16-le", "surrogatepass")) // 2
                    > MAX_TEXT_UNITS
                ):
                    d.oversized = True
                # any-occurrence, not last-wins: the C scanner caps EVERY
                # duplicate retweeted_status occurrence, while dict(pairs)
                # would keep only the last
                if k == "retweeted_status" and getattr(v, "oversized", False):
                    d.rt_oversized = True
            return d

        def oversized(obj) -> bool:
            # only the retweeted_status object's DIRECT text fields are
            # bounded (the C parser skips all other strings uncapped, incl.
            # anything nested inside the retweeted status)
            return getattr(obj, "rt_oversized", False)

        nl = data.rfind(b"\n")
        if nl < 0:
            return None, data
        lines, carry = data[:nl].split(b"\n"), data[nl + 1 :]
        numerics, texts = [], []
        for ln in lines:
            ln = ln.strip()
            if not ln:
                continue
            try:
                obj = json.loads(ln, object_pairs_hook=_pairs_hook)
                if oversized(obj):
                    raise ValueError("text exceeds the wire-format unit bound")
                status = Status.from_json(obj)
            except (ValueError, AttributeError, TypeError):
                # same contract as the C parser: malformed lines (including
                # valid JSON that isn't a tweet object) skip, never crash
                log.warning("block parser skipped a malformed line")
                _count_parse_drops(1)
                continue
            o = status.retweeted_status
            if o is not None and self.begin <= o.retweet_count <= self.end:
                numerics.append((
                    o.retweet_count, o.followers_count, o.favourites_count,
                    o.friends_count, o.created_at_ms,
                ))
                texts.append(o.text)
        units, offsets = encode_texts(texts)
        block = ParsedBlock(
            np.array(numerics, np.int64).reshape(len(texts), 5),
            units[: offsets[-1]],
            offsets,
            np.array([1 if t.isascii() else 0 for t in texts], np.uint8),
        )
        return block, carry




class BlockReplayFileSource(BlockParserMixin, Source):
    """Replay a .jsonl file through the NATIVE data loader: each yielded
    item is a columnar ParsedBlock (features/blocks.py) straight from the C
    parser (native/tweetjson.cpp), with the isRetweet + retweet-interval
    filter already applied — no per-tweet Python objects at all, an order of
    magnitude faster than the json.loads path. Pure-Python fallback (the
    ground truth) kicks in when the C library is unavailable. As-fast-as-
    possible only (block ingest has no per-tweet pacing).

    ``shard_index``/``shard_count`` select a BYTE-RANGE shard of the file
    (r5, multi-host block ingest — the Spark analog of shipping
    deserialization to every executor, SURVEY.md §2.4 L0): the file's byte
    span splits into ``shard_count`` equal ranges, and a line belongs to
    the shard containing its FIRST byte, so each host reads AND parses only
    ~1/N of the file with no coordination and no line read twice. Unlike
    ``ShardedSource``'s per-item round robin this keeps each shard's IO
    sequential — the point of the block loader."""

    name = "replay-block"

    def __init__(
        self,
        path: str,
        num_retweet_begin: int = 100,
        num_retweet_end: int = 1000,
        block_bytes: int = 1 << 20,
        loop: bool = False,
        copy: bool = True,
        wire: bool = False,
        shard_index: int = 0,
        shard_count: int = 1,
        **kw,
    ):
        super().__init__(**kw)
        self.path = path
        self.begin = num_retweet_begin
        self.end = num_retweet_end
        self.block_bytes = block_bytes
        self.loop = loop
        # copy=False: blocks are views into per-call buffers (see
        # native.parse_tweet_block) — for consumers that featurize each
        # block promptly (the bench pipeline), not for accumulation
        self.copy = copy
        # wire=True: parse through the zero-copy wire emitter (see
        # BlockParserMixin) — apps enable it for the ragged device wire
        self.wire = wire
        if not 0 <= shard_index < max(1, shard_count):
            raise ValueError(
                f"shard index {shard_index} out of range for {shard_count}"
            )
        self.shard_index = shard_index
        self.shard_count = max(1, shard_count)

    def _shard_range(self) -> "tuple[int, int]":
        """This shard's [start, stop) byte range, line-aligned: a raw range
        boundary is pushed forward past the line containing it (unless it
        already sits at a line start), identically for this shard's stop
        and the next shard's start — so every line lands in exactly one
        shard."""
        import os

        size = os.path.getsize(self.path)
        if self.shard_count <= 1:
            return 0, size

        def boundary(pos: int) -> int:
            if pos <= 0 or pos >= size:
                return min(max(pos, 0), size)
            with open(self.path, "rb") as fh:
                fh.seek(pos - 1)
                if fh.read(1) != b"\n":
                    fh.readline()  # mid-line: the line belongs to the left
                return fh.tell()

        lo = size * self.shard_index // self.shard_count
        hi = size * (self.shard_index + 1) // self.shard_count
        return boundary(lo), boundary(hi)

    def produce(self) -> Iterator:
        while True:
            lo, hi = self._shard_range()
            with open(self.path, "rb") as fh:
                fh.seek(lo)
                remaining = hi - lo
                carry = b""
                while True:
                    chunk = (
                        fh.read(min(self.block_bytes, remaining))
                        if remaining > 0
                        else b""
                    )
                    remaining -= len(chunk)
                    if not chunk:
                        # drain the tail through the shared capacity-bound
                        # loop (parse_buffer — one copy of the stall guard
                        # for both block sources, r5 review)
                        for block in self.parse_buffer(carry):
                            yield block
                        break
                    block, carry = self._parse(carry + chunk)
                    if block is not None and block.rows:
                        yield block
            if not self.loop:
                return



class SyntheticSource(Source):
    """Generate tweets whose retweet counts follow a known linear function of
    the features — gives analytically checkable RMSE curves (SURVEY.md §7
    stage 3). ``rate`` = tweets/sec (0 = unpaced), ``total`` = stop after n."""

    name = "synthetic"

    _WORDS = (
        "tpu stream learn fast jax pallas shard mesh grad psum tweet viral "
        "scale batch online model predict train news data"
    ).split()

    def __init__(
        self,
        total: int = 0,
        rate: float = 0.0,
        seed: int = 0,
        base_ms: int | None = None,
        **kw,
    ):
        super().__init__(**kw)
        self.total = total
        self.rate = rate
        self.seed = seed
        # created_at base: wall clock by default; pin it for BIT-exact
        # reproducibility across processes/runs (multi-host assembly
        # requires every process to build identical global batches)
        self.base_ms = base_ms

    def produce(self) -> Iterator[Status]:
        import numpy as np

        rng = np.random.default_rng(self.seed)
        count = 0
        while self.total <= 0 or count < self.total:
            n_words = int(rng.integers(3, 9))
            words = rng.choice(self._WORDS, size=n_words)
            text = " ".join(words)
            followers = int(rng.integers(100, 2_000_000))
            # ground truth: label correlates with followers + text length
            label = int(
                np.clip(100 + followers * 4e-4 + len(text) * 2 + rng.normal(0, 20),
                        100, 1000)
            )
            original = Status(
                text=text,
                retweet_count=label,
                followers_count=followers,
                favourites_count=int(rng.integers(0, 50_000)),
                friends_count=int(rng.integers(0, 10_000)),
                created_at_ms=(
                    self.base_ms
                    if self.base_ms is not None
                    else int(time.time() * 1000)
                ) - int(rng.integers(0, 86_400_000)),
            )
            yield Status(text="RT " + text, retweeted_status=original)
            count += 1
            if self.rate > 0 and self._stop.wait(1.0 / self.rate):
                return


class ShardedSource(Source):
    """Take items ``index``-of-``count`` (round-robin) from an inner source —
    the per-host intake shard of a multi-host run (SURVEY.md §7 stage 5):
    every host opens the same replay/synthetic source and keeps 1/N of the
    stream, so the union of all hosts' shards is exactly the single-host
    stream and host i's k-th batch interleaves with the others into the
    same global row set a single-host run would batch.

    **Elastic rebalance (r16)**: the shard key is a RESIDUE SET, not a
    single index — ``count`` stays the LAUNCH process count forever, and a
    departed host's residue classes are adopted by survivors
    (``adopt_residues``), so coverage going forward is exact without
    re-keying anyone's position. ``produce`` reads the set per item, so an
    adoption takes effect mid-stream from each adopter's current position
    (items of the departed residues between the death and the takeover are
    the counted loss window — streaming/membership.py)."""

    name = "shard"

    def __init__(self, inner: Source, index: int, count: int, **kw):
        super().__init__(**kw)
        if not 0 <= index < count:
            raise ValueError(f"shard index {index} out of range for {count}")
        self.inner = inner
        self.index = index
        self.count = count
        self.residues = {index}

    def adopt_residues(self, residues) -> None:
        """Take over the given residue classes (a departed host's shard),
        effective from this source's current stream position."""
        self.residues |= {int(r) % self.count for r in residues}
        log.warning(
            "intake shard rebalanced: now serving residues %s of %d",
            sorted(self.residues), self.count,
        )

    def release_residues(self, residues) -> None:
        """Hand residue classes back (a rejoined live host resumes its own
        slice); this host's original residue is never released."""
        self.residues -= {int(r) % self.count for r in residues}
        self.residues.add(self.index)

    def produce(self) -> Iterator[Status]:
        for i, status in enumerate(self.inner.produce()):
            if i % self.count in self.residues:
                yield status


class IdShardedSource(Source):
    """Take rows whose status id ≡ ``index`` (mod ``count``) from an inner
    source — the LIVE-stream intake shard of a multi-host run (BASELINE
    config #5's "4-way sharded stream" for ``--source twitter``, r5). A
    live sample stream has no deterministic item order across separately
    opened connections, so the round-robin ``ShardedSource`` cannot shard
    it; the tweet's snowflake id CAN — every host opens its own connection
    (duplicated ingress, tens of KB/s at real stream rates) and keeps a
    disjoint id-residue slice, so the union of all hosts' rows is the
    stream and no tweet trains twice. Rows without an id (id 0 — not
    produced by the real API) land on shard 0."""

    name = "idshard"

    def __init__(self, inner: Source, index: int, count: int, **kw):
        # supervision runs on THIS wrapper, so the inner source's restart
        # budget/backoff must carry through (the live receiver retries
        # indefinitely — twitter.py)
        kw.setdefault("max_restarts", inner.max_restarts)
        kw.setdefault("restart_backoff", inner.restart_backoff)
        super().__init__(**kw)
        if not 0 <= index < count:
            raise ValueError(f"shard index {index} out of range for {count}")
        self.inner = inner
        self.index = index
        self.count = count
        self.residues = {index}

    def adopt_residues(self, residues) -> None:
        """Elastic rebalance (r16): serve a departed host's id-residue
        classes too, from this connection, going forward — exact coverage
        for a live stream (ids are position-free, unlike replay indexes)."""
        self.residues |= {int(r) % self.count for r in residues}
        log.warning(
            "live intake shard rebalanced: now serving id residues %s of %d",
            sorted(self.residues), self.count,
        )

    def release_residues(self, residues) -> None:
        self.residues -= {int(r) % self.count for r in residues}
        self.residues.add(self.index)

    def produce(self) -> Iterator[Status]:
        for status in self.inner.produce():
            if status.id % self.count in self.residues:
                yield status

    def _backoff(self, exc: Exception, restarts: int) -> float:
        # delegate to the live source's error-class-aware ladder (420 vs
        # HTTP vs transport) — the supervisor wraps THIS source, so the
        # inner one's policy must carry through
        return self.inner._backoff(exc, restarts)


class SkipRowsSource(Source):
    """Discard the first ``skip_rows`` ROWS of an inner source — the boot
    half of journal replay recovery (apps/common.journal_boot_replay): on a
    restart, every row this host ever journaled is either inside the
    restored checkpoint (id < cursor) or re-enqueued from the journal
    (id >= cursor), so the deterministic source must fast-forward past ALL
    of them instead of re-producing from the top (which is what a bare
    checkpoint-restart of a replay file does — re-trained rows). A
    ParsedBlock item counts its rows and is SPLIT at the skip boundary
    (features/blocks.slice_block), matching the journal's row arithmetic.

    Wraps the OUTERMOST (post-shard) source: the journal records this
    host's post-shard stream, so the skip count is in the same row space.
    Exposes ``.inner`` for the elastic residue-rebalance chain walk."""

    name = "skiprows"

    def __init__(self, inner: Source, skip_rows: int, **kw):
        kw.setdefault("max_restarts", inner.max_restarts)
        kw.setdefault("restart_backoff", inner.restart_backoff)
        super().__init__(**kw)
        self.inner = inner
        self.skip_rows = int(skip_rows)

    def produce(self) -> Iterator[Status]:
        # a supervised restart re-enters produce(): the inner replay source
        # re-produces from its top, so the skip re-applies from its top too
        remaining = self.skip_rows
        for item in self.inner.produce():
            if remaining > 0:
                take = getattr(item, "rows", None)
                if take is None:
                    remaining -= 1
                    continue
                if take <= remaining:
                    remaining -= take
                    continue
                from ..features.blocks import slice_block

                cut = remaining
                remaining = 0
                item = slice_block(item, cut, take)
                if item.rows == 0:
                    continue
            yield item

    def _backoff(self, exc: Exception, restarts: int) -> float:
        return self.inner._backoff(exc, restarts)


class MultiSource(Source):
    """Sharded receiver fan-in: run N inner sources concurrently into one
    stream. The reference is hard-wired to a single Twitter4j receiver
    (SURVEY.md §2.4.4 "receiver parallelism = 1"); this is the single-host
    version of the N-way sharded stream in BASELINE config #5 (multi-host
    sharding lives in parallel/distributed.py)."""

    name = "multi"

    def __init__(self, sources: list[Source], **kw):
        super().__init__(**kw)
        self.sources = sources

    def start(self, emit) -> None:
        self._emit = emit
        self._stop.clear()
        self._exhausted.clear()
        for src in self.sources:
            src.start(emit)
        # watcher thread flips exhausted when every shard is done
        self._thread = threading.Thread(
            target=self._watch, name="twtml-source-multi", daemon=True
        )
        self._thread.start()

    def _watch(self) -> None:
        while not self._stop.is_set():
            if all(s.exhausted for s in self.sources):
                self._exhausted.set()
                return
            if self._stop.wait(0.05):
                return

    def stop(self) -> None:
        for src in self.sources:
            src.stop()
        super().stop()

    def produce(self):  # pragma: no cover - inner sources produce directly
        return iter(())


class QueueSource(Source):
    """Test source: push Status objects from the test thread."""

    name = "queue"

    def __init__(self, **kw):
        super().__init__(**kw)
        self._q: "queue.Queue[Status | None]" = queue.Queue()

    def push(self, status: Status) -> None:
        self._q.put(status)

    def close(self) -> None:
        self._q.put(None)

    def produce(self) -> Iterator[Status]:
        while True:
            try:
                item = self._q.get(timeout=0.1)
            except queue.Empty:
                if self._stop.is_set():
                    return  # interruptible without close()
                continue
            if item is None:
                return
            yield item
