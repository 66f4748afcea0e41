"""Live Twitter stream source (reference: TwitterUtils.createStream +
Twitter4j receiver, LinearRegression.scala:44; OAuth creds from system
properties, ConfArguments.scala:58-76).

The receiver connects to the streaming endpoint with the four
``twitter4j.oauth.*`` credentials from the process property table, parses one
JSON tweet per line, and yields ``Status`` objects. Connection handling is
delegated to the ``Source`` supervision harness (sources.py): drops and HTTP
errors raise, the supervisor restarts with exponential backoff — the upgrade
over the reference, whose receiver restart policy was whatever Spark defaults
did (SURVEY.md §5.3).

The full protocol path is native and stdlib-only: OAuth1 HMAC-SHA1 request
signing (oauth1.py, pinned by published test vectors) over a chunked
streaming HTTP/1.1 client (httpstream.py). The build environment has zero
egress, so tests drive the identical code path against a LOCAL server
speaking the v1.1 stream protocol — delimited JSON, keep-alive blank lines,
mid-stream disconnects, HTTP 420 — in tests/test_twitter_live.py;
``connect_fn`` injection remains for protocol-free unit tests.

Reconnect policy mirrors the Twitter streaming rules the Twitter4j client
implements: transport errors retry fast-linear (250 ms, +250 ms per attempt,
cap 16 s); HTTP errors retry exponentially from 5 s (cap 320 s); HTTP 420
rate limiting retries exponentially from a full minute.
"""

from __future__ import annotations

import json
from typing import Callable, Iterator

from .. import config as _config
from ..features.featurizer import Status
from ..utils import get_logger
from .httpstream import (
    RateLimitedError,
    RecvClock,
    StreamHTTPError,
    open_chunks,
    open_stream,
)
from .oauth1 import authorization_header
from .sources import BlockParserMixin, Source

log = get_logger("streaming.twitter")

STREAM_URL = "https://stream.twitter.com/1.1/statuses/sample.json"

OAUTH_KEYS = (
    "twitter4j.oauth.consumerKey",
    "twitter4j.oauth.consumerSecret",
    "twitter4j.oauth.accessToken",
    "twitter4j.oauth.accessTokenSecret",
)


class TwitterSource(Source):
    """Supervised live-stream receiver. ``connect_fn()`` must return an
    iterator of raw JSON lines; the default implementation opens the sample
    stream with the configured credentials."""

    name = "twitter"

    def __init__(
        self,
        credentials: dict[str, str],
        connect_fn: Callable[[], Iterator[str]] | None = None,
        url: str = STREAM_URL,
        **kw,
    ):
        # a live receiver retries indefinitely (Twitter4j semantics): the
        # backoff ladder, not a restart cap, is the pressure valve — the
        # generic max_restarts=3 would kill the stream on a 2s network blip
        # (three consecutive failed connects emit nothing, so the
        # healthy-production reset never fires)
        kw.setdefault("max_restarts", 1_000_000)
        super().__init__(**kw)
        self.credentials = credentials
        self.url = url
        self._connect_fn = connect_fn
        # set by a source that traces its socket reads (BlockTwitterSource)
        self._recv_clock = None

    @classmethod
    def from_properties(cls, **kw) -> "TwitterSource":
        """Build from the twitter4j.oauth.* property table (the reference's
        system-property contract)."""
        creds = {k: _config.get_property(k, "") for k in OAUTH_KEYS}
        missing = [k for k, v in creds.items() if not v]
        if missing:
            raise SystemExit(
                "Twitter credentials missing: "
                + ", ".join(missing)
                + " — pass --consumerKey/--consumerSecret/--accessToken/"
                "--accessTokenSecret or set them in application.conf"
            )
        # twitter4j's own endpoint-override property, honored here so the
        # full CLI path can be driven against a local v1.1-protocol server
        kw.setdefault(
            "url", _config.get_property("twitter4j.streamBaseURL", STREAM_URL)
        )
        return cls(creds, **kw)

    def _connect(self) -> Iterator[str]:
        if self._connect_fn is not None:
            return self._connect_fn()
        return self._open(open_stream)

    def _open(self, opener) -> Iterator:
        """The signed connection to ``url`` through ``opener``
        (``httpstream.open_stream``'s lines or ``open_chunks``' bytes)."""
        auth = authorization_header(
            "GET",
            self.url,
            consumer_key=self.credentials.get("twitter4j.oauth.consumerKey", ""),
            consumer_secret=self.credentials.get(
                "twitter4j.oauth.consumerSecret", ""
            ),
            token=self.credentials.get("twitter4j.oauth.accessToken", ""),
            token_secret=self.credentials.get(
                "twitter4j.oauth.accessTokenSecret", ""
            ),
        )
        # 90s read timeout: the stream keep-alives every ~30s, so a silent
        # socket for 90s is a stall and must raise into the supervisor
        return opener(self.url, headers={"Authorization": auth},
                      recv_clock=self._recv_clock)

    def _backoff(self, exc: Exception, restarts: int) -> float:
        """Twitter streaming reconnect rules (what Twitter4j implements for
        the reference): 420 → exponential from 60 s; other HTTP errors →
        exponential from 5 s capped 320 s; transport errors → linear 250 ms
        steps capped 16 s."""
        n = min(restarts - 1, 16)
        if isinstance(exc, RateLimitedError):
            return min(60.0 * (2**n), 960.0)
        if isinstance(exc, StreamHTTPError):
            return min(5.0 * (2**n), 320.0)
        return min(0.25 * restarts, 16.0)

    def produce(self) -> Iterator[Status]:
        for line in self._connect():
            line = line.strip()
            if not line:
                continue  # keep-alive newline
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                log.debug("skipping non-JSON stream line")
                continue
            if "text" not in obj:
                continue  # delete/limit notices
            yield Status.from_json(obj)
        if self._connect_fn is None:
            # a live stream never ends on purpose: a server-side close is a
            # disconnect, and the supervisor must reconnect (Twitter4j does
            # the same). Injected test streams DO end meaningfully.
            raise ConnectionError("stream ended by server; reconnecting")


class BlockTwitterSource(BlockParserMixin, TwitterSource):
    """The live stream through the NATIVE block parser (r5 — live
    ``--ingest block``): the response's byte CHUNKS, as the socket
    delivered them (``httpstream.open_chunks``), accumulate into blocks cut
    at their last newline, and each block goes through
    ``native.parse_tweet_block`` (the same C scanner + filter as replay
    block ingest, differential-tested against the Status path), yielding
    columnar ParsedBlocks. No per-line Python and no per-tweet object
    between the socket and the featurizer: C splits the lines, takes
    ``\\r\\n`` or ``\\n`` endings and skips blank keep-alives.

    A line that is not valid UTF-8 is the C parser's to judge, as in replay
    block ingest: skipped and counted (``ingest.rows_dropped_parse``), where
    object ingest (``TwitterSource``) decodes it with U+FFFD and keeps it.

    Flush policy: a block parses when the buffer reaches ``block_bytes``
    OR the first stream activity (a chunk, be it one keep-alive) at least
    ``flush_seconds`` after the first byte buffered for it, and holds a
    whole line. The clock is checked when the blocking chunk iterator
    yields, so on a QUIET stream the real latency bound is the protocol's
    ~30 s keep-alive cadence, not ``flush_seconds`` — acceptable for this
    source's regimes (the real sample stream runs 50–100 tweets/s and
    measurement streams far faster; a latency-critical quiet stream should
    keep object ingest).

    An injected ``connect_fn`` may yield ``bytes`` chunks, or ``str`` lines
    without terminators (each becomes one chunk)."""

    name = "twitter-block"

    def __init__(
        self,
        credentials: "dict[str, str]",
        num_retweet_begin: int = 100,
        num_retweet_end: int = 1000,
        block_bytes: int = 1 << 18,
        flush_seconds: float = 0.5,
        wire: bool = False,
        **kw,
    ):
        super().__init__(credentials, **kw)
        self.begin = num_retweet_begin
        self.end = num_retweet_end
        self.block_bytes = block_bytes
        self.flush_seconds = flush_seconds
        # zero-copy wire emitter (BlockParserMixin) — same opt-in as the
        # replay block source
        self.wire = wire

    @classmethod
    def from_properties(cls, **kw) -> "BlockTwitterSource":
        src = TwitterSource.from_properties()
        kw.setdefault("url", src.url)
        return cls(src.credentials, **kw)

    def _parse_block(self, data: bytes):
        """bytes → merged ParsedBlock | None (the shared C-parser stage
        with its Python ground-truth fallback, sources.BlockParserMixin)."""
        from ..features.blocks import merge_blocks

        blocks = self.parse_buffer(data)
        if not blocks:
            return None
        merged = merge_blocks(blocks)
        return merged if merged.rows else None

    def _chunks(self) -> "Iterator[bytes]":
        if self._connect_fn is None:
            return self._open(open_chunks)
        return (
            c if isinstance(c, bytes) else c.encode("utf-8") + b"\n"
            for c in self._connect_fn()
        )

    def produce(self) -> "Iterator":
        import time as _time

        from ..telemetry import trace as _trace

        tr = _trace.get()
        if tr.enabled:
            self._recv_clock = RecvClock()
        buf: list[bytes] = []  # this block's chunks, the carried tail first
        nbytes = 0
        cut = None  # (index into buf, end) of the newest newline buffered
        first_t = 0.0
        loop_t0 = _time.perf_counter()  # where this block's chunk loop began
        for chunk in self._chunks():
            now = _time.monotonic()
            if not nbytes:
                first_t = now
            nl = chunk.rfind(b"\n")
            if nl >= 0:
                cut = (len(buf), nl + 1)
            buf.append(chunk)
            nbytes += len(chunk)
            if cut is not None and (
                nbytes >= self.block_bytes
                or now - first_t >= self.flush_seconds
            ):
                i, end = cut
                data = b"".join(buf[:i] + [buf[i][:end]])
                if tr.enabled:
                    self._trace_chunk_loop(tr, loop_t0, data, i + 1)
                # the unterminated tail opens the next block, now
                tail = buf[i][end:]
                buf = ([tail] if tail else []) + buf[i + 1:]
                nbytes, cut, first_t = nbytes - len(data), None, now
                block = self._parse_block(data)
                if block is not None:
                    yield block
                loop_t0 = _time.perf_counter()  # not the time at ``yield``
        if nbytes:
            # the stream's end: what is left, an unterminated line too
            block = self._parse_block(b"".join(buf))
            if block is not None:
                yield block
        if self._connect_fn is None:
            raise ConnectionError("stream ended by server; reconnecting")

    def _trace_chunk_loop(self, tr, t0: float, data: bytes,
                          chunks: int) -> None:
        """One ``source_lines`` span per parsed block: this thread's chunk
        loop (``open_chunks``' framing and ``produce``'s buffering, cut
        and join) from the block's first chunk to the call of
        ``_parse_block`` — never the parse, never the time suspended while
        the consumer takes the block — and inside it one ``source_recv``
        span: the part spent in socket reads. Their difference is the
        loop's own Python (PERF.md §3). ``chunks`` says the chunk path
        ran; ``lines`` is counted after the span's end is read."""
        import time as _time

        dur = _time.perf_counter() - t0
        tr.complete("source_lines", t0, dur, lines=data.count(b"\n"),
                    bytes=len(data), chunks=chunks)
        recv_s, recv_bytes = self._recv_clock.take()
        tr.complete("source_recv", t0, recv_s, bytes=recv_bytes)
