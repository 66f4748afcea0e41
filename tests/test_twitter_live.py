"""Live-Twitter protocol path, exercised for real against a LOCAL server.

Covers what the reference delegates to Twitter4j (TwitterUtils.createStream,
LinearRegression.scala:44): OAuth1 HMAC-SHA1 signing (pinned by published
external test vectors), the chunked streaming HTTP client, the v1.1
delimited-JSON stream protocol (keep-alives, disconnects, HTTP 420), and the
Twitter reconnect/backoff policy. No egress: the server is in-process
http.server speaking real HTTP over loopback.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import unquote

import pytest

from twtml_tpu.streaming import oauth1
from twtml_tpu.streaming.faults import FaultInjectingSource
from twtml_tpu.streaming.httpstream import (
    RateLimitedError,
    StreamHTTPError,
    open_stream,
)
from twtml_tpu.streaming.twitter import OAUTH_KEYS, TwitterSource

# ---------------------------------------------------------------------------
# OAuth 1.0a signing — external published vectors


def test_rfc5849_example_signature():
    """RFC 5849 §1.2 temporary-credentials request (no token secret)."""
    params = [
        ("oauth_consumer_key", "dpf43f3p2l4k3l03"),
        ("oauth_signature_method", "HMAC-SHA1"),
        ("oauth_timestamp", "137131200"),
        ("oauth_nonce", "wIjqoS"),
        ("oauth_callback", "http://printer.example.com/ready"),
    ]
    sig = oauth1.sign(
        "POST", "https://photos.example.net/initiate", params,
        consumer_secret="kd94hf93k423kf44", token_secret="",
    )
    assert sig == "74KNZJeDHnMBp0EMJ9ZHt/XKycU="


def test_twitter_docs_signature_vector():
    """The worked example from Twitter's 'Creating a signature' developer
    doc (api.twitter.com/1.1/statuses/update.json)."""
    params = [
        ("status", "Hello Ladies + Gentlemen, a signed OAuth request!"),
        ("include_entities", "true"),
        ("oauth_consumer_key", "xvz1evFS4wEEPTGEFPHBog"),
        ("oauth_nonce", "kYjzVBB8Y0ZFabxSWbWovY3uYSQ2pTgmZeNu2VS4cg"),
        ("oauth_signature_method", "HMAC-SHA1"),
        ("oauth_timestamp", "1318622958"),
        ("oauth_token", "370773112-GmHxMAgYyLbNEtIKZeRNFsMKPR9EyMZeS9weJAEb"),
        ("oauth_version", "1.0"),
    ]
    sig = oauth1.sign(
        "POST", "https://api.twitter.com/1.1/statuses/update.json", params,
        consumer_secret="kAcSOqF21Fu85e7zjz7ZN2U4ZRhfV3WpwPAoE3Z7kBw",
        token_secret="LswwdoUaIvS8ltyTt5jkRh4J50vUPVVHtR2YPi5kE",
    )
    assert sig == "hCtSmYh+iHYCEqBWrE7C7hYmtUk="


def test_percent_encoding_rfc3986():
    assert oauth1.percent_encode("Ladies + Gentlemen") == "Ladies%20%2B%20Gentlemen"
    assert oauth1.percent_encode("safe-chars_are.kept~") == "safe-chars_are.kept~"
    assert oauth1.percent_encode("☃") == "%E2%98%83"  # UTF-8 bytes, uppercase hex


def test_authorization_header_query_params_signed_not_emitted():
    hdr = oauth1.authorization_header(
        "GET", "http://example.com/stream.json?delimited=length&x=a%20b",
        consumer_key="ck", consumer_secret="cs", token="tk", token_secret="ts",
        nonce="fixednonce", timestamp=1700000000,
    )
    assert hdr.startswith("OAuth ")
    assert "delimited" not in hdr  # query params signed but not in header
    fields = dict(
        p.split("=", 1) for p in hdr[len("OAuth ") :].split(", ")
    )
    assert fields["oauth_consumer_key"] == '"ck"'
    assert fields["oauth_signature_method"] == '"HMAC-SHA1"'
    # signature must cover the DECODED query values re-encoded once
    expected = oauth1.sign(
        "GET", "http://example.com/stream.json?delimited=length&x=a%20b",
        [
            ("oauth_consumer_key", "ck"),
            ("oauth_nonce", "fixednonce"),
            ("oauth_signature_method", "HMAC-SHA1"),
            ("oauth_timestamp", "1700000000"),
            ("oauth_token", "tk"),
            ("oauth_version", "1.0"),
            ("delimited", "length"),
            ("x", "a b"),
        ],
        "cs", "ts",
    )
    assert unquote(fields["oauth_signature"].strip('"')) == expected


# ---------------------------------------------------------------------------
# Local v1.1-protocol stream server

TWEETS = [
    json.dumps({
        "text": f"RT @u: tweet {i}",
        "retweeted_status": {
            "text": f"tweet {i}",
            "retweet_count": 100 + i,
            "user": {"followers_count": 10 * i},
        },
    })
    for i in range(40)
]


class StreamHandler(BaseHTTPRequestHandler):
    """Speaks the v1.1 stream shape: 200 + chunked delimited JSON with
    keep-alive blank lines, chunk boundaries deliberately misaligned with
    line boundaries. Behavior per path:

    - /stream           : all tweets, clean end (0-chunk terminator)
    - /drop             : half the tweets, then a hard disconnect (no
                          terminator) — next request serves the rest
    - /calm             : HTTP 420
    - /forbidden        : HTTP 401
    - /soak             : 10 tweets per connection, forever
    """

    protocol_version = "HTTP/1.1"
    server_state: dict = {}

    def log_message(self, *a):  # quiet
        pass

    def _start_stream(self):
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.send_header("Content-Type", "application/json")
        self.end_headers()

    def _send_raw(self, data: bytes, chunk: int = 37):
        """Write as chunked frames of ``chunk`` bytes — misaligned with the
        JSON lines so the client must reassemble across chunks."""
        for i in range(0, len(data), chunk):
            piece = data[i : i + chunk]
            self.wfile.write(f"{len(piece):x}\r\n".encode() + piece + b"\r\n")
        self.wfile.flush()

    def do_GET(self):
        self.server_state.setdefault("auth_headers", []).append(
            self.headers.get("Authorization", "")
        )
        if self.path == "/calm":
            self.send_response(420, "Enhance Your Calm")
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        if self.path == "/forbidden":
            self.send_response(401, "Unauthorized")
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        self._start_stream()
        if self.path == "/stream":
            body = "\r\n".join(TWEETS[:20]) + "\r\n\r\n\r\n"  # 2 keep-alives
            self._send_raw(body.encode())
            self.wfile.write(b"0\r\n\r\n")  # clean terminator
        elif self.path == "/drop":
            n = self.server_state.setdefault("drop_conns", 0)
            self.server_state["drop_conns"] = n + 1
            if n == 0:
                self._send_raw(("\r\n".join(TWEETS[:10]) + "\r\n").encode())
                # hard disconnect: no terminating chunk; abort the socket
                self.connection.close()
                raise ConnectionAbortedError  # stop handler, keep server
            self._send_raw(("\r\n".join(TWEETS[10:20]) + "\r\n").encode())
            self.wfile.write(b"0\r\n\r\n")
        elif self.path == "/soak":
            n = self.server_state.setdefault("soak_conns", 0)
            self.server_state["soak_conns"] = n + 1
            lo = (n * 10) % len(TWEETS)
            self._send_raw(("\r\n".join(TWEETS[lo : lo + 10]) + "\r\n").encode())
            self.wfile.write(b"0\r\n\r\n")
        self.close_connection = True


@pytest.fixture()
def stream_server():
    StreamHandler.server_state = {}
    server = ThreadingHTTPServer(("127.0.0.1", 0), StreamHandler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        yield f"http://127.0.0.1:{server.server_address[1]}"
    finally:
        server.shutdown()
        server.server_close()


CREDS = {k: "secret-" + k.rsplit(".", 1)[1] for k in OAUTH_KEYS}


def _collect(src: TwitterSource, expect: int, timeout: float = 15.0):
    got = []
    src.start(got.append)
    deadline = time.time() + timeout
    while len(got) < expect and not src.exhausted and time.time() < deadline:
        time.sleep(0.01)
    src.stop()
    return got


def test_real_http_stream_end_to_end(stream_server):
    """Full native path: OAuth header → HTTP request → chunked decode →
    line reassembly → Status parse. No connect_fn anywhere."""
    src = TwitterSource(CREDS, url=stream_server + "/stream")
    got = _collect(src, 20)
    assert len(got) == 20
    assert [s.retweeted_status.retweet_count for s in got] == list(range(100, 120))
    # the server saw a well-formed signed Authorization header
    auth = StreamHandler.server_state["auth_headers"][0]
    assert auth.startswith("OAuth ")
    for field in ("oauth_consumer_key", "oauth_nonce", "oauth_signature",
                  "oauth_timestamp", "oauth_token", "oauth_version"):
        assert field in auth


def test_server_side_signature_verifies(stream_server):
    """Recompute the signature server-side from the received header — proves
    the header's params and the signature agree end-to-end (the signing
    primitive itself is pinned by the external vectors above)."""
    url = stream_server + "/stream"
    src = TwitterSource(CREDS, url=url)
    _collect(src, 20)
    auth = StreamHandler.server_state["auth_headers"][0]
    fields = {
        k: unquote(v.strip('"'))
        for k, v in (p.split("=", 1) for p in auth[len("OAuth ") :].split(", "))
    }
    claimed = fields.pop("oauth_signature")
    recomputed = oauth1.sign(
        "GET", url, sorted(fields.items()),
        consumer_secret=CREDS["twitter4j.oauth.consumerSecret"],
        token_secret=CREDS["twitter4j.oauth.accessTokenSecret"],
    )
    assert claimed == recomputed


def test_disconnect_reconnects_and_resumes(stream_server):
    """Mid-stream hard disconnect → supervisor restarts with the transport
    backoff → second connection serves the remainder."""
    src = TwitterSource(CREDS, url=stream_server + "/drop")
    got = _collect(src, 20)
    assert StreamHandler.server_state["drop_conns"] == 2
    counts = [s.retweeted_status.retweet_count for s in got]
    assert counts == list(range(100, 120))


def test_http_420_raises_rate_limited(stream_server):
    with pytest.raises(RateLimitedError) as exc:
        list(open_stream(stream_server + "/calm"))
    assert exc.value.status == 420


def test_http_401_raises_stream_error(stream_server):
    with pytest.raises(StreamHTTPError) as exc:
        list(open_stream(stream_server + "/forbidden"))
    assert exc.value.status == 401
    assert not isinstance(exc.value, RateLimitedError)


def test_backoff_policy_matches_twitter_rules():
    src = TwitterSource(CREDS)
    # 420: exponential from 60s
    assert src._backoff(RateLimitedError(420), 1) == 60.0
    assert src._backoff(RateLimitedError(420), 2) == 120.0
    # other HTTP: exponential from 5s, cap 320
    assert src._backoff(StreamHTTPError(503), 1) == 5.0
    assert src._backoff(StreamHTTPError(503), 2) == 10.0
    assert src._backoff(StreamHTTPError(503), 10) == 320.0
    # transport: linear 250ms, cap 16s
    assert src._backoff(ConnectionError(), 1) == 0.25
    assert src._backoff(ConnectionError(), 4) == 1.0
    assert src._backoff(ConnectionError(), 100) == 16.0


def test_fault_injected_live_stream_soak(stream_server):
    """VERDICT r1 done-criterion: fault-injected fake-stream soak. The
    injector crashes the receiver every 17 tweets on top of the server
    ending every connection after 10 — both recovery paths interleave."""
    inner = TwitterSource(CREDS, url=stream_server + "/soak")
    src = FaultInjectingSource(inner, crash_every=17, max_crashes=3)
    got = _collect(src, 100, timeout=30.0)
    assert len(got) >= 100
    assert src.crashes == 3
    assert StreamHandler.server_state["soak_conns"] >= 10


def test_keep_alive_lines_skipped(stream_server):
    """/stream embeds blank keep-alive lines; none become Status objects."""
    src = TwitterSource(CREDS, url=stream_server + "/stream")
    got = _collect(src, 20)
    assert all(s.text for s in got)


# a body with every shape the reassembly must keep: CRLF and bare-LF lines,
# blank keep-alives, a byte that is not UTF-8, a line longer than a chunk,
# and a last line with no terminator
RAW_BODY = (
    b'{"a": 1}\r\n\r\n{"b": "\xc3\xa9"}\n{"c": "\xff"}\r\n\r\n\r\n'
    + b'{"d": "' + b"x" * 300 + b'"}\r\n{"tail": true}'
)


def _reference_lines(body: bytes) -> list[str]:
    parts = body.split(b"\n")
    last = parts.pop()
    out = [p.rstrip(b"\r").decode("utf-8", errors="replace") for p in parts]
    if last.strip():
        out.append(last.decode("utf-8", errors="replace"))
    return out


@pytest.mark.parametrize("chunk", [1, 2, 7, 37, 65536])
def test_open_stream_reassembles_lines_across_any_chunking(chunk):
    """``open_stream`` yields the body's lines whatever the chunk framing:
    a ``\\r\\n`` cut between two chunks, many lines in one chunk, one line
    over many chunks, keep-alives kept, the unterminated tail last."""

    class Raw(StreamHandler):
        def do_GET(self):
            self._start_stream()
            self._send_raw(RAW_BODY, chunk=chunk)
            self.wfile.write(b"0\r\n\r\n")
            self.close_connection = True

    server = ThreadingHTTPServer(("127.0.0.1", 0), Raw)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}/raw"
        assert list(open_stream(url, timeout=10.0)) == _reference_lines(
            RAW_BODY
        )
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# r5: multi-host live intake (id-residue sharding) + live block ingest


def _corpus_lines(n=40):
    from twtml_tpu.streaming.sources import SyntheticSource

    lines = []
    for i, s in enumerate(
        SyntheticSource(total=n, seed=13, base_ms=1785320000000).produce()
    ):
        d = s.to_json()
        d["id"] = 1000 + i  # snowflake ids — the shard key
        lines.append(json.dumps(d))
    return lines


def test_id_sharded_live_intake_disjoint_and_complete():
    """VERDICT r4 #8: every host opens its own connection to the SAME
    stream and keeps rows with id ≡ processId (mod N) — shard-disjoint,
    union-complete, through the real protocol path (N concurrent
    connections against the local v1.1 server)."""
    from localstream import LocalV11StreamServer
    from twtml_tpu.streaming.sources import IdShardedSource
    from twtml_tpu.streaming.twitter import TwitterSource

    lines = _corpus_lines(40)
    all_ids = set(range(1000, 1040))
    with LocalV11StreamServer(lines) as server:
        shard_ids = []
        for pid in range(2):
            src = IdShardedSource(
                TwitterSource(CREDS, url=server.url), pid, 2
            )
            got = _collect(src, 20)
            assert len(got) >= 20
            shard_ids.append({s.id for s in got})
    assert shard_ids[0] & shard_ids[1] == set()
    assert shard_ids[0] | shard_ids[1] == all_ids
    for pid in (0, 1):
        assert all(i % 2 == pid for i in shard_ids[pid])


def test_id_shard_wrapper_keeps_live_backoff_policy():
    from twtml_tpu.streaming.httpstream import RateLimitedError
    from twtml_tpu.streaming.sources import IdShardedSource
    from twtml_tpu.streaming.twitter import TwitterSource

    inner = TwitterSource(CREDS)
    shard = IdShardedSource(inner, 0, 2)
    assert shard.max_restarts == inner.max_restarts
    assert shard._backoff(RateLimitedError(420, ""), 1) == 60.0


def test_block_twitter_source_matches_object_path():
    """r5 live --ingest block: raw stream lines → native C parser →
    ParsedBlocks, byte-identical featurized batches vs the per-line
    json.loads Status path (config #2's host bottleneck deleted)."""
    import numpy as np

    from localstream import LocalV11StreamServer
    from twtml_tpu.features.blocks import merge_blocks, slice_block
    from twtml_tpu.features.featurizer import Featurizer, Status
    from twtml_tpu.streaming.twitter import BlockTwitterSource

    lines = _corpus_lines(40)
    statuses = [Status.from_json(json.loads(ln)) for ln in lines]
    feat = Featurizer(now_ms=1785320000000)

    blocks = []
    with LocalV11StreamServer(lines) as server:
        src = BlockTwitterSource(
            CREDS, url=server.url, flush_seconds=0.05,
        )
        src.start(blocks.append)
        deadline = time.time() + 20.0
        while (
            sum(b.rows for b in blocks) < 40 and time.time() < deadline
        ):
            time.sleep(0.01)
        src.stop()
    merged = merge_blocks(list(blocks))
    assert merged.rows >= 40
    first = slice_block(merged, 0, 40)

    obj = feat.featurize_batch_units(statuses, row_bucket=64, unit_bucket=128)
    blk = feat.featurize_parsed_block(first, row_bucket=64, unit_bucket=128)
    np.testing.assert_array_equal(obj.units, blk.units)
    np.testing.assert_array_equal(obj.length, blk.length)
    np.testing.assert_allclose(obj.numeric, blk.numeric, rtol=1e-6)
    np.testing.assert_array_equal(obj.label, blk.label)
    np.testing.assert_array_equal(obj.mask, blk.mask)


# ---------------------------------------------------------------------------
# PR 33: the live block source builds its blocks from the response's byte
# chunks; C splits the lines


def _rt_line(i: int, text: str) -> bytes:
    return json.dumps({
        "text": "RT @u: " + text,
        "retweeted_status": {
            "text": text, "retweet_count": 100 + i,
            "user": {"followers_count": 7 * i, "friends_count": i},
        },
    }, ensure_ascii=False).encode("utf-8")


def _chunk_corpus() -> "tuple[bytes, list[bytes]]":
    """(the body as the socket would deliver it, its sound lines): CRLF
    and bare-LF endings, blank keep-alives at the head, between lines and
    doubled, a non-ASCII text, a delete notice, a line that is not JSON, a
    retweet whose text holds a byte that is not UTF-8, and a last line
    with no terminator."""
    sound = [_rt_line(i, t) for i, t in enumerate([
        "plain ascii text", "café 中文 \U0001f600 mixed",
        "UPPER lower 123", "tail without a newline",
    ])]
    broken = _rt_line(9, "bad byte X here").replace(b"X", b"\xff")
    body = (
        b"\r\n" + sound[0] + b"\r\n\r\n" + sound[1] + b"\n"
        + b'{"delete": {"status": {"id": 1}}}\r\n' + b"not json\n"
        + broken + b"\r\n\r\n\r\n" + sound[2] + b"\r\n" + sound[3]
    )
    return body, sound


def _block_source(chunks, **kw):
    from twtml_tpu.streaming.twitter import BlockTwitterSource

    return BlockTwitterSource(CREDS, connect_fn=lambda: iter(chunks), **kw)


def _merged(blocks):
    from twtml_tpu.features.blocks import merge_blocks

    return merge_blocks(list(blocks))


def _assert_same_block(got, want):
    import numpy as np

    for g, w in zip(got, want, strict=True):
        np.testing.assert_array_equal(g, w)


def _line_path_block(lines: "list[bytes]", **kw):
    """What the line path built (PR 32's ``produce``): every line decoded,
    stripped, re-encoded and joined with ``\\n``, then parsed as one
    buffer."""
    data = b"".join(
        ln.decode("utf-8", errors="replace").strip().encode("utf-8") + b"\n"
        for ln in lines
    )
    return _merged(_block_source([], **kw).parse_buffer(data))


@pytest.mark.parametrize("wire", [False, True])
def test_chunk_path_equals_line_path_cut_at_every_byte_offset(wire):
    """One corpus cut at EVERY byte offset into two chunks yields the
    ParsedBlocks the line path did, with a block forced after every chunk
    that holds a newline (``block_bytes=1``), so the cut at the last
    newline and the carried tail are both on the path: a line split across
    chunks, ``\\r\\n`` split between them, keep-alives at a chunk's head,
    a chunk with no newline, the unterminated tail at the stream's end.

    THE RULE for a line that is not UTF-8 is the C parser's, as in replay
    block ingest: skipped and counted — the line path had decoded it with
    U+FFFD and kept the row."""
    from twtml_tpu.telemetry import metrics

    body, sound = _chunk_corpus()
    want = _line_path_block(sound, wire=wire)
    assert want.rows == len(sound) == 4
    drops = metrics.get_registry().counter("ingest.rows_dropped_parse")
    for cut in range(len(body) + 1):
        chunks = [c for c in (body[:cut], body[cut:]) if c]
        before = drops.value
        got = _merged(
            _block_source(chunks, block_bytes=1, wire=wire).produce())
        _assert_same_block(got, want)
        # "not json" and the retweet with the bad byte, once each
        assert drops.value - before == 2, cut


@pytest.mark.parametrize("wire", [False, True])
def test_chunk_path_in_64k_chunks_and_default_blocks(wire):
    """The cells' regime: 64 KiB chunks into 256 KiB blocks."""
    body, sound = _chunk_corpus()
    reps = 400
    stream = (body + b"\r\n") * reps
    assert len(stream) > 4 * 65536
    chunks = [stream[i:i + 65536] for i in range(0, len(stream), 65536)]
    blocks = list(_block_source(chunks, wire=wire).produce())
    assert len(blocks) > 1
    got, one = _merged(blocks), _line_path_block(sound, wire=wire)
    assert got.rows == one.rows * reps
    _assert_same_block(got, _merged([one] * reps))


def test_chunk_path_str_lines_of_an_injected_stream_are_one_chunk_each():
    """``connect_fn`` streams of ``str`` lines (no terminators, blank
    keep-alives) go through the same chunk loop."""
    body, sound = _chunk_corpus()
    lines = ["", sound[0].decode(), "", sound[1].decode(), "not json",
             sound[2].decode(), sound[3].decode()]
    got = _merged(_block_source(lines, block_bytes=1).produce())
    _assert_same_block(got, _line_path_block(sound))


def test_chunk_path_time_flush_and_a_chunk_without_newline(monkeypatch):
    """A block is cut when a chunk arrives ``flush_seconds`` after the
    first byte buffered for it — and only if it holds a whole line; what
    follows the last newline waits for the next block."""
    import time as _time

    body, sound = _chunk_corpus()
    half = len(sound[0]) // 2
    chunks = [
        sound[0][:half],          # no newline yet: nothing to cut
        sound[0][half:] + b"\r\n" + sound[1][:10],
        sound[1][10:] + b"\n",
        b"\r\n",                  # a keep-alive is activity too
        sound[2] + b"\n",
    ]
    clock = iter([0.0, 1.0, 1.1, 2.2, 2.3])
    monkeypatch.setattr(_time, "monotonic", lambda: next(clock))
    src = _block_source(chunks, block_bytes=1 << 30, flush_seconds=1.0)
    blocks = list(src.produce())
    # t=1.0: first block (sound[0]); the tail's clock starts at 1.0, so
    # t=1.1 cuts nothing; t=2.2 (the keep-alive) cuts sound[1]; the end
    # of the stream flushes sound[2]
    assert [b.rows for b in blocks] == [1, 1, 1]
    _assert_same_block(_merged(blocks), _line_path_block(sound[:3]))


def test_chunk_path_source_lines_span_carries_lines_bytes_chunks(tmp_path):
    from twtml_tpu.telemetry import trace

    body, sound = _chunk_corpus()
    stream = body + b"\n"
    chunks = [stream[i:i + 100] for i in range(0, len(stream), 100)]
    path = str(tmp_path / "spans.json")
    trace.install(path)
    try:
        rows = sum(b.rows for b in _block_source(
            chunks, block_bytes=len(stream)).produce())
    finally:
        trace.uninstall()
    assert rows == 4
    events = [json.loads(ln.rstrip(",\n")) for ln in open(path)
              if ln.startswith("{")]
    (lines_ev,) = [e for e in events if e["name"] == "source_lines"]
    assert lines_ev["args"] == {
        "lines": stream.count(b"\n"), "bytes": len(stream),
        "chunks": len(chunks),
    }
    (recv_ev,) = [e for e in events if e["name"] == "source_recv"]
    assert recv_ev["ts"] == lines_ev["ts"] and recv_ev["dur"] == 0


def test_open_chunks_delivers_the_body_bytes_untouched(stream_server):
    """``open_chunks`` shares ``open_stream``'s connection, status and
    error handling and yields the body as framed: joined, it is the body."""
    from twtml_tpu.streaming.httpstream import RecvClock, open_chunks

    clock = RecvClock()
    got = list(open_chunks(stream_server + "/stream", recv_clock=clock))
    assert b"".join(got) == ("\r\n".join(TWEETS[:20]) + "\r\n\r\n\r\n").encode()
    assert all(len(c) <= 37 for c in got) and len(got) > 20
    seconds, nbytes = clock.take()
    assert nbytes > len(b"".join(got)) and seconds >= 0
    with pytest.raises(RateLimitedError):
        list(open_chunks(stream_server + "/calm"))
    with pytest.raises(StreamHTTPError):
        list(open_chunks(stream_server + "/forbidden"))
