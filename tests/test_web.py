"""Web-server integration tests (reference: WebTestSuite.scala:10-42 — boot
the real server in-process and round-trip Config/Stats over real HTTP), plus
websocket broadcast/connect-push semantics the reference only exercised
manually via test.html."""

import asyncio
import json

import pytest

from twtml_tpu.telemetry.api_types import Config, Stats
from twtml_tpu.telemetry.web_client import WebClient
from twtml_tpu.web.cache import ApiCache
from twtml_tpu.web.server import Server

HOST = "127.0.0.1"


@pytest.fixture()
def server(tmp_path):
    cache = ApiCache(backup_file=str(tmp_path / "twtml-web.json"))
    srv = Server(port=0, host=HOST, cache=cache)
    srv.start_background()
    # port 0 → discover the bound port
    port = srv._runner.addresses[0][1]
    yield srv, f"http://{HOST}:{port}", cache
    srv.stop()


def test_http_roundtrip_config_stats(server):
    _, url, _ = server
    client = WebClient(url)
    client.config("100", "http://lightninghost", ["101", "102"])
    client.stats(1000, 10, 2000, 15, 25)
    assert client.get_config() == Config(id="100", host="http://lightninghost",
                                         viz=["101", "102"])
    assert client.get_stats() == Stats(count=1000, batch=10, mse=2000,
                                       realStddev=15, predStddev=25)


def test_defaults_before_any_post(server):
    _, url, _ = server
    client = WebClient(url)
    assert client.get_config() == Config()
    assert client.get_stats() == Stats()


def test_unknown_json_is_dropped(server):
    _, url, _ = server
    import urllib.request

    req = urllib.request.Request(
        url + "/api", data=b'{"jsonClass":"Nope"}',
        headers={"content-type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=2) as resp:
        assert json.loads(resp.read())["status"] == "OK"
    client = WebClient(url)
    assert client.get_stats() == Stats()  # cache untouched


def test_static_dashboard_served(server):
    _, url, _ = server
    import urllib.request

    with urllib.request.urlopen(url + "/", timeout=2) as resp:
        body = resp.read().decode()
    assert "twtml-tpu" in body and 'id="mse"' in body
    with urllib.request.urlopen(url + "/js/api.js", timeout=2) as resp:
        assert b"websocketOn" in resp.read()
    with pytest.raises(Exception):
        urllib.request.urlopen(url + "/definitely-missing", timeout=2)


def test_config_persistence_roundtrip(tmp_path):
    backup = str(tmp_path / "twtml-web.json")
    cache = ApiCache(backup_file=backup)
    cache.cache('{"jsonClass":"Config","id":"a","host":"h","viz":["1"]}')
    cache.cache('{"jsonClass":"Stats","count":5,"batch":1,"mse":2,'
                '"realStddev":3,"predStddev":4}')
    # fresh cache restores Config only (ApiCache.scala:27-31,50-56)
    fresh = ApiCache(backup_file=backup)
    fresh.restore()
    assert json.loads(fresh.config())["id"] == "a"
    assert json.loads(fresh.stats())["count"] == 0


def test_websocket_broadcast_and_connect_push(server):
    _, url, _ = server
    ws_url = url.replace("http://", "ws://") + "/api"
    client = WebClient(url)
    client.config("cfg-1", "http://lightning", ["viz-9"])

    async def scenario():
        import aiohttp

        async with aiohttp.ClientSession() as session:
            async with session.ws_connect(ws_url) as ws1, \
                    session.ws_connect(ws_url) as ws2:
                # on-connect push: cached Config to each new socket
                first1 = json.loads((await ws1.receive(timeout=5)).data)
                first2 = json.loads((await ws2.receive(timeout=5)).data)
                assert first1["jsonClass"] == first2["jsonClass"] == "Config"
                assert first1["id"] == "cfg-1"
                # a frame sent by one socket is broadcast to ALL (incl sender)
                payload = {"jsonClass": "Stats", "count": 7, "batch": 7,
                           "mse": 7, "realStddev": 7, "predStddev": 7}
                await ws1.send_str(json.dumps(payload))
                echo1 = json.loads((await ws1.receive(timeout=5)).data)
                echo2 = json.loads((await ws2.receive(timeout=5)).data)
                assert echo1 == echo2 == payload
        # and an HTTP POST is broadcast to websockets too
        return True

    assert asyncio.run(scenario())
    # the WS frame also updated the HTTP-readable cache
    assert client.get_stats().count == 7


def test_series_roundtrip_and_window(server):
    """Additive Series messages: cached in a rolling window, served at
    /api/series for chart backfill, broadcast like everything else."""
    _, url, cache = server
    client = WebClient(url)
    for k in range(3):
        client.series([float(k), k + 0.5], [k + 1.0, k + 1.5], 10.0, 12.0)
    import urllib.request

    with urllib.request.urlopen(url + "/api/series", timeout=2) as resp:
        items = json.loads(resp.read())
    assert len(items) == 3
    assert items[0]["jsonClass"] == "Series"
    assert items[-1]["real"] == [2.0, 2.5]
    assert items[-1]["realStddev"] == 10.0
    # rolling window bounded
    from twtml_tpu.web.cache import SERIES_WINDOW

    for k in range(SERIES_WINDOW + 10):
        client.series([1.0], [1.0], 0.0, 0.0)
    with urllib.request.urlopen(url + "/api/series", timeout=2) as resp:
        assert len(json.loads(resp.read())) == SERIES_WINDOW


def test_metrics_roundtrip_and_default(server):
    """Additive Metrics messages: cached last-value (in-memory, like Stats),
    served at /api/metrics for the dashboard's observability panel."""
    _, url, _ = server
    import urllib.request

    with urllib.request.urlopen(url + "/api/metrics", timeout=2) as resp:
        empty = json.loads(resp.read())
    assert empty["jsonClass"] == "Metrics"
    assert empty["counters"] == {} and empty["health"] == {}

    client = WebClient(url)
    client.metrics(
        {"pipeline.batches": 12, "wire.bytes": 1234567},
        {"fetch.queue_depth": 3, "host.rss_mb": 512.5},
        {"phase": "degraded", "rtt_ms": 412.0, "transitions": 2},
    )
    with urllib.request.urlopen(url + "/api/metrics", timeout=2) as resp:
        got = json.loads(resp.read())
    assert got["counters"]["pipeline.batches"] == 12
    assert got["gauges"]["host.rss_mb"] == 512.5
    assert got["health"]["phase"] == "degraded"


def test_hosts_roundtrip_and_default(server):
    """Additive Hosts messages (the lockstep fleet view): cached last-value
    like Metrics, served at /api/hosts, unknown to legacy caches."""
    _, url, _ = server
    import urllib.request

    with urllib.request.urlopen(url + "/api/hosts", timeout=2) as resp:
        empty = json.loads(resp.read())
    assert empty["jsonClass"] == "Hosts"
    assert empty["hosts"] == [] and empty["straggler"] == -1

    client = WebClient(url)
    client.hosts(
        [{"host": 0, "tick_prep_ms": 12.0}, {"host": 1, "tick_prep_ms": 140.0}],
        straggler=1, stage="upload", skew_ms=128.0,
    )
    with urllib.request.urlopen(url + "/api/hosts", timeout=2) as resp:
        got = json.loads(resp.read())
    assert got["straggler"] == 1 and got["stage"] == "upload"
    assert got["skewMs"] == 128.0
    assert got["hosts"][1]["tick_prep_ms"] == 140.0


def test_metrics_roundtrip_carries_derived_histograms(server):
    """r8: the Metrics message's additive ``histograms`` field (derived
    p50/p95/p99) round-trips; old payloads without it still decode."""
    _, url, _ = server
    import urllib.request

    client = WebClient(url)
    client.metrics(
        {"pipeline.batches": 3}, {}, {"phase": "healthy"},
        histograms={"fetch.latency_s": {
            "count": 12, "mean": 0.07, "p50": 0.064, "p95": 0.128,
            "p99": 0.256,
        }},
    )
    with urllib.request.urlopen(url + "/api/metrics", timeout=2) as resp:
        got = json.loads(resp.read())
    assert got["histograms"]["fetch.latency_s"]["p95"] == 0.128
    # a legacy Metrics payload (no histograms key) still caches cleanly
    from twtml_tpu.telemetry.api_types import decode

    legacy = decode('{"jsonClass":"Metrics","counters":{},"gauges":{},'
                    '"health":{}}')
    assert legacy.histograms == {}


def test_serving_roundtrip_and_default(server):
    """Additive Serving messages (the serve-plane view): cached last-value
    like Metrics, served at /api/serving, unknown to legacy caches; the
    predict front door answers 503 when no plane is attached."""
    _, url, _ = server
    import urllib.error
    import urllib.request

    with urllib.request.urlopen(url + "/api/serving", timeout=2) as resp:
        empty = json.loads(resp.read())
    assert empty["jsonClass"] == "Serving"
    assert empty["snapshotStep"] == -1 and empty["tenants"] == []

    client = WebClient(url)
    client.serving({
        "qps": 512.5, "rowsPerSec": 8200.0, "p50Ms": 8.2, "p95Ms": 61.0,
        "p99Ms": 84.0, "snapshotStep": 640, "level": "warn",
        "requests": 10000, "rows": 160000, "errors": 2,
        "tenants": [{"tenant": 0, "rows": 90000},
                    {"tenant": 1, "rows": 70000}],
    })
    with urllib.request.urlopen(url + "/api/serving", timeout=2) as resp:
        got = json.loads(resp.read())
    assert got["qps"] == 512.5 and got["p99Ms"] == 84.0
    assert got["snapshotStep"] == 640 and got["level"] == "warn"
    assert got["tenants"][1]["rows"] == 70000

    # POST /api/predict without an attached plane: 503 with a JSON error
    req = urllib.request.Request(
        url + "/api/predict", data=b'{"rows": [{"text": "x"}]}',
        headers={"content-type": "application/json"}, method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(req, timeout=2)
    assert exc_info.value.code == 503
    assert "serving" in json.loads(exc_info.value.read())["error"]


def test_history_roundtrip_and_default(server):
    """Additive History messages (the telemetry-historian view): cached
    last-value like Metrics, served at /api/history, unknown fields
    dropped at the client edge (additive-wire discipline)."""
    _, url, _ = server
    import urllib.request

    with urllib.request.urlopen(url + "/api/history", timeout=2) as resp:
        empty = json.loads(resp.read())
    assert empty["jsonClass"] == "History"
    assert empty["samples"] == 0 and empty["rss"] == []

    client = WebClient(url)
    client.history({
        "samples": 12, "runId": 3, "phase": "healthy", "rssMb": 300.5,
        "rssSlopeMbPerMin": 0.4, "rttMs": 71.0, "diskMb": 1.2,
        "regressions": 1, "rss": [299.0, 300.5], "rtt": [70.0, 71.0],
        "stageMs": [4.2, 4.4], "someFutureField": "dropped",
    })
    with urllib.request.urlopen(url + "/api/history", timeout=2) as resp:
        got = json.loads(resp.read())
    assert got["samples"] == 12 and got["rssMb"] == 300.5
    assert got["rss"] == [299.0, 300.5] and got["regressions"] == 1
    assert "someFutureField" not in got


def test_http_post_broadcasts_to_websockets(server):
    _, url, _ = server
    ws_url = url.replace("http://", "ws://") + "/api"

    async def scenario():
        import aiohttp

        async with aiohttp.ClientSession() as session:
            async with session.ws_connect(ws_url) as ws:
                await ws.receive(timeout=5)  # connect push
                WebClient(url).stats(11, 2, 3, 4, 5)
                frame = json.loads((await ws.receive(timeout=5)).data)
                assert frame["jsonClass"] == "Stats" and frame["count"] == 11

    asyncio.run(scenario())


def test_static_handler_rejects_traversal_and_absolute_paths(server):
    """GET //etc/passwd must never serve outside the assets root: pathlib
    joinpath with an absolute segment DISCARDS the base path entirely
    (and 'D:' does the same on Windows; control chars must 404, not 500)."""
    import urllib.error
    import urllib.request

    _, base, _ = server
    ok = urllib.request.urlopen(f"{base}/js/api.js", timeout=3)
    assert ok.status == 200
    for evil in (
        "//etc/passwd", "//root/.ssh/id_rsa", "/a//b", "/a/./b",
        "/D:/secrets.txt", "/js/%00x",
    ):
        try:
            resp = urllib.request.urlopen(base + evil, timeout=3)
            body = resp.read()
            assert b"root:" not in body, f"{evil} leaked a system file"
            raise AssertionError(f"{evil} unexpectedly served ({resp.status})")
        except urllib.error.HTTPError as exc:
            assert exc.code == 404, f"{evil} -> {exc.code}, want 404"


# -- the client's own exchange (PR 41): one buffer out, one buffer in, on a
# socket it keeps while the reply allows it ----------------------------------

import socket
import threading
import time

from twtml_tpu.telemetry import metrics as _metrics
from twtml_tpu.telemetry.web_client import WebStatusError

KEEP = (b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
        b"Content-Length: %d\r\n\r\n%s")


def _read_request(conn):
    """One request off ``conn``: its body, or None once the peer closed."""
    buf = b""
    while b"\r\n\r\n" not in buf:
        data = conn.recv(65536)
        if not data:
            return None
        buf += data
    head, _, body = buf.partition(b"\r\n\r\n")
    length = 0
    for line in head.lower().split(b"\r\n")[1:]:
        if line.startswith(b"content-length:"):
            length = int(line.split(b":")[1])
    while len(body) < length:
        body += conn.recv(65536)
    return body


class StubServer:
    """A socket server on a thread a connection. ``serve(stub, conn)`` is
    the whole conversation on one accepted connection; the stub counts the
    connections it accepted and keeps the request bodies it read."""

    def __init__(self, serve):
        self._serve = serve
        self.connections = 0
        self.bodies = []
        self.done = threading.Event()  # set by stop(): handlers may wait on it
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((HOST, 0))
        self._srv.listen(16)
        self.url = f"http://{HOST}:{self._srv.getsockname()[1]}"
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            self.connections += 1
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True).start()

    def _handle(self, conn):
        with conn:
            conn.settimeout(5.0)
            try:
                self._serve(self, conn)
            except OSError:
                pass

    def read(self, conn):
        body = _read_request(conn)
        if body is not None:
            self.bodies.append(body)
        return body

    def stop(self):
        self.done.set()
        self._srv.close()


def _serve_like_sink(stub, conn):
    """benchmark/sink.py's answer: ``Connection: close``, then the close."""
    from benchmark.sink import REPLY

    if stub.read(conn) is not None:
        conn.sendall(REPLY)


def _serve_keeping(stub, conn, reply=lambda body: b"{}"):
    while (body := stub.read(conn)) is not None:
        answer = reply(body)
        conn.sendall(KEEP % (len(answer), answer))


@pytest.fixture()
def stub():
    made = []

    def make(serve):
        made.append(StubServer(serve))
        return made[-1]

    yield make
    for s in made:
        s.stop()


@pytest.fixture(scope="module")
def tls(tmp_path_factory):
    """A self-signed certificate for ``localhost``: ``wrap(serve)`` is
    ``serve`` behind the server's handshake, ``trusting`` the client's
    context, ``url(stub)`` the stub's address under ``https``."""
    import ssl
    import subprocess
    import types

    where = tmp_path_factory.mktemp("tls")
    cert, key = str(where / "cert.pem"), str(where / "key.pem")
    try:
        made = subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", key, "-out", cert, "-days", "2", "-subj",
             "/CN=localhost", "-addext", "subjectAltName=DNS:localhost"],
            capture_output=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired) as exc:
        pytest.skip(f"no openssl to make a certificate with: {exc}")
    if made.returncode:
        pytest.skip("openssl made no certificate")
    context = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    context.load_cert_chain(cert, key)

    def wrap(serve):
        def behind_tls(stub, conn):
            with context.wrap_socket(conn, server_side=True) as secured:
                serve(stub, secured)
        return behind_tls

    return types.SimpleNamespace(
        wrap=wrap, trusting=ssl.create_default_context(cafile=cert),
        url=lambda srv: srv.url.replace("http://127.0.0.1",
                                        "https://localhost"))


def _https_client(tls, srv, monkeypatch, **kwargs):
    import ssl

    monkeypatch.setattr(ssl, "create_default_context", lambda: tls.trusting)
    return WebClient(tls.url(srv), **kwargs)


def _counts():
    reg = _metrics.get_registry()
    return (reg.counter("web.requests").snapshot(),
            reg.counter("web.connects").snapshot())


@pytest.mark.parametrize("keeps", [True, False],
                         ids=["web_server_keeps", "sink_stub_closes"])
def test_posts_answered_and_connections_counted(keeps, server, stub):
    """N POSTs of Stats + Series, every one answered: ONE connect against
    the dashboard (it keeps connections), N against a server that closes
    each like the benchmark's sink."""
    url = server[1] if keeps else stub(_serve_like_sink).url
    posts0, connects0 = _counts()
    client = WebClient(url)
    rounds = 6
    for k in range(rounds):
        client.stats(100 + k, 10, 2000, 15, 25)
        client.series([float(k)] * 400, [k + 0.5] * 400, 10.0, 12.0)
    n = 2 * rounds
    assert (client.requests, client.connects) == (n, 1 if keeps else n)
    posts1, connects1 = _counts()
    assert posts1 - posts0 == n
    assert connects1 - connects0 == (1 if keeps else n)
    if keeps:  # the last of each kind arrived whole, in order
        assert client.get_stats().count == 100 + rounds - 1
        cache = server[2]
        assert json.loads(cache.series())[-1]["real"] == [rounds - 1.0] * 400
    client.close()


@pytest.mark.parametrize("scheme", ["http", "https"])
def test_kept_connection_closed_while_idle_is_retried_once(
        scheme, stub, request, monkeypatch):
    """The server answers as one that keeps the connection and then closes
    it: the next request finds it closed before any byte of a reply, and
    goes out once more on a fresh one — no raise, one more connect. Under
    TLS the close arrives as a ``close_notify`` or an ``SSLEOFError``."""
    def serve(stub, conn):
        if stub.read(conn) is not None:
            conn.sendall(KEEP % (2, b"{}"))  # and the close, unannounced

    if scheme == "https":
        tls = request.getfixturevalue("tls")
        srv = stub(tls.wrap(serve))
        client = _https_client(tls, srv, monkeypatch)
    else:
        srv = stub(serve)
        client = WebClient(srv.url)
    client.stats(1, 1, 1, 1, 1)
    assert (client.requests, client.connects) == (1, 1)
    time.sleep(0.05)  # the server's close reaches this end
    client.stats(2, 1, 1, 1, 1)
    assert (client.requests, client.connects) == (2, 2)
    assert srv.connections == 2
    assert [json.loads(b)["count"] for b in srv.bodies] == [1, 2]
    client.close()


def test_fresh_connection_that_closes_unanswered_is_a_failure(stub):
    srv = stub(lambda stub, conn: stub.read(conn))  # reads, says nothing
    client = WebClient(srv.url)
    with pytest.raises(ConnectionError):
        client.stats(1, 1, 1, 1, 1)
    assert (client.requests, client.connects) == (1, 1)  # no second attempt
    assert srv.connections == 1


def test_status_outside_2xx_raises_and_reaches_the_breaker(stub):
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.telemetry.session_stats import SessionStats

    def serve(stub, conn):
        if stub.read(conn) is not None:
            conn.sendall(b"HTTP/1.1 500 Internal Server Error\r\n"
                         b"Content-Length: 4\r\nConnection: close\r\n\r\nboom")

    srv = stub(serve)
    with pytest.raises(WebStatusError) as exc_info:
        WebClient(srv.url).stats(1, 1, 1, 1, 1)
    assert exc_info.value.code == 500 and exc_info.value.body == b"boom"
    # a redirect is not followed: it raises like any status outside 2xx
    moved = stub(lambda stub, conn: stub.read(conn) is not None and
                 conn.sendall(b"HTTP/1.1 302 Found\r\nLocation: /x\r\n"
                              b"Content-Length: 0\r\n\r\n"))
    client = WebClient(moved.url)
    with pytest.raises(WebStatusError) as exc_info:
        client.get_stats()
    assert exc_info.value.code == 302 and moved.connections == 1
    client.close()

    conf = ConfArguments().parse(
        ["--twtweb", srv.url, "--lightning", "http://127.0.0.1:9"])
    session = SessionStats(conf)
    failures = _metrics.get_registry().counter("publish.web.failures")
    before = failures.snapshot()
    real = [1.0]
    session.update(1, 1, 1.0, 1.0, 1.0, real, real)  # never raises
    assert failures.snapshot() == before + 1
    assert session._web_breaker._consecutive == 1
    assert session.web.requests == 1  # no Series behind a failed Stats


@pytest.mark.parametrize("silent", ["http", "tls_before_the_handshake",
                                    "tls_after_the_handshake"])
def test_server_that_never_answers_raises_within_timeout(
        silent, stub, request, monkeypatch):
    """``timeout`` bounds the connect, the TLS handshake and every read: a
    server that accepts and says nothing — in the clear, before its
    handshake, or behind a completed one — raises ``TimeoutError``."""
    def wait(stub, conn):
        stub.done.wait(5.0)

    if silent == "http":
        client = WebClient(stub(wait).url, timeout=0.2)
    else:
        tls = request.getfixturevalue("tls")
        srv = stub(wait if silent == "tls_before_the_handshake"
                   else tls.wrap(wait))
        client = _https_client(tls, srv, monkeypatch, timeout=0.2)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        client.stats(1, 1, 1, 1, 1)
    assert 0.15 < time.monotonic() - t0 < 1.5
    assert (client.requests, client.connects) == (1, 1)
    assert client._sock is None


class _PiecesSocket:
    """A socket whose ``recv`` hands out the given pieces, then the close."""

    def __init__(self, pieces):
        self.pieces = list(pieces)
        self.sent = b""
        self.closed = False

    def sendall(self, data):
        self.sent += data

    def recv(self, n):
        return self.pieces.pop(0) if self.pieces else b""

    def close(self):
        self.closed = True


_STATS_JSON = (b'{"jsonClass":"Stats","count":7,"batch":2,"mse":3,'
               b'"realStddev":4,"predStddev":5}')
_HALF = len(_STATS_JSON) // 2
_REPLIES = {
    "content_length": (b"HTTP/1.1 200 OK\r\ncontent-LENGTH:  %d \r\n\r\n%s"
                       % (len(_STATS_JSON), _STATS_JSON), True),
    "chunked": (b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n"
                b"%x;ext=1\r\n%s\r\n%x\r\n%s\r\n0\r\nTrailer: t\r\n\r\n"
                % (_HALF, _STATS_JSON[:_HALF],
                   len(_STATS_JSON) - _HALF, _STATS_JSON[_HALF:]), True),
    "to_the_close": (b"HTTP/1.0 200 OK\r\nServer: old\r\n\r\n"
                     + _STATS_JSON, False),
}


@pytest.mark.parametrize("a_byte_a_recv", [True, False],
                         ids=["byte_by_byte", "whole"])
@pytest.mark.parametrize("form", sorted(_REPLIES))
def test_reply_forms_decode_alike(form, a_byte_a_recv, monkeypatch):
    """A reply with a length, a chunked one (extension, trailer) and one that
    runs to the close decode to the same Stats, whole or a byte a ``recv``;
    only the first two leave a connection to keep."""
    reply, keepable = _REPLIES[form]
    pieces = ([reply[i:i + 1] for i in range(len(reply))]
              if a_byte_a_recv else [reply])
    sock = _PiecesSocket(pieces)
    client = WebClient("http://dashboard.example:8123/base")
    monkeypatch.setattr(client, "_connect", lambda: sock)
    assert client.get_stats() == Stats(count=7, batch=2, mse=3,
                                       realStddev=4, predStddev=5)
    assert sock.sent == (
        b"GET /base/api/stats HTTP/1.1\r\nHost: dashboard.example:8123\r\n"
        b"Content-Type: application/json\r\nAccept: application/json\r\n\r\n")
    assert sock.closed is (not keepable)
    assert (client._sock is sock) is keepable


@pytest.mark.parametrize("at", ["sendall", "recv"])
@pytest.mark.parametrize("error", ["BrokenPipeError", "ConnectionResetError",
                                   "SSLEOFError", "SSLZeroReturnError"])
def test_every_face_of_a_kept_connection_found_closed_is_retried(
        error, at, monkeypatch):
    """However the peer's close of a KEPT connection shows — a broken pipe,
    a reset, or under TLS an EOF or a ``close_notify`` — before any byte of
    a reply, the request goes out once more on a fresh connection; the same
    error on that fresh one is a failure."""
    import builtins
    import ssl

    exc = getattr(builtins, error, None) or getattr(ssl, error)

    def closed(*_):
        raise exc(error)

    def dead():
        sock = _PiecesSocket([])
        setattr(sock, at, closed)
        return sock

    client = WebClient("http://dashboard.example")
    client._sock = kept = dead()
    fresh = _PiecesSocket([KEEP % (2, b"{}")])
    monkeypatch.setattr(client, "_connect", lambda: fresh)
    client.stats(1, 1, 1, 1, 1)
    assert kept.closed and client._sock is fresh and fresh.sent
    client._sock = dead()
    monkeypatch.setattr(client, "_connect", dead)
    with pytest.raises(exc):
        client.stats(2, 1, 1, 1, 1)
    assert client._sock is None


def test_request_is_one_buffer_in_one_sendall(monkeypatch):
    sock = _PiecesSocket([KEEP % (2, b"{}")])
    sends = []
    sock.sendall = sends.append
    client = WebClient("http://[::1]:8123")
    assert (client._host, client._port) == ("::1", 8123)
    monkeypatch.setattr(client, "_connect", lambda: sock)
    client.stats(1000, 10, 2000, 15, 25)
    body = (b'{"jsonClass": "Stats", "count": 1000, "batch": 10, '
            b'"mse": 2000, "realStddev": 15, "predStddev": 25}')
    assert sends == [
        b"POST /api HTTP/1.1\r\nHost: [::1]:8123\r\n"
        b"Content-Type: application/json\r\nAccept: application/json\r\n"
        b"Content-Length: %d\r\n\r\n%s" % (len(body), body)]
    assert WebClient("https://dash.example")._port == 443
    assert WebClient("dash.example")._port == 80


def test_chaos_web_fires_before_the_socket(stub):
    from twtml_tpu.streaming import faults

    srv = stub(_serve_like_sink)
    client = WebClient(srv.url)
    faults.install_chaos("web:error@2")
    try:
        client.stats(1, 1, 1, 1, 1)
        with pytest.raises(faults.InjectedFault):
            client.stats(2, 1, 1, 1, 1)
        client.stats(3, 1, 1, 1, 1)
    finally:
        faults.uninstall_chaos()
    # the injected failure never reached a socket, nor the counters
    assert srv.connections == 2 and (client.requests, client.connects) == (2, 2)
    assert [json.loads(b)["count"] for b in srv.bodies] == [1, 3]


def test_two_threads_on_one_client_do_not_interleave(stub):
    """Every caller gets the reply to ITS request, on the one connection."""
    import sys

    srv = stub(lambda stub, conn: _serve_keeping(stub, conn, lambda b: b))
    client = WebClient(srv.url)
    wrong, each = [], 150

    def post(tag):
        for k in range(each):
            sent = json.dumps({"tag": tag, "k": k, "pad": "x" * (k % 50)})
            if client._request(data=sent.encode()) != sent:
                wrong.append((tag, k))

    threads = [threading.Thread(target=post, args=(t,)) for t in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
    assert (client.requests, client.connects) == (4 * each, 1)
    assert srv.connections == 1 and len(srv.bodies) == 4 * each
    client.close()
    assert client._sock is None


def test_https_runs_the_same_exchange_on_a_wrapped_socket(
        stub, tls, monkeypatch):
    import ssl

    srv = stub(tls.wrap(_serve_keeping))
    client = _https_client(tls, srv, monkeypatch)
    client.stats(5, 1, 1, 1, 1)
    client.series([1.0] * 400, [2.0] * 400, 1.0, 1.0)
    assert (client.requests, client.connects, srv.connections) == (2, 1, 1)
    assert [json.loads(b)["jsonClass"] for b in srv.bodies] == [
        "Stats", "Series"]
    assert isinstance(client._sock, ssl.SSLSocket)
    client.close()


# -- what SessionStats writes on its span, and the benchmark's reader ---------

def _publish_spans(url, tmp_path, updates):
    """``updates`` traced ``SessionStats.update`` calls against ``url``:
    the span file's path and the ``stats_publish`` spans' args."""
    from benchmark import spans
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.telemetry import trace
    from twtml_tpu.telemetry.session_stats import SessionStats

    conf = ConfArguments().parse(
        ["--twtweb", url, "--lightning", "http://127.0.0.1:9"])
    session = SessionStats(conf)  # not opened: no Lightning chart
    path = str(tmp_path / "spans.json")
    trace.install(path)
    try:
        real = [float(k) for k in range(8)]
        for k in range(updates):
            session.update(8 * (k + 1), 8, 3.0, 1.0, 1.0, real, real)
    finally:
        trace.uninstall()
    session.web.close()
    return path, [e["args"] for e in spans.load_events(path)
                  if e["name"] == "stats_publish"]


# what ``modelwatch.last_model`` hands the publisher once a tick was recorded
_MODEL_VIEW = {"level": "warn", "drift_score": 2.5, "loss_trend": 0.0,
               "weight_norm": 0.0, "update_norm": 0.0, "grad_norm": 0.0,
               "mse": [1.0], "tenants": [], "episodes": 0}


def _frame_kinds(bodies):
    """The requests a server read that were neither Stats nor Series: the
    periodic frames, by kind, in the order they came."""
    kinds = [json.loads(b)["jsonClass"] for b in bodies]
    return [k for k in kinds if k not in ("Stats", "Series")]


@pytest.mark.parametrize("keeps", [False, True],
                         ids=["closing_stub", "keeping_stub"])
def test_stats_publish_span_carries_posts_and_connects(
        keeps, stub, tmp_path, monkeypatch):
    """Two periods and one update: the frames go out one an update, each
    once a period (PR 53), so no update sends more than three requests."""
    from benchmark import manifest, trace_files
    from twtml_tpu.telemetry import modelwatch
    from twtml_tpu.telemetry.session_stats import METRICS_EVERY

    monkeypatch.setattr(modelwatch, "last_model", lambda: _MODEL_VIEW)
    srv = stub(_serve_keeping if keeps else _serve_like_sink)
    path, args = _publish_spans(srv.url, tmp_path, 2 * METRICS_EVERY + 1)
    frames = _frame_kinds(srv.bodies)
    assert {"Metrics", "ModelHealth"} <= set(frames)
    assert all(frames.count(kind) == 2 for kind in set(frames))
    posts = [a["posts"] for a in args]
    assert set(posts) == {2, 3} and posts.count(3) == len(frames)
    assert posts[METRICS_EVERY - 1] == posts[2 * METRICS_EVERY - 1] == 3
    connects = [a["connects"] for a in args]
    assert connects == ([1] + [0] * (2 * METRICS_EVERY) if keeps else posts)
    assert sum(posts) == len(srv.bodies)
    assert srv.connections == sum(connects)
    assert all(a["rows"] == 8 for a in args)

    reader = manifest.load_module(
        manifest.layer_metric_path("publish_reuse_share"))
    monkeypatch.setattr(trace_files, "span_file", lambda: path)
    total = sum(posts)
    assert reader.read({}) == (100.0 * (total - 1) / total if keeps else 0.0)
    monkeypatch.setattr(trace_files, "span_file", lambda: None)
    assert reader.read({}) is None              # no live traced run
    bare = tmp_path / "bare.json"
    bare.write_text('[\n{"name": "stats_publish", "ph": "X", "ts": 1.0, '
                    '"dur": 2.0, "args": {"rows": 8}},\n')
    monkeypatch.setattr(trace_files, "span_file", lambda: str(bare))
    assert reader.read({}) is None              # the parent's program


@pytest.mark.parametrize("spans_ms, posts, p95", [
    ([4.0] * 8 + [11.0], [2] * 8 + [7], 11.0),      # a burst in nine: it
    ([4.0] * 39 + [11.0], None, 4.0),               # one in forty: not it
    ([], None, None),                               # no such span
    (None, None, None),                             # no live traced run
], ids=["burst_of_nine", "one_in_forty", "no_span", "no_file"])
def test_publish_ms_p95_reads_the_spans_durations(
        spans_ms, posts, p95, tmp_path, monkeypatch, capsys):
    from benchmark import manifest, trace_files

    path = None
    if spans_ms is not None:
        path = tmp_path / "spans.json"
        events = [{"name": "deliver_round", "ph": "i", "ts": 0.5}]
        for k, ms in enumerate(spans_ms):
            args = {"rows": 8}
            if posts is not None:
                args.update(posts=posts[k], connects=posts[k])
            events.append({"name": "stats_publish", "ph": "X",
                           "ts": 1e3 * k, "dur": 1e3 * ms, "args": args})
        path.write_text("[\n" + "".join(
            json.dumps(ev) + ",\n" for ev in events))
    monkeypatch.setattr(trace_files, "span_file",
                        lambda: None if path is None else str(path))
    reader = manifest.load_module(manifest.layer_metric_path("publish_ms_p95"))
    assert reader.read({}) == p95
    said = capsys.readouterr().out
    if p95 is None:
        assert said == ""
    else:
        most = max(posts) if posts else "not carried"
        assert f"most posts in one update: {most}" in said


def test_stats_publish_span_against_the_dashboard(server, tmp_path):
    """The repo's own server keeps the connection: 2 / 1 on the first
    update, 2 / 0 on every one after it."""
    _, args = _publish_spans(server[1], tmp_path, 4)
    assert [(a["posts"], a["connects"]) for a in args] == [
        (2, 1), (2, 0), (2, 0), (2, 0)]
    assert json.loads(server[2].stats())["count"] == 32


def test_the_dashboard_holds_every_frame_after_one_period(
        server, tmp_path, monkeypatch):
    """The frames reach the repo's own server one an update (PR 53) and it
    caches each by its ``jsonClass``: after one period every view a client
    asks for is there, beside the newest ``Stats``, though no round carried
    two of them."""
    from twtml_tpu.telemetry import (
        freshness, historian, modelwatch, tenants)
    from twtml_tpu.telemetry.session_stats import METRICS_EVERY

    monkeypatch.setattr(modelwatch, "last_model", lambda: _MODEL_VIEW)
    monkeypatch.setattr(tenants, "last_tenants", lambda: {
        "tenants": [], "gating": 3, "active": 4})
    monkeypatch.setattr(freshness, "last_freshness", lambda: {"batches": 7})
    monkeypatch.setattr(historian, "last_history", lambda: {"samples": 5})
    _, args = _publish_spans(server[1], tmp_path, METRICS_EVERY)
    assert sorted(a["posts"] for a in args) == [2] * 3 + [3] * 5
    cache = server[2]
    assert json.loads(cache.stats())["count"] == 8 * METRICS_EVERY
    assert "counters" in json.loads(cache.metrics())
    assert json.loads(cache.tenants())["gating"] == 3
    assert json.loads(cache.model())["driftScore"] == 2.5
    assert json.loads(cache.freshness())["batches"] == 7
    assert json.loads(cache.history())["samples"] == 5


def test_benchmark_lint_passes_with_the_new_entry():
    from benchmark import manifest

    loaded = manifest.load()
    for name in ("publish_reuse_share", "publish_ms_p95"):
        entry = [m for m in loaded["per_layer"] if m["name"] == name]
        assert entry and entry[0]["layer"] == "publish"
        assert entry[0]["moves"] == "batch_gap_ms_p95"
        assert entry[0]["workloads"] == [
            w["name"] for w in loaded["workloads"]]
    # the last metric appended for EVERY cell; what stands after it are
    # metrics a later cell reports alone (PR 55: lasso2e18-trimmed-280's)
    names = [m["name"] for m in loaded["per_layer"]]
    after = loaded["per_layer"][names.index("publish_ms_p95") + 1:]
    assert all(len(m["workloads"]) == 1 for m in after)
    assert manifest.lint() == []
