"""Minimal JSON/HTTP client for the twtml web API.

Same surface as the reference's scalaj-http client
(spark/.../web/WebClient.scala:9-56): POST Config/Stats to ``{server}/api``,
GET them back from ``/api/config`` and ``/api/stats``. No external HTTP
dependency; callers wrap calls best-effort like the reference wraps them in
``Try`` (SessionStats.scala:29-33,60).

The exchange is the client's own, over a socket it keeps (PR 41; what a POST
costs and what it cost through ``urllib.request.urlopen`` is in PERF.md §6):
a request is ONE buffer handed to ONE ``sendall``; the reply is read into
one buffer with as few ``recv`` calls as its bytes allow, its head cut at
the first blank line, and the status and the three headers that matter
(``Content-Length``, ``Connection``, ``Transfer-Encoding``) taken with
``bytes`` operations. The connection is kept while the reply allows it
(HTTP/1.1 without ``Connection: close``: the dashboard, web/server.py) and
the next request goes out on it with no ``connect``; a request that fails on
a KEPT connection before any byte of its reply arrived is sent once more on
a fresh one (the server had closed it while idle), a failure on a fresh
connection is a failure. ``https://`` wraps the same socket at connect and
runs the same exchange. Every call returns only after the server's reply to
it has been read: no pipelining. Redirects are NOT followed (the API has
none): a 3xx raises like any status outside 2xx. ``timeout`` bounds the
connect, a TLS handshake and every send and read (``settimeout``); a bound
that ran out raises ``TimeoutError``.

Counted: ``requests`` (sent; the read side's GETs too) and ``connects``
(connections opened) on the client, and over every client of the process in
the registry counters ``web.requests`` / ``web.connects``; SessionStats
writes each update's share of both on its ``stats_publish`` span as
``posts`` / ``connects`` (PERF.md §3, ``publish_reuse_share``).
"""

from __future__ import annotations

import socket
import ssl
import threading

from . import metrics as _metrics
from .api_types import (
    Config, Fleet, Freshness, History, Hosts, Metrics, ModelHealth, Series,
    Serving, Stats, Tenants, decode, encode,
)

DEFAULT_SERVER = "http://localhost:8888"  # WebClient.scala:13

_RECV = 65536


class WebStatusError(OSError):
    """The server answered with a status outside 2xx."""

    def __init__(self, code: int, body: bytes):
        super().__init__(f"HTTP {code}")
        self.code = code
        self.body = body


class _Stale(ConnectionError):
    """The peer had closed a kept connection: no byte of a reply came."""


def _header(head: bytes, name: bytes) -> bytes:
    """Value of header ``name`` in a LOWER-CASED reply head (b"" if absent)."""
    at = head.find(b"\r\n" + name + b":")
    if at < 0:
        return b""
    start = at + len(name) + 3
    end = head.find(b"\r\n", start)
    return head[start:end if end >= 0 else len(head)].strip()


def _more(sock: socket.socket) -> bytes:
    data = sock.recv(_RECV)
    if not data:
        raise ConnectionError("the server closed inside its reply")
    return data


def _dechunk(sock: socket.socket, buf: bytes) -> bytes:
    """Body of a chunked reply; ``buf`` is what arrived behind the head."""
    parts, at = [], 0
    while True:
        while (eol := buf.find(b"\r\n", at)) < 0:
            buf += _more(sock)
        size = int(buf[at:eol].partition(b";")[0], 16)
        if size == 0:  # the last chunk: trailers, if any, then a blank line
            while buf.find(b"\r\n\r\n", eol) < 0:
                buf += _more(sock)
            return b"".join(parts)
        end = eol + 2 + size
        while len(buf) < end + 2:
            buf += _more(sock)
        parts.append(buf[eol + 2:end])
        at = end + 2


class WebClient:
    def __init__(self, server: str = "", timeout: float = 2.0):
        self.server = server or DEFAULT_SERVER
        self.timeout = timeout
        self.requests = 0
        self.connects = 0
        registry = _metrics.get_registry()
        self._requests_c = registry.counter("web.requests")
        self._connects_c = registry.counter("web.connects")
        scheme, sep, rest = self.server.partition("://")
        if not sep:
            scheme, rest = "http", self.server
        authority, slash, path = rest.partition("/")
        self._tls = scheme.lower() == "https"
        if authority.startswith("["):  # an IPv6 literal
            host, _, port = authority[1:].partition("]")
            port = port.lstrip(":")
        else:
            host, _, port = authority.partition(":")
        self._host = host
        self._port = int(port) if port else (443 if self._tls else 80)
        # everything of a request that does not change between two of them
        self._target = (slash + path + "/api").encode("ascii")
        self._headers = (
            b" HTTP/1.1\r\nHost: " + authority.encode("ascii")
            + b"\r\nContent-Type: application/json"
            b"\r\nAccept: application/json\r\n"
        )
        self._sock: socket.socket | None = None
        self._context = None  # https: the TLS context, made at first connect
        self._lock = threading.Lock()  # one exchange at a time

    def close(self) -> None:
        """Close the kept connection, if any; the next request connects."""
        with self._lock:
            sock, self._sock = self._sock, None
        if sock is not None:
            sock.close()

    def _connect(self) -> socket.socket:
        self.connects += 1
        self._connects_c.inc()
        sock = socket.create_connection((self._host, self._port), self.timeout)
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            if self._tls:
                if self._context is None:
                    self._context = ssl.create_default_context()
                sock = self._context.wrap_socket(
                    sock, server_hostname=self._host
                )
        except BaseException:
            sock.close()
            raise
        return sock

    def _request(self, kind: str = "", data: bytes | None = None):
        # --chaos web injection point (streaming/faults.py): a dead or
        # slow dashboard, simulated before the socket. Lazy import — a
        # module-level one would cycle through streaming/__init__ while
        # telemetry/__init__ is still importing this module.
        from ..streaming import faults as _faults

        _faults.perturb("web")
        line = (self._target, kind.encode("ascii"), self._headers)
        if data is None:
            message = b"".join((b"GET ", *line, b"\r\n"))
        else:
            message = b"".join((
                b"POST ", *line, b"Content-Length: ",
                str(len(data)).encode("ascii"), b"\r\n\r\n", data,
            ))
        with self._lock:
            self.requests += 1
            self._requests_c.inc()
            if self._sock is not None:
                try:
                    return self._exchange(self._sock, message)
                except _Stale:
                    pass  # closed while idle: once more, on a fresh one
            return self._exchange(self._connect(), message)

    def _exchange(self, sock: socket.socket, message: bytes) -> str:
        """Send ``message`` and read its reply from ``sock``, which is kept
        for the next request if the reply allows it and closed otherwise.
        Raises ``_Stale`` if ``sock`` was a KEPT connection and the peer had
        closed it: nothing of a reply arrived."""
        kept, self._sock = sock is self._sock, None
        try:
            try:
                sock.sendall(message)
                buf = sock.recv(_RECV)
                if not buf:
                    raise ConnectionError("closed before any reply")
            except (ConnectionError, ssl.SSLEOFError,
                    ssl.SSLZeroReturnError) as exc:
                if kept:
                    raise _Stale(str(exc)) from exc
                raise
            while (cut := buf.find(b"\r\n\r\n")) < 0:
                buf += _more(sock)
            head, body = buf[:cut].lower(), buf[cut + 4:]
            status = int(head[9:12])
            connection = _header(head, b"connection")
            keep = (b"close" not in connection if head.startswith(b"http/1.1")
                    else b"keep-alive" in connection)
            if b"chunked" in _header(head, b"transfer-encoding"):
                body = _dechunk(sock, body)
            elif status in (204, 304):
                body = b""
            elif length := _header(head, b"content-length"):
                need = int(length)
                while len(body) < need:
                    body += _more(sock)
                if len(body) > need:  # more than it announced: out of step
                    body, keep = body[:need], False
            else:  # no length: the body runs to the close
                while more := sock.recv(_RECV):
                    body += more
                keep = False
        except BaseException:
            sock.close()
            raise
        if keep:
            self._sock = sock
        else:
            sock.close()
        if not 200 <= status < 300:
            raise WebStatusError(status, body)
        return body.decode("utf-8")

    def _post(self, obj: Config | Stats) -> None:
        self._request(data=encode(obj).encode("utf-8"))

    # -- writes (WebClient.scala:31-38) --------------------------------------
    def config(self, id: str, host: str, viz: list[str]) -> None:
        self._post(Config(id=id, host=host, viz=list(viz)))

    def stats(
        self, count: int, batch: int, mse: int, real_stddev: int, pred_stddev: int
    ) -> None:
        self._post(
            Stats(
                count=int(count),
                batch=int(batch),
                mse=int(mse),
                realStddev=int(real_stddev),
                predStddev=int(pred_stddev),
            )
        )

    def series(
        self, real, pred, real_stddev: float, pred_stddev: float
    ) -> None:
        """Push one batch's real/pred series for the built-in live chart
        (additive message; no reference equivalent — Lightning held these)."""
        self._post(
            Series(
                real=[float(v) for v in real],
                pred=[float(v) for v in pred],
                realStddev=float(real_stddev),
                predStddev=float(pred_stddev),
            )
        )

    def metrics(self, counters: dict, gauges: dict, health: dict,
                histograms: "dict | None" = None) -> None:
        """Push a pipeline-metrics snapshot for the dashboard's
        observability panel (additive message; telemetry/metrics.py).
        ``histograms`` carries the derived p50/p95/p99 per histogram."""
        self._post(Metrics(counters=dict(counters), gauges=dict(gauges),
                           health=dict(health),
                           histograms=dict(histograms or {})))

    def hosts(self, hosts: list, straggler: int = -1, stage: str = "",
              skew_ms: float = 0.0, epoch: int = -1, live_hosts: int = 0,
              departed: int = 0, rejoined: int = 0,
              lead_uid: int = -1) -> None:
        """Push the per-host lockstep sideband view for the dashboard's
        Hosts tile row (additive message; telemetry/sideband.py), plus the
        elastic membership summary (epoch, live host count, cumulative
        departed/rejoined, and the current lead's uid — it moves at a won
        election; streaming/membership.py gauges)."""
        self._post(Hosts(hosts=list(hosts), straggler=int(straggler),
                         stage=str(stage), skewMs=float(skew_ms),
                         epoch=int(epoch), liveHosts=int(live_hosts),
                         departed=int(departed), rejoined=int(rejoined),
                         leadUid=int(lead_uid)))

    def tenants(self, tenants: list, gating: int = -1, active: int = 0) -> None:
        """Push the per-tenant model-plane view for the dashboard's Tenants
        tile row (additive message; telemetry/tenants.py)."""
        self._post(Tenants(tenants=list(tenants), gating=int(gating),
                           active=int(active)))

    def model_health(self, level: str = "ok", drift_score: float = 0.0,
                     loss_trend: float = 0.0, weight_norm: float = 0.0,
                     update_norm: float = 0.0, grad_norm: float = 0.0,
                     mse=None, tenants=None, episodes: int = 0) -> None:
        """Push the model-health view for the dashboard's "model · drift"
        tile row + loss sparkline (additive message;
        telemetry/modelwatch.py)."""
        self._post(ModelHealth(
            level=str(level), driftScore=float(drift_score),
            lossTrend=float(loss_trend), weightNorm=float(weight_norm),
            updateNorm=float(update_norm), gradNorm=float(grad_norm),
            mse=[float(v) for v in (mse or [])],
            tenants=list(tenants or []), episodes=int(episodes),
        ))

    def serving(self, view: dict) -> None:
        """Push the serving-plane view (``ServingPlane.stats()``) for the
        dashboard's Serving tile row (additive message; serving/plane.py)."""
        known = Serving.__dataclass_fields__
        self._post(Serving(**{k: v for k, v in view.items() if k in known}))

    def freshness(self, view: dict) -> None:
        """Push the end-to-end freshness view (telemetry/freshness.py
        ``last_freshness()``) for the dashboard's "freshness · e2e lag"
        tile row (additive message)."""
        known = Freshness.__dataclass_fields__
        self._post(Freshness(**{k: v for k, v in view.items() if k in known}))

    def history(self, view: dict) -> None:
        """Push the telemetry-historian view (telemetry/historian.py
        ``last_history()``) for the dashboard's "history · long horizon"
        sparkline tile row (additive message)."""
        known = History.__dataclass_fields__
        self._post(History(**{k: v for k, v in view.items() if k in known}))

    def fleet(self, view: dict) -> None:
        """Push the read-fleet view (``FleetRouter.stats()``) for the
        dashboard's fleet tile row (additive message; serving/fleet.py)."""
        known = Fleet.__dataclass_fields__
        self._post(Fleet(**{k: v for k, v in view.items() if k in known}))

    # -- reads (WebClient.scala:40-46) ---------------------------------------
    def get_config(self) -> Config:
        return decode(self._request("/config"))

    def get_stats(self) -> Stats:
        return decode(self._request("/stats"))
