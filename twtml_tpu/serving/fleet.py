"""Read-fleet router: N serve replicas behind ONE front door (ISSUE 11).

The serving plane (PR 9) made the read path a product, but one process over
one snapshot directory. The "millions of users" story needs a horizontal
read fleet: N ``apps/serve.py`` replicas — each polling the SAME verified-
snapshot directory through its own ``SnapshotPromoter``, so replicas promote
independently but converge on the same stamped step via the shared
``is_promotable`` predicate — fronted by this router, which:

- **load-balances** ``POST /api/predict`` over the healthy replicas.
  ``--routePolicy p99`` picks the replica with the lowest EXPECTED p99
  cost — rolling forward p99 x (in-flight forwards + 1), the router's own
  view of each replica's line, no replica cooperation needed (raw
  least-p99 herds open-loop bursts onto one stale-lowest replica —
  measured); ``--routePolicy hash`` consistent-hashes the request key onto
  a vnode ring, so a given key sticks to a replica across requests
  (cache-friendly routing) and only 1/N of keys move when a replica joins
  or dies;
- **health-checks** replicas via ``GET /api/serving`` on a background
  cadence (the same view the dashboard reads — no new replica surface);
- **drains and ejects** a failing replica instead of surfacing its errors:
  a connection-refused/timeout/5xx forward retries on ANOTHER replica
  (counted in ``router.retries``) while the failing one is ejected
  (``fleet.replica_ejections``) behind a jittered exponential re-probe
  backoff — the ``Source._backoff`` cap+jitter ladder applied at the fleet
  tier, for the same reason: N routers re-probing a dead replica must not
  reconnect in phase. A recovered probe restores the replica and resets
  its ladder (the Twitter-reconnect rule: health resets backoff).

jax-free on purpose (like ``snapshot``/``client``): the router is a pure
HTTP process — it holds no model, so a fleet front door boots in
milliseconds and never competes with replicas for the one host core's
device runtime.
"""

from __future__ import annotations

import collections
import hashlib
import json
import random
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

from ..telemetry import metrics as _metrics
from ..utils import get_logger

log = get_logger("serving.fleet")

# rolling per-replica forward latencies backing the least-p99 policy and the
# Fleet view; bounded so a days-long router never grows it
LATENCY_WINDOW = 512
QPS_WINDOW_S = 30.0

# ejection backoff ladder (the Source._backoff shape: exponential, jittered
# to [0.5x, 1x], capped; the exponent is capped so unbounded flapping can't
# overflow 2**n)
BACKOFF_BASE_S = 0.5
BACKOFF_CAP_S = 15.0

# consistent-hash ring: vnodes per replica (enough that key movement on a
# replica death is ~1/N, small enough that ring walks stay trivial)
VNODES = 64

HEALTH_EVERY_S = 1.0
HEALTH_TIMEOUT_S = 2.0

# concurrent forward budget: forwards are IO-bound urllib calls that sleep
# on replica sockets (threads hide IO waits), and the fleet's aggregate
# in-flight ceiling is N replicas x serve depth, so the router must hold
# MORE in flight than any one replica can. asyncio's default executor is
# cpu+4 threads, which on a small host caps the fleet near one replica's
# in-flight budget
FORWARD_WORKERS = 64


def _jittered_backoff(ejections: int) -> float:
    """Seconds an ejected replica sits out before its next probe — the
    ``Source._backoff`` cap+jitter ladder (streaming/sources.py) applied to
    replicas instead of stream reconnects."""
    base = min(
        BACKOFF_BASE_S * (2 ** min(max(ejections, 1) - 1, 12)),
        BACKOFF_CAP_S,
    )
    return base * (0.5 + 0.5 * random.random())


class Replica:
    """Router-side state for one serve replica. All mutation happens under
    the router's lock; reads for the Fleet view copy plain values."""

    def __init__(self, index: int, url: str):
        self.index = index
        self.url = url.rstrip("/")
        self.healthy = True  # optimistic: the first forward/probe decides
        self.ejections = 0
        self.ejected_until = 0.0
        self.requests = 0
        self.errors = 0
        self.inflight = 0
        self.latencies: "collections.deque[float]" = collections.deque(
            maxlen=LATENCY_WINDOW
        )
        self.completions: "collections.deque[float]" = collections.deque(
            maxlen=LATENCY_WINDOW
        )
        self.last_view: dict = {}

    def p99_s(self) -> float:
        if not self.latencies:
            return 0.0
        vs = sorted(self.latencies)
        return vs[min(len(vs) - 1, int(0.99 * len(vs)))]

    def qps(self, now: float) -> float:
        lo = now - QPS_WINDOW_S
        n = sum(1 for t in self.completions if t >= lo)
        return n / QPS_WINDOW_S


class FleetRouter:
    """The fleet front door's routing core. ``predict`` is thread-safe and
    called from the web server's executor threads; the health loop runs on
    its own daemon thread. Pure stdlib HTTP (urllib), like ServingClient."""

    POLICIES = ("p99", "hash")

    def __init__(
        self,
        urls,
        policy: str = "p99",
        timeout: float = 30.0,
        health_every_s: float = HEALTH_EVERY_S,
    ):
        urls = [u for u in urls if u]
        if not urls:
            raise ValueError("a fleet router needs at least one replica URL")
        if policy not in self.POLICIES:
            raise ValueError(
                f"routePolicy must be one of {self.POLICIES}, got {policy!r}"
            )
        self.policy = policy
        self.timeout = float(timeout)
        self.health_every_s = max(0.05, float(health_every_s))
        self.replicas = [Replica(i, u) for i, u in enumerate(urls)]
        self._lock = threading.Lock()
        self._rr = 0  # round-robin tiebreak cursor
        self._ring: "list[tuple[int, int]]" = []  # (point, replica index)
        for rep in self.replicas:
            for v in range(VNODES):
                digest = hashlib.md5(
                    f"{rep.url}#{v}".encode("utf-8")
                ).digest()
                self._ring.append(
                    (int.from_bytes(digest[:8], "big"), rep.index)
                )
        self._ring.sort()
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None
        # the front door's forward pool (web/server.py runs predict
        # forwards here instead of asyncio's tiny default executor)
        self.executor = ThreadPoolExecutor(
            max_workers=FORWARD_WORKERS,
            thread_name_prefix="twtml-fleet-fwd",
        )
        reg = _metrics.get_registry()
        self._req_count = reg.counter("router.requests")
        self._retry_count = reg.counter("router.retries")
        self._err_count = reg.counter("router.errors")
        self._eject_count = reg.counter("fleet.replica_ejections")
        self._restore_count = reg.counter("fleet.replica_restores")

    # -- replica selection ---------------------------------------------------
    def _available(self, now: float, exclude: set) -> "list[Replica]":
        """Replicas a forward may try: healthy first; if none, ejected ones
        whose backoff expired (last resort — better a probe-by-forward than
        a guaranteed 503)."""
        healthy = [
            r for r in self.replicas
            if r.index not in exclude and r.healthy
        ]
        if healthy:
            return healthy
        return [
            r for r in self.replicas
            if r.index not in exclude and now >= r.ejected_until
        ]

    def _pick(self, key: bytes, exclude: set) -> "Replica | None":
        now = time.monotonic()
        with self._lock:
            candidates = self._available(now, exclude)
            if not candidates:
                return None
            if self.policy == "hash":
                point = int.from_bytes(
                    hashlib.md5(key).digest()[:8], "big"
                )
                ok = {r.index for r in candidates}
                # walk the ring from the key's point to the first live vnode
                lo, hi = 0, len(self._ring)
                while lo < hi:
                    mid = (lo + hi) // 2
                    if self._ring[mid][0] < point:
                        lo = mid + 1
                    else:
                        hi = mid
                for off in range(len(self._ring)):
                    idx = self._ring[(lo + off) % len(self._ring)][1]
                    if idx in ok:
                        rep = self.replicas[idx]
                        break
                else:  # pragma: no cover - candidates is non-empty
                    rep = candidates[0]
            else:
                # least-p99, QUEUE-AWARE: score = rolling p99 x (in-flight
                # + 1) — the expected completion cost of joining that
                # replica's line. Raw least-p99 herds an open-loop burst:
                # every request routes before any completes, so a stale
                # lower p99 would take the WHOLE burst (measured — a
                # 2-replica fleet ran at one replica's throughput).
                # Round-robin breaks exact ties.
                self._rr += 1
                rep = min(
                    candidates,
                    key=lambda r: (
                        max(r.p99_s(), 1e-3) * (r.inflight + 1),
                        (r.index - self._rr) % max(len(self.replicas), 1),
                    ),
                )
            rep.inflight += 1
            rep.requests += 1
            return rep

    # -- forwarding ----------------------------------------------------------
    def predict(self, body: bytes, key: "bytes | None" = None):
        """Forward one ``POST /api/predict`` body. Returns
        ``(http_status, response_bytes)``. A replica-side failure
        (connection refused, timeout, 5xx) ejects that replica and retries
        the NEXT one — the client sees an error only when EVERY replica is
        down this instant. 4xx pass through untouched (the request's fault,
        not the fleet's)."""
        self._req_count.inc()
        key = body if key is None else key
        tried: set = set()
        first_failure = ""
        while True:
            rep = self._pick(key, tried)
            if rep is None:
                self._err_count.inc()
                detail = first_failure or "no replica available"
                return 503, json.dumps({
                    "error": f"fleet has no live replica ({detail}); "
                    "replicas re-probe on a jittered backoff",
                }).encode("utf-8")
            tried.add(rep.index)
            t0 = time.perf_counter()
            try:
                req = urllib.request.Request(
                    rep.url + "/api/predict", data=body,
                    headers={"content-type": "application/json",
                             "accept": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(
                    req, timeout=self.timeout
                ) as resp:
                    payload = resp.read()
                self._record_success(rep, time.perf_counter() - t0)
                return 200, payload
            except urllib.error.HTTPError as exc:
                detail = exc.read()
                if exc.code < 500:
                    # the request itself is bad; every replica would agree
                    self._record_success(rep, time.perf_counter() - t0)
                    return exc.code, detail
                why = f"HTTP {exc.code} from {rep.url}"
            except (urllib.error.URLError, TimeoutError, OSError) as exc:
                why = f"{rep.url} unreachable ({getattr(exc, 'reason', exc)})"
            first_failure = first_failure or why
            self._record_failure(rep, why)
            if len(tried) < len(self.replicas):
                self._retry_count.inc()
                log.warning(
                    "predict forward failed (%s); retrying on another "
                    "replica (%d/%d tried)", why, len(tried),
                    len(self.replicas),
                )

    def _record_success(self, rep: Replica, dt: float) -> None:
        now = time.monotonic()
        with self._lock:
            rep.inflight = max(0, rep.inflight - 1)
            rep.latencies.append(dt)
            rep.completions.append(now)
            if not rep.healthy:
                rep.healthy = True
                rep.ejected_until = 0.0
                self._restore_count.inc()
                log.info("replica %s recovered (forward succeeded)", rep.url)

    def _record_failure(self, rep: Replica, why: str) -> None:
        with self._lock:
            rep.inflight = max(0, rep.inflight - 1)
            rep.errors += 1
            if rep.healthy or rep.ejected_until <= time.monotonic():
                rep.healthy = False
                rep.ejections += 1
                backoff = _jittered_backoff(rep.ejections)
                rep.ejected_until = time.monotonic() + backoff
                self._eject_count.inc()
                log.warning(
                    "ejecting replica %s for %.1fs (ejection #%d): %s",
                    rep.url, backoff, rep.ejections, why,
                )

    # -- health checks -------------------------------------------------------
    def health_check_once(self) -> None:
        """Probe every probe-eligible replica's ``GET /api/serving``: a live
        view restores (or confirms) it; a failure ejects it. Ejected
        replicas are skipped until their jittered backoff expires."""
        now = time.monotonic()
        for rep in self.replicas:
            if not rep.healthy and now < rep.ejected_until:
                continue
            try:
                req = urllib.request.Request(
                    rep.url + "/api/serving",
                    headers={"accept": "application/json"},
                )
                with urllib.request.urlopen(
                    req, timeout=HEALTH_TIMEOUT_S
                ) as resp:
                    view = json.loads(resp.read().decode("utf-8"))
                with self._lock:
                    rep.last_view = view if isinstance(view, dict) else {}
                    if not rep.healthy:
                        rep.healthy = True
                        rep.ejected_until = 0.0
                        self._restore_count.inc()
                        log.info(
                            "replica %s recovered (health probe)", rep.url
                        )
            except Exception as exc:  # lawcheck: disable=TW005 -- not a swallow: the failure drives the ejection ladder right here
                self._record_failure_probe(rep, exc)

    def _record_failure_probe(self, rep: Replica, exc: Exception) -> None:
        with self._lock:
            if rep.healthy or rep.ejected_until <= time.monotonic():
                rep.healthy = False
                rep.ejections += 1
                backoff = _jittered_backoff(rep.ejections)
                rep.ejected_until = time.monotonic() + backoff
                self._eject_count.inc()
                log.warning(
                    "health probe failed for %s; ejected for %.1fs "
                    "(ejection #%d): %s", rep.url, backoff, rep.ejections,
                    exc,
                )

    def start(self) -> "FleetRouter":
        self._thread = threading.Thread(
            target=self._health_loop, name="twtml-fleet-health", daemon=True
        )
        self._thread.start()
        return self

    def _health_loop(self) -> None:
        while not self._stop.wait(self.health_every_s):
            try:
                self.health_check_once()
            except Exception:
                log.exception("fleet health sweep failed; retrying")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self.executor.shutdown(wait=False)

    # -- the Fleet view ------------------------------------------------------
    def stats(self) -> dict:
        """The ``Fleet`` jsonClass view (/api/fleet + the dashboard's fleet
        tile row): per-replica health/latency/traffic plus the router's
        retry/ejection story. Plain host bookkeeping."""
        now = time.monotonic()
        with self._lock:
            replicas = []
            champion = -1
            for r in self.replicas:
                view = r.last_view or {}
                step = int(view.get("snapshotStep", -1))
                champ = int(view.get("champion", -1))
                if champ >= 0:
                    champion = champ
                replicas.append({
                    "replica": r.index,
                    "url": r.url,
                    "healthy": bool(r.healthy),
                    "p99Ms": round(r.p99_s() * 1e3, 2),
                    "qps": round(r.qps(now), 2),
                    "requests": int(r.requests),
                    "errors": int(r.errors),
                    "ejections": int(r.ejections),
                    "snapshotStep": step,
                })
        return {
            "policy": self.policy,
            "replicas": replicas,
            "requests": int(self._req_count.snapshot()),
            "retries": int(self._retry_count.snapshot()),
            "ejections": int(self._eject_count.snapshot()),
            "champion": champion,
        }
