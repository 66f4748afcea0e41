"""Library-level client for the serving front door (``POST /api/predict``).

Stdlib urllib, no external HTTP dependency (``telemetry/web_client.py``, the
trainer's per-batch publisher, runs its own exchange on a kept socket since
PR 41; this client is on no per-batch path) — and predict calls RAISE on
failure instead of the telemetry client's best-effort ``Try`` semantics: a
load generator or an ops script must see a refused/aborted predict, not
silently drop it. The serve-smoke tests drive this client as their load
face.
"""

from __future__ import annotations

import json
import random
import time
import urllib.error
import urllib.request

DEFAULT_SERVER = "http://localhost:8888"

# client retry ladder (ISSUE 11): jittered exponential with a cap — the
# Source._backoff shape (streaming/sources.py), for the same reason at the
# client tier: N clients retrying one briefly-503ing front door must not
# reconnect in phase. Small values on purpose: a predict client rides OVER
# the router's own replica failover, so a retry here only covers the window
# where the WHOLE fleet (or a single-process server) is momentarily down.
RETRY_BACKOFF_BASE_S = 0.1
RETRY_BACKOFF_CAP_S = 2.0
# HTTP statuses worth a retry: 503 (plane not attached yet / fleet draining)
# and 0 (connection refused / reset — the URLError face of a dead server)
RETRYABLE_STATUSES = (0, 502, 503)


class ServingError(RuntimeError):
    """A predict request failed server-side (watchdog abort, bad rows, or
    serving not attached); ``status`` carries the HTTP code when known."""

    def __init__(self, message: str, status: int = 0):
        super().__init__(message)
        self.status = status


class ServingClient:
    def __init__(self, server: str = "", timeout: float = 10.0,
                 retries: int = 2):
        self.server = server or DEFAULT_SERVER
        self.timeout = timeout
        self.retries = max(0, int(retries))

    @staticmethod
    def _backoff(attempt: int) -> float:
        """Sleep before retry ``attempt`` (1-based): exponential, jittered
        to [0.5x, 1x], capped — the ``Source._backoff`` ladder."""
        base = min(
            RETRY_BACKOFF_BASE_S * (2 ** min(attempt - 1, 12)),
            RETRY_BACKOFF_CAP_S,
        )
        return base * (0.5 + 0.5 * random.random())

    def predict(self, rows) -> dict:
        """POST rows (each a dict with ``text`` + optional author numerics,
        or a bare string) to ``/api/predict``; returns the response dict:
        ``{"predictions": [...], "snapshotStep": N, "servedRows": n}``.

        503/connection-refused failures retry up to ``retries`` times on a
        jittered backoff (counted in ``serve.client_retries``); anything
        else — a 400 bad request, a watchdog abort surfaced as plain 500 —
        raises immediately."""
        body = json.dumps({"rows": list(rows)}).encode("utf-8")
        attempt = 0
        while True:
            try:
                return self._predict_once(body)
            except ServingError as exc:
                attempt += 1
                if (
                    exc.status not in RETRYABLE_STATUSES
                    or attempt > self.retries
                ):
                    raise
                from ..telemetry import metrics as _metrics

                _metrics.get_registry().counter("serve.client_retries").inc()
                time.sleep(self._backoff(attempt))

    def _predict_once(self, body: bytes) -> dict:
        req = urllib.request.Request(
            self.server + "/api/predict",
            data=body,
            headers={
                "content-type": "application/json",
                "accept": "application/json",
            },
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout) as resp:
                return json.loads(resp.read().decode("utf-8"))
        except urllib.error.HTTPError as exc:
            detail = ""
            try:
                detail = json.loads(exc.read().decode("utf-8")).get("error", "")
            except Exception:  # lawcheck: disable=TW005 -- not a swallow: only the optional error-detail parse degrades; ServingError is raised right below either way
                pass
            raise ServingError(
                detail or f"predict failed: HTTP {exc.code}", status=exc.code
            ) from exc
        except urllib.error.URLError as exc:
            raise ServingError(f"predict failed: {exc.reason}") from exc

    def predict_texts(self, texts) -> "list[float]":
        """Convenience: predict bare texts, return just the predictions."""
        return [
            float(v)
            for v in self.predict([{"text": t} for t in texts])["predictions"]
        ]

    def serving(self) -> dict:
        """GET the latest ``Serving`` telemetry view (``/api/serving``)."""
        req = urllib.request.Request(
            self.server + "/api/serving",
            headers={"accept": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=self.timeout) as resp:
            return json.loads(resp.read().decode("utf-8"))

    def fleet(self) -> dict:
        """GET the latest ``Fleet`` view (``/api/fleet`` — live router
        state on a router process, the cached view elsewhere)."""
        req = urllib.request.Request(
            self.server + "/api/fleet",
            headers={"accept": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=self.timeout) as resp:
            return json.loads(resp.read().decode("utf-8"))
