"""End-to-end freshness plane — watermarks, critical path, SLOs (ISSUE 16).

Consumes the per-batch lineage records (telemetry/lineage.py) the existing
seams already stamp — pure host arithmetic over rolling windows, ZERO added
host fetches and ZERO added collectives (the PR 1/5/8 law, asserted by the
counting tests) — and derives the freshness story wall-clock stage gauges
cannot answer while fetch latency swings between health phases:

- **event-time watermarks**: ``freshness.event_lag_ms`` p50/p95/p99 from
  tweet ``created_at_ms`` to fetch delivery (exact percentiles over a
  rolling window, not histogram buckets — the buckets are seconds-scale),
  the same lag to stats publish, and a per-tick low watermark
  (now − oldest event-time still in flight) that rides the sideband vector
  to every host with no new allgather.
- **per-batch critical path**: the dominant seam-to-seam stage delta
  between open and delivery, rolled into ``freshness.critical.<edge>.ticks``
  counters — the r-series bottleneck-ladder verdicts, automated. The
  attribution is approximate under overlapped batches (stage clocks are
  cumulative across concurrent work) but names the binding rung.
- **SLO gate**: ``--freshnessSloMs`` with a sustained-breach run; the
  delivery adapter (apps/common.FreshnessGuard) turns a sustained run into
  blackbox events and ONE forced verified checkpoint per episode — the
  PR 8 early-warning shape, warn-only, sentinel untouched.

Mirrors the modelwatch module pattern: ``record_delivery`` is called by the
delivery adapter, ``record_publish`` by SessionStats, ``last_freshness``
feeds /api/freshness and the dashboard tiles, ``snapshot_for_checkpoint``
stamps verified checkpoints. Everything is a no-op until ``configure``
enables the plane; jax-free.
"""

from __future__ import annotations

import threading
from collections import deque

from ..utils import get_logger
from ..utils.clock import now_ms
from . import blackbox as _blackbox
from . import lineage as _lineage
from . import metrics as _metrics

log = get_logger("telemetry.freshness")

# rolling exact-percentile windows (per-batch lags; 512 batches ≈ minutes)
LAG_WINDOW = 512
# watermark sparkline shipped to the dashboard (Freshness.watermark)
SPARK_WINDOW = 64
# delivered-but-unpublished event stamps awaiting the next publish tick
PUBLISH_PENDING_MAX = 1024
# sustained-breach window (delivered batches over SLO before an episode
# fires) — the burn-rate analog of modelwatch's alert_run window
BREACH_WINDOW = 8

# ms-scale histogram bounds (1 ms .. ~2.3 h); the registry default bounds
# are seconds-geometry and would saturate at ~0.5 s
LAG_BOUNDS = tuple(1.0 * (2.0 ** i) for i in range(24))


def _pct(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return -1.0
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return float(sorted_vals[idx])


class FreshnessPlane:
    """Rolling freshness state for one process. Thread-safe: deliveries
    arrive on the fetch-pipeline worker threads, publishes on the stats
    path, views on the web publisher."""

    def __init__(self, slo_ms: float = 0.0, window: int = BREACH_WINDOW):
        self.slo_ms = float(slo_ms)
        self.window = int(window)
        self._lock = threading.Lock()
        self._lags: deque = deque(maxlen=LAG_WINDOW)
        self._publish_lags: deque = deque(maxlen=LAG_WINDOW)
        self._spark: deque = deque(maxlen=SPARK_WINDOW)
        self._pending_publish: deque = deque(maxlen=PUBLISH_PENDING_MAX)
        self._edge_ticks: dict = {}
        self._batches = 0
        self._rows = 0
        self._last_lag = -1.0
        self._last_watermark = -1.0
        self._critical = ""
        self._breach_run = 0
        self._breaches = 0
        self._in_episode = False

    # -- recording hooks -----------------------------------------------------
    def record_delivery(self) -> "dict | None":
        """Pop the oldest in-flight lineage record at fetch delivery and
        fold it into the rolling view. Returns the SLO verdict for the
        delivery adapter (None for blank/absent records)."""
        rec = _lineage.pop_delivery()
        if rec is None:
            return None
        delivered = rec["delivered_ms"]
        event_hi = rec.get("event_max_ms", 0)
        lag = float(delivered - event_hi) if event_hi > 0 else -1.0
        floor = _lineage.open_event_floor()
        if floor == 0:
            floor = rec.get("event_min_ms", 0)
        watermark = float(delivered - floor) if floor > 0 else lag
        critical = rec.get("critical", "")
        with self._lock:
            self._batches += 1
            self._rows += rec.get("rows", 0)
            self._critical = critical
            self._last_lag = lag
            if lag >= 0.0:
                self._lags.append(lag)
            self._last_watermark = watermark
            if watermark >= 0.0:
                self._spark.append(watermark)
            if critical:
                self._edge_ticks[critical] = (
                    self._edge_ticks.get(critical, 0) + 1
                )
            if event_hi > 0:
                self._pending_publish.append(event_hi)
            breach = self.slo_ms > 0.0 and lag >= 0.0 and lag > self.slo_ms
            if breach:
                self._breach_run += 1
            else:
                self._breach_run = 0
                self._in_episode = False
            run = self._breach_run
            sustained = False
            if run >= self.window and not self._in_episode:
                self._in_episode = True
                self._breaches += 1
                sustained = True
            in_episode = self._in_episode
            lags_sorted = sorted(self._lags)
        self._publish_gauges(lag, watermark, critical, lags_sorted)
        if sustained:
            _metrics.get_registry().counter("freshness.slo_breaches").inc()
            _blackbox.record(
                "freshness_slo_breach", lag_ms=round(lag, 1),
                slo_ms=self.slo_ms, run=run, critical=critical,
            )
            log.warning(
                "freshness SLO breach sustained: event lag %.0f ms > %.0f ms"
                " for %d batches (critical edge: %s)",
                lag, self.slo_ms, run, critical or "?",
            )
        return {
            "event_lag_ms": lag,
            "watermark_lag_ms": watermark,
            "critical": critical,
            "breach": breach,
            "breach_run": run,
            "sustained": sustained,
            "in_episode": in_episode,
        }

    def record_publish(self) -> None:
        """Stamp event→publish lag for every batch delivered since the last
        stats-publish tick (SessionStats calls this on its publish path)."""
        with self._lock:
            if not self._pending_publish:
                return
            ms = now_ms()
            while self._pending_publish:
                self._publish_lags.append(
                    float(ms - self._pending_publish.popleft())
                )
            pub_sorted = sorted(self._publish_lags)
        _metrics.get_registry().gauge("freshness.publish_lag_p95_ms").set(
            round(_pct(pub_sorted, 0.95), 1)
        )

    def _publish_gauges(self, lag, watermark, critical, lags_sorted) -> None:
        reg = _metrics.get_registry()
        if lag >= 0.0:
            reg.histogram("freshness.event_lag_ms", bounds=LAG_BOUNDS).observe(
                lag
            )
            reg.gauge("freshness.event_lag_p50_ms").set(
                round(_pct(lags_sorted, 0.50), 1)
            )
            reg.gauge("freshness.event_lag_p95_ms").set(
                round(_pct(lags_sorted, 0.95), 1)
            )
            reg.gauge("freshness.event_lag_p99_ms").set(
                round(_pct(lags_sorted, 0.99), 1)
            )
        if watermark >= 0.0:
            reg.gauge("freshness.watermark_lag_ms").set(round(watermark, 1))
        if critical:
            reg.counter(f"freshness.critical.{critical}.ticks").inc()

    # -- views ---------------------------------------------------------------
    def last_event_lag_ms(self) -> float:
        """Most recent delivery's event lag (the sideband column; 0 before
        the first delivery with a known event time)."""
        with self._lock:
            return self._last_lag if self._last_lag >= 0.0 else 0.0

    def view(self) -> "dict | None":
        """The dashboard/web view (None until a delivery was recorded)."""
        with self._lock:
            if self._batches == 0:
                return None
            lags = sorted(self._lags)
            pubs = sorted(self._publish_lags)
            return {
                "batches": self._batches,
                "rows": self._rows,
                "eventLagMs": round(self._last_lag, 1),
                "eventLagP50Ms": round(_pct(lags, 0.50), 1),
                "eventLagP95Ms": round(_pct(lags, 0.95), 1),
                "eventLagP99Ms": round(_pct(lags, 0.99), 1),
                "publishLagP95Ms": round(_pct(pubs, 0.95), 1),
                "watermarkLagMs": round(self._last_watermark, 1),
                "watermark": [round(v, 1) for v in self._spark],
                "critical": self._critical,
                "criticalTicks": dict(self._edge_ticks),
                "sloMs": self.slo_ms,
                "breachRun": self._breach_run,
                "breaches": self._breaches,
            }

    def checkpoint_snapshot(self) -> "dict | None":
        """Compact freshness stamp for a verified checkpoint's meta (plain
        floats, json-safe; None before the first delivery)."""
        with self._lock:
            if self._batches == 0:
                return None
            lags = sorted(self._lags)
            return {
                "event_lag_p95_ms": round(_pct(lags, 0.95), 1),
                "watermark_lag_ms": round(self._last_watermark, 1),
                "critical": self._critical,
                "batches": self._batches,
                "breaches": self._breaches,
            }


# -- process-wide plane -------------------------------------------------------

_lock = threading.Lock()
_PLANE: "FreshnessPlane | None" = None
_ON = False


def configure(conf=None, *, on=None, slo_ms=None, window=None) -> None:
    """Install the plane from a Config (apps call this at run() start) or
    from explicit knobs (tests/benches). ``--freshness off`` disables the
    lineage FIFOs too, making the off arm bit-identical to HEAD."""
    global _PLANE, _ON
    if conf is not None:
        on = getattr(conf, "freshness", "on") == "on" if on is None else on
        slo_ms = (
            float(getattr(conf, "freshnessSloMs", 0.0))
            if slo_ms is None else slo_ms
        )
    enabled = bool(on) if on is not None else True
    with _lock:
        _ON = enabled
        _PLANE = FreshnessPlane(
            slo_ms=slo_ms or 0.0,
            window=window or BREACH_WINDOW,
        ) if enabled else None
    _lineage.configure(enabled)


def enabled() -> bool:
    return _ON


def get_plane() -> "FreshnessPlane | None":
    with _lock:
        return _PLANE


def record_delivery() -> "dict | None":
    plane = get_plane()
    return plane.record_delivery() if plane is not None else None


def record_publish() -> None:
    plane = get_plane()
    if plane is not None:
        plane.record_publish()


def last_event_lag_ms() -> float:
    plane = get_plane()
    return plane.last_event_lag_ms() if plane is not None else 0.0


def last_freshness() -> "dict | None":
    """Latest freshness view for /api/freshness and SessionStats; None when
    the plane is off or nothing was delivered."""
    plane = get_plane()
    return plane.view() if plane is not None else None


def snapshot_for_checkpoint() -> "dict | None":
    plane = get_plane()
    return plane.checkpoint_snapshot() if plane is not None else None


def reset_for_tests() -> None:
    global _PLANE, _ON
    with _lock:
        _PLANE = None
        _ON = False
    _lineage.reset_for_tests()
