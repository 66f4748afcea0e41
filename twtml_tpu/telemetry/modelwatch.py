"""Model & data observability plane — the host half (ISSUE 8).

Consumes the in-step quality vector (ops/quality.py) the pipeline ALREADY
fetched as a StepOutput leaf — pure host numpy over rolling windows, ZERO
added host fetches and ZERO added collectives (the PR 1/5 law, asserted by
the counting tests) — and derives the streaming health story the serving
plane's promotion gate needs long before NaN:

- **drift scores**: per monitored moment (prediction/label/residual means,
  the 4 dense-feature means, the hash-bucket skew proxy), the z-shift of a
  RECENT window's mean against a rolling REFERENCE window
  (``|mean(recent) − mean(ref)| / std(ref)``). The reference LAGS the
  recent window (values graduate from recent into reference), and it
  FREEZES while the level is not ok — so a sustained shift stays an alert
  instead of silently becoming the new baseline, and the level recovers
  exactly when the stream returns to the pre-shift distribution. The
  model's drift score is the max over fields; no verdict until
  ``min_ref`` reference ticks exist.
- **loss trend**: fast/slow EWMAs of the per-batch mse; the trend is the
  fast EWMA's relative elevation over the slow one — a streaming slope
  that ignores the absolute loss scale.
- **graduated health levels**: ok → warn → alert on fixed z/trend
  thresholds; a non-finite quality entry is an immediate alert (the
  sentinel's rollback machinery stays the enforcement arm — levels are
  telemetry-only, PARITY.md).

Mirrors the sideband/tenants module pattern: ``record_tick`` is called by
the model-watch delivery adapter (apps/common.ModelWatchGuard),
``last_model`` exposes the rolling view the dashboard's "model · drift"
tiles and ``/api/model`` render, level flips and drift-episode starts land
in the flight-recorder ring, and ``snapshot_for_checkpoint`` stamps the
current quality picture into every verified checkpoint's meta
(tools/model_report.py renders the history — the promotion-gate substrate).

The stacked tenant plane records one track per tenant from the [M, Q]
quality leaf (per-tenant drift for free through the PR 7 adapter); the
model-level view is then the worst tenant's level/drift and the
row-weighted mean of the norms.
"""

from __future__ import annotations

import math
import threading
from collections import deque

import numpy as np

from ..utils import get_logger
from . import blackbox as _blackbox
from . import metrics as _metrics
from . import trace as _trace
from ..ops.quality import QUALITY_INDEX, QUALITY_WIDTH

log = get_logger("telemetry.modelwatch")

LEVELS = ("ok", "warn", "alert")
LEVEL_RANK = {name: i for i, name in enumerate(LEVELS)}

# rolling-window geometry: the reference window is the "what normal looks
# like" memory, the recent window the "what is happening now" probe
REF_WINDOW = 96
RECENT_WINDOW = 16
MIN_REF = 24

# drift thresholds (z of recent mean vs reference distribution); wide on
# purpose — a stationary stream's recent means sit within ~1σ/√RECENT of
# the reference mean, so 4σ/8σ only fire on real shifts
WARN_Z = 4.0
ALERT_Z = 8.0

# loss-trend EWMAs: trend = fast/slow − 1 (relative elevation)
TREND_FAST_ALPHA = 0.2
TREND_SLOW_ALPHA = 0.02
TREND_WARN = 0.25
TREND_ALERT = 1.0

# the quality fields whose z-shift constitutes data/model drift (means and
# the bucket-skew proxy; variances ride the view but don't score — a
# variance shift moves the mean z denominators already)
DRIFT_FIELDS = (
    "pred_mean",
    "label_mean",
    "resid_mean",
    "num_mean_0",
    "num_mean_1",
    "num_mean_2",
    "num_mean_3",
    "bucket_top_share",
)

# loss-sparkline window shipped to the dashboard (ModelHealth.mse)
SPARK_WINDOW = 64


class _Track:
    """Rolling drift/trend state for ONE model (one tenant, or the single
    model). Pure host arithmetic; deterministic given the tick stream."""

    def __init__(self, watch: "ModelWatch"):
        self._w = watch
        self.ref = {
            f: deque(maxlen=watch.ref_window) for f in DRIFT_FIELDS
        }
        self.recent = {
            f: deque(maxlen=watch.recent_window) for f in DRIFT_FIELDS
        }
        self.ewma_fast: float | None = None
        self.ewma_slow: float | None = None
        self.level = "ok"
        self.drift = 0.0
        self.trend = 0.0
        self.drift_field = ""
        self.alert_run = 0
        self.ticks = 0
        self.last_q: np.ndarray | None = None

    def observe(self, q: np.ndarray, mse: float) -> None:
        w = self._w
        self.ticks += 1
        self.last_q = q
        finite = bool(np.isfinite(q).all()) and math.isfinite(mse)
        if finite:
            # two-window drift state: fresh values enter the RECENT probe,
            # and the value falling out of it graduates into the lagged
            # REFERENCE — but only while the level is ok (the baseline
            # freezes during an episode, so a sustained shift stays an
            # alert instead of becoming the new normal)
            frozen = self.level != "ok"
            for f in DRIFT_FIELDS:
                rec = self.recent[f]
                if len(rec) == rec.maxlen and not frozen:
                    self.ref[f].append(rec[0])
                rec.append(float(q[QUALITY_INDEX[f]]))
            if self.ewma_fast is None:
                self.ewma_fast = self.ewma_slow = mse
            else:
                self.ewma_fast += w.trend_fast * (mse - self.ewma_fast)
                self.ewma_slow += w.trend_slow * (mse - self.ewma_slow)
            self.trend = (
                self.ewma_fast / max(self.ewma_slow, 1e-12) - 1.0
                if self.ewma_slow and self.ewma_slow > 0
                else 0.0
            )
            self.drift, self.drift_field = self._drift_score()
        level = self._level(finite)
        if level == "alert":
            self.alert_run += 1
        else:
            self.alert_run = 0
        self.level = level

    def _drift_score(self) -> "tuple[float, str]":
        w = self._w
        best, best_field = 0.0, ""
        for f in DRIFT_FIELDS:
            ref, recent = self.ref[f], self.recent[f]
            if len(ref) < w.min_ref or len(recent) < recent.maxlen:
                continue
            rv = np.asarray(ref, np.float64)
            ref_mean = float(rv.mean())
            # the z floor keeps a near-constant reference column (std ~ 0)
            # from turning float noise into infinite z
            scale = max(
                float(rv.std()), 1e-3 * abs(ref_mean), 1e-9
            )
            z = abs(
                float(np.asarray(recent, np.float64).mean()) - ref_mean
            ) / scale
            if z > best:
                best, best_field = z, f
        return best, best_field

    def _level(self, finite: bool) -> str:
        w = self._w
        if not finite:
            return "alert"
        if self.drift >= w.alert_z or self.trend >= w.trend_alert:
            return "alert"
        if self.drift >= w.warn_z or self.trend >= w.trend_warn:
            return "warn"
        return "ok"


class ModelWatch:
    """The per-process watcher: one ``_Track`` per model (grown lazily to
    the tenant count), registry gauges/counters, flight-recorder events,
    and the rolling dashboard/checkpoint views. Thresholds are injectable
    for tests; the module-level singleton below uses the defaults."""

    def __init__(
        self,
        ref_window: int = REF_WINDOW,
        recent_window: int = RECENT_WINDOW,
        min_ref: int = MIN_REF,
        warn_z: float = WARN_Z,
        alert_z: float = ALERT_Z,
        trend_fast: float = TREND_FAST_ALPHA,
        trend_slow: float = TREND_SLOW_ALPHA,
        trend_warn: float = TREND_WARN,
        trend_alert: float = TREND_ALERT,
    ):
        self.ref_window = ref_window
        self.recent_window = recent_window
        self.min_ref = min_ref
        self.warn_z = warn_z
        self.alert_z = alert_z
        self.trend_fast = trend_fast
        self.trend_slow = trend_slow
        self.trend_warn = trend_warn
        self.trend_alert = trend_alert
        self._tracks: list[_Track] = []
        self._mse_hist: deque[float] = deque(maxlen=SPARK_WINDOW)
        self._level = "ok"
        self._episodes = 0
        self._flips = 0
        self._ticks = 0
        self._last_norms = (0.0, 0.0, 0.0)
        self._lock = threading.Lock()

    # -- recording -----------------------------------------------------------
    def observe(self, quality, count, mse) -> dict:
        """One delivered tick's quality — ``quality`` is [Q] (single model)
        or [M, Q] (tenant plane); ``count``/``mse`` scalars or [M]. Returns
        the verdict dict the delivery adapter acts on."""
        q = np.asarray(quality, np.float64)
        if q.ndim == 1:
            q = q[None, :]
        counts = np.atleast_1d(np.asarray(count, np.float64))
        mses = np.atleast_1d(np.asarray(mse, np.float64))
        if q.shape[1] != QUALITY_WIDTH:
            raise ValueError(
                f"quality vector width {q.shape[1]} != {QUALITY_WIDTH}"
            )
        m = q.shape[0]
        with self._lock:
            while len(self._tracks) < m:
                self._tracks.append(_Track(self))
            prev_level = self._level
            for i in range(m):
                if counts[i] > 0:
                    self._tracks[i].observe(q[i], float(mses[i]))
            self._ticks += 1
            total = float(counts.sum())
            agg_mse = (
                float((counts * mses).sum() / total) if total > 0 else 0.0
            )
            if total > 0 and math.isfinite(agg_mse):
                self._mse_hist.append(agg_mse)
            # model-level verdict: the worst tenant; norms are the
            # row-weighted means over tenants active this tick
            worst = max(
                self._tracks[:m], key=lambda t: LEVEL_RANK[t.level]
            )
            self._level = worst.level
            active = counts > 0
            wn = un = gn = 0.0
            if active.any():
                aw = counts[active] / counts[active].sum()
                iw, iu, ig = (
                    QUALITY_INDEX["weight_norm"],
                    QUALITY_INDEX["update_norm"],
                    QUALITY_INDEX["grad_norm"],
                )
                qa = q[active]
                wn = float((aw * qa[:, iw]).sum())
                un = float((aw * qa[:, iu]).sum())
                gn = float((aw * qa[:, ig]).sum())
            self._last_norms = (wn, un, gn)
            # which Gram plane the step took (ops/gram.text_gram's index;
            # the slowest over tenants): 0 exact, 1 bf16, 2 s8, -1 no Gram
            plane = float(
                q[active, QUALITY_INDEX["gram_plane"]].min()
            ) if active.any() else -1.0
            drift = max((t.drift for t in self._tracks[:m]), default=0.0)
            trend = max((t.trend for t in self._tracks[:m]), default=0.0)
            alert_run = max(
                (t.alert_run for t in self._tracks[:m]), default=0
            )
            flipped = self._level != prev_level
            episode = flipped and LEVEL_RANK[self._level] > LEVEL_RANK[
                prev_level
            ] and prev_level == "ok"
            if flipped:
                self._flips += 1
            if episode:
                self._episodes += 1
            level = self._level
        self._publish(m, level, drift, trend, wn, un, gn, plane)
        # once per delivered batch, under the scheduler's sequence number
        # (the delivery runs inside its batch_scope); a no-op without --trace
        _trace.get().instant(
            "gram_plane", batch=_trace.current_batch(), plane=int(plane)
        )
        if flipped:
            _blackbox.record(
                "model_health", level=level, prev=prev_level,
                drift=round(drift, 3), trend=round(trend, 4),
            )
            (log.warning if level != "ok" else log.info)(
                "model health %s -> %s (drift z=%.2f, loss trend %+.1f%%)",
                prev_level, level, drift, trend * 100.0,
            )
        if episode:
            _metrics.get_registry().counter("model.drift_episodes").inc()
            _blackbox.record(
                "drift_episode", drift=round(drift, 3),
                field=max(
                    self._tracks[:m], key=lambda t: t.drift
                ).drift_field,
            )
        return {
            "level": level,
            "drift_score": drift,
            "loss_trend": trend,
            "alert_run": alert_run,
            "flipped": flipped,
        }

    def _publish(self, m, level, drift, trend, wn, un, gn, plane) -> None:
        reg = _metrics.get_registry()
        reg.gauge("model.gram_plane").set(plane)
        reg.gauge("model.weight_norm").set(round(wn, 4))
        reg.gauge("model.update_norm").set(round(un, 4))
        reg.gauge("model.grad_norm").set(round(gn, 4))
        reg.gauge("model.drift_score").set(round(drift, 4))
        reg.gauge("model.loss_trend").set(round(trend, 4))
        reg.gauge("model.health_level").set(LEVEL_RANK[level])
        if m > 1:
            for i, t in enumerate(self._tracks[:m]):
                reg.gauge(f"tenant.{i}.drift_score").set(round(t.drift, 4))
                reg.gauge(f"tenant.{i}.health_level").set(
                    LEVEL_RANK[t.level]
                )

    # -- views ---------------------------------------------------------------
    def view(self) -> "dict | None":
        """The dashboard/web view (None until a tick was recorded)."""
        with self._lock:
            if self._ticks == 0:
                return None
            wn, un, gn = self._last_norms
            m = len(self._tracks)
            drift = max((t.drift for t in self._tracks), default=0.0)
            trend = max((t.trend for t in self._tracks), default=0.0)
            return {
                "level": self._level,
                "drift_score": round(drift, 3),
                "loss_trend": round(trend, 4),
                "weight_norm": round(wn, 3),
                "update_norm": round(un, 4),
                "grad_norm": round(gn, 3),
                "mse": [round(v, 3) for v in self._mse_hist],
                "tenants": [
                    {
                        "tenant": i,
                        "level": t.level,
                        "drift": round(t.drift, 3),
                        "trend": round(t.trend, 4),
                    }
                    for i, t in enumerate(self._tracks)
                ] if m > 1 else [],
                "episodes": self._episodes,
                "ticks": self._ticks,
            }

    def checkpoint_snapshot(self) -> "dict | None":
        """The compact quality stamp a verified checkpoint's meta carries
        (plain floats — json-safe; None before the first tick)."""
        with self._lock:
            if self._ticks == 0:
                return None
            wn, un, gn = self._last_norms
            stamp = {
                "level": self._level,
                "drift_score": round(
                    max((t.drift for t in self._tracks), default=0.0), 4
                ),
                "loss_trend": round(
                    max((t.trend for t in self._tracks), default=0.0), 4
                ),
                "weight_norm": round(wn, 4),
                "update_norm": round(un, 4),
                "grad_norm": round(gn, 4),
                "mse": round(self._mse_hist[-1], 4) if self._mse_hist else -1.0,
                "ticks": self._ticks,
                "episodes": self._episodes,
            }
            if len(self._tracks) > 1:
                # per-tenant stamps (ISSUE 11): the champion/challenger
                # promotion rule compares variants by the ONLINE score the
                # trainer already computes — level, drift, trend, and the
                # fast loss EWMA — so A/B verdicts ride the checkpoint
                # handoff with zero new surfaces
                stamp["tenants"] = [
                    {
                        "tenant": i,
                        "level": t.level,
                        "drift_score": round(t.drift, 4),
                        "loss_trend": round(t.trend, 4),
                        "loss": (
                            round(t.ewma_fast, 4)
                            if t.ewma_fast is not None else -1.0
                        ),
                    }
                    for i, t in enumerate(self._tracks)
                ]
            return stamp


# -- process-wide watcher ----------------------------------------------------

_lock = threading.Lock()
_WATCH: "ModelWatch | None" = None


def get_watch() -> ModelWatch:
    global _WATCH
    with _lock:
        if _WATCH is None:
            _WATCH = ModelWatch()
        return _WATCH


def record_tick(quality, count, mse) -> dict:
    """Module-level recording hook (the delivery adapter's entry point)."""
    return get_watch().observe(quality, count, mse)


def last_model() -> "dict | None":
    """Latest model-health view for /api/model and SessionStats; None when
    nothing has been recorded (single source of truth: the watcher)."""
    with _lock:
        watch = _WATCH
    return watch.view() if watch is not None else None


def snapshot_for_checkpoint() -> "dict | None":
    with _lock:
        watch = _WATCH
    return watch.checkpoint_snapshot() if watch is not None else None


def reset_for_tests() -> None:
    global _WATCH
    with _lock:
        _WATCH = None
