"""Per-session stats publishing (reference: SessionStats.scala:9-63).

Opens a 4-series Lightning streaming line chart (real=blue, pred=yellow, with
lighter "detail" shades, SessionStats.scala:15-20,49-52), registers the
session with the twtml web server (``web.config``), and pushes per-batch
stats to both. Every network call is best-effort (``Try`` in the reference,
SessionStats.scala:29-33,60): the ML loop must survive telemetry outages.

Best-effort hardened (r7): each endpoint sits behind a circuit breaker
(telemetry/breaker.py) — a dead dashboard stops costing the hot path its
full ``--webTimeout`` per publish after ``FAILURE_THRESHOLD`` consecutive
failures (drop-and-count, half-open probe re-admits it) — and when the
fetch-health monitor reports a DEGRADED transport, the per-batch series
frames (the biggest payload) shed to every ``SERIES_SHED_EVERY``-th batch
while the scalar stats keep full resolution. Neither mechanism changes the
reference parity: publishes still never raise into the ML loop.
"""

from __future__ import annotations

import numpy as np

from ..utils import get_logger, round_half_up
from . import metrics as _metrics
from . import sideband as _sideband
from . import trace as _trace
from .breaker import CircuitBreaker
from .lightning import CHART_MAX_POINTS, Lightning, Visualization
from .web_client import WebClient

log = get_logger("telemetry.session")

# per-batch cap on chart series points shipped to the dashboard (shared
# with every streaming chart — telemetry/lightning.py)
SERIES_MAX_POINTS = CHART_MAX_POINTS

# the PERIOD, in stats updates, of every observability frame (``Metrics``,
# ``Hosts``, ``Tenants``, ``ModelHealth``, ``Freshness``, ``History``) and
# of the historian's sample: counters move every batch but the dashboard
# panel doesn't need per-batch resolution, and each frame is one more
# best-effort HTTP POST on the hot path: one buffer, one ``sendall`` and
# the reads of the reply on the socket the client keeps, plus a ``connect``
# where the server closed the last one (telemetry/web_client.py, PR 41;
# redirects are not followed) — and the scheduler still waits for every
# reply before it goes on. So the frames go out ONE AN UPDATE, each at a
# phase of its own (``SessionStats._PERIODIC``), not all on the eighth:
# sent together they made one gap in eight ~7 ms longer than the others,
# and one gap in eight is over 5% of the gaps, so that burst WAS the p95
# of the time between published batches wherever the host sets the pace
METRICS_EVERY = 8

# degraded-phase load shedding: ship only every Nth batch's series frame
# while the health monitor reports a degraded transport
SERIES_SHED_EVERY = 8

# host-process gauges (ISSUE 8 satellite): uptime is measured from this
# module's import — the app imports it at startup, so the gauge tracks the
# process lifetime a slow host-memory growth accumulates over
import time as _time_mod

_PROCESS_START_S = _time_mod.monotonic()

# SessionStats.scala:15-20
REAL_COLOR_DET = [173.0, 216.0, 230.0]  # light blue
REAL_COLOR = [30.0, 144.0, 255.0]  # blue
PRED_COLOR_DET = [238.0, 232.0, 170.0]  # pale yellow
PRED_COLOR = [255.0, 215.0, 0.0]  # gold


class SessionStats:
    def __init__(self, conf):
        self.conf = conf
        self.lgn = Lightning(host=conf.lightning)
        self.web = WebClient(
            conf.twtweb, timeout=float(getattr(conf, "webTimeout", 2.0))
        )
        self.viz: Visualization | None = None
        self._updates = 0
        # one breaker per endpoint: the web dashboard and Lightning fail
        # independently (PARITY: the reference's Try semantics are
        # preserved — the breaker only decides whether the best-effort
        # attempt is MADE, never raises into the ML loop)
        self._web_breaker = CircuitBreaker("web")
        self._lgn_breaker = CircuitBreaker("lightning")
        # rolling (monotonic_s, rss_mb) samples, one per publish tick, for
        # the continuous leak-rate gauge (ISSUE 16 satellite — the
        # tools/soak.py least-squares slope, live instead of offline)
        import collections

        self._rss_samples: collections.deque = collections.deque(maxlen=256)

    def open(self) -> "SessionStats":
        log.info("Initializing plot on lightning server: %s", self.conf.lightning)
        try:
            self.viz = self.lgn.line_streaming(
                series=[[0.0]] * 4,
                size=[1.0, 1.0, 2.0, 2.0],
                color=[REAL_COLOR_DET, PRED_COLOR_DET, REAL_COLOR, PRED_COLOR],
            )
            log.info(
                "lightning session: %s/sessions/%s — %s/visualizations/%s/pym",
                self.conf.lightning, self.viz.session,
                self.conf.lightning, self.viz.id,
            )
        except Exception as exc:
            log.warning("lightning unavailable (%s); charts disabled", exc)

        log.info("Initializing config on web server: %s", self.conf.twtweb)
        try:
            self.web.config(
                self.viz.session if self.viz else "",
                self.lgn.host,
                [self.viz.id] if self.viz else [],
            )
        except Exception as exc:
            log.warning("twtml-web unavailable (%s); dashboard disabled", exc)
        return self

    def update(
        self,
        count: int,
        batch: int,
        mse: float,
        real_stdev: float,
        pred_stdev: float,
        real: np.ndarray,
        pred: np.ndarray,
    ) -> None:
        """Push one batch of stats — same call shape as SessionStats.update
        (SessionStats.scala:22-34); mse/stdevs arrive already HALF_UP-rounded
        and are truncated to int for the dashboard like ``.toLong``. Timed
        unconditionally (per batch) for the sideband's publish stage."""
        import time as _time

        tr = _trace.get()
        t0 = _time.perf_counter()
        if not tr.enabled:
            self._update(count, batch, mse, real_stdev, pred_stdev, real, pred)
            _sideband.record_stage(
                "stats_publish", _time.perf_counter() - t0
            )
            return
        # ``batch`` here is the batch's ROW count; the span's ``batch`` arg
        # is the scheduler's sequence number (trace.batch_scope); ``posts`` /
        # ``connects``: the requests this update sent and the connections it
        # opened for them (benchmark/layer_metrics/publish_reuse_share.py)
        with tr.span("stats_publish", rows=int(batch)) as span:
            posts, connects = self.web.requests, self.web.connects
            self._update(count, batch, mse, real_stdev, pred_stdev, real, pred)
            span.add(posts=self.web.requests - posts,
                     connects=self.web.connects - connects)
        _sideband.record_stage("stats_publish", _time.perf_counter() - t0)

    def _series_due(self) -> bool:
        """Degraded-phase load shedding: the per-batch series frame is the
        biggest publish payload; while the health monitor reports a
        DEGRADED transport, ship only every ``SERIES_SHED_EVERY``-th one
        (the scalar stats above keep full per-batch resolution)."""
        monitor = _metrics.get_health_monitor()
        if monitor.phase != monitor.DEGRADED:
            return True
        if self._updates % SERIES_SHED_EVERY == 0:
            return True
        _metrics.get_registry().counter("publish.series_shed").inc()
        return False

    def _update(
        self, count, batch, mse, real_stdev, pred_stdev, real, pred
    ) -> None:
        stats_ok = False
        if self._web_breaker.allow():
            try:
                self.web.stats(
                    count, batch, int(mse), int(real_stdev), int(pred_stdev)
                )
                self._web_breaker.record_success()
                stats_ok = True
            except Exception:
                self._web_breaker.record_failure()
                log.debug("web.stats failed", exc_info=True)
        if stats_ok and self._series_due():
            # feed the built-in dashboard chart (Lightning-free path); the
            # chart window keeps ~400 points, so huge bench-scale batches are
            # subsampled before paying the JSON encode on the hot path
            try:
                self.web.series(
                    list(real[:SERIES_MAX_POINTS]),
                    list(pred[:SERIES_MAX_POINTS]),
                    real_stdev, pred_stdev,
                )
                self._web_breaker.record_success()
            except Exception:
                self._web_breaker.record_failure()
                log.debug("web.series failed", exc_info=True)
        if self.viz is not None and self._lgn_breaker.allow():
            try:
                real_stdev_arr = [real_stdev] * int(batch)
                pred_stdev_arr = [pred_stdev] * int(batch)
                self.lgn.line_streaming(
                    series=[list(real), list(pred), real_stdev_arr, pred_stdev_arr],
                    viz=self.viz,
                )
                self._lgn_breaker.record_success()
            except Exception:
                self._lgn_breaker.record_failure()
                log.debug("lightning append failed", exc_info=True)
        # freshness plane (ISSUE 16): stamp the event→publish lag for every
        # batch delivered since the last stats push — a host-clock read over
        # already-collected lineage records, inside the timed stats_publish
        # window (zero device traffic, no-op when --freshness off)
        from . import freshness as _freshness

        _freshness.record_publish()
        self._updates += 1
        phase = self._updates % METRICS_EVERY
        if phase < len(self._PERIODIC):
            self._PERIODIC[phase](self)

    def publish_metrics(self) -> None:
        """Everything the session owes once a period, NOW and in one call:
        the host gauges, the historian's sample and the ``Metrics`` frame
        (the process metrics registry + fetch-health summary for the
        dashboard's observability panel, /api/metrics), then ``Hosts``,
        ``Tenants``, ``ModelHealth``, ``Freshness`` and ``History``, each a
        best-effort frame of its own where its view is set. The apps call
        it for their final snapshot. ``_update`` never does: it sends the
        same items ONE AN UPDATE, item k on the update whose ordinal is k
        modulo ``METRICS_EVERY``, each with what its view holds then —
        all six in one round made one gap in eight ~7 ms longer, and that
        gap was the p95 of a host-paced run."""
        for item in self._PERIODIC:
            item(self)

    def _frame(self, kind: str, send) -> None:
        """One best-effort observability frame behind the web breaker."""
        if not self._web_breaker.allow():
            return
        try:
            send()
            self._web_breaker.record_success()
        except Exception:
            self._web_breaker.record_failure()
            log.debug("web.%s failed", kind, exc_info=True)

    def _publish_registry(self) -> None:
        """Item 0: the host gauges, the historian's sample, and the
        ``Metrics`` frame with derived per-histogram p50/p95/p99 (the
        latency tile)."""
        # host-process gauges, sampled once a period (ISSUE 8 satellite):
        # makes host RSS growth visible on every /api/metrics payload and post-mortem bundle —
        # statm reads, no device traffic
        try:
            from ..utils.rss import rss_mb, slope_mb_per_min

            reg = _metrics.get_registry()
            cur_mb = rss_mb()
            reg.gauge("host.rss_mb").set(round(cur_mb, 1))
            reg.gauge("host.uptime_s").set(
                round(_time_mod.monotonic() - _PROCESS_START_S, 1)
            )
            # continuous leak-rate gauge (ISSUE 16 satellite): least-squares
            # MB/min over the rolling once-a-period samples — the soak
            # estimator, live, so a host-memory leak shows as a rate
            # without a dedicated soak run
            self._rss_samples.append((_time_mod.monotonic(), cur_mb))
            reg.gauge("host.rss_slope_mb_per_min").set(
                round(slope_mb_per_min(self._rss_samples), 3)
            )
        except Exception:
            pass
        # telemetry historian (ISSUE 20): THE sampling seam — lawcheck
        # TW010 pins historian.sample() to this file, and this item is its
        # one caller, once a period. It snapshots the registry/health/stage
        # views (pure host reads, zero device traffic); no-op when
        # --history off. BEFORE the breaker gate: the historian writes to
        # local disk, so a dead dashboard must not stop the durable timeline
        from . import historian as _historian

        _historian.sample()

        def send():
            snap = _metrics.get_registry().snapshot()
            # ship the derived quantiles, not the raw buckets: the
            # dashboard tile wants three numbers per histogram, and the
            # wire stays small
            hists = {
                name: {
                    k: h[k] for k in ("count", "mean", "p50", "p95", "p99")
                }
                for name, h in snap["histograms"].items()
            }
            self.web.metrics(
                snap["counters"], snap["gauges"],
                _metrics.get_health_monitor().summary(),
                histograms=hists,
            )

        self._frame("metrics", send)

    def _publish_hosts(self) -> None:
        """The per-host ``Hosts`` view, when a lockstep sideband is live."""
        view = _sideband.last_hosts()
        if view is None:
            return

        def send():
            # elastic membership summary rides the same Hosts frame
            # (registry gauges the membership plane maintains; zero
            # when the run is not elastic)
            msnap = _metrics.get_registry().snapshot()
            gauges = msnap["gauges"]
            counters = msnap["counters"]
            self.web.hosts(
                view["hosts"], view["straggler"], view["stage"],
                view["skew_ms"],
                epoch=int(gauges.get("elastic.epoch", -1)),
                live_hosts=int(gauges.get("elastic.live_hosts", 0)),
                departed=int(counters.get("elastic.hosts_departed", 0)),
                rejoined=int(counters.get("elastic.hosts_rejoined", 0)),
                lead_uid=int(gauges.get("elastic.lead_uid", -1)),
            )

        self._frame("hosts", send)

    def _publish_tenants(self) -> None:
        """Per-tenant model-plane view (telemetry/tenants.py — recorded by
        the tenant handle adapter from the already-fetched stacked
        StepOutput; empty on single-tenant runs)."""
        from . import tenants as _tenants

        view = _tenants.last_tenants()
        if view is not None:
            self._frame("tenants", lambda: self.web.tenants(
                view["tenants"], view["gating"], view["active"]))

    def _publish_model_health(self) -> None:
        """Model-health view (telemetry/modelwatch.py — derived from the
        in-step quality vector the pipeline already fetched; empty until
        a --modelWatch tick has been recorded)."""
        from . import modelwatch as _modelwatch

        view = _modelwatch.last_model()
        if view is not None:
            self._frame("model_health", lambda: self.web.model_health(
                level=view["level"],
                drift_score=view["drift_score"],
                loss_trend=view["loss_trend"],
                weight_norm=view["weight_norm"],
                update_norm=view["update_norm"],
                grad_norm=view["grad_norm"],
                mse=view["mse"],
                tenants=view["tenants"],
                episodes=view["episodes"],
            ))

    def _publish_freshness(self) -> None:
        """End-to-end freshness view (telemetry/freshness.py — derived from
        lineage records stamped at seams the pipeline already crosses;
        None until a delivery has been observed or when --freshness off)."""
        from . import freshness as _freshness

        view = _freshness.last_freshness()
        if view is not None:
            self._frame("freshness", lambda: self.web.freshness(view))

    def _publish_history(self) -> None:
        """The historian's view as of its latest sample (item 0's, up to
        five updates back); None when --history off or nothing sampled."""
        from . import historian as _historian

        view = _historian.last_history()
        if view is not None:
            self._frame("history", lambda: self.web.history(view))

    # what the session owes once every ``METRICS_EVERY`` updates, in the
    # order ``publish_metrics`` sends it: item k goes out on the update
    # whose ordinal is k modulo ``METRICS_EVERY``, after that update's own
    # POSTs, and the phases past the last item stay plain
    _PERIODIC = (
        _publish_registry,
        _publish_hosts,
        _publish_tenants,
        _publish_model_health,
        _publish_freshness,
        _publish_history,
    )
