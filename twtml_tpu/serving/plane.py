"""The serving plane: bounded-latency request coalescing over the predict
engine, with depth-K pipelined result fetches.

Why this shape: a host fetch is a latency-bound REQUEST — naive per-request
serving pays a full dispatch + fetch round trip PER QUERY, while CONCURRENT
``device_get``s overlap their latencies. So the plane:

- **coalesces** requests into one featurize + ONE dispatch per batch: admit
  until ``--serveBatchRows`` rows or ``--serveMaxWaitMs`` since the oldest
  admitted request (the bounded-latency knob) — per-dispatch and
  per-fetch overheads amortize over the batch's rows;
- **pipelines** the result fetches through the EXISTING
  ``apps/common.FetchPipeline`` at ``--serveDepth`` (default 8): micro-batch
  N+1..N+K dispatch while batch N's predictions are still in flight, so
  fetch latency amortizes across in-flight batches. Dispatch and any
  ``device_put`` stay on the ONE serve-loop thread (lawcheck TW003);
  fetches are what the pool exists to issue;
- **hot-swaps** snapshots ATOMICALLY: the promoter hands a new snapshot to
  ``hot_swap`` (any thread), the serve loop installs it BETWEEN dispatches —
  a batch in flight completes against the weights it dispatched with, so no
  request is ever served by a half-applied swap (each batch carries its
  dispatch-time snapshot step into its response);
- **fails loudly, never hangs**: the FetchPipeline's FetchWatchdog owns
  stalled/failed fetches (--chaos injectable) — retries, then a clean abort
  that REJECTS every in-flight and queued request future instead of leaving
  clients waiting on a wedged fetch.

The train path is untouched: the plane reads verified snapshots from DISK
(checkpoint handoff), issues zero fetches against a co-located trainer's
device state, and shares no mutable state with the train loop.
"""

from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future

import numpy as np

from ..apps.common import FetchAbort, FetchPipeline
from ..telemetry import blackbox as _blackbox
from ..telemetry import metrics as _metrics
from ..utils import get_logger
from ..utils.clock import now_ms, now_s
from .engine import PredictEngine

log = get_logger("serving.plane")

# rolling completion window for the QPS/latency view (stats()); bounded so a
# days-long server never grows it
COMPLETION_WINDOW = 4096
QPS_WINDOW_S = 30.0


class _Request:
    __slots__ = ("statuses", "future", "t_arrival")

    def __init__(self, statuses, future, t_arrival):
        self.statuses = statuses
        self.future = future
        self.t_arrival = t_arrival


class ServingPlane:
    """Request front end over one ``PredictEngine``. ``submit`` is
    thread-safe (the web server's event loop and load generators call it);
    featurize/dispatch/swap all happen on the single serve-loop thread."""

    def __init__(
        self,
        snapshot,
        *,
        num_text_features: int = 1000,
        batch_rows: int = 256,
        max_wait_ms: float = 5.0,
        depth: int = 8,
        model_cls=None,
        tenant_key: str = "hash",
        dtype=None,
        featurizer=None,
        engine: "PredictEngine | None" = None,
        stale_slo_s: float = 0.0,
    ) -> None:
        from ..features.featurizer import Featurizer

        self.batch_rows = max(1, int(batch_rows))
        self.max_wait_s = max(0.0, float(max_wait_ms) / 1e3)
        self.depth = max(1, int(depth))
        self._engine = engine if engine is not None else PredictEngine(
            num_text_features=num_text_features,
            num_tenants=snapshot.num_tenants,
            tenant_key=tenant_key,
            dtype=dtype,
            model_cls=model_cls,
        )
        self._feat = featurizer if featurizer is not None else Featurizer(
            num_text_features=num_text_features
        )
        self._cond = threading.Condition()
        self._queue: "collections.deque[_Request]" = collections.deque()
        self._inflight: "set[_Request]" = set()
        self._pending_snapshot = None
        self._snapshot_level = ""
        self._stopping = False
        self.failed = False
        self._thread: "threading.Thread | None" = None
        reg = _metrics.get_registry()
        self._req_count = reg.counter("serve.requests")
        self._row_count = reg.counter("serve.rows")
        self._err_count = reg.counter("serve.errors")
        self._batch_count = reg.counter("serve.batches")
        self._swap_count = reg.counter("serve.hot_swaps")
        self._queue_gauge = reg.gauge("serve.queue_depth")
        self._step_gauge = reg.gauge("serve.snapshot_step")
        # serving staleness (ISSUE 16): installed-at stamp through the
        # TWTML_NOW_MS seam → serving.snapshot_age_s on /api/serving and a
        # dispatch-time model-staleness figure in every predict response;
        # --servingStaleSloS > 0 arms a warn-only breach episode
        self._age_gauge = reg.gauge("serving.snapshot_age_s")
        self._stale_breach_count = reg.counter("serve.stale_breaches")
        self.stale_slo_s = max(0.0, float(stale_slo_s or 0.0))
        self._installed_at_s = -1.0
        self._in_stale_episode = False
        self._latency = reg.histogram("serve.latency_s")
        self._batch_fill = reg.histogram("serve.batch_rows")
        # per-tenant served-row totals (the dashboard's per-tenant query
        # tiles); None on the single-model plane
        self._tenant_rows = (
            np.zeros((self._engine.num_tenants,), np.int64)
            if self._engine.num_tenants > 1 else None
        )
        # rolling completion record for the QPS view: (t_done, rows)
        self._completions: "collections.deque[tuple[float, int]]" = (
            collections.deque(maxlen=COMPLETION_WINDOW)
        )
        self._started_s = time.monotonic()
        # depth-K pipelined result fetches, reused verbatim from the
        # train path (apps/common.py); the
        # --chaos fetch/step injection points and the FetchWatchdog come
        # with it, so a wedged fetch aborts cleanly instead of hanging
        # every client
        self._pipe = FetchPipeline(
            self._engine, self._deliver, depth=self.depth,
            # the lean one-buffer wire, exactly like the train path (the
            # tenant engine's pack IS its routed tenant wire)
            pack=self._engine.accepts_packed,
            abort=self._on_abort,
        )
        self._install(snapshot)

    @classmethod
    def from_conf(cls, conf, snapshot, model_cls=None, featurizer=None,
                  engine=None):
        import jax.numpy as jnp

        return cls(
            snapshot,
            num_text_features=conf.numTextFeatures,
            batch_rows=int(getattr(conf, "serveBatchRows", 256) or 256),
            max_wait_ms=float(getattr(conf, "serveMaxWaitMs", 5.0) or 0.0),
            depth=int(getattr(conf, "serveDepth", 8) or 8),
            model_cls=model_cls,
            tenant_key=getattr(conf, "tenantKey", "hash"),
            dtype=jnp.dtype(getattr(conf, "dtype", "float32")),
            featurizer=featurizer,
            engine=engine,
            stale_slo_s=float(getattr(conf, "servingStaleSloS", 0.0) or 0.0),
        )

    # -- request intake ------------------------------------------------------
    @property
    def snapshot_step(self) -> int:
        return self._engine.snapshot_step

    @property
    def num_tenants(self) -> int:
        return self._engine.num_tenants

    def submit(self, statuses) -> Future:
        """Enqueue one predict request (a list of featurizer ``Status``
        rows; see ``statuses_from_rows`` for the JSON face). Returns a
        future resolving to ``{"predictions": [...], "snapshot_step": N}``.
        Thread-safe; never blocks on device work."""
        fut: Future = Future()
        if self.failed:
            fut.set_exception(RuntimeError(
                "serving plane aborted (fetch watchdog); restart the server"
            ))
            return fut
        if self._stopping:
            fut.set_exception(RuntimeError("serving plane is shutting down"))
            return fut
        statuses = list(statuses)
        if not statuses:
            fut.set_result({
                "predictions": [], "snapshot_step": self.snapshot_step,
            })
            return fut
        if len(statuses) > self.batch_rows:
            fut.set_exception(ValueError(
                f"request carries {len(statuses)} rows; the serve batch "
                f"bucket is {self.batch_rows} (--serveBatchRows) — split "
                "the request"
            ))
            return fut
        self._req_count.inc()
        self._row_count.inc(len(statuses))
        req = _Request(statuses, fut, time.perf_counter())
        with self._cond:
            self._queue.append(req)
            self._queue_gauge.set(len(self._queue))
            self._cond.notify_all()
        return fut

    @staticmethod
    def statuses_from_rows(rows):
        """The JSON request face → featurizer ``Status`` rows. Each row is
        either a plain object ``{"text": ..., "followers_count": ...,
        "favourites_count": ..., "friends_count": ..., "created_at_ms": ...,
        "retweet_count": ...}`` (a bare string is shorthand for
        ``{"text": ...}``) describing the ORIGINAL tweet the model scores,
        or a full standard-API tweet JSON carrying ``retweeted_status`` —
        then the reference's exact object path (``Status.from_json``)
        parses it. ``created_at_ms`` defaults to NOW (age feature 0) for
        queries about fresh tweets — read through the TWTML_NOW_MS seam so
        pinned replays see pinned ages (utils/clock)."""
        from ..features.featurizer import Status

        default_created_ms = now_ms()
        out = []
        for row in rows:
            if isinstance(row, str):
                row = {"text": row}
            if not isinstance(row, dict):
                raise ValueError(f"bad predict row: {row!r}")
            if row.get("retweeted_status"):
                status = Status.from_json(row)
            else:
                original = Status(
                    text=str(row.get("text", "")),
                    retweet_count=int(row.get("retweet_count") or 0),
                    followers_count=int(row.get("followers_count") or 0),
                    favourites_count=int(row.get("favourites_count") or 0),
                    friends_count=int(row.get("friends_count") or 0),
                    created_at_ms=int(
                        row.get("created_at_ms") or default_created_ms
                    ),
                    lang=str(row.get("lang") or ""),
                )
                status = Status(
                    text=original.text, retweeted_status=original,
                    lang=original.lang,
                )
            out.append(status)
        return out

    # -- snapshot management -------------------------------------------------
    def hot_swap(self, snapshot) -> None:
        """Stage a snapshot for atomic installation. Callable from any
        thread (the promoter's); the serve loop applies it BETWEEN
        dispatches, so an in-flight batch always completes against the
        weights it dispatched with — no request is ever torn across two
        snapshots."""
        with self._cond:
            self._pending_snapshot = snapshot
            self._cond.notify_all()

    def _install(self, snapshot) -> None:
        self._engine.set_snapshot(snapshot)
        self._snapshot_level = snapshot.quality_level
        self._step_gauge.set(self._engine.snapshot_step)
        # snapshot-age epoch: the swap moment through the pinnable clock
        # seam (TW006), so replayed runs see replayed ages
        self._installed_at_s = now_s()
        self._age_gauge.set(0.0)

    def _apply_pending_swap(self) -> None:
        with self._cond:
            snap, self._pending_snapshot = self._pending_snapshot, None
        if snap is not None:
            self._install(snap)
            self._swap_count.inc()
            log.info("hot-swapped serving snapshot to step %d", snap.step)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "ServingPlane":
        self._thread = threading.Thread(
            target=self._loop, name="twtml-serve-loop", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop intake, drain queued + in-flight requests, join the loop."""
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=60)

    def warmup(self) -> None:
        """Compile + fetch one all-padding-shaped batch BEFORE traffic so
        the first request doesn't pay the XLA compile (the serve-side
        analog of ``apps/common.warmup_compile``; the ragged units bucket
        is data-dependent, so real batches may still compile one or two
        more buckets in-flight)."""
        import jax

        from ..features.featurizer import Status

        warm = Status(text="warmup", retweeted_status=Status(
            text="warmup", created_at_ms=now_ms(),
        ))
        batch = self._featurize([warm])
        wire = self._engine.pack_for_wire(batch) if (
            self._engine.accepts_packed
        ) else batch
        jax.device_get(self._engine.step(wire))  # lawcheck: disable=TW002 -- one-off pre-traffic compile warmup on the serve-loop thread, before the FetchPipeline takes over; never on a per-request path

    # -- the serve loop -------------------------------------------------------
    def _featurize(self, statuses):
        return self._feat.featurize_batch_ragged(
            statuses, row_bucket=self.batch_rows, pre_filtered=True,
        )

    def _take_group(self):
        """Admit requests until the row bucket fills or the oldest admitted
        request has waited ``max_wait_s`` — the bounded-latency coalescer.
        Returns a list of requests, or None on an idle/stop tick (the
        caller polls the fetch pipeline then)."""
        group: "list[_Request]" = []
        rows = 0
        with self._cond:
            while True:
                while self._queue and (
                    rows + len(self._queue[0].statuses) <= self.batch_rows
                ):
                    req = self._queue.popleft()
                    group.append(req)
                    rows += len(req.statuses)
                self._queue_gauge.set(len(self._queue))
                if rows >= self.batch_rows or (group and self._queue):
                    # bucket full, or the next request no longer fits —
                    # dispatch what we have (never split one request)
                    return group
                if group:
                    wait_end = group[0].t_arrival + self.max_wait_s
                    left = wait_end - time.perf_counter()
                    if left <= 0 or self._stopping:
                        return group
                    self._cond.wait(timeout=left)
                    continue
                if self._stopping or self._pending_snapshot is not None:
                    return None
                # idle: short tick while fetches are in flight (results
                # must deliver promptly), longer when fully quiet
                self._cond.wait(
                    timeout=0.002 if self._pipe.pending_fetches else 0.05
                )
                if not self._queue:
                    return None

    def _loop(self) -> None:
        while True:
            group = self._take_group()
            if group is None:
                self._apply_pending_swap()
                try:
                    self._pipe.poll()
                except FetchAbort:
                    self._abort_requests()
                if self._stopping and not self._queue:
                    break
                if self.failed:
                    break
                continue
            # swaps land BETWEEN dispatches — the atomic hot-swap point
            self._apply_pending_swap()
            for req in group:
                self._inflight.add(req)
            statuses = [s for req in group for s in req.statuses]
            batch = self._featurize(statuses)
            self._batch_fill.observe(len(statuses))
            self._batch_count.inc()
            try:
                # ONE dispatch per coalesced batch; the snapshot step rides
                # the payload so the response names the weights that served
                # it even if a swap lands before the fetch returns
                self._pipe.on_batch(
                    batch,
                    (group, self._engine.snapshot_step,
                     self._installed_at_s),
                )
            except FetchAbort:
                self._abort_requests()
                break
        try:
            self._pipe.flush()
        except Exception:
            log.exception("serve pipeline flush failed")
        self._abort_requests(
            reason="serving plane stopped" if not self.failed else None
        )

    def _deliver(self, host_out, batch, payload, at_boundary=True) -> None:
        """FetchPipeline handler: slice the batch's predictions back to the
        requests that rode it and resolve their futures."""
        group, step, *rest = payload
        installed = rest[0] if rest else self._installed_at_s
        # dispatch-time model staleness: how old the serving weights were
        # when THIS batch dispatched — the per-response freshness figure
        # (ISSUE 16); a swap landing mid-flight doesn't rewrite history
        staleness = (
            max(0.0, now_s() - installed) if installed >= 0.0 else -1.0
        )
        preds = self._engine.predictions_for(host_out, batch)
        counts = self._engine.tenant_row_counts(batch)
        if counts is not None:
            self._tenant_rows += counts
        now = time.perf_counter()
        offset = 0
        for req in group:
            n = len(req.statuses)
            self._inflight.discard(req)
            self._latency.observe(now - req.t_arrival)
            self._completions.append((time.monotonic(), n))
            req.future.set_result({
                "predictions": [float(v) for v in preds[offset:offset + n]],
                "snapshot_step": int(step),
                "model_staleness_s": round(staleness, 3),
            })
            offset += n

    def _on_abort(self) -> None:
        self.failed = True
        with self._cond:
            self._cond.notify_all()

    def _abort_requests(self, reason: "str | None" = None) -> None:
        """Reject every in-flight and queued request future — the fetch
        watchdog already logged WHY; clients get an error, never a hang."""
        with self._cond:
            pending = list(self._queue)
            self._queue.clear()
            self._queue_gauge.set(0)
        stranded = pending + list(self._inflight)
        self._inflight.clear()
        if not stranded:
            return
        why = reason or (
            "serving fetch aborted by the watchdog (wedged transport); "
            "see the critical log"
        )
        for req in stranded:
            self._err_count.inc()
            if not req.future.done():
                req.future.set_exception(RuntimeError(why))
        log.warning("rejected %d stranded predict request(s): %s",
                    len(stranded), why)

    # -- telemetry view -------------------------------------------------------
    def stats(self) -> dict:
        """The ``Serving`` jsonClass view (QPS over the rolling window,
        latency quantiles from the serve histogram, active snapshot, per-
        tenant served rows) — plain host bookkeeping, zero device work."""
        now = time.monotonic()
        window = min(QPS_WINDOW_S, max(now - self._started_s, 1e-3))
        lo = now - window
        reqs = rows = 0
        for t_done, n in reversed(self._completions):
            if t_done < lo:
                break
            reqs += 1
            rows += n
        tenants = []
        if self._tenant_rows is not None:
            tenants = [
                {"tenant": m, "rows": int(r)}
                for m, r in enumerate(self._tenant_rows)
            ]
        age = (
            max(0.0, now_s() - self._installed_at_s)
            if self._installed_at_s >= 0.0 else -1.0
        )
        self._age_gauge.set(round(age, 1))
        if self.stale_slo_s > 0.0 and age > self.stale_slo_s:
            if not self._in_stale_episode:
                # one blackbox event + counter per breach episode — the
                # warn-only PR 8 shape (no serving behavior change)
                self._in_stale_episode = True
                self._stale_breach_count.inc()
                _blackbox.record(
                    "serving_stale_breach", age_s=round(age, 1),
                    slo_s=self.stale_slo_s, step=int(self.snapshot_step),
                )
                log.warning(
                    "serving snapshot is stale: age %.1f s > SLO %.1f s "
                    "(step %d) — promotion/handoff may be wedged",
                    age, self.stale_slo_s, self.snapshot_step,
                )
        else:
            self._in_stale_episode = False
        view = {
            "qps": round(reqs / window, 2),
            "rowsPerSec": round(rows / window, 1),
            "p50Ms": round(self._latency.percentile(0.50) * 1e3, 2),
            "p95Ms": round(self._latency.percentile(0.95) * 1e3, 2),
            "p99Ms": round(self._latency.percentile(0.99) * 1e3, 2),
            "snapshotAgeS": round(age, 1),
            "snapshotStep": int(self.snapshot_step),
            "level": self._snapshot_level,
            "requests": int(self._req_count.snapshot()),
            "rows": int(self._row_count.snapshot()),
            "errors": int(self._err_count.snapshot()),
            "tenants": tenants,
        }
        # champion/challenger slice (serving/abtest.py): the live champion
        # + per-tenant shadow divergence ride the same view, so the router
        # and the dashboard learn the A/B state from the health check they
        # already make
        ab = getattr(self._engine, "abtest_view", None)
        if ab is not None:
            view.update(ab())
        return view
