"""Streaming k-means entry point (reference: KMeans.scala:49-170).

Pipeline kept equivalent: retweets only (``isRetweet`` — NO retweet-interval
filter here, unlike the linear app, KMeans.scala:77-80), featurized to the
dense pair (original's retweetCount, original's followersCount)
(KMeans.scala:19-33), per-batch StandardScaler(false, true), manual
``update(scaled, decayFactor, timeUnit)`` on a k=3 half-life-5-batches model
with random 2-d centers (KMeans.scala:69-73,103-105), then per-batch debug
output of centers and assignments (KMeans.scala:118-127). The live cluster
scatter chart the reference sketches and leaves commented out
(KMeans.scala:89,129-132) is implemented here: per-batch points + predicted
cluster labels stream to a Lightning scatter viz, best-effort like all
telemetry.

Run: ``python -m twtml_tpu.apps.kmeans --source replay --replayFile ...``
"""

from __future__ import annotations

import queue
import sys
import threading

import jax
import numpy as np

from ..config import ConfArguments
from ..features.batch import pad_row_count
from ..features.featurizer import Status
from ..models.kmeans import StreamingKMeans
from ..ops.scaler import standard_scale
from ..streaming.context import StreamingContext
from ..streaming.sources import Source
from ..telemetry.lightning import CHART_MAX_POINTS, Lightning
from ..utils import get_logger
from .common import (
    AppCheckpoint,
    ProcessRecycler,
    build_mesh,
    build_source,
    init_distributed,
    install_blackbox,
    install_chaos,
    install_historian,
    install_trace,
    select_backend,
)

log = get_logger("apps.kmeans")

NUM_DIMENSIONS = 2  # KMeans.scala:57
NUM_CLUSTERS = 3  # KMeans.scala:58
CHART_FAILURE_LIMIT = 5  # consecutive append failures before giving up


def _start_chart_worker(conf) -> "queue.Queue":
    """Daemon thread owning every Lightning call for the cluster chart.
    Returns the frame queue (drop-oldest, depth 2); the worker creates the
    session + scatter viz, then streams frames, giving up for good after
    CHART_FAILURE_LIMIT consecutive failures."""
    q: "queue.Queue" = queue.Queue(maxsize=2)

    def _worker() -> None:
        try:
            lgn = Lightning(host=conf.lightning)
            lgn.create_session(conf.appName())
            viz = lgn.scatter_streaming([], [])
            log.info(
                "lightning cluster chart: %s/visualizations/%s",
                conf.lightning, viz.id,
            )
        except Exception as exc:
            log.warning("lightning unavailable (%s); cluster chart disabled", exc)
            return
        failures = 0
        while failures < CHART_FAILURE_LIMIT:
            x, y, label = q.get()
            try:
                lgn.scatter_streaming(x, y, label=label, viz=viz)
                failures = 0
            except Exception as exc:
                failures += 1
                log.debug("lightning append failed (%s)", exc)
        log.warning("cluster chart disabled after repeated append failures")

    threading.Thread(target=_worker, daemon=True).start()
    return q


def featurize(status: Status) -> np.ndarray:
    """Dense (retweetCount, followersCount) of the original tweet
    (KMeans.scala:19-33)."""
    original = status.retweeted_status
    return np.array(
        [float(original.retweet_count), float(original.followers_count)],
        dtype=np.float32,
    )


def run(conf: ConfArguments, max_batches: int = 0, wall_clock: bool = True) -> dict:
    if getattr(conf, "elastic", "off") == "on":
        # the k-means plane's raw-stream handler owns its own global
        # assembly; the elastic rebuild contract (model.rebuild + the
        # broadcast resync) is wired for the SGD-family apps only
        raise SystemExit(
            "--elastic on is wired for the SGD entry points (linear, "
            "logistic); the k-means plane keeps the abort-on-peer-loss "
            "behavior for now"
        )
    lead = init_distributed(conf)  # every entry point forms the group
    device = select_backend(conf)
    install_trace(conf)
    install_chaos(conf)
    install_blackbox(conf)  # crash flight recorder (apps/common)
    install_historian(conf)  # telemetry historian (--history, apps/common)
    multihost = jax.process_count() > 1
    if multihost and conf.batchBucket <= 0:
        raise SystemExit(
            "multi-host k-means needs --batchBucket: every host must "
            "dispatch the same fixed-shape collective program each tick"
        )
    # k-means keeps ALL retweets (isRetweet only, NO retweet-count interval —
    # KMeans.scala:77-80): block ingest overrides the parser's interval
    # filter; isRetweet filtering is inherent (rows without a
    # retweeted_status never emit)
    source: Source = build_source(
        conf, allow_block=True, block_interval=(0, 2**62)
    )

    # the scatter chart KMeans.scala:86-96 sets up (and :129-132 appends to,
    # commented out there) — best-effort, training survives telemetry
    # outages. ALL chart network IO (create + per-batch appends) lives on one
    # daemon thread behind a drop-oldest queue: urlopen's timeout doesn't
    # bound DNS resolution, so neither startup nor the batch loop may ever
    # wait on the resolver; a slow chart just skips frames. One chart per
    # RUN: the lead owns it (multi-host followers train silently).
    chart_q = _start_chart_worker(conf) if lead else None

    # mesh-sharded clustering on several devices / --master local[N]: rows
    # shard over 'data', per-center sums psum over ICI (models/kmeans.py)
    model = (
        StreamingKMeans(mesh=build_mesh(conf, what="clustering"))
        .set_k(NUM_CLUSTERS)
        .set_half_life(5, "batches")
        .set_random_centers(NUM_DIMENSIONS, 0.0)
    )
    scale = jax.jit(standard_scale)
    ssc = StreamingContext(
        batch_interval=conf.seconds,
        # bounded intake backpressure — same guard as the SGD apps; the
        # k-means stream has no SGD sentinel (its state is decayed
        # averages, not gradient-updated weights)
        max_queue_rows=conf.effective_max_queue_rows(),
        shed_policy=conf.shedPolicy,
    )
    totals = {"count": 0, "batches": 0, "device": device}

    # checkpoint/resume of the cluster state — same upgrade as the SGD apps
    # (SURVEY.md §5.4); state = centers + per-center decay weights
    ckpt = AppCheckpoint(
        conf,
        get_state=lambda: {
            "centers": model.latest_centers,
            "weights": np.asarray(model.cluster_weights),
        },
        set_state=lambda st: model.set_initial_centers(
            st["centers"], st["weights"]
        ),
        totals=totals,
        lead=lead,
    )
    recycler = ProcessRecycler(conf, ckpt, totals)

    # multi-host: the fixed per-host row shape (lockstep drains cap at it)
    local_bucket = (
        pad_row_count(
            conf.batchBucket, conf.batchBucket,
            max(1, model.num_data // jax.process_count()),
        )
        if multihost
        else 0
    )

    from ..utils.rss import RssWatchdog

    watchdog = RssWatchdog()  # host-memory growth guard (utils/rss.py)

    def on_batch_multihost(statuses: list[Status], _batch_time) -> None:
        """Per-host sharded k-means batch: local rows → one global
        row-sharded point matrix (`host_local_rows_to_global`), the
        per-batch StandardScaler computed GLOBALLY (jit over the global
        array — XLA inserts the mean/var collectives), and the mesh
        update's per-center psums span every host. A host with no rows
        still dispatches (all-padding — the update is a state no-op when
        the GLOBAL batch is empty, models/kmeans.py)."""
        from jax.experimental import multihost_utils

        from ..parallel.distributed import (
            host_local_rows_to_global,
            local_rows,
        )

        retweets = [s for s in statuses if s.is_retweet]
        if len(retweets) > local_bucket:
            log.error(
                "dropping %d rows over --batchBucket in multi-host "
                "lockstep (raise --batchBucket)",
                len(retweets) - local_bucket,
            )
            retweets = retweets[:local_bucket]
        n = len(retweets)
        pts = np.zeros((local_bucket, NUM_DIMENSIONS), np.float32)
        if n:
            pts[:n] = np.stack([featurize(s) for s in retweets])
        mask = np.zeros((local_bucket,), np.float32)
        mask[:n] = 1.0
        g_pts = host_local_rows_to_global(pts, model.mesh)
        g_mask = host_local_rows_to_global(mask, model.mesh)
        scaled_g = scale(g_pts, g_mask)
        assign = model.update(scaled_g, g_mask)[:n]  # this host's rows
        centers = model.latest_centers
        sl = local_rows(scaled_g)[:n]
        pred = (
            np.argmin(
                ((sl[:, None, :] - centers[None]) ** 2).sum(-1), axis=1
            )
            if n
            else np.zeros((0,), np.int64)
        )
        # ONE tiny allgather agrees global count + global cluster sizes
        # (every host calls it — lockstep keeps the order aligned)
        agg = multihost_utils.process_allgather(
            np.concatenate(
                [[n], np.bincount(pred, minlength=NUM_CLUSTERS)]
            ).astype(np.int64)
        ).sum(axis=0)
        n_global, sizes = int(agg[0]), agg[1:]
        if n_global == 0:
            log.debug("batch: 0 (global)")  # the update was a state no-op
            return
        totals["count"] += n_global
        totals["batches"] += 1
        watchdog.tick()
        if lead:
            print(
                f"count: {totals['count']}  batch: {n_global}  "
                f"centers: {np.round(centers, 3).tolist()}  "
                f"sizes: {sizes.tolist()}",
                flush=True,
            )
            log.debug("assignments: %s", assign.tolist())
            m = min(n, CHART_MAX_POINTS)
            try:
                chart_q.put_nowait((sl[:m, 0], sl[:m, 1], pred[:m]))
            except queue.Full:
                pass
        ckpt.maybe_save(totals)
        recycler.check()
        if max_batches and totals["batches"] >= max_batches:
            ssc.request_stop()

    def _rows_for(n: int) -> int:
        """The central padding policy (features/batch.py): power-of-two
        bucket, rounded to the mesh's data-axis multiple."""
        return pad_row_count(n, 0, model.num_data)

    def on_batch(statuses: list[Status], _batch_time) -> None:
        from ..features.blocks import COL_FOLLOWERS, COL_LABEL, ParsedBlock, merge_blocks

        if statuses and isinstance(statuses[0], ParsedBlock):
            # block ingest: both k-means dimensions are numeric columns —
            # the whole featurization is one vectorized slice
            block = merge_blocks(statuses)
            n = block.rows
            if n == 0:
                log.debug("batch: 0")
                return
            rows = _rows_for(n)
            pts = np.zeros((rows, NUM_DIMENSIONS), np.float32)
            pts[:n, 0] = block.numeric[:, COL_LABEL]
            pts[:n, 1] = block.numeric[:, COL_FOLLOWERS]
        else:
            retweets = [s for s in statuses if s.is_retweet]  # KMeans.scala:77-80
            if not retweets:
                log.debug("batch: 0")
                return
            n = len(retweets)
            rows = _rows_for(n)
            pts = np.zeros((rows, NUM_DIMENSIONS), np.float32)
            pts[:n] = np.stack([featurize(s) for s in retweets])
        mask = np.zeros((rows,), np.float32)
        mask[:n] = 1.0
        scaled = np.asarray(scale(pts, mask))
        assign = model.update(scaled, mask)[:n]
        pred = model.predict(scaled[:n])
        totals["count"] += n
        totals["batches"] += 1
        watchdog.tick()
        centers = model.latest_centers
        print(
            f"count: {totals['count']}  batch: {n}  "
            f"centers: {np.round(centers, 3).tolist()}  "
            f"sizes: {np.bincount(pred, minlength=NUM_CLUSTERS).tolist()}",
            flush=True,
        )
        log.debug("assignments: %s", assign.tolist())
        # subsample like session_stats.py: don't pay a multi-MB JSON encode
        # per batch at bench-scale batch sizes; drop the frame if the chart
        # worker is behind (latest batch wins)
        m = min(n, CHART_MAX_POINTS)
        try:
            chart_q.put_nowait((scaled[:m, 0], scaled[:m, 1], pred[:m]))
        except queue.Full:
            pass
        ckpt.maybe_save(totals)
        recycler.check()
        if max_batches and totals["batches"] >= max_batches:
            ssc.request_stop()

    # --batchBucket caps back-to-back drains in single-host mode too, so
    # replay batching is deterministic (and the multi-host fixed shape)
    ssc.raw_stream(
        source,
        row_bucket=local_bucket if multihost else max(0, conf.batchBucket),
    ).foreach_batch(on_batch_multihost if multihost else on_batch)
    try:
        if wall_clock or multihost:
            # multi-host always uses the lockstep scheduler (collective
            # cadence agreement), whatever the batch interval
            ssc.start(lockstep=multihost)
            try:
                ssc.await_termination()
            except KeyboardInterrupt:
                pass
            finally:
                ssc.stop()
        else:
            ssc.run_to_completion()
    finally:
        # like the sibling apps: the shutdown save must survive a handler
        # exception or Ctrl-C (run_to_completion raises on the main thread)
        from ..telemetry import trace as pipeline_trace

        pipeline_trace.uninstall()  # flush + close the --trace file
        ckpt.final_save(totals)
        from ..telemetry import historian as _historian_mod

        # perfGuard baseline stamps on CLEAN shutdown only
        if not ssc.failed:
            _historian_mod.stamp_baseline()
        _historian_mod.uninstall()
    if ssc.failed:
        raise RuntimeError(
            "run aborted by a runtime guard — lockstep peer loss or a fetch "
            "watchdog abort (see critical log above); progress up to the "
            "failure is checkpointed"
        )
    return totals


def main(argv=None) -> None:
    conf = (
        ConfArguments()
        .setAppName("twitter-stream-ml-kmeans")
        .parse(list(sys.argv[1:] if argv is None else argv))
    )
    totals = run(conf)
    log.info("done: %s tweets in %s batches", totals["count"], totals["batches"])


if __name__ == "__main__":
    main()
