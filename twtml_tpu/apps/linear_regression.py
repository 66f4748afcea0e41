"""Streaming linear-regression entry point — the flagship application.

Wires the same pipeline as the reference's ``LinearRegression.main``
(LinearRegression.scala:12-91): config → session stats → featurizer → model →
streaming context → source → per-batch predict/stats/train → run. The
reference's two registered outputs (stats ``foreachRDD`` then ``trainOn``)
collapse into one fused device step that scores with pre-update weights and
trains in the same XLA program.

Run: ``python -m twtml_tpu.apps.linear_regression --source replay \
      --replayFile tests/data/tweets.jsonl --seconds 1``
"""

from __future__ import annotations

import sys

import numpy as np

from ..config import ConfArguments
from ..features.featurizer import Featurizer
from ..streaming.context import StreamingContext
from ..telemetry.session_stats import SessionStats
from ..utils import get_logger, round_half_up

# shared app runtime (apps/common.py); re-exported here because this is the
# flagship entry other modules historically import the helpers from
from .common import (  # noqa: F401
    AppCheckpoint,
    ProcessRecycler,
    attach_pipeline,
    build_model,
    build_source,
    init_distributed,
    install_blackbox,
    install_chaos,
    install_historian,
    install_journal,
    install_trace,
    select_backend,
    warmup_compile,
)

log = get_logger("apps.linear")


def run(conf: ConfArguments, max_batches: int = 0) -> dict:
    # multi-host group formation MUST precede any backend use (apps/common)
    lead = init_distributed(conf)

    log.info("Initializing session stats...")
    # one telemetry session per RUN, not per host: the lead publishes the
    # global stats (they are psum-identical on every host); followers train
    session = SessionStats(conf).open() if lead else None

    log.info("Initializing TPU-native streaming model...")
    device = select_backend(conf)
    featurizer = Featurizer.from_conf(conf)
    model, row_multiple = build_model(conf)
    import jax

    lockstep = jax.process_count() > 1
    install_trace(conf)
    install_chaos(conf)
    # crash flight recorder: every abort path dumps a post-mortem bundle
    # next to the checkpoint dir (apps/common.install_blackbox)
    install_blackbox(conf)
    # durable intake journal (--journal, auto-on with --checkpointDir):
    # every recovery path below replays from it instead of counting loss
    install_journal(conf)
    # telemetry historian (--history, auto-on with --checkpointDir):
    # durable long-horizon time series at the stats-publish cadence
    install_historian(conf)

    log.info("Initializing streaming context... %s sec/batch", conf.seconds)
    ssc = StreamingContext(
        batch_interval=conf.seconds,
        # bounded intake backpressure (--maxQueueRows/--shedPolicy):
        # the queue was the last unbounded buffer in the pipeline
        max_queue_rows=conf.effective_max_queue_rows(),
        shed_policy=conf.shedPolicy,
    )
    stream = ssc.source_stream(
        build_source(conf, allow_block=True), featurizer,
        row_bucket=conf.batchBucket, token_bucket=conf.tokenBucket,
        row_multiple=row_multiple,
        device_hash=conf.hashOn == "device",
        ragged=conf.effective_wire() == "ragged",
    )

    # tenant count in the run record: callers (bench suite, tests) can see
    # how many models this run's one jit program trained — and which device
    # it ran on, so a CPU run is never read as a device result
    totals = {
        "count": 0, "batches": 0,
        "tenants": int(getattr(model, "num_tenants", 1) or 1),
        "device": device,
    }

    # checkpoint/resume (upgrade over the reference, SURVEY.md §5.4)
    ckpt = AppCheckpoint(
        conf,
        get_state=lambda: model.latest_weights,
        set_state=model.set_initial_weights,
        totals=totals,
        lead=lead,
    )

    # journal boot recovery (kill -9 / watchdog-abort restart): replay the
    # rows past the restored checkpoint's cursor and fast-forward the
    # source past everything journaled — resume is replay-exact
    from .common import journal_boot_replay

    journal_boot_replay(conf, ssc, ckpt, totals)

    # --recycleAfterMb: bounded process lifetime (checkpoint + exact-resume
    # re-exec) once RSS crosses the ceiling — the actionable form of the
    # RSS watchdog's diagnosis (apps/common.ProcessRecycler)
    recycler = ProcessRecycler(conf, ckpt, totals)

    # divergence sentinel (--sentinel, default on): non-finite per-batch
    # stats → skip the batch, roll back to the last verified-finite
    # checkpoint, abort cleanly after N rollbacks (apps/common)
    from .common import DivergenceSentinel, ModelWatchGuard

    sentinel = DivergenceSentinel(
        conf, model, ckpt, ssc, lead=lead, totals=totals
    )

    # model watch (--modelWatch, default on): drift/loss-trend telemetry
    # from the in-step quality vector riding the existing stats fetch;
    # sustained alert forces a verified-checkpoint save (apps/common)
    modelwatch = ModelWatchGuard(conf, ckpt, totals, lead=lead)

    # freshness plane (--freshness, default on): event-time watermarks +
    # per-batch critical-path lineage stamped at seams the pipeline already
    # crosses — zero added fetches/collectives; a sustained --freshnessSloMs
    # breach forces one verified checkpoint per episode (apps/common)
    from ..telemetry import freshness as _freshness
    from .common import FreshnessGuard

    _freshness.configure(conf)
    freshness_guard = FreshnessGuard(conf, ckpt, totals, lead=lead)

    from ..utils.tracing import Tracer

    tracer = Tracer(conf.profileDir)

    def handle(out, batch, _batch_time, at_boundary=True) -> None:
        b = int(out.count)
        totals["count"] += b
        totals["batches"] += 1
        mse = round_half_up(float(out.mse))
        real_stdev = round_half_up(float(out.real_stdev))
        pred_stdev = round_half_up(float(out.pred_stdev))
        if lead:
            # the reference's debug channel (LinearRegression.scala:67-74);
            # stats are global (psum over the data axis) so one host prints.
            # Per-row series are lead-local (followers don't even fetch
            # predictions, parallel/distributed.py) and may be empty when
            # the lead's own shard had no valid rows this batch.
            valid = batch.mask.astype(bool)
            real = batch.label[valid].astype(np.float64)
            pred = np.asarray(out.predictions)[valid].astype(np.float64)
            print(
                f"count: {totals['count']}  batch: {b}  mse: {mse}  "
                f"stdev (real, pred): ({int(real_stdev)}, {int(pred_stdev)})",
                flush=True,
            )
            session.update(
                totals["count"], b, mse, real_stdev, pred_stdev, real, pred
            )
        ckpt.maybe_save(totals, at_boundary)
        recycler.check(at_boundary)
        if max_batches and totals["batches"] >= max_batches:
            ssc.request_stop()

    # elastic membership plane (--elastic on): host loss degrades capacity
    # instead of killing the run; a recovered host rejoins at an epoch
    # boundary (apps/common.attach_elastic)
    from .common import attach_elastic, elastic_exit

    elastic_plane = attach_elastic(conf, ssc, model, stream, ckpt, totals)

    flush = attach_pipeline(
        conf, stream, model, handle,
        stop_requested=lambda: ssc.stop_requested,
        max_dispatch=(
            max(1, max_batches - totals["batches"]) if max_batches else 0
        ),
        abort=ssc.request_abort,  # fetch-watchdog aborts fail the run loudly
        sentinel=sentinel,
        modelwatch=modelwatch,
        elastic=elastic_plane,
        freshness=freshness_guard,
    )

    warmup_compile(stream, model)

    log.info("Starting the streaming computation...")
    tracer.start()
    import time as _time

    t_stream = _time.perf_counter()
    ssc.start(lockstep=lockstep)
    try:
        ssc.await_termination()
    except KeyboardInterrupt:
        pass
    finally:
        ssc.stop()
        flush()  # deliver what is still in flight before final state
        # the post-warmup streaming window (start → last batch drained):
        # what a steady-state rate should be computed over — session init,
        # model build, and the warmup compile are startup, not streaming
        # (the suite's twitter_live config reads this, VERDICT r3 #4)
        totals["stream_seconds"] = _time.perf_counter() - t_stream
        if hasattr(model, "device_span"):
            # mesh runs: how many devices the weights and the wire buffer
            # really spanned (parallel/sharding.ParallelSGDModel)
            totals["device_span"] = model.device_span()
        tracer.stop()
        if session is not None:
            # final metrics snapshot so the dashboard panel ends current
            session.publish_metrics()
        from ..telemetry import trace as pipeline_trace

        pipeline_trace.uninstall()  # flush + close the --trace file
        ckpt.final_save(totals)
        from ..streaming import journal as _journal_mod
        from ..telemetry import historian as _historian_mod

        # after the final save (it stamps the journal cursor): close the
        # segment files and clear the module face so a later run() in the
        # same process starts clean
        _journal_mod.uninstall()
        # perfGuard baseline stamps on CLEAN shutdown only — a guard-
        # aborted run's degraded stage costs must not become the next
        # run's "healthy" baseline
        if not ssc.failed:
            _historian_mod.stamp_baseline()
        _historian_mod.uninstall()
    if ssc.failed:
        # elastic runs leave via a hard exit either way (abandoned-epoch
        # teardown during interpreter finalization is unsafe)
        elastic_exit(failed=True)
        raise RuntimeError(
            "run aborted by a runtime guard — lockstep peer loss, a fetch "
            "watchdog abort, or the divergence sentinel (see critical log "
            "above); progress up to the failure is checkpointed"
        )
    elastic_exit(failed=False)
    return totals


def main(argv=None) -> None:
    conf = (
        ConfArguments()
        .setAppName("twitter-stream-ml-linear-regression")
        .parse(list(sys.argv[1:] if argv is None else argv))
    )
    totals = run(conf)
    log.info("done: %s tweets in %s batches", totals["count"], totals["batches"])


if __name__ == "__main__":
    main()
