"""Streaming logistic-regression entry point (BASELINE config #3: binary
sentiment on the tweet stream).

Same pipeline shape as the linear app (filter → featurize → fused
predict-then-train → stats) with the label swapped to the lexicon sentiment
of the original tweet (features/sentiment.py) and the logistic learner
(models/logistic.py). Reported ``mse`` over hard 0/1 predictions is the
misclassification rate.

Run: ``python -m twtml_tpu.apps.logistic_regression --source replay \
      --replayFile tests/data/tweets.jsonl --seconds 1``
"""

from __future__ import annotations

import sys

import numpy as np

from ..config import ConfArguments
from ..features.featurizer import Featurizer
from ..features.sentiment import (
    sentiment_label,
    sentiment_labels,
    sentiment_labels_from_units,
)
from ..models.logistic import StreamingLogisticRegressionWithSGD
from ..ops.quality import QUALITY_INDEX
from ..streaming.context import StreamingContext
from ..telemetry import metrics as _metrics
from ..telemetry.session_stats import SessionStats
from ..utils import get_logger, round_half_up
from .common import (
    AppCheckpoint,
    DivergenceSentinel,
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
    journal_boot_replay,
    select_backend,
    warmup_compile,
)

log = get_logger("apps.logistic")


def run(conf: ConfArguments, max_batches: int = 0) -> dict:
    lead = init_distributed(conf)  # before any backend use (apps/common)
    session = SessionStats(conf).open() if lead else None
    device = select_backend(conf)
    featurizer = Featurizer.from_conf(conf)
    featurizer.label_fn = sentiment_label
    featurizer.batch_label_fn = sentiment_labels  # C hot path, same labels
    featurizer.unit_label_fn = sentiment_labels_from_units  # block ingest
    # mesh-sharded automatically on several devices / --master local[N],
    # exactly like the flagship app (the logistic residual rides the same
    # sharded step)
    model, row_multiple = build_model(conf, StreamingLogisticRegressionWithSGD)
    import jax

    lockstep = jax.process_count() > 1
    install_trace(conf)
    install_chaos(conf)
    install_blackbox(conf)  # crash flight recorder (apps/common)
    install_journal(conf)  # durable intake journal (--journal, apps/common)
    install_historian(conf)  # telemetry historian (--history, apps/common)

    ssc = StreamingContext(
        batch_interval=conf.seconds,
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
    # how many models this run's one jit program trained
    totals = {
        "count": 0, "batches": 0,
        "tenants": int(getattr(model, "num_tenants", 1) or 1),
        "device": device,
    }

    # checkpoint/resume — same upgrade as the flagship app (SURVEY.md §5.4)
    ckpt = AppCheckpoint(
        conf,
        get_state=lambda: model.latest_weights,
        set_state=model.set_initial_weights,
        totals=totals,
        lead=lead,
    )
    # journal boot recovery — same replay-exact resume as the flagship app
    journal_boot_replay(conf, ssc, ckpt, totals)

    recycler = ProcessRecycler(conf, ckpt, totals)

    # divergence sentinel — same guard as the flagship app (apps/common)
    sentinel = DivergenceSentinel(
        conf, model, ckpt, ssc, lead=lead, totals=totals
    )

    # model watch — same drift/trend plane as the flagship app
    from .common import ModelWatchGuard

    modelwatch = ModelWatchGuard(conf, ckpt, totals, lead=lead)

    # freshness plane — same lineage/watermark/SLO plane as the flagship app
    from ..telemetry import freshness as _freshness
    from .common import FreshnessGuard

    _freshness.configure(conf)
    freshness_guard = FreshnessGuard(conf, ckpt, totals, lead=lead)

    label0_share = _metrics.get_registry().gauge("model.label0_share")

    def handle(out, batch, _batch_time, at_boundary=True) -> None:
        b = int(out.count)
        totals["count"] += b
        totals["batches"] += 1
        err_rate = float(out.mse)  # 0/1 preds → MSE == misclassification rate
        if getattr(out, "quality", None) is not None:
            # a stream that labels every tweet alike trains nothing: the
            # share of label 0, from the quality vector the batch's stats
            # fetch already brought (psum-global; zero added fetches)
            label0_share.set(round(1.0 - float(
                np.asarray(out.quality)[..., QUALITY_INDEX["label_mean"]].mean()
            ), 4))
        if lead:
            # per-row series are lead-local (followers don't fetch
            # predictions) and can be empty when the lead's own shard had
            # no valid rows this batch — the GLOBAL stats above still hold
            valid = batch.mask.astype(bool)
            real = batch.label[valid].astype(np.float64)
            pred = np.asarray(out.predictions)[valid].astype(np.float64)
            rates = (
                f"({real.mean():.2f}, {pred.mean():.2f})"
                if real.size else "(-, -)"
            )
            print(
                f"count: {totals['count']}  batch: {b}  "
                f"errRate: {err_rate:.3f}  posRate (real, pred): {rates}",
                flush=True,
            )
            session.update(
                totals["count"], b,
                round_half_up(err_rate * 100),  # percent for the int dashboard
                round_half_up(float(out.real_stdev) * 100),
                round_half_up(float(out.pred_stdev) * 100),
                real, pred,
            )
        ckpt.maybe_save(totals, at_boundary)
        recycler.check(at_boundary)
        if max_batches and totals["batches"] >= max_batches:
            ssc.request_stop()

    # elastic membership plane (--elastic on, apps/common.attach_elastic)
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
    ssc.start(lockstep=lockstep)
    try:
        ssc.await_termination()
    except KeyboardInterrupt:
        pass
    finally:
        ssc.stop()
        flush()  # deliver what is still in flight
        if session is not None:
            session.publish_metrics()  # final dashboard-panel snapshot
        from ..telemetry import trace as pipeline_trace

        pipeline_trace.uninstall()  # flush + close the --trace file
        ckpt.final_save(totals)
        from ..streaming import journal as _journal_mod
        from ..telemetry import historian as _historian_mod

        # after the final save (it stamps the journal cursor): close the
        # segment files and clear the module face so a later run() in the
        # same process starts clean
        _journal_mod.uninstall()
        # perfGuard baseline stamps on CLEAN shutdown only
        if not ssc.failed:
            _historian_mod.stamp_baseline()
        _historian_mod.uninstall()
    if ssc.failed:
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
        .setAppName("twitter-stream-ml-logistic-regression")
        .parse(list(sys.argv[1:] if argv is None else argv))
    )
    totals = run(conf)
    log.info("done: %s tweets in %s batches", totals["count"], totals["batches"])


if __name__ == "__main__":
    main()
