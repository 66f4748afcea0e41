"""Concurrent in-order stats fetch (apps/common.FetchPipeline): back-to-back
apps dispatch on the main thread and fetch each batch's StepOutput on a
small pool, so concurrent fetches overlap their latencies. Semantics must stay the synchronous path's: per-batch
stats in order, at_boundary only with current weights (drains), exact
max-batches caps, tail drained by flush()."""

import json

import numpy as np

from twtml_tpu.apps.common import FetchPipeline
from twtml_tpu.config import ConfArguments
from twtml_tpu.streaming.sources import SyntheticSource


class FakeModel:
    def __init__(self):
        self.dispatched = []

    def step(self, batch):
        self.dispatched.append(batch)
        return {"i": np.asarray(batch)}


def test_emits_in_order_and_flush_drains():
    model, events = FakeModel(), []
    pipe = FetchPipeline(
        model,
        lambda out, b, t, at_boundary: events.append((int(out["i"]), at_boundary)),
        depth=3,
    )
    for i in range(10):
        pipe.on_batch(i, 0.0)
    pipe.flush()
    assert model.dispatched == list(range(10))
    assert [e[0] for e in events] == list(range(10))  # strict order
    # at_boundary True iff the pipeline was empty after the emit (an
    # instant fake model drains opportunistically, so most emits qualify);
    # the final drained batch always does
    assert events[-1][1] is True


def test_max_dispatch_is_exact_and_stop_vetoes():
    model, events = FakeModel(), []
    stop = {"flag": False}

    def handle(out, b, t, at_boundary):
        events.append(int(out["i"]))
        if out["i"] >= 4:
            stop["flag"] = True

    pipe = FetchPipeline(
        model, handle, depth=3,
        stop_requested=lambda: stop["flag"], max_dispatch=5,
    )
    for i in range(20):
        pipe.on_batch(i, 0.0)
    pipe.flush()
    assert model.dispatched == [0, 1, 2, 3, 4]  # the cap, exactly
    assert events == [0, 1, 2, 3, 4]


def test_boundary_every_drains_at_cadence():
    model, events = FakeModel(), []
    pipe = FetchPipeline(
        model,
        lambda out, b, t, at_boundary: events.append((int(out["i"]), at_boundary)),
        depth=4, boundary_every=3,
    )
    for i in range(9):
        pipe.on_batch(i, 0.0)
    pipe.flush()
    boundaries = [i for i, at_b in events if at_b]
    # every 3rd batch is a drain point (weights current for checkpoints)
    assert set(boundaries) >= {2, 5, 8}
    assert [e[0] for e in events] == list(range(9))


def test_linear_app_max_batches_exact_under_fetch_pipeline(tmp_path):
    """The flagship app in back-to-back mode (--seconds 0, where the fetch
    pipeline engages) trains EXACTLY max_batches batches."""
    import jax

    from tools.bench_suite import _status_json
    from twtml_tpu.apps import linear_regression as app

    jax.devices()  # lock the conftest's 8-device backend before local[1]

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=8 * 16, seed=11, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(_status_json(s)) + "\n")

    conf = ConfArguments().parse([
        "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--master", "local[1]",
    ])
    totals = app.run(conf, max_batches=3)
    assert totals["batches"] == 3
    assert totals["count"] == 3 * 16


def test_linear_app_checkpoint_cadence_under_fetch_pipeline(tmp_path):
    """--checkpointDir/--checkpointEvery under the fetch pipeline: cadence
    saves see current weights (the pipeline drains at cadence points), and
    a resumed run continues the counters."""
    import jax

    from tools.bench_suite import _status_json
    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.checkpoint import Checkpointer

    jax.devices()

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=6 * 16, seed=12, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(_status_json(s)) + "\n")

    ck = str(tmp_path / "ck")
    conf_args = [
        "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--master", "local[1]",
        "--checkpointDir", ck, "--checkpointEvery", "2",
    ]
    totals = app.run(ConfArguments().parse(conf_args), max_batches=4)
    assert totals["batches"] == 4
    state, meta = Checkpointer(ck).restore()
    assert meta["batches"] == 4
    # resume: counters continue from the checkpoint (batches=4, count=64)
    # and the re-read replay file fast-forwards past the 64 journaled
    # rows the checkpoint covers (r21 exact resume) — only the 2 batches
    # the first run never reached train now: exactly-once over the corpus
    totals2 = app.run(ConfArguments().parse(conf_args))
    assert totals2["batches"] == 4 + 2
    assert totals2["count"] == 64 + 2 * 16


def test_cap_reached_still_delivers_pending_handles():
    """Regression: once max_dispatch is hit, further on_batch calls (an
    unbounded live source keeps producing) must still DELIVER the trained
    batches' handles — that is where the app's request_stop lives; without
    it the stream never learns it should stop."""
    model, events = FakeModel(), []
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: events.append(int(out["i"])),
        depth=8, max_dispatch=2,
    )
    pipe.on_batch(0, 0.0)
    pipe.on_batch(1, 0.0)
    pipe.on_batch(2, 0.0)  # beyond the cap: not trained, but 0 and 1 deliver
    assert model.dispatched == [0, 1]
    assert events == [0, 1]


def test_refund_does_not_perturb_checkpoint_cadence():
    """r3 advisor: cadence runs on a MONOTONIC counter — a refunded
    dispatch slot (multi-host empty-global batches) must not make the
    cadence pass a point twice or skip it."""
    model, events = FakeModel(), []
    pipe = FetchPipeline(
        model,
        lambda out, b, t, at_boundary: events.append((int(out["i"]), at_boundary)),
        depth=4, boundary_every=3, max_dispatch=50,
    )
    for i in range(9):
        pipe.on_batch(i, 0.0)
        pipe.refund_dispatch()  # every batch refunds (worst case)
    pipe.flush()
    boundaries = [i for i, at_b in events if at_b]
    # cadence unchanged by the refunds: every 3rd batch still drains
    assert set(boundaries) >= {2, 5, 8}
    # and the refunds did their own job: the cap accounting went negative-
    # of-dispatch (50-cap never reached, all 9 trained)
    assert [e[0] for e in events] == list(range(9))


def test_deterministic_mode_emits_only_at_deterministic_points():
    """r3 advisor (multi-host): with deterministic=True the opportunistic
    already-done early emit is disabled — deliveries happen only at depth
    backpressure, cadence drains, and flush, i.e. at points driven by the
    dispatch counter (identical on every lockstep host), never by
    wall-clock future completion."""
    import time as _time

    model, events = FakeModel(), []
    pipe = FetchPipeline(
        model,
        lambda out, b, t, at_boundary: events.append(int(out["i"])),
        depth=4, deterministic=True,
    )
    for i in range(4):
        pipe.on_batch(i, 0.0)
        _time.sleep(0.02)  # futures certainly done (instant fake model)...
        # ...yet nothing may emit below the depth watermark
        assert events == []
    pipe.on_batch(4, 0.0)  # 5th dispatch finds depth reached → one emit
    assert events == [0]
    pipe.flush()
    assert events == [0, 1, 2, 3, 4]
