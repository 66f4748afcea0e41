"""Concurrent in-order stats fetch (apps/common.FetchPipeline): back-to-back
apps dispatch on the main thread and fetch each batch's StepOutput on a
small pool, so concurrent fetches overlap their latencies. Semantics must stay the synchronous path's: per-batch
stats in order, at_boundary only with current weights (drains), exact
max-batches caps, tail drained by flush()."""

import functools
import json
import threading

import numpy as np
import pytest

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
    # instant fake model's results are done at the next round's count, so
    # most emits qualify); the final drained batch always does
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

    from twtml_tpu.apps import linear_regression as app

    jax.devices()  # lock the conftest's 8-device backend before local[1]

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=8 * 16, seed=11, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(s.to_json()) + "\n")

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

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.checkpoint import Checkpointer

    jax.devices()

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=6 * 16, seed=12, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(s.to_json()) + "\n")

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


# -- one delivery a round (PR 39; PERF.md §7 row 17) -------------------------
# The round is backpressure -> deliver -> pack -> dispatch, and the delivery is
# bounded by a count taken ONCE: the leading results whose fetch was done when
# the delivery began. A model whose fetches complete on command shows each
# side of the rule without a clock.

class GatedModel:
    """``step`` returns at once; batch i's pooled fetch blocks until
    ``finish(pipe, i)`` opens its gate. ``log`` holds dispatches and
    deliveries in the order the main thread made them."""

    def __init__(self):
        self.log = []
        self.gates = {}

    def step(self, batch):
        self.log.append(("dispatch", batch))
        self.gates[batch] = threading.Event()
        return batch

    def fetch_output(self, out):
        assert self.gates[out].wait(30)
        return out

    def finish(self, pipe, i):
        """Batch i's fetch completes, and is SEEN complete (``done()``)."""
        self.gates[i].set()
        next(e[0] for e in pipe._pending if e[2] == i).result(timeout=30)


def _gated_pipe(depth, deterministic=False, during=None, stop=None):
    """A pipeline over a GatedModel; ``during[i]()`` runs inside batch i's
    handler. Returns (model, pipe, delivered-with-at_boundary)."""
    model, events = GatedModel(), []

    def handle(out, b, t, at_boundary):
        model.log.append(("deliver", out))
        events.append((out, at_boundary))
        (during or {}).get(out, lambda: None)()

    pipe = FetchPipeline(model, handle, depth=depth,
                         deterministic=deterministic, stop_requested=stop)
    return model, pipe, events


def _case_becomes_done_during_the_heads_handler():
    # (a) head done, the next becomes done DURING the head's handler: the
    # round delivers ONE, the next round the other
    during = {}
    model, pipe, events = _gated_pipe(4, during=during)
    during[0] = lambda: model.finish(pipe, 1)
    pipe.on_batch(0, 0.0)
    pipe.on_batch(1, 0.0)
    model.finish(pipe, 0)
    pipe.on_batch(2, 0.0)
    assert events == [(0, False)]          # 1 is done by now, and waits
    assert pipe._pending[0][0].done()
    assert model.log[-2:] == [("deliver", 0), ("dispatch", 2)]
    pipe.on_batch(3, 0.0)
    assert events == [(0, False), (1, False)]
    return model, pipe, events, 4


def _case_two_done_when_the_phase_begins():
    # (b) a host that fell behind: two done when the delivery begins -> both
    # in that round, in order; the second leaves nothing in flight, so it
    # is a boundary (weights current), the first is not
    model, pipe, events = _gated_pipe(4)
    pipe.on_batch(0, 0.0)
    pipe.on_batch(1, 0.0)
    model.finish(pipe, 0)
    model.finish(pipe, 1)
    pipe.on_batch(2, 0.0)
    assert events == [(0, False), (1, True)]
    assert model.log[-3:] == [("deliver", 0), ("deliver", 1), ("dispatch", 2)]
    return model, pipe, events, 3


def _case_nothing_done_below_depth():
    # (c) nothing done and fewer than depth in flight: dispatched, nothing
    # delivered, nothing waited for
    model, pipe, events = _gated_pipe(4)
    for i in range(3):
        pipe.on_batch(i, 0.0)
    assert model.log == [("dispatch", i) for i in range(3)]
    assert events == [] and pipe.pending_fetches == 3
    return model, pipe, events, 3


def _case_depth_reached_blocks_before_the_dispatch():
    # (d) depth results in flight: the round blocks on the head BEFORE it
    # dispatches (the device-paced path, as before PR 39)
    model, pipe, events = _gated_pipe(2)
    pipe.on_batch(0, 0.0)
    pipe.on_batch(1, 0.0)
    assert not pipe._pending[0][0].done()
    opener = threading.Timer(0.05, model.gates[0].set)
    opener.start()
    pipe.on_batch(2, 0.0)                  # returns only once 0 was fetched
    opener.join()
    assert model.log == [("dispatch", 0), ("dispatch", 1), ("deliver", 0),
                         ("dispatch", 2)]
    assert events == [(0, False)] and pipe.pending_fetches == 2
    return model, pipe, events, 3


def _case_a_counted_delivery_can_veto_the_dispatch():
    # (e) the counted deliveries come BEFORE the round's dispatch (the
    # order is today's: PERF.md §6 PR 39), so a handler's request_stop
    # vetoes it and a capped run stays exact
    stop = {"flag": False}
    model, pipe, events = _gated_pipe(
        4, during={0: lambda: stop.update(flag=True)},
        stop=lambda: stop["flag"])
    pipe.on_batch(0, 0.0)
    model.finish(pipe, 0)
    pipe.on_batch(1, 0.0)                  # delivers 0, whose handler stops
    assert model.log == [("dispatch", 0), ("deliver", 0)]
    assert events == [(0, True)]
    return model, pipe, events, 1


def _case_deterministic_never_delivers_early():
    # (f) multi-host lockstep: done() never drives a delivery
    model, pipe, events = _gated_pipe(4, deterministic=True)
    for i in range(3):
        pipe.on_batch(i, 0.0)
        model.finish(pipe, i)
        assert events == []
    return model, pipe, events, 3


@pytest.mark.parametrize("case", [
    _case_becomes_done_during_the_heads_handler,
    _case_two_done_when_the_phase_begins,
    _case_nothing_done_below_depth,
    _case_depth_reached_blocks_before_the_dispatch,
    _case_a_counted_delivery_can_veto_the_dispatch,
    _case_deterministic_never_delivers_early,
], ids=lambda c: c.__name__[len("_case_"):])
def test_one_delivery_a_round(case):
    model, pipe, events, n = case()
    for gate in model.gates.values():
        gate.set()
    pipe.flush()
    # whatever the round did: every batch delivered once, in order, and the
    # last one at a boundary (nothing newer in flight)
    assert [e[0] for e in events] == list(range(n))
    assert events[-1][1] is True
    assert [b for what, b in model.log if what == "dispatch"] == list(range(n))


def test_deliver_round_instant_counts_the_round(tmp_path, monkeypatch):
    """Under --trace every round that dispatched leaves one
    ``deliver_round`` instant: what was ready when its delivery began, what
    it delivered (backpressure included) and what it left in flight — what
    benchmark/layer_metrics/paired_delivery_share.py reads."""
    from benchmark import manifest, spans, trace_files
    from twtml_tpu.telemetry import trace

    path = str(tmp_path / "spans.json")
    trace.install(path)
    try:
        model, pipe, events = _gated_pipe(2)
        pipe.on_batch(0, 0.0)                  # ready 0, delivered 0
        model.finish(pipe, 0)
        pipe.on_batch(1, 0.0)                  # ready 1, delivered 1
        pipe.on_batch(2, 0.0)                  # nothing done: 0 / 0
        model.finish(pipe, 1)
        model.finish(pipe, 2)
        pipe.on_batch(3, 0.0)                  # backpressure 1 + ready 1
        model.gates[3].set()
        pipe.flush()
    finally:
        trace.uninstall()
    rounds = [e["args"] for e in spans.load_events(path)
              if e["name"] == "deliver_round"]
    assert [(a["ready"], a["delivered"], a["pending"]) for a in rounds] == [
        (0, 0, 1), (1, 1, 1), (0, 0, 2), (1, 2, 1)]
    reader = manifest.load_module(
        manifest.layer_metric_path("paired_delivery_share"))
    monkeypatch.setattr(trace_files, "span_file", lambda: path)
    assert reader.read({}) == 100.0 * 2 / 3     # 2 of 3 in a round of two
    monkeypatch.setattr(trace_files, "span_file", lambda: None)
    assert reader.read({}) is None              # no live traced run
    empty = tmp_path / "none.json"
    empty.write_text('[\n{"name": "gram_plane", "ph": "i"},\n')
    monkeypatch.setattr(trace_files, "span_file", lambda: str(empty))
    assert reader.read({}) is None              # the parent's program


# -- pipelined == sequential, for every model kind and wire form -------------
# The engine every cell runs: FetchPipeline at its production depth must
# deliver, in order, the same StepOutputs and leave the same weights, bit for
# bit, as a plain ``model.step`` loop with one synchronous fetch per batch.

_N_BATCHES, _ROWS, _NOW = 12, 32, 1785320000000


def _statuses(seed=3):
    return list(SyntheticSource(
        total=_N_BATCHES * _ROWS, seed=seed, base_ms=_NOW
    ).produce())


def _batches(how, f_text=None, sentiment=False):
    from twtml_tpu.features.featurizer import Featurizer

    feat = Featurizer(now_ms=_NOW, **(
        {"num_text_features": f_text} if f_text else {}
    ))
    if sentiment:
        from twtml_tpu.features.sentiment import (
            sentiment_label, sentiment_labels,
        )

        feat.label_fn = sentiment_label
        feat.batch_label_fn = sentiment_labels
    statuses = _statuses()
    kwargs = dict(row_bucket=_ROWS, pre_filtered=True)
    if how == "featurize_batch":
        kwargs["token_bucket"] = 64
    if how == "featurize_batch_ragged":
        kwargs["unit_bucket"] = 8192  # one wire signature for the stream
    return [
        getattr(feat, how)(statuses[i * _ROWS : (i + 1) * _ROWS], **kwargs)
        for i in range(_N_BATCHES)
    ]


def _mesh(**axes):
    import jax

    from twtml_tpu.parallel import make_mesh

    return make_mesh(devices=jax.devices()[:4], **axes)


def _kind(name):
    """(make_model, batches) of one model kind."""
    from twtml_tpu.models import (
        StreamingLinearRegressionWithSGD as Linear,
        StreamingLogisticRegressionWithSGD as Logistic,
    )
    from twtml_tpu.parallel import ParallelSGDModel, TenantStackModel

    if name == "dense":
        return (lambda: Linear(num_iterations=5)), _batches("featurize_batch")
    if name == "gram":
        return (
            lambda: Linear(
                num_text_features=2**14, num_iterations=5, l2_reg=0.1
            ),
            _batches("featurize_batch_units", f_text=2**14),
        )
    if name == "logistic":
        return (
            lambda: Logistic(num_iterations=5),
            _batches("featurize_batch_units", sentiment=True),
        )
    if name == "ragged":
        return (
            lambda: Linear(num_iterations=5),
            _batches("featurize_batch_ragged"),
        )
    if name == "mesh1d":
        return (
            lambda: ParallelSGDModel(
                _mesh(num_data=4), num_iterations=5, step_size=0.05
            ),
            _batches("featurize_batch_ragged"),
        )
    if name == "mesh2d":
        return (
            lambda: ParallelSGDModel(
                _mesh(num_data=2, num_model=2), num_iterations=5,
                step_size=0.05,
            ),
            _batches("featurize_batch_ragged"),
        )
    assert name == "tenants2"
    return (
        lambda: TenantStackModel(2, num_iterations=5, wire_pack="group"),
        _batches("featurize_batch_ragged"),
    )


def _leaves(out):
    import jax

    return [np.asarray(a) for a in jax.tree_util.tree_leaves(out)]


@functools.lru_cache(maxsize=None)
def _sequential(kind):
    """The plain loop both wire forms of a kind are held to: one
    ``model.step`` and one synchronous fetch per batch."""
    import jax

    make_model, batches = _kind(kind)
    seq = make_model()
    want = [_leaves(jax.device_get(seq.step(b))) for b in batches]
    return (
        make_model, batches, want,
        np.asarray(seq.latest_weights).tobytes(),
    )



@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
@pytest.mark.parametrize("kind", [
    "dense", "gram", "logistic", "ragged", "mesh1d", "mesh2d", "tenants2",
])
def test_pipelined_equals_sequential(kind, packed):
    make_model, batches, want, want_weights = _sequential(kind)
    model, got = make_model(), []
    pipe = FetchPipeline(
        model,
        lambda out, b, t, at_boundary: got.append(
            (_leaves(out), b, t, at_boundary)
        ),
        depth=8, pack=packed,
    )
    for i, b in enumerate(batches):
        pipe.on_batch(b, float(i))
    pipe.flush()

    # every batch, once, in dispatch order, with the UNPACKED batch it was
    # dispatched for; the last delivery sees current weights
    assert [t for _, _, t, _ in got] == [float(i) for i in range(len(batches))]
    assert all(b is sent for (_, b, _, _), sent in zip(got, batches))
    assert got[-1][3] is True
    for i, ((leaves, _, _, _), ref) in enumerate(zip(got, want)):
        assert len(leaves) == len(ref)
        for a, r in zip(leaves, ref):
            assert a.dtype == r.dtype and a.tobytes() == r.tobytes(), (kind, i)
    assert np.asarray(model.latest_weights).tobytes() == want_weights
