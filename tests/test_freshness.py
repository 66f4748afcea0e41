"""End-to-end freshness plane (ISSUE 16): event-time watermarks, per-batch
critical-path lineage, and staleness SLOs at zero added fetches.

The laws under test, in the order the ISSUE states them:
- **lag/watermark exactness** under the pinned ``TWTML_NOW_MS`` seam: the
  event→delivery lag is exactly ``delivered − max(created_at_ms)`` and the
  low watermark exactly ``delivered − oldest event-time still in flight``;
- **critical-path attribution**: a seeded stage-clock delta between open
  and delivery names that edge and ticks its counter;
- **zero added fetches / zero added collectives** with the plane ON —
  asserted by COUNTING ``jax.device_get`` / ``process_allgather`` over a
  real lockstep run and a real app run (the PR 1/5/8 idiom);
- **off bit-parity**: ``--freshness off`` never touches the lineage FIFOs
  and the app's weights are bit-identical to the ON run's (the plane is a
  pure host-side observer);
- **SLO gate**: a sustained ``--freshnessSloMs`` breach fires ONE blackbox
  event + ONE forced verified-checkpoint save per episode (warn-only);
- **serving staleness**: ``serving.snapshot_age_s`` through the clock seam,
  ``model_staleness_s`` in every predict response, and the warn-only
  ``--servingStaleSloS`` breach episode;
- the ``Freshness`` wire type, ``/api/freshness``, the sideband columns,
  ``tools/freshness_report.py`` exit codes, and the satellite gauges
  (``ingest.event_time_lag_ms``, ``host.rss_slope_mb_per_min``).
"""

import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import freshness_report  # noqa: E402
from twtml_tpu.config import ConfArguments  # noqa: E402
from twtml_tpu.features.featurizer import Featurizer  # noqa: E402
from twtml_tpu.models import (  # noqa: E402
    StreamingLinearRegressionWithSGD,
)
from twtml_tpu.streaming.sources import (  # noqa: E402
    SyntheticSource,
    _record_event_lag,
)
from twtml_tpu.telemetry import blackbox as blackbox_mod  # noqa: E402
from twtml_tpu.telemetry import freshness as _freshness  # noqa: E402
from twtml_tpu.telemetry import lineage as _lineage  # noqa: E402
from twtml_tpu.telemetry import metrics as _metrics  # noqa: E402
from twtml_tpu.telemetry import sideband as _sideband  # noqa: E402

NOW_MS = 1785320000000
CLOSED = "http://127.0.0.1:9"


@pytest.fixture(autouse=True)
def _fresh_state():
    _metrics.reset_for_tests()
    _freshness.reset_for_tests()  # also clears the lineage FIFOs
    _sideband.reset_for_tests()
    yield
    _metrics.reset_for_tests()
    _freshness.reset_for_tests()
    _sideband.reset_for_tests()


def _st(created_at_ms):
    """A minimal status-like object for the lineage event-span reader."""
    return types.SimpleNamespace(created_at_ms=created_at_ms)


def _deliver(statuses):
    """One full open → dispatch → delivery cycle through the plane."""
    _lineage.open_batch(statuses)
    _lineage.mark_dispatch()
    return _freshness.record_delivery()


# ---------------------------------------------------------------------------
# watermark / lag exactness under the pinned clock seam


def test_lag_and_watermark_exactness(monkeypatch):
    """ACCEPTANCE: with TWTML_NOW_MS pinned, the event→delivery lag and the
    low watermark are EXACT ms values, not approximations."""
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    _freshness.configure(on=True)
    verdict = _deliver([_st(NOW_MS - 4000), _st(NOW_MS - 1000)])
    # lag is measured to the NEWEST event in the batch; with the FIFOs
    # drained the watermark falls back to the batch's own OLDEST event
    assert verdict["event_lag_ms"] == 1000.0
    assert verdict["watermark_lag_ms"] == 4000.0
    assert not verdict["breach"]  # no SLO armed
    view = _freshness.last_freshness()
    assert view["batches"] == 1 and view["rows"] == 2
    assert view["eventLagMs"] == 1000.0
    assert view["eventLagP50Ms"] == 1000.0
    assert view["eventLagP95Ms"] == 1000.0
    assert view["eventLagP99Ms"] == 1000.0
    assert view["watermarkLagMs"] == 4000.0
    assert view["watermark"] == [4000.0]
    reg = _metrics.get_registry()
    assert reg.gauge("freshness.event_lag_p95_ms").snapshot() == 1000.0
    assert reg.gauge("freshness.watermark_lag_ms").snapshot() == 4000.0
    snap = reg.snapshot()
    assert snap["histograms"]["freshness.event_lag_ms"]["count"] == 1


def test_watermark_tracks_oldest_inflight_event(monkeypatch):
    """The low watermark is ``delivered − min(event_min over BOTH FIFOs)``:
    a still-in-flight older batch holds the watermark down."""
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    _freshness.configure(on=True)
    _lineage.open_batch([_st(NOW_MS - 9000), _st(NOW_MS - 3000)])  # A
    _lineage.open_batch([_st(NOW_MS - 2000)])                       # B
    _lineage.mark_dispatch(2)
    v_a = _freshness.record_delivery()
    # A delivered while B (oldest event NOW-2000) is still in flight
    assert v_a["event_lag_ms"] == 3000.0
    assert v_a["watermark_lag_ms"] == 2000.0
    v_b = _freshness.record_delivery()
    assert v_b["event_lag_ms"] == 2000.0
    assert v_b["watermark_lag_ms"] == 2000.0  # own-batch fallback
    assert _lineage.depths() == (0, 0)


def test_publish_lag_drained_at_stats_tick(monkeypatch):
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    _freshness.configure(on=True)
    _deliver([_st(NOW_MS - 1500)])
    view = _freshness.last_freshness()
    assert view["publishLagP95Ms"] == -1.0  # nothing published yet
    _freshness.record_publish()  # the SessionStats._update hook
    view = _freshness.last_freshness()
    assert view["publishLagP95Ms"] == 1500.0
    assert _metrics.get_registry().gauge(
        "freshness.publish_lag_p95_ms"
    ).snapshot() == 1500.0


def test_unknown_event_times_fold_to_no_lag(monkeypatch):
    """Statuses without created_at_ms (the synthetic wrapper default) still
    count the batch but record no lag — the percentile windows only carry
    known event times."""
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    _freshness.configure(on=True)
    verdict = _deliver([_st(0), _st(0)])
    assert verdict["event_lag_ms"] == -1.0
    view = _freshness.last_freshness()
    assert view["batches"] == 1 and view["eventLagP95Ms"] == -1.0
    assert _freshness.last_event_lag_ms() == 0.0  # the sideband column


# ---------------------------------------------------------------------------
# critical-path attribution on seeded stage deltas


def test_critical_path_attribution_on_seeded_stage_delays(monkeypatch):
    """ACCEPTANCE: the dominant seam-to-seam stage delta between open and
    delivery names the critical edge and ticks its counter."""
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    _freshness.configure(on=True)
    _lineage.open_batch([_st(NOW_MS - 100)])
    _sideband.record_stage("dispatch", 0.5)  # 500 ms on the dispatch edge
    _lineage.mark_dispatch()
    verdict = _freshness.record_delivery()
    assert verdict["critical"] == "dispatch"
    reg = _metrics.get_registry()
    assert reg.counter("freshness.critical.dispatch.ticks").snapshot() == 1
    # second batch: featurize dominates (dispatch clock unchanged since its
    # open snapshot, so its delta is 0 for this batch)
    _lineage.open_batch([_st(NOW_MS - 100)])
    _sideband.record_stage("featurize", 2.0)
    _lineage.mark_dispatch()
    verdict = _freshness.record_delivery()
    assert verdict["critical"] == "featurize"
    view = _freshness.last_freshness()
    assert view["critical"] == "featurize"
    assert view["criticalTicks"] == {"dispatch": 1, "featurize": 1}
    assert reg.counter("freshness.critical.featurize.ticks").snapshot() == 1


def test_quiet_pipeline_has_no_critical_edge(monkeypatch):
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    _freshness.configure(on=True)
    verdict = _deliver([_st(NOW_MS - 100)])  # no stage work recorded
    assert verdict["critical"] == ""
    assert _freshness.last_freshness()["criticalTicks"] == {}


# ---------------------------------------------------------------------------
# lineage FIFO discipline: off is a no-op, blanks keep alignment


def test_off_plane_never_touches_the_fifos():
    """--freshness off bit-parity precondition: every lineage entry point
    is a no-op, so the off arm IS the pre-plane hot path."""
    assert not _lineage.enabled()
    _lineage.open_batch([_st(NOW_MS)])
    _lineage.mark_dispatch()
    assert _lineage.depths() == (0, 0)
    assert _lineage.pop_delivery() is None
    assert _lineage.open_event_floor() == 0
    assert _freshness.record_delivery() is None
    assert _freshness.last_freshness() is None
    assert _freshness.snapshot_for_checkpoint() is None
    assert _freshness.last_event_lag_ms() == 0.0


def test_blank_dispatches_keep_the_fifos_aligned(monkeypatch):
    """Dispatches with no matching open (serving, warmup, bare pipelines)
    push blanks; sheds drop the newest open record."""
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    _freshness.configure(on=True)
    _lineage.mark_dispatch()  # no open record: a blank
    assert _lineage.depths() == (0, 1)
    assert _freshness.record_delivery() is None  # blank pops silently
    _lineage.open_batch([_st(NOW_MS - 100)])
    _lineage.drop_newest()  # skip_empty shed before dispatch
    assert _lineage.depths() == (0, 0)
    # a real batch after the churn still matches positionally
    verdict = _deliver([_st(NOW_MS - 700)])
    assert verdict["event_lag_ms"] == 700.0
    assert _freshness.last_freshness()["batches"] == 1


# ---------------------------------------------------------------------------
# THE acceptance constraint: zero added fetches / zero added collectives
# with the plane ON, counted over a real lockstep run (the PR 1/5/8 law)


def test_freshness_adds_no_fetches_and_no_collectives(monkeypatch):
    import jax
    from jax.experimental import multihost_utils

    from twtml_tpu.apps.common import FetchPipeline, FreshnessGuard
    from twtml_tpu.streaming.context import StreamingContext

    jax.devices()  # lock the conftest backend
    calls = {"allgather": 0, "get": 0}
    real_ag = multihost_utils.process_allgather

    def counting_ag(arr):
        calls["allgather"] += 1
        return real_ag(arr)

    monkeypatch.setattr(multihost_utils, "process_allgather", counting_ag)
    real_get = jax.device_get

    def counting_get(x):
        calls["get"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)

    _freshness.configure(on=True)
    ssc = StreamingContext(batch_interval=0)
    stream = ssc.source_stream(
        SyntheticSource(total=64, seed=7, base_ms=NOW_MS),
        Featurizer(now_ms=NOW_MS),
        row_bucket=16, token_bucket=64, device_hash=True,
    )
    model = StreamingLinearRegressionWithSGD(num_iterations=2)
    guard = FreshnessGuard(ConfArguments(), None, {"count": 0, "batches": 0})

    def handle(out, b, t, at_boundary=True):
        guard.observe(out, at_boundary=at_boundary)

    pipe = FetchPipeline(model, handle, deterministic=True)
    stream.foreach_batch(pipe.on_batch)
    ssc.start(lockstep=True)
    assert ssc.await_termination(timeout=120)
    ssc.stop()
    pipe.flush()
    assert not ssc.failed
    assert ssc.batches_processed >= 4

    reg = _metrics.get_registry().snapshot()
    ticks = reg["counters"]["lockstep.ticks"]
    # ZERO added collectives: still exactly ONE allgather per lockstep tick
    assert calls["allgather"] == ticks
    # ZERO added host fetches: one per dispatched batch — the lineage
    # records are pure host-side stamps, the plane never touches the device
    assert calls["get"] == ssc.batches_processed
    view = _freshness.last_freshness()
    assert view is not None and view["batches"] == ssc.batches_processed
    assert _lineage.depths() == (0, 0)  # every record matched a delivery


# ---------------------------------------------------------------------------
# app-level acceptance: counting + checkpoint stamp + OFF bit-parity


BASE = [
    "--source", "replay", "--seconds", "0", "--backend", "cpu",
    "--batchBucket", "16", "--tokenBucket", "64", "--master", "local[1]",
    "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
]


def _corpus_file(tmp_path, total=8 * 16, seed=51):

    statuses = list(
        SyntheticSource(total=total, seed=seed, base_ms=NOW_MS).produce()
    )
    # the synthetic wrapper carries created_at_ms=0: stamp known event
    # times so the replayed stream exercises the lag-fold path exactly
    for j, s in enumerate(statuses):
        s.created_at_ms = NOW_MS - 1000 * (j + 1)
    path = tmp_path / "tweets.jsonl"
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(s.to_json()) + "\n")
    return path


def _run_counting_fetches(conf_args):
    import jax

    from twtml_tpu.apps import linear_regression as app

    jax.devices()
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    jax.device_get = counting
    try:
        totals = app.run(ConfArguments().parse(list(conf_args)))
    finally:
        jax.device_get = real
    return totals, calls["n"]


def test_app_default_freshness_counts_and_off_is_bit_exact(
    tmp_path, monkeypatch
):
    """ACCEPTANCE: a real app run with the DEFAULT --freshness on fetches
    exactly once per batch, the view and the checkpoint freshness stamp
    materialize, and a --freshness off run lands BIT-identical weights
    (the plane is observation-only)."""
    from twtml_tpu.checkpoint import Checkpointer

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    path = _corpus_file(tmp_path)
    totals_on, fetches_on = _run_counting_fetches(
        BASE + ["--replayFile", str(path),
                "--checkpointDir", str(tmp_path / "ck_on"),
                "--checkpointEvery", "1"]
    )
    assert totals_on["batches"] == 8
    assert fetches_on == 8  # ONE device_get per batch, the plane adds none
    view = _freshness.last_freshness()
    assert view is not None and view["batches"] == 8
    assert view["eventLagMs"] > 0  # real event times flowed end to end
    assert view["eventLagP95Ms"] > 0
    assert len(view["watermark"]) >= 1
    reg = _metrics.get_registry().snapshot()
    assert reg["gauges"]["freshness.event_lag_p95_ms"] > 0
    assert reg["histograms"]["freshness.event_lag_ms"]["count"] == 8
    # checkpoint freshness-stamp roundtrip (ACCEPTANCE)
    w_on, meta = Checkpointer(str(tmp_path / "ck_on")).restore()
    assert meta["freshness"]["batches"] >= 1
    assert meta["freshness"]["event_lag_p95_ms"] > 0
    json.dumps(meta["freshness"])  # json-safe

    totals_off, fetches_off = _run_counting_fetches(
        BASE + ["--replayFile", str(path), "--freshness", "off",
                "--checkpointDir", str(tmp_path / "ck_off"),
                "--checkpointEvery", "1"]
    )
    assert totals_off["batches"] == 8
    assert fetches_off == 8
    assert _freshness.last_freshness() is None  # plane fully off
    assert _lineage.depths() == (0, 0)
    w_off, meta_off = Checkpointer(str(tmp_path / "ck_off")).restore()
    assert "freshness" not in meta_off
    # the bit-parity law: identical weights with the plane on or off
    assert np.asarray(w_on).tobytes() == np.asarray(w_off).tobytes()
    assert totals_on["count"] == totals_off["count"]


# ---------------------------------------------------------------------------
# the SLO gate: blackbox events + ONE forced checkpoint per episode


class _FakeCkpt:
    def __init__(self):
        self.saves = 0

    def save_now(self, totals):
        self.saves += 1
        return True


def test_sustained_slo_breach_forces_one_checkpoint_per_episode(monkeypatch):
    from twtml_tpu.apps.common import FreshnessGuard

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    rec = blackbox_mod.install(config={"t": 1})
    try:
        _freshness.configure(on=True, slo_ms=100.0, window=3)
        ckpt = _FakeCkpt()
        guard = FreshnessGuard(ConfArguments(), ckpt, {"batches": 0})
        breach = [_st(NOW_MS - 500)]  # lag 500 ms > SLO 100 ms
        ok = [_st(NOW_MS - 50)]       # lag 50 ms, under SLO

        for _ in range(2):
            _lineage.open_batch(breach)
            _lineage.mark_dispatch()
            guard.observe(None)
        assert ckpt.saves == 0  # window (3) not reached yet
        # the episode fires on the 3rd breach, but weights are mid-flight
        # (at_boundary=False): the save waits for a weights-current delivery
        _lineage.open_batch(breach)
        _lineage.mark_dispatch()
        guard.observe(None, at_boundary=False)
        assert ckpt.saves == 0
        reg = _metrics.get_registry()
        assert reg.counter("freshness.slo_breaches").snapshot() == 1
        _lineage.open_batch(breach)
        _lineage.mark_dispatch()
        guard.observe(None)
        assert ckpt.saves == 1  # forced save at the first boundary
        for _ in range(5):
            _lineage.open_batch(breach)
            _lineage.mark_dispatch()
            guard.observe(None)
        assert ckpt.saves == 1  # ONE save per episode, not per batch
        _lineage.open_batch(ok)
        _lineage.mark_dispatch()
        guard.observe(None)  # episode closes
        for _ in range(3):
            _lineage.open_batch(breach)
            _lineage.mark_dispatch()
            guard.observe(None)
        assert ckpt.saves == 2  # a NEW episode earns a new save
        assert reg.counter("freshness.slo_breaches").snapshot() == 2
        assert reg.counter("freshness.slo_checkpoints").snapshot() == 2
        kinds = [e["kind"] for e in rec.bundle("t")["events"]]
        assert kinds.count("freshness_slo_breach") == 2
        view = _freshness.last_freshness()
        assert view["breaches"] == 2 and view["sloMs"] == 100.0
    finally:
        blackbox_mod.uninstall()


def test_guard_disabled_is_a_noop():
    from twtml_tpu.apps.common import FreshnessGuard

    conf_off = ConfArguments().parse(["--freshness", "off"])
    guard = FreshnessGuard(conf_off, _FakeCkpt(), {"batches": 0})
    assert not guard.enabled
    guard.observe(None)  # must not raise
    assert _freshness.last_freshness() is None


# ---------------------------------------------------------------------------
# serving staleness: snapshot age through the clock seam + per-response
# model staleness + the --servingStaleSloS breach episode


def test_serving_snapshot_age_staleness_and_breach_episode(monkeypatch):
    from twtml_tpu.serving import ServingSnapshot
    from twtml_tpu.serving.plane import ServingPlane

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    rec = blackbox_mod.install(config={"t": 1})
    plane = None
    try:
        snap = ServingSnapshot(step=3, weights=np.zeros(1004, np.float32))
        plane = ServingPlane(
            snap, featurizer=Featurizer(now_ms=NOW_MS), batch_rows=32,
            max_wait_ms=5.0, depth=4, stale_slo_s=5.0,
        )
        plane.start()
        statuses = list(SyntheticSource(total=8, seed=3).produce())
        res = plane.submit(statuses).result(timeout=120)
        # dispatch-time model staleness in EVERY predict response; the
        # pinned clock makes it exactly 0 (installed and dispatched at the
        # same pinned instant)
        assert res["model_staleness_s"] == 0.0
        assert res["snapshot_step"] == 3
        view = plane.stats()
        assert view["snapshotAgeS"] == 0.0
        reg = _metrics.get_registry()
        assert reg.gauge("serving.snapshot_age_s").snapshot() == 0.0
        assert reg.counter("serve.stale_breaches").snapshot() == 0
        # advance the pinned clock past the SLO: ONE breach episode
        monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS + 10_000))
        view = plane.stats()
        assert view["snapshotAgeS"] == 10.0
        assert reg.counter("serve.stale_breaches").snapshot() == 1
        plane.stats()  # still the same episode: no second count
        assert reg.counter("serve.stale_breaches").snapshot() == 1
        # a fresh install (clock back under the SLO) closes the episode...
        monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS + 1_000))
        plane.stats()
        # ...and a NEW sustained breach opens a new one
        monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS + 20_000))
        view = plane.stats()
        assert view["snapshotAgeS"] == 20.0
        assert reg.counter("serve.stale_breaches").snapshot() == 2
        kinds = [e["kind"] for e in rec.bundle("t")["events"]]
        assert kinds.count("serving_stale_breach") == 2
    finally:
        if plane is not None:
            plane.stop()
        blackbox_mod.uninstall()


# ---------------------------------------------------------------------------
# the sideband columns: the watermark rides the EXISTING cadence allgather


def test_sideband_carries_wire_pack_and_event_lag_columns(monkeypatch):
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    assert "wire_pack_ms" in _sideband.FIELDS
    assert "event_lag_ms" in _sideband.FIELDS
    assert _sideband.STAGE_FIELDS["wire_pack_ms"] == "wire_pack"
    collector = _sideband.SidebandCollector()
    _freshness.configure(on=True)
    _sideband.record_stage("wire_pack", 0.25)
    _deliver([_st(NOW_MS - 1234)])
    vec = collector.collect()
    assert vec[_sideband.FIELDS.index("wire_pack_ms")] == 250.0
    assert vec[_sideband.FIELDS.index("event_lag_ms")] == 1234.0
    # the column is a plain registry read: a second collect with no new
    # delivery repeats the last value, never blocks, never fetches
    assert vec.shape == (_sideband.WIDTH,)


# ---------------------------------------------------------------------------
# SessionStats publishes the Freshness view + the rolling RSS slope


def test_session_stats_publishes_freshness_and_rss_slope(monkeypatch):
    from twtml_tpu.telemetry.session_stats import SessionStats

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    sent = []

    class _Conf:
        lightning = CLOSED
        twtweb = CLOSED
        webTimeout = 0.2

    session = SessionStats(_Conf())
    monkeypatch.setattr(session.web, "freshness", lambda v: sent.append(v))
    monkeypatch.setattr(session.web, "metrics", lambda *a, **k: None)
    session.publish_metrics()
    assert sent == []  # nothing delivered yet: no Freshness frame
    _freshness.configure(on=True)
    _deliver([_st(NOW_MS - 900)])
    session.publish_metrics()
    assert len(sent) == 1
    assert sent[0]["batches"] == 1 and sent[0]["eventLagMs"] == 900.0
    reg = _metrics.get_registry().snapshot()
    # the continuous soak estimator (ISSUE 16 satellite): present every
    # publish tick; ~0 over two instant samples
    assert "host.rss_slope_mb_per_min" in reg["gauges"]


def test_rss_slope_least_squares():
    from twtml_tpu.utils.rss import slope_mb_per_min

    # 10 MB/min of linear growth, sampled every 30 s
    samples = [(30.0 * k, 100.0 + 5.0 * k) for k in range(8)]
    assert slope_mb_per_min(samples) == pytest.approx(10.0)
    assert slope_mb_per_min([]) == 0.0
    assert slope_mb_per_min([(0.0, 100.0)]) == 0.0
    assert slope_mb_per_min([(5.0, 100.0), (5.0, 200.0)]) == 0.0  # no var
    # the soak tool's estimator IS this function (one estimator, two faces)
    from tools.soak import _slope_mb_per_min

    assert _slope_mb_per_min(samples) == pytest.approx(10.0)


def test_ingest_event_time_lag_gauge(monkeypatch):
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    _record_event_lag(NOW_MS - 2500)
    reg = _metrics.get_registry()
    assert reg.gauge("ingest.event_time_lag_ms").snapshot() == 2500.0
    _record_event_lag(0)  # unknown event time: gauge untouched
    assert reg.gauge("ingest.event_time_lag_ms").snapshot() == 2500.0


# ---------------------------------------------------------------------------
# the Freshness wire type + /api/freshness


def test_freshness_wire_roundtrip():
    from twtml_tpu.telemetry.api_types import Freshness, decode, encode

    msg = Freshness(
        batches=12, rows=640, eventLagMs=640.0, eventLagP50Ms=640.0,
        eventLagP95Ms=813.0, eventLagP99Ms=1500.0, publishLagP95Ms=990.0,
        watermarkLagMs=870.0, watermark=[900.0, 880.0, 870.0],
        critical="dispatch", criticalTicks={"dispatch": 9, "fetch": 3},
        sloMs=1000.0, breachRun=2, breaches=1,
    )
    wire = encode(msg)
    assert json.loads(wire)["jsonClass"] == "Freshness"
    assert decode(wire) == msg


def test_api_freshness_endpoint_and_cache_dispatch(tmp_path):
    import urllib.request

    from twtml_tpu.telemetry.api_types import Freshness
    from twtml_tpu.telemetry.web_client import WebClient
    from twtml_tpu.web.cache import ApiCache
    from twtml_tpu.web.server import Server

    cache = ApiCache(backup_file=str(tmp_path / "twtml-web.json"))
    srv = Server(port=0, host="127.0.0.1", cache=cache)
    srv.start_background()
    try:
        port = srv._runner.addresses[0][1]
        url = f"http://127.0.0.1:{port}"
        # default before any post: a well-formed empty Freshness
        with urllib.request.urlopen(url + "/api/freshness", timeout=2) as r:
            doc = json.loads(r.read())
        assert doc["jsonClass"] == "Freshness" and doc["batches"] == 0
        client = WebClient(url)
        view = {
            "batches": 5, "rows": 80, "eventLagMs": 700.0,
            "eventLagP95Ms": 813.0, "watermarkLagMs": 870.0,
            "watermark": [900.0, 870.0], "critical": "fetch",
            "criticalTicks": {"fetch": 5}, "breaches": 1,
            "not_a_field": "dropped",  # unknown keys must not break the post
        }
        client.freshness(view)
        with urllib.request.urlopen(url + "/api/freshness", timeout=2) as r:
            doc = json.loads(r.read())
        assert doc["batches"] == 5
        assert doc["eventLagP95Ms"] == 813.0
        assert doc["watermark"] == [900.0, 870.0]
        assert doc["critical"] == "fetch"
        assert doc["criticalTicks"] == {"fetch": 5}
        assert "not_a_field" not in doc
        assert isinstance(cache._freshness, Freshness)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# tools/freshness_report.py exit codes (the CHECK contract)


def test_freshness_report_malformed_exits_2(tmp_path):
    assert freshness_report.main([]) == 2
    assert freshness_report.main([str(tmp_path / "absent.json")]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("not json at all")
    assert freshness_report.main([str(bad)]) == 2
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"kind": "something-else"}))
    assert freshness_report.main([str(wrong)]) == 2


def test_freshness_report_renders_a_real_bundle(tmp_path, capsys, monkeypatch):
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    blackbox_mod.install(config={"t": 1})
    try:
        # window=1: the very first over-SLO delivery is a sustained episode
        _freshness.configure(on=True, slo_ms=100.0, window=1)
        _lineage.open_batch([_st(NOW_MS - 500)])
        _sideband.record_stage("fetch", 0.3)
        _lineage.mark_dispatch()
        verdict = _freshness.record_delivery()
        assert verdict["sustained"]
        path = blackbox_mod.dump(
            "freshness-test", out_dir=str(tmp_path), force=True
        )
        assert path is not None
    finally:
        blackbox_mod.uninstall()
    assert freshness_report.main([path]) == 0
    text = capsys.readouterr().out
    assert "p95 500 ms" in text
    assert "fetch" in text  # the critical edge table
    assert "1 breach episode(s)" in text
    assert freshness_report.main([path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out.strip())
    assert summary["event_lag_p95_ms"] == 500.0
    assert summary["critical_ticks"] == {"fetch": 1}
    assert summary["critical"] == "fetch"
    assert summary["slo_breaches"] == 1
    assert summary["event_lag_batches"] == 1
    assert [e["kind"] for e in summary["breach_events"]] == [
        "freshness_slo_breach"
    ]


def test_freshness_report_handles_plane_off_bundles(tmp_path, capsys):
    """A bundle from a run predating the plane (or --freshness off) is
    well-formed: exit 0 with the no-telemetry note, never exit 2."""
    blackbox_mod.install(config={"t": 1})
    try:
        path = blackbox_mod.dump("quiet", out_dir=str(tmp_path), force=True)
    finally:
        blackbox_mod.uninstall()
    assert freshness_report.main([path]) == 0
    assert "no freshness telemetry" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# conf flags


def test_conf_flags():
    conf = ConfArguments()
    assert conf.freshness == "on"  # the plane is ON by default
    assert conf.freshnessSloMs == 0.0 and conf.servingStaleSloS == 0.0
    conf = ConfArguments().parse(
        ["--freshness", "off", "--freshnessSloMs", "2500",
         "--servingStaleSloS", "30"]
    )
    assert conf.freshness == "off"
    assert conf.freshnessSloMs == 2500.0 and conf.servingStaleSloS == 30.0
    with pytest.raises(SystemExit):
        ConfArguments().parse(["--freshness", "bogus"])
    with pytest.raises(SystemExit):
        ConfArguments().parse(["--freshnessSloMs", "-1"])
    with pytest.raises(SystemExit):
        ConfArguments().parse(["--servingStaleSloS", "-0.5"])
