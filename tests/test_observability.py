"""Fleet observability plane (ISSUE 5): the per-host telemetry sideband,
the lockstep straggler attributor, and the crash flight recorder.

The hard constraints are asserted the way PR 1/PR 4 asserted theirs:
the sideband path issues ZERO added host fetches (jax.device_get counted
end to end over a real lockstep run) and ZERO added collectives (exactly
one cadence allgather per tick — process_allgather counted). The flight
recorder's bundle must be parseable by tools/postmortem_report.py (exit 0;
malformed bundles exit 2), and the CI post-mortem smoke drives a chaos run
into a sentinel abort and renders the bundle it leaves behind.
"""

import json
import os
import signal

import numpy as np
import pytest

from tools import postmortem_report
from twtml_tpu.telemetry import blackbox as blackbox_mod
from twtml_tpu.telemetry import metrics as metrics_mod
from twtml_tpu.telemetry import sideband as sideband_mod
from twtml_tpu.telemetry.straggler import StragglerAttributor

BASE_MS = 1785320000000


@pytest.fixture(autouse=True)
def clean_state():
    metrics_mod.reset_for_tests()
    sideband_mod.reset_for_tests()
    blackbox_mod.uninstall()
    yield
    blackbox_mod.uninstall()
    sideband_mod.reset_for_tests()
    metrics_mod.reset_for_tests()


# ---------------------------------------------------------------------------
# stage clock + sideband collector


def test_stage_clock_accumulates():
    sideband_mod.record_stage("fetch", 0.25)
    sideband_mod.record_stage("fetch", 0.25)
    sideband_mod.record_stage("dispatch", 0.1)
    assert sideband_mod.stage_seconds()["fetch"] == pytest.approx(0.5)


def test_collector_ships_deltas_not_totals():
    c = sideband_mod.SidebandCollector()
    sideband_mod.record_stage("featurize", 0.2)
    v1 = c.collect()
    assert v1.shape == (sideband_mod.WIDTH,)
    assert v1.dtype == np.float64
    i = sideband_mod.FIELDS.index("featurize_ms")
    assert v1[i] == pytest.approx(200.0)
    # second tick with no new featurize work: the DELTA is zero
    v2 = c.collect()
    assert v2[i] == 0.0
    # registry-backed fields ride along
    metrics_mod.get_registry().gauge("ingest.queue_rows").set(4096)
    metrics_mod.get_registry().counter("ingest.rows_shed").inc(7)
    v3 = c.collect(rollbacks=2)
    assert v3[sideband_mod.FIELDS.index("queue_rows")] == 4096
    assert v3[sideband_mod.FIELDS.index("rows_shed")] == 7
    assert v3[sideband_mod.FIELDS.index("rollbacks")] == 2
    assert v3[sideband_mod.FIELDS.index("tick_prep_ms")] >= 0


def test_collector_never_ships_nonfinite():
    c = sideband_mod.SidebandCollector()
    sideband_mod.record_stage("fetch", float("nan"))
    v = c.collect()
    assert np.isfinite(v).all()


# ---------------------------------------------------------------------------
# straggler attribution


def _matrix(prep, **stages):
    """[hosts, WIDTH] matrix with per-host tick_prep and named stage ms."""
    m = np.zeros((len(prep), sideband_mod.WIDTH))
    m[:, sideband_mod.FIELDS.index("tick_prep_ms")] = prep
    for name, vals in stages.items():
        m[:, sideband_mod.FIELDS.index(name)] = vals
    return m


def test_straggler_names_host_and_ladder_stage():
    a = StragglerAttributor()
    # host 1 gates every tick, its dispatch (upload) dominating
    v = a.observe(_matrix(
        [10.0, 160.0],
        dispatch_ms=[2.0, 140.0], featurize_ms=[5.0, 6.0],
        fetch_ms=[2.0, 2.0],
    ))
    assert v["host"] == 1
    assert v["stage"] == "upload"
    assert v["skew_ms"] == pytest.approx(150.0)
    reg = metrics_mod.get_registry()
    assert reg.gauge("lockstep.straggler_host").snapshot() == 1
    assert reg.gauge("lockstep.tick_skew_ms").snapshot() == pytest.approx(150.0)
    assert reg.counter("straggler.upload.ticks").snapshot() == 1


def test_straggler_quiet_below_skew_floor():
    a = StragglerAttributor()
    v = a.observe(_matrix([10.0, 11.0], fetch_ms=[8.0, 8.0]))
    assert v["host"] == -1 and v["stage"] == ""
    assert metrics_mod.get_registry().gauge(
        "lockstep.straggler_host"
    ).snapshot() == -1


def test_straggler_falls_back_to_device_when_host_stages_explain_nothing():
    a = StragglerAttributor()
    # host 0 gates by 400ms but its host-side stages account for ~1% of the
    # tick: the time went to the device step / collective interior
    v = a.observe(_matrix([500.0, 100.0], dispatch_ms=[5.0, 4.0]))
    assert v["host"] == 0
    assert v["stage"] == "device"


def test_straggler_deviation_beats_absolute_once_history_exists():
    a = StragglerAttributor(min_history=4)
    # steady state: host 1 always has big (legitimate) fetch times
    for _ in range(8):
        a.observe(_matrix(
            [10.0, 12.0], fetch_ms=[50.0, 50.0], featurize_ms=[5.0, 5.0]
        ))
    # now featurize BLOWS UP on host 1 — deviation ranks it above the
    # absolutely-larger-but-unchanged fetch column
    v = a.observe(_matrix(
        [10.0, 90.0], fetch_ms=[50.0, 52.0], featurize_ms=[5.0, 70.0]
    ))
    assert v["host"] == 1
    assert v["stage"] == "featurize"


def test_lockstep_telemetry_publishes_hosts_view():
    tele = sideband_mod.LockstepTelemetry(0, 2)
    m = _matrix([10.0, 200.0], dispatch_ms=[2.0, 150.0])
    tele.ingest(m)
    view = sideband_mod.last_hosts()
    assert view is not None
    assert len(view["hosts"]) == 2
    assert view["hosts"][1]["tick_prep_ms"] == pytest.approx(200.0)
    assert view["straggler"] == 1
    assert view["stage"] == "upload"
    assert metrics_mod.get_registry().counter("lockstep.ticks").snapshot() == 1


# ---------------------------------------------------------------------------
# THE acceptance constraint: zero added fetches, zero added collectives —
# a real lockstep run with the sideband riding the one cadence allgather


def test_sideband_adds_no_fetches_and_no_collectives(monkeypatch):
    import jax
    from jax.experimental import multihost_utils

    from twtml_tpu.apps.common import FetchPipeline
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.context import StreamingContext
    from twtml_tpu.streaming.sources import SyntheticSource

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

    ssc = StreamingContext(batch_interval=0)
    stream = ssc.source_stream(
        SyntheticSource(total=64, seed=7, base_ms=BASE_MS),
        Featurizer(now_ms=BASE_MS),
        row_bucket=16, token_bucket=64, device_hash=True,
    )
    model = StreamingLinearRegressionWithSGD(num_iterations=2)
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: None, deterministic=True
    )
    stream.foreach_batch(pipe.on_batch)
    ssc.start(lockstep=True)
    assert ssc.await_termination(timeout=120)
    ssc.stop()
    pipe.flush()
    assert not ssc.failed
    assert ssc.batches_processed >= 4

    reg = metrics_mod.get_registry().snapshot()
    ticks = reg["counters"]["lockstep.ticks"]
    # ZERO added collectives: exactly ONE allgather per lockstep tick —
    # the sideband rides it, it never adds one
    assert calls["allgather"] == ticks
    # ZERO added host fetches: one per dispatched batch (FetchPipeline's
    # contract), none from the sideband/straggler/collector path
    assert calls["get"] == ssc.batches_processed
    assert reg["counters"]["fetch.count"] == ssc.batches_processed
    # and the hosts[] view materialized (single host, never "gating")
    view = sideband_mod.last_hosts()
    assert view is not None and len(view["hosts"]) == 1
    assert view["straggler"] == -1


# ---------------------------------------------------------------------------
# flight recorder: ring, notes, bundle, dump, SIGTERM


def test_ring_is_bounded_and_counts_drops(tmp_path):
    rec = blackbox_mod.install(
        config={"x": 1}, out_dir=str(tmp_path), capacity=8
    )
    for i in range(20):
        rec.record("tick", i=i)
    bundle = rec.bundle("test")
    assert len(bundle["events"]) == 8
    assert bundle["events"][-1]["i"] == 19  # newest survive
    assert bundle["events_dropped"] == 12
    for key in postmortem_report.REQUIRED_KEYS:
        assert key in bundle


def test_notes_survive_ring_churn(tmp_path):
    rec = blackbox_mod.install(out_dir=str(tmp_path), capacity=4)
    blackbox_mod.note("last_checkpoint", {"step": 12, "count": 24576})
    for i in range(64):
        rec.record("noise", i=i)
    assert rec.bundle("t")["notes"]["last_checkpoint"]["step"] == 12


def test_dump_is_single_shot_until_forced(tmp_path):
    rec = blackbox_mod.install(out_dir=str(tmp_path))
    p1 = rec.dump("first")
    p2 = rec.dump("second")  # no-op: one bundle per failure
    assert p1 == p2
    doc = json.load(open(p1))
    assert doc["reason"] == "first"
    p3 = rec.dump("forced", force=True)
    assert json.load(open(p3))["reason"] == "forced"


def test_request_abort_funnel_dumps_bundle(tmp_path):
    from twtml_tpu.streaming.context import StreamingContext

    blackbox_mod.install(config={"app": "t"}, out_dir=str(tmp_path))
    ssc = StreamingContext()
    ssc.request_abort("unit-test abort")
    assert ssc.failed
    path = blackbox_mod.last_dump_path()
    assert path and os.path.exists(path)
    doc = postmortem_report.load_bundle(path)
    assert doc["reason"] == "unit-test abort"
    assert any(e["kind"] == "abort" for e in doc["events"])
    assert postmortem_report.main([path]) == 0


def test_trace_spans_ride_the_ring(tmp_path):
    from twtml_tpu.telemetry import trace as trace_mod

    rec = blackbox_mod.install(out_dir=str(tmp_path))
    tr = trace_mod.install(str(tmp_path / "t.trace"))
    with tr.span("featurize", rows=16):
        pass
    tr.instant("health_phase", phase="degraded")
    trace_mod.uninstall()
    kinds = [e["kind"] for e in rec.bundle("t")["events"]]
    assert "span" in kinds and "instant" in kinds
    span = [e for e in rec.bundle("t")["events"] if e["kind"] == "span"][0]
    assert span["name"] == "featurize" and span["rows"] == 16


def test_sigterm_handler_dumps_and_chains(tmp_path):
    rec = blackbox_mod.install(out_dir=str(tmp_path))
    chained = []
    blackbox_mod._on_sigterm(
        signal.SIGTERM, None, _prev=lambda s, f: chained.append(s)
    )
    assert chained == [signal.SIGTERM]
    path = rec.last_dump_path
    assert path and json.load(open(path))["reason"] == "SIGTERM"


def test_module_level_record_is_noop_without_recorder():
    blackbox_mod.uninstall()
    blackbox_mod.record("anything", x=1)  # must not raise
    blackbox_mod.note("k", "v")
    assert blackbox_mod.abort_dump("r") is None
    assert blackbox_mod.dump("r") is None


# ---------------------------------------------------------------------------
# postmortem_report as a CHECK (CI and chaos_soak gate on its exit status)


def test_postmortem_report_exit_codes(tmp_path):
    rec = blackbox_mod.install(config={"a": 1}, out_dir=str(tmp_path))
    rec.record("chaos", target="fetch", action="delay", call=3)
    good = rec.dump("test bundle")
    assert postmortem_report.main([good]) == 0
    assert postmortem_report.main([good, "--json"]) == 0

    bad = tmp_path / "bad.json"
    bad.write_text("not json {")
    assert postmortem_report.main([str(bad)]) == 2
    empty = tmp_path / "empty.json"
    empty.write_text("")
    assert postmortem_report.main([str(empty)]) == 2
    not_bundle = tmp_path / "nb.json"
    not_bundle.write_text(json.dumps({"kind": "something-else"}))
    assert postmortem_report.main([str(not_bundle)]) == 2
    missing_keys = tmp_path / "mk.json"
    doc = json.load(open(good))
    del doc["events"]
    missing_keys.write_text(json.dumps(doc))
    assert postmortem_report.main([str(missing_keys)]) == 2
    assert postmortem_report.main([str(tmp_path / "absent.json")]) == 2


def test_postmortem_report_summary_contents(tmp_path):
    rec = blackbox_mod.install(
        config={"_appName": "twtml-test"}, out_dir=str(tmp_path)
    )
    blackbox_mod.note("last_checkpoint", {"step": 8, "count": 16384})
    rec.record("fetch_retry", attempt=1, why="timeout")
    rec.record("fetch_abort", attempts=4, why="timeout")
    sideband_mod.publish_hosts({
        "hosts": [{"host": 0}, {"host": 1}],
        "straggler": 1, "stage": "upload", "skew_ms": 140.0,
    })
    path = rec.dump("fetch watchdog exhausted")
    s = postmortem_report.summarize(postmortem_report.load_bundle(path))
    assert s["reason"] == "fetch watchdog exhausted"
    assert s["checkpoint"] == {"step": 8, "count": 16384}
    assert s["event_kinds"] == {"fetch_retry": 1, "fetch_abort": 1}
    assert s["straggler"] == {"host": 1, "stage": "upload", "skew_ms": 140.0}
    text = postmortem_report.render(s)
    assert "fetch watchdog exhausted" in text
    assert "host 1 · upload" in text


# ---------------------------------------------------------------------------
# CI post-mortem smoke: a chaos run dies on the sentinel's rollback budget
# and leaves a bundle the report renders — end to end through the real app


def _write_replay(tmp_path, n):
    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    with open(path, "w") as fh:
        for s in SyntheticSource(total=n, seed=7, base_ms=BASE_MS).produce():
            fh.write(json.dumps(s.to_json()) + "\n")
    return path


def test_postmortem_smoke_killed_chaos_run_leaves_wellformed_bundle(tmp_path):
    import jax

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.streaming import faults

    jax.devices()
    replay = _write_replay(tmp_path, 4 * 16)
    conf = ConfArguments().parse([
        "--source", "replay", "--replayFile", str(replay),
        "--seconds", "0", "--backend", "cpu", "--master", "local[1]",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--checkpointDir", str(tmp_path / "ck"), "--checkpointEvery", "1",
        "--chaos", "source.nan@2",
        "--sentinelRollbacks", "1", "--sentinelWindow", "8",
        "--lightning", "http://127.0.0.1:9", "--twtweb", "http://127.0.0.1:9",
    ])
    try:
        with pytest.raises(RuntimeError):
            app.run(conf)
    finally:
        faults.uninstall_chaos()
    path = blackbox_mod.last_dump_path()
    assert path and os.path.exists(path)
    # the bundle lands NEXT TO the checkpoint dir
    assert os.path.dirname(path) == str(tmp_path)
    assert postmortem_report.main([path]) == 0
    doc = postmortem_report.load_bundle(path)
    kinds = {e["kind"] for e in doc["events"]}
    # the way down is on record: the chaos rule fired, the sentinel rolled
    # back, the budget abort triggered, the funnel dumped
    assert {"chaos", "sentinel_rollback", "sentinel_abort", "abort"} <= kinds
    assert doc["notes"]["last_checkpoint"]["step"] >= 1
    assert doc["config"]["chaos"] == "source.nan@2"
