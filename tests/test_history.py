"""Telemetry historian (ISSUE 20): durable long-horizon time series +
phase-segmented cross-run perf regression sentinel.

The laws under test, in the order the ISSUE states them:
- **durability discipline** (the journal's): CRC32-framed records in
  rotated segments, torn tails truncated LOUDLY on recovery (and skipped,
  never fatal, by the offline reader), ``--historyMaxMb`` enforced by
  dropping whole oldest segments (counted), restart-append continuity —
  one directory accumulates a multi-run timeline;
- **SIGKILL reconstruction** (ACCEPTANCE): a killed run's leftover
  segments ALONE rebuild the healthy/degraded phase intervals and the
  least-squares RSS slope, and ``tools/history_report.py`` exits 0 on
  them;
- **perfGuard round trip** (ACCEPTANCE): run 1 stamps healthy-phase
  stage-clock medians into baseline.json at clean shutdown; run 2's
  SUSTAINED seeded regression fires ONE warn-only blackbox event per
  episode + ``perf.regressions`` — and never anything louder;
- **zero added fetches / zero added collectives** with sampling ON,
  COUNTED over a real lockstep run (the PR 5/8/16 idiom);
- **off bit-parity**: a ``--history off`` app run lands BIT-identical
  weights and never creates the history directory;
- the ``History`` wire view, the blackbox bundle's history tail, the
  postmortem rendering, ``tools/history_report.py`` exit codes, and the
  run-id/fingerprint provenance seam (utils/runid.py).
"""

import json
import os
import struct
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import history_report  # noqa: E402
from tools import postmortem_report  # noqa: E402
from twtml_tpu.config import ConfArguments  # noqa: E402
from twtml_tpu.telemetry import blackbox as blackbox_mod  # noqa: E402
from twtml_tpu.telemetry import historian as H  # noqa: E402
from twtml_tpu.telemetry import metrics as _metrics  # noqa: E402
from twtml_tpu.telemetry import sideband as _sideband  # noqa: E402

NOW_MS = 1785320000000
CLOSED = "http://127.0.0.1:9"


@pytest.fixture(autouse=True)
def _fresh_state():
    _metrics.reset_for_tests()
    _sideband.reset_for_tests()
    H.reset_for_tests()
    yield
    H.reset_for_tests()
    _metrics.reset_for_tests()
    _sideband.reset_for_tests()


class _Clock:
    """Drives the TWTML_NOW_MS seam sample-by-sample."""

    def __init__(self, monkeypatch, t0=NOW_MS):
        self._mp = monkeypatch
        self.t = t0
        self.set(t0)

    def set(self, t_ms):
        self.t = t_ms
        self._mp.setenv("TWTML_NOW_MS", str(int(t_ms)))

    def tick(self, dt_ms=60000):
        self.set(self.t + dt_ms)


def _seed_stages(monkeypatch):
    """Replace the cumulative stage clock with a driveable dict; bump the
    returned dict's values to seed per-sample deltas."""
    cum = {}
    monkeypatch.setattr(_sideband, "stage_seconds", lambda: dict(cum))
    return cum


def _seed_rss(monkeypatch):
    box = {"mb": 100.0}
    import twtml_tpu.utils.rss as rss_mod

    monkeypatch.setattr(rss_mod, "rss_mb", lambda: box["mb"])
    return box


def _flip_phase(phase, t_s):
    mon = _metrics.get_health_monitor()
    with mon._lock:
        mon.phase = phase
        mon.transitions.append((t_s, phase))


# ---------------------------------------------------------------------------
# durability discipline: frames, restart continuity, torn tails, ceiling


def test_frame_roundtrip_and_restart_continuity(tmp_path, monkeypatch):
    clock = _Clock(monkeypatch)
    d = str(tmp_path / "hist")
    H.configure(d, run_id=1, fingerprint="aaa111")
    for _ in range(3):
        clock.tick()
        H.sample()
    H.uninstall()

    recs = H.read_series(d)
    assert [r["k"] for r in recs] == ["r", "s", "s", "s"]
    assert recs[0]["run_id"] == 1 and recs[0]["fingerprint"] == "aaa111"
    assert [r["seq"] for r in recs if r["k"] == "s"] == [1, 2, 3]

    # restart: the second run APPENDS after the recovered tail — one
    # directory is one multi-run timeline
    h2 = H.configure(d, run_id=2, fingerprint="bbb222")
    assert h2.next_seq == 5  # 4 recovered records + this run's header
    clock.tick()
    H.sample()
    H.uninstall()
    recs = H.read_series(d)
    assert [r["run_id"] for r in recs if r["k"] == "r"] == [1, 2]
    assert len([r for r in recs if r["k"] == "s"]) == 4


def test_torn_tail_truncates_loudly_and_reader_skips_it(
    tmp_path, monkeypatch
):
    clock = _Clock(monkeypatch)
    d = str(tmp_path / "hist")
    H.configure(d, run_id=1)
    for _ in range(3):
        clock.tick()
        H.sample()
    H.uninstall()

    segs = sorted(p for p in os.listdir(d) if p.endswith(".twh"))
    assert len(segs) == 1
    path = os.path.join(d, segs[0])
    good_size = os.path.getsize(path)
    with open(path, "ab") as fh:  # a kill -9 mid-append: torn mid-payload
        fh.write(H.MAGIC + struct.pack("<II", 500, 12345) + b"partial")

    # the OFFLINE reader (a dead run's directory): torn tail skipped,
    # every complete record before it survives — never an error
    recs = H.read_series(d)
    assert len(recs) == 4

    # LIVE recovery truncates it loudly and appends after
    H.configure(d, run_id=2)
    reg = _metrics.get_registry()
    assert reg.counter("history.torn_tails").snapshot() == 1
    assert os.path.getsize(path) == good_size
    clock.tick()
    H.sample()
    H.uninstall()
    recs = H.read_series(d)
    assert [r["k"] for r in recs] == ["r", "s", "s", "s", "r", "s"]


def test_segment_rotation_and_disk_ceiling(tmp_path):
    d = str(tmp_path / "hist")
    h = H.configure(d, max_mb=1)  # segment_bytes = 256 KB
    assert h.segment_bytes == 256 * 1024
    pad = "x" * 20000
    for i in range(80):  # ~1.6 MB of records through a 1 MB ceiling
        h._write({"k": "s", "t_ms": NOW_MS + i, "rss_mb": 1.0, "pad": pad})
    reg = _metrics.get_registry()
    assert reg.counter("history.segments_dropped").snapshot() >= 1
    assert h.disk_bytes() <= h.max_bytes + h.segment_bytes
    segs = h._segments()
    assert len(segs) >= 2            # rotation happened
    assert segs[0][0] > 0            # ...and the OLDEST segment was dropped
    assert reg.gauge("history.disk_mb").snapshot() > 0
    assert H.read_series(d)          # survivors parse end to end
    H.uninstall()


# ---------------------------------------------------------------------------
# ACCEPTANCE: a SIGKILLed run's leftovers alone rebuild the timeline


def test_sigkill_leftovers_reconstruct_phases_and_slope(
    tmp_path, monkeypatch, capsys
):
    clock = _Clock(monkeypatch)
    rss = _seed_rss(monkeypatch)
    d = str(tmp_path / "hist")
    H.configure(d, run_id=5, fingerprint="deadbeef0001")

    def burst(n, phase=None):
        for _ in range(n):
            clock.tick()          # 1 min per sample
            rss["mb"] += 2.0      # 2 MB per sample -> 2 MB/min slope
            if phase is not None:
                _flip_phase(phase, clock.t / 1000.0)
                phase = None
            H.sample()

    burst(5)
    burst(5, phase="degraded")
    burst(5, phase="healthy")
    # the kill: no stamp, no clean close — plus a torn frame on the tail
    H.uninstall()
    seg = sorted(p for p in os.listdir(d) if p.endswith(".twh"))[-1]
    with open(os.path.join(d, seg), "ab") as fh:
        fh.write(b"\x00garbage-from-a-kill-mid-write")

    records = H.read_series(d)
    intervals = H.phase_intervals(records)
    assert [iv["phase"] for iv in intervals] == [
        "healthy", "degraded", "healthy",
    ]
    assert [iv["samples"] for iv in intervals] == [5, 5, 5]
    assert H.rss_slope(records) == pytest.approx(2.0, rel=0.05)
    trends = H.phase_trends(records)
    assert set(trends) == {"healthy", "degraded"}
    assert trends["healthy"]["samples"] == 10

    # the CLI check on the leftovers: exit 0 + the same derivations
    assert history_report.main([d, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["samples"] == 15
    assert len(summary["phase_intervals"]) == 3
    assert summary["rss_slope_mb_per_min"] == pytest.approx(2.0, rel=0.05)
    assert summary["runs"][0]["run_id"] == 5
    assert history_report.main([d]) == 0  # rendered form, same verdict
    assert "degraded" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# ACCEPTANCE: the cross-run perfGuard round trip (warn-only, episodic)


def test_perf_guard_baseline_round_trip_and_sustained_regression(
    tmp_path, monkeypatch
):
    clock = _Clock(monkeypatch)
    cum = _seed_stages(monkeypatch)
    d = str(tmp_path / "hist")
    rec = blackbox_mod.install(config={})
    reg = _metrics.get_registry()
    try:
        # run 1: steady 2.0 ms/tick featurize -> stamped at clean shutdown
        cum["featurize"] = 0.0
        H.configure(d, run_id=1, fingerprint="cfg1")
        for _ in range(10):
            clock.tick()
            cum["featurize"] += 0.002
            H.sample()
        base = H.stamp_baseline()
        assert base == {
            "version": 1, "run_id": 1, "fingerprint": "cfg1",
            "samples": 10, "stages_ms": {"featurize": 2.0},
        }
        H.uninstall()
        assert json.load(
            open(os.path.join(d, H.BASELINE_NAME))
        )["run_id"] == 1

        # run 2 loads the baseline; a SUSTAINED 2.5x regression fires ONE
        # episode after GUARD_WINDOW consecutive healthy breaches
        h2 = H.configure(d, run_id=2, fingerprint="cfg1")
        assert h2.baseline is not None
        for _ in range(3):  # at baseline: no breach run
            clock.tick()
            cum["featurize"] += 0.002
            H.sample()
        assert reg.counter("perf.regressions").snapshot() == 0
        for i in range(H.GUARD_WINDOW):
            clock.tick()
            cum["featurize"] += 0.005  # 5.0 ms/tick = 2.5x
            H.sample()
            if i < H.GUARD_WINDOW - 1:  # a burst below the window is noise
                assert reg.counter("perf.regressions").snapshot() == 0
        assert reg.counter("perf.regressions").snapshot() == 1
        events = [
            e for e in rec.bundle("t")["events"]
            if e["kind"] == "perf_regression"
        ]
        assert len(events) == 1
        assert events[0]["stage"] == "featurize"
        assert events[0]["ratio"] == pytest.approx(2.5, abs=0.01)
        assert events[0]["baseline_run_id"] == 1

        for _ in range(4):  # episode latch: no re-fire while sustained
            clock.tick()
            cum["featurize"] += 0.005
            H.sample()
        assert reg.counter("perf.regressions").snapshot() == 1
        clock.tick()
        cum["featurize"] += 0.002  # recovery closes the episode
        H.sample()
        for _ in range(H.GUARD_WINDOW):  # a NEW sustained breach re-fires
            clock.tick()
            cum["featurize"] += 0.005
            H.sample()
        assert reg.counter("perf.regressions").snapshot() == 2
        H.uninstall()

        # --perfGuard off: same breach pattern, sentinel fully quiet and
        # the clean-shutdown stamp is withheld
        h3 = H.configure(d, run_id=3, perf_guard=False)
        for _ in range(H.GUARD_WINDOW + 2):
            clock.tick()
            cum["featurize"] += 0.005
            H.sample()
        assert reg.counter("perf.regressions").snapshot() == 2
        assert H.stamp_baseline() is None
        assert h3.baseline is not None  # loaded for reports, just not armed
    finally:
        blackbox_mod.uninstall()


def test_guard_ignores_noise_scale_stages(tmp_path, monkeypatch):
    """Stages under GUARD_MIN_BASELINE_MS are jitter:
    a 0.01 -> 0.05 ms "5x" never pages."""
    clock = _Clock(monkeypatch)
    cum = _seed_stages(monkeypatch)
    d = str(tmp_path / "hist")
    cum["tiny"] = 0.0
    H.configure(d, run_id=1)
    for _ in range(10):
        clock.tick()
        cum["tiny"] += 0.00001  # 0.01 ms/tick baseline
        H.sample()
    assert H.stamp_baseline()["stages_ms"]["tiny"] == 0.01
    H.uninstall()
    H.configure(d, run_id=2)
    for _ in range(H.GUARD_WINDOW + 2):
        clock.tick()
        cum["tiny"] += 0.00005  # "5x regression" at noise scale
        H.sample()
    assert _metrics.get_registry().counter(
        "perf.regressions"
    ).snapshot() == 0


def test_baseline_needs_enough_healthy_samples(tmp_path, monkeypatch):
    clock = _Clock(monkeypatch)
    d = str(tmp_path / "hist")
    H.configure(d, run_id=1)
    for _ in range(H.BASELINE_MIN_SAMPLES - 1):
        clock.tick()
        H.sample()
    assert H.stamp_baseline() is None  # too few to be a verdict
    assert not os.path.exists(os.path.join(d, H.BASELINE_NAME))


# ---------------------------------------------------------------------------
# views: History wire view, blackbox bundle tail, postmortem rendering


def test_view_bundle_tail_and_postmortem_rendering(tmp_path, monkeypatch):
    clock = _Clock(monkeypatch)
    rss = _seed_rss(monkeypatch)
    d = str(tmp_path / "hist")
    rec = blackbox_mod.install(config={})
    try:
        assert H.last_history() is None and H.bundle_tail() is None
        H.configure(d, run_id=9, fingerprint="fff999")
        for _ in range(3):
            clock.tick()
            rss["mb"] += 1.0
            H.sample()
        view = H.last_history()
        assert view["samples"] == 3 and view["runId"] == 9
        assert view["phase"] == "healthy"
        assert len(view["rss"]) == 3 and view["rssMb"] == rss["mb"]
        assert view["regressions"] == 0
        from twtml_tpu.telemetry.api_types import History

        History(**view)  # the view IS the wire type, field for field

        bundle = rec.bundle("test-death")
        assert bundle["history"]["run_id"] == 9
        assert len(bundle["history"]["samples"]) == 3
        # postmortem narrates the minutes before death...
        summary = postmortem_report.summarize(bundle)
        assert summary["history"]["samples"] == 3
        assert "history tail (run 9)" in postmortem_report.render(summary)
        # ...and history_report accepts the bundle as a source (exit 0)
        bpath = tmp_path / "bundle.json"
        bpath.write_text(json.dumps(bundle))
        assert history_report.main([str(bpath)]) == 0

        H.uninstall()
        assert H.last_history() is None
        assert rec.bundle("after")["history"] is None
        assert postmortem_report.summarize(
            rec.bundle("after")
        )["history"] is None
    finally:
        blackbox_mod.uninstall()


def test_report_exit_codes(tmp_path, monkeypatch, capsys):
    empty = tmp_path / "empty"
    empty.mkdir()
    assert history_report.main([str(empty)]) == 2  # no records
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert history_report.main([str(bad)]) == 2    # malformed bundle
    assert history_report.main([]) == 2            # usage
    capsys.readouterr()
    clock = _Clock(monkeypatch)
    d = str(tmp_path / "hist")
    H.configure(d, run_id=1)
    clock.tick()
    H.sample()
    H.uninstall()
    assert history_report.main([d]) == 0


# ---------------------------------------------------------------------------
# THE counted constraint: sampling adds zero fetches, zero collectives
# over a real lockstep run (the PR 5/8/16 law)


def test_sampling_adds_no_fetches_and_no_collectives(tmp_path, monkeypatch):
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

    d = str(tmp_path / "hist")
    H.configure(d, run_id=1)
    ssc = StreamingContext(batch_interval=0)
    stream = ssc.source_stream(
        SyntheticSource(total=64, seed=7, base_ms=NOW_MS),
        Featurizer(now_ms=NOW_MS),
        row_bucket=16, token_bucket=64, device_hash=True,
    )
    model = StreamingLinearRegressionWithSGD(num_iterations=2)

    def handle(out, b, t, at_boundary=True):
        H.sample()  # the publish-seam cadence, once per delivered batch

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
    # ZERO added host fetches: one per dispatched batch — every sample was
    # a pure host-side snapshot of already-computed views
    assert calls["get"] == ssc.batches_processed
    assert reg["counters"]["history.samples"] == ssc.batches_processed
    samples = [r for r in H.read_series(d) if r.get("k") == "s"]
    assert len(samples) == ssc.batches_processed
    H.uninstall()


# ---------------------------------------------------------------------------
# app-level acceptance: default-on counting + OFF bit-parity


BASE = [
    "--source", "replay", "--seconds", "0", "--backend", "cpu",
    "--batchBucket", "16", "--tokenBucket", "64", "--master", "local[1]",
    "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
]


def _corpus_file(tmp_path, total=8 * 16, seed=51):
    from twtml_tpu.streaming.sources import SyntheticSource

    statuses = list(
        SyntheticSource(total=total, seed=seed, base_ms=NOW_MS).produce()
    )
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


def test_app_default_history_counts_and_off_is_bit_exact(
    tmp_path, monkeypatch
):
    """ACCEPTANCE: a real app run with the DEFAULT --history auto (on via
    --checkpointDir) fetches exactly once per batch, leaves CRC-valid
    segments behind, and a --history off run lands BIT-identical weights
    with no history directory at all."""
    from twtml_tpu.checkpoint import Checkpointer

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    monkeypatch.setenv("TWTML_RUN_ID_FILE", str(tmp_path / "runid"))
    path = _corpus_file(tmp_path)
    totals_on, fetches_on = _run_counting_fetches(
        BASE + ["--replayFile", str(path),
                "--checkpointDir", str(tmp_path / "ck_on"),
                "--checkpointEvery", "1"]
    )
    assert totals_on["batches"] == 8
    assert fetches_on == 8  # ONE device_get per batch, the historian adds none
    hist_dir = str(tmp_path / "ck_on" / "history")
    recs = H.read_series(hist_dir)
    heads = [r for r in recs if r["k"] == "r"]
    assert len(heads) == 1 and heads[0]["run_id"] >= 1
    assert len(heads[0]["fingerprint"]) == 12
    samples = [r for r in recs if r["k"] == "s"]
    assert samples and samples[0]["rss_mb"] > 0
    assert history_report.main([hist_dir]) == 0
    w_on, _meta = Checkpointer(str(tmp_path / "ck_on")).restore()

    totals_off, fetches_off = _run_counting_fetches(
        BASE + ["--replayFile", str(path), "--history", "off",
                "--checkpointDir", str(tmp_path / "ck_off"),
                "--checkpointEvery", "1"]
    )
    assert totals_off["batches"] == 8
    assert fetches_off == 8
    assert not os.path.exists(str(tmp_path / "ck_off" / "history"))
    assert H.last_history() is None  # module fully off after the off run
    w_off, _ = Checkpointer(str(tmp_path / "ck_off")).restore()
    # the bit-parity law: identical weights with the historian on or off
    assert np.asarray(w_on).tobytes() == np.asarray(w_off).tobytes()
    assert totals_on["count"] == totals_off["count"]


def test_history_on_without_checkpoint_dir_refuses(tmp_path):
    from twtml_tpu.apps.common import install_historian

    conf = ConfArguments().parse(BASE + ["--history", "on"])
    with pytest.raises(SystemExit):
        install_historian(conf)


# ---------------------------------------------------------------------------
# config resolution + the provenance seam (utils/runid.py)


def test_effective_history_resolution(tmp_path):
    conf = ConfArguments().parse(list(BASE))
    assert conf.history == "auto" and not conf.effective_history()
    conf = ConfArguments().parse(
        BASE + ["--checkpointDir", str(tmp_path / "ck")]
    )
    assert conf.effective_history()  # auto follows the checkpoint flag
    conf = ConfArguments().parse(
        BASE + ["--checkpointDir", str(tmp_path / "ck"),
                "--history", "off"]
    )
    assert not conf.effective_history()
    for bad in (["--history", "sometimes"], ["--historyMaxMb", "0"],
                ["--perfGuard", "abort"], ["--perfGuardRatio", "0.9"]):
        with pytest.raises(SystemExit):
            ConfArguments().parse(BASE + bad)


def test_run_id_monotonic_and_fingerprint_stable(tmp_path, monkeypatch):
    from twtml_tpu.utils.runid import config_fingerprint, next_run_id

    monkeypatch.setenv("TWTML_RUN_ID_FILE", str(tmp_path / "runid"))
    ids = [next_run_id() for _ in range(3)]
    assert ids == [1, 2, 3]  # monotonic across "runs" on one host

    fp1 = config_fingerprint({"batch": 2048, "wire": "ragged"})
    fp2 = config_fingerprint({"wire": "ragged", "batch": 2048})
    assert fp1 == fp2 and len(fp1) == 12  # order-free, compact
    assert fp1 != config_fingerprint({"batch": 1024, "wire": "ragged"})
    conf = ConfArguments().parse(list(BASE))
    assert len(config_fingerprint(conf)) == 12  # real config objects too
