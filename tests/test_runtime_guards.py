"""Runtime self-healing guards below the source layer (ISSUE 2): the fetch
watchdog (deadline / bounded re-issue / clean abort over the pooled
device_get), the publish circuit breaker (a dead dashboard stops taxing
the hot path), degraded-phase series shedding, and the satellite fixes
(stale checkpoint tmp sweep, wedged-producer stop warning, --webTimeout)."""

import logging
import os
import threading
import time

import numpy as np
import pytest

from twtml_tpu.apps.common import (
    FETCH_DEADLINE_MAX_S,
    FETCH_DEADLINE_MIN_S,
    FetchAbort,
    FetchPipeline,
    FetchWatchdog,
    SuperBatcher,
)
from twtml_tpu.config import ConfArguments
from twtml_tpu.telemetry import metrics as _metrics
from twtml_tpu.telemetry.breaker import CircuitBreaker


@pytest.fixture(autouse=True)
def fresh_metrics():
    _metrics.reset_for_tests()
    yield
    _metrics.reset_for_tests()


class FlakyFetchModel:
    """FakeModel whose per-batch fetch can stall or fail on chosen
    (batch, attempt) pairs — deterministic under the concurrent pool."""

    def __init__(self, slow: dict | None = None, errors: dict | None = None):
        self.dispatched = []
        self.slow = slow or {}  # {batch: {attempt: seconds}}
        self.errors = errors or {}  # {batch: {attempt}}
        self.attempts: dict = {}
        self._lock = threading.Lock()

    def step(self, batch):
        self.dispatched.append(batch)
        return {"i": np.asarray(batch)}

    def fetch_output(self, out):
        i = int(out["i"])
        with self._lock:
            n = self.attempts[i] = self.attempts.get(i, 0) + 1
        if n in self.errors.get(i, ()):
            raise ConnectionError(f"injected fetch failure b{i} attempt {n}")
        delay = self.slow.get(i, {}).get(n, 0.0)
        if delay:
            time.sleep(delay)
        return out


# -- fetch watchdog ----------------------------------------------------------

def test_fetch_deadline_derives_from_health_rtt(monkeypatch):
    class H:
        def __init__(self, ms):
            self.ms = ms

        def median_ms(self):
            return self.ms

    # no samples yet: maximally patient (first fetch of a run)
    assert FetchWatchdog(H(0)).deadline() == FETCH_DEADLINE_MAX_S
    # a 70 ms median fetch latency: the floor binds
    assert FetchWatchdog(H(70)).deadline() == FETCH_DEADLINE_MIN_S
    # multi-second stall regime: the cap binds
    assert FetchWatchdog(H(10_000)).deadline() == FETCH_DEADLINE_MAX_S
    # env pin (the ops/test hook) overrides the derivation
    monkeypatch.setenv("TWTML_FETCH_DEADLINE_S", "0.25")
    assert FetchWatchdog(H(70)).deadline() == 0.25


def test_fetch_timeout_reissues_and_preserves_order():
    # batch 0's first fetch stalls past the deadline; the re-issue is fast.
    model = FlakyFetchModel(slow={0: {1: 0.8}})
    events = []
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: events.append(int(out["i"])),
        depth=3, fetch_deadline_s=0.1, fetch_retries=2,
    )
    for i in range(5):
        pipe.on_batch(i, 0.0)
    pipe.flush()
    assert events == [0, 1, 2, 3, 4]  # strict order survives the retry
    assert _metrics.get_registry().counter("fetch.retries").snapshot() >= 1
    assert _metrics.get_registry().counter("fetch.aborts").snapshot() == 0
    assert not pipe._watchdog.aborted


def test_fetch_error_reissues_and_delivers():
    model = FlakyFetchModel(errors={1: {1}})  # batch 1, first attempt only
    events = []
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: events.append(int(out["i"])),
        depth=2, fetch_deadline_s=5.0, fetch_retries=2,
    )
    for i in range(4):
        pipe.on_batch(i, 0.0)
    pipe.flush()
    assert events == [0, 1, 2, 3]
    assert _metrics.get_registry().counter("fetch.retries").snapshot() == 1


def test_fetch_abort_after_bounded_retries():
    # every attempt at batch 0 stalls: bounded retries, then a clean abort
    model = FlakyFetchModel(slow={0: {n: 0.5 for n in range(1, 10)}})
    events, aborted = [], []
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: events.append(int(out["i"])),
        depth=1, fetch_deadline_s=0.05, fetch_retries=1,
        abort=lambda: aborted.append(True),
    )
    pipe.on_batch(0, 0.0)
    with pytest.raises(FetchAbort):
        pipe.on_batch(1, 0.0)  # depth backpressure forces the emit
    assert pipe._watchdog.aborted
    assert aborted == [True]
    assert _metrics.get_registry().counter("fetch.aborts").snapshot() == 1
    # after the abort nothing more trains, and flush neither hangs nor raises
    dispatched = len(model.dispatched)
    pipe.on_batch(2, 0.0)
    assert len(model.dispatched) == dispatched
    pipe.flush()
    assert events == []


def test_superbatcher_partial_path_abort():
    model = FlakyFetchModel(slow={0: {n: 0.5 for n in range(1, 10)}})
    aborted = []
    sb = SuperBatcher(
        model, 4, lambda out, b, t, at_boundary: None,
        abort=lambda: aborted.append(True),
        fetch_deadline_s=0.05, fetch_retries=1,
    )
    sb.on_batch(np.asarray(0), 0.0)  # one batch < k: a partial group
    with pytest.raises(FetchAbort):
        sb._close_group()  # the partial path's pooled fetch stalls
    assert sb._watchdog.aborted and aborted == [True]
    # flush after the abort is a clean no-op (pool shut down, nothing leaks)
    sb.flush()


def test_flush_shuts_pool_down_even_when_handler_raises():
    # satellite: an exception re-raised during the drain must not leak
    # executor threads — the pool shuts down in a finally
    model = FlakyFetchModel()

    def handler(out, b, t, at_boundary):
        raise ValueError("handler blew up")

    pipe = FetchPipeline(model, handler, depth=4)
    pipe.on_batch(0, 0.0)
    with pytest.raises(ValueError):
        pipe.flush()
    assert pipe._pool._shutdown  # stdlib flag: shutdown() was called


# -- lockstep peer watchdog (unit; the process-level case lives in
# tests/test_distributed_multiprocess.py::test_lockstep_peer_death_...) ------

def test_watched_allgather_timeout_and_error_paths(monkeypatch):
    from jax.experimental import multihost_utils

    from twtml_tpu.streaming.context import _watched_allgather

    # a collective that never completes (hard-killed peer, no RST): the
    # watchdog gives up and returns None instead of hanging forever
    release = threading.Event()
    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda arr: release.wait(5.0),
    )
    t0 = time.perf_counter()
    assert _watched_allgather(np.zeros(1), 0.1) is None
    assert time.perf_counter() - t0 < 2.0
    release.set()
    # a completing collective passes its result through
    monkeypatch.setattr(
        multihost_utils, "process_allgather", lambda arr: arr * 2
    )
    np.testing.assert_array_equal(
        _watched_allgather(np.ones(2), 1.0), 2 * np.ones(2)
    )
    # a raising collective (dead gloo peer = connection reset) propagates
    def boom(arr):
        raise ConnectionError("connection reset by peer")

    monkeypatch.setattr(multihost_utils, "process_allgather", boom)
    with pytest.raises(ConnectionError):
        _watched_allgather(np.ones(1), 1.0)


# -- publish circuit breaker -------------------------------------------------

def test_breaker_state_machine_with_half_open_probe():
    clock = {"t": 0.0}
    br = CircuitBreaker(
        "t1", failure_threshold=3, cooldown_s=10.0, now=lambda: clock["t"]
    )
    reg = _metrics.get_registry()
    # closed: flows; failures below the threshold keep it closed
    for _ in range(2):
        assert br.allow()
        br.record_failure()
    assert br.state == br.CLOSED
    assert br.allow()
    br.record_failure()  # 3rd consecutive: opens
    assert br.state == br.OPEN
    assert reg.gauge("publish.t1.breaker_open").snapshot() == 1
    # open: dropped-and-counted, no attempts
    assert not br.allow() and not br.allow()
    assert reg.counter("publish.t1.dropped").snapshot() == 2
    # cooldown elapsed: exactly ONE half-open probe is admitted
    clock["t"] = 10.0
    assert br.allow()
    assert br.state == br.HALF_OPEN
    assert not br.allow()  # probe outstanding: still shedding
    br.record_failure()  # probe failed: re-open for another cooldown
    assert br.state == br.OPEN
    assert not br.allow()
    # next probe succeeds: re-admit
    clock["t"] = 20.0
    assert br.allow()
    br.record_success()
    assert br.state == br.CLOSED
    assert reg.gauge("publish.t1.breaker_open").snapshot() == 0
    assert br.allow()
    # a success resets the consecutive-failure count
    br.record_failure()
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == br.CLOSED


def test_breaker_keeps_hot_path_fast_when_dashboard_is_dead():
    """Acceptance: with the breaker open, per-batch throughput must NOT
    collapse to the publish timeout — each publish used to block the batch
    handler for the full delay/timeout; after FAILURE_THRESHOLD failures
    the breaker drops them in microseconds."""
    from twtml_tpu.streaming import faults
    from twtml_tpu.telemetry.session_stats import SessionStats

    closed = "http://127.0.0.1:9"
    conf = ConfArguments().parse([
        "--twtweb", closed, "--lightning", closed, "--webTimeout", "0.5",
    ])
    # a slow-then-dead dashboard: every attempted publish costs 150ms
    faults.install_chaos("web:delay=0.15,web:error")
    try:
        session = SessionStats(conf)  # no open(): viz stays None
        real = np.array([1.0, 2.0])
        t0 = time.perf_counter()
        for i in range(5):  # FAILURE_THRESHOLD attempts, each slow
            session.update(10 * i, 2, 1.0, 1.0, 1.0, real, real)
        t_open = time.perf_counter()
        for i in range(20):  # breaker open: dropped, near-instant
            session.update(10 * i, 2, 1.0, 1.0, 1.0, real, real)
        t_end = time.perf_counter()
    finally:
        faults.uninstall_chaos()
    assert session._web_breaker.state == session._web_breaker.OPEN
    assert t_open - t0 >= 5 * 0.15  # the failures really were slow
    # 20 dropped publishes must cost nowhere near 20 x 150ms
    assert t_end - t_open < 1.0
    reg = _metrics.get_registry()
    assert reg.counter("publish.web.failures").snapshot() == 5
    assert reg.counter("publish.web.dropped").snapshot() >= 20


def test_series_sheds_to_every_nth_when_fetch_health_degraded():
    from twtml_tpu.telemetry.session_stats import SERIES_SHED_EVERY, SessionStats

    closed = "http://127.0.0.1:9"
    conf = ConfArguments().parse(["--twtweb", closed, "--lightning", closed])
    session = SessionStats(conf)
    calls = {"stats": 0, "series": 0, "metrics": 0}

    class StubWeb:
        timeout = 2.0

        def stats(self, *a, **k):
            calls["stats"] += 1

        def series(self, *a, **k):
            calls["series"] += 1

        def metrics(self, *a, **k):
            calls["metrics"] += 1

    session.web = StubWeb()
    monitor = _metrics.get_health_monitor()
    monitor.phase = monitor.DEGRADED  # force the degraded phase
    real = np.array([1.0])
    for i in range(2 * SERIES_SHED_EVERY):
        session.update(i, 1, 1.0, 1.0, 1.0, real, real)
    # stats keep full per-batch resolution; series shed to every Nth
    assert calls["stats"] == 2 * SERIES_SHED_EVERY
    assert calls["series"] == 2
    shed = _metrics.get_registry().counter("publish.series_shed").snapshot()
    assert shed == 2 * SERIES_SHED_EVERY - 2
    # recovery restores per-batch series
    monitor.phase = monitor.HEALTHY
    before = calls["series"]
    for i in range(3):
        session.update(i, 1, 1.0, 1.0, 1.0, real, real)
    assert calls["series"] == before + 3


# -- satellite fixes ---------------------------------------------------------

def test_checkpointer_sweeps_stale_tmp_files(tmp_path):
    from twtml_tpu.checkpoint import Checkpointer

    d = str(tmp_path / "ck")
    ck = Checkpointer(d)
    ck.save(1, np.arange(4.0), {"count": 4})
    # a hard kill mid-write leaves a mkstemp temp file _prune never touches
    stale = os.path.join(d, "tmpdeadbeef.tmp")
    with open(stale, "wb") as fh:
        fh.write(b"partial checkpoint bytes")
    ck2 = Checkpointer(d)
    assert not [n for n in os.listdir(d) if n.endswith(".tmp")]
    weights, meta = ck2.restore()  # real checkpoints survive the sweep
    assert meta["step"] == 1
    np.testing.assert_array_equal(weights, np.arange(4.0))


def test_source_stop_names_wedged_producer_thread(caplog):
    from twtml_tpu.streaming.sources import Source

    release = threading.Event()

    class Wedged(Source):
        name = "wedged"

        def produce(self):
            release.wait(5.0)  # ignores the stop event: a stuck blocking call
            return iter(())

    src = Wedged()
    src.JOIN_TIMEOUT_S = 0.1
    src.start(lambda s: None)
    time.sleep(0.05)
    with caplog.at_level(logging.WARNING, logger="twtml.streaming.sources"):
        src.stop()
    release.set()
    warnings = [r for r in caplog.records if "did not stop" in r.message]
    assert len(warnings) == 1
    assert "twtml-source-wedged" in warnings[0].getMessage()


def test_web_timeout_flag_threads_through():
    from twtml_tpu.telemetry.session_stats import SessionStats

    assert ConfArguments().webTimeout == 2.0  # default preserved
    conf = ConfArguments().parse(["--webTimeout", "0.25"])
    assert conf.webTimeout == 0.25
    assert SessionStats(conf).web.timeout == 0.25


# -- abort refunds (ISSUE 3 satellite): every dispatched batch is either
# delivered to the handler or refunded — partial singles and coalesced/
# grouped dispatches alike, so cap accounting stays honest across aborts --


def test_superbatcher_partial_abort_refunds_dispatch():
    """The partial path's batch trains before its synchronous fetch; when
    that fetch aborts, the dispatch slot is refunded (trained-but-
    undelivered must not consume max_dispatch budget)."""
    model = FlakyFetchModel(slow={0: {n: 0.5 for n in range(1, 10)}})
    sb = SuperBatcher(
        model, 4, lambda out, b, t, at_boundary: None,
        fetch_deadline_s=0.05, fetch_retries=1, max_dispatch=8,
    )
    sb.on_batch(np.asarray(0), 0.0)
    with pytest.raises(FetchAbort):
        sb._close_group()
    assert sb._dispatched == 0  # the slot came back
    assert _metrics.get_registry().counter("fetch.refunds").snapshot() == 1
    sb.flush()  # clean no-op after the abort


def _flight_recorder(tmp_path):
    from twtml_tpu.telemetry import blackbox

    blackbox.uninstall()
    return blackbox.install(config={"app": "guards"}, out_dir=str(tmp_path))


def _assert_bundle(tmp_path, reason_fragment, event_kind):
    from tools import postmortem_report
    from twtml_tpu.telemetry import blackbox

    path = blackbox.last_dump_path()
    assert path and os.path.exists(path), "no post-mortem bundle dumped"
    assert postmortem_report.main([path]) == 0  # well-formed
    doc = postmortem_report.load_bundle(path)
    assert reason_fragment in doc["reason"], doc["reason"]
    assert any(e["kind"] == event_kind for e in doc["events"]), doc["events"]
    blackbox.uninstall()


def test_fetch_watchdog_abort_dumps_postmortem_bundle(tmp_path):
    """Abort path 1 (fetch-watchdog exhaustion): the abort hook funnels
    through ssc.request_abort, which dumps the flight recorder's bundle."""
    from twtml_tpu.streaming.context import StreamingContext

    _flight_recorder(tmp_path)
    ssc = StreamingContext()
    model = FlakyFetchModel(slow={0: {n: 0.5 for n in range(1, 10)}})
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: None,
        depth=1, fetch_deadline_s=0.05, fetch_retries=1,
        abort=ssc.request_abort,
    )
    pipe.on_batch(0, 0.0)
    with pytest.raises(FetchAbort):
        pipe.on_batch(1, 0.0)
    pipe.flush()
    assert ssc.failed
    _assert_bundle(tmp_path, "runtime guard", "fetch_abort")


def test_sentinel_budget_abort_dumps_postmortem_bundle(tmp_path):
    """Abort path 2 (sentinel rollback budget): the sentinel's abort rides
    the same funnel; the bundle records the rollbacks and the budget
    abort."""
    from types import SimpleNamespace

    from twtml_tpu.apps.common import DivergenceSentinel
    from twtml_tpu.streaming.context import StreamingContext

    _flight_recorder(tmp_path)
    ssc = StreamingContext()

    class _Ckpt:
        def rollback_to_verified(self):
            return {"step": 3}

    conf = ConfArguments().parse(
        ["--sentinelRollbacks", "1", "--sentinelWindow", "8"]
    )
    s = DivergenceSentinel(conf, None, _Ckpt(), ssc)
    out = SimpleNamespace(
        mse=float("nan"), real_stdev=1.0, pred_stdev=1.0, count=16
    )
    assert not s.admit(out, None)
    assert ssc.failed
    _assert_bundle(tmp_path, "runtime guard", "sentinel_abort")


def test_lockstep_peer_watchdog_abort_dumps_postmortem_bundle(
    tmp_path, monkeypatch
):
    """Abort path 3 (lockstep peer death): a cadence allgather that makes
    no progress fires the peer watchdog, which aborts through the funnel
    and leaves a bundle naming the watchdog."""
    from jax.experimental import multihost_utils

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.context import StreamingContext
    from twtml_tpu.streaming.sources import SyntheticSource

    _flight_recorder(tmp_path)
    monkeypatch.setenv("TWTML_LOCKSTEP_TIMEOUT_S", "0.2")
    release = threading.Event()
    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda arr: release.wait(10.0),  # a peer that never answers
    )
    ssc = StreamingContext(batch_interval=0)
    ssc.source_stream(
        SyntheticSource(total=16, seed=7, base_ms=1785320000000),
        Featurizer(now_ms=1785320000000),
        row_bucket=16, token_bucket=64, device_hash=True,
    ).foreach_batch(lambda b, t: None)
    ssc.start(lockstep=True)
    assert ssc.await_termination(timeout=30)
    release.set()
    ssc.stop()
    assert ssc.failed
    _assert_bundle(tmp_path, "peer watchdog", "abort")


def test_cadence_disagreement_abort_dumps_postmortem_bundle(
    tmp_path, monkeypatch
):
    """Abort path 4 (rollback-count disagreement): fabricated gathered
    flags whose rollback column differs across hosts abort the group and
    leave a bundle naming the divergence."""
    import numpy as _np

    from jax.experimental import multihost_utils

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.context import StreamingContext
    from twtml_tpu.streaming.sources import SyntheticSource

    _flight_recorder(tmp_path)

    def disagreeing(arr):
        other = _np.array(arr, copy=True)
        other[3] += 1  # the peer claims one more sentinel rollback
        return _np.stack([_np.asarray(arr), other])

    monkeypatch.setattr(multihost_utils, "process_allgather", disagreeing)
    ssc = StreamingContext(batch_interval=0)
    ssc.source_stream(
        SyntheticSource(total=16, seed=7, base_ms=1785320000000),
        Featurizer(now_ms=1785320000000),
        row_bucket=16, token_bucket=64, device_hash=True,
    ).foreach_batch(lambda b, t: None)
    ssc.start(lockstep=True)
    assert ssc.await_termination(timeout=30)
    ssc.stop()
    assert ssc.failed
    assert _metrics.get_registry().counter(
        "lockstep.rollback_disagreements"
    ).snapshot() == 1
    _assert_bundle(tmp_path, "disagree", "abort")


def test_superbatcher_flush_refunds_undelivered_groups():
    """Grouped dispatches (the coalesced-wire path included) that are
    in flight when the transport wedges: flush drops them AND refunds every
    batch they carried."""
    import time as _time

    import jax

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.sources import SyntheticSource

    class WedgedGroupFetch:
        """Real learner, wedged pooled fetches — groups dispatch fine and
        every fetch stalls past the watchdog deadline."""

        accepts_packed = True

        def __init__(self):
            self.inner = StreamingLinearRegressionWithSGD(num_iterations=2)

        def step(self, b):
            return self.inner.step(b)

        def step_many(self, stacked):
            return self.inner.step_many(stacked)

        def fetch_output(self, out):
            _time.sleep(0.5)
            return jax.device_get(out)

        fetch_output_many = fetch_output

    statuses = list(
        SyntheticSource(total=64, seed=3, base_ms=1785320000000).produce()
    )
    feat = Featurizer(now_ms=1785320000000)
    batches = [
        feat.featurize_batch_ragged(
            statuses[i * 16 : (i + 1) * 16], row_bucket=16, unit_bucket=512,
            pre_filtered=True,
        )
        for i in range(4)
    ]
    for wire_pack in ("group", "stacked"):
        _metrics.reset_for_tests()
        aborted = []
        sb = SuperBatcher(
            WedgedGroupFetch(), 2, lambda out, b, t, at_boundary: None,
            fetch_depth=4, fetch_deadline_s=0.05, fetch_retries=1,
            abort=lambda: aborted.append(True), wire_pack=wire_pack,
        )
        for i, b in enumerate(batches):
            sb.on_batch(b, float(i))
        assert sb._dispatched == 4  # two groups of two, both in flight
        sb.flush()  # abort inside the drain is swallowed; refunds land
        assert aborted == [True]
        assert sb._dispatched == 0, wire_pack
        assert (
            _metrics.get_registry().counter("fetch.refunds").snapshot() == 4
        ), wire_pack
