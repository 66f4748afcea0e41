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
# delivered to the handler or refunded, and its arena lease is released
# (retired on delivery, discarded — never pooled — on an abort), so cap
# accounting and the wire arena stay honest across aborts --


class _LeasedWire:
    """A batch that carries an arena lease, as a packed wire does."""

    def __init__(self, i, nbytes=4096):
        from twtml_tpu.features import arena

        self.i = i
        self._lease = arena.get_arena().lease(nbytes)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.i, dtype)


def _arena():
    from twtml_tpu.features import arena

    return arena.get_arena()


def test_fetch_pipeline_abort_mid_flight_refunds_dispatch():
    """An abort while batches are in flight: the batch whose fetch wedged
    trained but was never delivered — its ``max_dispatch`` slot comes back
    and its lease is discarded; batches delivered before it keep their
    slots and retire their leases to the pool."""
    _arena().reset_for_tests()
    # batch 2 stalls on every attempt; 0 and 1 deliver normally
    model = FlakyFetchModel(slow={2: {n: 0.5 for n in range(1, 10)}})
    events, aborted = [], []
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: events.append(int(out["i"])),
        depth=8, deterministic=True, max_dispatch=8,
        fetch_deadline_s=0.05, fetch_retries=1,
        abort=lambda: aborted.append(True),
    )
    for i in range(4):
        pipe.on_batch(_LeasedWire(i), float(i))
    assert pipe._dispatched == 4 and _arena().stats()["in_use"] == 4
    with pytest.raises(FetchAbort):
        pipe.drain()  # 0, 1 deliver; 2 aborts mid-flight; 3 stays pending
    assert events == [0, 1] and aborted == [True]
    assert pipe._dispatched == 3  # batch 2's slot came back
    reg = _metrics.get_registry()
    assert reg.counter("fetch.refunds").snapshot() == 1
    st = _arena().stats()
    assert st["in_use"] == 1  # only the still-pending batch 3
    assert st["free_buffers"] == 2  # 0 and 1 retired; 2 was discarded
    pipe.flush()  # batch 3's fetch had completed: it still delivers
    assert events == [0, 1, 3]
    assert pipe._dispatched == 3  # exactly the delivered batches
    assert reg.counter("fetch.refunds").snapshot() == 1
    assert _arena().stats() == {
        "in_use": 0, "free_buffers": 3, "free_bytes": 3 * 4096,
    }


def test_fetch_pipeline_flush_refunds_undelivered_handles():
    """The transport wedges with a full window in flight: flush swallows
    the abort, drops every undelivered handle AND refunds the slot and
    discards the lease of each."""
    _arena().reset_for_tests()
    model = FlakyFetchModel(
        slow={i: {n: 0.5 for n in range(1, 10)} for i in range(4)}
    )
    aborted = []
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: None,
        depth=8, deterministic=True, max_dispatch=8,
        fetch_deadline_s=0.05, fetch_retries=1,
        abort=lambda: aborted.append(True),
    )
    for i in range(4):
        pipe.on_batch(_LeasedWire(i), float(i))
    assert pipe._dispatched == 4  # all four in flight
    pipe.flush()  # the abort inside the drain is swallowed; refunds land
    assert aborted == [True]
    assert pipe._dispatched == 0
    assert _metrics.get_registry().counter("fetch.refunds").snapshot() == 4
    st = _arena().stats()
    assert st["in_use"] == 0 and st["free_buffers"] == 0  # no reuse
    assert pipe._pool._shutdown


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
