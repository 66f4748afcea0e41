"""Metrics registry + fetch-health classifier (telemetry/metrics.py):
counter/gauge/histogram semantics, snapshot isolation, and health-phase
transitions on synthetic latency series — the observability layer's
contracts, independent of any pipeline."""

import threading

from twtml_tpu.telemetry.metrics import (
    MetricsRegistry,
    FetchHealthMonitor,
)


def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("pipeline.batches")
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    # get-or-create: same underlying metric
    assert reg.counter("pipeline.batches") is c
    g = reg.gauge("fetch.queue_depth")
    g.set(3)
    g.add(2)
    g.set(7)  # set wins over accumulated state
    assert g.snapshot() == 7


def test_histogram_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("fetch.latency_s")
    for v in (0.001, 0.002, 0.004, 0.1, 2.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert abs(snap["sum"] - 2.107) < 1e-9
    assert snap["min"] == 0.001 and snap["max"] == 2.0
    assert abs(snap["mean"] - 2.107 / 5) < 1e-9
    # bucket counts only for touched buckets
    assert sum(c for _, c in snap["buckets"]) == 5
    # percentile estimator: median lands at the 0.004 bucket's bound
    assert 0.002 <= h.percentile(0.5) <= 0.008
    assert h.percentile(1.0) >= 2.0


def test_histogram_snapshot_derived_percentiles_match_percentile():
    """r8: /api/metrics ships derived p50/p95/p99 per histogram — the
    snapshot values must be exactly what Histogram.percentile computes
    (one shared bucket walk), including the empty and overflow cases."""
    reg = MetricsRegistry()
    h = reg.histogram("fetch.latency_s")
    assert h.snapshot()["p50"] == 0.0  # empty: all quantiles zero
    import random

    rnd = random.Random(7)
    for _ in range(500):
        h.observe(rnd.uniform(0.001, 4.0))
    snap = h.snapshot()
    for key, p in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99)):
        assert snap[key] == h.percentile(p), key
    assert snap["p50"] <= snap["p95"] <= snap["p99"]
    # overflow tail: quantiles beyond the last bound report the true max
    h2 = reg.histogram("stall_s")
    for v in (1000.0, 2000.0, 3000.0):
        h2.observe(v)
    assert h2.snapshot()["p99"] == 3000.0


def test_snapshot_isolation():
    reg = MetricsRegistry()
    reg.counter("a").inc(2)
    reg.gauge("b").set(1)
    reg.histogram("h").observe(0.5)
    snap = reg.snapshot()
    reg.counter("a").inc(10)
    reg.gauge("b").set(9)
    reg.histogram("h").observe(0.5)
    # the snapshot taken earlier is immune to later mutation
    assert snap["counters"]["a"] == 2
    assert snap["gauges"]["b"] == 1
    assert snap["histograms"]["h"]["count"] == 1


def test_counter_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("x")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.snapshot() == 8000


# ---------------------------------------------------------------------------
# health-phase classifier on synthetic latency series


def test_health_steady_rtt_stays_healthy():
    reg = MetricsRegistry()
    mon = FetchHealthMonitor(registry=reg)
    for i in range(50):
        mon.observe(0.07 + 0.005 * (i % 3), now=float(i))
    assert mon.phase == FetchHealthMonitor.HEALTHY
    assert mon.transitions == []
    assert mon.observations["degraded"] == 0


def test_health_degrades_and_recovers():
    reg = MetricsRegistry()
    mon = FetchHealthMonitor(registry=reg)
    t = iter(range(1000))
    for _ in range(20):  # healthy baseline ~70 ms
        mon.observe(0.07, now=float(next(t)))
    assert mon.phase == FetchHealthMonitor.HEALTHY
    for _ in range(20):  # stall burst: 600 ms medians
        mon.observe(0.6, now=float(next(t)))
    assert mon.phase == FetchHealthMonitor.DEGRADED
    for _ in range(40):  # back to RTT scale
        mon.observe(0.07, now=float(next(t)))
    assert mon.phase == FetchHealthMonitor.HEALTHY
    phases = [p for _, p in mon.transitions]
    assert phases == ["degraded", "healthy"]
    # transition count landed in the registry too
    assert reg.counter("fetch_health.phase_transitions").snapshot() == 2
    assert mon.observations["degraded"] > 0
    summary = mon.summary()
    assert summary["phase"] == "healthy" and summary["transitions"] == 2
    assert summary["best_ms"] == 70.0


def test_health_floor_keeps_cpu_jitter_healthy():
    """µs-scale latencies (CPU backend, fake models) sit under the floor:
    relative jitter there must never classify as degraded."""
    mon = FetchHealthMonitor(registry=MetricsRegistry())
    for i in range(100):
        mon.observe(1e-6 if i % 2 else 2e-5, now=float(i))  # 20x swings
    assert mon.phase == FetchHealthMonitor.HEALTHY
    assert mon.transitions == []


def test_health_hysteresis_no_flap_on_single_outlier():
    mon = FetchHealthMonitor(registry=MetricsRegistry())
    for i in range(30):
        mon.observe(0.07, now=float(i))
    mon.observe(5.0, now=31.0)  # one stalled fetch
    # a single outlier does not move the rolling median past the threshold
    assert mon.phase == FetchHealthMonitor.HEALTHY
    assert mon.transitions == []
