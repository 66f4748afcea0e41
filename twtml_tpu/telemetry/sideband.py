"""Per-host telemetry sideband for multi-host lockstep runs.

The fleet was observationally blind: the lockstep scheduler
(streaming/context._lockstep_loop) gates every tick on the slowest host,
but nothing recorded WHICH host gated or WHAT stage of its pipeline was
slow. This module is the fix, under the measurement law that made PR 1
honest (lawcheck TW002/TW003): **zero added host fetches
and zero added collectives** — the sideband is a compact fixed-width float
vector of host-side bookkeeping that rides the EXISTING per-tick cadence
allgather (the flags array widens; no new collective is ever issued), and
every value in it is read from state the pipeline already maintains
(the stage clock below, the metrics registry, the fetch-health monitor).

Three pieces:

- **stage clock** (``record_stage``): cumulative per-stage wall seconds,
  fed by the instrumentation sites that already take timings (the pooled
  fetch wraps its one ``device_get``; dispatch/featurize/source-read wrap
  work the batch loop already does). Per-BATCH cost is a handful of
  ``perf_counter`` reads and one dict add — no device traffic, no threads.
  The per-tweet object-parse path stays trace-gated (two clock reads per
  tweet would tax the parser), so ``parse``
  attribution on object ingest needs ``--trace``; the block parser times
  per MB-scale chunk and always contributes.
- **SidebandCollector**: turns the clock deltas + registry gauges +
  health summary into the fixed ``FIELDS`` vector each tick.
- **LockstepTelemetry**: the context-side driver — builds this host's
  vector, ingests the gathered ``[hosts, WIDTH]`` matrix, feeds the
  straggler attributor (telemetry/straggler.py), and publishes the
  ``hosts[]`` view the dashboard and the flight recorder read
  (``last_hosts``).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..utils import get_logger

log = get_logger("telemetry.sideband")

# The fixed sideband layout. Every host MUST ship exactly this vector —
# the cadence allgather concatenates it after the 4 lockstep flags, so the
# wire shape is part of the collective program contract.
FIELDS = (
    "tick_prep_ms",     # wall ms this host spent between cadence allgathers
                        # (its own work: the direct gating measure)
    "source_read_ms",   # per-stage wall ms accumulated since the last tick
    "parse_ms",
    "featurize_ms",
    "dispatch_ms",      # argument uploads ride the dispatch (r2)
    "fetch_ms",
    "publish_ms",
    "queue_rows",       # intake queue depth (ingest.queue_rows gauge)
    "fetch_rtt_ms",     # fetch-health rolling median
    "rollbacks",        # divergence-sentinel rollbacks (model.rollbacks)
    "rows_shed",        # ingest.rows_shed counter
    "health_degraded",  # 0 healthy / 1 degraded
    "wire_pack_ms",     # per-tick delta like the other stage columns (r16)
    "event_lag_ms",     # freshness plane: last event→delivery lag on this
                        # host — the fleet's low watermark rides the
                        # EXISTING cadence allgather, never a new one (r16)
)
WIDTH = len(FIELDS)

# FIELDS entries that are per-tick deltas of the stage clock
STAGE_FIELDS = {
    "source_read_ms": "source_read",
    "parse_ms": "parse",
    "featurize_ms": "featurize",
    "dispatch_ms": "dispatch",
    "fetch_ms": "fetch",
    "publish_ms": "stats_publish",
    "wire_pack_ms": "wire_pack",
}


# -- stage clock -------------------------------------------------------------
# Cumulative wall seconds per pipeline stage, always on: the contributing
# sites run at batch cadence (or chunk cadence for the block parser), so the
# cost is one lock + one float add per stage per batch.

_STAGE_LOCK = threading.Lock()
_STAGE_SECONDS: "dict[str, float]" = {}


def record_stage(stage: str, dur_s: float) -> None:
    """Accumulate one stage timing (seconds). Pool threads call this for
    ``fetch`` concurrently, so cumulative fetch seconds may exceed wall
    time — fine for attribution, which compares a host against itself."""
    with _STAGE_LOCK:
        _STAGE_SECONDS[stage] = _STAGE_SECONDS.get(stage, 0.0) + dur_s


def stage_seconds() -> "dict[str, float]":
    with _STAGE_LOCK:
        return dict(_STAGE_SECONDS)


# -- per-tick collection -----------------------------------------------------


class SidebandCollector:
    """Builds this host's sideband vector each lockstep tick. Everything is
    host-side state: the stage clock, the metrics registry, and the health
    monitor — no ``device_get``, no collective (asserted by
    tests/test_observability.py the way the --trace tests assert it)."""

    def __init__(self):
        self._prev_stages = stage_seconds()
        self._prev_tick = time.perf_counter()

    def collect(self, rollbacks: int = 0) -> np.ndarray:
        from . import metrics as _metrics

        now = time.perf_counter()
        cur = stage_seconds()
        reg = _metrics.get_registry()
        health = _metrics.get_health_monitor()
        vec = np.zeros((WIDTH,), dtype=np.float64)
        for i, name in enumerate(FIELDS):
            stage = STAGE_FIELDS.get(name)
            if stage is not None:
                vec[i] = (
                    cur.get(stage, 0.0) - self._prev_stages.get(stage, 0.0)
                ) * 1e3
        vec[FIELDS.index("tick_prep_ms")] = (now - self._prev_tick) * 1e3
        vec[FIELDS.index("queue_rows")] = reg.gauge(
            "ingest.queue_rows"
        ).snapshot()
        vec[FIELDS.index("fetch_rtt_ms")] = health.median_ms()
        vec[FIELDS.index("rollbacks")] = float(rollbacks)
        vec[FIELDS.index("rows_shed")] = reg.counter(
            "ingest.rows_shed"
        ).snapshot()
        vec[FIELDS.index("health_degraded")] = (
            1.0 if health.phase == health.DEGRADED else 0.0
        )
        # lazy import: freshness imports this module for the stage clock
        from . import freshness as _freshness

        vec[FIELDS.index("event_lag_ms")] = _freshness.last_event_lag_ms()
        self._prev_stages = cur
        # non-finite values must never ride the collective (they would
        # poison every peer's view)
        np.nan_to_num(vec, copy=False, posinf=0.0, neginf=0.0)
        return vec

    def tick_done(self) -> None:
        """Mark the cadence allgather's return: the next tick_prep_ms
        window starts here, so time spent WAITING in the collective (the
        fast hosts' idle time) never counts as the host's own work."""
        self._prev_tick = time.perf_counter()


# -- the published hosts[] view ---------------------------------------------
# Last gathered per-host matrix + straggler verdict, published for the
# dashboard (SessionStats → Hosts message), the flight recorder, and tests.

_VIEW_LOCK = threading.Lock()
_LAST_VIEW: "dict | None" = None


def publish_hosts(view: dict) -> None:
    global _LAST_VIEW
    with _VIEW_LOCK:
        _LAST_VIEW = view


def last_hosts() -> "dict | None":
    with _VIEW_LOCK:
        return None if _LAST_VIEW is None else dict(_LAST_VIEW)


def reset_for_tests() -> None:
    global _LAST_VIEW
    with _VIEW_LOCK:
        _LAST_VIEW = None
    with _STAGE_LOCK:
        _STAGE_SECONDS.clear()


class LockstepTelemetry:
    """The lockstep scheduler's sideband driver: one instance per
    ``_lockstep_loop``. ``vector()`` before the allgather, ``tick_done()``
    right after it returns, ``ingest(matrix)`` on the gathered rows."""

    def __init__(self, process_index: int = 0, num_processes: int = 1):
        from . import metrics as _metrics
        from .straggler import StragglerAttributor

        self.process_index = process_index
        self.num_processes = num_processes
        self._collector = SidebandCollector()
        self._attributor = StragglerAttributor()
        self._ticks = _metrics.get_registry().counter("lockstep.ticks")

    def vector(self, rollbacks: int = 0) -> np.ndarray:
        return self._collector.collect(rollbacks=rollbacks)

    def tick_done(self) -> None:
        self._collector.tick_done()

    def ingest(self, matrix: np.ndarray) -> None:
        """Consume the gathered ``[hosts, WIDTH]`` sideband block: classify
        the straggler, publish the hosts[] view, and feed the flight
        recorder's ring. Pure host-side bookkeeping."""
        self._ticks.inc()
        verdict = self._attributor.observe(matrix)
        hosts = []
        for h in range(matrix.shape[0]):
            row = {"host": h}
            for i, name in enumerate(FIELDS):
                row[name] = round(float(matrix[h, i]), 3)
            hosts.append(row)
        view = {
            "hosts": hosts,
            "straggler": verdict["host"],
            "stage": verdict["stage"],
            "skew_ms": verdict["skew_ms"],
        }
        publish_hosts(view)
        from . import blackbox as _blackbox

        _blackbox.record(
            "sideband",
            straggler=verdict["host"], stage=verdict["stage"],
            skew_ms=verdict["skew_ms"],
            prep_ms=[round(float(v), 2) for v in matrix[:, 0]],
        )
