"""Crash flight recorder: a bounded in-memory ring of recent telemetry,
dumped as ONE post-mortem JSON bundle when a run aborts.

All four abort paths the runtime guards added (fetch-watchdog exhaustion,
sentinel rollback-budget, lockstep peer death, cadence disagreement) used
to die leaving nothing to debug a chaos-soak failure with but stdout. They
all funnel through ``StreamingContext.request_abort`` now; that funnel (and
a SIGTERM) triggers ``abort_dump``, which writes the bundle next to the
checkpoint directory: config snapshot, last-verified-checkpoint note, the
event ring (trace spans when ``--trace`` is live, health transitions, chaos
firings, guard events, per-tick sideband rows), a metrics-registry
snapshot, the fetch-health summary, and the last per-host sideband view.
``tools/postmortem_report.py`` renders it (exit 2 on malformed bundles,
like trace_report).

Measurement integrity: recording is host-side ring appends (one lock, one
deque append); the dump happens once, on the way DOWN — never on the hot
path. No ``device_get``, no collective, ever.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
from collections import deque

from ..utils import get_logger

log = get_logger("telemetry.blackbox")

BUNDLE_KIND = "twtml-postmortem"
BUNDLE_VERSION = 1
DEFAULT_CAPACITY = 512

# keys a bundle MUST carry to be parseable (postmortem_report checks)
REQUIRED_KEYS = (
    "kind", "version", "reason", "time_unix", "config", "events", "metrics",
)


class FlightRecorder:
    def __init__(self, config: "dict | None" = None, out_dir: str = "",
                 process_index: int = 0, capacity: int = DEFAULT_CAPACITY):
        self.config = dict(config or {})
        self.out_dir = out_dir or os.getcwd()
        self.process_index = int(process_index)
        self._ring: deque = deque(maxlen=capacity)
        self._dropped = 0
        self._notes: dict = {}
        self._lock = threading.Lock()
        self.last_dump_path: "str | None" = None
        self._dumped = False

    # -- recording (hot-path-safe: one lock + one append) --------------------
    def record(self, kind: str, **payload) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(
                {"t": round(time.time(), 3), "kind": kind, **payload}
            )

    def note(self, key: str, value) -> None:
        """Sticky context that should survive however old the ring gets
        (e.g. the last verified checkpoint id)."""
        with self._lock:
            self._notes[key] = value

    def on_trace_event(self, ev: dict) -> None:
        """Trace-writer sink (telemetry/trace.py): complete spans and
        instants join the ring in compact form; metadata/counter tracks are
        skipped — the ring wants the last N meaningful things that
        happened, not a second trace file."""
        ph = ev.get("ph")
        if ph == "X":
            self.record(
                "span", name=ev.get("name"),
                dur_ms=round(float(ev.get("dur", 0.0)) / 1e3, 3),
                **(ev.get("args") or {}),
            )
        elif ph == "i":
            self.record("instant", name=ev.get("name"),
                        **(ev.get("args") or {}))

    # -- the bundle ----------------------------------------------------------
    def bundle(self, reason: str) -> dict:
        from . import historian as _historian
        from . import metrics as _metrics
        from . import sideband as _sideband

        with self._lock:
            events = list(self._ring)
            notes = dict(self._notes)
            dropped = self._dropped
        return {
            "kind": BUNDLE_KIND,
            "version": BUNDLE_VERSION,
            "reason": reason,
            "time_unix": round(time.time(), 3),
            "process_index": self.process_index,
            "config": self.config,
            "notes": notes,
            "events": events,
            "events_dropped": dropped,
            "metrics": _metrics.get_registry().snapshot(),
            "health": _metrics.get_health_monitor().summary(),
            "hosts": _sideband.last_hosts(),
            # the minutes BEFORE death: the historian's in-memory tail
            # (samples + phase transitions), None when --history off
            "history": _historian.bundle_tail(),
        }

    def dump(self, reason: str, out_dir: "str | None" = None,
             force: bool = False) -> "str | None":
        """Write the post-mortem bundle; returns its path. ONE bundle per
        process per failure (the abort funnel and the SIGTERM handler can
        both fire on the same shutdown) — ``force`` re-dumps for artifact
        collection (tools/chaos_soak.py)."""
        with self._lock:
            if self._dumped and not force:
                return self.last_dump_path
            self._dumped = True
        target_dir = out_dir or self.out_dir
        path = os.path.join(
            target_dir,
            f"postmortem.p{self.process_index}.{os.getpid()}.json",
        )
        try:
            os.makedirs(target_dir, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(self.bundle(reason), fh, default=_json_default)
            os.replace(tmp, path)  # a torn bundle must never shadow a good one
        except Exception:
            log.exception("post-mortem bundle write failed (%s)", path)
            return None
        self.last_dump_path = path
        log.critical("post-mortem bundle written: %s (reason: %s)", path,
                     reason)
        return path


def _json_default(obj):
    """Bundles carry whatever rode the ring — numpy scalars/arrays from
    metrics payloads must serialize, not kill the dump."""
    import numpy as np

    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    return repr(obj)


# -- process-wide recorder ---------------------------------------------------

_RECORDER: "FlightRecorder | None" = None
_PREV_SIGTERM = None
_SIGTERM_INSTALLED = False


def install(config: "dict | None" = None, out_dir: str = "",
            process_index: int = 0,
            capacity: int = DEFAULT_CAPACITY) -> FlightRecorder:
    """Activate the flight recorder process-wide (re-install resets the
    ring — each app run records its own story) and hook the trace writer so
    ``--trace`` spans join the ring."""
    global _RECORDER
    _RECORDER = FlightRecorder(
        config=config, out_dir=out_dir, process_index=process_index,
        capacity=capacity,
    )
    from . import trace as _trace

    _trace.set_event_sink(_RECORDER.on_trace_event)
    return _RECORDER


def uninstall() -> None:
    global _RECORDER
    _RECORDER = None
    from . import trace as _trace

    _trace.set_event_sink(None)


def get() -> "FlightRecorder | None":
    return _RECORDER


def record(kind: str, **payload) -> None:
    """Module-level ring append — one None check when no recorder is
    installed (the default: tests and library embedding)."""
    if _RECORDER is not None:
        _RECORDER.record(kind, **payload)


def note(key: str, value) -> None:
    if _RECORDER is not None:
        _RECORDER.note(key, value)


def abort_dump(reason: str) -> "str | None":
    """The abort funnel (StreamingContext.request_abort): record the abort
    and dump the single post-mortem bundle."""
    if _RECORDER is None:
        return None
    _RECORDER.record("abort", reason=reason)
    return _RECORDER.dump(reason)


def last_dump_path() -> "str | None":
    return _RECORDER.last_dump_path if _RECORDER is not None else None


def dump(reason: str, out_dir: "str | None" = None,
         force: bool = False) -> "str | None":
    if _RECORDER is None:
        return None
    return _RECORDER.dump(reason, out_dir=out_dir, force=force)


def _on_sigterm(signum, frame, _prev=None) -> None:
    """Dump on SIGTERM, then chain to whatever handler was there before
    (default: terminate). A kill -TERM mid-soak leaves a bundle behind."""
    if _RECORDER is not None:
        _RECORDER.record("sigterm")
        _RECORDER.dump("SIGTERM")
    prev = _prev if _prev is not None else _PREV_SIGTERM
    if callable(prev):
        prev(signum, frame)
    elif prev == signal.SIG_DFL:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)


def install_signal_handler() -> bool:
    """Best-effort SIGTERM hook (main thread only — signal.signal raises
    elsewhere). Installed once per process; re-installs are no-ops so
    repeated app runs (tools/chaos_soak.py) never chain handlers into a
    loop."""
    global _PREV_SIGTERM, _SIGTERM_INSTALLED
    if _SIGTERM_INSTALLED:
        return True
    try:
        _PREV_SIGTERM = signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        return False  # not the main thread
    _SIGTERM_INSTALLED = True
    return True
