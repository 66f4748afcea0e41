"""Telemetry historian — durable long-horizon time series + phase-segmented
cross-run perf regression sentinel (ISSUE 20).

Every observability surface before this one (trace spans, the metrics
registry, the sideband, modelwatch, freshness) is instantaneous or a bounded
in-memory ring — nothing survives the process, so the long-horizon questions
(the host RSS curve, fetch-latency health phases, the run-over-run perf
trajectory) could not be answered from a run's leftovers.
The historian closes that gap with the cheapest possible sampling:

- **Sampled at the EXISTING stats-publish cadence.** ``sample()`` is called
  from exactly one place — ``SessionStats._publish_registry``, item 0 of
  the publisher's period, once every ``METRICS_EVERY`` updates (lawcheck
  TW010 pins the seam file the way TW009 pins the journal seam;
  ``publish_metrics`` runs the same item) — and snapshots the
  ALREADY-COMPUTED registry/health/stage views. Zero added host fetches,
  zero added collectives (counted in tests/test_history.py like PR 5/8/16).
- **The journal's durability discipline.** CRC32-framed JSON records in
  fixed-size rotated segments (``seg-<seq>.twh``); a kill -9 mid-write fails
  the CRC and the torn tail truncates LOUDLY (``history.torn_tails``);
  ``--historyMaxMb`` is a hard ceiling enforced by dropping the OLDEST
  segments (counted). A restart appends after the recovered tail, so one
  directory accumulates a multi-run timeline.
- **Phase segmentation.** The PR 1 fetch-health classifier's transitions
  persist as labeled records, so every derived statistic is phase-matched —
  a degraded-phase stall never pollutes a healthy-phase baseline.
- **Long-horizon derivations.** Hours-scale least-squares RSS slope (the
  soak gate's estimator, ``utils.rss.slope_mb_per_min``, over any run's
  leftovers), per-phase throughput / fetch-RTT trends — all computable from
  the raw segments alone (``read_series`` + the ``phase_intervals`` /
  ``rss_slope`` helpers; tools/history_report.py is the CLI).
- **Cross-run regression sentinel** (``--perfGuard warn|off``): per-stage
  stage-clock medians over HEALTHY-phase samples are stamped into
  ``<dir>/baseline.json`` at clean shutdown; the next run compares its
  healthy-phase per-tick stage costs against the baseline and a SUSTAINED
  regression (> ``--perfGuardRatio`` for ``GUARD_WINDOW`` consecutive
  healthy samples) raises ONE warn-only blackbox event per episode +
  ``perf.regressions`` counters. Never aborts — the sentinel is a narrator,
  not a gate.

``--history off`` is bit-exact HEAD: no module state, no file handles, the
sample hook no-ops (tests byte-compare weights).

Frame format (little-endian): ``b"TWTH" | u32 payload_len | u32
crc32(payload) | payload`` where payload is one UTF-8 JSON object with a
``"k"`` kind tag: ``"r"`` run header (run id + config fingerprint — joins
segments to BENCH_*.json rows), ``"s"`` sample, ``"p"`` phase transition.
"""

from __future__ import annotations

import json
import os
import re
import struct
import threading
import zlib
from collections import deque

from ..utils import get_logger
from ..utils.clock import now_ms
from . import metrics as _metrics
from . import sideband as _sideband

log = get_logger("telemetry.historian")

MAGIC = b"TWTH"
_FRAME = struct.Struct("<4sII")  # magic, payload_len, crc32(payload)
_SEG_RE = re.compile(r"^seg-(\d{20})\.twh$")
_PAYLOAD_MAX = 1 << 31  # sanity bound when scanning possibly-garbage tails

# segments rotate at this size unless --historyMaxMb forces smaller (the
# drop granularity under the disk ceiling: segments retire whole)
_SEGMENT_BYTES_DEFAULT = 4 * 1024 * 1024

BASELINE_NAME = "baseline.json"

# sustained-regression window: consecutive HEALTHY-phase samples a stage
# must sit above ratio x baseline before ONE episode fires (the freshness
# BREACH_WINDOW shape — burst noise never pages)
GUARD_WINDOW = 8
# stages cheaper than this per tick are below timing-noise scale;
# the sentinel ignores them (a 0.01 ms -> 0.03 ms "3x
# regression" is jitter, not a verdict)
GUARD_MIN_BASELINE_MS = 0.5
# healthy samples required before a baseline stamp is meaningful
BASELINE_MIN_SAMPLES = GUARD_WINDOW
# per-stage healthy-sample history kept for the shutdown baseline stamp
_STAGE_HISTORY = 4096
# in-memory tail ring: the blackbox bundle's "minutes before death" and the
# dashboard sparklines read this, never the disk
TAIL_RING = 256
# samples shipped per view/bundle
TAIL_SAMPLES = 64


def _median(vals) -> float:
    s = sorted(vals)
    if not s:
        return 0.0
    n = len(s)
    if n % 2:
        return float(s[n // 2])
    return (s[n // 2 - 1] + s[n // 2]) / 2.0


class Historian:
    """Bounded on-disk time-series historian for one process.

    Thread-safety: ``sample()`` runs on the stats-publish path only (the
    TW010 seam), but views/bundle reads arrive from web/blackbox threads —
    the lock guards the cheap bookkeeping; the file handle is touched only
    under it.
    """

    def __init__(
        self,
        directory: str,
        max_mb: int = 256,
        perf_guard: bool = True,
        guard_ratio: float = 1.5,
        run_id: int = 0,
        fingerprint: str = "",
    ):
        self.directory = directory
        self.max_bytes = max(1, int(max_mb)) * 1024 * 1024
        self.segment_bytes = max(
            64 * 1024, min(_SEGMENT_BYTES_DEFAULT, self.max_bytes // 4)
        )
        self.perf_guard = bool(perf_guard)
        self.guard_ratio = float(guard_ratio)
        self.run_id = int(run_id)
        self.fingerprint = str(fingerprint)
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.Lock()
        self._fh = None
        self._active_size = 0
        reg = _metrics.get_registry()
        self._samples_c = reg.counter("history.samples")
        self._torn = reg.counter("history.torn_tails")
        self._dropped_segments = reg.counter("history.segments_dropped")
        self._regressions = reg.counter("perf.regressions")
        self._disk_gauge = reg.gauge("history.disk_mb")
        self.next_seq = 0
        self._recover_tail()
        self._disk_bytes = self.disk_bytes()
        self._update_disk_gauge()
        # previous cumulative stage clock: per-sample deltas are the
        # per-publish-tick stage costs the sentinel compares (the sideband
        # collector keeps its own prev — the historian must not share it)
        self._prev_stages: "dict[str, float]" = dict(
            _sideband.stage_seconds()
        )
        self._seen_transitions = 0
        self._tail: deque = deque(maxlen=TAIL_RING)
        # healthy-phase per-stage history for the shutdown baseline stamp
        self._stage_hist: "dict[str, deque]" = {}
        self._healthy_samples = 0
        # sentinel state: per-stage consecutive-breach runs + episode latch
        self._breach_run: "dict[str, int]" = {}
        self._in_episode: "dict[str, bool]" = {}
        self.baseline: "dict | None" = self._load_baseline()
        # the run header joins these segments to BENCH_*.json rows and the
        # next run's baseline provenance
        self._write({
            "k": "r", "t_ms": now_ms(), "run_id": self.run_id,
            "fingerprint": self.fingerprint,
            "phase": _metrics.get_health_monitor().phase,
        })

    # ---------------------------------------------------------------- disk

    def _segments(self) -> "list[tuple[int, str]]":
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            m = _SEG_RE.match(name)
            if m:
                out.append(
                    (int(m.group(1)), os.path.join(self.directory, name))
                )
        out.sort()
        return out

    def _seg_path(self, first_seq: int) -> str:
        return os.path.join(self.directory, f"seg-{first_seq:020d}.twh")

    def _recover_tail(self) -> None:
        """Find the append position from the newest segment with a valid
        frame, truncating a torn tail LOUDLY (kill -9 mid-append)."""
        for first_seq, path in reversed(self._segments()):
            size = os.path.getsize(path)
            valid_end = 0
            count = 0
            for _rec, end in _scan_segment(path):
                valid_end = end
                count += 1
            if valid_end < size:
                self._torn.inc()
                log.error(
                    "historian: TORN TAIL in %s — %d byte(s) after the "
                    "last CRC-valid frame truncated (a kill mid-append); "
                    "every complete record before it survives",
                    path, size - valid_end,
                )
                with open(path, "r+b") as fh:
                    fh.truncate(valid_end)
            if count:
                self.next_seq = first_seq + count
                return
            if valid_end == 0 and first_seq != 0:
                os.unlink(path)  # fully-torn husk; position is below it
                continue
            self.next_seq = first_seq
            return

    def _rotate_if_needed(self) -> None:
        if self._fh is not None and self._active_size < self.segment_bytes:
            return
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        path = self._seg_path(self.next_seq)
        self._fh = open(path, "ab")
        self._active_size = self._fh.tell()

    def disk_bytes(self) -> int:
        return sum(os.path.getsize(p) for _, p in self._segments())

    def _update_disk_gauge(self) -> None:
        self._disk_gauge.set(round(self._disk_bytes / (1024 * 1024), 3))

    def _enforce_max_bytes(self) -> None:
        """--historyMaxMb is a HARD ceiling: drop the oldest whole segments
        (never the active one) until under it — loudly; dropped samples are
        history a later report can no longer see."""
        if self._disk_bytes <= self.max_bytes:
            return
        for _, path in self._segments()[:-1]:
            if self._disk_bytes <= self.max_bytes:
                break
            size = os.path.getsize(path)
            os.unlink(path)
            self._disk_bytes -= size
            self._dropped_segments.inc()
            log.warning(
                "historian: disk ceiling --historyMaxMb exceeded — dropped "
                "oldest segment %s (%d bytes); its samples are gone from "
                "the timeline (counted in history.segments_dropped)",
                os.path.basename(path), size,
            )

    def _write(self, rec: dict) -> None:
        """Append one CRC-framed JSON record (caller holds no lock — this
        runs from __init__ and from sample() which serializes itself)."""
        payload = json.dumps(
            rec, separators=(",", ":"), ensure_ascii=False
        ).encode("utf-8")
        with self._lock:
            self._rotate_if_needed()
            self._fh.write(
                _FRAME.pack(MAGIC, len(payload), zlib.crc32(payload))
            )
            self._fh.write(payload)
            self._fh.flush()
            self._active_size += _FRAME.size + len(payload)
            self._disk_bytes += _FRAME.size + len(payload)
            self.next_seq += 1
            if self._active_size >= self.segment_bytes:
                self._enforce_max_bytes()
            self._update_disk_gauge()

    # -------------------------------------------------------------- sample

    def sample(self) -> None:
        """Snapshot the already-computed telemetry views into one durable
        record. Called ONLY from SessionStats._publish_registry (TW010) —
        pure host-side reads: registry snapshot, health-monitor summary,
        cumulative stage clock, /proc statm. No device traffic."""
        from ..utils.rss import rss_mb

        monitor = _metrics.get_health_monitor()
        # persist phase transitions the classifier recorded since the last
        # sample — the labeled intervals every derivation is matched on
        with monitor._lock:
            transitions = list(monitor.transitions)
            phase = monitor.phase
        for t, ph in transitions[self._seen_transitions:]:
            self._write({"k": "p", "t_ms": int(t * 1000.0), "phase": ph})
        self._seen_transitions = len(transitions)

        stages = _sideband.stage_seconds()
        deltas = {
            k: round((v - self._prev_stages.get(k, 0.0)) * 1000.0, 3)
            for k, v in stages.items()
        }
        self._prev_stages = stages
        snap = _metrics.get_registry().snapshot()
        summary = monitor.summary()
        rec = {
            "k": "s",
            "seq": self.next_seq,
            "t_ms": now_ms(),
            "run_id": self.run_id,
            "phase": phase,
            "rss_mb": round(rss_mb(), 2),
            "rtt_ms": summary["rtt_ms"],
            "stages_ms": deltas,
            "counters": snap["counters"],
            "gauges": snap["gauges"],
        }
        self._write(rec)
        self._samples_c.inc()
        with self._lock:
            self._tail.append({
                "t_ms": rec["t_ms"], "phase": phase,
                "rss_mb": rec["rss_mb"], "rtt_ms": rec["rtt_ms"],
                "stages_ms": deltas,
            })
            if phase == monitor.HEALTHY:
                self._healthy_samples += 1
                for stage, ms in deltas.items():
                    self._stage_hist.setdefault(
                        stage, deque(maxlen=_STAGE_HISTORY)
                    ).append(ms)
        if self.perf_guard and phase == monitor.HEALTHY:
            self._guard_check(deltas)

    # ------------------------------------------------ regression sentinel

    def _guard_check(self, deltas: "dict[str, float]") -> None:
        """Phase-matched sustained-regression detection against the prior
        run's baseline. Warn-only by construction: one blackbox event +
        counter per episode, never a raise into the publish path."""
        base = self.baseline
        if not base:
            return
        for stage, base_ms in base.get("stages_ms", {}).items():
            if base_ms < GUARD_MIN_BASELINE_MS:
                continue
            cur = deltas.get(stage)
            if cur is None:
                continue
            if cur > self.guard_ratio * base_ms:
                run = self._breach_run.get(stage, 0) + 1
                self._breach_run[stage] = run
            else:
                self._breach_run[stage] = 0
                self._in_episode[stage] = False
                continue
            if run >= GUARD_WINDOW and not self._in_episode.get(stage):
                self._in_episode[stage] = True
                self._regressions.inc()
                ratio = round(cur / base_ms, 2)
                from . import blackbox as _blackbox

                _blackbox.record(
                    "perf_regression", stage=stage, ratio=ratio,
                    baseline_ms=round(base_ms, 3), current_ms=round(cur, 3),
                    window=run, baseline_run_id=base.get("run_id", -1),
                )
                log.warning(
                    "perfGuard: stage %r sustained at %.2fx the healthy-"
                    "phase baseline (%.3f ms -> %.3f ms per publish tick, "
                    "%d consecutive healthy samples; baseline from run %s)"
                    " — warn-only, counted in perf.regressions",
                    stage, ratio, base_ms, cur, run,
                    base.get("run_id", "?"),
                )

    def _load_baseline(self) -> "dict | None":
        path = os.path.join(self.directory, BASELINE_NAME)
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
            if isinstance(doc, dict) and isinstance(
                doc.get("stages_ms"), dict
            ):
                log.info(
                    "perfGuard: baseline loaded from %s (run %s, %d "
                    "healthy samples)", path, doc.get("run_id", "?"),
                    doc.get("samples", 0),
                )
                return doc
        except FileNotFoundError:
            pass
        except Exception:
            log.warning(
                "perfGuard: unreadable baseline %s ignored", path,
                exc_info=True,
            )
        return None

    def stamp_baseline(self) -> "dict | None":
        """Write per-stage healthy-phase medians as the next run's baseline
        (clean shutdown only — the app's finally block gates on a
        non-failed run). Atomic tmp+replace; returns the stamped doc or
        None when too few healthy samples exist to be a verdict."""
        with self._lock:
            if self._healthy_samples < BASELINE_MIN_SAMPLES:
                return None
            stages = {
                stage: round(_median(vals), 3)
                for stage, vals in self._stage_hist.items()
                if vals
            }
            samples = self._healthy_samples
        doc = {
            "version": 1,
            "run_id": self.run_id,
            "fingerprint": self.fingerprint,
            "samples": samples,
            "stages_ms": stages,
        }
        path = os.path.join(self.directory, BASELINE_NAME)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
        log.info(
            "perfGuard: baseline stamped to %s (%d healthy samples, "
            "%d stages)", path, samples, len(stages),
        )
        return doc

    # --------------------------------------------------------------- views

    def view(self) -> "dict | None":
        """The dashboard/web view (None until the first sample) — compact
        sparkline series from the in-memory tail ring, no disk reads."""
        with self._lock:
            if not self._tail:
                return None
            tail = list(self._tail)[-TAIL_SAMPLES:]
            disk_mb = round(self._disk_bytes / (1024 * 1024), 2)
        from ..utils.rss import slope_mb_per_min

        slope = slope_mb_per_min(
            [(t["t_ms"] / 1000.0, t["rss_mb"]) for t in tail]
        )
        return {
            "samples": int(self._samples_c.snapshot()),
            "runId": self.run_id,
            "phase": tail[-1]["phase"],
            "rssMb": tail[-1]["rss_mb"],
            "rssSlopeMbPerMin": round(slope, 3),
            "rttMs": tail[-1]["rtt_ms"],
            "diskMb": disk_mb,
            "regressions": int(self._regressions.snapshot()),
            "rss": [t["rss_mb"] for t in tail],
            "rtt": [t["rtt_ms"] for t in tail],
            "stageMs": [
                round(sum(t["stages_ms"].values()), 2) for t in tail
            ],
        }

    def bundle_tail(self, samples: int = TAIL_SAMPLES) -> dict:
        """The blackbox fold-in: the minutes before death (tail samples +
        every phase transition this process saw), straight from memory —
        the bundle writer must not pay disk reads mid-crash."""
        with self._lock:
            tail = list(self._tail)[-samples:]
        monitor = _metrics.get_health_monitor()
        with monitor._lock:
            transitions = [
                [int(t * 1000.0), ph] for t, ph in monitor.transitions
            ]
        return {
            "run_id": self.run_id,
            "fingerprint": self.fingerprint,
            "samples": tail,
            "transitions": transitions,
            "baseline": self.baseline,
        }

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


# ------------------------------------------------------------- raw readers
# module-level so tools/history_report.py can reconstruct the timeline from
# a SIGKILLed run's leftover segments with no live process state


def _scan_segment(path: str):
    """Yield (record_dict, end_offset) for every CRC-valid frame in one
    segment, stopping at the first invalid one (torn tail)."""
    with open(path, "rb") as fh:
        data = fh.read()
    pos = 0
    while pos + _FRAME.size <= len(data):
        magic, plen, crc = _FRAME.unpack_from(data, pos)
        if magic != MAGIC or plen == 0 or plen > _PAYLOAD_MAX:
            return
        end = pos + _FRAME.size + plen
        if end > len(data):
            return  # torn mid-payload
        payload = data[pos + _FRAME.size: end]
        if zlib.crc32(payload) != crc:
            return  # torn mid-frame / bit rot
        try:
            rec = json.loads(payload.decode("utf-8"))
        except ValueError:
            return
        yield rec, end
        pos = end


def read_series(directory: str) -> "list[dict]":
    """Every CRC-valid record across all segments, in append order — the
    offline entry point: works on a dead run's directory as-is (a torn
    tail is skipped, not an error; the live recovery truncates it)."""
    records: "list[dict]" = []
    names = []
    try:
        names = os.listdir(directory)
    except OSError:
        return records
    segs = sorted(
        (int(m.group(1)), os.path.join(directory, n))
        for n in names if (m := _SEG_RE.match(n))
    )
    for _first, path in segs:
        for rec, _end in _scan_segment(path):
            records.append(rec)
    return records


def phase_intervals(records: "list[dict]") -> "list[dict]":
    """Healthy/degraded episodes as labeled [start_ms, end_ms] intervals
    from run-header/phase/sample records alone (phase-matching for every
    derived statistic). Sample records vote too: a run that never flipped
    still yields its one interval."""
    out: "list[dict]" = []
    cur_phase = None
    cur_start = None
    last_t = None
    n_samples = 0

    def _close(end_ms):
        if cur_phase is not None and cur_start is not None:
            out.append({
                "phase": cur_phase,
                "start_ms": int(cur_start),
                "end_ms": int(end_ms),
                "samples": n_samples,
            })

    for rec in records:
        t = rec.get("t_ms")
        if t is None:
            continue
        kind = rec.get("k")
        phase = rec.get("phase")
        if kind == "s":
            last_t = t
        if not phase:
            continue
        if cur_phase is None:
            cur_phase, cur_start = phase, t
        elif phase != cur_phase:
            # "p" records carry the exact flip time; a sample or run header
            # with a new phase still flips the interval (robust to a torn
            # tail that ate the transition record)
            _close(t)
            cur_phase, cur_start = phase, t
            n_samples = 0
        if kind == "s":
            n_samples += 1
    _close(last_t if last_t is not None else cur_start)
    return out


def rss_slope(records: "list[dict]") -> float:
    """Least-squares RSS slope (MB/min) over every sample record — the
    soak gate's estimator, answerable from any run's leftovers."""
    from ..utils.rss import slope_mb_per_min

    return slope_mb_per_min([
        (rec["t_ms"] / 1000.0, rec["rss_mb"])
        for rec in records
        if rec.get("k") == "s" and "rss_mb" in rec
    ])


def phase_trends(records: "list[dict]") -> "dict[str, dict]":
    """Per-phase medians of the trend metrics (fetch RTT, per-tick stage
    costs, rows/s throughput from counter deltas) — the r-series verdicts,
    phase-matched so a degraded stall never dilutes the healthy numbers."""
    by_phase: "dict[str, dict]" = {}
    prev: "dict | None" = None
    for rec in records:
        if rec.get("k") != "s":
            continue
        bucket = by_phase.setdefault(rec.get("phase", "?"), {
            "samples": 0, "rtt_ms": [], "rss_mb": [], "stages_ms": {},
            "rows_per_s": [],
        })
        bucket["samples"] += 1
        if rec.get("rtt_ms", 0) > 0:
            bucket["rtt_ms"].append(rec["rtt_ms"])
        if "rss_mb" in rec:
            bucket["rss_mb"].append(rec["rss_mb"])
        for stage, ms in rec.get("stages_ms", {}).items():
            bucket["stages_ms"].setdefault(stage, []).append(ms)
        if prev is not None and prev.get("run_id") == rec.get("run_id"):
            dt_s = (rec["t_ms"] - prev["t_ms"]) / 1000.0
            rows = (
                rec.get("counters", {}).get("journal.appended_rows", 0)
                - prev.get("counters", {}).get("journal.appended_rows", 0)
            )
            if dt_s > 0 and rows > 0:
                bucket["rows_per_s"].append(rows / dt_s)
        prev = rec
    return {
        phase: {
            "samples": b["samples"],
            "rtt_ms": round(_median(b["rtt_ms"]), 3),
            "rss_mb": round(_median(b["rss_mb"]), 2),
            "rows_per_s": round(_median(b["rows_per_s"]), 1),
            "stages_ms": {
                stage: round(_median(vals), 3)
                for stage, vals in sorted(b["stages_ms"].items())
            },
        }
        for phase, b in by_phase.items()
    }


# ------------------------------------------------------- module-global face
# (the journal/blackbox idiom: entry points install once, THE seam calls
# sample(), tests uninstall)

_HISTORIAN: "Historian | None" = None


def configure(
    directory: str,
    max_mb: int = 256,
    perf_guard: bool = True,
    guard_ratio: float = 1.5,
    run_id: int = 0,
    fingerprint: str = "",
) -> Historian:
    global _HISTORIAN
    if _HISTORIAN is not None:
        _HISTORIAN.close()
    _HISTORIAN = Historian(
        directory, max_mb=max_mb, perf_guard=perf_guard,
        guard_ratio=guard_ratio, run_id=run_id, fingerprint=fingerprint,
    )
    log.info(
        "telemetry historian ON: %s (max %d MB, run_id=%d, perfGuard=%s, "
        "resumed at seq %d)", directory, max_mb, run_id,
        "warn" if perf_guard else "off", _HISTORIAN.next_seq,
    )
    return _HISTORIAN


def enabled() -> bool:
    return _HISTORIAN is not None


def get() -> "Historian | None":
    return _HISTORIAN


def sample() -> None:
    """THE sampling hook (lawcheck TW010: only SessionStats, from item 0 of
    its period, may call this) — no-op when the historian is off so
    ``--history off`` is bit-exact pre-historian behavior."""
    if _HISTORIAN is not None:
        _HISTORIAN.sample()


def last_history() -> "dict | None":
    """Latest historian view for /api/history and SessionStats; None when
    the historian is off or nothing was sampled."""
    return _HISTORIAN.view() if _HISTORIAN is not None else None


def bundle_tail() -> "dict | None":
    """The blackbox fold-in (the minutes before death); None when off."""
    return _HISTORIAN.bundle_tail() if _HISTORIAN is not None else None


def stamp_baseline() -> "dict | None":
    """Clean-shutdown hook: stamp this run's healthy-phase stage medians as
    the next run's perfGuard baseline."""
    if _HISTORIAN is not None and _HISTORIAN.perf_guard:
        return _HISTORIAN.stamp_baseline()
    return None


def uninstall() -> None:
    global _HISTORIAN
    if _HISTORIAN is not None:
        _HISTORIAN.close()
    _HISTORIAN = None


def reset_for_tests() -> None:
    uninstall()
