"""Per-batch lineage records for the freshness plane (ISSUE 16).

Every batch that enters the host pipeline gets ONE record stamped at the
existing seams — no new seams, no device work, no fetches:

  open      FeatureStream._process / _run_batch_aligned, right before
            featurize: captures a ``stage_seconds()`` snapshot, one
            ``now_ms()`` read (the TWTML_NOW_MS seam), and the event-time
            span of the batch (min/max ``created_at_ms``).
  dispatch  the two dispatch sites in apps/common (FetchPipeline,
            per_batch): moves the oldest open record into the in-flight
            FIFO.
  delivery  FreshnessGuard (outermost delivery wrapper): pops the oldest
            in-flight record and diffs the stage clock against the open
            snapshot — the per-stage deltas name the dominant edge.

Two FIFOs instead of a dict keyed on batch identity: deliveries are
strictly in dispatch order (FetchPipeline resolves futures FIFO), so
positional matching is exact and needs no key that survives a pack.
Dispatches with no open record (serving-plane predictions, warmup, tests
driving a bare pipeline) push a blank so the FIFOs stay aligned; both deques
are bounded so leaked records (shutdown, shed batches) cannot grow host
state.

Module is jax-free and every entry point is a cheap no-op until
``configure(True)`` — ``--freshness off`` never touches the deques, which is
what makes the off arm bit-identical to HEAD.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from ..utils.clock import now_ms
from . import sideband as _sideband

# seam-to-seam edges eligible for critical-path attribution (stage-clock
# keys; cumulative wall seconds, diffed open -> delivery per batch)
EDGES = ("source_read", "parse", "featurize", "wire_pack", "dispatch", "fetch")

# bounded FIFOs: deeper than any fetch-pipeline depth we run,
# shallow enough that leaked records are noise, not a leak
MAX_RECORDS = 4096

_LOCK = threading.Lock()
_ON = False
_PREP: deque = deque(maxlen=MAX_RECORDS)
_INFLIGHT: deque = deque(maxlen=MAX_RECORDS)


def configure(on: bool) -> None:
    global _ON
    with _LOCK:
        _ON = bool(on)


def enabled() -> bool:
    return _ON


def _numeric_span(numeric) -> tuple[int, int]:
    """Vectorized span over a ParsedBlock's int64 created_at column."""
    if getattr(numeric, "shape", (0,))[0] == 0:
        return 0, 0
    col = numeric[:, 4]
    col = col[col > 0]
    if col.size == 0:
        return 0, 0
    return int(col.min()), int(col.max())


def _event_span(statuses) -> tuple[int, int]:
    """(min_ms, max_ms) of ``created_at_ms`` over a Status list, a
    ParsedBlock, or a list of ParsedBlocks; zeros mean unknown."""
    numeric = getattr(statuses, "numeric", None)
    if numeric is not None:
        return _numeric_span(numeric)
    lo = hi = 0
    for item in statuses:
        n = getattr(item, "numeric", None)
        if n is not None:
            item_lo, item_hi = _numeric_span(n)
        else:
            ms = getattr(item, "created_at_ms", 0)
            item_lo = item_hi = ms if ms > 0 else 0
        if item_lo > 0 and (lo == 0 or item_lo < lo):
            lo = item_lo
        if item_hi > hi:
            hi = item_hi
    return lo, hi


def _rows(statuses) -> int:
    rows = getattr(statuses, "rows", None)
    if rows is not None:
        return int(rows)
    try:
        return sum(
            int(getattr(item, "rows", 1)) for item in statuses
        )
    except TypeError:
        return 0


def open_batch(statuses) -> None:
    """Stamp a lineage record as the batch enters featurize."""
    if not _ON:
        return
    lo, hi = _event_span(statuses)
    rec = {
        "t_open": time.perf_counter(),
        "opened_ms": now_ms(),
        "stages": _sideband.stage_seconds(),
        "event_min_ms": lo,
        "event_max_ms": hi,
        "rows": _rows(statuses),
    }
    with _LOCK:
        _PREP.append(rec)


def drop_newest() -> None:
    """The just-opened batch was shed before dispatch (skip_empty)."""
    if not _ON:
        return
    with _LOCK:
        if _PREP:
            _PREP.pop()


def mark_dispatch(n: int = 1) -> None:
    """Move the n oldest open records to the in-flight FIFO (called at the
    actual dispatch site). Blank records keep the FIFO aligned when a
    dispatch had no matching open (serving, warmup, bare-pipeline tests)."""
    if not _ON:
        return
    with _LOCK:
        for _ in range(n):
            _INFLIGHT.append(_PREP.popleft() if _PREP else None)


def pop_delivery() -> dict | None:
    """Pop the oldest in-flight record at fetch delivery and enrich it with
    the stage-clock deltas since open. None when the FIFO is empty or the
    record was a blank."""
    if not _ON:
        return None
    with _LOCK:
        rec = _INFLIGHT.popleft() if _INFLIGHT else None
    if rec is None:
        return None
    cur = _sideband.stage_seconds()
    base = rec.get("stages") or {}
    edges = {
        s: max(0.0, (cur.get(s, 0.0) - base.get(s, 0.0)) * 1e3) for s in EDGES
    }
    rec["edges_ms"] = edges
    rec["delivered_ms"] = now_ms()
    rec["e2e_ms"] = (time.perf_counter() - rec["t_open"]) * 1e3
    critical = max(edges, key=edges.get)
    rec["critical"] = critical if edges[critical] > 0.0 else ""
    return rec


def open_event_floor() -> int:
    """Oldest event-time still in flight (min event_min over both FIFOs);
    0 when nothing with a known event time is open — the low-watermark
    input for the current tick."""
    if not _ON:
        return 0
    floor = 0
    with _LOCK:
        for rec in (*_PREP, *_INFLIGHT):
            if rec is None:
                continue
            lo = rec.get("event_min_ms", 0)
            if lo > 0 and (floor == 0 or lo < floor):
                floor = lo
    return floor


def depths() -> tuple[int, int]:
    with _LOCK:
        return len(_PREP), len(_INFLIGHT)


def reset_for_tests() -> None:
    global _ON
    with _LOCK:
        _ON = False
        _PREP.clear()
        _INFLIGHT.clear()
