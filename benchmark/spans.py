"""Reduction of the program's ``--trace`` span file to per-stage totals.

Copied from ``tools/trace_report.py`` (``load_events`` + ``summarize``), so
that no later PR can change how a span becomes a number, and narrowed to one
stretch of time: span ``ts`` is ``time.perf_counter`` in microseconds, which
on Linux is the CLOCK_MONOTONIC the harness stamps its window with.
"""

from __future__ import annotations

import json
import os


def load_events(path: str) -> list:
    """The trace's events; a rotated ``PATH.1`` segment is prepended. The
    file is a ``[`` line and one event object per line with a trailing
    comma (Chrome's incremental array form)."""
    events: list = []
    for p in (path + ".1", path):
        if not os.path.exists(p):
            continue
        with open(p, encoding="utf-8") as fh:
            for raw in fh:
                line = raw.strip().rstrip(",")
                if line in ("", "[", "]"):
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue  # a line cut by the writer's shutdown
    return events


def summarize(events: list, t0_s: float, t1_s: float) -> dict:
    """Complete ("X") spans that START inside ``[t0_s, t1_s)``, per name:
    ``{name: {"count", "total_ms", "max_ms", "bytes", "rows"}}``."""
    lo, hi = t0_s * 1e6, t1_s * 1e6
    stages: dict = {}
    for ev in events:
        if ev.get("ph") != "X" or not lo <= float(ev.get("ts", -1)) < hi:
            continue
        st = stages.setdefault(
            ev.get("name", "?"),
            {"count": 0, "total_ms": 0.0, "max_ms": 0.0, "bytes": 0, "rows": 0},
        )
        dur_ms = float(ev.get("dur", 0.0)) / 1e3
        st["count"] += 1
        st["total_ms"] += dur_ms
        st["max_ms"] = max(st["max_ms"], dur_ms)
        args = ev.get("args") or {}
        for key in ("wire_bytes", "bytes"):
            if key in args:
                st["bytes"] += int(args[key])
                break
        for key in ("rows", "batch", "n"):
            if key in args:
                st["rows"] += int(args[key])
                break
    return stages
