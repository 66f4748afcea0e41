"""Process-RSS watchdog for long-running loops.

Why this exists: a device client that retains host-side transfer buffers
makes host RSS grow in proportion to UPLOADED BYTES while the identical
pipeline on the CPU backend stays flat (tools/soak.py measures the slope
and tells the two apart). The framework cannot free another library's
buffers, so the guard is operational: sample RSS cheaply on a
batch cadence, warn with the diagnosis and the workaround when growth
passes a threshold, and keep warning at each further threshold step. The
workaround is bounded process lifetime — checkpoint-restart is cheap here
(``--checkpointDir``/``--checkpointEvery`` resume exactly,
apps/common.AppCheckpoint), so a supervisor can recycle the process
before the leak matters. A runtime that does not leak never trips it.
"""

from __future__ import annotations

import os
import resource

from .logging import get_logger

log = get_logger("utils.rss")


def rss_mb() -> float:
    """Current resident set size in MB (statm is a no-syscall read on
    Linux; ru_maxrss — the high-water mark — is the portable fallback).

    Linux-only assumptions in the fallback: ru_maxrss is KB on Linux but
    BYTES on macOS (where this would over-report ~1000×), and a high-water
    mark can never shrink the way the statm reading can. Harmless on this
    rig; gate on sys.platform before reusing elsewhere."""
    try:
        with open("/proc/self/statm") as fh:
            pages = int(fh.read().split()[1])
        return pages * os.sysconf("SC_PAGESIZE") / 1e6
    except Exception:
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def slope_mb_per_min(samples) -> float:
    """Least-squares slope of ``(t_seconds, rss_mb)`` samples in MB/min —
    the tools/soak.py leak-rate estimator, shared so the live
    ``host.rss_slope_mb_per_min`` gauge and the offline soak report agree
    on the math. 0.0 until two samples exist or all timestamps coincide."""
    pts = list(samples)
    if len(pts) < 2:
        return 0.0
    xs = [t / 60.0 for t, _ in pts]
    ys = [m for _, m in pts]
    n = float(len(pts))
    mx = sum(xs) / n
    my = sum(ys) / n
    var = sum((x - mx) ** 2 for x in xs)
    if var == 0.0:
        return 0.0
    cov = sum((x - mx) * (y - my) for x, y in zip(xs, ys))
    return cov / var


class RssWatchdog:
    """``tick()`` once per batch; samples every ``sample_every`` ticks and
    warns when RSS has grown ``warn_growth_mb`` beyond the first sample
    (then again at each further ``warn_growth_mb`` of growth).

    ``TWTML_RSS_WARN_MB`` overrides the threshold; 0 disables the warning
    (sampling still happens so callers can read ``last_mb``)."""

    def __init__(
        self, warn_growth_mb: float | None = None, sample_every: int = 64
    ):
        if warn_growth_mb is None:
            warn_growth_mb = float(os.environ.get("TWTML_RSS_WARN_MB", 2048))
        self.warn_growth_mb = warn_growth_mb
        self.sample_every = max(1, sample_every)
        self.last_mb: float | None = None
        self.warn_count = 0
        self._base: float | None = None
        self._next_warn = warn_growth_mb
        self._ticks = 0

    def tick(self) -> None:
        self._ticks += 1
        if self._ticks % self.sample_every:
            return
        cur = rss_mb()
        self.last_mb = cur
        try:
            # observability side-channel: the per-N-batches RSS sample lands
            # in the metrics registry so the dashboard/bench see it live
            from ..telemetry import metrics as _metrics

            _metrics.get_registry().gauge("host.rss_mb").set(round(cur, 1))
        except Exception:  # lawcheck: disable=TW005 -- telemetry side-channel publish: the RSS gauge must never kill the recycle watchdog (Try-parity)
            pass
        if self._base is None:
            self._base = cur
            return
        growth = cur - self._base
        if self.warn_growth_mb > 0 and growth >= self._next_warn:
            log.warning(
                "process RSS grew %.0f MB since start (now %.0f MB). Growth "
                "in proportion to uploaded bytes, with the same pipeline "
                "flat on the CPU backend, points at transfer buffers the "
                "device client retains (tools/soak.py tells them apart). "
                "Workaround for long-lived runs: bound process "
                "lifetime via checkpoint-restart (--checkpointDir + "
                "--checkpointEvery resume exactly).",
                growth, cur,
            )
            self.warn_count += 1
            self._next_warn = growth + self.warn_growth_mb
