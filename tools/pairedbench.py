"""Shared interleaved/paired-ratio bench harness — the house measurement
method as a library.

A run's speed can swing between phases that last minutes (fetch-latency
health phases, a noisy neighbour), so sequential per-arm blocks confound arm
with phase. Every wire/dispatch
verdict in this repo therefore comes from ONE method: single passes
round-robin A/B/A/B… inside one budget window, then PAIRED per-round
ratios (each pair shares a phase window) summarized by their median —
health-phase-safe, because a phase swing hits both members of a pair.

This module extracts the arm scheduling and the ratio math that
tools/bench_ragged.py, tools/bench_2e18.py and tools/bench_telemetry.py
each re-implemented (r3–r5), so the method cannot drift between tools.

An *arm* is a zero-arg callable running ONE full pass and returning its
wall-clock seconds (or a ``(seconds, anything)`` tuple — the extra value
is discarded here; arms that need finals record them via closure). Arms
are responsible for their own warmup (compile + completion-fetch) before
entering the window: the harness times passes, it does not classify them.
"""

from __future__ import annotations

import statistics
import time


def run_rounds(
    arms: "dict[str, object]", budget_s: float, min_rounds: int = 1
) -> "dict[str, list[float]]":
    """Round-robin single passes over ``arms`` until the budget expires.

    Every started round COMPLETES (each arm ends with the same sample
    count — the paired-ratio invariant), and at least ``min_rounds``
    rounds run even past a tiny budget. Returns per-arm pass times in
    round order; ``paired_ratio_median`` consumes them pairwise."""
    times: "dict[str, list[float]]" = {name: [] for name in arms}
    t_end = time.perf_counter() + budget_s
    rounds = 0
    while rounds < min_rounds or time.perf_counter() < t_end:
        for name, run in arms.items():
            result = run()
            dt = result[0] if isinstance(result, tuple) else result
            times[name].append(float(dt))
        rounds += 1
    return times


def best_median_rate(
    pass_times: "list[float]", items: int
) -> "tuple[float, float]":
    """(best, median) items/second over a list of pass times."""
    return (
        round(items / min(pass_times), 1),
        round(items / statistics.median(pass_times), 1),
    )


def paired_ratios(
    base_times: "list[float]", arm_times: "list[float]"
) -> "list[float]":
    """Per-round base/arm time ratios (>1 = the arm is faster): the
    phase-robust comparison — each pair shares one window."""
    return [b / a for b, a in zip(base_times, arm_times)]


def paired_ratio_median(
    base_times: "list[float]", arm_times: "list[float]", digits: int = 3
) -> float:
    """Median paired speedup of ``arm`` over ``base`` — the ONE number a
    wire/dispatch verdict quotes (BENCHMARKS.md house rules)."""
    return round(
        statistics.median(paired_ratios(base_times, arm_times)), digits
    )
