"""The comparison that decides ``correct``: each number compared is printed
beside its own limit (``configs/<name>.json`` → ``correct.limits``; PERF.md
section 2 gives the readings every limit was set from)."""

from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

from .harness import say


class Verdict:
    def __init__(self):
        self.ok = True
        self.numbers: dict = {}

    def hold(self, name: str, value: float, limit: float) -> None:
        good = bool(value <= limit)   # a NaN fails
        self.numbers[name] = {"value": float(value), "limit": float(limit)}
        say(f"correct: {name} = {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if good else 'FAIL'}")
        self.ok = self.ok and good

    def fail(self, why: str) -> None:
        say(f"correct: FAIL — {why}")
        self.ok = False


def _half_up_integer(got: float, ref: dict) -> float:
    """|printed − reference| ÷ reference after ONE unit is taken off: both
    sides print a HALF_UP-rounded integer (an mse of ~85,000), so a unit is
    rounding and anything beyond it is relative to the reference."""
    return max(abs(got - ref["mse"]) - 1.0, 0.0) / max(ref["mse"], 1.0)


def _rate(got: float, ref: dict) -> float:
    """A share in [0, 1] printed to three decimals (a misclassification
    rate). The relative formula above is vacuous there (|Δ| < 1 always).
    Taken off: half a unit of the third decimal, the print's rounding, and
    ``near_rows`` ÷ rows — the rows whose reference margin |w·x| lies under
    the reference's stated ε, so that float32 rounding may flip their hard
    0/1 prediction (model-configs 3.3: hold margins and weights, not
    thresholded outputs). What is left is absolute: one row of 2048 wrongly
    classed reads 4.9e-4."""
    return max(abs(got - ref["rate"]) - 0.0005 - ref["near_rows"] / ref["count"],
               0.0)


class Statistic(NamedTuple):
    number: str           # the name of the number compared, and of its limit
    key: str              # the reference's stats hold the statistic under it
    deviation: Callable   # (printed value, the reference's stats) -> float


# ``correct.statistic`` of a configuration file names how the statistic the
# program prints per batch is held to the reference's. Absent means
# "half_up_integer".
STATISTICS = {
    "half_up_integer": Statistic("mse_dev", "mse", _half_up_integer),
    "rate": Statistic("rate_dev", "rate", _rate),
}


def statistic_of(config: dict) -> Statistic:
    rule = config["correct"].get("statistic", "half_up_integer")
    if rule not in STATISTICS:
        raise SystemExit(f"benchmark: correct.statistic {rule!r} is not one of "
                         f"{sorted(STATISTICS)}")
    return STATISTICS[rule]


def training(v: Verdict, config: dict, program: dict, ref_stats: list,
             ref_weights, tag: str = "") -> None:
    """``program``: {"batches": [{"count", "batch", "stat"}...], "weights"}
    from the check run; the reference's stats per batch and its weights;
    the rule and the limits from ``config["correct"]``.

    - ``count_diff``: per-batch rows and the running count, exact;
    - ``mse_dev`` / ``rate_dev``: the worst batch's deviation of the printed
      statistic by the configuration's rule (above);
    - ``weights_dev``: Σ|w − w_ref| / Σ|w_ref| after the check batches. The
      L1 norm, not the worst weight: float32 rounding leaves its largest
      error in a few weights, and that maximum swings sixfold from seed to
      seed in 2^18 dims, while a lower precision spreads its error over all
      of them; PERF.md section 2 has the readings of both.
    """
    limits = config["correct"]["limits"]
    rule = statistic_of(config)
    lines = program["batches"]
    if len(lines) != len(ref_stats):
        v.fail(f"{tag}the check run published {len(lines)} batches, the "
               f"reference trained {len(ref_stats)}")
        return
    total, count_diff, stat_dev = 0, 0, 0.0
    for got, ref in zip(lines, ref_stats):
        total += ref["count"]
        count_diff += abs(got["batch"] - ref["count"]) + abs(got["count"] - total)
        stat_dev = max(stat_dev, rule.deviation(got["stat"], ref))
    v.hold(f"{tag}count_diff", count_diff, limits["count_diff"])
    v.hold(f"{tag}{rule.number}", stat_dev, limits[rule.number])
    if program.get("weights") is not None:
        w = np.asarray(program["weights"], np.float64)
        r = np.asarray(ref_weights, np.float64)
        if w.shape != r.shape or not np.all(np.isfinite(w)):
            v.fail(f"{tag}weights of shape {w.shape} (reference {r.shape}) "
                   "or not finite")
            return
        say(f"correct: {tag}worst weight: max|dw|/max|w| = "
            f"{np.max(np.abs(w - r)) / np.max(np.abs(r)):.6g} (not compared)")
        v.hold(f"{tag}weights_dev",
               float(np.sum(np.abs(w - r)) / np.sum(np.abs(r))),
               limits["weights_dev"])
