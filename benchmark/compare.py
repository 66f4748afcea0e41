"""The comparison that decides ``correct``: each number compared is printed
beside its own limit (``configs/<name>.json`` → ``correct.limits``; PERF.md
section 2 gives the readings every limit was set from)."""

from __future__ import annotations

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


def training(v: Verdict, limits: dict, program: dict, ref_stats: list,
             ref_weights, tag: str = "") -> None:
    """``program``: {"batches": [{"count", "batch", "mse"}...], "weights"}
    from the check run; the reference's stats per batch and its weights.

    - ``count_diff``: per-batch rows and the running count, exact;
    - ``mse_dev``: worst batch's |mse - reference| / reference, after one
      HALF_UP unit is taken off (both sides print a rounded integer);
    - ``weights_dev``: Σ|w − w_ref| / Σ|w_ref| after the check batches. The
      L1 norm, not the worst weight: float32 rounding leaves its largest
      error in a few weights, and that maximum swings sixfold from seed to
      seed in 2^18 dims, while a lower precision spreads its error over all
      of them; PERF.md section 2 has the readings of both.
    """
    lines = program["batches"]
    if len(lines) != len(ref_stats):
        v.fail(f"{tag}the check run published {len(lines)} batches, the "
               f"reference trained {len(ref_stats)}")
        return
    total, count_diff, mse_dev = 0, 0, 0.0
    for got, ref in zip(lines, ref_stats):
        total += ref["count"]
        count_diff += abs(got["batch"] - ref["count"]) + abs(got["count"] - total)
        mse_dev = max(
            mse_dev,
            max(abs(got["mse"] - ref["mse"]) - 1.0, 0.0) / max(ref["mse"], 1.0),
        )
    v.hold(f"{tag}count_diff", count_diff, limits["count_diff"])
    v.hold(f"{tag}mse_dev", mse_dev, limits["mse_dev"])
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
