"""The control of ``correct`` (``--control bf16``): the reference, computed
with every product's floating operands rounded to bfloat16 — the nearest
precision below the float32 the configurations state, the step that would
tempt a later PR — is put in the program's place and compared with the
float64 reference by the same ``compare`` functions and limits. It has to
come out NOT correct. Pure NumPy: it needs no chip, so it runs at the cell's
own size wherever it is started."""

from __future__ import annotations

from . import compare
from .drivers import train
from .harness import say


def run(cell: dict, args) -> dict:
    cfg = cell["config"]
    ref, ref_stats = train.reference(cell, args.seed)
    low, low_stats = train.reference(cell, args.seed, precision=args.control)
    key = compare.statistic_of(cfg).key
    total, lines = 0, []
    for s in low_stats:
        total += s["count"]
        lines.append({"count": total, "batch": s["count"], "stat": s[key]})
    v = compare.Verdict()
    compare.training(v, cfg, {"batches": lines, "weights": low.w},
                     ref_stats, ref.w)
    say(f"control {args.control}: correct = {v.ok} (has to be False)")
    return {"control": args.control, "correct": v.ok, "numbers": v.numbers}
