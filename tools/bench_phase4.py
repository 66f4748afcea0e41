"""Config #4 (hashing_2e18_l2) sustained-rate measurement ACROSS
fetch-latency health phases.

A rate met inside one healthy window is not a sustained rate. This tool
runs the suite's exact
config-#4 shape (65536 synthetic tweets, ragged wire, int8 Gram plane,
batch 2048 vs 3072) as INTERLEAVED single passes for a fixed long budget
(default 1500 s — long enough to straddle slow phases that last minutes),
timestamps every
round, and reports:

- per-arm best / median over the WHOLE window (the sustained number);
- per-300 s-window medians (the phase profile — how far the swings go);
- the paired per-round b3072/b2048 ratio (operating-point check);
- the fraction of b2048 rounds at or above 150k tweets/s.

Usage: python tools/bench_phase4.py [--tweets N] [--budget S]
Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

F_TEXT = 2**18
WINDOW_S = 300.0


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    n_tweets, budget = 65536, 1500.0
    i = 0
    while i < len(args):
        if args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--budget":
            budget = float(args[i + 1]); i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    import jax

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.sources import SyntheticSource
    from twtml_tpu.utils.benchloop import _run_once

    feat = Featurizer(num_text_features=F_TEXT, now_ms=1785320000000)
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())

    arms: dict = {}
    for batch in (2048, 3072):
        chunks = [statuses[i : i + batch] for i in range(0, len(statuses), batch)]

        def fz(c, batch=batch):
            return feat.featurize_batch_ragged(
                c, row_bucket=batch, pre_filtered=True
            )

        m = StreamingLinearRegressionWithSGD(
            num_text_features=F_TEXT, l2_reg=0.1, gram_int8=True
        )
        for _ in range(2):
            float(m.step(fz(chunks[0])).mse)  # completion-fetch warmup

        def one_pass(m=m, fz=fz, chunks=chunks):
            m.reset()
            return _run_once(m, fz, chunks, prefetch=True)

        arms[f"b{batch}"] = one_pass

    rounds: dict[str, list] = {k: [] for k in arms}  # (t_offset, seconds)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget:
        for name, run in arms.items():
            dt, _ = run()
            rounds[name].append((round(time.perf_counter() - t0, 1), dt))

    out = {
        "config": "hashing_2e18_l2_phase_sustain",
        "tweets": n_tweets,
        "backend": jax.default_backend(),
        "budget_s": budget,
        "rounds": len(rounds["b2048"]),
    }
    for name, rs in rounds.items():
        ts = [dt for _, dt in rs]
        rates = [n_tweets / dt for dt in ts]
        windows: dict[int, list] = {}
        for off, dt in rs:
            windows.setdefault(int(off // WINDOW_S), []).append(n_tweets / dt)
        out[name] = {
            "best": round(max(rates), 1),
            "median": round(statistics.median(rates), 1),
            "per_window_median": {
                str(w): round(statistics.median(v), 1)
                for w, v in sorted(windows.items())
            },
            "frac_ge_150k": round(
                sum(r >= 150_000 for r in rates) / len(rates), 3
            ),
        }
    a, b = [dt for _, dt in rounds["b2048"]], [dt for _, dt in rounds["b3072"]]
    out["paired_b3072_over_b2048"] = round(
        statistics.median([x / y for x, y in zip(a, b)]), 3
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
