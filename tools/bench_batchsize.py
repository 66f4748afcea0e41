"""Headline-config batch-size sweep (r4): is 2048 still the right batch
for the dense ragged+packed flagship pipeline?

Why re-ask: the ragged + packed wire changed the bytes-per-batch landscape
the earlier choice of 2048 was made in. Larger batches amortize per-batch
fixed costs (dispatch, per-transfer cost, the packed-buffer assembly,
featurize-call overhead); smaller ones pipeline more finely. The answer
belongs to the machine it is measured on (ROADMAP S3).

Arms interleave round-robin within one window (a slow stretch hits
every arm equally) and the report gives paired per-round ratios vs the
b2048 incumbent — the same methodology as tools/bench_2e18.py.

Usage: python tools/bench_batchsize.py [--tweets N] [--budget S]
       [--config headline|logistic] [--batches 2048,8192,...]
``--config logistic`` sweeps CONFIG #3's own pipeline (lexicon sentiment
labeler + logistic learner, ragged+packed) instead of the headline's —
VERDICT r4 #6: the suite default there was set by analogy to the headline
profile; this measures it on the config itself.
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


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    n_tweets, budget, config = 131072, 300.0, "headline"
    batches = (1024, 2048, 4096, 8192, 16384, 32768)
    i = 0
    while i < len(args):
        if args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--budget":
            budget = float(args[i + 1]); i += 2
        elif args[i] == "--batches":
            batches = tuple(int(b) for b in args[i + 1].split(",")); i += 2
        elif args[i] == "--config":
            config = args[i + 1]; i += 2
            if config not in ("headline", "logistic"):
                raise SystemExit(f"unknown --config {config!r}")
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")
    if 2048 not in batches:
        batches = (2048,) + batches  # the paired baseline arm

    import jax

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import (
        StreamingLinearRegressionWithSGD,
        StreamingLogisticRegressionWithSGD,
    )
    from twtml_tpu.streaming.sources import SyntheticSource
    from twtml_tpu.utils.benchloop import _run_once

    feat = Featurizer(now_ms=1785320000000)
    if config == "logistic":
        # config #3's exact pipeline: lexicon sentiment labels via the C
        # batched labeler + the logistic learner (tools/bench_suite.py)
        from twtml_tpu.features.sentiment import (
            sentiment_label,
            sentiment_labels,
        )

        feat.label_fn = sentiment_label
        feat.batch_label_fn = sentiment_labels
        model_cls = StreamingLogisticRegressionWithSGD
    else:
        model_cls = StreamingLinearRegressionWithSGD
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())

    arms: dict = {}

    def arm(batch):
        chunks = [
            statuses[i : i + batch] for i in range(0, len(statuses), batch)
        ]

        def fz(c, batch=batch):
            return feat.featurize_batch_ragged(
                c, row_bucket=batch, pre_filtered=True, pack=True
            )

        m = model_cls()
        for _ in range(2):
            float(m.step(fz(chunks[0])).mse)  # completion-fetch warmup

        def one_pass(m=m, fz=fz, chunks=chunks):
            m.reset()
            return _run_once(m, fz, chunks, prefetch=True)

        arms[f"b{batch}"] = one_pass

    for b in batches:
        arm(b)

    times: dict[str, list] = {k: [] for k in arms}
    t_end = time.perf_counter() + budget
    while time.perf_counter() < t_end:
        for name, run in arms.items():
            dt, _ = run()
            times[name].append(dt)

    out = {"config": f"{config}_batch_sweep", "tweets": n_tweets,
           "backend": jax.default_backend(), "rounds": len(times["b2048"])}
    base = times["b2048"]
    for name, ts in times.items():
        out[name] = {
            "best": round(n_tweets / min(ts), 1),
            "median": round(n_tweets / statistics.median(ts), 1),
        }
        if name != "b2048":
            out[name]["paired_speedup_median"] = round(
                statistics.median([b / t for b, t in zip(base, ts)]), 3
            )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
