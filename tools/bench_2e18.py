"""Config #4 (hashing_2e18_l2) operating-point sweep — VERDICT r2 #4.

The 2^18 Gram-domain step is the one device-heavy program (the G = Z·Zᵀ
matmul is 2·B²·F = ~2.2 TFLOP at B=2048 by arithmetic; its time on this
machine is not measured beyond PERF.md §5's one smoke figure). The
G build costs B²·F FLOPs, i.e. PER-TWEET device cost scales linearly with
batch size, so a smaller batch trades per-batch overheads for less G work
per tweet. This tool interleaves arms (batch size × wire)
within one window — single passes round-robin, so a slow stretch hits
every arm equally — and reports each arm's best/median plus per-round
rates, to pick the config #4 operating point from data.

Usage: python tools/bench_2e18.py [--tweets N] [--budget S]
Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

F_TEXT = 2**18


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    n_tweets, budget = 65536, 240.0
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

    def chunked(b):
        return [statuses[i : i + b] for i in range(0, len(statuses), b)]

    def model(int8=None):
        # gram_int8 is threaded as a trace-time PARAMETER (not a module
        # global): the ragged wire retraces per flat-buffer bucket, and a
        # global flag would leave every post-warmup trace on the default
        # plane — the A/B arms would silently converge
        return StreamingLinearRegressionWithSGD(
            num_text_features=F_TEXT, l2_reg=0.1, gram_int8=int8
        )

    arms: dict = {}

    def pipeline_arm(name, batch, wire, int8=None):
        chunks = chunked(batch)
        fz = (
            (lambda c: feat.featurize_batch_ragged(
                c, row_bucket=batch, pre_filtered=True))
            if wire == "ragged"
            else (lambda c: feat.featurize_batch_units(
                c, row_bucket=batch, pre_filtered=True))
        )
        m = model(int8)
        for _ in range(2):
            float(m.step(fz(chunks[0])).mse)  # completion-fetch warmup

        def one_pass(m=m, fz=fz, chunks=chunks):
            m.reset()
            return _run_once(m, fz, chunks, prefetch=True)

        arms[name] = one_pass

    pipeline_arm("padded_b2048", 2048, "padded")  # the r2 operating point
    pipeline_arm("ragged_b2048", 2048, "ragged", int8=True)
    pipeline_arm("ragged_b3072", 3072, "ragged", int8=True)  # r4 point
    pipeline_arm("ragged_b4096", 4096, "ragged", int8=True)  # past-the-optimum
    pipeline_arm("ragged_b1024", 1024, "ragged", int8=True)  # r3 point
    pipeline_arm("ragged_b1024_bf16", 1024, "ragged", int8=False)  # r3 plane A/B
    pipeline_arm("ragged_b2048_bf16", 2048, "ragged", int8=False)
    pipeline_arm("ragged_b512", 512, "ragged")
    pipeline_arm("padded_b1024", 1024, "padded")

    # the house interleaved/paired scheduling (tools/pairedbench.py)
    from tools.pairedbench import (
        best_median_rate, paired_ratio_median, run_rounds,
    )

    times = run_rounds(arms, budget)

    out = {"config": "hashing_2e18_l2_sweep", "tweets": n_tweets,
           "backend": jax.default_backend(), "rounds": len(times["padded_b2048"])}
    for name, ts in times.items():
        best, median = best_median_rate(ts, n_tweets)
        out[name] = {"best": best, "median": median}
    base = times["padded_b2048"]
    for name, ts in times.items():
        if name != "padded_b2048":
            out[name]["paired_speedup_median"] = paired_ratio_median(base, ts)
    # the int8-plane question, answered directly: same wire, same batch,
    # per-round ratios of the bf16-plane arm over the int8-plane arm
    for b in (1024, 2048):
        i8, bf = times.get(f"ragged_b{b}"), times.get(f"ragged_b{b}_bf16")
        if i8 and bf:
            out[f"int8_vs_bf16_b{b}"] = paired_ratio_median(bf, i8)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
