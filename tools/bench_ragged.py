"""Wire-padding measurement: padded UnitBatch vs ragged RaggedUnitBatch.

VERDICT r2 #3: the padded [B, L_bucket] units buffer is the dominant wire
tensor and nothing measured what fraction of it is padding. This tool
reports, for a corpus at a given batch size:

  - the padding fraction of the padded units buffer (1 - Σlen / B·L);
  - wire bytes per batch for both formats (all five arrays);
  - the pipelined end-to-end rate for both formats on the current
    backend — single passes INTERLEAVED A/B/A/B (utils/benchloop._run_once
    per pass: dispatch freely, one completion fetch), with paired
    per-round ratios so a slow stretch hits both arms equally.

Usage: python tools/bench_ragged.py [--tweets N] [--batch B] [--budget S]
       [--config dense|2e18|logistic] [--ingest object|block]
Prints one JSON line. ``--ingest block`` compares the formats fed from the
native columnar parser's blocks (featurize_parsed_block) instead of Status
objects — the ragged form there skips the pad copy entirely.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def wire_bytes(batch) -> int:
    import jax

    return sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(batch)
    )


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    n_tweets, batch_size, budget, config = 65536, 2048, 45.0, "dense"
    ingest = "object"
    i = 0
    while i < len(args):
        if args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--batch":
            batch_size = int(args[i + 1]); i += 2
        elif args[i] == "--budget":
            budget = float(args[i + 1]); i += 2
        elif args[i] == "--config":
            config = args[i + 1]; i += 2
        elif args[i] == "--ingest":
            ingest = args[i + 1]; i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    import jax
    import numpy as np

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import (
        StreamingLinearRegressionWithSGD,
        StreamingLogisticRegressionWithSGD,
    )
    from twtml_tpu.streaming.sources import SyntheticSource

    f_text = 2**18 if config == "2e18" else 1000
    feat = Featurizer(num_text_features=f_text, now_ms=1785320000000)
    if config == "logistic":
        # the suite's config #3: lexicon sentiment labels via the C batch
        # scorer, logistic residual
        from twtml_tpu.features.sentiment import (
            sentiment_label,
            sentiment_labels,
            sentiment_labels_from_units,
        )

        feat.label_fn = sentiment_label
        feat.batch_label_fn = sentiment_labels
        feat.unit_label_fn = sentiment_labels_from_units  # block ingest
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())

    if ingest == "block":
        # columnar-block chunks (the config #1 path): materialize the
        # stream to .jsonl once, parse with the native loader, slice into
        # fixed row chunks; featurize_parsed_block builds either wire
        import tempfile

        from tools.bench_suite import _status_json
        from twtml_tpu.features.blocks import iter_row_chunks, merge_blocks
        from twtml_tpu.streaming.sources import BlockReplayFileSource

        with tempfile.NamedTemporaryFile(
            "w", suffix=".jsonl", delete=False
        ) as fh:
            for s in statuses:
                fh.write(json.dumps(_status_json(s)) + "\n")
            path = fh.name
        block = merge_blocks(list(BlockReplayFileSource(path).produce()))
        os.unlink(path)
        chunks = list(iter_row_chunks([block], batch_size))

        def fz_padded(sub):
            return feat.featurize_parsed_block(sub, row_bucket=batch_size)

        def fz_ragged(sub):
            return feat.featurize_parsed_block(
                sub, row_bucket=batch_size, ragged=True
            )
    else:
        chunks = [
            statuses[i : i + batch_size]
            for i in range(0, len(statuses), batch_size)
        ]

        def fz_padded(c):
            return feat.featurize_batch_units(
                c, row_bucket=batch_size, pre_filtered=True
            )

        def fz_ragged(c):
            return feat.featurize_batch_ragged(
                c, row_bucket=batch_size, pre_filtered=True
            )

    # ---- wire accounting on the first full chunk -------------------------
    pb = fz_padded(chunks[0])
    rb = fz_ragged(chunks[0])
    real_units = int(np.asarray(rb.offsets)[-1])
    padded_units = int(pb.units.shape[0] * pb.units.shape[1])
    out = {
        "config": config,
        "ingest": ingest,
        "batch": batch_size,
        "units_padding_fraction": round(1 - real_units / padded_units, 4),
        "padded_wire_bytes": wire_bytes(pb),
        "ragged_wire_bytes": wire_bytes(rb),
        "unit_dtype": str(pb.units.dtype),
        "backend": jax.default_backend(),
    }

    # ---- pipelined end-to-end rates, INTERLEAVED -------------------------
    # The house method (tools/pairedbench.py): single passes round-robin
    # A/B/A/B inside one window, paired per-round ratios — a slow
    # stretch hits both arms equally.
    from tools.pairedbench import (
        best_median_rate,
        paired_ratio_median,
        paired_ratios,
        run_rounds,
    )
    from twtml_tpu.utils.benchloop import _run_once

    finals: dict[str, float] = {}

    def make(name, featurize):
        if config == "logistic":
            model = StreamingLogisticRegressionWithSGD()
        else:
            model = StreamingLinearRegressionWithSGD(
                num_text_features=f_text,
                l2_reg=0.1 if config == "2e18" else 0.0,
            )
        warm = featurize(chunks[0])
        for _ in range(2):
            float(model.step(warm).mse)  # completion-fetch warmup

        def one_pass():
            model.reset()
            dt, last = _run_once(model, featurize, chunks, prefetch=True)
            finals[name] = round(float(last.mse), 3)
            return dt

        return one_pass

    arms = {
        "padded": make("padded", fz_padded),
        "ragged": make("ragged", fz_ragged),
    }
    n = sum(
        c.rows if hasattr(c, "rows") else len(c) for c in chunks
    )  # block chunks count rows, Status chunks count items
    times = run_rounds(arms, budget)
    for name, ts in times.items():
        best, median = best_median_rate(ts, n)
        out[name] = {
            "tweets_per_sec": best,
            "median_tweets_per_sec": median,
            "passes": len(ts),
            "final_mse": finals[name],
        }
    # paired per-round ratios: phase-robust (each pair shares a window)
    out["paired_speedup_median"] = paired_ratio_median(
        times["padded"], times["ragged"]
    )
    out["paired_speedup_all"] = [
        round(x, 3) for x in paired_ratios(times["padded"], times["ragged"])
    ]
    assert out["padded"]["final_mse"] == out["ragged"]["final_mse"], (
        "wire formats diverged — parity violation"
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
