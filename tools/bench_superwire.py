"""Lean wire v2 verdict: coalesced one-buffer superbatch wire vs stacked.

The question (ISSUE 3): ``--superBatch K`` stacks the ragged wire as K
per-field arrays — K small puts — where one LARGE coalesced put pays the
per-transfer cost once. ``--wirePack group``
(features/batch.pack_ragged_group) composes them: one contiguous buffer
per K batches, uint16-delta offsets, unpacked inside the scanned program.

Verdict comes from the house method only (tools/pairedbench.py):
interleaved single passes + paired per-round ratios, in two regimes —

- telemetry  : the per-batch-telemetry regime (f_text=1000,
               the SuperBatcher path end-to-end, per-batch handler work
               included — the regime where the wire binds);
- 2e18       : config #4 at its b1024 operating point (Gram-domain,
               device-heavy; if coalescing is negative here it must ship
               flag-off for this config — measure in the target regime).

Each regime also reports the wire accounting directly: bytes per group on
both layouts and the offset bytes the uint16-delta sideband deletes.

Usage: python tools/bench_superwire.py [--regime telemetry|2e18|both]
       [--tweets N] [--batch B] [--k K] [--budget S]
Prints one JSON line. Parity is asserted per round (identical final mse
across arms — the wire may never change the math).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _regime(
    name: str, f_text: int, l2: float, int8, batch: int, k: int,
    n_tweets: int, budget: float,
) -> dict:
    import jax

    from tools.pairedbench import (
        best_median_rate, paired_ratio_median, run_rounds,
    )
    from twtml_tpu.apps.common import SuperBatcher
    from twtml_tpu.features.batch import (
        pack_ragged_group, wire_composition, wire_nbytes,
    )
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.sources import SyntheticSource

    feat = Featurizer(num_text_features=f_text, now_ms=1785320000000)
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())
    chunks = [
        statuses[i : i + batch] for i in range(0, len(statuses), batch)
    ]
    batches = [
        feat.featurize_batch_ragged(c, row_bucket=batch, pre_filtered=True)
        for c in chunks
    ]

    def consume(out, b, t, at_boundary=True):
        # the app handlers' per-batch work: read every StepOutput field
        float(out.count); float(out.mse)
        float(out.real_stdev); float(out.pred_stdev)
        _ = out.predictions[0]

    # ---- wire accounting on the first full group -------------------------
    head = batches[: min(k, len(batches))]
    sig0 = (head[0].units.shape, str(head[0].units.dtype), head[0].row_len)
    same_sig = [
        b for b in head
        if (b.units.shape, str(b.units.dtype), b.row_len) == sig0
    ]
    stacked_bytes = sum(wire_nbytes(b) for b in same_sig)
    grouped = pack_ragged_group(same_sig)
    comp = wire_composition(same_sig[0])
    out = {
        "batch": batch,
        "k": k,
        "group_batches_sampled": len(same_sig),
        "stacked_wire_bytes_per_group": stacked_bytes,
        "coalesced_wire_bytes_per_group": int(grouped.buffer.nbytes),
        "offsets_bytes_per_batch_i32": comp["offsets"],
        "offsets_bytes_per_batch_u16delta": wire_composition(grouped)[
            "offsets"
        ] // len(same_sig),
    }

    finals: dict = {}

    def make_arm(mode):
        model = StreamingLinearRegressionWithSGD(
            num_text_features=f_text, l2_reg=l2, gram_int8=int8
        )

        def one_pass():
            model.reset()
            t0 = time.perf_counter()
            sb = SuperBatcher(
                model, k, consume, fetch_depth=4, wire_pack=mode
            )
            for rb in batches:
                sb.on_batch(rb, 0.0)
            sb.flush()
            dt = time.perf_counter() - t0
            finals[mode] = round(float(model.latest_weights.sum()), 6)
            return dt

        one_pass()  # warm every program this arm dispatches (per layout)
        return one_pass

    arms = {"stacked": make_arm("stacked"), "group": make_arm("group")}
    times = run_rounds(arms, budget)
    for mode, ts in times.items():
        best, median = best_median_rate(ts, n_tweets)
        out[mode] = {
            "tweets_per_sec_best": best,
            "tweets_per_sec_median": median,
            "passes": len(ts),
        }
    out["paired_group_vs_stacked"] = paired_ratio_median(
        times["stacked"], times["group"]
    )
    assert finals["stacked"] == finals["group"], (
        "wire layouts diverged — parity violation"
    )
    out["backend"] = jax.default_backend()
    return out


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    regime, n_tweets, budget, k = "both", 65536, 120.0, 8
    batch = 0  # per-regime default below
    i = 0
    while i < len(args):
        if args[i] == "--regime":
            regime = args[i + 1]; i += 2
        elif args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--batch":
            batch = int(args[i + 1]); i += 2
        elif args[i] == "--k":
            k = int(args[i + 1]); i += 2
        elif args[i] == "--budget":
            budget = float(args[i + 1]); i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")
    if regime not in ("telemetry", "2e18", "both"):
        raise SystemExit(f"unknown --regime {regime!r}")

    out = {"bench": "superwire"}
    per = budget / (2 if regime == "both" else 1)
    if regime in ("telemetry", "both"):
        # the per-batch-telemetry regime: f_text=1000, b2048 (the telemetry
        # operating point the fetch-pipeline/superbatch record uses)
        out["telemetry"] = _regime(
            "telemetry", 1000, 0.0, None, batch or 2048, k, n_tweets, per
        )
    if regime in ("2e18", "both"):
        # config #4 at its r3 operating point (b1024, Gram-domain int8)
        out["2e18"] = _regime(
            "2e18", 2**18, 0.1, True, batch or 1024, k, n_tweets, per
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
