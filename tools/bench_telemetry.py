"""Per-batch-telemetry regime: the fetch strategies, interleaved.

The production apps read the full StepOutput every batch for the stats
plane; where a host fetch costs a round trip that dwarfs the device step,
that caps the back-to-back telemetry-on rate far below the free-dispatch
rate. Arms (single passes round-robin in one window; paired
per-round ratios are the phase-robust comparison):

- sync     : device_get right after each dispatch;
- lag      : one-batch-lag fetch;
- pool8    : concurrent in-order fetches on a thread pool — the mechanism
             FetchPipeline ships;
- fetchpipe: the SHIPPED path end-to-end — apps/common.FetchPipeline over
             the ragged+packed wire, per-batch handler included.

No arm has been run on this machine (PERF.md); the earlier verdicts were
taken elsewhere and are not carried here.

Usage: python tools/bench_telemetry.py [--tweets N] [--batch B] [--budget S]
Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    n_tweets, batch, budget = 65536, 2048, 180.0
    i = 0
    while i < len(args):
        if args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--batch":
            batch = int(args[i + 1]); i += 2
        elif args[i] == "--budget":
            budget = float(args[i + 1]); i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    import jax

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.sources import SyntheticSource

    feat = Featurizer(now_ms=1785320000000)
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())
    chunks = [statuses[i : i + batch] for i in range(0, len(statuses), batch)]
    batches = [
        feat.featurize_batch_units(c, row_bucket=batch, pre_filtered=True)
        for c in chunks
    ]

    def consume(out, b, t, at_boundary=True):
        # what the app handlers do: read every StepOutput field on host
        float(out.count); float(out.mse)
        float(out.real_stdev); float(out.pred_stdev)
        _ = out.predictions[0]

    model = StreamingLinearRegressionWithSGD()
    for _ in range(2):
        float(model.step(batches[0]).mse)  # warm the program

    def sync_pass():
        model.reset()
        t0 = time.perf_counter()
        for b in batches:
            consume(jax.device_get(model.step(b)), b, 0.0)
        return time.perf_counter() - t0

    def lag_pass():
        """One-batch-lag fetch (dispatch k, then fetch k-1; async copy at
        dispatch) — kept as an arm beside the concurrent pool the
        shipped pipeline uses."""
        model.reset()
        pending = None
        t0 = time.perf_counter()
        for b in batches:
            out = model.step(b)
            for leaf in jax.tree_util.tree_leaves(out):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
            if pending is not None:
                consume(jax.device_get(pending[0]), pending[1], 0.0)
            pending = (out, b)
        if pending is not None:
            consume(jax.device_get(pending[0]), pending[1], 0.0)
        return time.perf_counter() - t0

    from concurrent.futures import ThreadPoolExecutor

    def pool_pass(workers=8):
        """Fetch each batch's StepOutput on a thread pool while the main
        thread keeps dispatching; consume in order. If concurrent
        host fetches overlap, N in-flight requests pipeline the fetch
        latency; if the runtime serializes them, this matches sync."""
        model.reset()
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=workers) as pool:
            futs = [
                pool.submit(jax.device_get, model.step(b)) for b in batches
            ]
            for f, b in zip(futs, batches):
                consume(f.result(), b, 0.0)
        return time.perf_counter() - t0

    from twtml_tpu.apps.common import FetchPipeline

    from twtml_tpu.features.batch import pack_batch

    r_batches = [
        feat.featurize_batch_ragged(c, row_bucket=batch, pre_filtered=True)
        for c in chunks
    ]
    # warm the PACKED program the timed arm actually dispatches
    # (pack=True → model.step(pack_batch(b)): a different jit pytree than
    # the raw ragged batch), once per distinct wire layout — the ragged
    # units bucket is data-dependent, so chunks can land in several
    seen_layouts = set()
    for rb in r_batches:
        key = (rb.units.shape, str(rb.units.dtype), rb.row_len)
        if key not in seen_layouts:
            seen_layouts.add(key)
            float(model.step(pack_batch(rb)).mse)
    model.reset()

    def fetchpipe_pass():
        """The shipped back-to-back path verbatim: FetchPipeline (depth 8,
        packed ragged wire) delivering every batch's StepOutput to the
        same handler work as every other arm."""
        model.reset()
        t0 = time.perf_counter()
        pipe = FetchPipeline(model, consume, depth=8, pack=True)
        for b in r_batches:
            pipe.on_batch(b, 0.0)
        pipe.flush()
        return time.perf_counter() - t0

    # the house interleaved/paired scheduling (tools/pairedbench.py)
    from tools.pairedbench import (
        best_median_rate, paired_ratio_median, run_rounds,
    )

    arms = {
        "sync": sync_pass, "lag": lag_pass, "pool8": pool_pass,
        "fetchpipe": fetchpipe_pass,
    }
    times = run_rounds(arms, budget)

    out = {"regime": "per-batch-telemetry", "batch": batch,
           "tweets": n_tweets, "backend": jax.default_backend(),
           "rounds": len(times["sync"])}
    for name, ts in times.items():
        best, median = best_median_rate(ts, n_tweets)
        out[name] = {
            "tweets_per_sec_best": best,
            "tweets_per_sec_median": median,
        }
    for name in ("lag", "pool8", "fetchpipe"):
        out[name]["paired_speedup_vs_sync"] = paired_ratio_median(
            times["sync"], times[name]
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
