"""One-pass featurize verdict (ISSUE 15): the featurize stage split into
its sub-stages and paired off/on, on BOTH ingest paths.

The question: where the host chain is featurize-dominated,
which HALF of featurize gates the host — the Python traversals, the
UTF-16 encode, the numeric scaling, or the wire build? This tool
measures the split BEFORE the attack (the r9/r17 honest-miss discipline:
the floor must be a number, not a guess), then renders the paired
verdicts:

- **object regime** — three interleaved arms over the identical Status
  chunks: ``r17`` (the pre-r18 call sequence recreated from the same
  building blocks: filtrate comprehension, originals comprehension,
  per-text ascii/lower loop, encode, numpy wire build, fromiter
  numeric/label/mask — byte parity asserted against the live path),
  ``truth`` (``--featurizeNative off``: the r18 one-traversal gather +
  numpy array passes), ``fused`` (``on``: gather + the one-pass C fill
  into an arena lease). ``paired_fused_vs_r17`` is the acceptance
  number (target >= 2x); fused-vs-truth isolates the C fill,
  truth-vs-r17 isolates the traversal collapse.
- **block regime** — the full host chain (raw JSONL bytes -> native wire
  parse -> featurize -> packed wire, the production block path) off vs
  on paired (target >= 1.4x), plus a featurize-stage-only window. The
  block ``off`` path IS the r17 path (unchanged numpy passes), so two
  arms suffice.
- **sub-stages** — per-arm median ms of the featurize sub-stage clock
  (featurizer.last_substages: encode / numeric / wire_build; the fused
  arm reports its C fill under wire_build), so the ladder names the
  dominator.

Method: the house harness only (tools/pairedbench.py) — interleaved
single passes, paired per-round ratios; batch parity asserted per window
(featurize may never change the batch).

Usage: python tools/bench_featurize.py [--regime object|block|both]
       [--tweets N] [--batch B] [--budget S]
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

NOW_MS = 1785320000000


def _statuses(n_tweets: int):
    from twtml_tpu.streaming.sources import SyntheticSource

    return list(SyntheticSource(total=n_tweets, seed=3).produce())


def _block_data(statuses) -> bytes:
    from tools.bench_suite import _status_json

    return (
        "\n".join(json.dumps(_status_json(s)) for s in statuses) + "\n"
    ).encode("utf-8")


def _r17_featurize(feat, statuses, row_bucket: int, stages: dict):
    """The pre-r18 object featurize, recreated from the SAME building
    blocks the live path still uses (encode_texts, ragged_wire_arrays,
    the fromiter numeric/label/mask) — the paired baseline arm, with its
    own sub-stage clock. Byte parity vs the live path is asserted once
    per window, so this recreation cannot drift silently."""
    import itertools

    import numpy as np

    from twtml_tpu.features import native
    from twtml_tpu.features.batch import (
        NUM_NUMBER_FEATURES,
        RaggedUnitBatch,
        ragged_wire_arrays,
    )
    from twtml_tpu.features.featurizer import _NUMERIC_COLS, AGE_SCALE, COUNT_SCALE

    t0 = time.perf_counter()
    keep = [s for s in statuses if feat.filtrate(s)]
    t1 = time.perf_counter()
    stages["filter"] += t1 - t0
    originals = [s.retweeted_status for s in keep]
    all_ascii = True
    texts = []
    for o in originals:
        t = o.text
        if not t.isascii():
            t = t.lower()
            all_ascii = False
        texts.append(t)
    units, offsets = native.encode_texts(texts)
    lengths = np.diff(offsets).astype(np.int32)
    t2 = time.perf_counter()
    stages["encode"] += t2 - t1
    n = len(keep)
    b, lu = feat._unit_batch_shape(n, lengths, row_bucket, 0, 1)
    flat, offs = ragged_wire_arrays(units, offsets, n, b, narrow=all_ascii)
    t3 = time.perf_counter()
    stages["wire_build"] += t3 - t2
    numeric = np.zeros((b, NUM_NUMBER_FEATURES), dtype=np.float32)
    label = np.zeros((b,), dtype=np.float32)
    mask = np.zeros((b,), dtype=np.float32)
    if n:
        cols = np.fromiter(
            itertools.chain.from_iterable(map(_NUMERIC_COLS, originals)),
            np.float64, n * 5,
        ).reshape(n, 5)
        numeric[:n, :3] = cols[:, :3] * COUNT_SCALE
        numeric[:n, 3] = (NOW_MS - cols[:, 3]) * AGE_SCALE
        label[:n] = cols[:, 4]
        mask[:n] = 1.0
    stages["numeric"] += time.perf_counter() - t3
    return RaggedUnitBatch(flat, offs, numeric, label, mask, row_len=lu)


def _retire(batch) -> None:
    lease = getattr(batch, "_lease", None)
    if lease is not None:
        lease.retire()  # featurize-only window: nothing is in flight


def _assert_same_batch(a, b, tag: str) -> None:
    import numpy as np

    for f in ("units", "offsets", "numeric", "label", "mask"):
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype and np.array_equal(x, y), (
            f"featurize path diverged: {tag}.{f}"
        )
    assert a.row_len == b.row_len, (tag, a.row_len, b.row_len)


def _substage_ms(samples: "dict[str, list[float]]") -> dict:
    return {
        name: round(statistics.median(ts) * 1e3, 3)
        for name, ts in samples.items()
        if ts
    }


def _object_window(statuses, batch: int, budget_s: float) -> dict:
    from tools.pairedbench import paired_ratio_median, run_rounds
    from twtml_tpu.features import featurize_native as ffz
    from twtml_tpu.features.featurizer import Featurizer

    feat = Featurizer(now_ms=NOW_MS)
    chunks = [
        statuses[i : i + batch] for i in range(0, len(statuses), batch)
    ]
    r17_stages = {"filter": 0.0, "encode": 0.0, "numeric": 0.0,
                  "wire_build": 0.0}
    subs: "dict[str, dict[str, list[float]]]" = {
        "truth": {}, "fused": {}, "r17": {}
    }

    def record_subs(arm: str) -> None:
        agg: "dict[str, float]" = {}
        for name, _t0, dur in feat.last_substages:
            agg[name] = agg.get(name, 0.0) + dur
        for name, dur in agg.items():
            subs[arm].setdefault(name, []).append(dur)

    def arm_r17():
        for k in r17_stages:
            r17_stages[k] = 0.0
        t0 = time.perf_counter()
        for c in chunks:
            _r17_featurize(feat, c, batch, r17_stages)
        dt = time.perf_counter() - t0
        for k, v in r17_stages.items():
            subs["r17"].setdefault(k, []).append(v)
        return dt

    def arm(mode, name):
        def run():
            with ffz.forced(mode):
                t0 = time.perf_counter()
                per_sub: "dict[str, float]" = {}
                for c in chunks:
                    b = feat.featurize_batch_ragged(c, row_bucket=batch)
                    for sname, _st, dur in feat.last_substages:
                        per_sub[sname] = per_sub.get(sname, 0.0) + dur
                    _retire(b)
                dt = time.perf_counter() - t0
            for sname, dur in per_sub.items():
                subs[name].setdefault(sname, []).append(dur)
            return dt

        return run

    # parity: the r17 recreation and both live modes emit identical batches
    ref = _r17_featurize(feat, chunks[0], batch, dict(r17_stages))
    with ffz.forced("off"):
        _assert_same_batch(
            ref, feat.featurize_batch_ragged(chunks[0], row_bucket=batch),
            "truth",
        )
    with ffz.forced("on"):
        got = feat.featurize_batch_ragged(chunks[0], row_bucket=batch)
        _assert_same_batch(ref, got, "fused")
        _retire(got)

    arms = {"r17": arm_r17, "truth": arm("off", "truth"),
            "fused": arm("on", "fused")}
    for run in arms.values():
        run()  # warmup: page in, fill the arena pool
    for v in subs.values():
        v.clear()
    times = run_rounds(arms, budget_s)
    n_valid = sum(
        1 for c in chunks for s in c if feat.filtrate(s)
    )
    med = statistics.median(times["fused"])
    return {
        "rounds": len(times["r17"]),
        "tweets_per_pass": len(statuses),
        "paired_fused_vs_r17": paired_ratio_median(
            times["r17"], times["fused"]
        ),
        "paired_fused_vs_truth": paired_ratio_median(
            times["truth"], times["fused"]
        ),
        "paired_truth_vs_r17": paired_ratio_median(
            times["r17"], times["truth"]
        ),
        "featurize_ms_median": {
            n: round(statistics.median(ts) * 1e3, 2)
            for n, ts in times.items()
        },
        "tweets_per_sec_fused": round(n_valid / med, 1) if med else None,
        "substage_ms": {k: _substage_ms(v) for k, v in subs.items()},
    }


def _block_window(data: bytes, batch: int, budget_s: float) -> dict:
    """Block regime: featurize-stage window + the full host chain (bytes
    -> native wire parse -> featurize -> packed wire), off vs on."""
    from tools.pairedbench import paired_ratio_median, run_rounds
    from twtml_tpu.features import featurize_native as ffz
    from twtml_tpu.features import native
    from twtml_tpu.features.batch import pack_batch
    from twtml_tpu.features.blocks import ParsedBlock, iter_row_chunks
    from twtml_tpu.features.featurizer import Featurizer

    feat = Featurizer(now_ms=NOW_MS)
    parsed = native.parse_tweet_block_wire(data, 0, 10**9)
    if parsed is None:
        raise SystemExit("block regime needs the native wire parser")
    block = ParsedBlock(*parsed[:4])
    blocks = list(iter_row_chunks([block], batch))
    subs: "dict[str, dict[str, list[float]]]" = {"truth": {}, "fused": {}}

    def featurize_only(mode, name):
        def run():
            with ffz.forced(mode):
                t0 = time.perf_counter()
                per_sub: "dict[str, float]" = {}
                for blk in blocks:
                    b = feat.featurize_parsed_block(
                        blk, row_bucket=batch, ragged=True
                    )
                    for sname, _st, dur in feat.last_substages:
                        per_sub[sname] = per_sub.get(sname, 0.0) + dur
                    _retire(b)
                dt = time.perf_counter() - t0
            for sname, dur in per_sub.items():
                subs[name].setdefault(sname, []).append(dur)
            return dt

        return run

    def chain(mode):
        def run():
            with ffz.forced(mode):
                t0 = time.perf_counter()
                p = native.parse_tweet_block_wire(data, 0, 10**9)
                blk_all = ParsedBlock(*p[:4])
                for blk in iter_row_chunks([blk_all], batch):
                    fb = feat.featurize_parsed_block(
                        blk, row_bucket=batch, ragged=True
                    )
                    pb = pack_batch(fb)
                    lease = getattr(pb, "_lease", None)
                    if lease is not None:
                        lease.retire()
                    _retire(fb)
                return time.perf_counter() - t0

        return run

    # parity per window
    import numpy as np  # noqa: F401

    with ffz.forced("off"):
        ref = feat.featurize_parsed_block(
            blocks[0], row_bucket=batch, ragged=True
        )
    with ffz.forced("on"):
        got = feat.featurize_parsed_block(
            blocks[0], row_bucket=batch, ragged=True
        )
        _assert_same_batch(ref, got, "block")
        _retire(got)

    f_arms = {"truth": featurize_only("off", "truth"),
              "fused": featurize_only("on", "fused")}
    c_arms = {"truth": chain("off"), "fused": chain("on")}
    for run in (*f_arms.values(), *c_arms.values()):
        run()
    for v in subs.values():
        v.clear()
    f_times = run_rounds(f_arms, budget_s / 2)
    c_times = run_rounds(c_arms, budget_s / 2)
    rows = sum(b.rows for b in blocks)
    med = statistics.median(c_times["fused"])
    return {
        "rounds": len(f_times["truth"]),
        "rows_per_pass": rows,
        "paired_featurize_fused_vs_truth": paired_ratio_median(
            f_times["truth"], f_times["fused"]
        ),
        "paired_chain_fused_vs_truth": paired_ratio_median(
            c_times["truth"], c_times["fused"]
        ),
        "featurize_ms_median": {
            n: round(statistics.median(ts) * 1e3, 2)
            for n, ts in f_times.items()
        },
        "chain_ms_median": {
            n: round(statistics.median(ts) * 1e3, 2)
            for n, ts in c_times.items()
        },
        "chain_tweets_per_sec_fused": round(rows / med, 1) if med else None,
        "substage_ms": {k: _substage_ms(v) for k, v in subs.items()},
    }


def measure(
    regime: str, n_tweets: int, batch: int, budget_s: float
) -> dict:
    from twtml_tpu.features import featurize_native as ffz

    statuses = _statuses(n_tweets)
    rec: dict = {
        "regime": regime, "tweets": n_tweets, "batch": batch,
        "featurize_native_available": ffz.available(),
    }
    if regime == "object":
        rec["object"] = _object_window(statuses, batch, budget_s)
    else:
        rec["block"] = _block_window(_block_data(statuses), batch, budget_s)
    return rec


def main() -> None:
    args = sys.argv[1:]

    def opt(name, default, cast):
        if name in args:
            return cast(args[args.index(name) + 1])
        return default

    regime = opt("--regime", "both", str)
    n_tweets = opt("--tweets", 65536, int)
    batch = opt("--batch", 8192, int)
    budget = opt("--budget", 60.0, float)
    regimes = ["object", "block"] if regime == "both" else [regime]
    out = [measure(r, n_tweets, batch, budget) for r in regimes]
    print(json.dumps(out if len(out) > 1 else out[0]))


if __name__ == "__main__":
    main()
