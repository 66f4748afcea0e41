"""One-pass wire assembly verdict (ISSUE 14): ``--wireAssemble`` off vs
on, paired, on the host chain the r2/r3 ladder says to shrink.

The question: the numpy pack pipeline touches the wire bytes 3-5 times on
the ONE usable host core (stack/contiguous copies, offsets→deltas, codec
encode, final concatenate); the fused C emitter
(native/wireassemble.cpp) lays the FINAL buffer down in one sweep into a
pooled arena lease. How much host does that buy — on the pack stage
alone, and diluted across the full host chain (bytes → packed wire)?

Method: the house harness only (tools/pairedbench.py) — interleaved
single passes, paired per-round ratios (each pair shares one window),
byte parity asserted per window (the assembler may never change
the wire). Three windows per regime (object / block ingest):

- **pack stage** — pack-only passes (k=1 flat + K-group coalesced),
  numpy vs fused: the assembler's whole timed delta. Target ≥1.5×.
- **host chain** — the full host side (block: raw JSONL bytes → native
  wire parse → featurize → pack; object: Status list → featurize →
  pack), numpy vs fused: the production dilution. Target ≥1.25×, with
  the honest-miss floor being featurize+parse (arm-identical work the
  assembler cannot touch).
- **CPU control + modeled upload** — the chain ratio is wire-neutral by
  construction (identical bytes both arms), so the modeled window adds
  EXACT upload arithmetic wire_bytes/BW across a MODELED 45-70 MB/s
  envelope to show the end-to-end dilution an upload-bound link would pay.

Pack-only arms retire each lease immediately (nothing is in flight), so
the arms measure the steady state: recycled arena buffers, zero fresh
allocations after warmup.

Usage: python tools/bench_wireassemble.py [--regime object|block|both]
       [--tweets N] [--batch B] [--k K] [--budget S]
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

# MODELED upload-bandwidth envelope (an upload-bound link; not a property
# of any machine the ledger runs on)
UPLOAD_MBS_SWEEP = (45.0, 55.0, 70.0)


def _statuses(n_tweets: int):
    from twtml_tpu.streaming.sources import SyntheticSource

    return list(SyntheticSource(total=n_tweets, seed=3).produce())


def _block_data(statuses) -> bytes:
    from tools.bench_suite import _status_json

    return (
        "\n".join(json.dumps(_status_json(s)) for s in statuses) + "\n"
    ).encode("utf-8")


def _featurize_object(statuses, batch):
    from twtml_tpu.features.featurizer import Featurizer

    feat = Featurizer(now_ms=1785320000000)
    return [
        feat.featurize_batch_ragged(
            statuses[i : i + batch], row_bucket=batch, pre_filtered=True
        )
        for i in range(0, len(statuses), batch)
    ]


def _featurize_block(data: bytes, batch):
    from twtml_tpu.features import native
    from twtml_tpu.features.blocks import ParsedBlock, iter_row_chunks
    from twtml_tpu.features.featurizer import Featurizer

    feat = Featurizer(now_ms=1785320000000)
    parsed = native.parse_tweet_block_wire(data, 0, 10**9)
    if parsed is None:
        raise SystemExit("block regime needs the native wire parser")
    block = ParsedBlock(*parsed[:4])
    return [
        feat.featurize_parsed_block(b, row_bucket=batch, ragged=True)
        for b in iter_row_chunks([block], batch)
    ]


def _uniform_groups(batches, k: int):
    from collections import Counter

    sig = lambda b: (b.units.shape, b.units.dtype, b.row_len)  # noqa: E731
    modal, _n = Counter(sig(b) for b in batches).most_common(1)[0]
    same = [b for b in batches if sig(b) == modal]
    groups = [same[i : i + k] for i in range(0, len(same) - k + 1, k)]
    if not groups:
        raise SystemExit("no signature-uniform group; raise --tweets")
    return groups


def _retire(pb) -> None:
    lease = getattr(pb, "_lease", None)
    if lease is not None:
        lease.retire()  # pack-only: nothing is in flight


def _assert_parity(batches, groups) -> None:
    """The assembler may never change the wire: byte + layout parity of
    both pack forms, asserted once per window."""
    import numpy as np

    from twtml_tpu.features import assemble
    from twtml_tpu.features.batch import pack_batch, pack_ragged_group

    for fn in (
        lambda: pack_batch(batches[0]),
        lambda: pack_ragged_group(groups[0]),
    ):
        with assemble.forced("off"):
            ref = fn()
        with assemble.forced("on"):
            got = fn()
        assert got.layout == ref.layout, "assembled layout diverged"
        assert np.array_equal(got.buffer, ref.buffer), (
            "assembled wire bytes diverged"
        )


def _pack_window(batches, groups, budget_s: float) -> dict:
    """Pack-stage-only window: numpy vs fused over the identical batch
    sequence (k=1 flat packs + K-group coalesced packs per pass), raw and
    codec wires. The floor the honest-miss rule measures against: the
    fused pass is ONE memcpy of the wire bytes (source fields → packed
    destination — the minimum any pack can do), so ``memcpy_floor_s`` is
    that byte volume at the host's measured copy bandwidth, taken from
    the fastest fused pass."""
    from tools.pairedbench import paired_ratio_median, run_rounds
    from twtml_tpu.features import assemble
    from twtml_tpu.features.batch import (
        pack_batch, pack_ragged_group, wire_nbytes,
    )

    pass_bytes = {"n": 0}

    def arm(mode, codec):
        def run():
            with assemble.forced(mode):
                t0 = time.perf_counter()
                total = 0
                for b in batches:
                    pb = pack_batch(b, codec=codec)
                    total += wire_nbytes(pb)
                    _retire(pb)
                for g in groups:
                    pb = pack_ragged_group(g, codec=codec)
                    total += wire_nbytes(pb)
                    _retire(pb)
                pass_bytes["n"] = total
                return time.perf_counter() - t0

        return run

    arms = {
        "numpy_raw": arm("off", None),
        "fused_raw": arm("on", None),
        "numpy_codec": arm("off", "dict"),
        "fused_codec": arm("on", "dict"),
    }
    for run in arms.values():
        run()  # warmup: page in, fill the arena pool, build the LUT
    times = run_rounds(arms, budget_s)
    return {
        "rounds": len(times["numpy_raw"]),
        "paired_fused_vs_numpy_raw": paired_ratio_median(
            times["numpy_raw"], times["fused_raw"]
        ),
        "paired_fused_vs_numpy_codec": paired_ratio_median(
            times["numpy_codec"], times["fused_codec"]
        ),
        "pack_ms_median": {
            n: round(statistics.median(ts) * 1e3, 3)
            for n, ts in times.items()
        },
        "wire_bytes_per_pass": pass_bytes["n"],
        # the one-copy floor: the fastest fused raw pass IS a single
        # memcpy of the wire plus call overhead — the denominator of any
        # honest pack-ratio ceiling claim
        "memcpy_floor_s": round(min(times["fused_raw"]), 5),
    }


def _chain_window(
    regime: str, statuses, data, batch: int, k: int, budget_s: float
) -> dict:
    """Full-host-chain window: bytes (or Status objects) → featurize →
    packed wire, numpy vs fused — the production dilution of the pack win,
    plus the modeled upload-bound ratios (identical wire bytes both arms,
    so upload only DILUTES; the envelope shows by how much)."""
    from tools.pairedbench import paired_ratio_median, paired_ratios, run_rounds
    from twtml_tpu.features import assemble
    from twtml_tpu.features.batch import pack_ragged_group, wire_nbytes

    wire_bytes = {"n": 0}

    def one_pass():
        batches = (
            _featurize_object(statuses, batch)
            if regime == "object"
            else _featurize_block(data, batch)
        )
        groups = _uniform_groups(batches, k)
        total = 0
        for g in groups:
            pb = pack_ragged_group(g)
            total += wire_nbytes(pb)
            _retire(pb)
        wire_bytes["n"] = total
        return len(groups)

    def arm(mode):
        def run():
            with assemble.forced(mode):
                t0 = time.perf_counter()
                n_groups = one_pass()
                dt = time.perf_counter() - t0
            return dt, n_groups

        return run

    arms = {"numpy": arm("off"), "fused": arm("on")}
    for run in arms.values():
        run()
    times = run_rounds(arms, budget_s)
    rec = {
        "rounds": len(times["numpy"]),
        "paired_fused_vs_numpy": paired_ratio_median(
            times["numpy"], times["fused"]
        ),
        "chain_s_median": {
            n: round(statistics.median(ts), 4) for n, ts in times.items()
        },
        "wire_bytes_per_pass": wire_bytes["n"],
        "paired_upload_bound": {},
    }
    for mbs in UPLOAD_MBS_SWEEP:
        up = wire_bytes["n"] / (mbs * 1e6)
        rec["paired_upload_bound"][str(int(mbs))] = round(
            statistics.median(paired_ratios(
                [t + up for t in times["numpy"]],
                [t + up for t in times["fused"]],
            )), 3,
        )
    return rec


def measure(
    regime: str, n_tweets: int, batch: int, k: int, budget_s: float
) -> dict:
    import jax

    from twtml_tpu.features import assemble
    from twtml_tpu.features.arena import get_arena
    from twtml_tpu.telemetry import metrics as _metrics

    statuses = _statuses(n_tweets)
    data = _block_data(statuses) if regime == "block" else b""
    batches = (
        _featurize_object(statuses, batch)
        if regime == "object"
        else _featurize_block(data, batch)
    )
    groups = _uniform_groups(batches, k)
    _assert_parity(batches, groups)
    rec = {
        "regime": regime, "tweets": n_tweets, "batch": batch, "k": k,
        "backend": jax.devices()[0].platform,
        "assembler_available": assemble.available(),
        "pack_stage": _pack_window(batches, groups, budget_s),
        "host_chain": _chain_window(
            regime, statuses, data, batch, k, budget_s
        ),
        "arena": get_arena().stats(),
        "assembled_native_packs": _metrics.get_registry().counter(
            "wire.assembled_native"
        ).snapshot(),
    }
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
    k = opt("--k", 4, int)
    budget = opt("--budget", 60.0, float)
    regimes = ["object", "block"] if regime == "both" else [regime]
    out = [measure(r, n_tweets, batch, k, budget) for r in regimes]
    print(json.dumps(out if len(out) > 1 else out[0]))


if __name__ == "__main__":
    main()
