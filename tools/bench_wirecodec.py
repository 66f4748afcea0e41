"""Compressed-wire verdict (ISSUE 12): ``--wireCodec dict`` off vs on,
paired, in the two ingest regimes.

The question: the digram codec (features/wirecodec.py) shrinks the
dominant wire tensor on ASCII tweet text, paying a host encode (one C
pass) and an in-jit gather-expand decode. Does the
byte saving beat the encode cost where upload binds?

Method: the house harness only (tools/pairedbench.py) — interleaved
single passes, paired per-round ratios, parity asserted per round (the
codec may never change the math). Per regime (object / block ingest),
the k=1 packed wire and the K-group coalesced (tenant) wire, each raw
and codec ("codec off/on × stacked/group" — "stacked" here is the
per-batch one-buffer pack; the codec rides packed forms only,
config.effective_wire_pack rejects the contradictory combo). The control
window steps the k=1 wire only; the group wire is timed pack-only.

Each regime answers twice:

- CPU control — the full pipeline (pack → step → completion fetch) on the
  CPU backend. Wire-insensitive by design: this isolates the codec's HOST
  cost (the encode) as a paired ratio ~1x-minus-encode.
- modeled upload-bound transport — paired pack-only passes (the codec's
  only timed host delta) plus EXACT upload arithmetic wire_bytes/BW over
  a MODELED 45-70 MB/s envelope (an upload-bound link: upload is the top
  of the ladder and dispatch/compute overlap underneath it, so serialized
  upload + pack IS the bound in that regime). Deterministic
  bytes x measured pack times — no sleep-granularity noise, no CPU
  device-step compute that a real accelerator would not pay. The on-chip
  re-run of this tool is ROADMAP S3's.

Usage: python tools/bench_wirecodec.py [--regime object|block|both]
       [--tweets N] [--batch B] [--k K] [--budget S]
Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _make_batches(regime: str, n_tweets: int, batch: int):
    """Pre-featurized ragged batches (the wire inputs). Featurize cost is
    identical across arms; what differs — and what each arm's pass times —
    is pack (the codec encode rides it), upload, dispatch, fetch."""
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.sources import SyntheticSource

    feat = Featurizer(now_ms=1785320000000)
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())
    if regime == "object":
        return [
            feat.featurize_batch_ragged(
                statuses[i : i + batch], row_bucket=batch, pre_filtered=True
            )
            for i in range(0, n_tweets, batch)
        ]
    # block ingest: JSONL → native wire parser → columnar blocks →
    # the same ragged batches, zero per-tweet Python objects
    from tools.bench_suite import _status_json
    from twtml_tpu.features import native
    from twtml_tpu.features.blocks import ParsedBlock, iter_row_chunks

    data = (
        "\n".join(json.dumps(_status_json(s)) for s in statuses) + "\n"
    ).encode("utf-8")
    parsed = native.parse_tweet_block_wire(data, 0, 10**9)
    if parsed is None:
        raise SystemExit("block regime needs the native wire parser")
    block = ParsedBlock(*parsed[:4])
    return [
        feat.featurize_parsed_block(b, row_bucket=batch, ragged=True)
        for b in iter_row_chunks([block], batch)
    ]


# MODELED upload-bandwidth envelope (an upload-bound link):
# the modeled verdict is reported across it, never at one cherry-picked
# operating point
UPLOAD_MBS_SWEEP = (45.0, 55.0, 70.0)


def _uniform_groups(batches, k: int):
    """K-groups of signature-matching batches (the group pack's rule).
    Batches sharing the MODAL signature are grouped (the bench's corpus is
    small enough that the data-dependent units bucket can differ batch to
    batch)."""
    from collections import Counter

    sig = lambda b: (b.units.shape, b.units.dtype, b.row_len)  # noqa: E731
    modal, _n = Counter(sig(b) for b in batches).most_common(1)[0]
    same = [b for b in batches if sig(b) == modal]
    groups = [
        same[i : i + k] for i in range(0, len(same) - k + 1, k)
    ]
    if not groups:
        raise SystemExit("no signature-uniform group; raise --tweets")
    return groups


def _control_window(batches, k: int, budget_s: float) -> dict:
    """The CPU-control window: the FULL pipeline (pack → step → one
    completion fetch), the k=1 wire raw and codec, round-robin.
    Every arm trains its OWN model over the same batch sequence each pass
    (arms stay step-for-step comparable because run_rounds completes
    every round); parity is asserted on final mse per window. A light
    step (5 inner iterations) stands in for the device — the CPU default
    of 50 iterations would drown the wire contrast in compute an
    accelerator pays far less for. Identical across arms either way."""
    import jax
    import numpy as np

    from tools.pairedbench import paired_ratio_median, run_rounds
    from twtml_tpu.features.batch import pack_batch
    from twtml_tpu.models import StreamingLinearRegressionWithSGD

    finals: dict[str, float] = {}

    def single_arm(name, codec):
        model = StreamingLinearRegressionWithSGD(num_iterations=5)

        def run():
            t0 = time.perf_counter()
            out = None
            for b in batches:
                out = model.step(pack_batch(b, codec=codec))
            finals[name] = float(np.asarray(jax.device_get(out.mse)))
            return time.perf_counter() - t0

        return run

    arms = {
        "single_raw": single_arm("single_raw", None),
        "single_codec": single_arm("single_codec", "dict"),
    }
    for run in arms.values():  # warmup: compile + completion fetch
        run()
    times = run_rounds(arms, budget_s)
    # parity per window: identical batch sequence → identical final mse
    assert finals["single_raw"] == finals["single_codec"], finals
    return {
        "rounds": len(times["single_raw"]),
        "paired_single_codec_vs_raw": paired_ratio_median(
            times["single_raw"], times["single_codec"]
        ),
        "final_mse": finals["single_raw"],
    }


def _modeled_window(batches, k: int, budget_s: float) -> dict:
    """The modeled upload-bound window: paired PACK-ONLY passes (the
    codec's entire timed host delta — featurize is arm-identical and
    dispatch/compute overlap under upload in the target regime), then
    exact serialized-upload arithmetic wire_bytes/BW across the measured
    45-70 MB/s envelope. Parity of the packed wires themselves is the
    test suite's job (tests/test_wirecodec.py byte-parity)."""
    from tools.pairedbench import paired_ratios, run_rounds
    from twtml_tpu.features.batch import (
        pack_batch, pack_ragged_group, wire_composition, wire_nbytes,
    )
    import statistics

    groups = _uniform_groups(batches, k)
    wire: dict[str, int] = {}

    def single_pack(name, codec):
        def run():
            t0 = time.perf_counter()
            for b in batches:
                w = pack_batch(b, codec=codec)
            wire[name] = wire_nbytes(w)
            return time.perf_counter() - t0

        return run

    def group_pack(name, codec):
        def run():
            t0 = time.perf_counter()
            for g in groups:
                w = pack_ragged_group(g, codec=codec)
            wire[name] = wire_nbytes(w)
            return time.perf_counter() - t0

        return run

    arms = {
        "single_raw": single_pack("single_raw", None),
        "single_codec": single_pack("single_codec", "dict"),
        "group_raw": group_pack("group_raw", None),
        "group_codec": group_pack("group_codec", "dict"),
    }
    for run in arms.values():
        run()  # warmup: page in buffers, build the LUT once
    times = run_rounds(arms, budget_s)

    def modeled(base, arm, n_transfers, mbs):
        # per-round modeled pass time = measured pack pass + exact upload
        up_b = wire[base] * n_transfers / (mbs * 1e6)
        up_a = wire[arm] * n_transfers / (mbs * 1e6)
        return round(statistics.median(paired_ratios(
            [t + up_b for t in times[base]],
            [t + up_a for t in times[arm]],
        )), 3)

    comp = wire_composition(pack_batch(batches[0], codec="dict"))
    rec = {
        "rounds": len(times["single_raw"]),
        "wire_bytes": dict(wire),
        "wire_ratio_single": round(
            wire["single_raw"] / wire["single_codec"], 3
        ),
        "wire_ratio_group": round(
            wire["group_raw"] / wire["group_codec"], 3
        ),
        "units_ratio": (
            round(comp["units"] / comp["units_compressed"], 3)
            if comp.get("units_compressed")
            else 1.0
        ),
        "pack_ms_per_batch": {
            n: round(
                statistics.median(ts) * 1e3 / len(batches), 3
            )
            for n, ts in times.items()
        },
        "paired_upload_bound": {},
    }
    for mbs in UPLOAD_MBS_SWEEP:
        rec["paired_upload_bound"][str(int(mbs))] = {
            "single_codec_vs_raw": modeled(
                "single_raw", "single_codec", len(batches), mbs
            ),
            "group_codec_vs_raw": modeled(
                "group_raw", "group_codec", len(groups), mbs
            ),
        }
    return rec


def measure(
    regime: str = "object", n_tweets: int = 65536, batch: int = 8192,
    k: int = 4, budget_s: float = 60.0,
) -> dict:
    import jax

    batches = _make_batches(regime, n_tweets, batch)
    return {
        "regime": regime, "tweets": n_tweets, "batch": batch, "k": k,
        "backend": jax.devices()[0].platform,
        # the CPU control is wire-insensitive by design: it isolates the
        # codec's host cost (encode + the extra in-program decode)
        "control": _control_window(batches, k, budget_s),
        # the modeled upload-bound verdict across the measured bandwidth
        # envelope: the acceptance regime until an on-chip window
        # re-runs this tool
        "modeled_upload": _modeled_window(batches, k, budget_s),
    }


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
