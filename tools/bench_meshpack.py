"""Mesh-packed ragged wire: paired packed-vs-unpacked on the SHARDED model
(a one-buffer win must be measured, not assumed, on the mesh path that
ships it).

Arms (single passes round-robin in one window; the phase-robust comparison
is the paired per-round ratio):

- unpacked: ``model.step(ragged_batch)`` — the shard-aligned ragged arrays
  placed per step (4 host arrays on the wire);
- packed:   ``model.step(model.pack_for_wire(ragged_batch))`` — the shipped
  default: one per-shard-segmented buffer, row-sharded over the data axis.

Both arms pay their full host cost in-loop (alignment, packing, placement),
exactly as the app does; final-batch mse is asserted bit-identical between
arms every round.

Two regimes matter (run both, record both):
- the TPU backend (`--devices 1` or the host's chips): this drives
  `ParallelSGDModel.pack_for_wire`'s exact code over real host→device
  transfers;
- the 8-device CPU mesh (`--cpu --devices 8`, a virtual-device switch like
  the test conftest's): local transfers are ~free, so neutral is the
  expected honest result — the mesh pack is transfer-motivated, and this
  arm bounds its local-backend overhead.

Usage: python tools/bench_meshpack.py [--devices N] [--tweets N] [--batch B]
       [--budget S] [--cpu]
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
    n_tweets, batch, budget, devices, cpu = 65536, 16384, 240.0, 1, False
    i = 0
    while i < len(args):
        if args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--batch":
            batch = int(args[i + 1]); i += 2
        elif args[i] == "--budget":
            budget = float(args[i + 1]); i += 2
        elif args[i] == "--devices":
            devices = int(args[i + 1]); i += 2
        elif args[i] == "--cpu":
            cpu = True; i += 1
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    if cpu:
        from twtml_tpu.utils import force_virtual_cpu_devices

        if not force_virtual_cpu_devices(devices):
            raise SystemExit("--cpu: a backend is already initialized")

    import jax

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.parallel import ParallelSGDModel, make_mesh
    from twtml_tpu.streaming.sources import SyntheticSource

    if len(jax.devices()) < devices:
        raise SystemExit(
            f"--devices {devices} but only {len(jax.devices())} present"
        )
    mesh = make_mesh(num_data=devices)

    feat = Featurizer(now_ms=1785320000000)
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())
    chunks = [statuses[i : i + batch] for i in range(0, len(statuses), batch)]
    r_batches = [
        feat.featurize_batch_ragged(c, row_bucket=batch, pre_filtered=True)
        for c in chunks
    ]

    import numpy as np

    from twtml_tpu.models.sgd import NUM_NUMBER_FEATURES

    model = ParallelSGDModel(mesh)
    zeros = np.zeros(
        (model.num_text_features + NUM_NUMBER_FEATURES,), np.float32
    )

    def unpacked_pass():
        model.set_initial_weights(zeros)
        for rb in r_batches:
            out = model.step(rb)
        return float(out.mse)

    def packed_pass():
        model.set_initial_weights(zeros)
        for rb in r_batches:
            out = model.step(model.pack_for_wire(rb))
        return float(out.mse)

    mse_u = unpacked_pass()  # warm both programs (per ragged layout the
    mse_p = packed_pass()    # corpus produces)
    if mse_u != mse_p:
        raise SystemExit(f"arms diverge: unpacked {mse_u} packed {mse_p}")

    t_unpacked, t_packed = [], []
    t_end = time.perf_counter() + budget
    while time.perf_counter() < t_end:
        t0 = time.perf_counter(); mu = unpacked_pass()
        t1 = time.perf_counter(); mp = packed_pass()
        t2 = time.perf_counter()
        if mu != mp:
            raise SystemExit(f"arms diverge: unpacked {mu} packed {mp}")
        t_unpacked.append(t1 - t0)
        t_packed.append(t2 - t1)

    out = {
        "regime": "mesh-packed ragged wire", "devices": devices,
        "batch": batch, "tweets": n_tweets,
        "backend": jax.default_backend(), "rounds": len(t_unpacked),
        "final_mse_bit_identical": True,
    }
    for name, ts in (("unpacked", t_unpacked), ("packed", t_packed)):
        out[name] = {
            "tweets_per_sec_best": round(n_tweets / min(ts), 1),
            "tweets_per_sec_median": round(n_tweets / statistics.median(ts), 1),
        }
    out["packed"]["paired_speedup_vs_unpacked"] = round(
        statistics.median([u / p for u, p in zip(t_unpacked, t_packed)]), 3
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
