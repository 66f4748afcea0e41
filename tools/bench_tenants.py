"""Multi-tenant model plane vs M sequential single-tenant pipelines, paired.

The regime the plane exists for: M scenario models (per-topic / per-language
/ per-A/B-arm) with per-batch telemetry. Today that costs M full pipelines —
M featurize passes, M wires, M dispatches and M host fetches. The tenant stack routes one shared stream
into M models inside ONE jit program with ONE stacked stats fetch per tick.

Arms (single passes round-robin in one budget window on the shared
tools/pairedbench.py harness; PAIRED per-round ratios are the verdict —
sequential arm blocks confound arm with whatever slow phase they land in):

- seq{M}   : M sequential single-tenant passes — pass m featurizes the full
             stream, keeps tenant m's routed rows, and steps its own model
             with a per-batch stats fetch (today's cost of M scenarios:
             M × (featurize + wire + dispatch + fetch));
- mt{M}    : the multi-tenant plane — ONE featurize pass, host routing, one
             stacked wire, one dispatch and ONE stacked fetch per tick
             (TenantStackModel, --wirePack stacked);
- mt{M}_group: same with the coalesced one-buffer tenant wire
             (--wirePack group — the pack_ragged_group reuse).

Both arms deliver every tenant's per-batch stats to the same consume() so
the handler work matches; aggregate tweets/s = stream tweets per wall
second with ALL M tenants served.

``--modelRttMs R`` (default 0) sleeps R ms inside EVERY host fetch of both
arms — a modeled fetch latency for backends where fetches are free (the
CPU control), so the amortization mechanism is demonstrable without a
device. Results with it are labeled ``modeled_rtt_ms`` and are NEVER a
verdict about a device (measure in the target regime before shipping) —
on the chip run this tool with the flag at 0.

Usage: python tools/bench_tenants.py [--tenants M] [--tweets N] [--batch B]
       [--budget S] [--modelRttMs R]   — prints one JSON line.
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
    n_tweets, batch, budget, m_tenants = 65536, 2048, 180.0, 8
    model_rtt_ms = 0.0
    i = 0
    while i < len(args):
        if args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--batch":
            batch = int(args[i + 1]); i += 2
        elif args[i] == "--budget":
            budget = float(args[i + 1]); i += 2
        elif args[i] == "--tenants":
            m_tenants = int(args[i + 1]); i += 2
        elif args[i] == "--modelRttMs":
            model_rtt_ms = float(args[i + 1]); i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    import jax
    import numpy as np

    from twtml_tpu.features.batch import (
        split_batch_tenants, tenant_route_keys,
    )
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.parallel import TenantStackModel
    from twtml_tpu.streaming.sources import SyntheticSource

    feat = Featurizer(now_ms=1785320000000)
    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())
    chunks = [
        statuses[i : i + batch] for i in range(0, len(statuses), batch)
    ]

    def fetch(out):
        # the ONE host fetch per tick, optionally RTT-modeled (see banner)
        host = jax.device_get(out)
        if model_rtt_ms > 0:
            time.sleep(model_rtt_ms / 1e3)
        return host

    def consume(out):
        # per-tenant per-batch handler work, identical in every arm
        float(np.asarray(out.count).sum())
        float(np.asarray(out.mse).sum())

    # ---- sequential arm: M single-tenant pipelines ------------------------
    seq_model = StreamingLinearRegressionWithSGD()

    def featurize(chunk):
        return feat.featurize_batch_ragged(
            chunk, row_bucket=batch, pre_filtered=True
        )

    def seq_pass():
        t0 = time.perf_counter()
        for m in range(m_tenants):
            seq_model.reset()
            for chunk in chunks:
                rb = featurize(chunk)
                part = split_batch_tenants(
                    rb, tenant_route_keys(rb, m_tenants), m_tenants
                )[m]
                consume(fetch(seq_model.step(part)))
        return time.perf_counter() - t0

    # ---- multi-tenant arms ------------------------------------------------
    mt = TenantStackModel(m_tenants, wire_pack="stacked")
    mt_group = TenantStackModel(m_tenants, wire_pack="group")

    def mt_pass(model):
        model.reset()
        t0 = time.perf_counter()
        for chunk in chunks:
            consume(fetch(model.step(featurize(chunk))))
        return time.perf_counter() - t0

    # warm every program (compile + completion fetch outside the window)
    warm = featurize(chunks[0])
    consume(jax.device_get(seq_model.step(
        split_batch_tenants(
            warm, tenant_route_keys(warm, m_tenants), m_tenants
        )[0]
    )))
    consume(jax.device_get(mt.step(warm)))
    consume(jax.device_get(mt_group.step(warm)))

    from tools.pairedbench import (
        best_median_rate, paired_ratio_median, run_rounds,
    )

    arms = {
        f"seq{m_tenants}": seq_pass,
        f"mt{m_tenants}": lambda: mt_pass(mt),
        f"mt{m_tenants}_group": lambda: mt_pass(mt_group),
    }
    times = run_rounds(arms, budget)

    out = {
        "regime": "multi-tenant-telemetry",
        "tenants": m_tenants,
        "batch": batch,
        "tweets": n_tweets,
        "backend": jax.default_backend(),
        "modeled_rtt_ms": model_rtt_ms,
        "rounds": len(times[f"seq{m_tenants}"]),
    }
    for name, ts in times.items():
        best, median = best_median_rate(ts, n_tweets)
        out[name] = {
            "tweets_per_sec_best": best,
            "tweets_per_sec_median": median,
        }
    # the acceptance ratio: M tenants served by one plane vs M sequential
    # single-tenant pipelines, paired per round
    out[f"mt{m_tenants}"]["paired_speedup_vs_seq"] = paired_ratio_median(
        times[f"seq{m_tenants}"], times[f"mt{m_tenants}"]
    )
    out[f"mt{m_tenants}_group"]["paired_speedup_vs_seq"] = (
        paired_ratio_median(
            times[f"seq{m_tenants}"], times[f"mt{m_tenants}_group"]
        )
    )
    out[f"mt{m_tenants}_group"]["paired_vs_stacked"] = paired_ratio_median(
        times[f"mt{m_tenants}"], times[f"mt{m_tenants}_group"]
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
