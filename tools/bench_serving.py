"""Coalesced + pipelined serving vs naive per-request serving, paired.

The regime the serving plane exists for (ISSUE 9): query traffic against a
device-resident snapshot where a host fetch has a latency that dwarfs the
device step (this machine's is not measured; PERF.md). Naive per-request
serving pays that latency PER QUERY; the plane coalesces requests into one
dispatch per batch and pipelines the result fetches at depth K
(``apps/common.FetchPipeline``).

Arms (single passes round-robin in one budget window on the shared
tools/pairedbench.py harness; PAIRED per-round ratios are the verdict):

- naive     : one ServingPlane per-request — batch bucket = the request's
              rows, depth 1, no admission wait: every request is its own
              featurize + dispatch + synchronous fetch (today's cost of a
              query without the plane);
- pipelined : the shipped plane — ``--batchRows`` coalescing bucket,
              ``--serveMaxWaitMs``-style admission wait, depth-``--depth``
              pipelined fetches.

Both arms serve the SAME open-loop load: N requests of R rows each submitted
as fast as possible, a pass completes when every future resolves. Sustained
QPS = N / pass seconds; per-request latencies (submit -> resolve) pool into
p50/p95/p99. An open-loop burst makes the tail latencies queue-dominated —
that is the honest shape of a load test, and the bounded p99 is reported
as such.

``--modelRttMs R`` (default 70) additionally runs BOTH arms with R ms slept
inside every host fetch — a modeled fetch latency for backends where
fetches are free (the CPU control), so the amortization mechanism is
demonstrable without a device. Modeled numbers are labeled and are NEVER a
verdict about a device (measure in the target regime); on the chip run
this tool with ``--modelRttMs 0``.

Usage: python tools/bench_serving.py [--requests N] [--rowsPerRequest R]
       [--batchRows B] [--depth K] [--budget S] [--modelRttMs MS]
       — prints one JSON line.
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


def build_plane(snapshot, *, batch_rows, max_wait_ms, depth, rtt_ms,
                num_text_features=1000):
    """One serving plane arm; ``rtt_ms`` > 0 wraps its fetch with the
    modeled transport RTT (slept in the fetch pool, so depth-K arms
    overlap the sleeps the way concurrent real fetches overlap)."""
    import jax

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.serving.engine import PredictEngine
    from twtml_tpu.serving.plane import ServingPlane

    engine = PredictEngine(
        num_text_features=num_text_features,
        num_tenants=snapshot.num_tenants,
    )
    if rtt_ms > 0:
        def rtt_fetch(out, _get=jax.device_get, _s=rtt_ms / 1e3):
            host = _get(out)
            time.sleep(_s)
            return host

        engine.fetch_output = rtt_fetch
    plane = ServingPlane(
        snapshot,
        num_text_features=num_text_features,
        batch_rows=batch_rows,
        max_wait_ms=max_wait_ms,
        depth=depth,
        featurizer=Featurizer(now_ms=NOW_MS),
        engine=engine,
    )
    return plane.start()


def measure(requests: int = 96, rows_per_request: int = 16,
            batch_rows: int = 256, depth: int = 8, budget: float = 60.0,
            model_rtt_ms: float = 70.0) -> dict:
    import jax
    import numpy as np

    from tools.pairedbench import paired_ratio_median, run_rounds
    from twtml_tpu.serving.snapshot import ServingSnapshot
    from twtml_tpu.streaming.sources import SyntheticSource

    statuses = list(
        SyntheticSource(total=requests * rows_per_request, seed=3).produce()
    )
    loads = [
        statuses[i * rows_per_request:(i + 1) * rows_per_request]
        for i in range(requests)
    ]
    rng = np.random.default_rng(7)
    weights = rng.standard_normal(1004).astype(np.float32) * 1e-3
    snapshot = ServingSnapshot(
        step=1, weights=weights, meta={"quality": {"level": "ok"}}
    )

    arm_specs = {
        "naive": dict(batch_rows=rows_per_request, max_wait_ms=0.0, depth=1,
                      rtt_ms=0.0),
        "pipelined": dict(batch_rows=batch_rows, max_wait_ms=5.0,
                          depth=depth, rtt_ms=0.0),
    }
    if model_rtt_ms > 0:
        arm_specs["naive_rtt"] = dict(
            batch_rows=rows_per_request, max_wait_ms=0.0, depth=1,
            rtt_ms=model_rtt_ms,
        )
        arm_specs["pipelined_rtt"] = dict(
            batch_rows=batch_rows, max_wait_ms=5.0, depth=depth,
            rtt_ms=model_rtt_ms,
        )
    planes = {
        name: build_plane(snapshot, **spec)
        for name, spec in arm_specs.items()
    }
    latencies: "dict[str, list[float]]" = {name: [] for name in planes}
    qps: "dict[str, list[float]]" = {name: [] for name in planes}

    def one_pass(name):
        plane = planes[name]
        lats = []
        t0 = time.perf_counter()
        futs = []
        for load in loads:
            t_sub = time.perf_counter()
            fut = plane.submit(load)
            fut.add_done_callback(
                lambda _f, t=t_sub: lats.append(time.perf_counter() - t)
            )
            futs.append(fut)
        for fut in futs:
            fut.result(timeout=600)
        dt = time.perf_counter() - t0
        latencies[name].extend(lats)
        qps[name].append(requests / dt)
        return dt

    # warm every arm outside the window (compile + first-bucket programs)
    for name in planes:
        one_pass(name)
    for d in (latencies, qps):
        for name in d:
            d[name].clear()

    arms = {name: (lambda n=name: one_pass(n)) for name in planes}
    times = run_rounds(arms, budget)

    def quantiles(values):
        vs = sorted(values)

        def q(p):
            return round(vs[min(len(vs) - 1, int(p * len(vs)))] * 1e3, 2)

        return {"p50_ms": q(0.50), "p95_ms": q(0.95), "p99_ms": q(0.99)}

    out = {
        "regime": "serving",
        "backend": jax.default_backend(),
        "requests": requests,
        "rows_per_request": rows_per_request,
        "batch_rows": batch_rows,
        "depth": depth,
        "modeled_rtt_ms": model_rtt_ms,
        "rounds": len(times["naive"]),
    }
    for name in planes:
        out[name] = {
            "qps_median": round(statistics.median(qps[name]), 1),
            "qps_best": round(max(qps[name]), 1),
            **quantiles(latencies[name]),
        }
    out["pipelined"]["paired_speedup_vs_naive"] = paired_ratio_median(
        times["naive"], times["pipelined"]
    )
    if model_rtt_ms > 0:
        out["pipelined_rtt"]["paired_speedup_vs_naive"] = paired_ratio_median(
            times["naive_rtt"], times["pipelined_rtt"]
        )
    for plane in planes.values():
        plane.stop()
    return out


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    kw = dict(requests=96, rows_per_request=16, batch_rows=256, depth=8,
              budget=60.0, model_rtt_ms=70.0)
    flags = {
        "--requests": ("requests", int),
        "--rowsPerRequest": ("rows_per_request", int),
        "--batchRows": ("batch_rows", int),
        "--depth": ("depth", int),
        "--budget": ("budget", float),
        "--modelRttMs": ("model_rtt_ms", float),
    }
    i = 0
    while i < len(args):
        if args[i] in flags:
            key, cast = flags[args[i]]
            kw[key] = cast(args[i + 1])
            i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")
    print(json.dumps(measure(**kw)))


if __name__ == "__main__":
    main()
