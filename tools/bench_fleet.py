"""Aggregate fleet QPS + pooled p99 vs fleet size, paired (ISSUE 11).

The regime the read fleet exists for: open-loop predict traffic through a
front-door router over N serve replicas, where a result fetch has a
latency that dwarfs the device step (the fetch latency of this machine is
not measured; PERF.md). A single replica's throughput ceiling in that
regime is its in-flight fetch budget (``--depth`` pipelined fetches /
fetch latency); a fleet multiplies that budget by N — IF the router and
the host don't bind first. This bench measures which it is.

Arms (single passes round-robin in one budget window on the shared
tools/pairedbench.py harness; PAIRED per-round ratios are the verdict):

- fleet1 / fleet2 / fleet4: a REAL router front door (aiohttp server +
  FleetRouter, policy least-p99) over 1/2/4 in-process replicas — each a
  full ServingPlane behind its own HTTP server, exactly the apps/serve
  stack. Every arm serves the same open-loop load: ``--requests`` requests
  of ``--rowsPerRequest`` rows fired from ``--clients`` threads through
  the router; a pass completes when every response arrives. Aggregate
  QPS = requests / pass seconds; per-request latencies pool into p99.

``--modelRttMs R`` (default 70) runs a second arm set with R ms slept
inside every replica's host fetch — a modeled fetch latency for backends
where fetches are free (the CPU control, which is fetch-unbound and shows
the HOST floor instead). Modeled numbers are labeled and are NEVER a
verdict about a device (measure in the target regime); on the chip run
this with ``--modelRttMs 0``.

Usage: python tools/bench_fleet.py [--requests N] [--rowsPerRequest R]
       [--clients C] [--depth K] [--budget S] [--modelRttMs MS]
       [--sizes 1,2,4] — prints one JSON line.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NOW_MS = 1785320000000


class FleetArm:
    """One fleet size: N replica planes+servers behind a real router
    front door. Built once, reused across rounds (arms own their warmup —
    the pairedbench contract)."""

    def __init__(self, size, *, rows_per_request, depth, rtt_ms, tmp_dir):
        import jax

        from twtml_tpu.features.featurizer import Featurizer
        from twtml_tpu.serving.engine import PredictEngine
        from twtml_tpu.serving.fleet import FleetRouter
        from twtml_tpu.serving.plane import ServingPlane
        from twtml_tpu.serving.snapshot import ServingSnapshot
        from twtml_tpu.web.cache import ApiCache
        from twtml_tpu.web.server import Server

        import numpy as np

        rng = np.random.default_rng(7)
        weights = rng.standard_normal(1004).astype(np.float32) * 1e-3
        snapshot = ServingSnapshot(
            step=1, weights=weights, meta={"quality": {"level": "ok"}}
        )
        self.size = size
        self.planes = []
        self.servers = []
        urls = []
        for i in range(size):
            engine = PredictEngine(num_text_features=1000)
            if rtt_ms > 0:
                def rtt_fetch(out, _get=jax.device_get, _s=rtt_ms / 1e3):
                    host = _get(out)
                    time.sleep(_s)
                    return host

                engine.fetch_output = rtt_fetch
            plane = ServingPlane(
                snapshot,
                num_text_features=1000,
                # one dispatch per request: the per-replica ceiling is then
                # cleanly depth/RTT, which is what fleet size multiplies
                batch_rows=rows_per_request,
                max_wait_ms=0.0,
                depth=depth,
                featurizer=Featurizer(now_ms=NOW_MS),
                engine=engine,
            ).start()
            server = Server(
                port=0, host="127.0.0.1",
                cache=ApiCache(backup_file=os.path.join(
                    tmp_dir, f"replica-{rtt_ms}-{size}-{i}.json"
                )),
            ).attach_serving(plane)
            server.start_background()
            urls.append(f"http://127.0.0.1:{server._runner.addresses[0][1]}")
            self.planes.append(plane)
            self.servers.append(server)
        self.router = FleetRouter(urls, policy="p99", timeout=120.0)
        self.front = Server(
            port=0, host="127.0.0.1",
            cache=ApiCache(backup_file=os.path.join(
                tmp_dir, f"router-{rtt_ms}-{size}.json"
            )),
        ).attach_fleet(self.router)
        self.front.start_background()
        self.url = f"http://127.0.0.1:{self.front._runner.addresses[0][1]}"

    def stop(self):
        self.front.stop()
        self.router.stop()
        for server in self.servers:
            server.stop()
        for plane in self.planes:
            plane.stop()


def measure(requests: int = 192, rows_per_request: int = 16,
            clients: int = 64, depth: int = 4, budget: float = 60.0,
            model_rtt_ms: float = 70.0, sizes=(1, 2, 4)) -> dict:
    import tempfile

    import jax

    from tools.pairedbench import paired_ratio_median, run_rounds
    from twtml_tpu.serving.client import ServingClient
    from twtml_tpu.streaming.sources import SyntheticSource

    statuses = list(
        SyntheticSource(total=requests * rows_per_request, seed=3).produce()
    )
    loads = []
    for i in range(requests):
        chunk = statuses[i * rows_per_request:(i + 1) * rows_per_request]
        loads.append([{
            "text": s.retweeted_status.text,
            "followers_count": s.retweeted_status.followers_count,
            "favourites_count": s.retweeted_status.favourites_count,
            "friends_count": s.retweeted_status.friends_count,
            "created_at_ms": s.retweeted_status.created_at_ms,
            "retweet_count": s.retweeted_status.retweet_count,
        } for s in chunk])

    tmp_dir = tempfile.mkdtemp(prefix="twtml-bench-fleet-")
    rtt_modes = [0.0]
    if model_rtt_ms > 0:
        rtt_modes.append(model_rtt_ms)
    arms_objs: dict[str, FleetArm] = {}
    for rtt in rtt_modes:
        for size in sizes:
            name = f"fleet{size}" + ("_rtt" if rtt > 0 else "")
            arms_objs[name] = FleetArm(
                size, rows_per_request=rows_per_request, depth=depth,
                rtt_ms=rtt, tmp_dir=tmp_dir,
            )
    latencies: dict[str, list] = {n: [] for n in arms_objs}
    qps: dict[str, list] = {n: [] for n in arms_objs}
    pool = ThreadPoolExecutor(max_workers=clients)

    def one_pass(name):
        arm = arms_objs[name]
        client = ServingClient(arm.url, timeout=300.0, retries=0)
        lats = []

        def one(load):
            t_sub = time.perf_counter()
            client.predict(load)
            lats.append(time.perf_counter() - t_sub)

        t0 = time.perf_counter()
        futs = [pool.submit(one, load) for load in loads]
        for fut in futs:
            fut.result(timeout=600)
        dt = time.perf_counter() - t0
        latencies[name].extend(lats)
        qps[name].append(requests / dt)
        return dt

    # warm every arm outside the window (compile + route + first buckets)
    for name in arms_objs:
        one_pass(name)
    for d in (latencies, qps):
        for name in d:
            d[name].clear()

    arms = {name: (lambda n=name: one_pass(n)) for name in arms_objs}
    times = run_rounds(arms, budget)

    def quantiles(values):
        vs = sorted(values)

        def q(p):
            return round(vs[min(len(vs) - 1, int(p * len(vs)))] * 1e3, 2)

        return {"p50_ms": q(0.50), "p99_ms": q(0.99)}

    out = {
        "regime": "fleet",
        "backend": jax.default_backend(),
        "requests": requests,
        "rows_per_request": rows_per_request,
        "clients": clients,
        "depth": depth,
        "modeled_rtt_ms": model_rtt_ms,
        "sizes": list(sizes),
        "rounds": len(times[next(iter(arms_objs))]),
    }
    for name in arms_objs:
        out[name] = {
            "qps_median": round(statistics.median(qps[name]), 1),
            "qps_best": round(max(qps[name]), 1),
            **quantiles(latencies[name]),
        }
    base = f"fleet{sizes[0]}"
    for size in sizes[1:]:
        out[f"fleet{size}"]["paired_speedup_vs_fleet1"] = (
            paired_ratio_median(times[base], times[f"fleet{size}"])
        )
        if model_rtt_ms > 0:
            out[f"fleet{size}_rtt"]["paired_speedup_vs_fleet1"] = (
                paired_ratio_median(
                    times[base + "_rtt"], times[f"fleet{size}_rtt"]
                )
            )
    for arm in arms_objs.values():
        arm.stop()
    pool.shutdown(wait=False)
    return out


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    kw = dict(requests=192, rows_per_request=16, clients=64, depth=4,
              budget=60.0, model_rtt_ms=70.0, sizes=(1, 2, 4))
    flags = {
        "--requests": ("requests", int),
        "--rowsPerRequest": ("rows_per_request", int),
        "--clients": ("clients", int),
        "--depth": ("depth", int),
        "--budget": ("budget", float),
        "--modelRttMs": ("model_rtt_ms", float),
        "--sizes": ("sizes", lambda v: tuple(
            int(x) for x in v.split(",") if x
        )),
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
