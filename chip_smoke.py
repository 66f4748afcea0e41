#!/usr/bin/env python3
"""Chip smoke: does the system still start, train and serve on the TPU?

Drives the main path ONCE through the entry points a user starts —
``apps.linear_regression.run`` (flagship 1,004-dim learner at b16384 from a
block-ingested replay file, then the device-bound 2^18 Gram learner) and
``apps.serve.run`` on the checkpoint the flagship run wrote — in ONE process
(a chip belongs to one process), at full width with random-from-a-seed
data, and checks the answers by the repo's own means: exact counts, the
dashboard's last published batch, a verified checkpoint, and agreement of
weights / per-batch mse / served predictions with the SAME entry point run
on the CPU backend of this process (``jax.devices("cpu")``) on the same
batches.

``main()`` cannot pass without a chip: it exits non-zero, naming the
platform jax found, unless that platform is ``tpu``. Every phase is a
function that takes its sizes as arguments (tests/test_chip_smoke.py calls
them tiny under the CPU test mesh); a failing phase raises, and the raise
is the exit status. Compile seconds, compilation counts and per-batch
milliseconds are printed as INFORMATION — they are not benchmark metrics.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Usage: python3 chip_smoke.py      (run it through the chip tool)
"""

from __future__ import annotations

import contextlib
import functools
import json
import math
import os
import shutil
import statistics
import sys
import threading
import time
from unittest import mock

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# peak dense bf16 matmul rate per chip, keyed by jax's ``device_kind``
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16). A kind that is
# not in the table is an error, never a default.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0, "TPU v5e": 197.0}

# pins the age feature and every replay-identity clock (utils/clock.py), so
# the chip run and its CPU reference featurize byte-identical batches
NOW_MS = 1785320000000
CLOSED = "http://127.0.0.1:9"  # --lightning: nothing listens, fails at once

# Agreement with the CPU reference. The dense path is ``x_dense @ weights``
# at jax's DEFAULT matmul precision (models/sgd.py). Had the chip multiplied
# f32 operands in one bf16 pass, weights could only agree at bf16 scale
# (2^-9 ~ 2e-3 per product, compounded over numIterations x batches); what
# the v5e measured with this installation is f32 scale — max|dw|/max|w| of
# 2.3e-7 (1,004-dim) and 3.6e-7 (2^18 Gram), every per-batch mse and served
# prediction identical (my chip run, PR 21). The bounds sit between the two
# scales: ~300x the measured f32-scale deviation, 20x under bf16 scale, so a
# silent drop to bf16 products fails the smoke. The app prints mse HALF_UP-
# rounded to an integer (~1e5 here) and serving rounds predictions the same
# way, so one unit of rounding flip is allowed on top.
TOL_WEIGHTS = 1e-4   # max|dw| / max|w|
TOL_MSE = 1e-4       # per batch: |dmse| <= 1 + TOL * mse
TOL_PRED = 1e-4      # served predictions: |dp| <= 1 + TOL * |p|


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------------
# information: compile counters (jax.monitoring), per-batch wall times

_COMPILE = {"n": 0, "secs": 0.0, "hits": 0, "writes": 0}


@functools.cache  # listeners cannot be unregistered: register them once
def _listen_for_compiles() -> None:
    import jax.monitoring as mon

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["n"] += 1
            _COMPILE["secs"] += secs

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _COMPILE["writes"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)


@contextlib.contextmanager
def compile_info(label: str):
    """Print what compiling cost inside the block (information only)."""
    _listen_for_compiles()
    before = dict(_COMPILE)
    t0 = time.perf_counter()
    yield
    d = {k: _COMPILE[k] - before[k] for k in _COMPILE}
    say(
        f"info {label}: wall {time.perf_counter() - t0:.1f} s, "
        f"compilations {d['n']} taking {d['secs']:.1f} s "
        f"(persistent-cache hits {d['hits']}, new entries {d['writes']})"
    )


class _Tee:
    """stdout pass-through that also parses the app's per-batch lines
    (``count: N  batch: b  mse: M ...``, apps/linear_regression.handle)."""

    def __init__(self, real):
        self.real = real
        self.batches: list[dict] = []
        self._buf = ""

    def write(self, text: str) -> int:
        self.real.write(text)
        self._buf += text
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line.startswith("count: "):
                f = line.split()
                self.batches.append({
                    "count": int(f[1]), "batch": int(f[3]),
                    "mse": float(f[5]), "t": time.perf_counter(),
                })
        return len(text)

    def flush(self) -> None:
        self.real.flush()


def pinned_clock():
    return mock.patch.dict(os.environ, TWTML_NOW_MS=str(NOW_MS))


@contextlib.contextmanager
def cpu_reference():
    """Run the enclosed entry-point call on this process's CPU backend:
    un-placed arrays and programs follow ``jax_default_device`` (global, so
    the app's scheduler and fetch threads follow it too). ``--backend cpu``
    accepts this (apps/common.select_backend reads the same identity)."""
    import jax

    prev = jax.config.jax_default_device
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    try:
        yield
    finally:
        jax.config.update("jax_default_device", prev)


# --------------------------------------------------------------------------
# phases

def phase_devices() -> dict:
    """Print what jax sees, first. Returns the device identity."""
    import importlib.metadata as md

    import jax
    import jaxlib

    dev = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    ident = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    say(
        f"platform={ident['platform']} device_kind={ident['kind']!r} "
        f"devices={ident['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"python={sys.version.split()[0]}"
    )
    return ident


def phase_native() -> dict:
    """The C fast path must be LIVE — built on this machine from the tracked
    native/*.cpp, every symbol bound — or the smoke fails instead of timing
    the Python fallback."""
    from twtml_tpu.features import native

    live = native.require_live()
    say(
        f"native: {len(live['symbols'])} symbols bound "
        f"({', '.join(live['symbols'])}) from {live['lib']}, built on this "
        f"host from native/*.cpp (sources sha256 "
        f"{live['stamp']['sources'][:12]}, flags {' '.join(live['stamp']['flags'])})"
    )
    return live


def phase_clock(n: int = 4096, peaks: "dict | None" = None) -> dict:
    """Is the clock honest? Time one n^3 bf16 matmul around
    ``block_until_ready`` and refuse an implied rate above the chip's peak
    (a sync that does not wait "finishes" a 4096^3 matmul in microseconds).
    Also times the same call closed by a host fetch of a data-dependent
    scalar: the two clocks must tell the same story."""
    import jax
    import jax.numpy as jnp

    peaks = PEAK_BF16_TFLOPS if peaks is None else peaks
    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        raise RuntimeError(
            f"device_kind {kind!r} is not in the peak table "
            f"({sorted(peaks)}): add it with its source before trusting "
            "any rate from this device"
        )
    a = jnp.full((n, n), 0.5, jnp.bfloat16)
    b = jnp.full((n, n), 0.25, jnp.bfloat16)
    mm = jax.jit(lambda x, y: x @ y)
    mm(a, b).block_until_ready()  # compile + warm

    def best(close) -> float:
        out = math.inf
        for _ in range(5):
            t0 = time.perf_counter()
            close(mm(a, b))
            out = min(out, time.perf_counter() - t0)
        return out

    t_block = best(lambda c: c.block_until_ready())
    t_fetch = best(lambda c: float(c[0, 0]))
    flop = 2.0 * n ** 3
    implied = flop / t_block / 1e12
    say(
        f"clock: {n}^3 bf16 matmul — block_until_ready {t_block * 1e3:.3f} ms "
        f"({implied:.1f} TFLOP/s implied), host-fetch-closed "
        f"{t_fetch * 1e3:.3f} ms ({flop / t_fetch / 1e12:.1f} TFLOP/s); "
        f"peak for {kind!r}: {peaks[kind]} TFLOP/s"
    )
    if implied > peaks[kind]:
        raise RuntimeError(
            f"block_until_ready returned after {t_block * 1e6:.0f} us: an "
            f"implied {implied:.0f} TFLOP/s exceeds the {peaks[kind]} "
            f"TFLOP/s peak of {kind!r} — the sync does not wait, and no "
            "timing on this machine can be trusted"
        )
    return {"t_block_s": t_block, "t_fetch_s": t_fetch, "tflops": implied}


def write_replay_file(path: str, n: int, seed: int) -> int:
    """``n`` seeded synthetic tweets as JSONL (there is no network and
    tests/data holds ten tweets). Returns how many the filter keeps — the
    exact count a run over the file must report."""
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.sources import SyntheticSource

    feat = Featurizer(now_ms=NOW_MS)
    kept = 0
    with open(path, "w", encoding="utf-8") as fh:
        for s in SyntheticSource(total=n, seed=seed, base_ms=NOW_MS).produce():
            kept += bool(feat.filtrate(s))
            fh.write(json.dumps(s.to_json()) + "\n")
    return kept


def _train_once(
    tag: str, out_dir: str, replay: str, *, backend: str, master: str,
    batch: int, n_batches: int, extra: "list[str]",
) -> dict:
    """One ``apps.linear_regression.run`` against an in-process dashboard on
    an ephemeral port. Returns the run record plus the per-batch lines, the
    dashboard's last Stats and the verified checkpoint's weights."""
    from twtml_tpu.apps import linear_regression
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.serving import load_servable
    from twtml_tpu.telemetry.web_client import WebClient
    from twtml_tpu.web.cache import ApiCache
    from twtml_tpu.web.server import Server

    ckpt = os.path.join(out_dir, f"ckpt_{tag}")
    shutil.rmtree(ckpt, ignore_errors=True)
    dash = Server(
        port=0, host="127.0.0.1",
        cache=ApiCache(backup_file=os.path.join(out_dir, f"dash_{tag}.json")),
    ).start_background()
    tee = _Tee(sys.stdout)
    try:
        url = f"http://127.0.0.1:{dash._runner.addresses[0][1]}"
        conf = ConfArguments().parse([
            "--backend", backend, "--master", master,
            "--source", "replay", "--replayFile", replay, "--ingest", "block",
            "--seconds", "0", "--batchBucket", str(batch),
            "--checkpointDir", ckpt, "--twtweb", url, "--lightning", CLOSED,
            *extra,
        ])
        if conf.effective_wire() != "ragged":
            raise RuntimeError("main path must resolve to the ragged wire")
        with contextlib.redirect_stdout(tee), compile_info(f"train[{tag}]"):
            totals = linear_regression.run(conf, max_batches=n_batches)
        stats = WebClient(url).get_stats()
    finally:
        dash.stop()
    snapshot, reason = load_servable(ckpt)
    if snapshot is None:
        raise RuntimeError(f"train[{tag}] left no servable checkpoint: {reason}")
    return {
        "totals": totals, "batches": tee.batches, "stats": stats,
        "weights": snapshot.weights, "step": snapshot.step, "ckpt": ckpt,
    }


def _check_run(tag: str, run: dict, *, platform: str, kept: int,
               n_batches: int) -> None:
    import numpy as np

    totals, lines, stats = run["totals"], run["batches"], run["stats"]
    if totals["device"]["platform"] != platform:
        raise RuntimeError(
            f"train[{tag}] ran on {totals['device']}, wanted {platform!r}"
        )
    if totals["count"] != kept or totals["batches"] != n_batches:
        raise RuntimeError(
            f"train[{tag}] counted {totals['count']} tweets in "
            f"{totals['batches']} batches; the file holds exactly {kept} "
            f"kept tweets for {n_batches} batches"
        )
    if len(lines) != n_batches or lines[-1]["count"] != kept:
        raise RuntimeError(f"train[{tag}] printed {len(lines)} batch lines")
    # a swallowed publish (telemetry/session_stats.py is best-effort) must
    # not pass for a working one: the dashboard holds the LAST batch
    if (stats.count, stats.batch) != (kept, lines[-1]["batch"]):
        raise RuntimeError(
            f"train[{tag}] dashboard /api/stats holds count={stats.count} "
            f"batch={stats.batch}; the run ended at count={kept} "
            f"batch={lines[-1]['batch']}"
        )
    if run["step"] != n_batches:
        raise RuntimeError(
            f"train[{tag}] checkpoint is at step {run['step']}, not {n_batches}"
        )
    w = np.asarray(run["weights"])
    if not np.all(np.isfinite(w)) or not np.any(w):
        raise RuntimeError(f"train[{tag}] weights are not finite and non-zero")
    if not all(math.isfinite(b["mse"]) for b in lines):
        raise RuntimeError(f"train[{tag}] printed a non-finite mse")


def _batch_ms_info(tag: str, lines: "list[dict]") -> None:
    gaps = [
        (b["t"] - a["t"]) * 1e3 for a, b in zip(lines, lines[1:])
    ]
    if gaps:
        say(
            f"info train[{tag}]: per-batch wall between published batches "
            f"(end to end, any in-stream compile included): "
            f"min {min(gaps):.1f} ms, median {statistics.median(gaps):.1f} "
            f"ms, max {max(gaps):.1f} ms over {len(gaps)} gaps"
        )


def compare_weights(label: str, got, ref, tol: float) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise RuntimeError(f"{label}: weight shapes {got.shape} vs {ref.shape}")
    dev = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))
    say(f"{label}: max|dw|/max|w| = {dev:.3e} (tolerance {tol:.0e})")
    if not dev <= tol:
        raise RuntimeError(f"{label}: weights deviate {dev:.3e} > {tol:.0e}")
    return dev


def compare_mse(label: str, got: "list[dict]", ref: "list[dict]",
                tol: float) -> float:
    pairs = [(abs(g["mse"] - r["mse"]), abs(r["mse"])) for g, r in zip(got, ref)]
    devs = [d / max(m, 1.0) for d, m in pairs]
    say(
        f"{label}: per-batch relative dmse = "
        f"[{', '.join(f'{d:.2e}' for d in devs)}] "
        f"(allowed: 1 + {tol:.0e}*mse)"
    )
    if len(got) != len(ref) or any(d > 1.0 + tol * m for d, m in pairs):
        raise RuntimeError(f"{label}: per-batch mse deviates beyond {tol:.0e}")
    return max(devs)


def phase_train(
    name: str, out_dir: str, *, backend: str, num_text_features: int,
    batch: int, n_batches: int, seed: int, extra: "tuple[str, ...]" = (),
) -> dict:
    """Train ``n_batches`` of ``batch`` tweets through the flagship entry
    point on ``backend`` (one device), again on the CPU backend of this
    process, and compare. Returns the chip run (its checkpoint dir feeds
    the serve phase, its weights the multichip phase)."""
    os.makedirs(out_dir, exist_ok=True)
    replay = os.path.join(out_dir, f"tweets_{name}.jsonl")
    kept = write_replay_file(replay, batch * n_batches, seed)
    args = dict(
        backend=backend, master="local[1]", batch=batch, n_batches=n_batches,
        extra=["--numTextFeatures", str(num_text_features), *extra],
    )
    with pinned_clock():
        run = _train_once(name, out_dir, replay, **args)
        _check_run(name, run, platform=backend, kept=kept, n_batches=n_batches)
        _batch_ms_info(name, run["batches"])
        # outside any timed window: the reference, same entry point, same
        # file, on the CPU backend of this process
        with cpu_reference():
            ref = _train_once(
                f"{name}_cpuref", out_dir, replay, **{**args, "backend": "cpu"}
            )
        _check_run(f"{name}_cpuref", ref, platform="cpu", kept=kept,
                   n_batches=n_batches)
    compare_weights(f"train[{name}] vs cpu", run["weights"], ref["weights"],
                    TOL_WEIGHTS)
    compare_mse(f"train[{name}] vs cpu", run["batches"], ref["batches"],
                TOL_MSE)
    say(f"train[{name}]: OK — {kept} tweets, {n_batches} batches of {batch}, "
        f"F={num_text_features}+4 on {run['totals']['device']}")
    run["replay"], run["kept"] = replay, kept
    return run


def _serve_once(tag: str, ckpt: str, *, backend: str,
                requests: "list[list[dict]]", expect_step: int) -> dict:
    """One ``apps.serve.run``: from the ``started`` hook a client thread
    POSTs the requests over HTTP, waits for the plane's own ``Serving``
    publish to show them on ``/api/serving``, then stops the server."""
    from twtml_tpu.apps import serve
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.serving import ServingClient

    from twtml_tpu.telemetry import metrics

    # the plane counts into the process-wide registry: this run's share is
    # what it adds to what an earlier serve run in this process left there
    reg = metrics.get_registry()
    base_requests = int(reg.counter("serve.requests").snapshot())
    base_rows = int(reg.counter("serve.rows").snapshot())
    stop = threading.Event()
    got: dict = {}

    def client(port: int) -> None:
        try:
            api = ServingClient(f"http://127.0.0.1:{port}", timeout=120.0)
            got["answers"] = [api.predict(rows) for rows in requests]
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                view = api.serving()
                if view.get("requests", 0) >= base_requests + len(requests):
                    got["view"] = view
                    break
                time.sleep(0.25)
        except Exception as exc:  # re-raised on the phase's thread below
            got["error"] = exc
        finally:
            stop.set()

    threads: list[threading.Thread] = []

    def started(server, _plane, _promoter) -> None:
        t = threading.Thread(
            target=client, args=(server._runner.addresses[0][1],), daemon=True
        )
        threads.append(t)
        t.start()

    conf = ConfArguments().parse([
        "--backend", backend, "--master", "local[1]",
        "--checkpointDir", ckpt, "--servePort", "0",
        "--servePromoteEvery", "600",
    ])
    with compile_info(f"serve[{tag}]"):
        stats = serve.run(conf, started=started, stop_event=stop,
                          max_seconds=300.0)
    for t in threads:
        t.join(timeout=30.0)
    if "error" in got:
        raise got["error"]
    if "view" not in got:
        raise RuntimeError(
            f"serve[{tag}]: /api/serving never showed {len(requests)} requests"
        )
    rows = sum(len(r) for r in requests)
    if stats["device"]["platform"] != backend:
        raise RuntimeError(f"serve[{tag}] ran on {stats['device']}")
    counted = (stats["requests"] - base_requests, stats["rows"] - base_rows)
    if counted != (len(requests), rows) or stats["errors"]:
        raise RuntimeError(
            f"serve[{tag}] counted {counted} requests/rows and "
            f"{stats['errors']} errors; sent {len(requests)} / {rows}"
        )
    preds: list[float] = []
    for req, ans in zip(requests, got["answers"]):
        if (ans["servedRows"], ans["snapshotStep"]) != (len(req), expect_step):
            raise RuntimeError(f"serve[{tag}] answered {ans} for {len(req)} rows")
        if len(ans["predictions"]) != len(req) or not all(
            math.isfinite(p) for p in ans["predictions"]
        ):
            raise RuntimeError(f"serve[{tag}] predictions malformed")
        preds.extend(float(p) for p in ans["predictions"])
    if got["view"]["snapshotStep"] != expect_step:
        raise RuntimeError(f"serve[{tag}] /api/serving: {got['view']}")
    return {"predictions": preds, "view": got["view"], "stats": stats}


def phase_serve(ckpt: str, *, backend: str, expect_step: int,
                row_counts: "tuple[int, ...]" = (1, 5, 64, 200),
                seed: int = 23) -> dict:
    """Serve the checkpoint the flagship run just wrote: a few
    ``/api/predict`` requests of mixed row counts over HTTP, then the same
    requests against the same entry point on the CPU backend."""
    import numpy as np

    from twtml_tpu.streaming.sources import SyntheticSource

    statuses = iter(
        SyntheticSource(total=sum(row_counts), seed=seed, base_ms=NOW_MS).produce()
    )
    requests = [[
        {
            "text": s.retweeted_status.text,
            "followers_count": s.retweeted_status.followers_count,
            "favourites_count": s.retweeted_status.favourites_count,
            "friends_count": s.retweeted_status.friends_count,
            "created_at_ms": s.retweeted_status.created_at_ms,
        }
        for s in (next(statuses) for _ in range(n))
    ] for n in row_counts]
    with pinned_clock():
        served = _serve_once("chip", ckpt, backend=backend, requests=requests,
                             expect_step=expect_step)
        with cpu_reference():
            ref = _serve_once("cpuref", ckpt, backend="cpu", requests=requests,
                              expect_step=expect_step)
    p, r = np.asarray(served["predictions"]), np.asarray(ref["predictions"])
    excess = np.abs(p - r) - (1.0 + TOL_PRED * np.abs(r))
    say(
        f"serve vs cpu: {len(p)} predictions over {len(requests)} requests of "
        f"{list(row_counts)} rows — max|dp| = {float(np.max(np.abs(p - r))):.3g}, "
        f"max|dp|/|p| = {float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1.0))):.3e}, "
        f"{int(np.sum(p != r))} differ (allowed: 1 + {TOL_PRED:.0e}*|p|)"
    )
    if np.any(excess > 0):
        raise RuntimeError("served predictions deviate from the CPU reference")
    view = served["view"]
    say(
        f"serve: OK — /api/serving snapshotStep={view['snapshotStep']} "
        f"level={view.get('level')!r} requests={view['requests']} "
        f"rows={view['rows']} (process-cumulative counters)"
    )
    return served


def phase_multichip(out_dir: str, one_chip: dict, *, backend: str,
                    num_text_features: int, batch: int, n_batches: int,
                    n_devices: int, master: str = "local[*]") -> dict:
    """The DEFAULT main path on a multi-device host (``--master local[*]``
    shards over every device: ParallelSGDModel under shard_map). Same file
    as the one-chip flagship run; asserts the batch buffer and the weights
    really span ``n_devices`` devices, and compares weights with the
    one-chip run."""
    with pinned_clock():
        run = _train_once(
            "multichip", out_dir, one_chip["replay"], backend=backend,
            master=master, batch=batch, n_batches=n_batches,
            extra=["--numTextFeatures", str(num_text_features)],
        )
    _check_run("multichip", run, platform=backend, kept=one_chip["kept"],
               n_batches=n_batches)
    _batch_ms_info("multichip", run["batches"])
    # the run record says how many devices the arrays really occupied
    # (len(sharding.device_set) — not N shards on device 0)
    span = run["totals"].get("device_span")
    say(f"multichip: run record device_span = {span}, wanted {n_devices}")
    if span != {"weights": n_devices, "batch": n_devices}:
        raise RuntimeError(
            f"multichip: arrays did not span all {n_devices} devices: {span}"
        )
    compare_weights("multichip vs one chip", run["weights"],
                    one_chip["weights"], TOL_WEIGHTS)
    compare_mse("multichip vs one chip", run["batches"], one_chip["batches"],
                TOL_MSE)
    say(f"multichip: OK — {n_devices} devices")
    return run


# --------------------------------------------------------------------------

def main() -> int:
    # a smoke run leaves behind only what it must (the native library, the
    # compile cache, the tool's output directory): no __pycache__ either —
    # the repo's modules are all imported below this line
    sys.dont_write_bytecode = True
    ident = phase_devices()
    if ident["platform"] != "tpu":
        print(
            f"chip_smoke: FAIL — jax's first device is platform "
            f"{ident['platform']!r} ({ident['kind']}), not 'tpu'; this "
            "script only passes on a chip (run it through the chip tool)",
            file=sys.stderr,
        )
        return 1
    from twtml_tpu.utils.backend import configure_compile_cache

    t0 = time.perf_counter()
    say(f"compile cache: {configure_compile_cache()}")
    # replay files, journals and checkpoints are tens of MB at full width:
    # they live in a work directory under the tool's output directory and
    # go when the run ends, either way
    work = os.path.join(OUT_DIR, "work")
    say(f"work directory: {work}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        phase_native()
        with compile_info("clock"):
            phase_clock()
        flagship = dict(num_text_features=1000, batch=16384, n_batches=8)
        one_chip = phase_train("flagship", work, backend="tpu", seed=7,
                               **flagship)
        phase_train("gram2e18", work, backend="tpu",
                    num_text_features=262144, batch=2048, n_batches=4,
                    seed=11, extra=("--l2Reg", "0.1"))
        phase_serve(one_chip["ckpt"], backend="tpu",
                    expect_step=flagship["n_batches"])
        if ident["count"] > 1:
            phase_multichip(work, one_chip, backend="tpu",
                            n_devices=ident["count"], **flagship)
        else:
            say("multichip: not run (1 device)")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say(
        f"info whole run: {time.perf_counter() - t0:.0f} s wall, "
        f"{_COMPILE['n']} compilations taking {_COMPILE['secs']:.1f} s "
        f"(persistent-cache hits {_COMPILE['hits']}, new entries "
        f"{_COMPILE['writes']})"
    )
    print(json.dumps({"ok": True, "device": ident}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
