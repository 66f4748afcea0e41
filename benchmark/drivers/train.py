"""Driver of the training cells (traffic ``kind: train``): the served path
from outside.

  feeder (child) --HTTP/1.1 chunked--> twtml_tpu.apps.<app>.run (this
  process, owns the chip) --per-batch stats POST--> sink (child)

The LEARNER is data: the configuration file's ``app`` names the entry point,
its ``reference`` the plain reference (one signature, ``reference`` below),
its ``correct.statistic`` the rule the printed statistic is held by
(``compare.STATISTICS``), and the mix's ``generator`` where the labels come
from. A second learner adds files and edits none.

One run: set-up (children, device, native library), a CHECK run of the same
entry point with the same flags on the first batches of the seeded pool, the
WINDOW run (warm-up until a whole pass over the pool brought no compilation,
then ``--seconds`` measured, then a clean stop), and — after the window, not
in ``setup_s`` — the plain reference and the comparison. Window and metrics
come from the sink's record alone.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import math
import os
import re
import shutil
import signal
import threading
import time

from .. import compare, gen, harness, manifest, spans
from ..harness import say

OAUTH = ("consumerKey", "consumerSecret", "accessToken", "accessTokenSecret")
TAIL = re.compile(r"^batch_gap_ms_p(\d+)$")   # the manifest names the tail
WARMUP_LIMIT_S = 900.0   # a cold first run compiles several shapes in-stream


class Tee:
    """Takes the program's stdout: keeps its per-batch lines with a stamp
    and passes nothing on (a window prints hundreds). A line is read BY
    POSITION, ``count: N  batch: b  <name>: v ...``: every app prints that
    shape (apps/linear_regression.handle ``mse: M``, an integer;
    apps/logistic_regression.handle ``errRate: r``, three decimals), and
    what ``v`` means is the configuration's ``correct.statistic``."""

    def __init__(self):
        self.batches: list = []
        self._buf = ""

    def write(self, text: str) -> int:
        self._buf += text
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line.startswith("count: "):
                f = line.split()
                self.batches.append({
                    "count": int(f[1]), "batch": int(f[3]),
                    "stat": float(f[5]), "t": time.monotonic(),
                })
        return len(text)

    def flush(self) -> None:
        pass


def program_flags(cfg: dict, backend: str, ckpt: str, sink_url: str,
                  extra=()) -> list:
    """Every default of the program stays as shipped; these are the flags a
    user of this deployment would give."""
    return [
        "--backend", backend, "--source", "twitter", "--ingest", "block",
        "--seconds", "0", "--checkpointDir", ckpt, "--twtweb", sink_url,
        "--lightning", harness.CLOSED, *cfg["flags"], *extra,
    ]


def app_of(cfg: dict):
    """The entry point the configuration names: ``twtml_tpu.apps.<app>``,
    whose ``run(conf, max_batches=0)`` every app has."""
    return importlib.import_module(f"twtml_tpu.apps.{cfg['app']}")


def start_children(work: str, cell: dict, seed: int):
    path = os.path.join(work, "traffic.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(cell["traffic"], fh)
    feeder = harness.Child("feeder", ["--traffic", path, "--seed", seed])
    sink = harness.Child("sink", [])
    return feeder, sink


def prepare_program(cell: dict, feeder, sink, rehearse: bool,
                    t_start: "float | None" = None):
    """Device, native library, credentials and the stream URL. Returns
    ``(ident, backend, sink_url, pool)``. With ``t_start`` it prints the
    set-up's timeline, so a slow set-up can be laid at a stage's door."""
    cfg = cell["config"]

    def mark(what):
        if t_start is not None:
            harness.mark(what, t_start)

    mark("harness imported, children started")
    os.environ["TWTML_NOW_MS"] = str(cell["traffic"]["generator"]["now_ms"])
    say(f"compile cache: {harness.place_compile_cache()}; runtime: "
        f"{harness.place_runtime_env()}")
    ident = harness.require_device(cell["workload"]["chips"], rehearse)
    mark("jax imported, devices found")
    harness.CompileCounter.install()
    from twtml_tpu import config as pconf
    from twtml_tpu.features import native

    live = native.require_live()
    mark("program imported, native library live")
    say(f"native: {len(live['symbols'])} symbols bound from {live['lib']}")
    for k in OAUTH:   # made-up credentials: the feeder checks none
        pconf.set_property(f"twitter4j.oauth.{k}", "benchmark")
    pool = feeder.ready(timeout=300.0)
    mark("feeder's pool ready")
    say(f"feeder: pool of {pool['lines']} lines, {pool['bytes']} bytes, "
        f"made in {pool['gen_s']:.1f} s, port {pool['ready']}")
    pconf.set_property(
        "twitter4j.streamBaseURL",
        f"http://127.0.0.1:{pool['ready']}/1.1/statuses/sample.json",
    )
    sink_url = f"http://127.0.0.1:{sink.ready()['ready']}"
    if cfg.get("must_take_gram_plane"):
        from twtml_tpu.ops.gram import fits_gram

        m = cfg["model"]
        if not fits_gram(cfg["batch_rows"], m["numTextFeatures"],
                         m["numIterations"]):
            raise SystemExit(
                "benchmark: this configuration must take the Gram plane and "
                "ops/gram.fits_gram refuses its size"
            )
    return ident, ("cpu" if rehearse else "tpu"), sink_url, pool


def check_run(cell: dict, backend: str, sink_url: str, work: str) -> dict:
    """The same entry point, the same flags, on the first ``check_batches``
    batches of the pool: per-batch lines and the verified checkpoint."""
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.serving import load_servable

    ckpt = os.path.join(work, "ckpt_check")
    shutil.rmtree(ckpt, ignore_errors=True)
    conf = ConfArguments().parse(
        program_flags(cell["config"], backend, ckpt, sink_url)
    )
    tee = Tee()
    n = int(cell["traffic"]["check_batches"])
    t0 = time.monotonic()
    with contextlib.redirect_stdout(tee):
        totals = app_of(cell["config"]).run(conf, max_batches=n)
    snapshot, reason = load_servable(ckpt)
    if snapshot is None:
        raise RuntimeError(f"the check run left no servable checkpoint: {reason}")
    say(f"check run: {totals['batches']} batches, {totals['count']} tweets in "
        f"{time.monotonic() - t0:.1f} s, wire {conf.effective_wire()!r}, "
        f"checkpoint step {snapshot.step}")
    return {"batches": tee.batches, "weights": snapshot.weights,
            "step": int(snapshot.step), "ckpt": ckpt, "totals": totals}


def reference(cell: dict, seed: int, precision: str = "float64"):
    """The configuration's plain reference (the module its ``reference``
    names) on the same first batches, from the generator's truth columns.
    Every reference has ONE signature, ``train_on_chunks(chunks, *,
    batch_rows, n_batches, model, generator, precision)``, and picks what it
    needs out of the configuration's ``model`` and the mix's ``generator``
    itself. Returns ``(model with .w, stats per batch)``."""
    cfg, traffic = cell["config"], cell["traffic"]
    g = traffic["generator"]
    n = int(traffic["check_batches"])
    rows = n * cfg["batch_rows"]
    # enough chunks to hold `rows` kept lines at the mix's keep share
    n_chunks = min(
        math.ceil(rows / g["keep_share"] * 1.02 / gen.CHUNK) + 1,
        math.ceil(g["pool_lines"] / gen.CHUNK),
    )
    vocab = gen.build_vocab(g, seed)
    chunks = [
        gen.make_chunk(g, vocab, seed, c,
                       min(gen.CHUNK, g["pool_lines"] - c * gen.CHUNK))
        for c in range(n_chunks)
    ]
    return manifest.load_module(
        os.path.join(manifest.ROOT, cfg["reference"])
    ).train_on_chunks(
        chunks, batch_rows=cfg["batch_rows"], n_batches=n,
        model=cfg["model"], generator=g, precision=precision,
    )


def pool_batches(cell: dict) -> int:
    """Batches in one pass over the pool: its kept lines over the batch."""
    g = cell["traffic"]["generator"]
    return int(g["pool_lines"] * g["keep_share"]) // cell["config"]["batch_rows"]


def tail_metric(cell: dict) -> "tuple[str, int]":
    """The cell's gap-tail metric and its percentile, from the manifest's
    name (``batch_gap_ms_p95`` -> 95)."""
    for m in cell["end_to_end"]:
        hit = TAIL.match(m["name"])
        if hit:
            return m["name"], int(hit.group(1))
    raise SystemExit("benchmark: a training cell reports a batch_gap_ms_p<q>")


class Window:
    """Warm-up, the measured window and the clean stop, from a harness
    thread while the program runs on the main thread."""

    def __init__(self, cell, sink, seconds, trace, work, stop,
                 rehearse=False):
        self.cell, self.sink, self.seconds = cell, sink, float(seconds)
        self.trace, self.work, self.stop = trace, work, stop
        self.rehearse = rehearse
        self.done = threading.Event()
        self.error = ""
        self.t_begin = time.monotonic()
        self.t_open = self.t_close = 0.0
        self.profile = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-window")

    def start(self) -> "Window":
        self._thread.start()
        return self

    def join(self) -> None:
        self.done.set()
        self._thread.join(timeout=60.0)

    def records(self) -> list:
        return [r for r in self.sink.snapshot()
                if "t" in r and r["t"] >= self.t_begin]

    def warm(self) -> bool:
        """A stated number of consecutive batches with no compilation, and
        at least ``min_seconds`` of stream."""
        cfg, traffic = self.cell["config"], self.cell["traffic"]
        quiet = max(1, pool_batches(self.cell))
        recs = self.records()
        if not recs:
            return False
        last_compile = harness.CompileCounter.install().last()
        since = sum(1 for r in recs if r["t"] > last_compile)
        return (time.monotonic() - recs[0]["t"]
                >= traffic["warmup"]["min_seconds"] and since >= quiet)

    def _run(self) -> None:
        try:
            while not self.warm():
                if self.done.wait(0.02):
                    return
                if time.monotonic() - self.t_begin > WARMUP_LIMIT_S:
                    self.error = (f"no steady stream within "
                                  f"{WARMUP_LIMIT_S:.0f} s of warm-up")
                    return
            self.t_open = time.monotonic()
            if self.trace:
                self.done.wait(1.0)
                self.profile = harness.Profiler(
                    os.path.join(self.work, "profile"))
                self.profile.take(self.cell["traffic"]["profile_seconds"])
            self.done.wait(max(0.0, self.t_open + self.seconds - time.monotonic()))
            # a rehearsal (no metric is printed) keeps its window open until
            # one pass of its tiny pool was published inside it, however slow
            # a batch is where it runs: seconds each into 2^20 dims on a CPU
            while self.rehearse and not self.done.is_set() and sum(
                    1 for r in self.records() if r["t"] >= self.t_open
            ) < pool_batches(self.cell):
                self.done.wait(0.1)
            self.t_close = time.monotonic()
        except Exception as exc:   # reported by the main thread
            self.error = f"window thread failed: {exc!r}"
        finally:
            self.stop()


def interrupt_main() -> None:
    """``run`` handles KeyboardInterrupt as its clean stop: deliver SIGINT
    to the main thread, which waits in ``await_termination``."""
    signal.pthread_kill(threading.main_thread().ident, signal.SIGINT)


def arm_interrupt() -> None:
    """A process started in the background (``cmd &``, a job runner)
    inherits SIGINT ignored, and Python then installs no KeyboardInterrupt
    handler: the clean stop would never arrive. Install it ourselves."""
    signal.signal(signal.SIGINT, signal.default_int_handler)


def feeder_share(feeder, t0: float, t1: float) -> "dict | None":
    """Blocked seconds over ``[t0, t1]`` from the feeder's own samples (the
    last sample at or before each edge)."""
    recs = [r for r in feeder.snapshot() if "blocked_s" in r]

    def at(t):
        older = [r for r in recs if r["t"] <= t]
        return older[-1] if older else None

    a, b = at(t0), at(t1)
    if a is None or b is None or b["t"] <= a["t"] or a["conn"] != b["conn"]:
        return None
    return {"blocked_s": b["blocked_s"] - a["blocked_s"],
            "window_s": b["t"] - a["t"], "sent": b["sent"] - a["sent"]}


def run(cell: dict, args, t_start: float) -> dict:
    if args.check_only:
        return check_only(cell, args)
    work = harness.fresh_dir(cell["workload"]["name"])
    feeder, sink = start_children(work, cell, args.seed)
    try:
        return _run(cell, args, t_start, work, feeder, sink)
    finally:
        feeder.stop()
        sink.stop()
        shutil.rmtree(work, ignore_errors=True)


def check_only(cell: dict, args) -> dict:
    """``--check-only``: set-up, the check run and the comparison for
    ``--seed`` and every ``--more-seeds``, in one process and with no
    window — the sound runs' readings that limits are set from."""
    g = cell["traffic"]["generator"]
    g["pool_lines"] = min(   # the check batches are all this mode streams
        g["pool_lines"],
        math.ceil(cell["traffic"]["check_batches"]
                  * cell["config"]["batch_rows"] / g["keep_share"]))
    readings, ok = {}, True
    for seed in [args.seed, *args.more_seeds]:
        work = harness.fresh_dir(cell["workload"]["name"])
        feeder, sink = start_children(work, cell, seed)
        try:
            _ident, backend, sink_url, _pool = prepare_program(
                cell, feeder, sink, args.rehearse)
            checked = check_run(cell, backend, sink_url, work)
            model, ref_stats = reference(cell, seed)
            v = compare.Verdict()
            compare.training(v, cell["config"], checked, ref_stats, model.w,
                             tag=f"seed{seed}_")
            readings[str(seed)] = v.numbers
            ok = ok and v.ok
        finally:
            feeder.stop()
            sink.stop()
            shutil.rmtree(work, ignore_errors=True)
    return {"check_only": True, "correct": ok, "readings": readings}


def _run(cell, args, t_start, work, feeder, sink) -> dict:
    from twtml_tpu.config import ConfArguments

    cfg, traffic = cell["config"], cell["traffic"]
    ident, backend, sink_url, pool = prepare_program(
        cell, feeder, sink, args.rehearse, t_start)
    compiles = harness.CompileCounter.install()
    verdict = compare.Verdict()

    checked = check_run(cell, backend, sink_url, work)
    harness.mark("check run done, window run starts", t_start)

    # ---- the window run: same entry point, same flags, a fresh directory
    span_file = os.path.join(work, "spans.json")
    conf = ConfArguments().parse(program_flags(
        cfg, backend, os.path.join(work, "ckpt_window"), sink_url,
        ["--trace", span_file] if args.trace else [],
    ))
    tee = Tee()
    arm_interrupt()
    window = Window(cell, sink, args.seconds, args.trace, work,
                    interrupt_main, args.rehearse).start()
    try:
        with contextlib.redirect_stdout(tee):
            totals = app_of(cfg).run(conf)
    finally:
        window.join()
    if window.error or not window.t_close:
        raise RuntimeError(window.error or "the run ended before its window")
    t_open, t_close = window.t_open, window.t_close
    setup_s = t_open - t_start
    say(f"window: opened {setup_s:.2f} s after process start, "
        f"{t_close - t_open:.3f} s long; {compiles.seconds():.1f} s in "
        f"{len(compiles.events)} compilations so far "
        f"({compiles.cache_hits} from the persistent cache)")

    # ---- metrics, from the sink's record alone
    recs = sorted(window.records(), key=lambda r: r["t"])
    inside = [r for r in recs if t_open <= r["t"] < t_close]
    before = [r for r in recs if r["t"] < t_open]
    c0 = before[-1]["count"] if before else 0
    c1 = inside[-1]["count"] if inside else c0
    tweets = c1 - c0
    gaps = [(b["t"] - a["t"]) * 1e3 for a, b in zip(inside, inside[1:])]
    say(f"window: {len(inside)} batches, {tweets} tweets published, "
        f"{len(gaps)} gaps between them")
    if len(gaps) < 2:
        raise RuntimeError("fewer than three batches were published in the window")
    tail_name, tail_q = tail_metric(cell)
    pct = {q: harness.percentile(gaps, q)
           for q in sorted({50, 90, 95, 99, 100, tail_q})}
    long_at = [i for i, x in enumerate(gaps) if x > 1.5 * pct[50]]
    say("gaps ms: " + ", ".join(f"p{q} {v:.3f}" for q, v in pct.items())
        + f"; {sum(1 for x in gaps if x > pct[tail_q])} beyond p{tail_q}; "
        f"{len(long_at)} over 1.5x the median, at {long_at[:40]}")
    printed = [b for b in tee.batches if t_open <= b["t"] < t_close]
    # failed: a batch of the window whose stats POST never reached the sink
    # (the sentinel skipped it, or the publish was dropped), or not finite
    posted = {r["count"] for r in recs}
    failed = sum(1 for b in printed
                 if b["count"] not in posted or not math.isfinite(b["stat"]))

    # ---- correct
    model, ref_stats = reference(cell, args.seed)
    compare.training(verdict, cfg, checked, ref_stats, model.w)
    # the timed run itself replays the pool from its start: its own first
    # batches are held to the same reference
    compare.training(
        verdict, cfg,
        {"batches": tee.batches[:len(ref_stats)], "weights": None},
        ref_stats, None, tag="window_",
    )
    rows = cfg["batch_rows"]
    steps = [(b["count"] - a["count"], b["batch"]) for a, b in zip(recs, recs[1:])]
    if any(r["batch"] != rows for r in recs) or any(d != b for d, b in steps):
        verdict.fail("the sink's count is not a whole number of full batches "
                     "of kept tweets")
    if any(not math.isfinite(b["stat"]) for b in tee.batches):
        verdict.fail("a batch published a non-finite statistic")
    fed = feeder_share(feeder, t_open, t_close)
    if fed is None:
        verdict.fail("the feeder's record does not span the window on one "
                     "connection (the trainer reconnected?)")
    else:
        share = fed["blocked_s"] / fed["window_s"]
        floor = traffic["feeder"]["min_blocked_share"]
        say(f"feeder: blocked {100 * share:.1f}% of the window "
            f"(floor {100 * floor:.0f}%), {fed['sent'] / fed['window_s'] / 1e6:.1f} "
            "MB/s sent")
        if share < floor:
            verdict.fail("the feeder was the bottleneck: this run is not a "
                         "measurement of the system")
        sent_kept = (fed["sent"] / (pool["bytes"] / pool["lines"])
                     * traffic["generator"]["keep_share"])
        if tweets > sent_kept + 9 * rows:   # intake queue of 8 batches + 1
            verdict.fail(f"{tweets} tweets published, {sent_kept:.0f} kept "
                         "lines sent")
    span = cfg.get("must_span_devices")
    if span and ident["count"] >= span and totals.get("device_span") != {
        "weights": span, "batch": span,
    }:
        verdict.fail(f"arrays span {totals.get('device_span')}, not {span} devices")
    n_compiles = compiles.between(t_open, t_close)
    say(f"compilations inside the window: {n_compiles}")

    result = {
        "correct": verdict.ok, "attempted": len(printed), "failed": failed,
        "numbers": verdict.numbers,
    }
    values = {
        "ingest_tweets_per_s": tweets / (t_close - t_open),
        tail_name: pct[tail_q],
        "setup_s": setup_s,
    }
    art = {"tweets": tweets, "compiles_in_window": n_compiles, "feeder": fed}
    if args.trace:   # spans, profile, work count: what the readers read
        stages = spans.summarize(spans.load_events(span_file), t_open, t_close)
        for name, st in sorted(stages.items(), key=lambda kv: -kv[1]["total_ms"]):
            say(f"span {name}: {st['count']} events, {st['total_ms']:.1f} ms in "
                f"all, max {st['max_ms']:.2f} ms, {st['bytes']} bytes")
        prof = window.profile
        in_stretch = sum(1 for r in recs if prof.t_start <= r["t"] < prof.t_stop)
        pack = stages.get("wire_pack") or {}
        art.update(
            spans=stages, profile=prof.reduce(in_stretch),
            work=manifest.load_module(manifest.work_count_path(cfg)).work(
                cfg, cell["workload"]["chips"],
                pack["bytes"] / pack["count"] if pack.get("bytes") else 0.0),
        )
    return harness.finish(cell, args, result, ident, values=values, art=art,
                          note={"batches_in_window": len(inside)})
