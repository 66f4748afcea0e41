"""Pipeline tracing (telemetry/trace.py + tools/trace_report.py): span
nesting, crash flush, the off-by-default null tracer, trace_report's
malformed-file check — and the tier-1 integration smoke: a ``--trace`` run
of the linear-regression entry on the local replay source produces a
Perfetto-valid trace with every expected stage name and ZERO extra host
fetches vs the untraced run (the measurement-integrity constraint of
lawcheck TW002, asserted against FetchPipeline's one-fetch-per-batch)."""

import json

import pytest

from tools import trace_report
from twtml_tpu.telemetry import trace
from twtml_tpu.telemetry import metrics as metrics_mod


@pytest.fixture(autouse=True)
def _clean_tracer():
    yield
    trace.uninstall()


def test_null_tracer_is_noop():
    tr = trace.get()
    assert not tr.enabled
    with tr.span("anything", rows=1):
        pass
    tr.instant("x")
    with tr.batch_scope(1):
        pass
    tr.close()  # all no-ops


def test_span_nesting_and_args(tmp_path):
    path = str(tmp_path / "t.trace")
    tr = trace.install(path)
    with tr.span("featurize", items=3) as sp:
        with tr.span("parse"):
            pass
        sp.add(rows=4, wire_bytes=128)
    trace.uninstall()
    events = trace_report.load_events(path)
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(spans) == {"featurize", "parse"}
    assert spans["featurize"]["args"] == {
        "items": 3, "rows": 4, "wire_bytes": 128,
    }
    # nesting: the inner span lies within the outer span's window
    outer, inner = spans["featurize"], spans["parse"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_crash_flush_leaves_events_on_disk(tmp_path):
    """Line-buffered writes: a crash mid-run (no close()) must still leave
    every completed span on disk, and the span an exception escaped through
    is recorded with the error class."""
    path = str(tmp_path / "crash.trace")
    tr = trace.install(path)
    with pytest.raises(RuntimeError):
        with tr.span("dispatch", depth=2):
            raise RuntimeError("boom")
    # read WITHOUT closing — simulating a crashed process's file
    events = trace_report.load_events(path)
    (ev,) = [e for e in events if e.get("ph") == "X"]
    assert ev["name"] == "dispatch"
    assert ev["args"]["error"] == "RuntimeError"


def test_instant_events(tmp_path):
    path = str(tmp_path / "i.trace")
    tr = trace.install(path)
    tr.instant("health_phase", phase="degraded", latency_ms=412.0)
    trace.uninstall()
    events = trace_report.load_events(path)
    kinds = {e["ph"] for e in events}
    assert "i" in kinds
    summary = trace_report.summarize(events)
    assert summary["health_transitions"] == [
        {"phase": "degraded", "latency_ms": 412.0}
    ]


# ---------------------------------------------------------------------------
# size-based rotation (r8): PATH -> PATH.1, stitched reports, dropped count


def test_trace_rotation_keeps_two_segments_and_counts_drops(tmp_path):
    metrics_mod.reset_for_tests()
    path = str(tmp_path / "r.trace")
    # tiny cap: every few spans rotate the file
    tr = trace.install(path, max_bytes=2048)
    for i in range(200):
        with tr.span("featurize", rows=i):
            pass
    trace.uninstall()
    import os

    assert os.path.exists(path) and os.path.exists(path + ".1")
    # both segments bounded by the cap (+ one event of slack)
    assert os.path.getsize(path) <= 2048 + 512
    assert os.path.getsize(path + ".1") <= 2048 + 512
    # rotations beyond the second segment DROP events, loudly counted
    dropped = metrics_mod.get_registry().counter(
        "trace.dropped_events"
    ).snapshot()
    assert dropped > 0
    # each surviving segment is independently a valid trace
    for p in (path + ".1",):
        events = trace_report._load_one(p)
        assert any(e.get("ph") == "X" for e in events)
    # stitched load covers both segments, older first
    stitched = trace_report.load_events(path)
    spans = [e for e in stitched if e.get("ph") == "X"]
    rows = [e["args"]["rows"] for e in spans]
    assert rows == sorted(rows)  # chronological across the stitch
    assert rows[-1] == 199  # the newest event survived
    # accounting: every span not in a surviving segment was counted as
    # dropped (dropped also counts each dead segment's one metadata event)
    assert len(spans) < 200
    assert len(spans) + dropped >= 200
    assert trace_report.main([path]) == 0


def test_trace_unbounded_by_default_never_rotates(tmp_path):
    path = str(tmp_path / "u.trace")
    tr = trace.install(path)  # max_bytes=0
    for _ in range(100):
        with tr.span("parse"):
            pass
    trace.uninstall()
    import os

    assert not os.path.exists(path + ".1")
    assert len(trace_report.load_events(path)) >= 100


# ---------------------------------------------------------------------------
# trace_report as a CHECK (bench scripts gate on its exit status)


def test_trace_report_exit_codes(tmp_path):
    good = tmp_path / "good.trace"
    tr = trace.install(str(good))
    with tr.span("featurize"):
        pass
    trace.uninstall()
    assert trace_report.main([str(good)]) == 0
    assert trace_report.main([str(good), "--json"]) == 0

    bad = tmp_path / "bad.trace"
    bad.write_text("this is { not a trace\n")
    assert trace_report.main([str(bad)]) == 2
    empty = tmp_path / "empty.trace"
    empty.write_text("")
    assert trace_report.main([str(empty)]) == 2
    only_bracket = tmp_path / "brackets.trace"
    only_bracket.write_text("[\n")
    assert trace_report.main([str(only_bracket)]) == 2
    missing = tmp_path / "missing.trace"
    assert trace_report.main([str(missing)]) == 2
    # a JSON document that parses but is not a trace
    scalar = tmp_path / "scalar.trace"
    scalar.write_text("42")
    assert trace_report.main([str(scalar)]) == 2


def test_trace_report_accepts_closed_json_array(tmp_path):
    path = tmp_path / "closed.trace"
    path.write_text(json.dumps([
        {"name": "parse", "ph": "X", "ts": 0, "dur": 1000, "pid": 1,
         "tid": 1, "args": {"bytes": 10}},
    ]))
    summary = trace_report.summarize(trace_report.load_events(str(path)))
    assert summary["stages"]["parse"]["count"] == 1
    assert summary["stages"]["parse"]["bytes"] == 10


# ---------------------------------------------------------------------------
# integration smoke (tier-1, fast): the flagship app under --trace


def _write_replay(tmp_path, n):
    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    with open(path, "w") as fh:
        for s in SyntheticSource(
            total=n, seed=7, base_ms=1785320000000
        ).produce():
            fh.write(json.dumps(s.to_json()) + "\n")
    return path


def _run_linear(tmp_path, extra):
    """Run the flagship app over a 4-batch corpus (to natural exhaustion, so
    the source thread flushes its aggregated parse span), counting every
    jax.device_get — the ONLY host fetch the back-to-back pipeline makes
    (FetchPipeline submits one per batch)."""
    import jax

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.config import ConfArguments

    jax.devices()  # lock the conftest's backend before local[1]
    tmp_path.mkdir(parents=True, exist_ok=True)
    path = _write_replay(tmp_path, 4 * 16)
    conf = ConfArguments().parse([
        "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--master", "local[1]",
    ] + extra)
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    jax.device_get = counting
    try:
        totals = app.run(conf)
    finally:
        jax.device_get = real
    return totals, calls["n"]


def test_trace_smoke_linear_app(tmp_path):
    """Acceptance: a --trace replay run yields a valid trace containing
    every pipeline stage, with per-batch dispatch/fetch spans, and the
    tracing adds no host fetches (fetch count == batches, and == the
    untraced run's count)."""
    metrics_mod.reset_for_tests()
    totals_off, fetches_off = _run_linear(tmp_path / "off", [])
    assert totals_off["batches"] == 4
    assert fetches_off == 4  # FetchPipeline: exactly one fetch per batch

    metrics_mod.reset_for_tests()
    trace_path = tmp_path / "run.trace"
    totals_on, fetches_on = _run_linear(
        tmp_path / "on", ["--trace", str(trace_path)]
    )
    assert totals_on["batches"] == totals_off["batches"]
    # ZERO extra host fetches from instrumentation (measurement integrity)
    assert fetches_on == fetches_off

    # the registry saw the same story
    reg = metrics_mod.get_registry().snapshot()
    assert reg["counters"]["fetch.count"] == 4
    assert reg["counters"]["pipeline.batches"] == 4
    assert reg["counters"]["pipeline.tweets"] == totals_on["count"]
    assert reg["counters"]["wire.bytes"] > 0
    assert reg["histograms"]["fetch.latency_s"]["count"] == 4

    # trace is valid (trace_report exit 0) and carries the stage set
    assert trace_report.main([str(trace_path)]) == 0
    summary = trace_report.summarize(
        trace_report.load_events(str(trace_path))
    )
    stages = set(summary["stages"])
    for stage in ("source_read", "parse", "featurize", "dispatch", "fetch",
                  "stats_publish"):
        assert stage in stages, f"missing stage {stage} in {stages}"
    # per-batch stages traced once per batch
    assert summary["stages"]["dispatch"]["count"] == 4
    assert summary["stages"]["fetch"]["count"] == 4
    # featurize spans carry bytes-on-wire (the bottleneck-ladder input)
    assert summary["stages"]["featurize"]["bytes"] > 0


def test_trace_off_leaves_no_file(tmp_path):
    metrics_mod.reset_for_tests()
    _run_linear(tmp_path, [])
    assert not list(tmp_path.glob("*.trace"))
    assert not trace.get().enabled


# ---------------------------------------------------------------------------
# PR 24: batch ids, compile spans, waits — and nothing at all while off


def _spans(path, name):
    return [e for e in trace_report.load_events(str(path))
            if e.get("ph") == "X" and e["name"] == name]


def test_every_published_batch_keeps_one_id_through_the_pipeline(tmp_path):
    """featurize, dispatch, fetch (a pool thread) and stats_publish of the
    n-th batch all carry batch == n, the scheduler's own count; and the
    fetch count is still one per batch with the new spans in the run."""
    metrics_mod.reset_for_tests()
    trace_path = tmp_path / "ids.trace"
    totals, fetches = _run_linear(tmp_path / "on", ["--trace", str(trace_path)])
    assert totals["batches"] == 4 and fetches == 4
    for stage in ("featurize", "dispatch", "fetch", "stats_publish"):
        ids = sorted(e["args"]["batch"] for e in _spans(trace_path, stage)
                     if "batch" in e.get("args", {}))
        assert ids == [1, 2, 3, 4], (stage, ids)
    # stats_publish keeps the batch's row count beside its id
    assert all(e["args"]["rows"] == 16
               for e in _spans(trace_path, "stats_publish"))
    # the ragged wire's bucket is data-dependent: warmup_compile compiles
    # nothing, and the step compiles ONCE, inside batch 1's dispatch, under
    # a name that no bucket or wire form changes
    (warm,) = _spans(trace_path, "warmup_compile")
    compiles = _spans(trace_path, "compile")
    assert not [e for e in compiles
                if e["args"]["during"] == "warmup_compile"]
    (step,) = [e for e in compiles if e["args"]["fun"] == "jit(train_step)"]
    (first,) = [e for e in _spans(trace_path, "dispatch")
                if e["args"]["batch"] == 1]
    assert step["args"]["during"] == "dispatch"
    assert first["ts"] <= step["ts"] + step["dur"] <= first["ts"] + first["dur"] + 1
    sig = step["args"]["signature"]
    assert (sig["rows"], sig["row_len"], sig["units"]) == (16, 64, "uint8")
    assert sig["wire"] and sig["units_len"] > 0


@pytest.mark.parametrize("flags, plane", [
    (["--numTextFeatures", "16384"], 2),   # Gram basis, short text: s8
    ([], -1),                              # the 1,004-dim dense model
])
def test_gram_plane_instant_once_per_batch(tmp_path, flags, plane):
    """PR 25: which plane each batch's Gram build took, from the quality
    vector the one fetch already carried, under the batch's id."""
    metrics_mod.reset_for_tests()
    trace_path = tmp_path / "plane.trace"
    totals, fetches = _run_linear(
        tmp_path / "on", ["--trace", str(trace_path)] + flags
    )
    assert totals["batches"] == 4 and fetches == 4
    marks = [e for e in trace_report.load_events(str(trace_path))
             if e.get("ph") == "i" and e["name"] == "gram_plane"]
    assert [e["args"]["batch"] for e in marks] == [1, 2, 3, 4]
    assert [e["args"]["plane"] for e in marks] == [plane] * 4


def test_compile_span_for_a_new_shape_and_not_for_its_repeat(tmp_path):
    import jax
    import numpy as np

    path = tmp_path / "c.trace"
    tr = trace.install(str(path))
    step = jax.jit(lambda x: x * 2.0 + 1.0)

    def dispatch(rows):
        with tr.batch_scope(rows), tr.span(
            "dispatch", signature=lambda: {"rows": rows, "wire": "test"}
        ):
            step(np.ones(rows, np.float32)).block_until_ready()

    dispatch(3)
    dispatch(3)   # the repeat: served by jit's cache, compiles nothing
    dispatch(5)
    step(np.ones(7, np.float32))   # outside any span
    trace.uninstall()
    compiles = _spans(path, "compile")
    by_rows = [e["args"]["signature"]["rows"] for e in compiles
               if e["args"]["during"] == "dispatch"]
    assert sorted(by_rows) == [3, 5]
    for e in compiles:
        assert e["args"]["seconds"] >= 0 and "cache_hit" in e["args"]
        assert e["dur"] == pytest.approx(e["args"]["seconds"] * 1e6, abs=100)
    (outside,) = [e for e in compiles if e["args"]["during"] == "startup"]
    assert outside["args"]["signature"] is None
    # the dispatch span's own args carry the id, not the signature
    assert {e["args"]["batch"] for e in _spans(path, "dispatch")} == {3, 5}


def _fill(q, n_items, rows):
    class Block:
        pass

    for _ in range(n_items):
        item = Block()
        item.rows = rows
        q.put(item)


@pytest.mark.parametrize("consumer_sleep_s, bound, waits", [
    (0.15, 16, True),     # bound = one batch, slow consumer: the producer waits
    (0.0, 16 * 64, False),  # room for everything: it never does
])
def test_intake_wait_only_when_the_producer_waited(
    tmp_path, consumer_sleep_s, bound, waits
):
    import threading
    import time

    from twtml_tpu.streaming.context import _RowCountQueue

    path = tmp_path / "w.trace"
    trace.install(str(path))
    q = _RowCountQueue()
    q.configure_bound(bound, "block")
    producer = threading.Thread(target=_fill, args=(q, 4, 16), daemon=True)
    producer.start()
    drained = 0
    deadline = time.monotonic() + 20.0
    while drained < 4 and time.monotonic() < deadline:
        time.sleep(consumer_sleep_s)
        drained += len(q.drain_rows(16))
    producer.join(timeout=10.0)
    assert not producer.is_alive() and drained == 4
    trace.uninstall()
    spans = _spans(path, "intake_wait")
    if not waits:
        assert spans == []
        return
    assert spans and all(e["args"]["rows"] == 16 for e in spans)
    assert sum(e["dur"] for e in spans) >= 0.1e6


def test_deliver_wait_only_when_the_result_was_not_ready(tmp_path):
    """FetchPipeline._emit_one spans ``deliver_wait`` (with the delivered
    batch's id) only if it had to wait for the oldest in-flight fetch."""
    import threading

    from twtml_tpu.apps.common import FetchPipeline

    gate = threading.Event()

    class Model:
        def step(self, batch):
            return batch

        def fetch_output(self, out):
            if out == "slow":
                gate.wait(10.0)
            return out

    delivered = []
    path = tmp_path / "d.trace"
    tr = trace.install(str(path))
    pipe = FetchPipeline(
        Model(), lambda out, batch, t, at_boundary: delivered.append(out),
        depth=4, deterministic=True,
    )
    try:
        for seq, batch in enumerate(["fast", "slow"], start=1):
            with tr.batch_scope(seq):
                pipe.on_batch(batch, 0.0)
        pipe._pending[0][0].result(timeout=10.0)   # batch 1 is ready
        threading.Timer(0.05, gate.set).start()
    finally:
        pipe.flush()
    trace.uninstall()
    assert delivered == ["fast", "slow"]
    (wait,) = _spans(path, "deliver_wait")
    assert wait["args"]["batch"] == 2 and wait["dur"] >= 0.02e6
    assert sorted(e["args"]["batch"] for e in _spans(path, "fetch")) == [1, 2]


def test_tracing_off_registers_no_listener_and_writes_nothing(tmp_path):
    import jax
    from jax._src import monitoring as mon

    def listening():
        return (trace._on_compile in mon.get_event_duration_listeners(),
                trace._on_cache_hit in mon.get_event_listeners())

    assert listening() == (False, False)
    jax.jit(lambda x: x - 3.0)(1.0)   # compiles; nobody is told
    assert not list(tmp_path.iterdir())
    path = tmp_path / "on.trace"
    trace.install(str(path))
    assert listening() == (True, True)
    trace.install(str(path))          # a second install registers no second
    assert mon.get_event_duration_listeners().count(trace._on_compile) == 1
    trace.uninstall()
    assert listening() == (False, False)
    jax.jit(lambda x: x - 4.0)(1.0)
    assert _spans(path, "compile") == []
