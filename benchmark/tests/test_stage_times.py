"""``benchmark/stage_times.py`` and the readers PR 24 added, held to (1) a
small scoped trace recorded on the chip (``testdata/scoped.xplane.pb``:
three batches of the real train step at 2^18 dims, driven through the
program's own spans, under the harness's profiler options) and the numbers
recorded beside it, (2) ``jax.profiler.ProfileData`` event by event, (3)
hand-made cases. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os

import pytest

from benchmark import manifest, reduce_xplane, spans, stage_times

DATA = os.path.join(manifest.HERE, "testdata")
TRACE = os.path.join(DATA, "scoped.xplane.pb")
NEW_METRICS = [
    *(f"stage_ms.{s}" for s in stage_times.STAGES),
    "idle_attributed_share", "source_loop_us_per_tweet",
    "intake_wait_ms_per_batch", "deliver_wait_ms_per_batch",
    "warmup_compile_s",
]


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(DATA, "scoped.expected.json"), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def reduced(expected):
    return stage_times.reduce(TRACE, expected["span_names"])


def test_stages_sum_to_the_busy_time(reduced):
    """The eight stages partition the union of the device-op intervals:
    their sum is ``reduce_xplane``'s busy time within 0.1%."""
    busy = reduce_xplane.reduce(TRACE)["busy_s"]
    assert sum(reduced["stage_s"].values()) == pytest.approx(busy, rel=1e-3)
    assert reduced["busy_s"] == pytest.approx(busy, rel=1e-3)


def test_nothing_nested_counts_twice(reduced):
    """Summing durations counts a ``conditional`` and the matmul inside it
    twice (the ledger's ``device_ops`` did); the stages do not."""
    planes = stage_times.read_xspace(TRACE)
    summed = sum(
        (e - s) / 1e12 for p in planes if p["name"].startswith("/device:")
        for ln in p["lines"] if ln["name"] == stage_times.OPS_LINE
        for s, e, _m in ln["events"]
    )
    assert summed > 1.05 * reduced["busy_s"]      # the trace does nest
    assert sum(reduced["stage_s"].values()) <= reduced["busy_s"] * 1.000001


def test_recorded_numbers(reduced, expected):
    """Every stage the bf16 plane runs is there, under its name, with the
    seconds recorded on the chip; the gaps the recording made by sleeping
    in ``featurize`` are put down to it."""
    for stage, want in expected["stage_s"].items():
        assert reduced["stage_s"][stage] == pytest.approx(want, rel=1e-9, abs=1e-12)
    for stage in ("repad", "predict", "gram_count", "gram_matmul",
                  "dual_loop", "writeback"):
        assert reduced["stage_s"][stage] > 0, stage
    assert reduced["stage_s"]["other"] < 0.1 * reduced["busy_s"]
    assert reduced["scoped_events"] == expected["scoped_events"]
    assert [n for n, _ in reduced["idle_gaps"][:2]] == ["featurize"] * 2
    assert reduced["idle_by_span_s"] == pytest.approx(expected["idle_by_span_s"])


def test_wire_reader_agrees_with_profile_data():
    """The protobuf reader against jax's own, event by event."""
    from jax.profiler import ProfileData

    mine = {p["name"]: p for p in stage_times.read_xspace(TRACE)}
    seen = 0
    for plane in ProfileData.from_file(TRACE).planes:
        for theirs, ours in zip(plane.lines, mine[plane.name]["lines"]):
            assert theirs.name == ours["name"]
            events = list(theirs.events)
            assert len(events) == len(ours["events"])
            for ev, (start, end, meta) in zip(events, ours["events"]):
                assert ev.name == mine[plane.name]["event_name"][meta]
                assert ev.start_ns == pytest.approx(start / 1e3, abs=1.0)
                assert ev.duration_ns == pytest.approx((end - start) / 1e3, abs=1.0)
                seen += 1
    assert seen > 100


def test_exclusive_gives_each_instant_to_the_innermost_event():
    credit, gaps = stage_times.exclusive([
        (0, 100, "cond"), (10, 60, "matmul"), (20, 30, "inner"),
        (60, 90, "count"), (120, 130, "late"), (125, 140, "overlaps"),
    ])
    assert credit == {"cond": 20, "matmul": 40, "inner": 10, "count": 30,
                      "late": 5, "overlaps": 15}
    assert gaps == [(100, 120)]
    assert sum(credit.values()) == reduce_xplane.union_ns(
        [(0, 100), (10, 60), (20, 30), (60, 90), (120, 130), (125, 140)])[0]


def test_a_helper_without_a_name_takes_the_stage_of_what_it_feeds():
    """The compiler's own operations (no op-name): the stage of the next
    NAMED operation in the same enclosing operation or program run; with
    none following, ``other``. The exact plane's expanded scatter is one."""
    op_name = {
        1: "jit(train_step)/cond/branch_0_fun/gram_count/scatter-add:",
        2: "jit(train_step)/cond",
        3: "jit(train_step)/cond/branch_0_fun/gram_matmul/dot_general:",
        4: "jit(train_step)/repad/gather:",
        5: "jit(train_step)/unpack/slice:",
    }
    ops = [
        (5, 8, 0), (8, 10, 5),            # a copy before ``unpack``: other
        (100, 400, 2),                    # the conditional, and inside it:
        (110, 150, 0), (150, 160, 0),     #   sort + expanded scatter ...
        (160, 200, 1),                    #   ... feed the named reshape
        (200, 300, 3), (300, 350, 0),     #   nothing follows the last one
        (400, 410, 0),                    # nothing follows in THIS run
        (1010, 1020, 0), (1020, 1100, 4), # the next run's first helper
    ]
    got = stage_times.label(ops, [(0, 1000), (1000, 2000)], op_name)
    assert [st for _s, _e, st in got] == [
        "other", "other", "other", "gram_count", "gram_count", "gram_count",
        "gram_matmul", "other", "other", "repad", "repad",
    ]
    assert [(s, e) for s, e, _st in got] == sorted((s, e) for s, e, _m in ops)


@pytest.mark.parametrize("gap, want", [
    ((100, 200), "featurize"),        # inside one span
    ((290, 330), "dispatch"),         # the innermost of two open spans
    ((395, 440), "no_span"),          # mostly under nothing
    ((395, 404), "deliver_wait"),     # mostly under a span that then ends
])
def test_gap_goes_to_the_span_open_for_most_of_it(gap, want):
    spans_ = sorted([(50, 250, "featurize"), (250, 400, "deliver_wait"),
                     (280, 340, "dispatch")])
    starts = [s for s, _e, _n in spans_]
    assert stage_times._attribute(gap, spans_, starts, 200) == want


def test_stage_of_takes_the_first_scope_on_the_path():
    assert stage_times.stage_of(
        "jit(train_step)/cond/branch_1_fun/gram_matmul/dot_general:") == "gram_matmul"
    assert stage_times.stage_of("jit(train_step)/dual_loop/while/body/writeback/x") == "dual_loop"
    assert stage_times.stage_of("jit(train_step)/cond") == "other"
    assert stage_times.stage_of("") == "other"


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_returns_none_without_its_input(metric, tmp_path, monkeypatch):
    """On an ``art`` without its input — a program from before the spans
    and scopes, no live trace files — a reader returns None and raises
    nothing: the line then leaves the metric out."""
    from benchmark import harness

    monkeypatch.setattr(harness, "WORK", str(tmp_path))   # no live run
    monkeypatch.setattr(stage_times, "_cache", {})
    read = manifest.load_module(manifest.layer_metric_path(metric)).read
    old_spans = {"dispatch": {"count": 3, "total_ms": 2.0},
                 "stats_publish": {"count": 3, "total_ms": 6.0}}
    for art in ({}, {"spans": old_spans, "tweets": 6144,
                     "profile": {"busy_s": 0.1, "window_s": 0.2,
                                 "batches": 3.0}}):
        assert read(art) is None


def test_span_readers_on_a_window_of_spans():
    stages = spans.summarize([
        {"name": "stats_publish", "ph": "X", "ts": 10, "dur": 1000},
        {"name": "stats_publish", "ph": "X", "ts": 20, "dur": 1000},
        {"name": "deliver_wait", "ph": "X", "ts": 30, "dur": 9000,
         "args": {"batch": 7}},
        {"name": "source_lines", "ph": "X", "ts": 40, "dur": 5000,
         "args": {"lines": 512, "bytes": 262144}},
        {"name": "source_recv", "ph": "X", "ts": 40, "dur": 1000,
         "args": {"bytes": 262144}},
    ], 0.0, 1.0)
    art = {"spans": stages, "tweets": 1000}

    def read(metric):
        return manifest.load_module(manifest.layer_metric_path(metric)).read(art)

    assert read("deliver_wait_ms_per_batch") == pytest.approx(4.5)
    assert read("intake_wait_ms_per_batch") == 0.0   # has the spans, never waited
    assert read("source_loop_us_per_tweet") == pytest.approx(4.0)
