"""Device time by STAGE of the train step, and each idle gap put down to
what the host was doing — from one ``jax.profiler`` trace.

The program names the stages of its step with ``jax.named_scope``
(``twtml_tpu/models/sgd.STAGE_SCOPES``): every device operation's op-name
path, e.g. ``jit(train_step)/cond/branch_1_fun/gram_matmul/dot_general``,
then holds the stage it belongs to, whatever number the compiler gave its
fusion. On each ``/device:TPU:n`` plane's ``XLA Ops`` line every nanosecond
goes to the INNERMOST event covering it (operations nest: a ``conditional``
or a ``while`` contains what it runs), and that event's stage is the first
scope name on its path, else ``other``. So stages never overlap, nothing
nested counts twice, and the stages sum to the busy time that
``reduce_xplane`` reports (the union of the same intervals).

Operations the COMPILER made carry no op-name at all: a copy, a sort, the
expanded scatter of the exact Gram plane (13 ms a batch, PERF.md §5). Such
an operation is a helper of what follows it, so it takes the stage of the
next operation WITH an op-name inside the same enclosing operation (the
same ``conditional``, ``while`` or, on the top level, the same run of the
program on the ``XLA Modules`` line); where none follows it stays ``other``.

The program's ``--trace`` spans are also ``TraceAnnotation``s, so the same
trace holds them on its ``/host:CPU`` plane, on the device events' clock.
Each idle gap of a device plane is put down to the program span open on the
scheduler's thread (the host line that holds the ``dispatch`` spans) for
most of the gap, innermost first, else ``no_span``.

The op-name path is a stat of the event's METADATA, which
``jax.profiler.ProfileData`` does not hand out (it gives an event's own
stats only), so this module reads the ``.xplane.pb`` itself: a protobuf
wire-format reader for the six messages of ``xplane.proto``, nothing
imported. ``benchmark/tests/test_stage_times.py`` holds it to
``ProfileData`` event by event on a trace recorded on the chip.

How to add a device-stage metric: name the stage in the program with
``jax.named_scope("<stage>")`` around the code (a new name also goes into
``STAGE_SCOPES`` there and ``SCOPES`` here); add
``benchmark/layer_metrics/stage_ms.<stage>.py`` with
``read = stage_times.reader("<stage>")``; append the ``per_layer`` entry
(``source: device_trace``, ``layer: device_step``, ``moves:
ingest_tweets_per_s``) to ``BENCHMARK.json``; and, if the stage was reported
under ``other`` until now, say in PERF.md that ``stage_ms.other`` shrank by it.

``python -m benchmark.stage_times FILE [SPANS.json]`` prints the reduction.
"""

from __future__ import annotations

import bisect
import functools
import json
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
OP_NAME_STAT = "tf_op"   # the profiler's name for an HLO op's op_name
# the program's scope names (twtml_tpu/models/sgd.STAGE_SCOPES), and the
# stages reported: ``unpack``, ``quality`` and the unscoped are ``other``
SCOPES = ("unpack", "repad", "hash", "predict", "gram_count", "gram_matmul",
          "dual_loop", "writeback", "quality")
STAGES = ("repad", "hash", "predict", "gram_count", "gram_matmul",
          "dual_loop", "writeback", "other")
SCHEDULER_SPAN = "dispatch"
NO_SPAN = "no_span"
TOP_GAPS = 10


# --------------------------------------------------------------------------
# xplane.proto, read from the wire (field numbers in the comments)


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) for each field of the message in buf[i:end]:
    an int for a varint, ``(start, end)`` for a length-delimited field;
    fixed-width fields (doubles) are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, (i, i + size)
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} is not in xplane.proto")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span) -> tuple:
    """A ``map<int64, Message>`` entry: (key = 1, value = 2's span)."""
    key, value = 0, (0, 0)
    for num, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _event_metadata(buf, span) -> dict:
    """XEventMetadata: name = 2, stats = 5 (XStat: metadata_id = 1,
    str_value = 5, ref_value = 7)."""
    out = {"name": "", "stats": []}
    for num, v in _fields(buf, *span):
        if num == 2:
            out["name"] = _text(buf, v)
        elif num == 5:
            stat = {"id": 0, "str": None, "ref": None}
            for n2, v2 in _fields(buf, *v):
                if n2 == 1:
                    stat["id"] = v2
                elif n2 == 5:
                    stat["str"] = _text(buf, v2)
                elif n2 == 7:
                    stat["ref"] = v2
            out["stats"].append(stat)
    return out


def _line(buf, span) -> dict:
    """XLine: name = 2, timestamp_ns = 3, events = 4 (XEvent: metadata_id
    = 1, offset_ps = 2, duration_ps = 3). Events come out as
    ``(start_ps, end_ps, metadata_id)`` on the trace's clock."""
    name, t0_ns, raw = "", 0, []
    for num, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            t0_ns = v
        elif num == 4:
            raw.append(v)
    events = []
    for ev in raw:
        meta = offset = dur = 0
        for n2, v2 in _fields(buf, *ev):
            if n2 == 1:
                meta = v2
            elif n2 == 2:
                offset = v2
            elif n2 == 3:
                dur = v2
        start = t0_ns * 1000 + offset
        events.append((start, start + dur, meta))
    return {"name": name, "events": events}


def read_xspace(path: str) -> list:
    """The planes of an ``.xplane.pb``: ``{"name", "lines": [{"name",
    "events": [(start_ps, end_ps, metadata_id)]}], "event_name": {id: str},
    "op_name": {id: str}}`` — ``op_name`` for the events whose metadata
    carries one (XSpace: planes = 1; XPlane: name = 2, lines = 3,
    event_metadata = 4, stat_metadata = 5)."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    planes = []
    for num, span in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, lines, metas, stat_names = "", [], {}, {}
        for n2, v in _fields(buf, *span):
            if n2 == 2:
                name = _text(buf, v)
            elif n2 == 3:
                lines.append(v)
            elif n2 == 4:
                key, value = _map_entry(buf, v)
                metas[key] = _event_metadata(buf, value)
            elif n2 == 5:
                key, value = _map_entry(buf, v)
                stat_names[key] = next(
                    (_text(buf, v3) for n3, v3 in _fields(buf, *value)
                     if n3 == 2), "")
        op_name = {}
        for key, meta in metas.items():
            for stat in meta["stats"]:
                if stat_names.get(stat["id"]) == OP_NAME_STAT:
                    op_name[key] = (stat["str"] if stat["str"] is not None
                                    else stat_names.get(stat["ref"], ""))
        planes.append({
            "name": name,
            "lines": [_line(buf, ln) for ln in lines],
            "event_name": {k: m["name"] for k, m in metas.items()},
            "op_name": op_name,
        })
    return planes


# --------------------------------------------------------------------------
# the reduction


@functools.lru_cache(maxsize=None)
def stage_of(op_name: str) -> str:
    """The first scope name on an op-name path, else ``other``."""
    for part in op_name.rstrip(":").split("/"):
        if part in SCOPES:
            return part
    return "other"


def label(ops: list, modules: list, op_name: dict) -> list:
    """``[(start, end, metadata id)]`` of one ``XLA Ops`` line →
    ``[(start, end, stage)]``: an operation's own stage by its op-name, and
    for one without any the stage of the next named operation in the same
    enclosing operation or program run (``modules``: ``[(start, end)]``)."""
    items = sorted([(s, -e, False, 0) for s, e in modules]
                   + [(s, -e, True, m) for s, e, m in ops])
    out: list = []
    top = [float("inf"), []]    # [end, indices of helpers awaiting a stage]
    stack: list = []
    for start, neg_end, is_op, meta in items:
        while stack and stack[-1][0] <= start:
            stack.pop()         # helpers nothing followed stay ``other``
        if is_op:
            frame = stack[-1] if stack else top
            name = op_name.get(meta, "")
            stage = stage_of(name)
            stage = stage if stage in STAGES else "other"
            if name:
                for i in frame[1]:
                    out[i] = (out[i][0], out[i][1], stage)
                frame[1].clear()
            else:
                frame[1].append(len(out))
            out.append((start, -neg_end, stage))
        stack.append([-neg_end, []])
    return out


def exclusive(events: list) -> tuple:
    """``[(start, end, label)]`` → (``{label: time}`` with every instant
    given to the event that STARTED LAST among those covering it, the
    intervals in which none does ``[(start, end)]``). Events may nest or
    overlap in any way; the times sum to the union of the intervals."""
    credit: dict = {}
    gaps: list = []
    stack: list = []   # open events, latest started last
    cursor = None

    def advance(to: int) -> None:
        nonlocal cursor
        while cursor < to:
            while stack and stack[-1][1] <= cursor:
                stack.pop()
            if not stack:
                return
            upto = min(to, stack[-1][1])
            credit[stack[-1][2]] = credit.get(stack[-1][2], 0) + upto - cursor
            cursor = upto

    for ev in sorted(events, key=lambda e: (e[0], -e[1])):
        if ev[1] <= ev[0]:
            continue
        if cursor is None:
            cursor = ev[0]
        advance(ev[0])
        if cursor < ev[0]:
            gaps.append((cursor, ev[0]))
            cursor = ev[0]
        stack.append(ev)
    if cursor is not None:
        advance(max(e[1] for e in events))
    return credit, gaps


def _scheduler_spans(planes: list, span_names: set) -> list:
    """The program's spans on the scheduler's thread: of the host plane's
    lines, the one with the most ``dispatch`` events, as ``[(start, end,
    name)]`` sorted by start."""
    best: list = []
    most = 0
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            mine = [(s, e, plane["event_name"].get(m, ""))
                    for s, e, m in line["events"]]
            mine = [ev for ev in mine if ev[2] in span_names]
            n = sum(1 for ev in mine if ev[2] == SCHEDULER_SPAN)
            if n > most:
                best, most = mine, n
    return sorted(best)


def _attribute(gap: tuple, spans: list, starts: list, longest: int) -> str:
    """The span open on the scheduler's thread for most of the gap."""
    lo = bisect.bisect_left(starts, gap[0] - longest)
    hi = bisect.bisect_right(starts, gap[1])
    clipped = [(max(s, gap[0]), min(e, gap[1]), name)
               for s, e, name in spans[lo:hi] if e > gap[0] and s < gap[1]]
    credit, _ = exclusive(clipped) if clipped else ({}, [])
    credit[NO_SPAN] = (gap[1] - gap[0]) - sum(credit.values())
    return max(credit, key=credit.get)


def reduce(path: str, span_names=()) -> "dict | None":
    """None when no device plane ran anything. Times in seconds, per chip:
    the mean over the chips that ran anything, as ``reduce_xplane`` has it.
    ``span_names``: the names of the program's spans (from its span file);
    without them every gap is ``no_span``."""
    planes = read_xspace(path)
    stage_ps = dict.fromkeys(STAGES, 0)
    gaps: list = []
    chips = scoped = 0
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        modules = [(s, e) for line in plane["lines"]
                   if line["name"] == MODULES_LINE
                   for s, e, _m in line["events"]]
        events = []
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                events += label(line["events"], modules, plane["op_name"])
                scoped += sum(1 for _s, _e, m in line["events"] if stage_of(
                    plane["op_name"].get(m, "")) != "other")
        if not events:
            continue
        chips += 1
        credit, plane_gaps = exclusive(events)
        for stage, ps in credit.items():
            stage_ps[stage] += ps
        gaps += plane_gaps
    if not chips:
        return None
    spans = _scheduler_spans(planes, set(span_names))
    starts = [s for s, _e, _n in spans]
    longest = max((e - s for s, e, _n in spans), default=0)
    by_span: dict = {}
    named = []
    for gap in gaps:
        name = _attribute(gap, spans, starts, longest) if spans else NO_SPAN
        by_span[name] = by_span.get(name, 0) + gap[1] - gap[0]
        named.append((gap[1] - gap[0], name))
    return {
        "chips": chips,
        "scoped_events": scoped,
        "busy_s": sum(stage_ps.values()) / chips / 1e12,
        "stage_s": {k: v / chips / 1e12 for k, v in stage_ps.items()},
        "idle_s": sum(by_span.values()) / chips / 1e12,
        "idle_by_span_s": {k: v / chips / 1e12 for k, v in
                           sorted(by_span.items(), key=lambda kv: -kv[1])},
        "scheduler_spans": len(spans),
        "idle_gaps": [[name, ps / 1e12] for ps, name in
                      sorted(named, reverse=True)[:TOP_GAPS]],
    }


# --------------------------------------------------------------------------
# what the per-layer readers share

_cache: dict = {}


def of_live_run() -> "dict | None":
    """The reduction of the live run's profile with its span file's names
    (``trace_files``), made once per process."""
    from . import spans, trace_files

    path = trace_files.xplane_file()
    if path is None:
        return None
    if path not in _cache:
        span_file = trace_files.span_file()
        names = {ev.get("name") for ev in spans.load_events(span_file)
                 if ev.get("ph") == "X"} if span_file else set()
        _cache[path] = reduce(path, names)
    return _cache[path]


def reader(stage: str):
    """``read(art)`` of ``stage_ms.<stage>``: the stage's device time per
    batch, over the batches ``step_device_ms`` divides by. None where the
    trace carries no scope name at all (a program from before the scopes)."""

    def read(art):
        profile = art.get("profile")
        if not profile or not profile.get("batches"):
            return None
        red = of_live_run()
        if red is None or not red["scoped_events"]:
            return None
        return 1e3 * red["stage_s"][stage] / profile["batches"]

    return read


if __name__ == "__main__":
    names = set()
    if len(sys.argv) > 2:
        from . import spans as _spans

        names = {ev.get("name") for ev in _spans.load_events(sys.argv[2])}
    print(json.dumps(reduce(sys.argv[1], names), indent=1))
