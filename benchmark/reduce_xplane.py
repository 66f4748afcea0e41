"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to device busy
time, the operations that took most of it and the longest idle gaps.

Device planes are the planes named ``/device:<KIND>:<n>`` (``/host:*`` is
the host). On such a plane the line ``XLA Ops`` holds one event per executed
operation; busy time is the UNION of those intervals (operations nest and
overlap, so durations are never summed for it), taken per chip and averaged
over the chips that ran anything. The traced window is first event start to
last event end over all device planes. Reads the file with nothing but JAX
(``jax.profiler.ProfileData``).

``python -m benchmark.reduce_xplane FILE`` prints the reduction;
``selfcheck()`` reduces the small recorded trace kept in
``benchmark/testdata/`` and compares with the numbers recorded beside it.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
TOP = 10
NAME_CHARS = 160
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo: str) -> str:
    """An operation under the name XLA gave it (its HLO instruction), with
    the layout annotations dropped and cut to a readable length."""
    return _LAYOUT.sub("", hlo)[:NAME_CHARS]


def union_ns(intervals: list) -> tuple:
    """``[(start, end), ...]`` → (covered length, merged intervals)."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def find_xplane(trace_dir: str) -> "str | None":
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return files[-1] if files else None


def reduce(path: str) -> "dict | None":
    """None when the trace holds no device plane with an operation (a CPU
    run): no device number is ever made from host planes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips = []
    by_name: dict = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        spans = []
        for ln in lines:
            for ev in ln.events:
                if ev.duration_ns <= 0:
                    continue
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                name = short_name(ev.name)
                by_name[name] = by_name.get(name, 0.0) + ev.duration_ns
        if spans:
            busy, merged = union_ns(spans)
            chips.append({"plane": plane.name, "busy_ns": busy,
                          "merged": merged, "events": len(spans)})
    if not chips:
        return None
    t0 = min(c["merged"][0][0] for c in chips)
    t1 = max(c["merged"][-1][1] for c in chips)
    gaps = []
    for c in chips:
        m = c["merged"]
        gaps += [m[i + 1][0] - m[i][1] for i in range(len(m) - 1)]
        gaps += [m[0][0] - t0, t1 - m[-1][1]]
    gaps = sorted((g for g in gaps if g > 0), reverse=True)[:TOP]
    n = len(chips)
    return {
        "busy_s": sum(c["busy_ns"] for c in chips) / n / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "per_chip": [
            {"plane": c["plane"], "busy_s": c["busy_ns"] / 1e9,
             "events": c["events"]} for c in chips
        ],
        # per chip: an operation's summed durations, mean over chips
        "device_ops": [
            [name, ns / n / 1e9] for name, ns in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
        ],
        # the host's spans are on another clock until the program writes
        # them into the profiler's trace (the `tracing` issue, PERF.md §7)
        "idle_gaps": [["unattributed", g / 1e9] for g in gaps],
    }


def describe(path: str) -> None:
    """Planes, lines and event counts: what to look at by hand first."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for ln in plane.lines:
            evs = list(ln.events)
            head = [(e.name[:40], round(e.duration_ns)) for e in evs[:3]]
            print(f"   line {ln.name!r}: {len(evs)} events, first {head}")


def selfcheck() -> None:
    """Reduce the recorded trace and compare with the recorded numbers."""
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
    with open(os.path.join(here, "small.expected.json"), encoding="utf-8") as fh:
        want = json.load(fh)
    got = reduce(os.path.join(here, "small.xplane.pb"))
    if got is None:
        raise AssertionError("the recorded trace reduced to no device plane")
    for key in ("busy_s", "window_s"):
        if abs(got[key] - want[key]) > 1e-9:
            raise AssertionError(f"{key}: {got[key]!r} != recorded {want[key]!r}")
    if [n for n, _ in got["device_ops"]] != [n for n, _ in want["device_ops"]]:
        raise AssertionError("top device operations differ from the recording")
    if not 0 < got["busy_s"] <= got["window_s"]:
        raise AssertionError("busy time must lie in (0, window]")
    # the union, on a hand-made case: nesting and overlap count once
    if union_ns([(0, 10), (2, 5), (8, 12), (20, 21)])[0] != 13:
        raise AssertionError("union_ns is wrong")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--describe":
        describe(sys.argv[2])
    else:
        print(json.dumps(reduce(sys.argv[1]), indent=1))
