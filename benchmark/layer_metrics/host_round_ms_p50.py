"""The scheduler's round, in ms: the median distance between consecutive
``deliver_round`` instants of the program's span file (PR 39's instant, one
per call of ``FetchPipeline.on_batch`` that got as far as its dispatch,
stamped at the round's end on the scheduler's thread). One round dispatches
one batch, so 2,048 ÷ this is the pace the run kept: in a device-paced cell
the round waits for the device and this reads the device's step (compare
``step_device_ms``); in the host-paced cell ``hash2e18-ab4-trimmed-280`` it
reads the host's own round (~17 ms over a ~12 ms step), the pace that cell's
rate said while it was on the rate's list (benchmark/README.md has the rule).
The MEDIAN, so the eighth update's longer rounds and a stall move it little:
it sits under the mean round where those are many. Recorded, not gated. Read
from the file itself as ``paired_delivery_share`` is (instants are not in
``art["spans"]``), so it is over every round of the window run. A program
without the instant gives None."""

import statistics

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    ends = sorted(float(ev["ts"]) for ev in spans.load_events(path)
                  if ev.get("ph") == "i" and ev.get("name") == "deliver_round"
                  and "ts" in ev)
    if len(ends) < 2:
        return None
    return statistics.median(b - a for a, b in zip(ends, ends[1:])) / 1e3
