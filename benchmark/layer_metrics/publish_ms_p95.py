"""The publisher's slow updates, in ms: the 95th percentile of the durations
of the program's ``stats_publish`` spans (one per ``SessionStats.update``:
``Stats`` and ``Series``, and whatever periodic frame that update carries;
``telemetry/session_stats.py``). ``publish_ms_per_batch`` is their MEAN, which
says how much the publisher sends; this says how unevenly: while the eighth
update sent every observability frame in one round (until PR 53), one span in
eight was ~7 ms longer than the others and this read that burst (~11.7 ms
over a plain ~4.1 in ``hash2e18-ab4-trimmed-280``); with the frames sent one
an update it reads a plain update plus one frame. In a host-paced cell the
scheduler's gap holds the span, so this is the part of ``batch_gap_ms_p95``
the publisher owes. Read from the file itself as ``host_round_ms_p50`` reads
its instants, so it is over every update of the window run; the log line also
gives the largest ``posts`` (the requests one update sent) where the spans
carry it. Nearest rank on the sorted durations: no interpolation between a
plain update and a burst. A program without the span gives None."""

import math

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    found = [ev for ev in spans.load_events(path)
             if ev.get("ph") == "X" and ev.get("name") == "stats_publish"
             and "dur" in ev]
    if not found:
        return None
    durs = sorted(float(ev["dur"]) / 1e3 for ev in found)
    p95 = durs[math.ceil(0.95 * len(durs)) - 1]
    posts = [int(ev["args"]["posts"]) for ev in found
             if "posts" in (ev.get("args") or {})]
    print(f"[bench] publish_ms_p95: {p95:.3f} ms over {len(durs)} updates "
          f"(p50 {durs[(len(durs) - 1) // 2]:.3f}, max {durs[-1]:.3f}); "
          f"most posts in one update: {max(posts) if posts else 'not carried'}")
    return p95
