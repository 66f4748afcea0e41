"""Share of the traced run's batches whose Gram matrix was built on a fast
plane: the ``gram_plane`` instants of the program's span file (one per
delivered batch, ``telemetry/modelwatch.py``) whose ``plane`` is 1 (bf16)
or 2 (s8) and not 0 (the exact f32 plane), out of all of them. ``plane``
is the index ``ops/gram.text_gram``'s gate took inside the step, fetched
with the batch's statistics; nothing recomputes it. Read from the file
itself (``benchmark/trace_files.py``): ``art["spans"]`` holds no instants
and ``art`` no window times, so the share is over every batch of the window
run — its warm-up pass, the window and the stop — which all take the plane
their text asks for. A program without the instant (from before PR 25)
gives None."""

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    planes = [ev["args"]["plane"] for ev in spans.load_events(path)
              if ev.get("ph") == "i" and ev.get("name") == "gram_plane"
              and "plane" in (ev.get("args") or {})]
    if not planes:
        return None
    return 100.0 * sum(1 for p in planes if p >= 1) / len(planes)
