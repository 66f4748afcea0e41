"""Share of the device's idle time in the profiled stretch that falls under
a named span of the program: each gap between device operations is put down
to the ``--trace`` span open on the scheduler's thread for most of it
(``benchmark/stage_times.py``; the spans are on the profile's host plane as
``TraceAnnotation``s). None where the profile holds no program span (a
program from before the annotations). Prints where the idle time went."""

from benchmark import stage_times


def read(art):
    red = stage_times.of_live_run() if art.get("profile") else None
    if not red or not red["scheduler_spans"] or not red["idle_s"]:
        return None
    print(f"[bench] idle: {red['idle_s'] * 1e3:.3f} ms per chip in the "
          f"profile, by the scheduler's open span: "
          + ", ".join(f"{k} {v * 1e3:.3f} ms"
                      for k, v in red["idle_by_span_s"].items())
          + "; longest gaps: "
          + ", ".join(f"{k} {v * 1e6:.1f} us" for k, v in red["idle_gaps"]),
          flush=True)
    unnamed = red["idle_by_span_s"].get(stage_times.NO_SPAN, 0.0)
    return 100.0 * (red["idle_s"] - unnamed) / red["idle_s"]
