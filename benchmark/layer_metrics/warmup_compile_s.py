"""Seconds of the window run spent compiling or fetching compiled programs
from the persistent cache before and in the window: every ``compile`` span
of the program's span file (one per ``jax.monitoring`` backend-compile
event, telemetry/trace.py). Read from the file itself
(``benchmark/trace_files.py``): those spans start before the window, so
``art["spans"]`` does not hold them. Prints what compiled, during which
span and whether the persistent cache served it."""

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    compiles = [ev for ev in spans.load_events(path)
                if ev.get("ph") == "X" and ev.get("name") == "compile"]
    if not compiles:
        return None
    for ev in compiles:
        a = ev.get("args") or {}
        if a.get("seconds", 0.0) >= 0.05:
            print(f"[bench] compile: {a.get('seconds', 0.0):7.3f} s "
                  f"{a.get('fun')} during {a.get('during')}, "
                  f"{'from the persistent cache' if a.get('cache_hit') else 'compiled'}"
                  f", {a.get('signature')}", flush=True)
    return sum(float(ev.get("dur", 0.0)) for ev in compiles) / 1e6
