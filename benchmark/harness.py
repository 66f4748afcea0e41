"""What every driver kind shares: child processes, the compile counter, the
device's identity and memory, the work directory, percentiles and the
reading of the per-layer metrics. Imports jax only inside the functions
that need the device (children and the lint never do)."""

from __future__ import annotations

import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

from . import manifest

ROOT = manifest.ROOT
WORK = os.path.join(ROOT, ".bench_work")   # listed in .gitignore
CLOSED = "http://127.0.0.1:9"              # --lightning: nothing listens


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def mark(what: str, t_start: float) -> None:
    """One line of the set-up's timeline: seconds since process start."""
    say(f"set-up +{time.monotonic() - t_start:6.2f} s: {what}")


class Child:
    """``python -m benchmark.<module>`` as a child that never imports jax.
    Its stdout is JSON lines; a reader thread keeps them in ``records`` and
    wakes waiters. ``stop()`` ends it and waits until it has gone."""

    def __init__(self, module: str, args: list, name: str = ""):
        self.name = name or module
        self.records: list = []
        self.cond = threading.Condition()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", f"benchmark.{module}", *map(str, args)],
            cwd=ROOT, stdout=subprocess.PIPE, stdin=subprocess.DEVNULL,
            text=True, bufsize=1,
        )
        self._reader = threading.Thread(
            target=self._read, name=f"bench-{self.name}", daemon=True
        )
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            with self.cond:
                self.records.append(rec)
                self.cond.notify_all()

    def wait_for(self, pred, timeout: float):
        """The first record ``pred`` accepts; raises when the child dies or
        the time runs out first."""
        deadline = time.monotonic() + timeout
        seen = 0
        with self.cond:
            while True:
                for rec in self.records[seen:]:
                    if pred(rec):
                        return rec
                seen = len(self.records)
                left = deadline - time.monotonic()
                if left <= 0 or self.proc.poll() is not None:
                    raise RuntimeError(
                        f"child {self.name}: no answer within {timeout:.0f} s "
                        f"(exit status {self.proc.poll()})"
                    )
                self.cond.wait(min(left, 0.5))

    def ready(self, timeout: float = 120.0) -> dict:
        return self.wait_for(lambda r: "ready" in r, timeout)

    def snapshot(self) -> list:
        with self.cond:
            return list(self.records)

    def stop(self, timeout: float = 20.0) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=5.0)
        self.proc.stdout.close()


class CompileCounter:
    """Backend compilations as ``jax.monitoring`` reports them, stamped on
    the monotonic clock. A program fetched from the persistent cache counts
    too: inside a window either one is a stall."""

    _instance = None

    def __init__(self):
        self.events: list = []   # (t_monotonic, seconds)
        self.cache_hits = 0

    @classmethod
    def install(cls) -> "CompileCounter":
        if cls._instance is None:   # listeners cannot be unregistered
            import jax.monitoring as mon

            inst = cls._instance = cls()

            def on_duration(event: str, secs: float, **_kw) -> None:
                if event == "/jax/core/compile/backend_compile_duration":
                    inst.events.append((time.monotonic(), secs))

            def on_event(event: str, **_kw) -> None:
                if event == "/jax/compilation_cache/cache_hits":
                    inst.cache_hits += 1

            mon.register_event_duration_secs_listener(on_duration)
            mon.register_event_listener(on_event)
        return cls._instance

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in list(self.events) if t0 <= t < t1)

    def last(self) -> float:
        ev = list(self.events)
        return ev[-1][0] if ev else 0.0

    def seconds(self) -> float:
        return sum(s for _, s in list(self.events))


def fresh_dir(*parts: str) -> str:
    path = os.path.join(WORK, *parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def place_runtime_env() -> dict:
    """The TPU runtime's settings every run starts under
    (``runtime_env.json`` says what each is for and what it was measured
    at), unless the machine placed them. The runtime reads them once, as the
    first device is asked for."""
    table = manifest.load_json(os.path.join(manifest.HERE, "runtime_env.json"))
    return {k: os.environ.setdefault(k, v) for k, v in table["env"].items()}


def place_compile_cache() -> str:
    """The persistent compile cache lives at one fixed path inside the
    checkout unless the machine placed it; the program takes the variable
    (``utils/backend.configure_compile_cache``) and sets nothing itself.
    Both drivers call this before anything imports jax, so the runtime's
    settings are placed here too."""
    place_runtime_env()
    return os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache")
    )


def require_device(chips: int, rehearse: bool) -> dict:
    """The device as jax reports it; anything but ``chips`` TPU chips or
    more is an error (a rehearsal takes the CPU it is given and says so)."""
    import jax

    devs = jax.devices()
    ident = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if rehearse:
        say(f"REHEARSAL on {ident}: no device metric will be printed")
        return ident
    if ident["platform"] != "tpu" or ident["count"] < chips:
        raise SystemExit(
            f"benchmark: this cell needs {chips} TPU chip(s); jax found "
            f"{ident['count']} x {ident['platform']!r} ({ident['kind']})"
        )
    return ident


def memory_peak_bytes() -> int:
    """Peak bytes held on the fullest chip. This runtime keeps two pools and
    reports them apart: ``peak_bytes_in_use`` (arrays: weights, batches in
    flight, outputs) and ``peak_bytes_reserved`` (what running programs
    reserve for their temporaries: a compiled step's ``temp_size_in_bytes``
    shows up only here). A program runs with its arguments alive, so the
    device holds both at once and the peak is their sum (an upper bound by
    at most the swing of the small array pool; PERF.md section 4)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0))
                     + int(st.get("peak_bytes_reserved", 0)))
        say(f"memory {d}: arrays peak {st.get('peak_bytes_in_use', 0)} B, "
            f"programs' reserved peak {st.get('peak_bytes_reserved', 0)} B "
            f"of {st.get('bytes_limit', 0)} B")
    return max(peaks) if peaks else 0


def peaks_for(kind: str) -> dict:
    table = manifest.load_json(os.path.join(manifest.HERE, "peaks.json"))
    if kind not in table["devices"]:
        raise SystemExit(
            f"benchmark: device_kind {kind!r} is not in benchmark/peaks.json "
            f"({sorted(table['devices'])}): add it with its source"
        )
    return table["devices"][kind]


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all the values (q in (0, 100])."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def read_layer_metrics(cell: dict, art: dict) -> dict:
    """Each per-layer metric of the cell through its own reader
    (``benchmark/layer_metrics/<name>.py``); a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        value = manifest.load_module(
            manifest.layer_metric_path(m["name"])
        ).read(art)
        if value is None:
            say(f"layer metric {m['name']}: nothing to read, left out")
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def finish(cell: dict, args, result: dict, ident: dict, *, values: dict,
           art: dict, note: dict) -> dict:
    """The result line from what a driver measured. Untraced: the cell's
    end-to-end metrics out of ``values``. Traced: its per-layer metrics
    through their readers over ``art`` (whose ``profile`` is the reduced
    device trace), plus ``busy_s``/``window_s`` and the breakdown. A
    rehearsal returns ``note`` and the names it could read instead of any
    metric: no number of a CPU run goes under a metric's name."""
    red = art.get("profile")
    if args.rehearse:
        if args.trace:
            note = dict(note, layer_metrics_read=sorted(
                read_layer_metrics(cell, art)))
        return dict(result, rehearsal=True, **note)
    device = dict(ident, memory_peak_bytes=memory_peak_bytes())
    if not args.trace:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]
        }
        return dict(result, device=device)
    if red is None:
        raise RuntimeError("the profiler's trace holds no device operation")
    art["peaks"] = peaks_for(ident["kind"])
    result["metrics"] = read_layer_metrics(cell, art)
    result["device"] = dict(device, busy_s=red["busy_s"],
                            window_s=red["window_s"])
    result["breakdown"] = {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}
    return result


class Profiler:
    """A ``jax.profiler`` trace of a few seconds of the steady stream, taken
    from a harness thread while the program runs, reduced by
    ``reduce_xplane``."""

    def __init__(self, out_dir: str):
        self.dir = out_dir
        self.t_start = self.t_stop = 0.0

    def take(self, seconds: float) -> None:
        import jax.profiler as jp

        # device operations only: tracing every Python call slows the host
        # chain this run is measuring (the per-line source loop most of all)
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jp.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.monotonic()
        time.sleep(seconds)
        self.t_stop = time.monotonic()
        jp.stop_trace()

    def reduce(self, batches_in_stretch: "float | None" = None) -> "dict | None":
        from . import reduce_xplane

        path = reduce_xplane.find_xplane(self.dir)
        if path is None:
            return None
        red = reduce_xplane.reduce(path)
        if red is None:
            return None
        say(f"profile: {os.path.getsize(path)} bytes, window "
            f"{red['window_s']:.3f} s, busy per chip "
            f"{[round(c['busy_s'], 4) for c in red['per_chip']]}")
        if batches_in_stretch is not None:
            # batches counted on the host clock over [t_start, t_stop],
            # scaled to the trace's own window
            red["batches"] = (batches_in_stretch * red["window_s"]
                              / (self.t_stop - self.t_start))
        return red
