"""Profiling/tracing hooks (SURVEY.md §5.1: absent in the reference — Spark's
UI was the de-facto profiler; here jax.profiler is first-class).

``Tracer(profile_dir)`` wraps jax.profiler.start_trace/stop_trace with a
no-op mode when disabled, so apps can call it unconditionally:

    tracer = Tracer(conf.profileDir)
    tracer.start()
    ... training ...
    tracer.stop()

Traces are TensorBoard-compatible (xplane) under ``profile_dir``; on TPU they
include device timelines and XLA op breakdowns, the device stages by name
(the ``jax.named_scope``s of the train step: ``repad``, ``hash``,
``predict``, ``gram_count``, ``gram_matmul``, ``dual_loop``, ``writeback``
...) and, with ``--trace`` also on, the pipeline's spans on the host plane
(``annotate`` below; telemetry/trace.py). The profiler runs with the options
the benchmark's harness uses — Python tracer OFF: tracing every Python call
halves the ingest rate through the per-line source loop (PERF.md §3), and
the program's own spans say more.
"""

from __future__ import annotations

from . import get_logger

log = get_logger("tracing")


class Tracer:
    def __init__(self, profile_dir: str = ""):
        self.profile_dir = profile_dir
        self._active = False

    @property
    def enabled(self) -> bool:
        return bool(self.profile_dir)

    def start(self) -> None:
        if not self.enabled or self._active:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.profile_dir, profiler_options=options)
        self._active = True
        log.info("jax.profiler trace started → %s", self.profile_dir)

    def stop(self) -> None:
        if not self._active:
            return
        import jax

        jax.profiler.stop_trace()
        self._active = False
        log.info("jax.profiler trace written → %s", self.profile_dir)

    def __enter__(self) -> "Tracer":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


def annotate(name: str, **kwargs):
    """Named region on the host plane of a ``jax.profiler`` trace
    (TraceAnnotation) — the one place the program makes one; every
    ``--trace`` span opens its twin here (telemetry/trace.py). Costs a
    flag test while no profiler session runs."""
    import jax

    return jax.profiler.TraceAnnotation(name, **kwargs)
