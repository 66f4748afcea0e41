"""Pipeline tracing: Chrome-trace-event JSONL spans over the per-batch stages.

A bottleneck ladder (upload, host parse, host featurize, device step, fetch,
publish — in whatever order the machine puts them) should not have to be
reconstructed by hand from ad-hoc bench scripts; a ``--trace PATH`` run
writes it directly: every stage of every batch becomes a span
carrying bytes-on-wire, batch size, and fetch depth, so
``tools/trace_report.py`` (or Perfetto) reproduces the per-stage time budget
from the file alone.

File format: the Chrome JSON **array** trace format, written incrementally —
a ``[`` line followed by one complete event object per line (trailing
comma). The spec makes the closing ``]`` optional exactly so writers can
append and crashes lose nothing, which also makes the file line-parseable as
JSONL after stripping the decoration (``tools/trace_report.py`` does). Loads
as-is in Perfetto / ``chrome://tracing``.

Measurement-integrity constraints (lawcheck TW002/TW003): tracing adds **no**
``device_get``/``block_until_ready`` calls and no non-main-thread
``device_put`` — spans only time work the pipeline already does. Off is the
default and must stay ~free on the hot path: ``get()`` returns a null tracer
whose ``enabled`` is False and whose ``span()`` hands back one shared no-op
context manager — instrumentation sites guard-check ``enabled`` before doing
any argument computation.

Threading: spans are written from the main thread AND the fetch pool
(apps/common.FetchPipeline) — one lock around the line write keeps events
intact; ``tid`` records the emitting thread so Perfetto lanes stay honest.

Growth cap (r8): a ``--trace`` file grows without bound over a 600 s bench
or a multi-hour soak, so the writer rotates on size — when the active file
crosses ``max_bytes`` it becomes ``PATH.1`` (replacing any previous
``PATH.1``, whose events are the DROPPED ones — counted in the
``trace.dropped_events`` registry counter) and a fresh ``PATH`` segment
starts. ``tools/trace_report.py`` stitches ``PATH.1`` + ``PATH`` back into
one report. ``--traceMaxMb 0`` disables rotation.

One clock (PR 24): while a tracer is installed every ``span()`` also opens
a ``jax.profiler.TraceAnnotation`` of the same name (made in
``utils/tracing.annotate``, the one place), so a ``jax.profiler`` trace
taken meanwhile shows the program's spans on its ``/host:CPU`` plane, on
the clock of the device's own events — ``--profileDir``'s trace and the
benchmark's (``benchmark/stage_times.py`` puts each device idle gap down
to the span open on the scheduler thread). No profiler session: one
flag test per span.

Batch ids: ``batch_scope(n)`` marks the calling thread as working for the
scheduler's n-th batch; every span opened inside carries ``batch=n``
(``featurize``, ``wire_pack``, ``dispatch``, ``deliver_wait``,
``stats_publish``; ``fetch`` runs on a pool thread and is handed its id),
and ``dispatch`` takes it into its annotation, so the n-th
``jit_train_step`` on the device plane is batch n.

Waits, written only when one happened: ``intake_wait`` (the source thread
on the intake queue's row bound, streaming/context.py), ``deliver_wait``
(the scheduler on the oldest in-flight fetch, apps/common.py); and per
parsed block ``source_lines`` / ``source_recv`` (the source thread's chunk
loop and its socket reads, streaming/twitter.py). PERF.md §3 names the
benchmark metric that reads each.

Rounds (PR 39): every call of ``FetchPipeline.on_batch`` that got as far
as its dispatch leaves one ``deliver_round`` instant (apps/common.py):
``batch`` (the one it dispatched), ``ready`` (the leading in-flight results
whose fetch was done when the round's delivery began: the count that bounds
it), ``delivered`` (every result the round handed to the handlers:
backpressure, the ``ready`` ones, a cadence drain's) and ``pending`` (left
in flight). ``paired_delivery_share`` reads it: the share of deliveries
made two or more to a round.

Publish (PR 41): every ``stats_publish`` span carries ``posts`` (the
requests that update sent: ``Stats`` and ``Series``, and the one periodic
frame due on that update, if any: 2 or 3, ``telemetry/session_stats.py``)
and ``connects`` (the connections
``telemetry/web_client.WebClient`` opened for them: 0 while the server keeps
the connection, one a request where it closes each). ``publish_reuse_share``
reads both.

``compile`` spans: ``install()`` registers ``jax.monitoring`` listeners
(``uninstall()`` takes them away again; nothing is registered while
tracing is off) that write one ``compile`` span per backend compilation
or persistent-cache fetch: ``seconds``, ``cache_hit``, ``fun``,
``during`` (the innermost span open on the compiling thread, else
``startup``) and ``signature`` (what the dispatch site says of the call:
rows, row length, units dtype, wire form).

Mesh layout: a mesh model's run opens with one ``mesh_layout`` instant
(``apps/common.attach_pipeline``): ``data`` and ``model`` axis sizes,
``f_text_local`` (hashed features a model shard holds) and ``devices``.
The device time of the mesh steps' collectives is not a span: it is on the
device plane under the ``collective`` scope (parallel/sharding.py). A mesh
model with ARMS (``--tenantKey all --modelShards m``, PR 52) adds one
``mesh_arms`` instant beside it: ``arms``, ``data``, ``model`` and what a
chip ships a batch for the arms, ``u_gather_bytes`` (the other data shards'
``[M, B/d]`` f32 rows of ``u``) and ``delta_psum_bytes`` (the slice's
``[M, F/m + 4]`` f32 write-back deltas); its device program maps the arms'
dual loops under the ``arm_map`` scope, and each collective that carries
the arms sits under ``collective`` inside ``predict``, ``dual_loop`` or
``writeback``.

Tenant plane (``--tenants M``, PR 35): ``tenant_split`` spans the host's
route key, M-way split and stack or pack of the tenant wire (``rows``,
``tenants``, ``bytes`` and, PR 49, ``rungs`` = the fullest part's row rung and
the other parts'; inside ``wire_pack``, parallel/tenants.py), and every
delivered batch leaves one ``tenant_rows`` instant with the M valid-row
counts of its ONE stacked fetch (``rows``), the row rung the split padded
the FULLEST tenant's part to for that batch (``bucket``, PR 36: read off the
fetched ``[M, bucket]`` predictions leaf), the M parts' rungs in tenant order
(``buckets``, PR 49: kept by the plane from the batch's split to its
delivery; under an even key all equal ``bucket``, under a lopsided one the
fullest's and a lower one for the rest) and ``pad_rows`` = Σ ``buckets`` −
Σ ``rows``, and under ``--modelWatch on`` each part's OWN Gram plane
(``planes``, PR 42: off the stacked quality leaf of the same fetch; a
near-dry part of short rows takes s8 beside bf16 ones)
(apps/common.attach_pipeline); the device program sits under the
``tenant_map`` scope, both halves of a two-rung one. Under ``--tenantKey
all`` (key ``all``: every arm saw the whole batch, ``rows`` is ``[B]·M``)
the instant of a MESH run also carries ``mesh`` = ``[d, m]``. None of the
three exists on the single-model plane.

Event sink (r8): the crash flight recorder (telemetry/blackbox.py) attaches
via ``set_event_sink`` so recent spans ride its bounded in-memory ring —
one callback per written event, no second file, nothing when tracing is
off.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time

from ..utils import get_logger
from ..utils.tracing import annotate as _annotate

log = get_logger("telemetry.trace")

# the per-batch pipeline stages (the instrumentation contract — tests and
# trace_report key on these names)
STAGES = (
    "source_read",   # queue drain on the batch scheduler
    "parse",         # bytes/lines → Status/ParsedBlock, on the source thread
    "featurize",     # host featurize incl. wire build (FeatureStream)
    "wire_pack",     # one-buffer pack of the ragged wire (when --wire
                     # ragged); carries ``mode="single"`` (one batch,
                     # one buffer; the tenant stack's M-batch wire
                     # packs inside this same span) plus ``wire_bytes``
    "dispatch",      # model.step dispatch — argument uploads ride this
    "fetch",         # pipelined StepOutput host fetch (FetchPipeline pool)
    "stats_publish", # telemetry POSTs (SessionStats)
)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()  # also for sites that span only sometimes


class _NullTrace:
    """The off-by-default tracer: every operation is a guard-checked no-op."""

    enabled = False
    path = ""

    def span(self, name, signature=None, **args):
        return NULL_SPAN

    def complete(self, name, t0_s, dur_s, **args):
        pass

    def instant(self, name, **args):
        pass

    def batch_scope(self, batch):
        return NULL_SPAN

    def close(self):
        pass


_NULL = _NullTrace()

# per thread: the spans open on it, innermost last (``compile``'s
# ``during``), the batch it works for, and whether the compile in progress
# was served by the persistent cache
_tls = threading.local()


def _open_spans() -> list:
    try:
        return _tls.spans
    except AttributeError:
        _tls.spans = []
        return _tls.spans


class _BatchScope:
    """``with trace.batch_scope(n):`` — spans opened on this thread inside
    the block carry ``batch=n``. Nests (the fetch pipeline delivers an older
    batch in the middle of a newer one's dispatch)."""

    __slots__ = ("_batch", "_outer")

    def __init__(self, batch):
        self._batch = batch

    def __enter__(self):
        self._outer = getattr(_tls, "batch", None)
        _tls.batch = self._batch
        return self

    def __exit__(self, *exc):
        _tls.batch = self._outer
        return False


class _Span:
    """Context manager recording one complete ("X") event on exit, open
    meanwhile as a profiler annotation of the same name. ``signature``: a
    callable returning what a ``compile`` span should say of the call being
    dispatched; it runs only if something compiles."""

    __slots__ = ("_trace", "_name", "_args", "_t0", "_annotation",
                 "signature")

    def __init__(self, trace: "PipelineTrace", name: str, args: dict,
                 signature=None):
        self._trace = trace
        self._name = name
        self._args = args
        self.signature = signature

    def __enter__(self):
        batch = current_batch()
        if batch is not None:
            self._args.setdefault("batch", batch)
        # the span's twin on the profiler's clock; ``dispatch`` takes its
        # batch id along (module docstring)
        if self._name == "dispatch" and "batch" in self._args:
            self._annotation = _annotate("dispatch", batch=self._args["batch"])
        else:
            self._annotation = _annotate(self._name)
        self._annotation.__enter__()
        _open_spans().append(self)
        self._t0 = time.perf_counter()
        return self

    def add(self, **args) -> None:
        """Attach args discovered mid-span (e.g. rows known only after
        featurize returns)."""
        self._args.update(args)

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        _open_spans().remove(self)
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self._args["error"] = exc_type.__name__
        self._trace.complete(self._name, self._t0, dur, **self._args)
        return False


class PipelineTrace:
    """Chrome-trace-event writer. ``ts`` is ``time.perf_counter`` µs (one
    monotonic timebase across threads); writes are line-buffered so a crash
    loses at most the event being formatted. ``max_bytes`` arms size-based
    rotation (module docstring; 0 = unbounded)."""

    enabled = True

    def __init__(self, path: str, max_bytes: int = 0):
        self.path = path
        self.max_bytes = max(0, int(max_bytes))
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._bytes = 0
        self._events_in_file = 0
        self._rotated_events = 0  # events in OUR current PATH.1 segment
        # buffering=1: every event line reaches the OS immediately — the
        # crash-flush guarantee without an explicit flush per event
        self._fh = open(path, "w", encoding="utf-8", buffering=1)
        self._fh.write("[\n")
        self._write_meta()

    def _write_meta(self) -> None:
        self._event(
            {"name": "process_name", "ph": "M", "pid": self._pid, "tid": 0,
             "args": {"name": "twtml-tpu pipeline"}}
        )

    # -- event plumbing ------------------------------------------------------
    def _event(self, ev: dict) -> None:
        line = json.dumps(ev, separators=(",", ":"))
        with self._lock:
            if self._fh.closed:
                return
            self._fh.write(line + ",\n")
            self._bytes += len(line) + 2
            self._events_in_file += 1
            if self.max_bytes and self._bytes >= self.max_bytes:
                self._rotate_locked()
        sink = _SINK
        if sink is not None:
            try:
                sink(ev)
            except Exception:  # a sick sink must never kill the pipeline
                log.debug("trace event sink failed", exc_info=True)

    def _rotate_locked(self) -> None:
        """Size rotation (caller holds the lock): the active segment becomes
        PATH.1; a previous PATH.1's events fall off the end and are counted
        as dropped — the bounded two-segment policy keeps worst-case disk
        at ~2 x max_bytes for arbitrarily long runs."""
        self._fh.close()
        rotated = self.path + ".1"
        if self._rotated_events:
            from . import metrics as _metrics

            _metrics.get_registry().counter("trace.dropped_events").inc(
                self._rotated_events
            )
            log.warning(
                "trace rotation dropped %d event(s) from the oldest "
                "segment (%s)", self._rotated_events, rotated,
            )
        os.replace(self.path, rotated)
        self._rotated_events = self._events_in_file
        self._bytes = 0
        self._events_in_file = 0
        self._fh = open(self.path, "w", encoding="utf-8", buffering=1)
        self._fh.write("[\n")
        # re-emit the metadata so the fresh segment stands alone in Perfetto
        meta = {"name": "process_name", "ph": "M", "pid": self._pid,
                "tid": 0, "args": {"name": "twtml-tpu pipeline"}}
        line = json.dumps(meta, separators=(",", ":"))
        self._fh.write(line + ",\n")
        self._bytes += len(line) + 2
        self._events_in_file += 1

    def _base(self, name: str) -> dict:
        return {
            "name": name,
            "cat": "pipeline",
            "pid": self._pid,
            "tid": threading.get_ident() & 0xFFFFFFFF,
        }

    # -- public API ----------------------------------------------------------
    def span(self, name: str, signature=None, **args) -> _Span:
        """``with trace.span("featurize", rows=...):`` — one complete event
        spanning the with-block. Nest freely; Chrome's viewer nests X events
        by time containment per thread. ``signature``: see ``_Span``."""
        return _Span(self, name, args, signature)

    def complete(self, name: str, t0_s: float, dur_s: float, **args) -> None:
        """Record a complete event from an already-taken (start, duration)
        pair — for call sites that need the duration themselves (the fetch
        wrapper feeds it to the health monitor too)."""
        ev = self._base(name)
        ev["ph"] = "X"
        ev["ts"] = round(t0_s * 1e6, 1)
        ev["dur"] = round(dur_s * 1e6, 1)
        if args:
            ev["args"] = args
        self._event(ev)

    def instant(self, name: str, **args) -> None:
        """Zero-duration mark (health-phase transitions)."""
        ev = self._base(name)
        ev["ph"] = "i"
        ev["ts"] = round(time.perf_counter() * 1e6, 1)
        ev["s"] = "p"  # process-scoped mark
        if args:
            ev["args"] = args
        self._event(ev)

    def batch_scope(self, batch: int) -> _BatchScope:
        """Spans opened on this thread inside the block carry
        ``batch=<batch>`` (the scheduler's sequence number)."""
        return _BatchScope(batch)

    def close(self) -> None:
        with self._lock:
            if not self._fh.closed:
                self._fh.close()


# -- module-level active tracer ---------------------------------------------
# One active tracer per process, installed by the app entry points from
# ``--trace PATH``. Instrumentation sites call ``get()`` and guard on
# ``.enabled`` — with no tracer installed that is one attribute read.

_active: "PipelineTrace | _NullTrace" = _NULL

# optional per-event callback (the flight recorder's ring — blackbox.py);
# one attribute read per written event, None when nothing listens
_SINK = None


def set_event_sink(sink) -> None:
    """Attach/detach the per-event callback (``None`` detaches). Events
    only flow while a real tracer is installed — the sink never turns
    tracing on by itself."""
    global _SINK
    _SINK = sink


def _on_cache_hit(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        _tls.cache_hit = True  # read by the duration event that follows


def _on_compile(event: str, secs: float, **kw) -> None:
    """One ``compile`` span per backend compilation, written when jax
    reports its duration (on the compiling thread, inside whatever program
    span made the call)."""
    if event != BACKEND_COMPILE_EVENT:
        return
    hit, _tls.cache_hit = getattr(_tls, "cache_hit", False), False
    tr = _active
    if not tr.enabled:
        return
    spans = _open_spans()
    signature = next(
        (sp.signature() for sp in reversed(spans) if sp.signature), None
    )
    tr.complete(
        "compile", time.perf_counter() - secs, secs,
        seconds=round(secs, 4), cache_hit=hit,
        fun=str(kw.get("fun_name", "")),
        during=spans[-1]._name if spans else "startup",
        signature=signature,
    )


_listening = False


def _listen(on: bool) -> None:
    """Register / take away the two ``jax.monitoring`` listeners."""
    global _listening
    if on == _listening:
        return
    import jax.monitoring as mon

    if on:
        mon.register_event_listener(_on_cache_hit)
        mon.register_event_duration_secs_listener(_on_compile)
    else:
        mon.unregister_event_listener(_on_cache_hit)
        mon.unregister_event_duration_listener(_on_compile)
    _listening = on


def install(path: str, max_bytes: int = 0) -> "PipelineTrace | _NullTrace":
    """Activate tracing to ``path`` (empty path → stays off). Closes any
    previously installed tracer; registered atexit so a crash still flushes
    and closes the file. ``max_bytes`` arms size rotation (0 = off)."""
    global _active
    if not path:
        return _active
    if _active.enabled:
        _active.close()
    _active = PipelineTrace(path, max_bytes=max_bytes)
    atexit.register(_active.close)
    _listen(True)
    log.info("pipeline trace → %s (Perfetto-loadable)", path)
    return _active


def uninstall() -> None:
    """Deactivate and close the active tracer (app shutdown path)."""
    global _active
    if _active.enabled:
        _active.close()
    _active = _NULL
    _listen(False)


def get() -> "PipelineTrace | _NullTrace":
    return _active


def current_batch() -> "int | None":
    """The batch id of the ``batch_scope`` this thread is in, if any — what
    a pipeline keeps beside an in-flight dispatch to hand to its fetch and
    delivery."""
    return getattr(_tls, "batch", None)
