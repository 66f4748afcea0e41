"""Micro-batch streaming runtime — the DStream/StreamingContext equivalent.

The reference slices a live stream into RDDs every ``seconds`` and runs two
registered outputs per batch: the stats ``foreachRDD`` and ``model.trainOn``
(LinearRegression.scala:40-47,53,86). Here a ``StreamingContext`` owns one
source feeding a thread-safe queue; a scheduler thread wakes every
``batch_interval`` seconds, drains the queue, filters + featurizes + pads the
tweets into one fixed-shape ``FeatureBatch``, and invokes every registered
output in registration order (so stats-before-train ordering is preserved
when callers register them separately; the fused model step keeps it
internally regardless).

Differences by design:
- featurization happens once per batch on the host (numpy), not as per-element
  closures shipped to executors — the device program consumes one padded batch;
- ``run_to_completion`` offers a deterministic clock-free mode (replay/bench):
  process fixed-size batches back-to-back until the source is exhausted,
  which wall-clock DStreams cannot do;
- batch row/token counts are padded to power-of-two buckets (features/batch.py)
  so XLA compiles a handful of programs, not one per batch shape.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable

from ..features.batch import FeatureBatch, UnitBatch
from ..features.featurizer import Featurizer, Status
from ..telemetry import lineage as _lineage
from ..telemetry import metrics as _metrics
from ..telemetry import sideband as _sideband
from ..telemetry import trace as _trace
from ..utils import get_logger
from ..utils.clock import now_s
from . import journal as _journal
from .sources import Source

log = get_logger("streaming.context")

BatchFn = Callable[[FeatureBatch, float], None]

# lockstep peer watchdog: how long the per-tick cadence allgather may make
# no progress before this host concludes a peer is gone (hard kill /
# network partition) and aborts loudly instead of hanging in the
# collective forever. Generous default: ticks legitimately skew by a slow
# host's featurize/parse + a ~30s first-batch compile. 0 disables.
LOCKSTEP_TIMEOUT_ENV = "TWTML_LOCKSTEP_TIMEOUT_S"
LOCKSTEP_TIMEOUT_DEFAULT_S = 120.0


def _watched_allgather(arr, timeout_s: float):
    """Run one cadence allgather under a progress watchdog: returns the
    gathered array, or None when the watchdog fired. The collective runs
    on a daemon thread (never a ThreadPoolExecutor — concurrent.futures
    joins its workers at interpreter exit, so a wedged collective would
    hang shutdown; a daemon thread dies with the process). The scheduler
    blocks on the result before dispatching, so per-host collective issue
    order stays total — only the executing thread changes. Thread spawn is
    ~50µs against a per-batch tick; exceptions from the collective (a dead
    peer often surfaces as a transport error rather than a hang) propagate
    to the caller."""
    from jax.experimental import multihost_utils

    if timeout_s <= 0:
        return multihost_utils.process_allgather(arr)
    box: dict = {}
    done = threading.Event()

    def run() -> None:
        try:
            box["out"] = multihost_utils.process_allgather(arr)
        except BaseException as exc:  # noqa: BLE001 — re-raised below  # lawcheck: disable=TW005 -- not a swallow: captured into the box and re-raised by the waiting caller
            box["exc"] = exc
        done.set()

    threading.Thread(
        target=run, daemon=True, name="twtml-lockstep-allgather"
    ).start()
    if not done.wait(timeout_s):
        return None
    if "exc" in box:
        raise box["exc"]
    return box["out"]


SHED_POLICIES = ("block", "shed-oldest")


class _RowCountQueue(queue.Queue):
    """queue.Queue that also tracks the queued ROW count (a ParsedBlock item
    counts its rows, a Status counts 1) — maintained inside ``_put``/``_get``,
    which run under the queue's own mutex, so the per-tweet intake path pays
    no extra lock. The back-to-back fill gate compares ``rows_queued`` (not
    item count) to the row bucket; reading the int without the mutex is fine
    for a gate that only ever errs toward one more 2 ms wait.

    **Bounded backpressure (r7)**: ``configure_bound`` arms a ROW-count
    ceiling (``--maxQueueRows``) with two overload policies — the intake
    queue was the last unbounded buffer in the pipeline (a source burst or
    a slow stretch downstream grew host RSS without limit):

    - ``block`` (default): the producer thread waits until the consumer
      drains below the bound — correct for replay/backfill sources, where
      the data can't be lost and the file isn't going anywhere;
    - ``shed-oldest``: drop whole items from the queue FRONT until the new
      item fits — correct for live sources, where the freshest rows are
      the valuable ones and blocking would just move the loss upstream
      into the kernel socket buffer. Shedding from the front never
      reorders the survivors (parity: predict-then-train ordering holds
      on whatever rows remain — tests/test_backpressure.py).

    Shed rows are counted (``ingest.rows_shed``); an item bigger than the
    whole bound is admitted alone (blocking it forever would deadlock the
    stream on one oversized block). ``close()`` releases a blocked
    producer at shutdown. Unbounded (``max_rows=0``) puts take the exact
    pre-r7 path."""

    max_rows = 0
    policy = "block"

    def _init(self, maxsize: int) -> None:
        super()._init(maxsize)
        self.rows_queued = 0
        self.rows_shed_total = 0
        self._closed = False

    def configure_bound(self, max_rows: int, policy: str = "block") -> None:
        if policy not in SHED_POLICIES:
            raise ValueError(
                f"shed policy must be one of {SHED_POLICIES}, got {policy!r}"
            )
        self.max_rows = max(0, int(max_rows))
        self.policy = policy

    def close(self) -> None:
        """Release producers blocked on a full bounded queue (shutdown:
        the consumer is gone, so waiting would wedge ``Source.stop``)."""
        with self.mutex:
            self._closed = True
            self.not_full.notify_all()

    def put(self, item, block=True, timeout=None) -> None:
        if self.max_rows <= 0:
            return super().put(item, block, timeout)
        rows = getattr(item, "rows", 1)
        waited_since = 0.0
        with self.not_full:
            if self.policy == "block":
                # admit when empty regardless of size: one item larger
                # than the whole bound must pass, not deadlock
                while (
                    self.rows_queued > 0
                    and self.rows_queued + rows > self.max_rows
                    and not self._closed
                ):
                    waited_since = waited_since or time.perf_counter()
                    # timed wait belt-and-braces: queue.Queue.get always
                    # notifies not_full, but a missed wakeup must not
                    # wedge the producer forever
                    self.not_full.wait(0.1)
            else:  # shed-oldest
                shed = 0
                while self.queue and self.rows_queued + rows > self.max_rows:
                    old = self.queue.popleft()
                    r = getattr(old, "rows", 1)
                    self.rows_queued -= r
                    shed += r
                if shed:
                    self.rows_shed_total += shed
                    reg = _metrics.get_registry()
                    reg.counter("ingest.rows_shed").inc(shed)
                    reg.gauge("ingest.queue_rows").set(self.rows_queued)
                    log.warning(
                        "intake queue over --maxQueueRows %d: shed %d "
                        "oldest row(s) to admit %d new (total shed %d)",
                        self.max_rows, shed, rows, self.rows_shed_total,
                    )
            self._put(item)
            self.unfinished_tasks += 1
            self.not_empty.notify()
        if waited_since:
            # the producer's wait on the row bound: the source thread's
            # slack (PERF.md §3). Written after the mutex is released, and
            # only when a wait happened
            tr = _trace.get()
            if tr.enabled:
                tr.complete(
                    "intake_wait", waited_since,
                    time.perf_counter() - waited_since, rows=rows,
                )

    def putback(self, item) -> None:
        """Return an item to the FRONT of the queue (the drain splitter's
        remainder — it must come out first so row order is preserved).
        Exempt from the bound: these rows were already admitted once."""
        with self.mutex:
            self.queue.appendleft(item)
            self.rows_queued += getattr(item, "rows", 1)
            self.not_empty.notify()

    def drain_rows(self, limit: int = 0, slicer=None):
        """Pop queued items up to ``limit`` ROWS (0 = everything) under ONE
        mutex acquire, splitting an overshooting block via ``slicer(item,
        cut) -> (head, tail)`` with the tail left at the queue front.

        Why not get_nowait in a loop: every ``Queue.get`` notifies
        ``not_full``, so a 2048-row drain woke a bound-blocked producer
        2048 times to re-check and re-sleep against a still-full queue —
        needless lock churn. One acquire + one
        ``notify_all`` per drain instead, and the producer wakes exactly
        once, into a freshly drained bound."""
        out: list = []
        rows = 0
        with self.mutex:
            while self.queue and (not limit or rows < limit):
                item = self.queue[0]
                take = getattr(item, "rows", None)
                if take is not None and limit and rows + take > limit:
                    cut = limit - rows
                    head, tail = slicer(item, cut)
                    self.queue[0] = tail
                    self.rows_queued -= cut
                    out.append(head)
                    rows = limit
                    break
                self.queue.popleft()
                taken = take if take is not None else 1
                self.rows_queued -= taken
                rows += taken
                out.append(item)
            self.not_full.notify_all()
        return out

    def _put(self, item) -> None:
        super()._put(item)
        self.rows_queued += getattr(item, "rows", 1)

    def _get(self):
        item = super()._get()
        self.rows_queued -= getattr(item, "rows", 1)
        return item


class RawStream:
    """A stream of raw Status lists — for apps with their own featurization
    (the k-means entry featurizes to a dense pair, KMeans.scala:19-33).
    Outputs fire per micro-batch in registration order (reference: foreachRDD
    at LinearRegression.scala:53, trainOn at :86).

    ``row_bucket`` (optional) caps the scheduler's back-to-back drains —
    required by multi-host lockstep, where the app's per-batch handler owns
    fixed-shape padding and every host must dispatch the same program."""

    def __init__(self, row_bucket: int = 0):
        self._outputs: list[Callable] = []
        self.row_bucket = row_bucket

    def foreach_batch(self, fn) -> "RawStream":
        self._outputs.append(fn)
        return self

    def _process(self, statuses: list[Status], batch_time: float):
        for fn in self._outputs:
            fn(statuses, batch_time)


class FeatureStream(RawStream):
    """A RawStream whose outputs receive padded FeatureBatches instead of
    Status lists (DStream.map(featurize) analog)."""

    def __init__(
        self,
        featurizer: Featurizer,
        row_bucket: int = 0,
        token_bucket: int = 0,
        row_multiple: int = 1,
        device_hash: bool = False,
        ragged: bool = False,
    ):
        super().__init__()
        self.featurizer = featurizer
        self.row_bucket = row_bucket
        self.token_bucket = token_bucket
        self.row_multiple = row_multiple
        self.device_hash = device_hash
        self.ragged = ragged
        if ragged and not device_hash:
            raise ValueError(
                "the ragged wire IS a device-hash wire format: "
                "--wire ragged requires --hashOn device"
            )
        self._bucket_overflow_warned = False
        self.batches_seen = 0  # the batch id of the --trace spans
        # the pinned row shape includes the mesh-divisibility round-up,
        # matching every batch the featurizer emits; fixed at construction
        from ..features.batch import pad_row_count

        self._pinned_rows = (
            pad_row_count(0, row_bucket, row_multiple) if row_bucket > 0 else 0
        )

    @staticmethod
    def batch_shape(batch) -> "tuple[int, int]":
        """(rows, tokens-or-units) of a featurized batch — the two axes the
        pinned buckets govern."""
        from ..features.batch import RaggedUnitBatch

        if isinstance(batch, RaggedUnitBatch):
            # the ragged wire's row length is static aux (the device-side
            # re-pad width) — the same axis token_bucket pins
            return batch.mask.shape[0], batch.row_len
        tokens = (
            batch.units.shape[1]
            if isinstance(batch, UnitBatch)
            else batch.token_idx.shape[1]
        )
        return batch.mask.shape[0], tokens

    def bucket_overflow(self, batch) -> bool:
        """Whether a featurized batch outgrew the pinned buckets (the
        featurizer grows rather than truncates)."""
        rows, tokens = self.batch_shape(batch)
        return (0 < self._pinned_rows < rows) or (
            0 < self.token_bucket < tokens
        )

    def _check_buckets(self, batch) -> None:
        """Warn (once) when a batch overflowed the pinned buckets: the
        featurizer grows the bucket rather than truncate, so the step
        recompiles for the bigger shape — silently defeating a pre-stream
        compile warmup and multiplying program count."""
        if self._bucket_overflow_warned or not self.bucket_overflow(batch):
            return
        self._bucket_overflow_warned = True
        rows, tokens = self.batch_shape(batch)
        log.warning(
            "batch shape (%d, %d) overflowed the pinned buckets "
            "(%d, %d): the step recompiles for the larger shape — "
            "raise --batchBucket/--tokenBucket to keep one program",
            rows, tokens, self.row_bucket, self.token_bucket,
        )

    def _featurize(self, statuses: list) -> "FeatureBatch | UnitBatch":
        """The ONE featurize dispatch for this stream's configuration —
        shared by the per-batch path and ``featurize_empty`` so a compile
        warmup always warms exactly the program the stream will run.
        Instrumented as the ``featurize`` stage (host featurize incl. wire
        build); the span and the ``pipeline.*``/``wire.bytes`` metrics are
        side-channel only — the batch itself is untouched. Timed
        unconditionally (two clock reads per BATCH) so the per-host
        sideband's featurize attribution works without ``--trace``."""
        tr = _trace.get()
        t0 = time.perf_counter()
        if not tr.enabled:
            batch = self._featurize_impl(statuses)
            _sideband.record_stage("featurize", time.perf_counter() - t0)
            self._record_substages(None)
            return self._poison_gate(statuses, batch)
        with tr.span("featurize", items=len(statuses)) as sp:
            batch = self._featurize_impl(statuses)
            from ..features.batch import wire_nbytes

            sp.add(
                rows=int(batch.mask.shape[0]),
                valid=batch.num_valid,
                wire_bytes=wire_nbytes(batch),
            )
        _sideband.record_stage("featurize", time.perf_counter() - t0)
        self._record_substages(tr)
        return self._poison_gate(statuses, batch)

    def _record_substages(self, tr) -> None:
        """The featurize sub-stage clock (r18): per-batch encode /
        numeric / wire_build durations recorded by the featurizer
        (featurizer.last_substages) become ``featurize.<name>_ms``
        gauges on /api/metrics — so the straggler ladder can name WHICH
        half of featurize gates a host — and, under ``--trace``, nested
        ``featurize.<name>`` complete-events inside the featurize span.
        Telemetry side-channel only: host clock reads, zero added
        fetches (the gauges never touch a device array)."""
        subs = getattr(self.featurizer, "last_substages", None)
        if not subs:
            return
        agg: "dict[str, float]" = {}
        carried = getattr(self.featurizer, "last_substage_args", None) or {}
        for name, sub_t0, dur in subs:
            agg[name] = agg.get(name, 0.0) + dur
            if tr is not None:
                tr.complete(
                    "featurize." + name, sub_t0, dur, **carried.get(name, {})
                )
        reg = _metrics.get_registry()
        for name, dur in agg.items():
            reg.gauge(f"featurize.{name}_ms").set(round(dur * 1e3, 4))

    @staticmethod
    def _poison_gate(statuses: list, batch):
        """--chaos ``source.nan`` injection point: only REAL batches count
        toward (and may fire) the rule — warmup/all-padding featurizes pass
        ``statuses=[]`` and must not advance the per-host call counter
        (lockstep hosts featurize in step; a dry host skewing the counter
        would desynchronize deterministic triggers across the group)."""
        from . import faults as _faults_inner

        if not statuses or _faults_inner._CHAOS is None:
            return batch
        return _faults_inner.maybe_poison_labels(batch)

    @staticmethod
    def _record_metrics(batch) -> None:
        from ..features.batch import wire_composition, wire_nbytes

        reg = _metrics.get_registry()
        reg.counter("pipeline.batches").inc()
        reg.counter("pipeline.tweets").inc(batch.num_valid)
        reg.counter("wire.bytes").inc(wire_nbytes(batch))
        # per-batch wire composition (Lean wire v2): the units/offsets/
        # sideband split makes the offset-narrowing visible in /api/metrics
        # and trace reports without a bench run
        comp = wire_composition(batch)
        reg.gauge("wire.units_bytes").set(comp["units"])
        reg.gauge("wire.offsets_bytes").set(comp["offsets"])
        reg.gauge("wire.sideband_bytes").set(comp["sideband"])

    def _featurize_impl(self, statuses: list) -> "FeatureBatch | UnitBatch":
        from ..features.blocks import ParsedBlock, merge_blocks

        if statuses and isinstance(statuses[0], ParsedBlock):
            # native block ingest: items are pre-filtered columnar blocks
            # (sources.BlockReplayFileSource); featurize without per-tweet
            # Python objects
            return self.featurizer.featurize_parsed_block(
                merge_blocks(statuses), row_bucket=self.row_bucket,
                unit_bucket=self.token_bucket, row_multiple=self.row_multiple,
                ragged=self.ragged,
            )
        if self.device_hash:
            if self.ragged:
                # concatenated units + offsets: no per-row pad bytes on the
                # wire (features/batch.RaggedUnitBatch)
                return self.featurizer.featurize_batch_ragged(
                    statuses, row_bucket=self.row_bucket,
                    unit_bucket=self.token_bucket,
                    row_multiple=self.row_multiple,
                )
            # ship raw code units; the learner hashes bigrams on device
            # (ops/text_hash.py) — bit-identical features, less host work
            return self.featurizer.featurize_batch_units(
                statuses, row_bucket=self.row_bucket,
                unit_bucket=self.token_bucket, row_multiple=self.row_multiple,
            )
        return self.featurizer.featurize_batch(
            statuses, row_bucket=self.row_bucket,
            token_bucket=self.token_bucket,
            row_multiple=self.row_multiple,
        )

    def featurize_empty(self) -> "FeatureBatch | UnitBatch":
        """An all-padding batch of this stream's exact configured shape
        (meaningful when both buckets are pinned) — for pre-stream compile
        warmup."""
        return self._featurize([])

    def _process(
        self, statuses: list[Status], batch_time: float
    ) -> "FeatureBatch | UnitBatch":
        # one id per batch: the spans this thread opens for the batch
        # (featurize, wire_pack, dispatch) carry the scheduler's count, and
        # the fetch pipeline hands it on to fetch, deliver_wait and
        # stats_publish (telemetry/trace.py batch_scope)
        self.batches_seen += 1
        with _trace.get().batch_scope(self.batches_seen):
            # freshness lineage (r16): stamp the batch's record as it
            # enters featurize — the event-time span + a stage-clock
            # snapshot; no-op unless the plane is on
            _lineage.open_batch(statuses)
            # durable intake journal (r21): the ONE blessed append seam
            # with _run_batch_aligned below (lawcheck TW009) — raw rows
            # become a CRC-framed replay record BEFORE featurize, so every
            # recovery path re-ingests bytes the unchanged featurize path
            # re-reads
            _journal.record_intake(statuses)
            batch = self._featurize(statuses)
            self._check_buckets(batch)
            self._record_metrics(batch)
            for fn in self._outputs:
                fn(batch, batch_time)
            return batch


class StreamingContext:
    def __init__(self, batch_interval: float = 5.0,
                 max_queue_rows: int = 0, shed_policy: str = "block"):
        """``max_queue_rows``/``shed_policy`` arm the bounded intake queue
        (``--maxQueueRows``/``--shedPolicy`` — see _RowCountQueue); 0 keeps
        the pre-r7 unbounded queue (tests and embedded uses)."""
        self.batch_interval = batch_interval
        self._queue: _RowCountQueue = _RowCountQueue()
        if max_queue_rows > 0:
            self._queue.configure_bound(max_queue_rows, shed_policy)
        self._source: Source | None = None
        self._stream: RawStream | None = None
        self._scheduler: threading.Thread | None = None
        self._stop = threading.Event()
        self._terminated = threading.Event()
        self.batches_processed = 0
        # set when a lockstep run aborted (this host or a peer): the app
        # must surface a failure instead of reporting success
        self.failed = False
        # divergence-sentinel hook (apps/common.DivergenceSentinel.bind_ssc):
        # returns this host's cumulative rollback count, so the decision
        # rides the per-tick cadence allgather in lockstep runs and every
        # host can verify the group rolled back the same steps
        self.rollback_count_fn: "Callable[[], int] | None" = None
        # elastic membership plane (--elastic on, streaming/membership.py):
        # when set, peer loss re-forms the group instead of aborting it,
        # and the membership columns ride the cadence allgather
        self.membership = None

    def source_stream(
        self,
        source: Source,
        featurizer: Featurizer,
        row_bucket: int = 0,
        token_bucket: int = 0,
        row_multiple: int = 1,
        device_hash: bool = False,
        ragged: bool = False,
    ) -> FeatureStream:
        """Attach the (single) source and build its feature stream —
        equivalent of TwitterUtils.createStream().filter().map().cache()
        (LinearRegression.scala:44-47)."""
        if self._source is not None:
            raise ValueError("StreamingContext supports one source stream")
        self._source = source
        self._stream = FeatureStream(
            featurizer, row_bucket, token_bucket, row_multiple, device_hash,
            ragged,
        )
        return self._stream

    def raw_stream(self, source: Source, row_bucket: int = 0) -> RawStream:
        """Attach the source with no featurization — outputs receive the raw
        Status list per micro-batch. ``row_bucket`` caps back-to-back
        drains (required in multi-host lockstep)."""
        if self._source is not None:
            raise ValueError("StreamingContext supports one source stream")
        self._source = source
        self._stream = RawStream(row_bucket)
        return self._stream

    def _drain(self, limit: int = 0) -> list[Status]:
        """Drain queued items; ``limit`` caps the drained ROW count (a
        ParsedBlock item counts its rows, a Status counts 1). A ParsedBlock
        that would overshoot the cap is SPLIT at the cap (r5) and its
        remainder put back at the queue front — capped drains are therefore
        exactly ``limit`` rows while data lasts, which multi-host lockstep
        requires (an overshooting block would grow this host's program
        shape away from its peers') and which makes single-host
        back-to-back block batches deterministic bucket-sized too.

        Instrumented as the ``source_read`` stage when tracing is on; timed
        unconditionally (per drain, not per item) for the sideband."""
        tr = _trace.get()
        t0 = time.perf_counter()
        if not tr.enabled:
            out = self._drain_impl(limit)
            _sideband.record_stage("source_read", time.perf_counter() - t0)
            return out
        with tr.span("source_read") as sp:
            out = self._drain_impl(limit)
            sp.add(items=len(out))
        _sideband.record_stage("source_read", time.perf_counter() - t0)
        return out

    def _drain_impl(self, limit: int = 0) -> list[Status]:
        from ..features.blocks import ParsedBlock, merge_blocks, slice_block

        out = self._queue.drain_rows(
            limit,
            slicer=lambda item, cut: (
                slice_block(item, 0, cut),
                slice_block(item, cut, item.rows),
            ),
        )
        if len(out) > 1 and isinstance(out[0], ParsedBlock):
            # parsed blocks: ONE merge at the seam — the lineage stamp, the
            # journal's record and featurize each merged the list again
            out = [merge_blocks(out)]
        # queue depth is per-BATCH registry state (one gauge set per drain,
        # never per tweet — the intake hot path pays no metric lock)
        _metrics.get_registry().gauge("ingest.queue_rows").set(
            self._queue.rows_queued
        )
        return out

    def _run_batch(self, statuses: list[Status], batch_time: float) -> None:
        try:
            self._stream._process(statuses, batch_time)
            self.batches_processed += 1
        except Exception:
            log.exception("batch at t=%.3f failed", batch_time)

    def _scheduler_loop(self) -> None:
        # back-to-back mode (--seconds 0) with a pinned row bucket: cap each
        # batch at the bucket so a fast source yields deterministic
        # fixed-size batches (the run_to_completion semantic) instead of one
        # giant drain — bounded memory, one compiled shape. Wall-clock
        # mode drains the full interval.
        limit = (
            getattr(self._stream, "row_bucket", 0)
            if self.batch_interval == 0
            else 0
        )
        next_tick = time.monotonic() + self.batch_interval
        while not self._stop.is_set():
            delay = next_tick - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                break
            next_tick += self.batch_interval
            if limit and self._queue.rows_queued < limit and not self._source.exhausted:
                # fill the bucket before processing: batch boundaries stay
                # deterministic (full buckets + one tail) instead of racing
                # the producer — the run_to_completion contract
                self._stop.wait(0.002)
                continue
            self._run_batch(self._drain(limit), now_s())
            if self._source.exhausted and self._queue.empty():
                break
        self._terminated.set()

    def request_stop(self) -> None:
        """Ask the scheduler to stop after the current batch — the public
        early-exit hook apps use for max-batches caps."""
        self._stop.set()

    def request_abort(self, reason: str = "runtime guard abort") -> None:
        """Loud-failure hook for the runtime guards (fetch watchdog,
        divergence sentinel, lockstep peer watchdog, cadence
        disagreement): mark the run failed and stop after the current
        batch, so the app's shutdown path still flushes its final
        checkpoint and the process exits non-zero.

        Every abort path funnels through here, which makes it the crash
        flight recorder's trigger (telemetry/blackbox.py): the post-mortem
        bundle dumps ONCE, before the stream winds down — no-op when no
        recorder is installed."""
        self.failed = True
        from ..telemetry import blackbox as _blackbox

        _blackbox.abort_dump(reason)
        self.request_stop()

    @property
    def stop_requested(self) -> bool:
        """Whether a stop has been requested (read by the concurrent
        fetch pipeline to honor max-batches caps exactly, apps/common.py
        FetchPipeline)."""
        return self._stop.is_set()

    def _putback(self, items: list) -> None:
        """Return this tick's drained items to the queue FRONT in order —
        an elastic membership transition re-forms the group between ticks,
        and the rows drained for the interrupted tick must train on the
        next one (no silent loss)."""
        for item in reversed(items):
            self._queue.putback(item)

    def _elastic_recover(self, local: list, why: str) -> bool:
        """Peer-loss recovery hook: with an elastic membership plane
        installed, a wedged/failed cadence collective becomes a rescue
        (shrink + re-form + continue) instead of an abort. Returns True
        when the loop should continue on the re-formed group."""
        if self.membership is None:
            return False
        self._putback(local)
        _metrics.get_registry().counter("lockstep.elastic_rescues").inc()
        log.critical(
            "lockstep cadence collective failed (%s); elastic membership "
            "is ON — attempting an out-of-band shrink instead of aborting",
            why,
        )
        try:
            return self.membership.rescue(why)
        except Exception:
            log.critical("elastic rescue failed", exc_info=True)
            return False

    def _run_batch_aligned(self, statuses: list[Status], batch_time: float) -> None:
        """Lockstep-mode batch: host-local failures must never change this
        host's COLLECTIVE program sequence (the other hosts' psums would
        block forever on the missing program). A featurize failure — purely
        host-side, nothing dispatched yet — substitutes the all-padding
        batch (rows lost, loudly). A shape overflow of the pinned buckets
        would dispatch a DIFFERENTLY-SHAPED program than the peers', so it
        is a hard error. Output (dispatch/handler) exceptions propagate to
        the loop: after a possible partial dispatch alignment is unknowable,
        and failing fast beats a distributed hang."""
        stream = self._stream
        if not isinstance(stream, FeatureStream):
            # raw lockstep (the k-means entry): the app's per-batch handler
            # owns fixed-shape padding and global assembly, so there is no
            # featurize stage to guard here; handler failures propagate to
            # the loop's abort path (alignment unknowable after a possible
            # partial dispatch)
            stream._process(statuses, batch_time)
            self.batches_processed += 1
            return
        # freshness lineage (r16): one open per lockstep batch, stamped
        # before featurize like FeatureStream._process (the failure paths
        # below re-featurize but never re-open)
        _lineage.open_batch(statuses)
        # intake journal (r21): append ONCE per lockstep batch — the
        # failure paths below re-featurize but never re-append
        _journal.record_intake(statuses)
        try:
            batch = stream._featurize(statuses)
        except Exception:
            log.exception(
                "featurize failed in lockstep mode; substituting an "
                "all-padding batch to keep the group's collective sequence "
                "aligned (these rows are lost)"
            )
            batch = stream._featurize([])
        if stream.bucket_overflow(batch):
            # single-host runs grow the bucket and recompile (benign); here
            # a grown shape means THIS host dispatches a differently-shaped
            # collective program than its peers → distributed hang. The
            # overflow is data-dependent (one long tweet), so it must not
            # kill the run either: drop the over-long rows, keep the rest.
            # conservative probe: the featurizer owns the canonical text
            # encoding (host-hash wire carries units-1 bigram tokens, so
            # <= token_bucket under-admits by at most one unit there)
            kept = [
                s for s in statuses
                if stream.featurizer.unit_len(s) <= stream.token_bucket
            ]
            rows, tokens = stream.batch_shape(batch)
            log.error(
                "batch shape (%d, %d) overflowed the pinned buckets "
                "(%d, %d) in a multi-host run; dropping %d over-long row(s) "
                "to keep the group's program shapes aligned — raise "
                "--batchBucket/--tokenBucket", rows, tokens,
                stream.row_bucket, stream.token_bucket,
                len(statuses) - len(kept),
            )
            # registry state, not log-only (r7): dropped rows must show on
            # /api/metrics next to the other ingest-loss counters
            _metrics.get_registry().counter(
                "ingest.rows_dropped_overflow"
            ).inc(len(statuses) - len(kept))
            batch = stream._featurize(kept)
            if stream.bucket_overflow(batch):
                # probe missed (e.g. a case fold changed the length):
                # last resort keeps alignment at the cost of the batch
                log.error("overflow persists; dropping the whole batch")
                _metrics.get_registry().counter(
                    "ingest.rows_dropped_overflow"
                ).inc(len(kept))
                batch = stream._featurize([])
        stream._record_metrics(batch)
        for fn in stream._outputs:
            fn(batch, batch_time)
        self.batches_processed += 1

    def _lockstep_loop(self) -> None:
        """Multi-host batch scheduler: every process must run the SAME
        sequence of collective programs, so batch cadence and termination
        are agreed per tick with one tiny all-process allgather of
        (has_rows, more_coming, abort). A host whose intake shard ran dry
        keeps dispatching all-padding batches (zero-sample steps are weight
        no-ops) until EVERY host is exhausted — otherwise the other hosts'
        psums would wait forever on its missing program.

        A batch failure AFTER featurize leaves this host's collective
        alignment unknowable, so it stops dispatching — but it keeps
        ticking the allgather with abort=1 until every peer has seen it
        (peers then stop too instead of stalling in their next collective),
        and the run is marked ``failed`` so the app can exit non-zero
        rather than report success.

        A hard-killed peer can never tick its abort flag, so the allgather
        itself runs under a progress watchdog (``_watched_allgather``,
        ``TWTML_LOCKSTEP_TIMEOUT_S``): when it fires — or the collective
        raises a transport error, the other way a dead peer surfaces —
        this host aborts LOUDLY (``failed=True`` → the app exits non-zero
        after its shutdown path flushes a final checkpoint) instead of
        hanging in the collective forever. Collectives INSIDE a dispatched
        step are covered separately: their results surface through the
        pooled stats fetch, whose own watchdog (apps/common.FetchWatchdog)
        aborts the same way.

        Drains are capped at the row bucket in BOTH modes (wall-clock rows
        beyond the bucket stay queued for the next tick): an uncapped drain
        could exceed --batchBucket and grow this host's program shape away
        from its peers'.

        **Per-host telemetry sideband (r8)**: the flags array WIDENS to
        carry each host's fixed sideband vector (telemetry/sideband.py —
        per-stage wall times, queue depth, fetch-RTT median, shed/rollback
        counters, health phase) on the SAME allgather: zero added
        collectives, zero added host fetches (the vector is host-side
        bookkeeping). Every host then holds the full ``[hosts, W]`` matrix
        per tick; the straggler attributor (telemetry/straggler.py) names
        the gating host + stage, and the view feeds the dashboard's
        ``Hosts`` tiles and the crash flight recorder."""
        import os

        import jax
        import numpy as np

        from . import faults as _faults
        from . import membership as _membership

        watch_s = float(
            os.environ.get(LOCKSTEP_TIMEOUT_ENV, "")
            or LOCKSTEP_TIMEOUT_DEFAULT_S
        )
        tele = _sideband.LockstepTelemetry(
            jax.process_index(), jax.process_count()
        )
        limit = getattr(self._stream, "row_bucket", 0)
        next_tick = time.monotonic() + self.batch_interval
        aborting = False
        tick_no = 0
        while not self._stop.is_set():
            if self.batch_interval > 0 and not aborting:
                delay = next_tick - time.monotonic()
                if delay > 0 and self._stop.wait(delay):
                    break
                next_tick += self.batch_interval
            elif limit and not aborting:
                # back-to-back fill gate, as in _scheduler_loop
                while (
                    self._queue.rows_queued < limit
                    and not self._source.exhausted
                    and not self._stop.is_set()
                ):
                    self._stop.wait(0.002)
            tick_no += 1
            # --chaos peer.kill/peer.pause: membership churn injectable
            # from the CLI like every other fault (streaming/faults.py) —
            # a hard exit or a long stall at a deterministic tick. The uid
            # selector (peer.kill:uid=N) targets the ORIGINAL process id,
            # stable across elastic epochs, so one shared --chaos spec
            # kills/pauses specific hosts (the lead included) from a
            # fleet-wide command line.
            _faults.lockstep_chaos(
                tick_no, self.batch_interval,
                uid=(
                    self.membership.uid if self.membership is not None
                    else jax.process_index()
                ),
            )
            local = self._drain(limit)
            rows = sum(getattr(s, "rows", 1) for s in local)
            more = (not self._source.exhausted) or self._queue.rows_queued > 0
            # the divergence sentinel's rollback count rides the SAME
            # cadence allgather (zero extra collectives): stats are
            # psum-global and deliveries deterministic, so every host
            # reaches the same verdict at the same step — the gathered
            # counts verify that instead of assuming it
            rollbacks = (
                int(self.rollback_count_fn())
                if self.rollback_count_fn is not None
                else 0
            )
            mem_cols = (
                self.membership.pre_tick()
                if self.membership is not None
                else np.zeros((_membership.WIDTH,), np.float64)
            )
            try:
                # the sideband AND the membership columns ride the SAME
                # allgather: flags widen from 4 ints to 4 + membership.WIDTH
                # + sideband.WIDTH floats (int flags are exact in float64)
                # — never a second collective
                flags = _watched_allgather(
                    np.concatenate([
                        np.array(
                            [rows > 0 and not aborting,
                             more and not aborting, aborting, rollbacks],
                            dtype=np.float64,
                        ),
                        mem_cols,
                        tele.vector(rollbacks=rollbacks),
                    ]),
                    watch_s,
                )
            except Exception:
                if self._elastic_recover(
                    local, "cadence allgather transport error"
                ):
                    tele = _sideband.LockstepTelemetry(
                        jax.process_index(), jax.process_count()
                    )
                    next_tick = time.monotonic() + self.batch_interval
                    continue
                log.critical(
                    "lockstep cadence allgather FAILED — a peer likely "
                    "died mid-run; aborting this host loudly (progress up "
                    "to the last checkpoint boundary is saved)",
                    exc_info=True,
                )
                _metrics.get_registry().counter(
                    "lockstep.watchdog_aborts"
                ).inc()
                self.request_abort("lockstep cadence allgather failed "
                                   "(peer death / transport error)")
                break
            tele.tick_done()  # waiting-in-collective ends here
            if flags is None:
                if self._elastic_recover(
                    local, f"no allgather progress in {watch_s:.0f}s"
                ):
                    tele = _sideband.LockstepTelemetry(
                        jax.process_index(), jax.process_count()
                    )
                    next_tick = time.monotonic() + self.batch_interval
                    continue
                log.critical(
                    "lockstep peer watchdog: the cadence allgather made no "
                    "progress in %.0fs — a peer is gone (hard kill or "
                    "network partition). Aborting this host loudly instead "
                    "of hanging in the collective; tune with %s (0 "
                    "disables).",
                    watch_s, LOCKSTEP_TIMEOUT_ENV,
                )
                _metrics.get_registry().counter(
                    "lockstep.watchdog_aborts"
                ).inc()
                _trace.get().instant("lockstep_watchdog", timeout_s=watch_s)
                self.request_abort(
                    f"lockstep peer watchdog: no allgather progress in "
                    f"{watch_s:.0f}s"
                )
                break
            # single-process gathers come back without the process axis
            flags = np.atleast_2d(np.asarray(flags))
            fi = flags[:, :4].astype(np.int64)  # the lockstep decisions
            mem_end = 4 + _membership.WIDTH
            if flags.shape[1] > mem_end:
                # per-host sideband matrix: straggler attribution + the
                # hosts[] view (pure host-side bookkeeping)
                tele.ingest(flags[:, mem_end:].astype(np.float64))
            if self.membership is not None:
                action = self.membership.ingest(
                    flags[:, 4:mem_end].astype(np.int64)
                )
                if action == "reform":
                    # a committed view change: this tick's rows go back to
                    # the queue, the group re-forms (members of the new
                    # view; a clean commit is loss-free — the lead
                    # checkpoints inside the transition), and the loop
                    # resumes on the new epoch
                    self._putback(local)
                    self.membership.execute_reform()
                    tele = _sideband.LockstepTelemetry(
                        jax.process_index(), jax.process_count()
                    )
                    next_tick = time.monotonic() + self.batch_interval
                    continue
                if action == "parked":
                    # evicted: leave the group, then poll for readmission
                    self._putback(local)
                    if self.membership.park():
                        tele = _sideband.LockstepTelemetry(
                            jax.process_index(), jax.process_count()
                        )
                        next_tick = time.monotonic() + self.batch_interval
                        continue
                    self.request_abort(
                        "elastic: evicted from the lockstep group and not "
                        "readmitted within the park window"
                    )
                    break
            if fi[:, 2].any():
                # this host (or a peer) aborted: everyone has now agreed on
                # it in the same tick, so everyone can stop dispatching
                if not aborting:
                    log.critical("a peer host aborted the lockstep run")
                self.request_abort(
                    "lockstep batch failure on this host"
                    if aborting else "a peer host aborted the lockstep run"
                )
                break
            if len(set(fi[:, 3].tolist())) > 1:
                # sentinel rollbacks must land on the SAME step on every
                # host (global stats + deterministic deliveries guarantee
                # it); disagreement means the hosts' model states have
                # diverged — abort the group rather than train past it
                log.critical(
                    "lockstep hosts disagree on sentinel rollback counts "
                    "%s — model states have diverged; aborting the group",
                    fi[:, 3].tolist(),
                )
                _metrics.get_registry().counter(
                    "lockstep.rollback_disagreements"
                ).inc()
                self.request_abort(
                    "lockstep hosts disagree on sentinel rollback counts "
                    f"{fi[:, 3].tolist()}"
                )
                break
            if fi[:, 0].any():
                # somebody has rows: EVERY host dispatches (local may be
                # empty — it pads to the pinned bucket)
                try:
                    self._run_batch_aligned(local, now_s())
                except Exception:
                    log.critical(
                        "lockstep batch failed after featurize; this host's "
                        "collective alignment is unknowable — aborting the "
                        "group (fail fast beats a distributed hang)",
                        exc_info=True,
                    )
                    aborting = True  # next tick broadcasts abort to peers
            if not aborting and not (fi[:, 0].any() or fi[:, 1].any()):
                break
        self._terminated.set()

    # -- lifecycle (ssc.start/awaitTermination, LinearRegression.scala:89-91) --
    def start(self, lockstep: bool = False) -> None:
        """``lockstep=True`` (multi-host runs) replaces the local scheduler
        with the collectively-agreed one (``_lockstep_loop``)."""
        if self._stream is None:
            raise ValueError("no stream registered")
        self._stop.clear()
        self._terminated.clear()
        self.failed = False
        self._source.start(self._queue.put)
        self._scheduler = threading.Thread(
            target=self._lockstep_loop if lockstep else self._scheduler_loop,
            name="twtml-batch-scheduler", daemon=True,
        )
        self._scheduler.start()

    def await_termination(self, timeout: float | None = None) -> bool:
        return self._terminated.wait(timeout)

    def stop(self) -> None:
        self._stop.set()
        # release a producer blocked on a full bounded queue FIRST, or the
        # source's join would time out against a wedged put()
        self._queue.close()
        if self._source is not None:
            self._source.stop()
        if self._scheduler is not None:
            self._scheduler.join(timeout=10)
        self._terminated.set()

    # -- deterministic replay mode (no wall clock) ---------------------------
    def run_to_completion(self, max_batch_size: int = 1024) -> int:
        """Drive the source synchronously: fill batches of up to
        ``max_batch_size`` tweets and process back-to-back. Returns number of
        batches run. Used by benchmarks and parity tests where the 5s cadence
        would only add idle time."""
        if self._stream is None:
            raise ValueError("no stream registered")
        self._source.start(self._queue.put)
        n0 = self.batches_processed
        pending: list[Status] = []
        while not self._stop.is_set():
            try:
                pending.append(self._queue.get(timeout=0.05))
                if len(pending) >= max_batch_size:
                    self._run_batch(pending, now_s())
                    pending = []
            except queue.Empty:
                if self._source.exhausted:
                    # re-drain: the source may have emitted between our
                    # timeout and the exhausted flag being set
                    pending.extend(self._drain())
                    break
        if pending and not self._stop.is_set():
            self._run_batch(pending, now_s())
        self._terminated.set()
        return self.batches_processed - n0
