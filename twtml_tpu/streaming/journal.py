"""Durable intake journal — crash-equals-clean replay recovery (ISSUE 19).

The reference delegates durability to Spark's receiver write-ahead log
(SURVEY §1); this repo's recovery paths historically *counted* rows lost
(sentinel skips, elastic in-flight discards, watchdog-abort restarts). The
journal closes that gap: every batch of raw rows is appended at the ONE
intake seam (post-parse, pre-featurize — ``FeatureStream._process`` /
``StreamingContext._run_batch_aligned``; lawcheck TW009 pins the seam) as a
CRC32-framed record with a monotonic lineage id, and every recovery path
re-ingests from the cursor its checkpoint stamped instead of skipping.

Design points:

- **Host-side only.** Appends are buffered file writes + one ``flush()``
  (no fsync — a SIGKILL'd process's flushed pages survive in the page
  cache; only a machine crash loses them, and the frame CRC turns that
  into a LOUD truncated tail, never silent corruption). Zero added device
  fetches, zero added collectives; multi-host replay rides the existing
  lockstep cadence (replayed rows re-enter the queue; dry hosts dispatch
  all-padding per the lockstep invariant).
- **Parity ground truth.** Object records serialize the ``Status`` fields
  the featurizer reads (recursively through ``retweeted_status``); block
  records preserve the ``ParsedBlock`` arrays bit-for-bit including the
  units dtype (uint8 ASCII wire vs uint16). Replayed rows re-enter the
  UNCHANGED featurize path, so replay is byte-identical to first ingest
  (differential-tested both paths, tests/test_journal.py).
- **Bounded disk.** Fixed-size segments rotate; a segment retires once a
  verified checkpoint covers every record in it (the cursor stamped into
  checkpoint meta by ``AppCheckpoint._save``), and ``--journalMaxMb`` is a
  hard ceiling enforced by dropping the OLDEST segments loudly (counted).
- **Replay suppression.** Replayed rows re-cross the intake seam; the
  journal suppresses re-appending exactly those rows (putback lands at the
  queue FRONT and the scheduler is single-threaded, so the first N rows
  through the seam after a replay ARE the N replayed rows) — without this
  a second rollback to the same checkpoint would double-train.

Frame format (little-endian):
``b"TWJL" | u32 payload_len | u32 crc32(payload) | payload`` where
``payload = u64 record_id | u64 rows_after | u8 kind | u32 nrows | body``.
``rows_after`` is the cumulative row count AFTER this record, so the tail
of the last segment alone recovers the journal position; a torn tail from
kill -9 mid-write fails the CRC (or length) check and is truncated loudly
(``journal.torn_tails``).
"""

from __future__ import annotations

import collections
import json
import operator
import os
import re
import struct
import threading
import zlib

from ..telemetry import metrics as _metrics
from ..utils import get_logger

log = get_logger("streaming.journal")

MAGIC = b"TWJL"
_FRAME = struct.Struct("<4sII")  # magic, payload_len, crc32(payload)
_RECORD = struct.Struct("<QQBI")  # record id, rows_after, kind, nrows
KIND_OBJ = 1
KIND_BLOCK = 2
# block body header: units dtype code (1 = uint8 ASCII wire, 2 = uint16)
_BLOCK = struct.Struct("<BQ")  # units dtype code, units length
_SEG_RE = re.compile(r"^seg-(\d{20})\.twj$")

# segments rotate at this size unless --journalMaxMb forces smaller (the
# retirement granularity: a segment only retires whole)
_SEGMENT_BYTES_DEFAULT = 16 * 1024 * 1024
_PAYLOAD_MAX = 1 << 31  # sanity bound when scanning possibly-garbage tails


# KIND_OBJ body: a JSON array of 9-element rows
# [text, retweet_count, followers_count, favourites_count, friends_count,
#  created_at_ms, lang, id, retweeted_status-row-or-null]. Rows, not
# key-value objects: the C-speed attrgetter + positional JSON encode is
# faster and smaller than per-status dicts, and the append sits
# on the hot intake seam.
_STATUS_FIELDS = operator.attrgetter(
    "text", "retweet_count", "followers_count", "favourites_count",
    "friends_count", "created_at_ms", "lang", "id", "retweeted_status",
)


def _status_to_row(s) -> tuple:
    row = _STATUS_FIELDS(s)
    if row[8] is None:
        return row
    return row[:8] + (_status_to_row(row[8]),)


def _row_to_status(v):
    from ..features.featurizer import Status

    rs = v[8]
    return Status(
        text=v[0], retweet_count=v[1], followers_count=v[2],
        favourites_count=v[3], friends_count=v[4],
        created_at_ms=v[5], lang=v[6], id=v[7],
        retweeted_status=_row_to_status(rs) if rs is not None else None,
    )


def _encode_block(block) -> bytes:
    import numpy as np

    units = np.ascontiguousarray(block.units)
    code = 1 if units.dtype == np.uint8 else 2
    return b"".join((
        _BLOCK.pack(code, units.size),
        np.ascontiguousarray(block.numeric, dtype=np.int64).tobytes(),
        units.tobytes(),
        np.ascontiguousarray(block.offsets, dtype=np.int64).tobytes(),
        np.ascontiguousarray(block.ascii, dtype=np.uint8).tobytes(),
    ))


def _decode_block(nrows: int, body: bytes):
    import numpy as np

    from ..features.blocks import ParsedBlock

    code, units_len = _BLOCK.unpack_from(body, 0)
    pos = _BLOCK.size
    numeric = np.frombuffer(
        body, np.int64, nrows * 5, pos).reshape(nrows, 5).copy()
    pos += nrows * 5 * 8
    units_dtype = np.uint8 if code == 1 else np.uint16
    units = np.frombuffer(body, units_dtype, units_len, pos).copy()
    pos += units_len * units_dtype().itemsize
    offsets = np.frombuffer(body, np.int64, nrows + 1, pos).copy()
    pos += (nrows + 1) * 8
    ascii_col = np.frombuffer(body, np.uint8, nrows, pos).copy()
    return ParsedBlock(numeric, units, offsets, ascii_col)


def _rows_of(items: list) -> int:
    # seam batches are homogeneous (Status objects OR parsed blocks, per
    # source kind — the same assumption ``_encode_items`` keys on). Probe
    # once: a per-item getattr-with-default over a Status batch pays a
    # swallowed AttributeError PER ROW, and this runs on the hot seam.
    if not items or getattr(items[0], "rows", None) is None:
        return len(items)
    return sum(item.rows for item in items)


class IntakeJournal:
    """Append-only, segment-rotated, CRC-framed row journal for one host.

    Thread-safety: appends happen on the scheduler thread only (the seam);
    replay/retire happen on the same thread (recovery runs inside the
    scheduler's delivery path or before the stream starts). The lock
    guards the cheap bookkeeping against telemetry readers.
    """

    def __init__(self, directory: str, max_mb: int = 512):
        self.directory = directory
        self.max_bytes = max(1, int(max_mb)) * 1024 * 1024
        self.segment_bytes = max(
            1024 * 1024, min(_SEGMENT_BYTES_DEFAULT, self.max_bytes // 4)
        )
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.Lock()
        self._fh = None
        self._active_size = 0
        self._pending_replay = 0  # rows to suppress re-appending
        reg = _metrics.get_registry()
        self._appended = reg.counter("journal.appended_rows")
        self._replayed = reg.counter("journal.replayed_rows")
        self._torn = reg.counter("journal.torn_tails")
        self._dropped_segments = reg.counter("journal.segments_dropped")
        self._disk_gauge = reg.gauge("journal.disk_mb")
        self.next_id = 0
        self.rows_total = 0
        self._recover_tail()
        # dispatch-token cursor: the FetchPipeline dispatches AHEAD of
        # delivery, so the journal tail at save time can include records no
        # trained weight covers yet. Each seam crossing pushes its
        # post-append position; the delivery path pops in order and commits
        # a position only when its batch is FULLY admitted (note_delivered)
        # — the checkpoint stamps _committed, never the tail.
        self._inflight: "collections.deque" = collections.deque()
        self._delivery_pos: "tuple[int, int] | None" = None
        self._replay_draining = False
        self._committed = (self.next_id, self.rows_total)
        # incrementally-maintained disk total: the per-append gauge update
        # must not pay an os.listdir + stat sweep per batch
        # (recomputed exactly at open and on retire/drop)
        self._disk_bytes = self.disk_bytes()
        self._update_disk_gauge()

    # ---------------------------------------------------------------- disk

    def _segments(self) -> "list[tuple[int, str]]":
        """Sorted (first_record_id, path) of every on-disk segment."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            m = _SEG_RE.match(name)
            if m:
                out.append((int(m.group(1)), os.path.join(self.directory, name)))
        out.sort()
        return out

    def _seg_path(self, first_id: int) -> str:
        return os.path.join(self.directory, f"seg-{first_id:020d}.twj")

    def _scan_segment(self, path: str):
        """Yield (record_id, rows_after, kind, nrows, body, end_offset) for
        every CRC-valid frame, stopping at the first invalid one."""
        with open(path, "rb") as fh:
            data = fh.read()
        pos = 0
        while pos + _FRAME.size <= len(data):
            magic, plen, crc = _FRAME.unpack_from(data, pos)
            if magic != MAGIC or plen < _RECORD.size or plen > _PAYLOAD_MAX:
                return
            end = pos + _FRAME.size + plen
            if end > len(data):
                return  # torn mid-payload
            payload = data[pos + _FRAME.size: end]
            if zlib.crc32(payload) != crc:
                return  # torn mid-frame / bit rot
            rec_id, rows_after, kind, nrows = _RECORD.unpack_from(payload, 0)
            yield rec_id, rows_after, kind, nrows, payload[_RECORD.size:], end
            pos = end

    def _recover_tail(self) -> None:
        """Find the journal position (next_id, rows_total) from the newest
        segment holding a valid frame, truncating a torn tail LOUDLY."""
        segments = self._segments()
        for first_id, path in reversed(segments):
            size = os.path.getsize(path)
            valid_end = 0
            last = None
            for rec in self._scan_segment(path):
                last = rec
                valid_end = rec[5]
            if valid_end < size:
                self._torn.inc()
                log.error(
                    "journal: TORN TAIL in %s — %d byte(s) after the last "
                    "CRC-valid frame truncated (a kill mid-append); every "
                    "complete record before it survives", path,
                    size - valid_end,
                )
                with open(path, "r+b") as fh:
                    fh.truncate(valid_end)
            if last is not None:
                self.next_id = last[0] + 1
                self.rows_total = last[1]
                return
            if valid_end == 0 and first_id != 0:
                # fully-torn empty segment: position comes from the
                # previous segment's tail; drop the husk
                os.unlink(path)
                continue
            self.next_id = first_id
            return

    def _rotate_if_needed(self) -> None:
        if self._fh is not None and self._active_size < self.segment_bytes:
            return
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        if self._fh is None:
            path = self._seg_path(self.next_id)
            self._fh = open(path, "ab")
            self._active_size = self._fh.tell()

    def disk_bytes(self) -> int:
        return sum(os.path.getsize(p) for _, p in self._segments())

    def _update_disk_gauge(self) -> None:
        self._disk_gauge.set(round(self._disk_bytes / (1024 * 1024), 3))

    def _enforce_max_bytes(self) -> None:
        """--journalMaxMb is a HARD disk ceiling: drop the oldest whole
        segments (never the active one) until under it — loudly, because
        dropped records are rows a deep-enough rollback can no longer
        replay (the normal path retires them via checkpoint coverage
        first, so this only fires when the cadence lags the intake)."""
        if self._disk_bytes <= self.max_bytes:
            return
        for _, path in self._segments()[:-1]:
            if self._disk_bytes <= self.max_bytes:
                break
            size = os.path.getsize(path)
            os.unlink(path)
            self._disk_bytes -= size
            self._dropped_segments.inc()
            log.warning(
                "journal: disk ceiling --journalMaxMb exceeded — dropped "
                "oldest segment %s (%d bytes); rows in it are no longer "
                "replayable (counted in journal.segments_dropped)",
                os.path.basename(path), size,
            )

    # -------------------------------------------------------------- append

    def append(self, items: list) -> None:
        """Journal one seam batch (list of Status, or list of ParsedBlock).
        Empty batches (all-padding lockstep ticks, warmups) are skipped.
        Rows under replay suppression are NOT re-appended — their original
        records already cover them; a mixed batch (replayed head + fresh
        tail, one fill-gate drain) appends only the fresh tail."""
        rows = _rows_of(items)
        if rows == 0:
            return
        with self._lock:
            if self._pending_replay:
                if rows <= self._pending_replay:
                    self._pending_replay -= rows
                    return
                items = self._split_items(items, self._pending_replay)
                rows = _rows_of(items)
                self._pending_replay = 0
            kind, body, nrows = self._encode_items(items)
            payload = _RECORD.pack(
                self.next_id, self.rows_total + nrows, kind, nrows
            ) + body
            self._rotate_if_needed()
            self._fh.write(_FRAME.pack(MAGIC, len(payload), zlib.crc32(payload)))
            self._fh.write(payload)
            self._fh.flush()
            self._active_size += _FRAME.size + len(payload)
            self._disk_bytes += _FRAME.size + len(payload)
            self.next_id += 1
            self.rows_total += nrows
            self._appended.inc(nrows)
            if self._active_size >= self.segment_bytes:
                self._enforce_max_bytes()
            self._update_disk_gauge()

    # ------------------------------------------------- dispatch-token cursor

    def push_dispatch(self) -> None:
        """Called once per seam crossing, AFTER ``append`` (even for empty
        batches — all-padding lockstep ticks still dispatch a program).
        Pushes the post-append journal position, or ``None`` while replay
        suppression is still armed: a mid-replay batch's delivery must not
        move the committed cursor (its rows sit BELOW the replay cursor the
        current weights already lost). The batch that drains suppression to
        zero pushes the real tail — when IT delivers, every journaled row
        has been trained exactly once."""
        with self._lock:
            if self._pending_replay > 0:
                self._inflight.append(None)
            else:
                self._inflight.append((self.next_id, self.rows_total))

    def pop_dispatch(self) -> None:
        """Called once per delivered batch at the OUTERMOST delivery
        wrapper, before any admission filter can return early — deliveries
        arrive in dispatch order, so popping left re-pairs each delivery
        with its seam token even when an inner wrapper then skips it."""
        with self._lock:
            self._delivery_pos = (
                self._inflight.popleft() if self._inflight else None
            )

    def note_delivered(self) -> None:
        """Called from the INNERMOST delivery wrapper — only batches every
        admission filter accepted (no sentinel skip, no globally-empty
        no-op) reach it. Commits the popped token: records below it are now
        inside the trained weights, so a checkpoint may stamp it."""
        with self._lock:
            pos = self._delivery_pos
            self._delivery_pos = None
            if pos is not None and pos[0] >= self._committed[0]:
                self._committed = pos
                self._replay_draining = False

    def drop_newest(self) -> None:
        """A single-host empty batch was shed before dispatch: un-push its
        seam token (the scheduler is single-threaded, so the newest token
        is this batch's)."""
        with self._lock:
            if self._inflight:
                self._inflight.pop()

    def clear_inflight(self) -> None:
        """Elastic reform discards the fetch pipeline's in-flight
        deliveries wholesale (drain_discard) — their tokens would strand
        and desync every later pairing. Drop them; replay re-covers their
        rows."""
        with self._lock:
            self._inflight.clear()
            self._delivery_pos = None

    @property
    def save_allowed(self) -> bool:
        """False while a replay is still draining through the seam: a save
        now would stamp a cursor the weights do not cover yet (the final
        replayed batch has not delivered), and a crash after it would
        double-train on restore. Callers defer the save one boundary."""
        with self._lock:
            return not self._replay_draining

    @staticmethod
    def _split_items(items: list, skip_rows: int) -> list:
        """Drop the first ``skip_rows`` rows of a seam batch (the replayed
        head of a mixed drain)."""
        first = items[0]
        if getattr(first, "rows", None) is None:
            return items[skip_rows:]
        from ..features.blocks import merge_blocks, slice_block

        block = merge_blocks(list(items))
        return [slice_block(block, skip_rows, block.rows)]

    @staticmethod
    def _encode_items(items: list):
        first = items[0]
        if getattr(first, "rows", None) is not None:
            from ..features.blocks import merge_blocks

            block = merge_blocks(list(items))
            return KIND_BLOCK, _encode_block(block), block.rows
        body = json.dumps(
            [_status_to_row(s) for s in items],
            separators=(",", ":"), ensure_ascii=False,
        ).encode("utf-8")
        return KIND_OBJ, body, len(items)

    # -------------------------------------------------------------- replay

    def records_from(self, cursor: int):
        """Yield (record_id, items) for every record with id >= cursor, in
        id order. Items decode to exactly what crossed the seam: a list of
        Status for object records, a one-ParsedBlock list for block
        records. A CRC failure mid-history (bit rot in a non-tail segment)
        raises — silent partial replay would be silent data loss."""
        segments = self._segments()
        for i, (first_id, path) in enumerate(segments):
            next_first = (
                segments[i + 1][0] if i + 1 < len(segments) else self.next_id
            )
            if next_first <= cursor:
                continue
            expect = first_id
            for rec_id, _rows_after, kind, nrows, body, _end in (
                self._scan_segment(path)
            ):
                expect = rec_id + 1
                if rec_id < cursor:
                    continue
                if kind == KIND_BLOCK:
                    yield rec_id, [_decode_block(nrows, body)]
                else:
                    yield rec_id, [
                        _row_to_status(d)
                        for d in json.loads(body.decode("utf-8"))
                    ]
            if expect < next_first:
                raise RuntimeError(
                    f"journal segment {path} is corrupt mid-history "
                    f"(valid through record {expect - 1}, expected "
                    f"{next_first - 1}); replay would silently lose rows"
                )

    def replay_from(self, cursor: int) -> "tuple[list, int]":
        """Materialize every record with id >= cursor as queue items and
        ARM replay suppression for their rows (they will re-cross the
        seam). Returns (items, rows). Counted in journal.replayed_rows."""
        items: list = []
        for _rec_id, rec_items in self.records_from(cursor):
            items.extend(rec_items)
        rows = _rows_of(items)
        with self._lock:
            self._pending_replay += rows
            # the restored weights cover exactly [0, cursor): re-base the
            # committed position there and hold checkpoint saves until the
            # final replayed batch delivers (save_allowed)
            self._committed = (cursor, self.rows_total - rows)
            self._replay_draining = rows > 0
        if rows:
            self._replayed.inc(rows)
        return items, rows

    def cancel_pending_replay(self) -> int:
        """Rows of an earlier replay still awaiting their seam re-cross.
        A NEW replay supersedes them (its cursor sits at or below theirs,
        so its items re-cover the same rows): the caller must remove them
        from the queue front and this zeroes the suppression they armed —
        leaving both would putback the overlap twice and double-train."""
        with self._lock:
            stale = self._pending_replay
            self._pending_replay = 0
            return stale

    def rows_from(self, cursor: int) -> int:
        """Row count of records with id >= cursor (no decode of bodies
        beyond the record header — used for count-only assertions)."""
        rows = 0
        segments = self._segments()
        for i, (first_id, path) in enumerate(segments):
            next_first = (
                segments[i + 1][0] if i + 1 < len(segments) else self.next_id
            )
            if next_first <= cursor:
                continue
            for rec_id, _ra, _kind, nrows, _body, _end in (
                self._scan_segment(path)
            ):
                if rec_id >= cursor:
                    rows += nrows
        return rows

    # ---------------------------------------------------- checkpoint hooks

    def snapshot_for_checkpoint(self) -> dict:
        """The cursor stamp ``AppCheckpoint._save`` writes into verified
        checkpoint meta: every record with id < cursor is inside the saved
        state. This is the COMMITTED delivery position, not the journal
        tail — the fetch pipeline dispatches ahead of delivery, so at save
        time the tail can include in-flight records no trained weight
        covers yet; stamping those would lose them on the next rollback."""
        with self._lock:
            return {"cursor": self._committed[0], "rows": self._committed[1]}

    def retire_covered(self, cursor: int) -> int:
        """Unlink whole segments every record of which is < cursor — the
        oldest RETAINED verified checkpoint covers them, so no rollback
        can need them. Never touches the active (newest) segment."""
        segments = self._segments()
        retired = 0
        for i, (_first_id, path) in enumerate(segments[:-1]):
            if segments[i + 1][0] > cursor:
                break
            try:
                os.unlink(path)
                retired += 1
            except OSError:
                break
        if retired:
            log.info(
                "journal: retired %d segment(s) covered by verified "
                "checkpoint cursor %d", retired, cursor,
            )
            self._disk_bytes = self.disk_bytes()
            self._update_disk_gauge()
        return retired

    def reset(self) -> None:
        """Drop every journaled record (elastic rejoin: this host's
        pre-departure coverage was adopted by the survivors — replaying it
        would double-train). Record ids stay MONOTONIC: the next append
        opens a fresh segment at the current ``next_id``, so cursor
        comparisons against old checkpoint stamps remain ordered. Also
        clears any armed replay suppression — rows putback before a reset
        never re-cross the seam."""
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None
            for _first_id, path in self._segments():
                try:
                    os.unlink(path)
                except OSError:
                    pass
            self._active_size = 0
            self._pending_replay = 0
            self._inflight.clear()
            self._delivery_pos = None
            self._replay_draining = False
            self._committed = (self.next_id, self.rows_total)
            self._disk_bytes = self.disk_bytes()
            self._update_disk_gauge()
        log.warning(
            "journal: RESET — all segments dropped, next append starts a "
            "fresh segment at id %d", self.next_id,
        )

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


# ------------------------------------------------------- module-global face
# (the blackbox/faults idiom: entry points install once, seams call the
# module-level hook, tests uninstall)

_JOURNAL: "IntakeJournal | None" = None


def install(directory: str, max_mb: int = 512) -> IntakeJournal:
    global _JOURNAL
    if _JOURNAL is not None:
        _JOURNAL.close()
    _JOURNAL = IntakeJournal(directory, max_mb=max_mb)
    log.info(
        "intake journal ON: %s (max %d MB, position id=%d rows=%d)",
        directory, max_mb, _JOURNAL.next_id, _JOURNAL.rows_total,
    )
    return _JOURNAL


def get() -> "IntakeJournal | None":
    return _JOURNAL


def uninstall() -> None:
    global _JOURNAL
    if _JOURNAL is not None:
        _JOURNAL.close()
    _JOURNAL = None


def record_intake(items: list) -> None:
    """THE intake seam hook (lawcheck TW009: only streaming/context.py may
    call this) — append one drained seam batch and push its dispatch token
    (the delivery path pops it to advance the committed cursor); no-op when
    the journal is off so ``--journal off`` is bit-exact pre-journal
    behavior."""
    if _JOURNAL is not None:
        _JOURNAL.append(items)
        _JOURNAL.push_dispatch()


def snapshot_for_checkpoint() -> "dict | None":
    return _JOURNAL.snapshot_for_checkpoint() if _JOURNAL is not None else None
