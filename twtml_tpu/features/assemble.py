"""One-pass wire assembly (r17) — the fused native fast path of the three
packed-wire builders.

The numpy pack pipeline in ``features/batch.py`` stays the byte-identical
ground truth (the parity law, PARITY.md): it touches the wire bytes 3-5
times between featurize and ``device_put`` (per-field stack/contiguous
copies, the offsets→deltas pass, the digram-encode pass, the final
concatenate). On the host core that also parses and featurizes that is
pure CPU churn between featurize and upload, so this module routes every
eligible pack through ONE C sweep (native/wireassemble.cpp) that emits
the final ``PackedBatch`` buffer — units digram-encoded in place during
the copy (same LUT, same greedy encode, same all-or-nothing per-segment
fallback as ``_encode_units_segments``), offsets as uint16 deltas under
the same static ``row_len`` gate, sideband laid down behind them — into a
buffer LEASED from the pooled arena (features/arena.py).

Dispatch contract: each ``try_assemble_*`` returns a PackedBatch
byte-identical to its numpy twin, or None — wrong mode, stale/absent
native library (the ``native.assemble_degraded`` seam), an ineligible
dtype/layout, or an input the C pass refuses (delta overflow, forced
codec bucket under-coverage) — and the caller falls through to the numpy
pipeline, which raises the canonical errors. Differential-tested on every
layout × codec × fallback in tests/test_wireassemble.py; sanitized by
tools/native_sanity.py.

``--wireAssemble <auto|on|off>`` (config.py) drives ``configure``; auto
means "whenever the native assembler is loadable" — unlike the wire
codec there is no transport-regime risk to gate on: the assembler moves
host work only and the wire bytes are identical by law.
"""

from __future__ import annotations

import contextlib
import os

import numpy as np

NUM_NUMBER_FEATURES = 4  # features/batch.py (MllibHelper.scala:13)

_MODES = ("auto", "on", "off")
_mode = os.environ.get("TWTML_WIRE_ASSEMBLE", "auto")
if _mode not in _MODES:
    _mode = "auto"


def configure(mode: str) -> None:
    """Set the process-wide assembler mode (the ``--wireAssemble`` seam)."""
    global _mode
    if mode not in _MODES:
        raise ValueError(
            f"wireAssemble must be one of {_MODES}, got {mode!r}"
        )
    _mode = mode


def mode() -> str:
    return _mode


def available() -> bool:
    """Whether packs will actually ride the fused C pass right now."""
    from . import native

    return _mode != "off" and native.assemble_available()


@contextlib.contextmanager
def forced(mode_: str):
    """Scoped mode override — the differential tests and the paired bench
    flip between the numpy ground truth and the fused path with it."""
    prev = _mode
    configure(mode_)
    try:
        yield
    finally:
        configure(prev)


# int64 per-segment encode-length scratch, cached per (thread, size):
# tiny (8 bytes per segment), but the pack hot path allocates nothing per
# tick (TW008); thread-local because a prefetch worker may pack while the
# main thread packs a different stream (tools/soak.py's featurize thread)
_len_scratch = __import__("threading").local()


def _enc_lens_scratch(n: int) -> np.ndarray:
    cache = getattr(_len_scratch, "bufs", None)
    if cache is None:
        cache = _len_scratch.bufs = {}
    buf = cache.get(n)
    if buf is None:
        buf = cache[n] = np.empty((n,), np.int64)
    return buf


def _field_arrays(rb) -> "tuple | None":
    """(units, offsets, numeric, label, mask) as contiguous numpy arrays
    in the exact wire dtypes the C pass assumes, or None when any field
    is off-schema (the numpy pipeline handles exotic inputs)."""
    units = np.ascontiguousarray(np.asarray(rb.units))
    offsets = np.ascontiguousarray(np.asarray(rb.offsets))
    numeric = np.ascontiguousarray(np.asarray(rb.numeric))
    label = np.ascontiguousarray(np.asarray(rb.label))
    mask = np.ascontiguousarray(np.asarray(rb.mask))
    if units.dtype not in (np.uint8, np.uint16) or units.ndim != 1:
        return None
    if offsets.dtype != np.int32 or offsets.ndim != 1:
        return None
    b = mask.shape[0] if mask.ndim == 1 else -1
    if (
        numeric.dtype != np.float32
        or numeric.shape != (b, NUM_NUMBER_FEATURES)
        or label.dtype != np.float32
        or label.shape != (b,)
        or mask.dtype != np.float32
    ):
        return None
    return units, offsets, numeric, label, mask


def _codec_lut(codec: "str | None", units_dtype) -> "np.ndarray | None":
    """The pair LUT when the codec applies, None for the raw wire. An
    unknown codec returns the sentinel ``()`` so callers fall back to the
    numpy path, which raises the canonical error."""
    if codec is None or codec in ("", "off"):
        return None
    if codec != "dict":
        return ()  # type: ignore[return-value]
    if np.dtype(units_dtype) != np.uint8:
        return None  # non-ASCII-widened wire ships raw, like numpy
    from .wirecodec import pair_lut

    return pair_lut()


def _run(
    fields_per_batch: "list[tuple]",
    s: int,
    bl: int,
    n_sb: int,
    narrow: bool,
    lut: "np.ndarray | None",
    forced_bucket: int,
):
    """Lease destination (+ scratch), run the C pass, return
    (buffer view, enc_bucket, lease) or None."""
    from . import native
    from .arena import lease_wire

    k = len(fields_per_batch)
    unit_size = fields_per_batch[0][0].dtype.itemsize
    per_units_raw = n_sb * unit_size
    per_offs = bl * 2 if narrow else (bl + 1) * 4
    per_side = bl * NUM_NUMBER_FEATURES * 4 + bl * 4 + bl * 4
    raw_total = s * k * (per_units_raw + per_offs + per_side)
    scratch_lease = None
    scratch = enc_lens = None
    if lut is not None:
        scratch_lease = lease_wire(s * k * n_sb)
        scratch = scratch_lease.buf
        enc_lens = _enc_lens_scratch(s * k)
    lease = lease_wire(raw_total)
    try:
        got = native.wire_assemble(
            [f[0] for f in fields_per_batch],
            [f[1] for f in fields_per_batch],
            [f[2] for f in fields_per_batch],
            [f[3] for f in fields_per_batch],
            [f[4] for f in fields_per_batch],
            s, n_sb, bl, narrow, lut, forced_bucket,
            scratch, enc_lens, lease.buf,
        )
    finally:
        if scratch_lease is not None:
            # encode scratch is transient: nothing references it past the
            # call, so it goes straight back to the pool
            scratch_lease.retire()
    if got is None:
        lease.retire()
        return None
    total, enc_bucket = got
    buffer = lease.buf if total == raw_total else lease.buf[:total]
    from ..telemetry import metrics as _metrics

    _metrics.get_registry().counter("wire.assembled_native").inc()
    return buffer, enc_bucket, lease


def _attach(pb, lease):
    # the lease rides the PackedBatch to the dispatch pipelines, which
    # retire it when the corresponding fetch delivers (apps/common.py)
    pb._lease = lease
    return pb


def try_assemble_group(
    batches, s: int, bl: int, n_sb: int, narrow: bool,
    codec: "str | None", codec_bucket: "int | None",
    num_shards_out: int,
):
    """Fused twin of ``pack_ragged_group``'s body (validation already done
    by the caller). None → numpy pipeline. ``codec_bucket`` forces the
    cross-host agreed group bucket (multi-host codec groups), mirroring
    ``try_assemble_sharded``."""
    if not available():
        return None
    first = batches[0]
    lut = _codec_lut(codec, np.asarray(first.units).dtype)
    if isinstance(lut, tuple):  # unknown codec: numpy raises
        return None
    fields = []
    for rb in batches:
        fa = _field_arrays(rb)
        if fa is None:
            return None
        fields.append(fa)
    got = _run(fields, s, bl, n_sb, narrow, lut, int(codec_bucket or 0))
    if got is None:
        return None
    buffer, enc_bucket, lease = got
    k = len(batches)
    units_meta = (
        ((enc_bucket,), np.dtype(np.uint8).str)
        if enc_bucket
        else ((n_sb,), fields[0][0].dtype.str)
    )
    offs_meta = (
        ((bl,), np.dtype(np.uint16).str)
        if narrow
        else ((bl + 1,), np.dtype(np.int32).str)
    )
    f4 = np.dtype(np.float32).str
    layout = (
        "RaggedGroupSegments",
        (
            units_meta, offs_meta,
            ((bl, NUM_NUMBER_FEATURES), f4), ((bl,), f4), ((bl,), f4),
        ),
        (
            first.row_len, num_shards_out or s, k,
            "u16delta" if narrow else "i32",
        ) + (() if not enc_bucket else (("dict", n_sb),)),
    )
    from .batch import PackedBatch

    return _attach(PackedBatch(buffer, layout), lease)


def try_assemble_sharded(
    rb, s: int, bl: int, n_sb: int, narrow: bool,
    codec: "str | None", codec_bucket: "int | None",
    num_shards_out: int,
):
    """Fused twin of ``pack_ragged_sharded``'s body. None → numpy."""
    if not available():
        return None
    lut = _codec_lut(codec, np.asarray(rb.units).dtype)
    if isinstance(lut, tuple):
        return None
    fa = _field_arrays(rb)
    if fa is None:
        return None
    got = _run([fa], s, bl, n_sb, narrow, lut, int(codec_bucket or 0))
    if got is None:
        return None
    buffer, enc_bucket, lease = got
    units_meta = (
        ((enc_bucket,), np.dtype(np.uint8).str)
        if enc_bucket
        else ((n_sb,), fa[0].dtype.str)
    )
    offs_meta = (
        ((bl,), np.dtype(np.uint16).str)
        if narrow
        else ((bl + 1,), np.dtype(np.int32).str)
    )
    f4 = np.dtype(np.float32).str
    layout = (
        "RaggedShardSegments",
        (
            units_meta, offs_meta,
            ((bl, NUM_NUMBER_FEATURES), f4), ((bl,), f4), ((bl,), f4),
        ),
        (rb.row_len, num_shards_out or s, "u16delta" if narrow else "i32")
        + (() if not enc_bucket else (("dict", n_sb),)),
    )
    from .batch import PackedBatch

    return _attach(PackedBatch(buffer, layout), lease)


def try_assemble_flat(rb, narrow: bool, codec: "str | None"):
    """Fused twin of ``pack_batch``'s ragged branch — the k=1, s=1
    degenerate of the same C entry (one segment holding the whole batch,
    fields back to back = the field-major flat wire). Shard-aligned flat
    packs (num_shards > 1) keep the numpy path: their delta segments
    differ from their units segmentation, a layout only the ground truth
    carries. None → numpy."""
    if not available() or rb.num_shards != 1:
        return None
    lut = _codec_lut(codec, np.asarray(rb.units).dtype)
    if isinstance(lut, tuple):
        return None
    fa = _field_arrays(rb)
    if fa is None:
        return None
    units, offsets = fa[0], fa[1]
    b = fa[4].shape[0]
    if offsets.shape[0] != b + 1:
        return None
    n = units.shape[0]
    got = _run([fa], 1, b, n, narrow, lut, 0)
    if got is None:
        return None
    buffer, enc_bucket, lease = got
    units_meta = (
        ((enc_bucket,), np.dtype(np.uint8).str)
        if enc_bucket
        else ((n,), units.dtype.str)
    )
    offs_meta = (
        ((b,), np.dtype(np.uint16).str)
        if narrow
        else ((b + 1,), np.dtype(np.int32).str)
    )
    f4 = np.dtype(np.float32).str
    layout = (
        "RaggedUnitBatch",
        (
            units_meta, offs_meta,
            ((b, NUM_NUMBER_FEATURES), f4), ((b,), f4), ((b,), f4),
        ),
        (rb.row_len, 1, "u16delta" if narrow else "i32")
        + (() if not enc_bucket else (("dict", (n,)),)),
    )
    from .batch import PackedBatch

    return _attach(PackedBatch(buffer, layout), lease)
