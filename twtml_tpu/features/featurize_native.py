"""One-pass host featurize (r18) — the fused native fast path of the
ragged-wire featurize stage, behind ``--featurizeNative``.

PR 6 made parse native and PR 14 made pack native, but the stage
between them still ran several separate
numpy array passes (float64 scale + f32 cast, label/mask fills, the
ragged-wire zero+copy) on BOTH ingest paths. This module routes the
array half of featurize through ONE C sweep (native/featurize.cpp): the
batch's encoded units + numeric columns go straight to the final
ragged-wire arrays — flat units (narrow uint8 under the caller's
metadata gate), padded int32 offsets, scaled float32 numeric/label/mask
— carved as views out of ONE pooled arena lease (features/arena.py),
so the stage allocates nothing fresh per tick (the TW008 law extended
to the featurize rung).

Dispatch contract: each ``try_fill`` returns the five wire arrays (+
max row length + the lease) byte-identical to the Python/numpy ground
truth in ``features/featurizer.py``, or None — mode off, stale/absent
native library (the ``native.featurize_degraded`` seam), or an input
the C pass refuses — and the featurizer falls through to the ground
truth. Differential-tested in tests/test_featurize_native.py; sanitized
by tools/native_sanity.py.

Lease lifetime: the lease rides the RaggedUnitBatch (``batch._lease``)
to the dispatch sites in apps/common.py, which chain it with the packed
wire's own lease (``arena.chain_leases``) and retire both when the
batch's stats fetch delivers — after the delivery handler has run, so
nothing can still read the arrays. Batches that never reach a dispatch
site (tests, benches, warmup) carry a GC finalizer that ``discard``s
the lease instead: accounting stays exact and the buffer is simply
never reused — indistinguishable from a fresh allocation.

``--featurizeNative <auto|on|off>`` (config.py) drives ``configure``;
auto means "whenever the native emitter is loadable" — like
``--wireAssemble``, this moves host work only and the batches are
byte-identical by law, so there is no transport-regime gate.
"""

from __future__ import annotations

import contextlib
import os
import weakref

import numpy as np

NUM_NUMBER_FEATURES = 4  # features/batch.py (MllibHelper.scala:13)

# column order the C pass reads: followers, favourites, friends,
# created_ms, label — mapped per caller so the scaling code exists once.
# The pointer ints are cached alongside: the arrays are module-lifetime
# constants and a numpy ``.ctypes`` access costs ~2-3 µs per call
_OBJECT_COL_ORDER = np.arange(5, dtype=np.int64)  # the Status traversal
_BLOCK_COL_ORDER = np.array([1, 2, 3, 4, 0], np.int64)  # blocks.COL_*
_COL_ORDER_PTRS = {
    id(_OBJECT_COL_ORDER): _OBJECT_COL_ORDER.ctypes.data,
    id(_BLOCK_COL_ORDER): _BLOCK_COL_ORDER.ctypes.data,
}

_MODES = ("auto", "on", "off")
_mode = os.environ.get("TWTML_FEATURIZE_NATIVE", "auto")
if _mode not in _MODES:
    _mode = "auto"


def configure(mode: str) -> None:
    """Set the process-wide featurize mode (the ``--featurizeNative``
    seam)."""
    global _mode
    if mode not in _MODES:
        raise ValueError(
            f"featurizeNative must be one of {_MODES}, got {mode!r}"
        )
    _mode = mode


def mode() -> str:
    return _mode


def available() -> bool:
    """Whether featurize will actually ride the fused C pass right now."""
    from . import native

    return _mode != "off" and native.featurize_available()


@contextlib.contextmanager
def forced(mode_: str):
    """Scoped mode override — the differential tests and the paired
    bench flip between the Python ground truth and the fused path."""
    prev = _mode
    configure(mode_)
    try:
        yield
    finally:
        configure(prev)


def _lease_views(b: int, n_bucket: int, unit_dtype):
    """ONE arena lease carved into the five wire arrays. Layout keeps
    every 4-byte field at a 4-byte offset (numeric, label, mask, offsets
    first; units last): numeric [b,4] f32 | label [b] f32 | mask [b] f32
    | offsets [b+1] i32 | units [n_bucket] u8|u16. Also returns the five
    section pointers, derived from the ONE lease base address (one
    ``.ctypes`` access instead of five)."""
    from .arena import lease_wire

    unit_itemsize = np.dtype(unit_dtype).itemsize
    side = b * NUM_NUMBER_FEATURES * 4 + b * 4 + b * 4 + (b + 1) * 4
    lease = lease_wire(side + n_bucket * unit_itemsize)
    buf = lease.buf
    base = buf.ctypes.data
    o_label = b * 16
    o_mask = o_label + b * 4
    o_offsets = o_mask + b * 4
    o_units = o_offsets + (b + 1) * 4
    numeric = buf[0:o_label].view(np.float32).reshape(b, 4)
    label = buf[o_label:o_mask].view(np.float32)
    mask = buf[o_mask:o_offsets].view(np.float32)
    offsets = buf[o_offsets:o_units].view(np.int32)
    units = buf[o_units:].view(unit_dtype)
    ptrs = (base + o_units, base + o_offsets, base, base + o_label,
            base + o_mask)  # units, offsets, numeric, label, mask
    return lease, units, offsets, numeric, label, mask, ptrs


def _fused_counter():
    # looked up per call, not cached: reset_for_tests clears the registry
    # in place — its contract is that hot paths hold no metric references
    from ..telemetry import metrics as _metrics

    return _metrics.get_registry().counter("featurize.fused_native")


def try_fill(
    units: np.ndarray,
    offsets: np.ndarray,
    cols: np.ndarray,
    col_order: np.ndarray,
    n: int,
    b: int,
    narrow: bool,
    now_ms: int,
):
    """The shared fused fill: (flat units, padded offsets, numeric,
    label, mask, max_len, lease) or None → the Python ground truth.
    ``cols`` is float64 [n, 5] (object path) or int64 [n, 5] (block
    columns); the C pass applies the reference scaling bit-identically
    (float64 multiply, f32 cast on store)."""
    if not available():
        return None
    from . import native
    from .batch import RAGGED_UNIT_MULTIPLE

    units = np.ascontiguousarray(units)
    offsets = np.ascontiguousarray(offsets)
    cols = np.ascontiguousarray(cols)
    if offsets.dtype != np.int64 or units.dtype not in (np.uint8, np.uint16):
        return None
    total = int(offsets[n]) if n else 0
    n_bucket = max(
        RAGGED_UNIT_MULTIPLE,
        -(-total // RAGGED_UNIT_MULTIPLE) * RAGGED_UNIT_MULTIPLE,
    )
    out_dtype = np.uint8 if narrow else np.uint16
    lease, out_units, out_offsets, numeric, label, mask, ptrs = (
        _lease_views(b, n_bucket, out_dtype)
    )
    if cols.dtype == np.float64:
        cols_f64, cols_i64 = cols.ctypes.data, None
    elif cols.dtype == np.int64:
        cols_f64, cols_i64 = None, cols.ctypes.data
    elif n:
        lease.retire()
        return None
    else:
        cols_f64 = cols_i64 = None
    max_len = native.featurize_wire_raw(
        units.ctypes.data,
        int(units.dtype.itemsize),
        offsets.ctypes.data,
        cols_f64,
        cols_i64,
        _COL_ORDER_PTRS.get(id(col_order)) or col_order.ctypes.data,
        n,
        b,
        n_bucket,
        int(now_ms),
        1 if narrow else 0,
        *ptrs,
    )
    if max_len is None:
        lease.retire()  # untouched destination: straight back to the pool
        return None
    _fused_counter().inc()
    return out_units, out_offsets, numeric, label, mask, max_len, lease


def attach_lease(batch, lease) -> None:
    """Hang the featurize lease on the batch for the dispatch sites
    (apps/common.chain_leases → retire on fetch delivery), with a GC
    ``discard`` finalizer as the never-dispatched backstop (accounting
    stays exact; a discarded buffer is never reused, so views extracted
    from the batch can never alias a recycled buffer)."""
    batch._lease = lease
    weakref.finalize(batch, lease.discard)


def object_col_order() -> np.ndarray:
    return _OBJECT_COL_ORDER


def block_col_order() -> np.ndarray:
    return _BLOCK_COL_ORDER
