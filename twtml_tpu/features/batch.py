"""Fixed-shape padded micro-batches — the XLA-facing data contract.

The reference hands MLlib a per-tweet ``LabeledPoint`` with a 1004-dim sparse
vector (MllibHelper.scala:73-82). XLA wants static shapes, so a micro-batch
here is a struct of padded arrays: hashed token indices/counts per tweet
(sparse text features), the 4 dense numeric features, labels, and a validity
mask. Batch row counts and token counts are padded up to bucket sizes so a
stream of varying batch sizes reuses a small set of compiled programs instead
of recompiling per batch (SURVEY.md §7 "hard parts" (a)).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

NUM_NUMBER_FEATURES = 4  # MllibHelper.scala:13


class FeatureBatch(NamedTuple):
    """One padded micro-batch. All arrays are host numpy until the learner
    moves them to device; as a NamedTuple it is automatically a JAX pytree.

    Shapes (B = padded rows, L = padded tokens/tweet):
      token_idx: int  [B, L] — hashed bigram indices into [0, numTextFeatures)
      token_val: num  [B, L] — term-frequency counts (0 where padded)
      numeric:   float32[B, 4] — scaled followers/favourites/friends/age feats
      label:     float32[B]    — retweet count of the retweeted status
      mask:      float32[B]    — 1.0 for real rows, 0.0 for padding

    ``token_idx``/``token_val`` travel in the narrowest lossless dtype
    (int16/uint16 when the feature space and counts fit — see
    ``compact_tokens``): fewer bytes on the host→device wire, and the
    learner steps upcast on device.
    """

    token_idx: np.ndarray
    token_val: np.ndarray
    numeric: np.ndarray
    label: np.ndarray
    mask: np.ndarray

    @property
    def num_valid(self) -> int:
        return int(self.mask.sum())


class UnitBatch(NamedTuple):
    """A padded micro-batch carrying raw UTF-16 code units instead of
    host-hashed tokens — the wire format of the on-device featurization path
    (ops/text_hash.py). The learner hashes bigrams inside the jit step, so
    host work per tweet drops to encode + pad and the transfer shrinks to
    2 bytes/unit. Learner steps accept either batch type; both produce
    bit-identical features (same Java-hashCode bigram hash).

    Shapes (B = padded rows, L = padded units/tweet, L ≥ 2):
      units:   uint8|uint16 [B, L] — lowercased text as UTF-16-LE code
               units; ships uint8 when every row is ASCII (metadata-gated,
               the common case — halves the dominant wire tensor; the
               device hash upcasts to int32 either way)
      length:  int32  [B]      — real unit count per row (0 for padding)
      numeric: float32[B, 4], label: float32[B], mask: float32[B] — as in
      FeatureBatch.
    """

    units: np.ndarray
    length: np.ndarray
    numeric: np.ndarray
    label: np.ndarray
    mask: np.ndarray

    @property
    def num_valid(self) -> int:
        return int(self.mask.sum())


def compact_tokens(
    token_idx: np.ndarray,
    token_val: np.ndarray,
    num_features: int,
    counts: bool = False,
    validate: bool = True,
) -> tuple[np.ndarray, np.ndarray]:
    """Downcast the token arrays to the narrowest lossless wire dtype.

    ``num_features`` is the text-index space: indices lie in
    [0, num_features), so they fit int16 whenever num_features ≤ 2^15 (the
    1000-dim default does; the 2^18-dim config keeps int32). Values go to
    uint16 only when the caller declares them term-frequency counts
    (``counts=True``) — a schema property, NOT sniffed from the data, so
    every batch of a stream shares one dtype (one compiled program, and
    multi-host global-batch assembly sees matching per-process dtypes). The
    learner steps upcast on device, so this only changes wire bytes.

    A misdeclared schema raises rather than silently wrapping or switching
    dtype mid-stream: indices outside [0, num_features), and ``counts=True``
    values that don't survive the uint16 round-trip (fractional, negative,
    or ≥ 2^16 — true term-frequency counts are bounded by a tweet's bigram
    count, ≪ 2^16). ``validate=False`` skips those data passes for callers
    whose arrays are in-range by construction (the native featurizer path:
    the C hasher emits ``hash % num_features`` indices and per-tweet counts
    ≤ the token bucket).
    """
    if 0 < num_features <= np.iinfo(np.int16).max + 1:
        if validate and token_idx.size and (
            token_idx.min() < 0 or token_idx.max() >= num_features
        ):
            raise ValueError(
                "token indices outside the declared feature space "
                f"[0, {num_features})"
            )
        token_idx = token_idx.astype(np.int16)
    if counts:
        compacted = token_val.astype(np.uint16)
        if validate and not np.array_equal(compacted, token_val):
            raise ValueError(
                "counts=True but token values are not uint16-exact "
                "(fractional, negative, or >= 2**16)"
            )
        token_val = compacted
    return token_idx, token_val


class PackedBatch:
    """A FeatureBatch or UnitBatch flattened into ONE contiguous uint8
    buffer for the wire, plus static layout metadata.

    Why it exists: a transport with a per-transfer cost makes five small
    arrays dearer than one buffer of the same bytes. Whether that cost
    shows end to end depends on the regime: behind overlapped transfers of
    a fat PADDED wire it can hide, on the lean RAGGED wire it does not —
    packing is the SHIPPED default there (ROADMAP S3 re-measures it on the
    chip). The learner steps accept a
    PackedBatch and unpack INSIDE the jit program with offset slices +
    ``lax.bitcast_convert_type`` — zero-copy reinterpretation, bit-identical
    arrays — so packing changes wire shape only, never semantics.

    Registered as a pytree whose only leaf is the buffer; the layout (field
    shapes/dtypes and the batch class) is static aux data, so each distinct
    layout compiles once, exactly like the unpacked batch types.
    """

    def __init__(self, buffer, layout: tuple):
        self.buffer = buffer
        self.layout = layout  # (cls_name, ((shape, dtype_str), ...))
        # arena lease backing the buffer (features/arena.py), when the
        # pack leased its destination: the dispatch pipelines retire it
        # once the corresponding fetch delivers (apps/common.py). Not
        # pytree state — a re-built PackedBatch simply carries no lease.
        self._lease = None

    def _with_lease(self, lease) -> "PackedBatch":
        self._lease = lease
        return self

    @property
    def num_valid(self) -> int:
        return int(unpack_batch(self.buffer, self.layout).mask.sum())


def _register_packed():
    import jax

    jax.tree_util.register_pytree_node(
        PackedBatch,
        lambda pb: ((pb.buffer,), pb.layout),
        lambda layout, leaves: PackedBatch(leaves[0], layout),
    )


_register_packed()


class RaggedUnitBatch:
    """A micro-batch whose text ships as CONCATENATED code units + row
    offsets — no per-row padding on the wire.

    Why: the padded ``UnitBatch`` units buffer is the dominant wire tensor
    of the streaming hot loop, and every unit beyond a row's length is pure
    waste on the wire (the padded [B, L] carries B·L units where only
    Σlengths are real). The ragged wire carries Σlengths units
    (rounded up to ``RAGGED_UNIT_MULTIPLE`` so program count stays finite)
    plus a [B+1] int32 offsets vector; the learner re-pads INSIDE the jit
    step by whole 128-lane rows of the units buffer and a shift
    (ops/ragged.py; its device cost: PERF.md §5, ``repad``) and case-folds
    ASCII on device,
    producing bit-identical features (tests/test_ragged_wire.py).

    ``row_len`` (the padded L the device re-pad rebuilds) is STATIC aux
    data, like PackedBatch's layout: each distinct (shapes, row_len)
    compiles once.

    Fields: units [N] uint8|uint16 (narrow iff every row ASCII, as in
    UnitBatch), offsets [B+1] int32, numeric/label/mask as in UnitBatch.

    ``num_shards`` > 1 marks a SHARD-ALIGNED buffer (``align_ragged_shards``):
    the units are S equal sub-buffers of N/S units (shard s's rows
    concatenated, zero-padded per sub-buffer) and the offsets are S
    segment-RELATIVE [B/S + 1] blocks ([B + S] total) — every leaf's
    leading dim is divisible by S, so the mesh data axis shards the ragged
    wire like any padded batch and each device receives exactly its rows'
    units with no cross-shard bytes. ``ops/ragged.ragged_repad`` rebuilds
    identically in every layout. Static aux, like ``row_len``.
    """

    def __init__(
        self, units, offsets, numeric, label, mask, row_len: int,
        num_shards: int = 1,
    ):
        self.units = units
        self.offsets = offsets
        self.numeric = numeric
        self.label = label
        self.mask = mask
        self.row_len = int(row_len)
        self.num_shards = int(num_shards)

    @property
    def num_valid(self) -> int:
        return int(np.asarray(self.mask).sum())


def _register_ragged():
    import jax

    jax.tree_util.register_pytree_node(
        RaggedUnitBatch,
        lambda rb: (
            (rb.units, rb.offsets, rb.numeric, rb.label, rb.mask),
            (rb.row_len, rb.num_shards),
        ),
        lambda aux, leaves: RaggedUnitBatch(
            *leaves, row_len=aux[0], num_shards=aux[1]
        ),
    )


_register_ragged()


class TwoRungWire:
    """The tenant wire of a LOPSIDED split (``stack_two_rungs``): two
    stacked members in one pytree — ``full``, the fullest tenant's part
    ``[1, r_full, ...]``, and ``rest``, the other M−1 parts
    ``[M−1, r_rest, ...]`` at the rung THEY need — plus ``ids``, ``[M]``
    int32: ``ids[0]`` the fullest tenant, ``ids[1:]`` the others in tenant
    order. A traced VALUE, not static: WHICH tenant is fullest may change
    from batch to batch without a new program; the two rungs are the
    program's shape (parallel/tenants.py runs both members in ONE jit
    program)."""

    def __init__(self, full, rest, ids):
        self.full = full
        self.rest = rest
        self.ids = ids


def _register_two_rung():
    import jax

    jax.tree_util.register_pytree_node(
        TwoRungWire,
        lambda w: ((w.full, w.rest, w.ids), None),
        lambda _aux, leaves: TwoRungWire(*leaves),
    )


_register_two_rung()


_WIRE_FIELDS = (
    "token_idx", "token_val", "units", "offsets", "length",
    "numeric", "label", "mask", "buffer",
)


def wire_nbytes(batch) -> int:
    """Bytes this batch puts on the host→device wire (the sum of its array
    fields' nbytes, whatever the batch type) — the per-batch upload
    volume, recorded by the telemetry layer
    (telemetry/trace.py spans, ``wire.bytes`` counter)."""
    if isinstance(batch, TwoRungWire):
        return (
            wire_nbytes(batch.full) + wire_nbytes(batch.rest)
            + int(batch.ids.nbytes)
        )
    total = 0
    for name in _WIRE_FIELDS:
        arr = getattr(batch, name, None)
        nbytes = getattr(arr, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
    return total


def wire_signature(wire, batch) -> dict:
    """What a ``compile`` trace span says of the call being dispatched
    (telemetry/trace.py): everything of ``batch`` (the unpacked view of
    ``wire``) that the compiled program's shape depends on — rows, row
    length, units dtype and bucket — and the wire form. Shapes only. A
    stacked tenant wire (``[M, rung, ...]`` leaves) is read itself: its
    rows and units bucket are the split's rung's, not the host batch's
    (the one-buffer tenant wire, ``--wirePack group``, still reports the
    host batch). A two-rung tenant wire reports its fullest member's and,
    as ``rest_rows`` / ``rest_units_len``, the other member's rung."""
    if isinstance(wire, TwoRungWire):
        rest = wire_signature(wire.rest, batch)
        return {
            **wire_signature(wire.full, batch),
            "rest_rows": rest["rows"], "rest_units_len": rest["units_len"],
            "wire": type(wire).__name__,
        }
    if getattr(getattr(wire, "mask", None), "ndim", 1) == 2:
        batch = wire
    units = getattr(batch, "units", None)
    width = units if units is not None else batch.token_idx
    return {
        "rows": int(batch.mask.shape[-1]),
        "row_len": int(getattr(batch, "row_len", 0) or width.shape[-1]),
        "units": str(units.dtype) if units is not None else "hashed",
        "units_len": int(units.shape[-1]) if units is not None else 0,
        "wire": (
            wire.layout[0] if isinstance(wire, PackedBatch)
            else type(wire).__name__
        ),
    }


def wire_composition(batch) -> "dict[str, int]":
    """The per-batch wire split {units, offsets, sideband} in bytes — what
    the Lean-wire-v2 offset shrink moves, surfaced as gauges in the metrics
    registry (streaming/context.py) so /api/metrics and trace reports show
    the wire composition without a bench run. ``units`` is the text
    payload (code units, or hashed token idx/val on the host-hash wire),
    ``offsets`` the row-boundary sideband (offsets/length deltas), and
    ``sideband`` the numeric/label/mask tail. A PackedBatch reports its
    layout's recorded fields (× segment count), so the packed and unpacked
    views of one batch agree byte-for-byte. A codec layout
    (``--wireCodec dict``) keeps ``units`` as the RAW units bytes (still
    agreeing with the unpacked view) and adds ``units_compressed`` — the
    bytes the transport actually carries; their quotient is the live
    ``wire.codec_ratio`` gauge (apps/common.py)."""
    if isinstance(batch, TwoRungWire):
        full, rest = wire_composition(batch.full), wire_composition(batch.rest)
        return {name: full[name] + rest[name] for name in full}
    if isinstance(batch, PackedBatch):
        tag = batch.layout[0]
        if tag in ("RaggedShardSegments", "RaggedGroupSegments"):
            segs = 1
            per_seg = sum(
                int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
                for shape, dt in batch.layout[1]
            )
            if per_seg:
                segs = int(batch.buffer.shape[0]) // per_seg
            names = ("units", "offsets", "sideband", "sideband", "sideband")
        else:
            names = {
                "FeatureBatch": (
                    "units", "units", "sideband", "sideband", "sideband"
                ),
                "UnitBatch": (
                    "units", "offsets", "sideband", "sideband", "sideband"
                ),
                "RaggedUnitBatch": (
                    "units", "offsets", "sideband", "sideband", "sideband"
                ),
            }[tag]
            segs = 1
        out = {"units": 0, "offsets": 0, "sideband": 0}
        for name, (shape, dt) in zip(names, batch.layout[1]):
            out[name] += segs * int(
                np.prod(shape, dtype=np.int64)
            ) * np.dtype(dt).itemsize
        codec_tag = _layout_codec(batch.layout)
        if codec_tag is not None:
            # compressed wire: "units" stays the raw bytes (the unpacked
            # view), "units_compressed" is what the transport carries
            out["units_compressed"] = out["units"]
            out["units"] = (
                int(np.prod(codec_tag[1], dtype=np.int64))
                if tag == "RaggedUnitBatch"
                else segs * int(codec_tag[1])
            )
        return out
    groups = {
        "units": ("units", "token_idx", "token_val"),
        "offsets": ("offsets", "length"),
        "sideband": ("numeric", "label", "mask"),
    }
    out = {}
    for name, attrs in groups.items():
        total = 0
        for attr in attrs:
            arr = getattr(batch, attr, None)
            nbytes = getattr(arr, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
        out[name] = total
    return out


def _shard_segment_need(rb: "RaggedUnitBatch", num_shards: int) -> int:
    """Raw units each shard segment must hold (the longest shard's real
    units) — the ONE shard-boundary computation align/bucket share."""
    b = rb.mask.shape[0]
    if b % num_shards:
        raise ValueError(f"batch rows {b} not divisible by {num_shards} shards")
    offs = np.asarray(rb.offsets, np.int64)
    starts = offs[0 : b + 1 : b // num_shards]
    return int((starts[1:] - starts[:-1]).max())


def ragged_shard_bucket(rb: "RaggedUnitBatch", num_shards: int) -> int:
    """The per-shard sub-buffer capacity ``align_ragged_shards`` would pick
    for this batch — exposed so multi-host assembly can allgather-max it
    across processes and pass the agreed value back as ``unit_bucket``
    (every host must compile the same program shapes)."""
    if rb.num_shards == num_shards:
        return rb.units.shape[0] // num_shards
    if rb.num_shards != 1:
        # a batch aligned to a DIFFERENT shard count would fall through to
        # _shard_segment_need, which reads the segment-relative offsets as
        # one flat [B+1] vector and returns garbage — and in multi-host
        # assembly that garbage is allgathered before align_ragged_shards
        # finally raises, surfacing as a confusing cross-host bucket
        # mismatch (r4 advisor). Mirror align's "re-align from flat" check.
        raise ValueError(
            f"batch is aligned to {rb.num_shards} shards; re-align from "
            f"flat before bucketing for {num_shards}"
        )
    need = _shard_segment_need(rb, num_shards)
    return max(
        RAGGED_UNIT_MULTIPLE,
        -(-need // RAGGED_UNIT_MULTIPLE) * RAGGED_UNIT_MULTIPLE,
    )


def align_ragged_shards(
    rb: "RaggedUnitBatch", num_shards: int, unit_bucket: int = 0
) -> "RaggedUnitBatch":
    """Re-lay a ragged batch into ``num_shards`` equal shard segments so a
    mesh data axis can shard it (see RaggedUnitBatch docstring). Host-side,
    two memcpys of the units. ``unit_bucket`` pins the per-shard sub-buffer
    capacity (multi-host runs agree it via the lockstep tick so every
    process compiles the same program); 0 sizes it from this batch's
    longest shard, rounded to RAGGED_UNIT_MULTIPLE."""
    if rb.num_shards == num_shards:
        cur = rb.units.shape[0] // num_shards
        if not unit_bucket or unit_bucket == cur:
            return rb
        if unit_bucket < cur:
            raise ValueError(
                f"batch is aligned to sub-buffers of {cur} units; cannot "
                f"shrink to the pinned bucket {unit_bucket}"
            )
        # grow each sub-buffer to the pinned bucket (a multi-host agreed
        # bucket can exceed this host's local need — e.g. every process
        # owning ONE data shard, where a flat batch is trivially aligned);
        # segment-relative offsets are untouched by tail padding
        grown = np.zeros((num_shards, unit_bucket), rb.units.dtype)
        grown[:, :cur] = np.asarray(rb.units).reshape(num_shards, cur)
        return RaggedUnitBatch(
            grown.reshape(-1), rb.offsets, rb.numeric, rb.label, rb.mask,
            row_len=rb.row_len, num_shards=num_shards,
        )
    if rb.num_shards != 1:
        raise ValueError("batch is already shard-aligned; re-align from flat")
    b = rb.mask.shape[0]
    b_local = b // num_shards
    need = _shard_segment_need(rb, num_shards)
    n_sb = ragged_shard_bucket(rb, num_shards)
    offs = np.asarray(rb.offsets, np.int64)
    starts = offs[0 : b + 1 : b_local]  # shard boundaries, [S+1]
    if unit_bucket:
        if need > unit_bucket:
            raise ValueError(
                f"shard units {need} exceed the pinned bucket {unit_bucket}"
            )
        n_sb = unit_bucket
    units = np.asarray(rb.units)
    flat = np.zeros((num_shards * n_sb,), units.dtype)
    new_offs = np.empty((b + num_shards,), np.int32)
    for s in range(num_shards):
        lo, hi = int(starts[s]), int(starts[s + 1])
        flat[s * n_sb : s * n_sb + (hi - lo)] = units[lo:hi]
        blk = offs[s * b_local : (s + 1) * b_local + 1] - lo
        new_offs[s * (b_local + 1) : (s + 1) * (b_local + 1)] = blk
    return RaggedUnitBatch(
        flat, new_offs, rb.numeric, rb.label, rb.mask,
        row_len=rb.row_len, num_shards=num_shards,
    )

# the ragged units buffer rounds its total up to this multiple: waste is
# bounded by RAGGED_UNIT_MULTIPLE units (≤8 KB uint16) per batch while the
# program count stays small (total unit counts concentrate tightly around
# B·mean_len, so real streams hit one or two buckets)
RAGGED_UNIT_MULTIPLE = 4096

# ---- narrow offset wire (Lean wire v2) ------------------------------------
# The ragged wire's [B+1] int32 offsets are pure sideband: every row length
# is bounded by the STATIC rebuilt row length L (``row_len`` — the
# featurizer's bucket policy guarantees lengths ≤ L), so whenever L fits
# uint16 the offsets can ship as per-row LENGTH DELTAS in half the bytes
# minus four per segment (b16384: 65,540 → 32,768 bytes). The device
# cumsums them back to segment-relative offsets in-program
# (ops/ragged.offsets_from_deltas) — a pure re-encoding, bit-identical
# features. The gate is static per program, exactly like the uint8/uint16
# units switch: a schema property of the layout, never sniffed per batch,
# with the int32 path as the metadata-gated fallback for row_len > 65,535.
OFFSET_DELTA_MAX = 2**16 - 1


def offsets_narrow(row_len: int) -> bool:
    """Whether this batch's offsets may ship as uint16 length deltas —
    static in ``row_len`` (see OFFSET_DELTA_MAX note)."""
    return 0 < int(row_len) <= OFFSET_DELTA_MAX


def _offsets_to_deltas(offsets, num_segments: int) -> np.ndarray:
    """Segment-relative int32 offsets [S·(B_s+1)] → uint16 per-row length
    deltas [S·B_s] (the narrow offset wire). Each segment's offsets start
    at 0 by construction (ragged_wire_arrays / align_ragged_shards), so the
    deltas are lossless; a delta that overflows uint16 means the caller's
    ``row_len`` gate was misdeclared — raise, never wrap."""
    offs = np.asarray(offsets, np.int64).reshape(num_segments, -1)
    d = offs[:, 1:] - offs[:, :-1]
    if d.size and (d.min() < 0 or d.max() > OFFSET_DELTA_MAX):
        raise ValueError(
            "offsets are not uint16-delta encodable (negative or "
            f"> {OFFSET_DELTA_MAX} length); keep the int32 offset wire"
        )
    return d.astype(np.uint16).reshape(-1)


def _deltas_to_offsets_np(deltas, num_segments: int) -> np.ndarray:
    """Host twin of ``ops/ragged.offsets_from_deltas``."""
    d = np.asarray(deltas, np.int64).reshape(num_segments, -1)
    out = np.zeros((num_segments, d.shape[1] + 1), np.int64)
    np.cumsum(d, axis=1, out=out[:, 1:])
    return out.reshape(-1).astype(np.int32)


def _decode_offsets(arr, num_segments: int):
    """Delta-wire decode for ``unpack_batch``: host numpy cumsums here; a
    traced device array cumsums in-program (ops/ragged.offsets_from_deltas)
    — either way the rebuilt offsets are bit-identical to the int32 wire."""
    if isinstance(arr, np.ndarray):
        return _deltas_to_offsets_np(arr, num_segments)
    from ..ops.ragged import offsets_from_deltas

    return offsets_from_deltas(arr, num_segments)


# ---- compressed units wire (r15, --wireCodec dict) -------------------------
# The digram codec (features/wirecodec.py: static-dictionary byte-pair
# coding, C-side encode, in-jit gather-expand decode) shrinks the dominant
# wire tensor another ~1.4-2x on ASCII tweet text. It applies ONLY to the
# PACKED wire forms (pack_batch / pack_ragged_sharded / pack_ragged_group):
# compression keeps to the one-buffer forms that are the lean-wire
# default, and every host-side
# consumer between featurize and pack (tenant routing, shard alignment,
# stacking) indexes RAW units by offset. Two gates, both loud and lossless:
# uint16 (non-ASCII-widened) units ship uncompressed — a metadata gate,
# like the int32 offset fallback — and a batch whose bucketed encoding is
# not strictly smaller than its raw buffer ships raw, recorded in the
# layout and counted by the app seam (wire.codec_fallbacks).


def _encode_units_codec(units: np.ndarray, codec: "str | None"):
    """Bucketed digram codes for an eligible raw units buffer, or None →
    the raw wire (codec off, uint16 units, or incompressible batch)."""
    if codec is None or codec in ("", "off"):
        return None
    if codec != "dict":
        raise ValueError(f"unknown wire codec {codec!r} (know: dict)")
    units = np.asarray(units)
    if units.dtype != np.uint8:
        return None  # non-ASCII-widened wire: uncompressed, like int32 offsets
    from .wirecodec import encode_bucketed

    return encode_bucketed(units.reshape(-1))


def _encode_units_segments(
    units: np.ndarray, num_segments: int, codec: "str | None",
    bucket: "int | None" = None,
):
    """Per-segment digram codes [num_segments, shared bucket] for a
    SEGMENTED raw units buffer (shard sub-buffers / group segments —
    each must decode independently under its device's slice), or None →
    raw wire. The bucket is joint (max segment, rounded) so every segment
    is the same static shape; all-or-nothing per pack.

    ``bucket`` (r16, multi-host codec) FORCES the shared bucket to a
    cross-host AGREED value (parallel/distributed.py
    ``_ragged_local_aligned_codec``): every process must emit identical
    codec segment shapes for the global wire assembly, so the local-max
    bucket (and the local incompressibility fallback) must not decide. A
    segment encoding past the agreed bucket is a codec-bound bug and
    raises — silent truncation would corrupt the wire."""
    if codec is None or codec in ("", "off"):
        return None
    if codec != "dict":
        raise ValueError(f"unknown wire codec {codec!r} (know: dict)")
    u = np.asarray(units)
    if u.dtype != np.uint8:
        return None  # non-ASCII-widened wire ships uncompressed
    from .wirecodec import encode, encoded_bucket

    rows = u.reshape(num_segments, -1)
    enc = [encode(r) for r in rows]
    if bucket is None:
        bucket = encoded_bucket(max(e.shape[0] for e in enc))
        if bucket >= rows.shape[1]:
            return None  # incompressible: the raw wire is the smaller wire
    else:
        over = max(e.shape[0] for e in enc)
        if over > bucket:
            raise ValueError(
                f"agreed codec bucket {bucket} under-covers a segment "
                f"encoding of {over} units — the cross-host zero-pad bound "
                "is violated (codec bug)"
            )
    out = np.zeros((num_segments, bucket), np.uint8)
    for i, e in enumerate(enc):
        out[i, : e.shape[0]] = e
    return out


def _decode_units(arr, out_len: int):
    """Codec-wire decode for the unpack paths: host numpy decodes via the
    wirecodec twin; a traced device array decodes in-program
    (ops/ragged.units_from_codes) — either way the rebuilt units are
    bit-identical to the uncompressed wire. ``arr`` holds per-stream codes
    along the LAST axis ([..., M] → [..., out_len]; leading axes pass
    through, so stacked/segmented wires decode in one call)."""
    if isinstance(arr, np.ndarray):
        from .wirecodec import decode_np

        return decode_np(arr, out_len)
    from ..ops.ragged import units_from_codes

    return units_from_codes(arr, out_len)


def _layout_codec(layout: tuple) -> "tuple | None":
    """The codec entry ``("dict", raw_units_per_stream)`` of a packed
    layout, or None for the raw wire. One reader for all three packed
    tags, so the position of the appended entry cannot drift."""
    extra = layout[2] if len(layout) > 2 else None
    if not extra:
        return None
    at = {
        "RaggedUnitBatch": 3, "RaggedShardSegments": 3,
        "RaggedGroupSegments": 4,
    }.get(layout[0])
    if at is None or len(extra) <= at:
        return None
    return extra[at]


def ragged_wire_arrays(
    units: np.ndarray, offsets: np.ndarray, n: int, b: int, narrow: bool
) -> tuple[np.ndarray, np.ndarray]:
    """(flat units buffer, padded [b+1] int32 offsets) for the ragged wire —
    the ONE bucket/narrowing policy shared by both featurizer builders
    (Status lists and columnar blocks), so the formats cannot drift.
    ``narrow`` ships uint8 (lossless iff every row is ASCII — the callers'
    metadata gate); pad rows get ``offsets[i] = total`` (length 0)."""
    total = int(offsets[-1]) if n else 0
    n_bucket = max(
        RAGGED_UNIT_MULTIPLE,
        -(-total // RAGGED_UNIT_MULTIPLE) * RAGGED_UNIT_MULTIPLE,
    )
    flat = np.zeros((n_bucket,), np.uint8 if narrow else np.uint16)
    flat[:total] = units[:total]
    offs = np.full((b + 1,), total, np.int32)
    offs[: n + 1] = offsets[: n + 1].astype(np.int32)
    return flat, offs


def _finish_pack(chunks, axis: int, layout: tuple) -> PackedBatch:
    """The one place the numpy packers materialize their final wire
    buffer: ``np.concatenate`` into an ARENA-LEASED destination
    (features/arena.py — fresh per-tick wire buffers are the TW008
    finding class: host CPU churn plus fuel for RSS that grows with
    uploaded bytes). The lease rides the PackedBatch to the
    dispatch pipelines, which retire it on fetch delivery."""
    from .arena import lease_wire

    lease = lease_wire(sum(c.nbytes for c in chunks))
    shape = list(chunks[0].shape)
    shape[axis] = sum(c.shape[axis] for c in chunks)
    out = lease.buf.reshape(shape)
    np.concatenate(chunks, axis=axis, out=out)
    return PackedBatch(out.reshape(-1), layout)._with_lease(lease)


def pack_ragged_sharded(
    rb: "RaggedUnitBatch", num_shards_out: int = 0,
    narrow_offsets: "bool | None" = None,
    codec: "str | None" = None,
    codec_bucket: "int | None" = None,
) -> PackedBatch:
    """A SHARD-ALIGNED ragged batch → one wire buffer laid out PER SHARD, so
    a mesh data axis can shard the single buffer (``pack_batch``'s
    field-major layout has no row sharding, so one-buffer packing was
    single-device-only before this).

    Layout: the buffer is S equal segments; segment s holds shard s's five
    fields back to back (units sub-buffer, segment-relative offsets,
    numeric, label, mask). ``P(data)`` on the buffer then gives each device
    exactly its own rows' bytes, and the shard_map body rebuilds its local
    RaggedUnitBatch with the same zero-copy bitcasts as ``unpack_batch``.
    The static layout records PER-SHARD field shapes under the
    ``RaggedShardSegments`` tag plus (row_len, total shards).

    ``num_shards_out`` overrides the recorded shard count — multi-host
    callers pack their LOCAL shards and assemble the global buffer from
    every process, so the layout must carry the GLOBAL count. ``s = 1`` is
    legal (a 1-device mesh, or the one-data-shard-per-process topology):
    the "per-shard" layout is then simply the whole local batch as one
    segment.

    ``narrow_offsets`` (default: auto from the static ``row_len`` gate,
    ``offsets_narrow``) ships the per-shard offsets as uint16 LENGTH DELTAS
    instead of [B_s+1] int32 — the Lean-wire-v2 sideband shrink; the unpack
    cumsums them back in-program, bit-identically.

    ``codec="dict"`` (r15, ``--wireCodec``) digram-compresses each shard's
    units sub-buffer into a shared static bucket; the unpack gather-expands
    them back in-program ahead of the re-pad — byte-identical units
    (tests/test_wirecodec.py). Ineligible/incompressible batches keep the
    raw layout (see ``_encode_units_segments``)."""
    s = rb.num_shards
    b = rb.mask.shape[0]
    bl = b // s
    n_sb = rb.units.shape[0] // s
    narrow = (
        offsets_narrow(rb.row_len) if narrow_offsets is None
        else narrow_offsets
    )
    # fused native fast path (r17): one C sweep emits the identical final
    # buffer into an arena lease; None falls through to the ground truth
    from .assemble import try_assemble_sharded

    fast = try_assemble_sharded(
        rb, s, bl, n_sb, narrow, codec, codec_bucket, num_shards_out
    )
    if fast is not None:
        return fast
    offs_wire = (
        (_offsets_to_deltas(rb.offsets, s), (bl,))
        if narrow
        else (rb.offsets, (bl + 1,))
    )
    codes = _encode_units_segments(rb.units, s, codec, bucket=codec_bucket)
    units_wire = (
        (rb.units, (n_sb,)) if codes is None else (codes, (codes.shape[1],))
    )
    fields = tuple(
        np.ascontiguousarray(np.asarray(a).reshape((s,) + shape))
        for a, shape in (
            units_wire,
            offs_wire,
            (rb.numeric, (bl, NUM_NUMBER_FEATURES)),
            (rb.label, (bl,)),
            (rb.mask, (bl,)),
        )
    )
    layout = (
        "RaggedShardSegments",
        tuple((f.shape[1:], f.dtype.str) for f in fields),
        (rb.row_len, num_shards_out or s, "u16delta" if narrow else "i32")
        + (() if codes is None else (("dict", n_sb),)),
    )
    return _finish_pack(
        [f.view(np.uint8).reshape(s, -1) for f in fields], 1, layout
    )


def _unpack_ragged_shards(buffer, layout: tuple) -> "RaggedUnitBatch":
    """Rebuild from a ``RaggedShardSegments`` buffer. Host numpy gets the
    full S-segment buffer back as the shard-aligned batch; inside a
    shard_map body the local slice holds ONE segment and rebuilds the
    shard-local batch (num_shards=1 — the body is per-shard by
    construction). A ``u16delta`` layout (narrow offset wire) cumsums the
    per-row length deltas back to segment-relative offsets here —
    in-program on device, numpy on host — before the batch is rebuilt; a
    codec layout (``--wireCodec dict``) likewise gather-expands each
    shard's digram codes back to its raw units sub-buffer first."""
    fields_meta = layout[1]
    row_len, s_total = layout[2][0], layout[2][1]
    offs_mode = layout[2][2] if len(layout[2]) > 2 else "i32"
    codec_tag = _layout_codec(layout)
    per_shard = sum(
        int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
        for shape, dt in fields_meta
    )
    s_here = buffer.shape[0] // per_shard
    if buffer.shape[0] != s_here * per_shard:
        raise ValueError(
            f"buffer of {buffer.shape[0]} bytes is not a whole number of "
            f"{per_shard}-byte shard segments"
        )
    fields = []
    off = 0
    for shape, dtype_str in fields_meta:
        dt = np.dtype(dtype_str)
        count = int(np.prod(shape, dtype=np.int64))
        nbytes = count * dt.itemsize
        if isinstance(buffer, np.ndarray):
            chunk = np.ascontiguousarray(
                buffer.reshape(s_here, per_shard)[:, off : off + nbytes]
            )
            arr = chunk.view(dt).reshape((s_here,) + shape)
        else:
            from jax import lax

            if s_here != 1:
                raise ValueError(
                    "device-side unpack sees exactly one shard segment "
                    "(the shard_map-local slice)"
                )
            chunk = buffer[off : off + nbytes]
            if dt.itemsize > 1:
                chunk = chunk.reshape(count, dt.itemsize)
            arr = lax.bitcast_convert_type(chunk, dt).reshape((1,) + shape)
        off += nbytes
        # flatten the segment axis back into the leading dim
        fields.append(arr.reshape((arr.shape[0] * shape[0],) + shape[1:]))
    if codec_tag is not None:
        n_sb_raw = int(codec_tag[1])
        fields[0] = _decode_units(
            fields[0].reshape(s_here, -1), n_sb_raw
        ).reshape(s_here * n_sb_raw)
    if offs_mode == "u16delta":
        fields[1] = _decode_offsets(fields[1], s_here)
    return RaggedUnitBatch(
        *fields, row_len=row_len, num_shards=s_here if s_here > 1 else 1
    )


def pack_ragged_group(
    batches, num_shards_out: int = 0,
    narrow_offsets: "bool | None" = None,
    codec: "str | None" = None,
    codec_bucket: "int | None" = None,
) -> PackedBatch:
    """K same-signature ragged batches → ONE contiguous uint8 wire buffer
    (the coalesced tenant wire, ``--wirePack group``: K = M tenants,
    parallel/tenants.py).

    Why: the stacked tenant wire (``stack_batches``) ships K separate sets
    of per-field arrays — K×5 small puts where one large coalesced put
    pays the per-transfer cost once. The K batches' five fields
    flatten into one buffer with a STATIC per-group layout, uploaded by
    ONE main-thread ``device_put`` (rides the step dispatch), and the
    in-jit unpack (``_unpack_ragged_group``) slices the K segments back
    into the stacked [K, ...] leaves the tenant program maps over —
    bit-identical to ``stack_batches`` of the same batches, leaf for leaf
    (the wire law, tests/test_superwire.py).

    Layout: the buffer is laid out SHARD-MAJOR, [S, K, per-segment bytes]
    flattened — ``P(data)`` on the one buffer then hands each device its
    own K segments (the shard-aligned variant of the one-buffer wire,
    parallel/sharding.py), with S = 1 collapsing to the single-device
    [K, per-batch] layout. Offsets ride the narrow uint16-delta wire under
    the same static ``row_len`` gate as ``pack_ragged_sharded``.

    All batches must share one wire signature (shapes, dtypes, row_len,
    shard alignment) — the tenant split emits M same-signature batches,
    so each distinct (signature, K) compiles exactly one program.
    ``num_shards_out`` mirrors ``pack_ragged_sharded`` (multi-host callers
    pack local shards, the layout carries the global count); ``codec``
    mirrors it too (per-segment digram compression, shared bucket,
    all-or-nothing raw fallback — see ``_encode_units_segments``), as does
    ``codec_bucket`` (the cross-host AGREED group bucket: every process
    must emit identical codec segment shapes for the global wire)."""
    if not batches:
        raise ValueError("cannot pack an empty group")
    first = batches[0]
    if not isinstance(first, RaggedUnitBatch):
        raise TypeError("pack_ragged_group is the ragged wire's group pack")
    k = len(batches)
    for rb in batches[1:]:
        if (
            not isinstance(rb, RaggedUnitBatch)
            or (rb.row_len, rb.num_shards) != (first.row_len, first.num_shards)
            or rb.units.shape != first.units.shape
            or rb.units.dtype != first.units.dtype
            or rb.mask.shape != first.mask.shape
        ):
            raise ValueError(
                "group batches must share one wire signature (shapes, "
                "dtypes, row_len, shard alignment)"
            )
    s = first.num_shards
    b = first.mask.shape[0]
    bl = b // s
    n_sb = first.units.shape[0] // s
    narrow = (
        offsets_narrow(first.row_len) if narrow_offsets is None
        else narrow_offsets
    )
    # fused native fast path (r17): one C sweep over the K batches emits
    # the identical shard-major buffer; None falls through to the truth
    from .assemble import try_assemble_group

    fast = try_assemble_group(
        batches, s, bl, n_sb, narrow, codec, codec_bucket, num_shards_out
    )
    if fast is not None:
        return fast
    specs = (
        ((lambda rb: rb.units), (n_sb,)),
        (
            (lambda rb: _offsets_to_deltas(rb.offsets, s))
            if narrow else (lambda rb: rb.offsets),
            (bl,) if narrow else (bl + 1,),
        ),
        ((lambda rb: rb.numeric), (bl, NUM_NUMBER_FEATURES)),
        ((lambda rb: rb.label), (bl,)),
        ((lambda rb: rb.mask), (bl,)),
    )
    # [S, K, ...] per field: shard-major so P(data) on the flattened buffer
    # hands each device exactly its own K segments
    fields = list(
        np.ascontiguousarray(np.stack(
            [np.asarray(get(rb)).reshape((s,) + shape) for rb in batches],
            axis=1,
        ))
        for get, shape in specs
    )
    # compressed units wire (``--wireCodec dict``): every (shard, k)
    # segment's sub-buffer encodes independently into one shared bucket —
    # each device slice / scan step decodes exactly its own segments
    codes = _encode_units_segments(fields[0], s * k, codec, bucket=codec_bucket)
    if codes is not None:
        fields[0] = np.ascontiguousarray(
            codes.reshape(s, k, codes.shape[1])
        )
    layout = (
        "RaggedGroupSegments",
        tuple((f.shape[2:], f.dtype.str) for f in fields),
        (
            first.row_len, num_shards_out or s, k,
            "u16delta" if narrow else "i32",
        ) + (() if codes is None else (("dict", n_sb),)),
    )
    return _finish_pack(
        [f.view(np.uint8).reshape(s, k, -1) for f in fields], 2, layout
    )


def _decode_offsets_stacked(arr, s_here: int):
    """Stacked [K, S·B_s] delta wire → [K, S·(B_s+1)] int32 offsets."""
    if isinstance(arr, np.ndarray):
        k = arr.shape[0]
        return _deltas_to_offsets_np(
            arr.reshape(k * s_here, -1), k * s_here
        ).reshape(k, -1)
    from ..ops.ragged import offsets_from_deltas

    return offsets_from_deltas(arr, s_here)


def _unpack_ragged_group(buffer, layout: tuple) -> "RaggedUnitBatch":
    """Rebuild the STACKED ragged batch ([K, ...] leaves — what
    ``stack_batches`` would have produced) from a ``RaggedGroupSegments``
    buffer. Host numpy gets the full group back shard-aligned; inside a
    jit program (single device, or a shard_map body's local slice) the
    buffer holds ONE shard's K segments and the zero-copy bitcasts rebuild
    the shard-local stacked batch the scanned step consumes."""
    fields_meta = layout[1]
    row_len, _s_total, k, offs_mode = layout[2][:4]
    codec_tag = _layout_codec(layout)
    per_seg = sum(
        int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
        for shape, dt in fields_meta
    )
    s_here = buffer.shape[0] // (k * per_seg)
    if buffer.shape[0] != s_here * k * per_seg:
        raise ValueError(
            f"buffer of {buffer.shape[0]} bytes is not a whole number of "
            f"{k}x{per_seg}-byte group segments"
        )
    fields = []
    off = 0
    for shape, dtype_str in fields_meta:
        dt = np.dtype(dtype_str)
        count = int(np.prod(shape, dtype=np.int64))
        nbytes = count * dt.itemsize
        if isinstance(buffer, np.ndarray):
            chunk = np.ascontiguousarray(
                buffer.reshape(s_here, k, per_seg)[:, :, off : off + nbytes]
            )
            arr = chunk.view(dt).reshape((s_here, k) + shape)
            # [S, K, d0, ...] → [K, S·d0, ...]: K leads (the scan axis),
            # the segment axis folds back into each leaf's leading dim
            arr = np.ascontiguousarray(
                arr.transpose((1, 0) + tuple(range(2, arr.ndim)))
            ).reshape((k, s_here * shape[0]) + shape[1:])
        else:
            from jax import lax

            if s_here != 1:
                raise ValueError(
                    "device-side group unpack sees exactly one shard "
                    "segment (the shard_map-local slice)"
                )
            chunk = buffer.reshape(k, per_seg)[:, off : off + nbytes]
            if dt.itemsize > 1:
                chunk = chunk.reshape(k, count, dt.itemsize)
            arr = lax.bitcast_convert_type(chunk, dt).reshape((k,) + shape)
        off += nbytes
        fields.append(arr)
    if codec_tag is not None:
        n_sb_raw = int(codec_tag[1])
        fields[0] = _decode_units(
            fields[0].reshape(k, s_here, -1), n_sb_raw
        ).reshape(k, s_here * n_sb_raw)
    if offs_mode == "u16delta":
        fields[1] = _decode_offsets_stacked(fields[1], s_here)
    return RaggedUnitBatch(
        *fields, row_len=row_len, num_shards=s_here if s_here > 1 else 1
    )


def pack_batch(
    batch: "FeatureBatch | UnitBatch | RaggedUnitBatch",
    narrow_offsets: "bool | None" = None,
    codec: "str | None" = None,
) -> PackedBatch:
    """Flatten a host batch into one uint8 wire buffer (cheap memcpy).
    RaggedUnitBatch packs its five arrays too, with ``row_len`` carried in
    the static layout (third element) — and its offsets ship as uint16
    length deltas whenever the static ``row_len`` gate allows
    (``offsets_narrow``; the in-jit unpack cumsums them back,
    bit-identically — the Lean-wire-v2 sideband shrink). ``codec="dict"``
    additionally digram-compresses the ragged units buffer (one stream —
    this flat layout is never device-sliced), decoded in-jit by the
    unpack; ineligible/incompressible batches keep the raw layout."""
    if isinstance(batch, RaggedUnitBatch):
        narrow = (
            offsets_narrow(batch.row_len) if narrow_offsets is None
            else narrow_offsets
        )
        # fused native fast path (r17): the k=1, s=1 degenerate of the
        # same C entry; None falls through to the ground truth
        from .assemble import try_assemble_flat

        fast = try_assemble_flat(batch, narrow, codec)
        if fast is not None:
            return fast
        offs = (
            _offsets_to_deltas(batch.offsets, batch.num_shards)
            if narrow
            else batch.offsets
        )
        units = np.asarray(batch.units)
        codes = _encode_units_codec(units, codec)
        arrays: tuple = (
            units if codes is None else codes, offs, batch.numeric,
            batch.label, batch.mask,
        )
        extra: "tuple | None" = (
            batch.row_len, batch.num_shards,
            "u16delta" if narrow else "i32",
        ) + (() if codes is None else (("dict", tuple(units.shape)),))
    else:
        arrays = tuple(batch)
        extra = None
    fields = tuple(np.ascontiguousarray(a) for a in arrays)
    layout = (
        type(batch).__name__,
        tuple((a.shape, a.dtype.str) for a in fields),
    ) + ((extra,) if extra is not None else ())
    return _finish_pack(
        [a.view(np.uint8).reshape(-1) for a in fields], 0, layout
    )


def unpack_batch(buffer, layout: tuple):
    """Rebuild the batch from the wire buffer — works on device inside jit
    (bitcast + reshape; no data movement) and on host numpy alike."""
    if layout[0] == "RaggedShardSegments":
        return _unpack_ragged_shards(buffer, layout)
    if layout[0] == "RaggedGroupSegments":
        return _unpack_ragged_group(buffer, layout)
    cls = {
        "FeatureBatch": FeatureBatch,
        "UnitBatch": UnitBatch,
        "RaggedUnitBatch": RaggedUnitBatch,
    }[layout[0]]
    fields = []
    off = 0
    for shape, dtype_str in layout[1]:
        dt = np.dtype(dtype_str)
        count = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = count * dt.itemsize
        chunk = buffer[off : off + nbytes]
        off += nbytes
        if isinstance(chunk, np.ndarray):
            arr = chunk.view(dt).reshape(shape)
        else:
            from jax import lax

            if dt.itemsize > 1:
                chunk = chunk.reshape(count, dt.itemsize)
            arr = lax.bitcast_convert_type(chunk, dt).reshape(shape)
        fields.append(arr)
    if cls is RaggedUnitBatch:
        extra = layout[2]
        num_shards = extra[1] if len(extra) > 1 else 1
        codec_tag = _layout_codec(layout)
        if codec_tag is not None:
            raw_shape = tuple(codec_tag[1])
            n_raw = int(np.prod(raw_shape, dtype=np.int64))
            fields[0] = _decode_units(
                fields[0].reshape(-1), n_raw
            ).reshape(raw_shape)
        if len(extra) > 2 and extra[2] == "u16delta":
            fields[1] = _decode_offsets(fields[1], num_shards)
        return RaggedUnitBatch(
            *fields,
            row_len=extra[0],
            num_shards=num_shards,
        )
    return cls(*fields)


# ---- multi-tenant routing (ISSUE 7) ---------------------------------------
# The tenant plane splits one featurized batch's VALID rows into M per-tenant
# batches of ONE shared padded shape (one wire signature — the lockstep
# invariant extended to tenants: dry tenants ship all-padding batches so the
# collective/jit program is identical every tick), then ships them as the
# stacked / coalesced tenant wire (stack_batches / pack_ragged_group). That
# shape is a ROW RUNG read off the batch in hand (tenant_row_rungs): the rows
# the fullest tenant got, not the whole batch's.
# Routing is a pure deterministic function of the batch, so the delivery-side
# split (per-tenant stats, prediction re-ordering) recomputes it instead of
# carrying a permutation through the fetch pipeline.

def _splitmix(x: np.ndarray) -> np.ndarray:
    """SplitMix64 finalizer over uint64 lanes — a routing mixer, not a
    cryptographic hash (uniform-ish A/B-arm splits from weak row sums)."""
    x = x ^ (x >> np.uint64(33))
    x = x * np.uint64(0xFF51AFD7ED558CCD)
    return x ^ (x >> np.uint64(33))


def _ragged_row_reduce(ufunc, units: np.ndarray, offsets: np.ndarray, dtype):
    """(per-row ``ufunc`` reduction in ``dtype``, per-row lengths) of a FLAT
    ragged buffer — one segmented reduction over the units as they are (no
    widened copy): the non-empty rows' starts ascend strictly, so
    ``reduceat`` reduces each up to the next one's start, and the last up to
    the units' end. An empty row reads 0."""
    offs = np.asarray(offsets, np.int64)
    lengths = offs[1:] - offs[:-1]
    out = np.zeros(lengths.shape, dtype)
    rows = np.nonzero(lengths)[0]
    if rows.size:
        out[rows] = ufunc.reduceat(
            np.asarray(units)[: offs[-1]], offs[rows], dtype=dtype
        )
    return out, lengths


def _script_class_ids(maxs: np.ndarray, num_tenants: int) -> np.ndarray:
    """``--tenantKey lang``: the script class of each row's LARGEST code
    unit (0 for a row under 128, else 1 + the unit's high byte), mod M."""
    maxs = maxs.astype(np.int32)
    cls = np.where(maxs < 128, 0, 1 + (maxs >> 8))
    return (cls % num_tenants).astype(np.int32)


def tenant_route_keys(
    batch, num_tenants: int, mode: str = "hash"
) -> np.ndarray:
    """Per-row tenant id [B] for a host batch — the cheap host-side routing
    key of the multi-tenant plane (``--tenantKey``).

    ``hash``: SplitMix64 over (unit-sum, length) per row — a uniform
    A/B-arm style split, content-deterministic on every wire (FeatureBatch
    rows key off their hashed-token sums instead of raw units).
    ``lang``: a script-class heuristic from the row's max code unit (0 for
    pure-ASCII rows, else keyed by the max unit's high byte) — the
    per-language/per-script scenario axis; requires a raw-units wire
    (device hashing), because host-hashed tokens carry no script signal.
    The classes fold mod M, so one script can spread over several tenants
    (a CJK row's follows its largest unit's high byte, an emoji row's the
    low surrogate's: PERF.md §7) and one tenant holds several classes.
    Both keys read the units the wire carries, on their own dtype.
    (``--tenantKey all`` is not a routing key: every tenant takes every
    row, nothing is split, and ``parallel/tenants.py`` never asks here.)

    Padding rows get tenant 0 (they are masked out of every tenant batch
    anyway). Keys are heuristic ROUTING, not semantics: each tenant's model
    math on its routed rows stays byte-identical to the reference
    single-model path (PARITY.md)."""
    if mode not in ("hash", "lang"):
        raise ValueError(f"tenant key mode must be 'hash' or 'lang', got {mode!r}")
    lang = mode == "lang"
    if isinstance(batch, RaggedUnitBatch):
        if batch.num_shards != 1:
            raise ValueError(
                "route before shard alignment (tenant batches are "
                "shard-aligned per tenant afterwards)"
            )
        units = np.asarray(batch.units)
        if lang:
            maxs, _ = _ragged_row_reduce(
                np.maximum, units, batch.offsets, units.dtype
            )
            return _script_class_ids(maxs, num_tenants)
        sums, lengths = _ragged_row_reduce(
            np.add, units, batch.offsets, np.uint64
        )
    elif isinstance(batch, UnitBatch):
        units = np.asarray(batch.units)
        if lang:
            maxs = (
                units.max(axis=1) if units.shape[1]
                else np.zeros(units.shape[:1], units.dtype)
            )
            return _script_class_ids(maxs, num_tenants)
        sums = units.sum(axis=1, dtype=np.uint64)
        lengths = np.asarray(batch.length, np.uint64)
    elif isinstance(batch, FeatureBatch):
        if lang:
            raise ValueError(
                "--tenantKey lang needs a raw-units wire (--hashOn device); "
                "host-hashed tokens carry no script signal"
            )
        sums = np.asarray(batch.token_idx, np.int64).astype(np.uint64).sum(axis=1)
        lengths = (np.asarray(batch.token_val) != 0).sum(axis=1).astype(np.uint64)
    else:
        raise TypeError(f"cannot route a {type(batch).__name__}")
    x = (
        sums.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        + lengths.astype(np.uint64) * np.uint64(0xBF58476D1CE4E5B9)
    )
    return (_splitmix(x) % np.uint64(num_tenants)).astype(np.int32)


def tenant_rows(batch, tenant_ids: np.ndarray, num_tenants: int):
    """Per-tenant original-row indices [list of M int arrays], valid rows
    only, ascending (original relative order preserved within each tenant —
    the parity law's ordering holds on each tenant's sub-stream)."""
    valid = np.asarray(batch.mask) > 0
    ids = np.where(valid, np.asarray(tenant_ids), -1)
    return [np.nonzero(ids == m)[0] for m in range(num_tenants)]


def gather_tenant_predictions(
    tenant_preds, batch, tenant_ids: np.ndarray, num_tenants: int
) -> np.ndarray:
    """The fetched ``[M, R, ...]`` per-tenant predictions (R the split's row
    rung, each tenant's rows packed to the front) → one array in the
    ORIGINAL batch's row count and row order; padding rows read 0."""
    tenant_preds = np.asarray(tenant_preds)
    preds = np.zeros(
        (batch.mask.shape[0],) + tenant_preds.shape[2:], tenant_preds.dtype
    )
    for m, rows in enumerate(tenant_rows(batch, tenant_ids, num_tenants)):
        preds[rows] = tenant_preds[m][: rows.shape[0]]
    return preds


# every rung below the top one is a multiple of this many rows (lanes, MXU
# tiles); the top rung is the batch's own row count, whatever it is
TENANT_RUNG_MULTIPLE = 128


def tenant_row_rungs(
    rows: int, num_tenants: int, row_multiple: int = 1
) -> "tuple[int, ...]":
    """The row counts a tenant's part of a ``rows``-row batch may be padded
    to — a short fixed ladder, a function of ``rows`` and ``num_tenants``
    (and the mesh's data axis, ``row_multiple``) ONLY, so the programs a
    stream can compile are known before it starts. First rung: an even
    split's share with a quarter of slack, ``1.25·rows/num_tenants`` rounded
    up to ``TENANT_RUNG_MULTIPLE`` (640 at 2,048 rows and 4 tenants, 384 at
    8: +6.5 sd over a uniform key's binomial share); then doubling; the top
    rung is ``rows`` itself — the whole batch's shape, which every lopsided
    split (``--tenantKey lang``, a dry-tenant stream, one tenant) still
    reaches. A batch too small for a rung under it has the top rung alone."""
    step = math.lcm(TENANT_RUNG_MULTIPLE, max(int(row_multiple), 1))
    rung = -(-5 * rows // (4 * num_tenants))
    rung += (-rung) % step
    rungs = []
    while rung < rows:
        rungs.append(rung)
        rung *= 2
    return (*rungs, rows)


def _rung_units(parent_units: int, rung: int, rows: int) -> int:
    """Units buffer of a ragged part padded to ``rung`` of the batch's
    ``rows``: the parent's units bucket scaled by rung/rows, rounded up to
    ``RAGGED_UNIT_MULTIPLE`` — a function of the parent's bucket and the
    rung, never of one tenant's own text, so a rung adds no program per
    tenant. The top rung keeps the parent's buffer as it is."""
    if rung >= rows:
        return parent_units
    scaled = -(-parent_units * rung // rows)
    scaled += (-scaled) % RAGGED_UNIT_MULTIPLE
    return min(parent_units, scaled)


def _two_rung_units(parent_units: int) -> int:
    """The units bucket a TWO-rung split sizes its members' buffers from:
    the parent's, rounded up to a sixteenth of the next power of two (32,768
    units at 2,048 rows of 20–280: 303,104 … 327,680 all give 327,680), at
    most an eighth more units on the wire. Why coarser than
    ``RAGGED_UNIT_MULTIPLE``: the two-rung program holds TWO whole steps, so
    each one a stream meets costs twice the one-rung program's trace and
    lowering and two to five times its load from the compile cache (0.4–0.6
    + 0.4–0.6 + 0.4–2.0 s against 0.2–0.3 + 0.3 + 0.1–0.8 on the v5e's
    host), and with the parent's own buckets — four in the 280-unit mix —
    the lopsided cell's set-up read 20.1–20.6 s against 14.3 (my chip runs,
    PR 49; PERF.md §6). With this one a stream has one program a pair of
    rungs, or two where its batches straddle a multiple."""
    step = max(RAGGED_UNIT_MULTIPLE, _bucket(parent_units) // 16)
    return -(-parent_units // step) * step


def split_batch_tenants(
    batch, tenant_ids: np.ndarray, num_tenants: int,
    row_multiple: int = 1, rung: int = 0, one_rung: bool = False,
):
    """One featurized batch → M per-tenant batches, valid rows routed by
    ``tenant_ids`` and packed to the front in original relative order; dry
    tenants come back all-padding.

    The shapes are TWO tenant ROW RUNGS, read off the batch in hand. Each
    tenant NEEDS the smallest rung of ``tenant_row_rungs`` that holds its
    rows — and, on the ragged wire, its units in ``_rung_units``' buffer
    (rows that fit a rung whose units do not take the next). The FULLEST
    tenant (the one that needs the highest rung; the first of them) has its
    part padded to the rung it needs; the other M−1 parts are padded to the
    rung the fullest of THEM needs. Where the two rungs are equal — every
    batch of an even key, two tenants that both need the top rung, M = 1 —
    the M parts share one wire signature and ``stack_batches`` /
    ``pack_ragged_group`` turn them into the one ``[M, rung, ...]`` tenant
    wire; where they differ (a lopsided key: one tenant of ``--tenantKey
    lang`` with over 62.5% of a batch) M−1 parts share the lower rung's
    signature and ``stack_two_rungs`` makes the two-member wire. What the
    device then works on is Σ rungs rows, not M·B and not M times the
    fullest's rung: under a uniform key every batch takes the first rung
    for all (four parts of 640 rows for a batch of 2,048; PERF.md §6, PR
    36), under the lopsided one 2,048 + 3·640 (PR 49). Token width /
    ``row_len`` / units dtype are the parent's. At the top rung a part of a
    ONE-rung split has the parent's own shape, and a tenant that got every
    row gets the parent back byte for byte; the members of a TWO-rung split
    size their units buffers from ``_two_rung_units``' coarser bucket of the
    parent's (one program a pair of rungs, not one per units bucket).

    ONE rung for all parts, the fullest's — as until PR 49 — under
    ``one_rung`` (``TenantStackModel.split`` asks for it where its wire or
    program has one shape for all tenants: ``--wirePack group``, a mesh)
    and under ``rung``, which pins the shape itself (multi-host callers,
    whose hosts must agree on it, pass the batch's row count)."""
    rows_per = tenant_rows(batch, tenant_ids, num_tenants)
    b = batch.mask.shape[0]
    ragged = isinstance(batch, RaggedUnitBatch)
    n_parent = 0  # the padded wires have no units buffer to fit
    totals = [0] * num_tenants
    if ragged:
        units = np.asarray(batch.units)
        offs = np.asarray(batch.offsets, np.int64)
        lengths = offs[1:] - offs[:-1]
        lens_per = [lengths[rows] for rows in rows_per]
        totals = [int(lens_m.sum()) for lens_m in lens_per]
        n_parent = units.shape[0]
    ladder = (int(rung),) if rung else tenant_row_rungs(
        b, num_tenants, row_multiple
    )
    needs = [
        next(
            (r for r in ladder
             if r >= rows.shape[0] and _rung_units(n_parent, r, b) >= total),
            0,
        )
        for rows, total in zip(rows_per, totals)
    ]
    if not all(needs):
        raise ValueError(
            f"tenant rung {ladder[-1]} cannot hold a part of "
            f"{max(rows.shape[0] for rows in rows_per)} rows and "
            f"{max(totals)} units"
        )
    fullest = int(np.argmax(needs))
    rest = max(needs[:fullest] + needs[fullest + 1:], default=needs[fullest])
    rungs = [
        needs[fullest] if one_rung or m == fullest else rest
        for m in range(num_tenants)
    ]

    def padded(arr, rows, r):
        arr = np.asarray(arr)
        dest = np.zeros((r,) + arr.shape[1:], arr.dtype)
        dest[: rows.shape[0]] = arr[rows]
        return dest

    if not ragged:
        return [
            type(batch)(*(padded(arr, rows, r) for arr in batch))
            for rows, r in zip(rows_per, rungs)
        ]
    if len(set(rungs)) > 1:
        n_parent = _two_rung_units(n_parent)
    # every unit's tenant (-1: a row the mask leaves out): a tenant's units
    # are then one ordered selection of the parent's, its rows' relative
    # order kept
    unit_ids = np.repeat(
        np.where(np.asarray(batch.mask) > 0, tenant_ids, -1).astype(np.int16),
        lengths,
    )
    live = units[offs[0] : offs[-1]]
    out = []
    for m, (rows, lens_m, total, r) in enumerate(
        zip(rows_per, lens_per, totals, rungs)
    ):
        units_m = np.zeros((_rung_units(n_parent, r, b),), units.dtype)
        units_m[:total] = live[unit_ids == m]
        offs_m = np.full((r + 1,), total, np.int32)
        offs_m[0] = 0
        np.cumsum(lens_m, out=offs_m[1 : rows.shape[0] + 1])
        out.append(RaggedUnitBatch(
            units_m, offs_m, padded(batch.numeric, rows, r),
            padded(batch.label, rows, r), padded(batch.mask, rows, r),
            row_len=batch.row_len, num_shards=1,
        ))
    return out


def stack_batches(batches):
    """K same-shape batches → one batch whose arrays carry a leading [K]
    axis — the stacked tenant wire (``--wirePack stacked``: K = M tenants,
    one dispatch maps the step over the axis, parallel/tenants.py). All
    batches must share type, shapes, and dtypes (an even tenant split pads
    every part to one row rung; ragged parts additionally share that rung's
    units buffer), so the wire is ``[K, rung, ...]``. A lopsided split's
    parts have TWO rungs and go through ``stack_two_rungs``, which stacks
    each rung's parts with this."""
    first = batches[0]
    for b in batches[1:]:
        if type(b) is not type(first):
            raise TypeError("cannot stack mixed batch types")
    if isinstance(first, RaggedUnitBatch):
        for b in batches[1:]:
            if (b.row_len, b.num_shards) != (first.row_len, first.num_shards):
                raise ValueError(
                    "cannot stack ragged batches with different row_len or "
                    "shard alignment"
                )
        return RaggedUnitBatch(
            *(
                np.stack([getattr(b, f) for b in batches])
                for f in ("units", "offsets", "numeric", "label", "mask")
            ),
            row_len=first.row_len,
            num_shards=first.num_shards,
        )
    return type(first)(*(np.stack(arrs) for arrs in zip(*batches)))


def stack_two_rungs(parts):
    """The M parts of a lopsided tenant split (``split_batch_tenants``: the
    fullest tenant's at its rung, the others at theirs) → the
    ``TwoRungWire``: ``[1, r_full, ...]`` and ``[M−1, r_rest, ...]`` stacked
    members plus the tenant ids of both, so what is uploaded and what the
    device works on is r_full + (M−1)·r_rest rows where one rung for all
    was M·r_full (2,048 + 3·640 against 4·2,048 for a batch of 2,048 with
    one tenant over 62.5% of it)."""
    rows = [p.mask.shape[0] for p in parts]
    fullest = int(np.argmax(rows))
    ids = [fullest] + [m for m in range(len(parts)) if m != fullest]
    return TwoRungWire(
        stack_batches([parts[fullest]]),
        stack_batches([parts[m] for m in ids[1:]]),
        np.asarray(ids, np.int32),
    )


def _bucket(n: int, minimum: int = 8) -> int:
    """Next power-of-two bucket ≥ n (≥ minimum), to bound compile count."""
    b = minimum
    while b < n:
        b *= 2
    return b


def pad_row_count(n: int, row_bucket: int, row_multiple: int = 1) -> int:
    """Padded row count: the requested bucket when it fits, else the
    power-of-two bucket — then rounded up to ``row_multiple`` (mesh data-axis
    divisibility for shard_map training)."""
    b = row_bucket if row_bucket >= n and row_bucket > 0 else _bucket(max(n, 1))
    if row_multiple > 1:
        b += (-b) % row_multiple
    return b


def pad_feature_batch(
    rows: list[tuple[dict[int, float], np.ndarray, float]],
    row_bucket: int = 0,
    token_bucket: int = 0,
    row_multiple: int = 1,
    num_features: int = 0,
    counts: bool = False,
) -> FeatureBatch:
    """Assemble per-tweet sparse features into one padded FeatureBatch.

    ``rows`` holds (text_counts: {hashed_idx: count}, numeric[4], label) per
    tweet, i.e. the output of ``Featurizer.featurize``. Padding rows carry
    mask 0 and are excluded from every statistic and gradient on device.
    """
    n = len(rows)
    max_tok = max((len(r[0]) for r in rows), default=1)
    b = pad_row_count(n, row_bucket, row_multiple)
    lt = token_bucket if token_bucket >= max_tok and token_bucket > 0 else _bucket(
        max(max_tok, 1)
    )

    token_idx = np.zeros((b, lt), dtype=np.int32)
    token_val = np.zeros((b, lt), dtype=np.float32)
    numeric = np.zeros((b, NUM_NUMBER_FEATURES), dtype=np.float32)
    label = np.zeros((b,), dtype=np.float32)
    mask = np.zeros((b,), dtype=np.float32)

    for i, (text_counts, nums, lab) in enumerate(rows):
        for j, (idx, val) in enumerate(text_counts.items()):
            token_idx[i, j] = idx
            token_val[i, j] = val
        numeric[i] = nums
        label[i] = lab
        mask[i] = 1.0
    token_idx, token_val = compact_tokens(
        token_idx, token_val, num_features, counts=counts
    )
    return FeatureBatch(token_idx, token_val, numeric, label, mask)
