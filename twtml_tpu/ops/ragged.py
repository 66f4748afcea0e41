"""Device-side re-pad of the ragged units wire — ONE definition shared by
every step builder (single-device, data-parallel, feature-sharded), so the
wire semantics cannot drift between layouts.

The ragged wire (features/batch.py ``RaggedUnitBatch``) ships text as
concatenated code units + row offsets — no per-row pad bytes on the
wire. The learner rebuilds the padded [B, L] layout INSIDE the jit
program and case-folds ASCII there, which the padded wire's C pad copy did
on the host. Features are bit-identical either way
(tests/test_ragged_wire.py).

The re-pad moves WHOLE 128-lane rows, never single units (PR 33): the
units buffer is viewed as a [N/128, 128] table, row b gathers the
ceil(L/128) + 1 table rows from ``start_b // 128`` on, and a seven-stage
barrel shifter (one static shift per bit of ``start_b % 128``) moves its
first unit to column 0. A gather of one-element slices with a [B, L] index
is what the TPU runs an element at a time (PERF.md §6, PR 33).

Under shard_map the arrays arrive SHARD-LOCAL (this shard's sub-buffer and
its shard-relative offsets — features/batch.py ``align_ragged_shards``),
and the same re-pad rebuilds this shard's [B_local, L] rows; ``row_len``
(L) is static and global, so every shard's re-pad agrees with the
single-device layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# lanes of a vector register row: the re-pad moves the units buffer by
# whole rows of this many units
LANE_BITS = 7
LANES = 1 << LANE_BITS


def offsets_from_deltas(deltas, num_segments: int = 1):
    """uint16 per-row length deltas → segment-relative int32 offsets, in
    program — the decode half of the NARROW offset wire (Lean wire v2:
    features/batch.py ships offsets as length deltas in half the sideband
    bytes whenever the static ``row_len`` gate allows; this cumsum rebuilds
    the exact offsets, so every downstream consumer — ``ragged_repad``
    first — sees the int32 wire bit-identically).

    Shapes: [..., S·B_s] → [..., S·(B_s+1)] (leading axes pass through —
    a stacked [M, B] tenant wire decodes to [M, B+1] in one call).
    Each segment's offsets start at 0 by construction
    (``ragged_wire_arrays`` / ``align_ragged_shards``), which is what makes
    the delta encoding lossless."""
    lead = deltas.shape[:-1]
    d = deltas.astype(jnp.int32).reshape(lead + (num_segments, -1))
    zero = jnp.zeros(lead + (num_segments, 1), jnp.int32)
    out = jnp.concatenate([zero, jnp.cumsum(d, axis=-1)], axis=-1)
    return out.reshape(lead + (-1,))


def units_from_codes(codes, out_len: int):
    """Digram-coded units wire → the raw uint8 units buffer, in program —
    the decode half of the COMPRESSED wire (``--wireCodec dict``:
    features/wirecodec.py encodes the all-ASCII uint8 units into literal
    bytes < 0x80 and two-unit dictionary codes >= 0x80 on the host; this
    rebuilds the exact buffer ahead of the ragged re-pad, so every
    downstream consumer — ``ragged_repad`` first — sees the uncompressed
    wire bit-identically).

    Decode is a bounded gather-expand + cumsum, the ``offsets_from_deltas``
    family: per-code expanded lengths (1 or 2) cumsum to output positions,
    one searchsorted maps each of the ``out_len`` output slots back to its
    code (a vectorized binary search — gathers only, never a scatter or a
    data-dependent loop: the TW004/XLA serialization trap), and a two-entry
    table gather materializes the unit. The 128×2 decode table is a static
    compile-time constant (the dictionary ships in the program, not on the
    wire). ``out_len`` is static (the raw units bucket recorded in the
    packed layout); trailing padding codes past it are never gathered.

    Shapes: [..., M] codes → [..., out_len] uint8 units (leading axes pass
    through — the stacked [K, M] group wire decodes in one call)."""
    from ..features.wirecodec import CODE_BASE, decode_table

    table = jnp.asarray(decode_table())  # [128, 2] uint8, baked constant

    def one(c1d):
        c = c1d.astype(jnp.int32)
        lens = 1 + (c >= CODE_BASE).astype(jnp.int32)
        ends = jnp.cumsum(lens)  # inclusive expansion ends, [M]
        t = jnp.arange(out_len, dtype=jnp.int32)
        j = jnp.clip(
            jnp.searchsorted(ends, t, side="right"), 0, c.shape[0] - 1
        ).astype(jnp.int32)
        k = jnp.clip(t - (ends[j] - lens[j]), 0, 1)
        cj = c[j]
        exp = table[jnp.clip(cj - CODE_BASE, 0, CODE_BASE - 1), k]
        return jnp.where(cj < CODE_BASE, cj, exp.astype(jnp.int32)).astype(
            jnp.uint8
        )

    if codes.ndim == 1:
        return one(codes)
    lead = codes.shape[:-1]
    out = jax.vmap(one)(codes.reshape((-1, codes.shape[-1])))
    return out.reshape(lead + (out_len,))


@jax.named_scope("repad")  # device stage name (models/sgd.py STAGE_SCOPES)
def ragged_repad(units, offsets, row_len: int, rows: int | None = None,
                 deltas: bool = False):
    """(flat units [N], offsets, static L) → (padded int32 [B, L]
    case-folded units, int32 [B] lengths) — the padded-wire layout, on
    device.

    ``rows`` (B, the row count the caller's mask carries) tells the shard
    count apart statically: a shard-ALIGNED buffer carries one
    [B_s + 1] offsets block per segment, so S = offsets.size − rows
    (S = 1 when offsets is the plain [B + 1] vector; None means plain).
    Segment s's sub-buffer starts at s·(N/S) and its offsets are
    segment-relative, so converting to absolute starts is one broadcast
    add — the re-pad itself is identical in every layout.

    ``deltas=True`` accepts the NARROW offset wire directly: ``offsets``
    then holds uint16 per-row length deltas ([B], one segment per
    ``rows``-worth of deltas is impossible to infer from size, so callers
    on the multi-segment layout decode via ``offsets_from_deltas`` first)
    and the cumsum happens here, in-program — the repad result is
    bit-identical to the int32 wire."""
    if deltas:
        offsets = offsets_from_deltas(offsets)
        rows = None
    offs = offsets.astype(jnp.int32)
    n_segments = 1 if rows is None else offsets.shape[0] - rows
    if n_segments > 1:
        ob = offs.reshape(n_segments, -1)  # [S, B_s + 1], segment-relative
        base = (
            jnp.arange(n_segments, dtype=jnp.int32)
            * (units.shape[0] // n_segments)
        )[:, None]
        starts = (ob[:, :-1] + base).reshape(-1)
        lens = (ob[:, 1:] - ob[:, :-1]).reshape(-1)
    else:
        starts, lens = offs[:-1], offs[1:] - offs[:-1]
    # whole lane rows, then a shift — never one gather per element: the
    # units as a [T, 128] table; row b's units lie inside the k table
    # rows from starts[b] // 128 on, ``starts[b] % 128`` lanes in
    table = units.astype(jnp.int32)
    table = jnp.pad(table, (0, -table.shape[0] % LANES)).reshape(-1, LANES)
    k = -(-row_len // LANES) + 1
    first = (starts >> LANE_BITS)[:, None] + jnp.arange(k, dtype=jnp.int32)
    # a clipped row index only ever lands beyond the row's own units
    # (starts + lens <= N), where the mask below writes 0
    wide = table[jnp.clip(first, 0, table.shape[0] - 1)]
    wide = wide.reshape(starts.shape[0], -1)
    # barrel shifter: shift left by starts % 128, one static shift per bit
    shift = starts & (LANES - 1)
    for bit in reversed(range(LANE_BITS)):
        by = 1 << bit
        # after this stage at most ``by - 1`` lanes of shift remain
        keep = row_len + by - 1
        wide = jnp.where(
            ((shift >> bit) & 1)[:, None] == 1,
            wide[:, by:by + keep], wide[:, :keep],
        )
    cols = jnp.arange(row_len, dtype=jnp.int32)[None, :]
    buf = jnp.where(cols < lens[:, None], wide, 0)
    buf = buf + ((buf >= 65) & (buf <= 90)) * 32  # ASCII case fold
    return buf, lens
