"""Pooled destination buffers for the packed wire (r17).

Why this exists: every pack built its destination buffer FRESH each tick
— pure allocator churn on the host core that also parses and featurizes,
and fuel for host RSS that grows with uploaded bytes wherever a device
client retains references to upload buffers (ever-new buffers pin
ever-new pages, while recycled buffers bound them; tools/soak.py measures
the slope). The arena is a
size-bucketed free list of uint8 buffers: the wire assembler (or the
numpy fallback's ``np.concatenate(..., out=)``) writes into a LEASED
buffer, ``device_put`` uploads it, and the lease retires back to the pool
when the FetchPipeline delivers (or refunds) the
corresponding dispatch — by which point the step has executed and nothing
can alias the bytes (a ``device_get`` completing is the proof the
dispatch consumed its inputs; retiring at pack/dispatch time would race
the backend's zero-copy aliasing of host numpy buffers).

Ownership only, never layout: the arena changes WHO owns the bytes, not
what they are — decoded features stay bit-identical and model
trajectories bitwise-equal (tests/test_wireassemble.py). Packed-wire
sizes repeat per (signature, K) exactly like compiled programs, so the
free list is keyed by exact byte size and stays small; a bounded
``max_pool_bytes`` cap drops the oldest buffers rather than growing
without bound.

Leases are resilient by construction: a caller that never retires (a
test packing one batch, a bench) simply gets a fresh buffer that the GC
reclaims — indistinguishable from the pre-arena world. ``discard()`` is
the abort path: a wedged dispatch whose execution state is
unknown must never donate its buffer back for reuse.

Telemetry: ``wire.arena_in_use`` (gauge — outstanding leases),
``wire.arena_recycled`` / ``wire.arena_misses`` (counters — pool hits vs
fresh allocations) and ``wire.arena_pool_mb`` (gauge) ride /api/metrics
and the dashboard's arena tile. TW008 (tools/lawcheck) makes the arena a
paid-for law: fresh wire-sized allocations in the pack hot path outside
this module are findings.
"""

from __future__ import annotations

import threading

import numpy as np


class Lease:
    """One leased destination buffer. ``buf`` is the uint8 array to write
    into; call ``retire()`` when the dispatch that uploaded it has
    provably executed (the pipeline's fetch delivery), or ``discard()``
    on abort paths. Both are idempotent."""

    __slots__ = ("_arena", "buf", "_done")

    def __init__(self, arena: "WireArena", buf: np.ndarray):
        self._arena = arena
        self.buf = buf
        self._done = False

    def retire(self) -> None:
        if not self._done:
            self._done = True
            self._arena._retire(self.buf, recycle=True)

    def discard(self) -> None:
        """Abort path: count the lease closed but never reuse the buffer
        (the dispatch that uploaded it may still execute on a wedged
        backend — donating the pages back would risk aliasing)."""
        if not self._done:
            self._done = True
            self._arena._retire(self.buf, recycle=False)


class LeaseChain:
    """Several leases retiring/discarding as ONE — the dispatch-site
    handle for a batch whose wire buffer AND featurize-stage arrays
    (the one-pass native featurizer, r18) are both arena-leased. The
    pipelines hold one lease object per in-flight dispatch; chaining
    keeps that contract while both buffers ride to the same fetch
    delivery. ``buf`` exposes the primary (wire) buffer so accounting
    probes keep working."""

    __slots__ = ("leases", "buf")

    def __init__(self, *leases):
        self.leases = [le for le in leases if le is not None]
        self.buf = self.leases[0].buf if self.leases else None

    def retire(self) -> None:
        for le in self.leases:
            le.retire()

    def discard(self) -> None:
        for le in self.leases:
            le.discard()


def chain_leases(*leases):
    """None-safe, identity-deduplicating combinator: the single lease
    when only one distinct lease is present (the common case — an
    unpacked dispatch sees the same object through both the wire and the
    batch), a ``LeaseChain`` otherwise, None for none."""
    seen: list = []
    for le in leases:
        if le is not None and not any(le is s for s in seen):
            seen.append(le)
    if not seen:
        return None
    if len(seen) == 1:
        return seen[0]
    return LeaseChain(*seen)


class WireArena:
    """Size-bucketed pool of wire destination buffers (module docstring)."""

    def __init__(self, max_pool_bytes: int = 256 << 20):
        self.max_pool_bytes = int(max_pool_bytes)
        self._lock = threading.Lock()
        self._free: "dict[int, list[np.ndarray]]" = {}
        self._free_bytes = 0
        self._in_use = 0
        self.enabled = True

    # gauges/counters resolved lazily so importing this module never pulls
    # the telemetry registry (or anything heavier) at import time; looked
    # up per call, NOT cached — reset_for_tests clears the registry in
    # place, and its contract is exactly that the hot path holds no metric
    # references across calls
    def _metrics(self):
        from ..telemetry import metrics as _metrics

        reg = _metrics.get_registry()
        return (
            reg.gauge("wire.arena_in_use"),
            reg.counter("wire.arena_recycled"),
            reg.counter("wire.arena_misses"),
            reg.gauge("wire.arena_pool_mb"),
        )

    def lease(self, nbytes: int) -> Lease:
        """A uint8 buffer of exactly ``nbytes``, recycled when the pool
        has one, freshly allocated (a counted miss) otherwise."""
        nbytes = int(nbytes)
        in_use, recycled, misses, pool_mb = self._metrics()
        with self._lock:
            bucket = self._free.get(nbytes)
            if self.enabled and bucket:
                buf = bucket.pop()
                self._free_bytes -= nbytes
                recycled.inc()
            else:
                buf = np.empty((nbytes,), np.uint8)
                misses.inc()
            self._in_use += 1
            in_use.set(self._in_use)
            pool_mb.set(round(self._free_bytes / 1e6, 3))
        return Lease(self, buf)

    def _retire(self, buf: np.ndarray, recycle: bool) -> None:
        in_use, _recycled, _misses, pool_mb = self._metrics()
        with self._lock:
            self._in_use -= 1
            in_use.set(self._in_use)
            if (
                recycle
                and self.enabled
                and self._free_bytes + buf.nbytes <= self.max_pool_bytes
            ):
                self._free.setdefault(int(buf.nbytes), []).append(buf)
                self._free_bytes += int(buf.nbytes)
            pool_mb.set(round(self._free_bytes / 1e6, 3))

    def stats(self) -> dict:
        with self._lock:
            return {
                "in_use": self._in_use,
                "free_buffers": sum(len(v) for v in self._free.values()),
                "free_bytes": self._free_bytes,
            }

    def reset_for_tests(self) -> None:
        with self._lock:
            self._free.clear()
            self._free_bytes = 0
            self._in_use = 0
            self.enabled = True


_arena: "WireArena | None" = None
_arena_lock = threading.Lock()


def get_arena() -> WireArena:
    """The process-wide arena every pack destination leases from."""
    global _arena
    with _arena_lock:
        if _arena is None:
            _arena = WireArena()
        return _arena


def set_enabled(on: bool) -> None:
    """Soak/bench control (``tools/soak.py --arena off``): a disabled
    arena hands out fresh buffers and recycles nothing — the pre-arena
    allocation behavior, kept reachable so RSS-slope comparisons have a
    true control arm."""
    get_arena().enabled = bool(on)


def lease_wire(nbytes: int) -> Lease:
    """Module-level convenience: ``get_arena().lease(nbytes)``."""
    return get_arena().lease(nbytes)
