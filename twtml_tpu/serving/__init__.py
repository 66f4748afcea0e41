"""Serving plane: batched, pipelined low-latency inference from verified
snapshots (ISSUE 9 / ROADMAP item 1).

The reference's entire upper half (SURVEY §1, L4-L6) is a live *read* path —
the trained model exists to answer queries — but through PR 8 prediction only
existed fused inside the train step. This package splits it out as a product:

- ``snapshot``  — verified-checkpoint snapshots + the ONE promotion predicate
                  (finite + quality level <= warn) shared with
                  ``tools/model_report.py --gate``, and the hot-swap promoter;
- ``engine``    — the jitted predict-only program over a device-resident
                  snapshot (the fused train step with ``num_iterations=0``:
                  the SAME traced prediction prologue, so serve-path
                  predictions are BIT-identical to the train step's
                  pre-update predictions — the parity law on the read path);
- ``plane``     — the bounded-latency request coalescer + depth-K pipelined
                  result fetches through ``apps/common.FetchPipeline``
                  (overlapping ``device_get``s, as the trainer does);
- ``client``    — the library-level HTTP client (``POST /api/predict``) for
                  load generation and ops scripts;
- ``fleet``     — the read-fleet router (ISSUE 11): N serve replicas behind
                  one front door — least-p99/consistent-hash routing,
                  health checks, ejection behind a jittered backoff;
- ``abtest``    — champion/challenger on the tenant stack: the champion
                  answers live traffic, challengers shadow-score the same
                  mirrored batch, and per-tenant quality stamps
                  auto-promote through the ONE ``is_promotable`` gate.

Import discipline: ``snapshot``, ``client``, and ``fleet`` are jax-free
(ops tools — ``tools/model_report.py --gate`` — must not initialize a
backend to answer "is this checkpoint servable?", and the router process
holds no model at all); the engine/plane/abtest modules import jax lazily
via ``__getattr__``.
"""

from __future__ import annotations

from .client import ServingClient
from .fleet import FleetRouter
from .snapshot import (
    ServingSnapshot,
    SnapshotPromoter,
    is_promotable,
    load_servable,
)

__all__ = [
    "ChampionEngine",
    "ChampionSelector",
    "FleetRouter",
    "ServingClient",
    "ServingPlane",
    "ServingSnapshot",
    "SnapshotPromoter",
    "is_promotable",
    "load_servable",
]

_LAZY = {
    # lazy: these pull in jax via the model layer
    "ServingPlane": ("plane", "ServingPlane"),
    "ChampionEngine": ("abtest", "ChampionEngine"),
    "ChampionSelector": ("abtest", "ChampionSelector"),
}


def __getattr__(name: str):
    target = _LAZY.get(name)
    if target is not None:
        import importlib

        module = importlib.import_module(f".{target[0]}", __name__)
        return getattr(module, target[1])
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
