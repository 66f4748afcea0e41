"""Share of the chip's interconnect peak at which the mesh step's
collectives moved what they had to: the bytes a chip must SEND per batch,
from the configuration's own sizes (``must_send_bytes`` below), over the
time a collective was in flight on the chip with the most of it
(``collective_ms_per_batch``), against 200 GB/s — the 1600 Gbit/s of one
v5e chip's interconnect that ``peaks.json``'s ``source`` line gives (the
table has no such key). The bytes are the least any algorithm sends and
the time is a union that holds every transfer, so the share cannot pass
100%. A low share with a small ``collective_ms_per_batch`` says the
collectives are latency-bound and cheap; a low share with a large one says
they wait (a chip arrives late: ``chip_step_skew``).

The configuration is the live cell's (``art`` carries no sizes): the cell
is the directory the harness keeps the live run's files in
(``trace_files``), its layout the ``--modelShards`` of the configuration's
``flags`` over its ``chips``. None where there is no such flag (a one-chip
or data-only configuration), no profile, or nothing under the scope."""

import os

from benchmark import collectives, harness, manifest, trace_files

ICI_BYTES_PER_S = 1600e9 / 8   # one v5e chip, all links (peaks.json source)


def must_send_bytes(config: dict, wire_bytes_per_batch: float) -> "float | None":
    """What one chip must send per batch on the configuration's
    ``(data, model) = (d, m)`` mesh, each term the least any algorithm
    moves: this data shard's rows to the other ``d − 1`` (the wire bytes
    themselves; the program gathers the hashed (idx, val) pairs, which is
    more); the ``[B/d, B]`` f32 panel's all-reduce over ``model``
    (``2·S·(m−1)/m``: a reduce-scatter and an all-gather); the G all-gather
    over ``data`` (this chip's panel to the other ``d − 1``); the
    write-back all-reduce over ``data`` of the slice's ``F/m`` f32 deltas.
    None without a ``--modelShards`` flag."""
    flags = list(config.get("flags") or [])
    if "--modelShards" not in flags:
        return None
    m = int(flags[flags.index("--modelShards") + 1])
    d = int(config["chips"]) // m
    b = float(config["batch_rows"])
    f_local = float(config["model"]["numTextFeatures"]) / m
    panel = b / d * b * 4
    return (
        wire_bytes_per_batch / d * (d - 1)
        + 2 * panel * (m - 1) / m
        + panel * (d - 1)
        + 2 * f_local * 4 * (d - 1) / d
    )


def live_config() -> "dict | None":
    path = trace_files.xplane_file()
    if path is None:
        return None
    name = os.path.relpath(path, harness.WORK).split(os.sep)[0]
    return manifest.cell(manifest.load(), name)["config"]


def read(art):
    ms = collectives.ms_per_batch(art)
    if not ms:
        return None
    pack = (art.get("spans") or {}).get("wire_pack") or {}
    wire = pack["bytes"] / pack["count"] if pack.get("bytes") else 0.0
    nbytes = must_send_bytes(live_config() or {}, wire)
    if not nbytes:
        return None
    print(f"[bench] collective_ici_share: {nbytes / 1e6:.2f} MB a chip and "
          f"batch, floor {1e3 * nbytes / ICI_BYTES_PER_S:.4f} ms at "
          f"{ICI_BYTES_PER_S / 1e9:.0f} GB/s, in flight {ms:.4f} ms")
    return 100.0 * (nbytes / ICI_BYTES_PER_S) / (ms / 1e3)
