"""Device time a chip spends in the collectives of a mesh step, from one
``jax.profiler`` trace: what ``collective_ms_per_batch`` and
``collective_ici_share`` read (benchmark/layer_metrics/).

The program puts every all-gather and psum of its mesh steps under a
``jax.named_scope("collective")`` INSIDE the stage it serves
(``twtml_tpu/parallel/sharding.py``), so a collective's event on a
``/device:TPU:n`` plane's ``XLA Ops`` line carries an op-name path like
``jit(sharded_train_step)/shard_map/gram_matmul/collective/all_gather``. An
event counts when ``collective`` is a part of that path; ``stage_times``
goes on giving its nanoseconds to the stage in front (the first of its nine
names), so this time is a PART of ``step_device_ms`` and of the
``stage_ms.*``, not beside them.

A synchronous collective is in flight for its own duration. An asynchronous
pair (``…-start`` … ``…-done``, both named alike; the compiler may run
compute between them) is in flight from the start's begin to the done's
end, starts and dones of one kind paired in order. A chip's time is the
UNION of those intervals, so overlapping collectives count once and bytes
moved over that time can never exceed what the interconnect carries; the
figure reported is the chip with the most of it (the chip that arrives
first at a collective waits there for the others).

A program without the scope (a one-chip step, a commit from before it)
has no such event: ``reduce`` says ``events: 0`` and the readers return
None. ``python -m benchmark.collectives FILE.xplane.pb`` prints the
reduction.
"""

from __future__ import annotations

import json
import re
import sys

from . import reduce_xplane, stage_times

SCOPE = "collective"
_ASYNC = re.compile(r"^%?([A-Za-z][\w\-]*?)-(start|done)(?:\.\d+)*\b")


def in_scope(op_name: str) -> bool:
    return SCOPE in op_name.rstrip(":").split("/")


def in_flight(events: list) -> list:
    """``[(start, end, instruction name)]``, the scope's events of one
    ``XLA Ops`` line → the intervals a collective is in flight,
    ``[(start, end)]``. A ``-done`` with no open ``-start`` (the trace
    began between them) counts for itself, a ``-start`` the trace cut off
    for its own duration."""
    out, open_starts = [], {}
    for start, end, name in sorted(events):
        hit = _ASYNC.match(name.strip())
        if hit is None:
            out.append((start, end))
        elif hit.group(2) == "start":
            open_starts.setdefault(hit.group(1), []).append((start, end))
        elif open_starts.get(hit.group(1)):
            out.append((open_starts[hit.group(1)].pop(0)[0], end))
        else:
            out.append((start, end))
    for starts in open_starts.values():
        out += starts
    return out


def reduce_planes(planes: list) -> "dict | None":
    """``stage_times.read_xspace``'s planes → None when no device plane ran
    anything, else ``chips``, ``events`` (the scope's events seen) and
    ``per_chip_s`` (each chip's union of in-flight intervals, seconds)."""
    per_chip, events = [], 0
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        ops = [(s, e, m) for line in plane["lines"]
               if line["name"] == stage_times.OPS_LINE
               for s, e, m in line["events"] if e > s]
        if not ops:
            continue
        mine = [(s, e, plane["event_name"].get(m, "")) for s, e, m in ops
                if in_scope(plane["op_name"].get(m, ""))]
        events += len(mine)
        per_chip.append(   # the trace's clock is picoseconds
            reduce_xplane.union_ns(in_flight(mine))[0] / 1e12)
    if not per_chip:
        return None
    return {"chips": len(per_chip), "events": events, "per_chip_s": per_chip}


def reduce(path: str) -> "dict | None":
    return reduce_planes(stage_times.read_xspace(path))


_cache: dict = {}


def of_live_run() -> "dict | None":
    """The reduction of the live run's profile (``trace_files``), made once
    per process."""
    from . import trace_files

    path = trace_files.xplane_file()
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = reduce(path)
    return _cache[path]


def ms_per_batch(art, red: "dict | None" = None) -> "float | None":
    """Collective in-flight time per batch on the chip with the most of it,
    over the batches ``step_device_ms`` divides by; None without a profile
    and where the profile holds no event under the scope."""
    profile = art.get("profile")
    if not profile or not profile.get("batches"):
        return None
    red = of_live_run() if red is None else red
    if red is None or not red["events"]:
        return None
    return 1e3 * max(red["per_chip_s"]) / profile["batches"]


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
