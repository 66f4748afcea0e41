"""How unevenly the chips of the mesh are loaded: the busiest chip's busy
time in the profiled stretch over the least busy chip's (``profile.per_chip``:
the union of each chip's ``XLA Ops`` intervals), a ratio, 1.0 when even.
Every chip runs the same program in lockstep, so a skew is work one shard
has and another has not (rows of one data shard longer than the other's, a
feature slice with more of the tokens) and shows again as collective wait on
the chips that arrive early (``collective_ms_per_batch``). None on one
chip."""


def read(art):
    chips = (art.get("profile") or {}).get("per_chip") or []
    busy = [c["busy_s"] for c in chips if c.get("busy_s", 0) > 0]
    if len(busy) < 2:
        return None
    return max(busy) / min(busy)
