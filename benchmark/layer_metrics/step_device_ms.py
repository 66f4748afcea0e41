"""Device busy time per batch: the profiled stretch's busy seconds (union of
device-op intervals, mean over the chips used) over the batches the sink saw
published in that stretch."""


def read(art):
    p = art.get("profile")
    if not p or not p.get("batches"):
        return None
    return 1e3 * p["busy_s"] / p["batches"]
