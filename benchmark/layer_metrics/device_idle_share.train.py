"""Share of the profiled stretch in which no operation ran on the device
(mean over the chips used)."""


def read(art):
    p = art.get("profile")
    return None if not p else 100.0 * (1.0 - p["busy_s"] / p["window_s"])
