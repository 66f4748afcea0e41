"""Share of the window the feeder spent waiting for the socket to take more
(its own clock): high when the trainer pushes back, low when the feeder was
the limit."""


def read(art):
    f = art.get("feeder")
    return None if not f else 100.0 * f["blocked_s"] / f["window_s"]
