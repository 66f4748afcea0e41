"""Host time in ``parse`` spans per tweet published in the window."""


def read(art):
    st = (art.get("spans") or {}).get("parse")
    tweets = art.get("tweets")
    return None if not st or not tweets else 1e3 * st["total_ms"] / tweets
