"""The source thread's line loop per tweet published in the window: the
``source_lines`` spans (``BlockTwitterSource.produce`` +
``httpstream.open_stream``, first line of a block to the call of the
parser) less the ``source_recv`` spans (the part inside socket reads) — the
loop's own Python, which no other span covers."""


def read(art):
    spans, tweets = art.get("spans") or {}, art.get("tweets")
    if "source_lines" not in spans or not tweets:
        return None
    ms = spans["source_lines"]["total_ms"] - spans.get(
        "source_recv", {}).get("total_ms", 0.0)
    return 1e3 * ms / tweets
