"""Host time in ``featurize`` + ``wire_pack`` spans per tweet published in
the window."""


def read(art):
    spans, tweets = art.get("spans") or {}, art.get("tweets")
    if "featurize" not in spans or not tweets:
        return None
    ms = spans["featurize"]["total_ms"] + spans.get("wire_pack", {}).get("total_ms", 0.0)
    return 1e3 * ms / tweets
