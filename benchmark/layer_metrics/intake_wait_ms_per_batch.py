"""Time the source thread waited on the intake queue's row bound
(``--maxQueueRows``, ``_RowCountQueue.put``) per batch published in the
window: the ``intake_wait`` spans. It is the source thread's SLACK: high
while the device sets the pace, near 0 once the host does.

A span is written only when a wait happened, so a program that has the
span and never waited reads 0; None only where the program has none of the
wait spans at all (a program from before them)."""

HAS_WAIT_SPANS = ("intake_wait", "deliver_wait", "source_lines")


def read(art):
    spans = art.get("spans") or {}
    batches = spans.get("stats_publish", {}).get("count")
    if not batches or not any(k in spans for k in HAS_WAIT_SPANS):
        return None
    return spans.get("intake_wait", {}).get("total_ms", 0.0) / batches
