"""Time the scheduler's thread waited for the oldest in-flight result
(``FetchPipeline._emit_one`` around ``FetchWatchdog.await_result``) per batch
published in the window: the ``deliver_wait`` spans. It is the batch loop's
SLACK: the gap between batches less the scheduler's own work; near 0 once
the host sets the pace.

A span is written only when a wait happened, so a program that has the
span and never waited reads 0; None only where the program has none of the
wait spans at all (a program from before them)."""

HAS_WAIT_SPANS = ("intake_wait", "deliver_wait", "source_lines")


def read(art):
    spans = art.get("spans") or {}
    batches = spans.get("stats_publish", {}).get("count")
    if not batches or not any(k in spans for k in HAS_WAIT_SPANS):
        return None
    return spans.get("deliver_wait", {}).get("total_ms", 0.0) / batches
