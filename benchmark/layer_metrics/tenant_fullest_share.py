"""How lopsided the tenant plane's routing key split the stream: over the
``tenant_rows`` instants of the program's span file (one per delivered
batch, ``apps/common.attach_pipeline``'s tenant adapter: ``rows``, the M
valid-row counts of the batch's ONE fetch), the mean over the batches with
rows of max_m rows_m ÷ Σ_m rows_m, in percent. The fullest tenant picks the
row rung of EVERY part (``features/batch.split_batch_tenants``), so this is
what the split on the host hands the device step: 100 ÷ M plus a little
under an even key (~26 at M = 4), 70–80 under ``--tenantKey lang`` on a
stream of mostly all-ASCII rows; over 62.5 at M = 4 and B = 2,048 means the
top rung. Read from the file itself as ``tenant_pad_share`` is (instants are
not in ``art["spans"]``), so it is over every batch of the window run. A
program without the instant gives None."""

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    shares = []
    for ev in spans.load_events(path):
        a = ev.get("args") or {}
        if ev.get("ph") == "i" and ev.get("name") == "tenant_rows":
            rows = [int(n) for n in a.get("rows") or ()]
            if sum(rows):
                shares.append(max(rows) / sum(rows))
    return 100.0 * sum(shares) / len(shares) if shares else None
