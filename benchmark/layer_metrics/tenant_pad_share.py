"""Share of the rows the tenant plane's step computed on that were padding:
over the ``tenant_rows`` instants of the program's span file (one per
delivered batch, ``apps/common.attach_pipeline``'s tenant adapter, from the
stacked counts the batch's ONE fetch brought), Σ ``pad_rows`` ÷ Σ
(``pad_rows`` + the M valid-row counts), in percent. Every tenant's batch is
padded to the full row bucket, so with M tenants and full batches it reads
100·(1 − 1/M): 75.0 at M = 4. Read from the file itself as
``gram_fast_plane_share`` is (instants are not in ``art["spans"]``), so it is
over every batch of the window run. A program without the instant gives
None."""

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    pad = rows = 0
    for ev in spans.load_events(path):
        a = ev.get("args") or {}
        if (ev.get("ph") == "i" and ev.get("name") == "tenant_rows"
                and "pad_rows" in a and "rows" in a):
            pad += int(a["pad_rows"])
            rows += int(sum(a["rows"]))
    return 100.0 * pad / (pad + rows) if pad + rows else None
