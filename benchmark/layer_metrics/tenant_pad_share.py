"""Share of the rows the tenant plane's step computed on that were padding:
over the ``tenant_rows`` instants of the program's span file (one per
delivered batch, ``apps/common.attach_pipeline``'s tenant adapter, from the
stacked counts the batch's ONE fetch brought), Σ ``pad_rows`` ÷ Σ
(``pad_rows`` + the M valid-row counts), in percent. ``pad_rows`` is
M·``bucket`` − Σ rows, ``bucket`` the row rung the split padded every part
of that batch to (PR 36), so with full batches of B rows it reads
100·(1 − B ÷ (M·rung)): 20.0 at four parts of 640 rows for 2,048 (an even
key), and 75.0 = 100·(1 − 1/M) only where every split takes the top rung B
(a lopsided key: ``hash2e18-lang4-trimmed-280``; every cell until PR 36).
Read from the file itself as
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
