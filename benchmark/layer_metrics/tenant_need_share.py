"""Share of the Gram work the tenant plane's mapped step SPENT that the
tenants' rows called for: over the ``tenant_rows`` instants of the program's
span file (one per delivered batch, ``apps/common.attach_pipeline``'s tenant
adapter: ``rows``, the M valid-row counts of the batch's ONE fetch, and
``bucket``, the row rung every part was padded to),

    100 × Σ_batches Σ_m rows_m² ÷ Σ_batches M·bucket²

A Gram step of n rows costs 2·n²·F, and ``parallel/tenants._mapped`` runs M
steps of ``bucket`` rows whatever the parts hold, so this is the plane's own
roofline under skew: ~64 under an even split at the first rung (four parts
of ~512 rows in 640), ~14 where one tenant holds ~72% of a batch and all
four parts take the batch's own 2,048 rows. It cannot pass 100 (rows_m ≤
bucket). Read from the file itself as ``tenant_pad_share`` is (instants are
not in ``art["spans"]``), so it is over every batch of the window run. A
program without the instant, or from before it carried ``bucket`` (PR 36),
gives None."""

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    need = spent = 0
    for ev in spans.load_events(path):
        a = ev.get("args") or {}
        if (ev.get("ph") == "i" and ev.get("name") == "tenant_rows"
                and "rows" in a and "bucket" in a):
            need += sum(int(n) ** 2 for n in a["rows"])
            spent += len(a["rows"]) * int(a["bucket"]) ** 2
    return 100.0 * need / spent if spent else None
