"""Share of the tenant parts the mapped step ran whose Gram matrix was built
on the s8 plane: over the ``planes`` of the ``tenant_rows`` instants of the
program's span file (one per delivered batch, ``apps/common.attach_pipeline``'s
tenant adapter; ``planes`` is each part's OWN index of
``ops/gram.text_gram``'s gate, off the stacked quality leaf of the batch's
ONE fetch: 0 exact, 1 bf16, 2 s8, -1 no Gram), the parts that read 2 out of
all M a batch, in percent. The gate reads a part's VALID rows, so under a
skewed key a near-dry part of short rows takes s8 (~6 ms less of a 16.7 ms
step at 2,048 rows) beside bf16 ones, and a cell has two step times by seed:
0 in the usual mode, ~25 where one part of four is on s8 in most batches.
``gram_fast_plane_share`` cannot tell them apart (it counts bf16 and s8
alike, from the slowest plane over the tenants with rows). Read from the
file itself as ``tenant_pad_share`` is, so it is over every batch of the
window run. A program whose instant has no ``planes`` (from before PR 42, or
under ``--modelWatch off``) gives None."""

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    s8 = parts = 0
    for ev in spans.load_events(path):
        a = ev.get("args") or {}
        if (ev.get("ph") == "i" and ev.get("name") == "tenant_rows"
                and "planes" in a):
            s8 += sum(1 for p in a["planes"] if int(p) == 2)
            parts += len(a["planes"])
    return 100.0 * s8 / parts if parts else None
