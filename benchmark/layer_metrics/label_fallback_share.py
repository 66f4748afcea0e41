"""Share of the labelled rows that took the per-row Python rule instead of
the C scan: rows of the ``label_fallback`` spans (written only when some row
fell back, ``features/sentiment._labels_from_scores``) over the rows of the
``featurize.label`` sub-spans, in percent. 0 when no row fell back; None
where the program has no ``featurize.label`` span (no label read from the
text, or a program from before PR 32)."""


def read(art):
    spans = art.get("spans") or {}
    rows = spans.get("featurize.label", {}).get("rows")
    if not rows:
        return None
    return 100.0 * spans.get("label_fallback", {}).get("rows", 0) / rows
