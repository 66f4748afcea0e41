"""Share of the delivered batches that left the fetch pipeline in a round
that delivered two or more: over the ``deliver_round`` instants of the
program's span file (one per call of ``FetchPipeline.on_batch`` that got as
far as its dispatch: ``ready``, the leading results that were done when the
round's delivery began; ``delivered``, every result the round handed to the
handlers; ``pending``, what it left in flight), 100 × Σ ``delivered`` over
the rounds with ``delivered`` ≥ 2 ÷ Σ ``delivered``. Under one delivery a
round it reads ~0 (only a catch-up round delivers two); where rounds
alternate two deliveries and none it reads ~100·(pairs ÷ all). Read from
the file itself as ``tenant_pad_share`` is (instants are not in
``art["spans"]``), so it is over every round of the window run. A program
without the instant gives None."""

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    paired = total = 0
    for ev in spans.load_events(path):
        a = ev.get("args") or {}
        if (ev.get("ph") == "i" and ev.get("name") == "deliver_round"
                and "delivered" in a):
            n = int(a["delivered"])
            total += n
            if n >= 2:
                paired += n
    return 100.0 * paired / total if total else None
