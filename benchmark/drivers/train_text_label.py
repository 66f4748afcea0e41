"""Driver of the training cells whose LABEL the program reads from the text
on the host (traffic ``kind: train_text_label``): ``drivers/train``'s run,
unchanged, behind ONE gate.

``train.prepare_program`` refuses to measure the native library's Python
fallback (``native.require_live``: "a host-bound number taken on the Python
fallback is a tenth of the real one with nothing said"). The labeler is host
code of the same kind, on the batch loop's thread, with a fallback of its
own: the per-row Python rule (``features/sentiment.sentiment_score``: decode,
lower-case, regex-split, two set tests; ~13 us a row). A program that sends
rows of the mix through that rule is paced by the loop and not by the chip.
The program before PR 32 did so for every row holding a unit >= 128, which is
30% of this mix: 8.1-8.4 ms a batch, 75-76k tweets/s against the chip's 79k,
and six runs of it spread 1.1% on the rate and 4.4% on the p95 gap (the
driver's runs, PR 32), where a cell is admitted under 0.5% and 1.5%. Such a
program cannot be held to this cell's bounds, so it is refused HERE, before a
child is started or the device touched: exit code 1, one line on stderr.

The gate is behavioural and asks nothing of the program's names but the two
the cell's app installs: it labels a probe block (ASCII, Latin-1, U+0130, a
surrogate pair beside lexicon words) with ``sentiment_labels_from_units``,
the labeler of the block-ingest path, and counts the calls of
``sentiment_score`` meanwhile. Any call is a row that fell back (a program
with no C library at all falls back on every row, and is refused alike).
"""

from __future__ import annotations

import numpy as np

from .. import harness
from . import train

PROBE = (
    "good morning, what a great day",
    "bad caf\u00e9, terrible cr\u00e8me br\u00fbl\u00e9e, awful",
    "\u0130yi good \u212aind nice",
    "sad \U0001f600 sad \U0001f61e love",
)


def rows_through_the_python_rule() -> int:
    """How many of the probe's rows the program's block labeler sent
    through its per-row Python rule."""
    from twtml_tpu.features import sentiment

    units = np.frombuffer("".join(PROBE).encode("utf-16-le"), np.uint16)
    offsets = np.zeros(len(PROBE) + 1, np.int64)
    np.cumsum([len(t.encode("utf-16-le")) // 2 for t in PROBE],
              out=offsets[1:])
    calls = []
    rule = sentiment.sentiment_score

    def counted(text):
        calls.append(text)
        return rule(text)

    sentiment.sentiment_score = counted
    try:
        sentiment.sentiment_labels_from_units(units, offsets)
    finally:
        sentiment.sentiment_score = rule
    return len(calls)


def run(cell: dict, args, t_start: float) -> dict:
    # the probe imports the program, and with it jax, which reads the
    # variable once, as it is imported: placed later, no persistent cache
    # took effect and every run of this cell compiled cold (PERF.md section 6)
    harness.place_compile_cache()
    fell_back = rows_through_the_python_rule()
    if fell_back:
        raise SystemExit(
            f"benchmark: {cell['workload']['name']} reads its label from the "
            f"text on the host, and this program's labeler sent {fell_back} "
            f"of {len(PROBE)} probe rows through its per-row Python rule: "
            "refusing to measure the fallback (a host-paced run cannot be "
            "held to this cell's bounds)"
        )
    return train.run(cell, args, t_start)
