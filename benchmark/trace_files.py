"""Where the live run keeps its trace files.

``art`` (what a per-layer reader is handed) carries numbers, no paths, so
the readers that need the files themselves — the program's whole span file
(``art["spans"]`` holds only spans that started in the window) and the
profiler's ``*.xplane.pb`` — find them here: one cell runs per process, its
driver keeps them under ``harness.WORK/<cell>/`` and removes that directory
only after the readers have run. A ``benchmark`` issue that puts the two
paths into ``art`` retires this module.
"""

from __future__ import annotations

import glob
import os

from . import harness, reduce_xplane


def span_file() -> "str | None":
    """The window run's ``--trace`` file, if a traced run is live."""
    found = sorted(glob.glob(os.path.join(harness.WORK, "*", "spans.json")))
    return found[-1] if found else None


def xplane_file() -> "str | None":
    """The harness's profile of the window, if one was taken."""
    for work in sorted(glob.glob(os.path.join(harness.WORK, "*")), reverse=True):
        found = reduce_xplane.find_xplane(os.path.join(work, "profile"))
        if found:
            return found
    return None
