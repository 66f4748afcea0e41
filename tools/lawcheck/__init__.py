"""Law-checker: a repo-specific static analyzer for the repo's structural laws.

The repo keeps a set of structural invariants — one counted fetch per tick,
main-thread-only ``device_put``, no scatter into 2^18 (XLA serializes it),
Try-parity on publish paths, no module-scope backend init before the
conftest mesh pin, the ``TWTML_NOW_MS`` determinism seam, and flag/doc
sync. Each was enforced only by convention and a handful of runtime
counting tests; a single unreviewed call site could silently break one.
This package enforces them over the AST, in CI. Which of the
performance-motivated ones still earn their place on this machine is
ROADMAP D5's question (nothing about their cost is measured here yet —
PERF.md).

One rule per law (``python -m tools.lawcheck --list-rules``); every finding
message states the mechanism it guards. Pure stdlib (``ast``), no jax
import, no third-party deps.

Usage::

    python -m tools.lawcheck            # exit 0 clean / 1 findings / 2 malformed
    python -m tools.lawcheck --json     # machine-readable findings
    # lawcheck: disable=TW004 -- <reason>   (inline, reason REQUIRED)

The checked-in baseline (``tools/lawcheck/baseline.json``) exists for
grandfathered findings and is kept EMPTY on purpose: fix, don't baseline.
"""

from .engine import main, run_repo  # noqa: F401
from .findings import Finding, Malformed  # noqa: F401
