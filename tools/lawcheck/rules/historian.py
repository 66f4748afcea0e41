"""Historian law: telemetry samples are taken at ONE seam only.

The telemetry historian (telemetry/historian.py, ISSUE 20) is zero-cost by
construction ONLY because ``historian.sample()`` runs at the existing
stats-publish cadence — it snapshots registry/health/stage views that
publish tick already computed. A second sampling site would either pay new
snapshot work on a hot path or, worse, tempt a caller into fetching device
state "for the historian" — the exact failure mode the counted-fetch tests
exist to prevent. TW010 pins the seam the same way TW009 pins the journal's
intake seam.
"""

from __future__ import annotations

import ast

from ..findings import Finding
from . import FileContext, Rule
from .transport import dotted, import_aliases


class TW010HistorianSeam(Rule):
    id = "TW010"
    title = "historian sampling outside the blessed publish seam"
    law = (
        "the telemetry historian adds zero fetches/collectives only "
        "because historian.sample() is called from exactly ONE seam — "
        "SessionStats' once-a-period item (_publish_registry), at the "
        "stats-publish cadence; any other sampling site pays new "
        "snapshot work on a hot path or invites a device fetch the "
        "counted-fetch law forbids (telemetry/historian.py docstring; "
        "ISSUE 20)"
    )
    # the seam caller and the implementation itself
    SEAM_FILES = frozenset({
        "twtml_tpu/telemetry/session_stats.py",
        "twtml_tpu/telemetry/historian.py",
    })

    def check(self, ctx: FileContext):
        if not ctx.path.startswith("twtml_tpu/"):
            return []
        if ctx.path in self.SEAM_FILES:
            return []
        aliases = import_aliases(ctx.tree)
        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            path = dotted(node.func, aliases)
            # match the module hook (historian.sample / _historian.sample)
            # and the instance method through a historian-named handle
            # (historian.get().sample()) — but not random.sample and
            # friends: the receiver must be historian-flavored
            if path.endswith(".sample") and "histor" in path.lower():
                findings.append(Finding(
                    self.id, ctx.path, node.lineno,
                    "historian.sample() outside the blessed publish seam "
                    "— " + self.law,
                ))
        return findings
