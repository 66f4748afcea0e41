"""Inline suppression comments: ``lawcheck: disable=TWxxx -- reason``.

A suppression silences named rules on ITS OWN line only, and the trailing
reason is mandatory — the whole point of the law checker is that every
deviation from a law carries its justification next to the code
(nothing is exempt silently). A reasonless
suppression, an unknown rule id, or a malformed comment body is a
``Malformed`` record (exit 2), not a silent no-op: a typo'd suppression
that silently failed to apply would surface as a phantom finding, and one
that silently applied too broadly would hide real ones.

Grammar (one comment per line, after any code; one or more rule ids,
comma-separated, then ``--`` and the reason)::

    X = X.at[idx].set(v)  # lawcheck: disable=TW004 -- bounded K-sized scatter
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from .findings import Malformed

# the marker is permissive (any "lawcheck:" comment is inspected) so typos
# like "disable TW004" are caught as malformed instead of silently ignored
_MARKER = re.compile(r"#\s*lawcheck:\s*(?P<body>.*)$")
_DISABLE = re.compile(
    r"^disable=(?P<rules>[A-Z]{2}\d{3}(?:\s*,\s*[A-Z]{2}\d{3})*)"
    r"(?:\s+--\s*(?P<reason>.*))?$"
)


@dataclass
class Suppressions:
    """Per-file map of line -> set of rule ids suppressed on that line."""

    by_line: dict[int, set[str]] = field(default_factory=dict)
    malformed: list[Malformed] = field(default_factory=list)

    def covers(self, line: int, rule: str) -> bool:
        return rule in self.by_line.get(line, ())


def scan(path: str, source: str, known_rules: frozenset[str]) -> Suppressions:
    out = Suppressions()
    for lineno, text in enumerate(source.splitlines(), start=1):
        m = _MARKER.search(text)
        if not m:
            continue
        body = m.group("body").strip()
        d = _DISABLE.match(body)
        if not d:
            out.malformed.append(Malformed(
                path, lineno,
                f"unrecognized lawcheck comment {body!r} — expected "
                "'disable=TWxxx[,TWyyy] -- reason'",
            ))
            continue
        reason = (d.group("reason") or "").strip()
        if not reason:
            out.malformed.append(Malformed(
                path, lineno,
                "suppression without a reason — every deviation from a "
                "law must carry its justification "
                "('disable=TW004 -- why this site is exempt')",
            ))
            continue
        rules = {r.strip() for r in d.group("rules").split(",")}
        unknown = sorted(rules - known_rules)
        if unknown:
            out.malformed.append(Malformed(
                path, lineno,
                f"suppression names unknown rule(s) {', '.join(unknown)} — "
                "see 'python -m tools.lawcheck --list-rules'",
            ))
            continue
        out.by_line.setdefault(lineno, set()).update(rules)
    return out
