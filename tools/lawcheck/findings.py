"""Finding/Malformed records and their wire forms.

A ``Finding`` is one rule violation at one source location; its
``fingerprint`` (``RULE:path:line``) is the baseline key. ``Malformed`` is
a defect in the *checking machinery itself* — an unparsable target file, a
suppression comment without the required reason, an unknown rule id in a
suppression, a corrupt baseline — and maps to exit code 2: a law checker
that cannot read its inputs must fail loudly, not report "clean".
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Finding:
    rule: str  # "TW001".."TW007"
    path: str  # repo-relative posix path ("" for repo-level rules)
    line: int  # 1-based; 0 for repo-level findings with no anchor line
    message: str  # states the violation AND cites the law

    @property
    def fingerprint(self) -> str:
        return f"{self.rule}:{self.path}:{self.line}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
        }


@dataclass(frozen=True)
class Malformed:
    path: str
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: MALFORMED {self.message}"

    def to_json(self) -> dict:
        return {
            "rule": "MALFORMED",
            "path": self.path,
            "line": self.line,
            "message": self.message,
        }
