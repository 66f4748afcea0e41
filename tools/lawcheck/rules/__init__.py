"""Rule registry. One rule per law; ids are stable (baseline and
suppression comments reference them), so retired rules must not be reused.

A rule is either per-file (``check(FileContext) -> list[Finding]``) or
repo-level (``check_repo(RepoContext) -> list[Finding]``, for laws that
relate files to each other, like flag/doc sync). Rules never import jax:
the checker must run in milliseconds with no backend side effects.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass


@dataclass
class FileContext:
    path: str  # repo-relative posix
    source: str
    tree: ast.AST
    lines: list[str]


@dataclass
class RepoContext:
    root: str  # absolute repo root
    files: "list[FileContext]"  # every scanned python file, parsed

    def get(self, path: str) -> "FileContext | None":
        for f in self.files:
            if f.path == path:
                return f
        return None


class Rule:
    id: str = ""
    title: str = ""  # one line, shown by --list-rules and cited in docs
    law: str = ""  # the mechanism this guards, with its source doc

    def check(self, ctx: FileContext):  # per-file rules override
        return []

    def check_repo(self, repo: RepoContext):  # repo-level rules override
        return []


def all_rules() -> "list[Rule]":
    from .arena import TW008WireArena
    from .device import TW004Scatter
    from .docs import TW007FlagDocs
    from .historian import TW010HistorianSeam
    from .host import TW005SilentSwallow, TW006WallClock
    from .journal import TW009JournalSeam
    from .transport import TW001BackendInit, TW002FetchSeam, TW003ThreadPut

    return [
        TW001BackendInit(),
        TW002FetchSeam(),
        TW003ThreadPut(),
        TW004Scatter(),
        TW005SilentSwallow(),
        TW006WallClock(),
        TW007FlagDocs(),
        TW008WireArena(),
        TW009JournalSeam(),
        TW010HistorianSeam(),
    ]


def rule_ids() -> frozenset[str]:
    return frozenset(r.id for r in all_rules())
