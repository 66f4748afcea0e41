"""Walker, baseline, reporting, and the CLI contract.

Exit codes (stable, used by CI and tests/test_lawcheck.py):

- 0 — clean: no findings beyond suppressions and the baseline
- 1 — findings: at least one non-baselined, non-suppressed violation
- 2 — malformed: the CHECKER's inputs are broken (unparsable target file,
  reasonless/unknown-rule suppression, corrupt baseline) — failing loud
  beats reporting "clean" off unreadable inputs

The baseline file (``tools/lawcheck/baseline.json``) holds grandfathered
finding fingerprints. Target state: EMPTY — fix, don't baseline. Stale
entries (baselined findings that no longer fire) are reported so the file
shrinks monotonically.
"""

from __future__ import annotations

import ast
import json
import os
import sys
import tempfile

from .findings import Finding, Malformed
from .rules import FileContext, RepoContext, all_rules, rule_ids
from .suppress import scan as scan_suppressions

# generated / gitignored trees are not the repo's code (a tree unpacked
# under target/ or _checkout/ for a chip rehearsal must not be judged twice;
# _scratch/ and .bench_work/ hold the benchmark tests' fixture trees)
_SKIP_DIRS = {
    ".git", "__pycache__", ".pytest_cache", "node_modules", "doc",
    "target", "chiprun_out", ".jax_cache", ".hypothesis", ".bench_work",
    "_checkout", "_scratch",
}
_DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.json")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


def _foreign_dirs(root: str) -> set[str]:
    """A TMPDIR or HOME that lies INSIDE the checkout (the driver's test
    run gives each checkout its own) is not the repo's code either: pytest
    keeps its ``tmp_path`` trees there, among them the mini-repos with
    seeded violations and unparsable files that this checker's own tests
    write — judged as part of the repo they made the real checkout read
    "malformed" in every whole run and clean alone (PR 39)."""
    root = os.path.realpath(root)
    found = set()
    for d in (tempfile.gettempdir(), os.path.expanduser("~")):
        d = os.path.realpath(d)
        if d.startswith(root + os.sep):
            found.add(d)
    return found


def iter_py_files(root: str) -> list[str]:
    out: list[str] = []
    foreign = _foreign_dirs(root)
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames if d not in _SKIP_DIRS and not (
                foreign
                and os.path.realpath(os.path.join(dirpath, d)) in foreign
            )
        )
        for name in sorted(filenames):
            if name.endswith(".py"):
                out.append(os.path.join(dirpath, name))
    return out


class Report:
    def __init__(self) -> None:
        self.findings: list[Finding] = []
        self.malformed: list[Malformed] = []
        self.suppressed: list[Finding] = []
        self.baselined: list[Finding] = []
        self.stale_baseline: list[str] = []

    @property
    def exit_code(self) -> int:
        if self.malformed:
            return 2
        if self.findings:
            return 1
        return 0

    def to_json(self) -> dict:
        return {
            "findings": [f.to_json() for f in self.findings],
            "malformed": [m.to_json() for m in self.malformed],
            "suppressed": len(self.suppressed),
            "baselined": len(self.baselined),
            "stale_baseline": list(self.stale_baseline),
            "exit_code": self.exit_code,
        }


def _load_baseline(path: str, report: Report) -> set[str]:
    if not os.path.exists(path):
        return set()
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
        entries = data["findings"]
        if not isinstance(entries, list) or not all(
            isinstance(e, str) for e in entries
        ):
            raise ValueError("'findings' must be a list of fingerprints")
    except Exception as exc:
        report.malformed.append(Malformed(
            os.path.relpath(path, repo_root()).replace(os.sep, "/"), 0,
            f"unreadable baseline: {exc}",
        ))
        return set()
    return set(entries)


def run_repo(root: str | None = None,
             baseline_path: str | None = None) -> Report:
    root = root or repo_root()
    baseline_path = baseline_path or _DEFAULT_BASELINE
    report = Report()
    known = rule_ids()
    rules = all_rules()

    contexts: list[FileContext] = []
    suppressions = {}
    for abspath in iter_py_files(root):
        rel = os.path.relpath(abspath, root).replace(os.sep, "/")
        try:
            with open(abspath, "r", encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source, filename=rel)
        except (OSError, SyntaxError, ValueError) as exc:
            report.malformed.append(Malformed(
                rel, getattr(exc, "lineno", 0) or 0,
                f"cannot parse target file: {exc}",
            ))
            continue
        contexts.append(FileContext(rel, source, tree, source.splitlines()))
        sup = scan_suppressions(rel, source, known)
        report.malformed.extend(sup.malformed)
        suppressions[rel] = sup

    raw: list[Finding] = []
    repo_ctx = RepoContext(root, contexts)
    for rule in rules:
        for ctx in contexts:
            raw.extend(rule.check(ctx))
        raw.extend(rule.check_repo(repo_ctx))

    baseline = _load_baseline(baseline_path, report)
    seen_fingerprints: set[str] = set()
    deduped: dict[tuple, Finding] = {
        (f.rule, f.path, f.line): f for f in raw
    }
    for f in sorted(deduped.values(), key=lambda f: (f.path, f.line, f.rule)):
        seen_fingerprints.add(f.fingerprint)
        sup = suppressions.get(f.path)
        if sup is not None and sup.covers(f.line, f.rule):
            report.suppressed.append(f)
        elif f.fingerprint in baseline:
            report.baselined.append(f)
        else:
            report.findings.append(f)
    report.stale_baseline = sorted(baseline - seen_fingerprints)
    return report


def write_baseline(report: Report, path: str) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(
            {
                "_comment": (
                    "grandfathered lawcheck findings — target state is "
                    "EMPTY: fix, don't baseline"
                ),
                "findings": sorted(
                    f.fingerprint
                    for f in report.findings + report.baselined
                ),
            },
            fh, indent=2,
        )
        fh.write("\n")


def _print_human(report: Report, out) -> None:
    for m in report.malformed:
        print(m.render(), file=out)
    for f in report.findings:
        print(f.render(), file=out)
    for fp in report.stale_baseline:
        print(f"note: stale baseline entry (no longer fires): {fp}",
              file=out)
    bits = [f"{len(report.findings)} finding(s)",
            f"{len(report.malformed)} malformed",
            f"{len(report.suppressed)} suppressed",
            f"{len(report.baselined)} baselined"]
    print("lawcheck: " + ", ".join(bits), file=out)


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m tools.lawcheck",
        description=(
            "Static analyzer for this repo's structural and parity "
            "laws (exit 0 clean / 1 findings / 2 malformed)"
        ),
    )
    parser.add_argument("--json", action="store_true",
                        help="machine-readable report on stdout")
    parser.add_argument("--root", default=None,
                        help="repo root to scan (default: this checkout)")
    parser.add_argument("--baseline", default=None,
                        help="baseline file (default: tools/lawcheck/"
                             "baseline.json)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="write current findings to the baseline file "
                             "(for grandfathering; target state is empty)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print every rule with the law it "
                             "encodes")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in all_rules():
            print(f"{rule.id}  {rule.title}")
            print(f"       law: {rule.law}")
        return 0

    report = run_repo(root=args.root, baseline_path=args.baseline)
    if args.write_baseline:
        write_baseline(
            report, args.baseline or _DEFAULT_BASELINE
        )
        print(f"baseline written "
              f"({len(report.findings) + len(report.baselined)} entries)")
        return 0
    if args.json:
        print(json.dumps(report.to_json(), indent=2))
    else:
        _print_human(report, sys.stdout)
    return report.exit_code


if __name__ == "__main__":
    raise SystemExit(main())
