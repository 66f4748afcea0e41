"""The documents and the comments name what is there.

A session reads CLAUDE.md, README.md and the rest before it reads code, and a
path that no longer exists sends it looking (CLAUDE.md cited a file for
thirty PRs after it was deleted). Two checks, no jax: every repo path a
document names exists, and no source file cites a record or a harness that
is gone.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = [
    "CLAUDE.md",
    "README.md",
    "PARITY.md",
    "SCALING.md",
    ".github/workflows/ci.yml",
    ".claude/skills/verify/SKILL.md",
]

# a file as a document writes it: directories and a name with a source
# file's extension, or a bare name (``chip_smoke.py``, ``PERF.md``);
# not a piece of a longer path, a URL or a glob
_PATH = re.compile(
    r"(?<![\w./\-<>*:{}])"
    r"((?:[A-Za-z_][\w\-]*/)+[\w.\-]+\.(?:py|md|cpp|h|json|jsonl|js|sh|yml)"
    r"|[A-Za-z_][\w\-]*\.(?:py|md))"
    r"(?![\w/\-*{<]|\.\w)"
)
# files a run writes, the driver's records, the reference's own sources and
# names a document makes up for an example
_NOT_OURS = {
    "SKILL.md", "BENCHMARK_REFUSED.md", "consume.py", "pym.js",
}
_SKIP_DIRS = {".git", "_checkout", "_scratch", "chiprun_out", "__pycache__",
              ".jax_cache", ".pytest_cache"}


def _tree():
    files = set()
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
        rel = os.path.relpath(dirpath, REPO)
        for name in filenames:
            files.add(os.path.normpath(os.path.join(rel, name)))
    return files


def _named_paths(text):
    return sorted({m.group(1) for m in _PATH.finditer(text)})


def _missing(paths, files):
    """A document may shorten a module by its package (``features/batch.py``,
    ``config.py``): a name exists if some file of the tree ends with it."""
    return [
        p for p in paths
        if os.path.basename(p) not in _NOT_OURS
        and not any(f == p or f.endswith("/" + p) for f in files)
    ]


@pytest.mark.parametrize("doc", DOCS)
def test_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        text = fh.read()
    assert _missing(_named_paths(text), _tree()) == []


# the pre-round write-up (deleted in PR 21) and the harnesses that wrote it
# (deleted in PR 51), spelled so that this file does not cite them itself
_GONE = re.compile("|".join([
    "BENCHMARKS" + r"\.md", r"\bbench" + r"\.py", "bench" + "_suite",
    "paired" + "bench", "bench" + "loop",
]))


@pytest.mark.parametrize("tree", ["twtml_tpu", "tools", "tests", "native"])
def test_sources_cite_no_deleted_record(tree):
    cited = []
    for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, tree)):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith((".pyc", ".so", ".stamp", ".gz", ".pb")):
                continue
            path = os.path.join(dirpath, name)
            with open(path, encoding="utf-8", errors="replace") as fh:
                for n, line in enumerate(fh, 1):
                    if _GONE.search(line):
                        cited.append(f"{os.path.relpath(path, REPO)}:{n}")
    assert cited == []
