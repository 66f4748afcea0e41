"""``python -m benchmark.lint``: check ``BENCHMARK.json`` and every file it
names against the naming and shape rules of the benchmark's contract, in
seconds and without a chip. ``run.py`` refuses to start on a manifest that
fails. Exit status 0 = clean, 1 = faults (one per line on stdout)."""

from __future__ import annotations

import sys

from . import manifest


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    path = args[0] if args else manifest.MANIFEST
    faults = manifest.lint(path)
    for f in faults:
        print(f"benchmark.lint: {f}")
    if faults:
        print(f"benchmark.lint: {len(faults)} fault(s) in {path}")
        return 1
    m = manifest.load(path)
    print(
        f"benchmark.lint: OK — {len(m['workloads'])} workloads, "
        f"{len(m['configs'])} configs, {len(m['end_to_end'])} end-to-end and "
        f"{len(m['per_layer'])} per-layer metrics; every name, unit, layer, "
        "file and moves-target checks out"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
