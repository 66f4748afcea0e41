"""``JAX_PLATFORMS=cpu python -m benchmark.rehearse``: the rehearsal
self-checks, by hand, before a chip call. Tiny sizes (``run.py --rehearse``,
a flag of the harness, not of the program); prints no device metric.

  1. the manifest lint;
  2. ``reduce_xplane`` on its small recorded trace;
  3. every cell end to end through ``run.py --rehearse``, traced and not:
     feeder -> trainer -> sink (a four-chip cell would run on the
     four-device virtual mesh), and in each the reference against the
     program on the check batches (``correct`` has to come out true). A
     rehearsal's window stays open until one pass of its tiny pool was
     published (``drivers/train.Window``), so ``--seconds 2`` serves every
     cell, however slow a batch is on the CPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from . import manifest, reduce_xplane


def main() -> int:
    faults = manifest.lint()
    for f in faults:
        print(f"benchmark.lint: {f}")
    if faults:
        return 1
    print("rehearse: lint OK")
    reduce_xplane.selfcheck()
    print("rehearse: reduce_xplane reproduces its recorded trace")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bad = 0
    for w in manifest.load()["workloads"]:
        for trace in (0, 1):
            p = subprocess.run(
                [sys.executable, "-m", "benchmark.run", "--workload",
                 w["name"], "--seed", str(2147483659 + trace), "--seconds",
                 "2", "--trace", str(trace), "--rehearse"],
                cwd=manifest.ROOT, env=env, capture_output=True, text=True,
            )
            last = (p.stdout.strip().splitlines() or ["{}"])[-1]
            ok = p.returncode == 0 and json.loads(last).get("correct") is True
            print(f"rehearse: {w['name']} --trace {trace}: "
                  f"{'OK' if ok else 'FAILED'} {last}")
            if not ok:
                bad += 1
                print(p.stdout[-3000:], p.stderr[-3000:], sep="\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
