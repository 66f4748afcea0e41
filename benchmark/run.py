"""``python3 -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, in a new process that owns the chip.

Lints the manifest (a faulty one is refused before anything starts), finds
the cell's configuration, traffic mix and driver kind by name, and hands
over to ``benchmark/drivers/<kind>.py``. The last line of stdout is the
result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
when traced, ``breakdown``. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.

Flags of the HARNESS, for work outside the driver's checks:
  --rehearse      tiny sizes on whatever jax finds (JAX_PLATFORMS=cpu): the
                  whole loop end to end; prints no metric
  --check-only    set-up, check run and comparison, no window, for --seed
                  and each of --more-seeds a,b,c: the sound runs' readings
                  that limits are set from (training cells)
  --control bf16  put the reference in the lower precision in the program's
                  place: must come out not correct
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # before any import that costs time

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REHEARSAL_ROWS = 256
REHEARSAL_BATCHES = 16


def shrink_for_rehearsal(cell: dict) -> None:
    """Tiny sizes for a CPU rehearsal: batches of 256 rows, a pool of 16
    batches, a second of warm-up. Widths stay."""
    cfg, traffic = cell["config"], cell["traffic"]
    flags = list(cfg["flags"])
    flags[flags.index("--batchBucket") + 1] = str(REHEARSAL_ROWS)
    cfg["flags"], cfg["batch_rows"] = flags, REHEARSAL_ROWS
    g = traffic["generator"]
    g["pool_lines"] = int(REHEARSAL_ROWS * REHEARSAL_BATCHES
                          / g.get("keep_share", 1.0))
    traffic["warmup"]["min_seconds"] = 1.0
    traffic["profile_seconds"] = 0.5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--check-only", action="store_true")
    ap.add_argument("--more-seeds", default=[],
                    type=lambda s: [int(x) for x in s.split(",") if x])
    ap.add_argument("--control", choices=("bf16",), default="")
    args = ap.parse_args(argv)

    from . import manifest

    faults = manifest.lint()
    if faults:
        for f in faults:
            print(f"benchmark.lint: {f}", file=sys.stderr)
        print("benchmark.run: BENCHMARK.json fails its lint; not starting",
              file=sys.stderr)
        return 2
    cell = manifest.cell(manifest.load(), args.workload)
    if args.rehearse:
        shrink_for_rehearsal(cell)
        if cell["workload"]["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count="
                f"{cell['workload']['chips']}"
            )
    if args.control:
        from . import control

        result = control.run(cell, args)
    else:
        driver = manifest.load_module(
            manifest.driver_path(cell["traffic"]["kind"]))
        result = driver.run(cell, args, T_START)
    # each number compared, beside its limit: the last lines of stderr, and
    # the last key of the result's line
    numbers = result.pop("numbers", None)
    if numbers is not None:
        for name, n in numbers.items():
            print(f"correct: {name} = {n['value']:.6g} (limit {n['limit']:.6g})",
                  file=sys.stderr)
        sys.stderr.flush()
        result["numbers"] = numbers
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
