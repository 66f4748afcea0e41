"""Builds, in a directory of its own, a checkout as a later PR that adds a
second learner BY FILES ALONE would leave it: every file of ``benchmark/``
as it stands (links), plus one configuration, one mix and one
``BENCHMARK.json`` that names them; ``twtml_tpu`` and ``native`` beside it
(links). ``python3 -m benchmark.run`` started there runs ``run.py``'s own
code path on the fixture; nothing is patched and nothing under
``benchmark/`` outside ``benchmark/tests/`` knows of it.

By hand, on the chip (``_scratch/`` is listed in ``.gitignore``):

    chiprun -- python3 -m benchmark.tests.fixture_tree _scratch/second \\
        --check-only --seed 3100000019 --more-seeds 3100000037,3100000061
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from benchmark import manifest

FIXTURES = os.path.join(manifest.HERE, "tests", "fixtures")
CELL, CONFIG, MIX = "logit2e18-trimmed-280-lex", "logit2e18", "trimmed-kept-280-lex"


def build(dst: str) -> str:
    """The tree under ``dst`` (made anew). Returns its absolute path."""
    dst = os.path.abspath(dst)
    shutil.rmtree(dst, ignore_errors=True)
    bench = os.path.join(dst, "benchmark")
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(bench, d))
    for name in ("twtml_tpu", "native"):
        os.symlink(os.path.join(manifest.ROOT, name), os.path.join(dst, name))
    for name in os.listdir(manifest.HERE):
        if name not in ("configs", "traffic", "__pycache__"):
            os.symlink(os.path.join(manifest.HERE, name),
                       os.path.join(bench, name))
    # the configuration: one new file
    shutil.copy(os.path.join(FIXTURES, CONFIG + ".json"),
                os.path.join(bench, "configs", CONFIG + ".json"))
    # the mix: trimmed-kept-280 as it stands, plus the lexicon object
    mix = manifest.load_json(manifest.traffic_path("trimmed-kept-280"))
    mix["generator"]["lexicon"] = manifest.load_json(
        os.path.join(FIXTURES, "lexicon.json"))["lexicon"]
    with open(os.path.join(bench, "traffic", MIX + ".json"), "w",
              encoding="utf-8") as fh:
        json.dump(mix, fh, indent=1)
    # the manifest: the real one's metrics, each reported by the one cell
    m = manifest.load()
    cfg = manifest.load_json(os.path.join(FIXTURES, CONFIG + ".json"))
    m["configs"] = [{
        "name": CONFIG, "source": cfg["source"],
        "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
        "why": "fixture: the logistic learner at hash2e18's width",
    }]
    m["workloads"] = [{
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": "fixture: 20-280 unit texts that carry lexicon words, batches "
               "of 2048 into 2^18 dims through the second entry point",
    }]
    one_chip = {w["name"] for w in manifest.load()["workloads"]
                if w["chips"] == 1}
    for key in ("end_to_end", "per_layer"):
        kept = [x for x in m[key]
                if "workloads" not in x or one_chip & set(x["workloads"])]
        for x in kept:
            if "workloads" in x:
                x["workloads"] = [CELL]
        m[key] = kept
    with open(os.path.join(dst, "BENCHMARK.json"), "w", encoding="utf-8") as fh:
        json.dump(m, fh, indent=1)
    return dst


def remove(tree: str) -> None:
    """Takes the tree away again (its links must not travel with a copy of
    the repo)."""
    shutil.rmtree(tree, ignore_errors=True)


def run(tree: str, argv: list, prelude: str = "", after: str = "",
        timeout: float = 1800.0, env=None, capture: bool = True):
    """``benchmark.run`` of the fixture cell, in a process of its own started
    in the tree (``prelude``: code run first, to break the program;
    ``after``: code run in that process once the run has ended)."""
    code = prelude + (
        "\nimport sys\nfrom benchmark import run\n"
        f"rc = run.main(['--workload', {CELL!r}, *{list(argv)!r}])\n"
    ) + after + "\nsys.exit(rc)\n"
    return subprocess.run([sys.executable, "-c", code], cwd=tree, env=env,
                          capture_output=capture, text=True, timeout=timeout)


# after a trial by hand: what the check run left on the device, and the Gram
# plane the last delivered batch took (the program's own gauge: 1 = bf16,
# 2 = s8, 0 = exact, -1 = no Gram basis)
TRIAL_REPORT = """
from benchmark import harness
from twtml_tpu.telemetry import metrics
harness.say("memory_peak_bytes: %d" % harness.memory_peak_bytes())
harness.say("model.gram_plane gauge: %r"
            % metrics.get_registry().gauge("model.gram_plane").snapshot())
"""


if __name__ == "__main__":
    sys.exit(run(build(sys.argv[1]), sys.argv[2:], after=TRIAL_REPORT,
                 capture=False).returncode)
