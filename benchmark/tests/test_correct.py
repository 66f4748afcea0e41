"""Tests of the comparison that decides ``correct``, at sizes a test run can
hold (CPU, rehearsal sizes). Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

1. the CONTROL (the reference in bfloat16, in the program's place) comes out
   not correct, on three seeds, in every cell;
2. the rest of a run, driven with the harness's look for a chip skipped
   (``--rehearse``) and the timed path BROKEN underneath — a train step that
   returns its state unchanged — sees ``correct`` come out false;
   unbroken, true.
"""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark import control, manifest, run

ROOT = manifest.ROOT
CELLS = [w["name"] for w in manifest.load()["workloads"]]

# every class whose ``step`` a training cell's timed path runs: the
# single-device model (and its subclasses: the logistic learner's step is
# this one), the mesh model (``test_hash2e20.py``'s BREAK_MESH is this
# patch for that class alone) and, since PR 46, the tenant plane's stack
# (``test_hash2e18_ab4.py``'s BREAK_TENANT_STEP is this patch for that class
# alone): the two tenant cells enter neither of the other two
BREAK_TRAIN = """
import jax
from twtml_tpu.models import sgd
from twtml_tpu.parallel import sharding, tenants
def broken(cls):
    _step = cls.step
    def step(self, batch):
        w = jax.tree_util.tree_map(lambda a: a + 0, self._weights)  # donated below
        out = _step(self, batch)
        self._weights = w          # the state comes back unchanged
        return out
    cls.step = step
broken(sgd.StreamingSGDModel)
broken(sharding.ParallelSGDModel)
broken(tenants.TenantStackModel)
"""


def _cell(name):
    cell = manifest.cell(manifest.load(), name)
    run.shrink_for_rehearsal(cell)
    return cell


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, seed):
    args = argparse.Namespace(seed=seed, control="bf16")
    assert control.run(_cell(name), args)["correct"] is False


def _drive(name, patch):
    code = patch + (
        "\nimport sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', {name!r}, '--seed', '2147483659', "
        "'--seconds', '2', '--trace', '0', '--rehearse']))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("patch,want", [("", True), (BREAK_TRAIN, False)])
def test_broken_path_is_seen(name, patch, want):
    assert _drive(name, patch)["correct"] is want
