"""Test harness config: force an 8-device virtual CPU mesh before any JAX use.

Multi-chip TPU hardware is not available in CI; sharding/collective tests run
against an 8-device virtual CPU backend, which exercises the same
Mesh/shard_map/psum program structure the TPU path compiles. The switch
happens via jax.config (tier-1 also runs under ``JAX_PLATFORMS=cpu``) —
legal as long as no backend has been initialized yet.
"""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Make the repo importable without installation (no-network image: pip install
# of the package is not possible, tests import straight from the source tree).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import pytest  # noqa: E402


@pytest.fixture()
def clean_properties():
    """Snapshot/restore the process property table around a test."""
    from twtml_tpu import config

    saved = dict(config._SYSTEM_PROPERTIES)
    yield config._SYSTEM_PROPERTIES
    config._SYSTEM_PROPERTIES.clear()
    config._SYSTEM_PROPERTIES.update(saved)


@pytest.fixture(autouse=True)
def fresh_model_watch():
    """The model watcher is process-wide (one process = one run in
    production) and an app run never resets it: inside one xdist worker a
    test's drift baseline was whatever stream the PREVIOUS test's app run
    fed it — a logistic run before a linear one reads as drift, the stamped
    checkpoints say ``alert`` and a promoter refuses them
    (tests/test_fleet.py failed exactly so). Every test starts and ends
    with no watcher."""
    from twtml_tpu.telemetry import modelwatch

    modelwatch.reset_for_tests()
    yield
    modelwatch.reset_for_tests()
