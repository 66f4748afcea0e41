"""Backend/platform selection helpers.

One place for the "force an n-device virtual CPU mesh" dance used by the
driver entry (``__graft_entry__.dryrun_multichip``) and the CLI backend
selector (``apps.common.select_backend``): both need to set
``jax_num_cpu_devices`` *before* any backend initialization and degrade
gracefully when one is already live. tests/conftest.py deliberately does not
import this (it must configure jax before the repo is even on sys.path), but
follows the same recipe.

Also the one place that says where compiled programs are cached
(``configure_compile_cache``) and which devices a run computes on
(``run_devices`` / ``device_identity``) — every entry point, the bench
children and ``chip_smoke.py`` share both through ``select_backend``.
"""

from __future__ import annotations

import os

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, in-checkout, never a temp name / pid / time: the directory is part
# of how a later process finds the entries, so a path that moves never hits
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
# bound for the in-checkout default only (jax evicts least-recently-used
# entries past it): the ragged wire's bucketed shapes keep minting programs
# in a long-lived app, and an unbounded directory inside a checkout is a
# slow leak. A cache the operator placed is the operator's to size.
_COMPILE_CACHE_MAX_BYTES = 1 << 30


def backends_initialized() -> bool:
    """Whether a backend is initialized in this process (after which
    device-count configs can no longer change)."""
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere a LATER process
    finds again, and return that directory. Where ``JAX_COMPILATION_CACHE_DIR``
    is set the operator placed the cache (jax reads the variable itself) and
    nothing is set in code; otherwise the one fixed in-checkout directory,
    size-bounded. Either way the cache outlives the process only where the
    directory does: a machine that starts from a fresh copy of the tree
    (every chip tool call) starts cold unless the variable points at
    storage that machine keeps."""
    placed = os.environ.get(COMPILE_CACHE_ENV, "")
    if placed:
        return placed
    import jax

    if jax.config.jax_compilation_cache_dir != _COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE_DIR)
        jax.config.update(
            "jax_compilation_cache_max_size", _COMPILE_CACHE_MAX_BYTES
        )
    return _COMPILE_CACHE_DIR


def run_devices() -> list:
    """The devices this process computes on: every device of the platform
    its un-placed arrays and programs land on. Honors ``jax_default_device``
    (how ``chip_smoke.py`` runs its CPU reference inside a process that
    holds the chip), so a mesh built from this list and the identity in the
    run record can never name different platforms. Initializes the backend."""
    import jax

    dev = jax.config.jax_default_device
    if isinstance(dev, str):
        return jax.devices(dev)
    if dev is None:
        return jax.devices()
    return jax.devices(dev.platform)


def device_identity() -> dict:
    """``run_devices()`` as jax reports it — what every run record and
    benchmark line names so a CPU run can never be read as a device
    result."""
    devices = run_devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def _set_cpu_device_count(n_devices: int) -> bool:
    """``jax_num_cpu_devices``; False when a live backend with a different
    CPU device count makes the change impossible."""
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
        return True
    except RuntimeError:
        return False


def force_virtual_cpu_devices(n_devices: int) -> bool:
    """Switch jax to an ``n_devices``-device virtual CPU backend.

    The virtual CPU mesh compiles and executes the same
    Mesh/shard_map/psum program structure the TPU path uses, which is how
    multi-chip sharding is validated on hosts without n real chips.

    Returns True when the configuration was applied; False when a backend was
    already initialized (the config is then left untouched and the caller
    should use whatever devices exist).
    """
    import jax

    if backends_initialized():
        return False
    if not _set_cpu_device_count(n_devices):
        return False
    jax.config.update("jax_platforms", "cpu")
    return True


def set_cpu_device_count_hint(n_devices: int) -> bool:
    """Set the CPU device count without forcing the platform (the local[N]
    hint: only affects runs where the CPU backend wins platform selection).
    Returns False if a backend is already initialized, leaving it untouched."""
    if backends_initialized():
        return False
    return _set_cpu_device_count(n_devices)
