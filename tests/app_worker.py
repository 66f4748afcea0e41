"""Launcher for multi-host APP-LEVEL integration tests: configures a CPU/gloo
jax runtime, then drives a REAL entry-point main() with its own CLI — the
reference's one-flag cluster story exercised end to end
(``--coordinator host:port --numProcesses N --processId I``,
apps/common.init_distributed).

Not a test module — spawned by tests/test_distributed_multiprocess.py.

Usage: python tests/app_worker.py <process_id> <num_processes> <port> \
           <devices_per_process> <app> [app args...]

``num_processes == 1`` runs the same main single-host (no coordinator
flags) — the ground-truth run the multi-host stats must match.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

pid, nprocs, port, ndev = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
)
app_name, app_args = sys.argv[5], list(sys.argv[6:])

jax.config.update("jax_platforms", "cpu")
if nprocs > 1:
    # only request gloo when this worker actually joins a group
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
from twtml_tpu.utils.backend import set_cpu_device_count_hint  # noqa: E402

set_cpu_device_count_hint(ndev)

if nprocs > 1:
    app_args += [
        "--master", f"twtml://127.0.0.1:{port}",  # the cluster master URL
        "--numProcesses", str(nprocs),
        "--processId", str(pid),
    ]

from twtml_tpu.apps import (  # noqa: E402
    kmeans,
    linear_regression,
    logistic_regression,
)

{
    "linear": linear_regression,
    "logistic": logistic_regression,
    "kmeans": kmeans,
}[app_name].main(app_args)
