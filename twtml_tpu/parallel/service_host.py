"""Fate-isolated coordination-service host (r20, ISSUE 17).

The epoch coordination service used to live INSIDE the pid-0 member's
process. That made whoever hosted it the fleet's last single point of
failure at the TRANSPORT level: when that process hard-died, its service
socket closed and every surviving member's client error-poll thread
(``PollForError``) reacted with the ``client.h:80`` LOG(FATAL) — SIGABRT
within milliseconds, long before the app-level lockstep watchdog could
run the election (measured; doc/elastic_probe_notes.md probe 5). With
heartbeat detection disabled, the service's SOCKET is the only thing a
live client's poll thread can trip on — so the fix is fate isolation:
the service runs in this tiny standalone process, spawned by the epoch's
pid-0 member (``ElasticRuntime.form``), and survives any member's death.

Lifetime: the fleet's members cannot reap this process (its whole point
is outliving them), so it watches the membership BEACON port instead —
the one address that stays bound across elections (the winner re-binds
it within seconds of a lead death). Once the beacon has been unreachable
for ``linger_s`` straight (default ``TWTML_ELASTIC_SERVICE_LINGER_S``,
45 s — well past a worst-case election + rescue), the run is over and
this process exits. It must NOT exit sooner: abandoned epochs' clients
keep leaked poll threads pointed here (probe 4), and closing the socket
under them would FATAL every still-running member.

Only ``jaxlib`` is imported (no ``jax``, no backend init): the service
is pure coordination, it owns no devices.

Usage: python -m twtml_tpu.parallel.service_host <port> <nprocs> \
           <beacon_host> <beacon_port> [linger_s]
"""

from __future__ import annotations

import os
import socket
import sys
import time

# mirrors parallel/elastic.py: detection stays OFF — the app-level
# lockstep watchdog is the one death detector
_HEARTBEAT_TIMEOUT_S = 10_000_000

LINGER_ENV = "TWTML_ELASTIC_SERVICE_LINGER_S"
LINGER_DEFAULT_S = 45.0


def _beacon_up(host: str, port: int) -> bool:
    try:
        with socket.create_connection((host, port), timeout=0.5):
            return True
    except OSError:
        return False


def main(argv: "list[str] | None" = None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    port, nprocs = int(args[0]), int(args[1])
    beacon_host, beacon_port = args[2], int(args[3])
    linger_s = float(args[4]) if len(args) > 4 else float(
        os.environ.get(LINGER_ENV, "") or LINGER_DEFAULT_S
    )
    from jaxlib import _jax  # jaxlib only: no jax, no backend

    service = _jax.get_distributed_runtime_service(
        f"[::]:{port}", nprocs,
        heartbeat_timeout=_HEARTBEAT_TIMEOUT_S,
    )
    last_ok = time.monotonic()
    while True:
        time.sleep(2.0)
        if _beacon_up(beacon_host, beacon_port):
            last_ok = time.monotonic()
        elif time.monotonic() - last_ok > linger_s:
            break
    del service  # nothing polls a finished fleet; plain teardown is safe
    os._exit(0)


if __name__ == "__main__":
    main()
