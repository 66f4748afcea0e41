"""Elastic process-group runtime: form, shrink, and re-grow a
jax.distributed gloo group IN-PROCESS (``--elastic on``).

The reference inherited Spark 1.6.1's executor-loss recovery for free
(SURVEY §1): a lost executor degraded capacity, it never killed the job.
Our lockstep fleet had the opposite failure mode — every peer loss funneled
into ``ssc.request_abort`` — because ``jax.distributed`` has no membership
concept: the group is its launch topology forever, and its coordination
service's default reaction to a dead task is to TERMINATE every survivor
(``client.h:80`` LOG(FATAL) on the error broadcast, measured in this PR's
probe runs — doc/elastic_probe_notes.md records every observed failure
mode these deviations dodge).

This module makes the group a sequence of EPOCHS instead. Per epoch it owns
the jaxlib distributed client/service directly (not
``jax.distributed.initialize``) with four deliberate deviations, each
forced by a measured failure mode of the stock lifecycle:

- **dead-task detection is disabled at the transport** (service
  ``max_missing_heartbeats`` effectively infinite): the stock service
  broadcasts an error when a task misses heartbeats, and the pybind caster
  for a Python ``missed_heartbeat_callback`` in this jaxlib throws
  ``std::bad_cast`` (process abort) — so the APP-level lockstep watchdog
  (``TWTML_LOCKSTEP_TIMEOUT_S``) is the one death detector, exactly the
  seam the repo already trusts;
- **no shutdown barrier, ever, mid-run** (``shutdown_on_destruction=False``
  + ``abandon()`` instead of ``client.shutdown()``): the stock shutdown
  barrier with a dead peer LOG(FATAL)s the survivor. Abandoned epoch
  objects go to a process-lifetime graveyard — a few leaked threads and one
  bound port per epoch, bounded by churn count;
- **hard exit once any epoch was abandoned** (``finalize_exit``): an
  abandoned client's leaked error-poll thread LOG(FATAL)s the process the
  moment its service's socket closes during interpreter teardown (probe 4),
  so an elastic process must leave via ``os._exit`` after flushing — the
  same discipline tests/distributed_worker.py's peer_kill mode already
  uses for exactly this reason;
- **the coordination service never shares a process with a member**
  (r20, ``parallel/service_host.py``): a member-hosted service socket
  closes with its host, and every LIVE client's error-poll thread answers
  ``Socket closed`` with the same ``client.h:80`` LOG(FATAL) within
  milliseconds — faster than any watchdog, which made the service owner
  the fleet's last single point of failure (probe 5). Each epoch's pid-0
  member SPAWNS the service as a detached jaxlib-only subprocess that
  outlives every member and self-reaps once the membership beacon has
  been gone past the linger window (``TWTML_ELASTIC_SERVICE_LINGER_S``).

Epoch e's coordinator listens on ``base_port + 2 + e`` (base_port is the
``--master twtml://host:port`` port; +1 is the membership beacon); every
member derives it locally, so re-formation needs no negotiation beyond the
agreed epoch number and member set. Backend re-creation clears the
xla_bridge backend table AND its lru-cached topology readers
(``process_count``/``local_devices``) — stale caches were the first probe's
silent wrong-world bug.

The **beacon** is the lead's out-of-band membership channel: a tiny
host-side JSON-over-TCP listener (NOT a collective — the per-tick law is
untouched) used only when the in-band flag row cannot work: wedge reports
after a peer death (the dead peer can never ack in-band), join requests
from parked/restarted hosts, and plan polling while a host is outside the
group. Healthy ticks never touch it.

**Lead election (r20, ISSUE 17)**: the lead is no longer special. The
beacon PORT is the election lock — exactly one process can bind
``base + 1``, and the OS arbitrates the race atomically. A dead lead's
socket closes with it (``os._exit`` releases the fd), so survivors whose
wedge reports hit connection-refused know the beacon is ORPHANED (a
merely-paused lead's beacon thread still answers — pause never triggers
an election) and run the successor rule: candidates rank by uid in the
committed view, each waits rank × stagger while probing, then tries the
bind — so the lowest LIVE uid wins deterministically and every loser
observes the winner's beacon instead. The winner adopts ``lead_uid``,
publishes the rescue plan, and restores fleet state from its own
verified checkpoint (every elastic host shadow-saves — the
any-host-can-restore discipline, apps/common.AppCheckpoint). Because
the successor is the lowest live uid, it is also pid 0 of the epoch it
forms — service spawner, broadcast authority, and beacon owner stay one
host by construction (the service itself runs fate-isolated in its own
subprocess, so no lead's death ever closes a live epoch's socket). Leadership is STICKY thereafter: a rejoining
ex-lead is admitted as a follower (demotion is just "your uid is no
longer the elected lead's"), so ``lead_uid`` only moves at elections.

Reachability note: election assumes the beacon/coordinator ``host:port``
space stays bindable wherever a lead lands — true for the virtual
(single-machine) fleets the proof harness runs, or for real fleets
fronted by a shared address (VIP/DNS). A lead pinned to one machine's
address keeps the PR 13 behavior: its death is unrecoverable.
"""

from __future__ import annotations

import gc
import json
import os
import socket
import threading
import time

from ..utils import get_logger

log = get_logger("parallel.elastic")

# transport-level heartbeat detection is DISABLED (the app watchdog owns
# death detection): a member is only declared dead by the coordination
# service after this many seconds without a heartbeat
_HEARTBEAT_TIMEOUT_S = 10_000_000

# beacon request cap: one JSON line per connection, bounded
_BEACON_MAX_BYTES = 65536

BEACON_OFFSET = 1      # beacon port = base + 1
EPOCH_PORT_OFFSET = 2  # epoch e coordinator port = base + 2 + e

INIT_TIMEOUT_ENV = "TWTML_ELASTIC_INIT_TIMEOUT_S"
INIT_TIMEOUT_DEFAULT_S = 60.0


def _init_timeout_s() -> int:
    return int(float(
        os.environ.get(INIT_TIMEOUT_ENV, "") or INIT_TIMEOUT_DEFAULT_S
    ))


def uids_from_mask(mask: int) -> "list[int]":
    """Member uids encoded in a view bitmask, ascending (uid = bit index).
    Uids are the ORIGINAL launch process ids — stable across epochs, which
    is what makes the mask meaningful on every host."""
    out = []
    bit = 0
    m = int(mask)
    while m:
        if m & 1:
            out.append(bit)
        m >>= 1
        bit += 1
    return out


def mask_from_uids(uids) -> int:
    mask = 0
    for u in uids:
        if not 0 <= int(u) < 52:
            # the mask rides a float64 flag column; int-exactness ends at
            # 2^53, so 52 hosts is the hard fleet ceiling of this encoding
            raise ValueError(f"elastic member uid {u} out of range [0, 52)")
        mask |= 1 << int(u)
    return mask


class BeaconServer:
    """The lead's membership side-channel: JSON-over-TCP, one request per
    connection, answered from a lock-protected state dict the membership
    plane updates. Runs on a daemon thread; never touches jax."""

    def __init__(self, port: int, lead_uid: int = 0):
        self.port = port
        self._lock = threading.Lock()
        self._state: dict = {
            "state": "forming", "epoch": 0, "members": [], "plan": None,
            "lead_uid": int(lead_uid),
        }
        self._joins: "dict[int, float]" = {}     # uid -> monotonic seen
        self._wedged: "dict[int, int]" = {}      # uid -> epoch reported
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("0.0.0.0", port))
        self._sock.listen(16)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._serve, name="twtml-elastic-beacon", daemon=True
        )
        self._thread.start()
        log.info("elastic membership beacon listening on :%d", port)

    # -- state the membership plane publishes --------------------------------

    def publish(self, state: str, epoch: int, members: "list[int]") -> None:
        with self._lock:
            self._state["state"] = state
            self._state["epoch"] = int(epoch)
            self._state["members"] = [int(u) for u in members]

    def publish_plan(self, plan: "dict | None") -> None:
        """The committed next-epoch plan ({epoch, members}) parked/wedged
        hosts poll for; None clears it once the epoch is live. Plans carry
        the owner's ``lead_uid`` so followers that resolve a plan through a
        HANDED-OFF beacon adopt the elected lead in the same poll."""
        with self._lock:
            if plan is not None:
                plan = dict(plan)
                plan.setdefault("lead_uid", self._state["lead_uid"])
            self._state["plan"] = plan

    def fresh_joins(self, max_age_s: float) -> "list[int]":
        """Uids with a join request newer than ``max_age_s`` — admission
        only proposes FRESH joiners (they re-send per poll), because the
        new epoch's formation blocks until every admitted member connects
        and a no-show joiner would wedge it."""
        now = time.monotonic()
        with self._lock:
            return sorted(
                u for u, t in self._joins.items() if now - t <= max_age_s
            )

    def wedge_reports(self, epoch: int) -> "list[int]":
        """Uids that reported a wedged collective at ``epoch``."""
        with self._lock:
            return sorted(
                u for u, e in self._wedged.items() if e == int(epoch)
            )

    def clear_wedges(self) -> None:
        with self._lock:
            self._wedged.clear()

    def close(self) -> None:
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass

    # -- wire ----------------------------------------------------------------

    def _serve(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _addr = self._sock.accept()
            except OSError:
                return  # closed
            try:
                conn.settimeout(2.0)
                data = b""
                while b"\n" not in data and len(data) < _BEACON_MAX_BYTES:
                    chunk = conn.recv(4096)
                    if not chunk:
                        break
                    data += chunk
                req = json.loads(data.decode("utf-8").strip() or "{}")
                resp = self._handle(req)
                conn.sendall((json.dumps(resp) + "\n").encode("utf-8"))
            except Exception:
                log.debug("beacon request failed", exc_info=True)
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def _handle(self, req: dict) -> dict:
        op = req.get("op", "")
        uid = int(req.get("uid", -1))
        with self._lock:
            st = dict(self._state)
            if op == "hello":
                return {
                    "state": st["state"], "epoch": st["epoch"],
                    "members": st["members"],
                    "member": uid in st["members"],
                    "plan": st["plan"], "lead_uid": st["lead_uid"],
                }
            if op == "join":
                self._joins[uid] = time.monotonic()
                return {
                    "queued": True, "epoch": st["epoch"],
                    "lead_uid": st["lead_uid"],
                }
            if op == "wedged":
                self._wedged[uid] = int(req.get("epoch", -1))
                return {"ok": True, "plan": st["plan"],
                        "lead_uid": st["lead_uid"]}
            if op == "plan":
                return {"plan": st["plan"], "epoch": st["epoch"],
                        "lead_uid": st["lead_uid"]}
        return {"error": f"unknown op {op!r}"}


class BeaconClient:
    """Follower/joiner side of the beacon: short-lived connections, every
    failure surfaced as None (the caller owns retry/abort policy)."""

    def __init__(self, host: str, port: int, timeout_s: float = 3.0):
        self.host = host
        self.port = port
        self.timeout_s = timeout_s

    def request(self, op: str, uid: int, **kw) -> "dict | None":
        try:
            with socket.create_connection(
                (self.host, self.port), timeout=self.timeout_s
            ) as conn:
                payload = dict(op=op, uid=uid, **kw)
                conn.sendall((json.dumps(payload) + "\n").encode("utf-8"))
                conn.settimeout(self.timeout_s)
                data = b""
                while b"\n" not in data and len(data) < _BEACON_MAX_BYTES:
                    chunk = conn.recv(4096)
                    if not chunk:
                        break
                    data += chunk
                return json.loads(data.decode("utf-8").strip() or "null")
        except (OSError, ValueError) as exc:
            log.debug("beacon %s failed: %s", op, exc)
            return None


def probe_port(host: str, port: int, timeout_s: float = 0.5) -> bool:
    """Plain TCP reachability probe. A joiner MUST probe before
    ``client.connect()``: a connect whose coordinator never comes up dies
    by LOG(FATAL) (DEADLINE_EXCEEDED on RegisterTask), not by exception —
    measured in this PR's probes."""
    try:
        with socket.create_connection((host, port), timeout=timeout_s):
            return True
    except OSError:
        return False


class ElasticRuntime:
    """Owns the per-epoch jax.distributed lifecycle for one process.

    ``uid`` is this host's ORIGINAL process id (stable across epochs).
    ``lead_uid`` is the CURRENT lead's uid — uid 0 at launch, then sticky
    across epochs until an election moves it (module docstring). A
    restarted ex-lead finds the beacon port taken by its successor, keeps
    ``beacon=None``, and rejoins through the follower parking path —
    demotion is just losing the bind."""

    def __init__(self, host: str, base_port: int, uid: int):
        self.host = host
        self.base_port = int(base_port)
        self.uid = int(uid)
        self.epoch = -1
        self.members: "list[int]" = []
        self.reformed_ever = False
        # True when this process joined a LIVE run (a restarted host
        # admitted mid-flight): replay-index intake shards must then park
        # as standby (apps/common._rebalance_intake's rejoin rule applies
        # from the first batch, not only at later reforms)
        self.joined_late = False
        # abandoned epochs' client/service objects: destructing them risks
        # the error-poll LOG(FATAL) (see module docstring) — they leak for
        # the process lifetime, and finalize_exit skips teardown entirely
        self._graveyard: list = []
        # fate-isolated coordination-service subprocesses this host
        # spawned (parallel/service_host.py) — kept only for diagnostics;
        # they self-reap off the beacon's liveness, never via this list
        self._service_hosts: list = []
        self.lead_uid = 0
        self.beacon: "BeaconServer | None" = None
        if self.uid == 0:
            # launch-lead bind is a TRY: a restarted ex-lead races the
            # elected successor for this port and must lose gracefully
            # (beacon stays None → _init_elastic routes it through the
            # follower hello/park path and it adopts the winner's lead_uid)
            try:
                self.beacon = BeaconServer(self.beacon_port, lead_uid=0)
            except OSError:
                log.warning(
                    "beacon port :%d already owned — uid 0 restarting into "
                    "a fleet led by an elected successor; joining as a "
                    "follower", self.beacon_port,
                )

    @property
    def is_lead(self) -> bool:
        return self.uid == self.lead_uid

    def set_lead(self, uid: int) -> None:
        """Adopt ``uid`` as the current lead (from a beacon hello/plan, or
        self after winning an election)."""
        self.lead_uid = int(uid)

    def take_over_beacon(self) -> bool:
        """Attempt the election bind race: bind the beacon port and become
        the lead. EXACTLY ONE caller can win (the OS arbitrates the bind);
        a loser returns False and must re-resolve through the winner's
        beacon. Winner adopts its own uid as ``lead_uid``."""
        if self.beacon is not None:
            return True
        try:
            self.beacon = BeaconServer(self.beacon_port, lead_uid=self.uid)
        except OSError as exc:
            log.info(
                "beacon takeover lost (:%d already bound: %s) — another "
                "survivor won the election", self.beacon_port, exc,
            )
            return False
        self.lead_uid = self.uid
        return True

    # -- address arithmetic --------------------------------------------------

    @property
    def beacon_port(self) -> int:
        return self.base_port + BEACON_OFFSET

    def port_for(self, epoch: int) -> int:
        return self.base_port + EPOCH_PORT_OFFSET + int(epoch)

    def beacon_client(self) -> BeaconClient:
        return BeaconClient(self.host, self.beacon_port)

    @property
    def pid(self) -> int:
        """This host's dense jax process id in the CURRENT epoch (index of
        its uid in the sorted member list)."""
        return self.members.index(self.uid)

    # -- epoch lifecycle -----------------------------------------------------

    def _spawn_service_host(self, port: int, nprocs: int) -> None:
        """Launch epoch ``port``'s coordination service in a FATE-ISOLATED
        subprocess (parallel/service_host.py): the service socket must
        survive any member's death — including this spawner's — or every
        survivor's client error-poll thread LOG(FATAL)s the instant it
        closes (probe 5, doc/elastic_probe_notes.md). Detached session,
        all stdio on /dev/null: the host must not hold a pipe a test
        harness waits on. It self-reaps once the beacon has been gone for
        the linger window (the run is over)."""
        import subprocess
        import sys

        proc = subprocess.Popen(
            [sys.executable, "-m", "twtml_tpu.parallel.service_host",
             str(port), str(nprocs), self.host, str(self.beacon_port)],
            stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL, start_new_session=True,
        )
        self._service_hosts.append(proc)
        log.info(
            "elastic coordination service for :%d hosted fate-isolated "
            "(pid %d, %d task(s))", port, proc.pid, nprocs,
        )

    def form(self, epoch: int, members: "list[int]") -> None:
        """Join epoch ``epoch`` with the given member uids (sorted; this
        host must be one of them). Spawns the epoch's fate-isolated
        coordination service from the pid-0 slot, creates a
        detection-disabled client everywhere, and leaves the xla_bridge
        caches cleared so the next jax call builds the new world's
        backend."""
        from jax._src import distributed as _dist
        from jaxlib import _jax

        members = sorted(int(u) for u in members)
        if self.uid not in members:
            raise ValueError(
                f"host uid {self.uid} is not in epoch {epoch}'s member set "
                f"{members}"
            )
        pid = members.index(self.uid)
        nprocs = len(members)
        port = self.port_for(epoch)
        coordinator = f"{self.host}:{port}"
        state = _dist.global_state
        if pid == 0:
            self._spawn_service_host(port, nprocs)
        client = _jax.get_distributed_runtime_client(
            coordinator, pid,
            init_timeout=_init_timeout_s(),
            shutdown_timeout=5,
            heartbeat_timeout=_HEARTBEAT_TIMEOUT_S,
            shutdown_on_destruction=False,
            use_compression=True,
        )
        client.connect()
        state.client = client
        state.process_id = pid
        state.num_processes = nprocs
        state.coordinator_address = coordinator
        self.epoch = int(epoch)
        self.members = members
        if self.beacon is not None:
            self.beacon.publish("live", self.epoch, members)
        log.info(
            "elastic epoch %d formed: %d host(s) %s, this host uid=%d "
            "pid=%d, coordinator %s",
            epoch, nprocs, members, self.uid, pid, coordinator,
        )

    def abandon(self) -> None:
        """Leave the current epoch WITHOUT the shutdown barrier (the
        barrier with a dead/absent peer LOG(FATAL)s — module docstring).
        The epoch's client/service objects are kept alive in the graveyard
        forever; jax's backend table and cached topology readers are
        cleared so the next epoch builds a fresh gloo world."""
        import jax
        from jax._src import distributed as _dist
        from jax._src import xla_bridge

        state = _dist.global_state
        if state.client is not None or state.service is not None:
            self._graveyard.append((state.client, state.service))
        state.client = None
        state.service = None
        state.process_id = 0
        state.num_processes = 1
        state.coordinator_address = None
        self.reformed_ever = True
        jax.clear_caches()
        xla_bridge.process_count.cache_clear()
        xla_bridge.local_devices.cache_clear()
        xla_bridge._clear_backends()
        gc.collect()
        log.info(
            "elastic epoch %d abandoned (graveyard now %d epoch(s))",
            self.epoch, len(self._graveyard),
        )

    def finalize_exit(self, code: int) -> None:
        """Leave the process via ``os._exit`` after flushing std streams —
        MANDATORY after any ``abandon()``: interpreter teardown would
        destruct graveyard services under live leaked poll threads, which
        LOG(FATAL)s (observed SIGABRT in probe 4). No-op-ish when nothing
        was ever abandoned is still fine: elastic runs always exit here so
        the exit path does not depend on churn history."""
        import sys

        for stream in (sys.stdout, sys.stderr):
            try:
                stream.flush()
            except Exception:
                log.debug("stream flush failed at elastic exit")
        os._exit(int(code))


# process-wide runtime: formation happens before the app builds anything,
# and the lockstep loop + app teardown both need the same instance
_RUNTIME: "ElasticRuntime | None" = None


def install_runtime(host: str, base_port: int, uid: int) -> ElasticRuntime:
    global _RUNTIME
    if _RUNTIME is not None:
        raise RuntimeError("elastic runtime already installed")
    _RUNTIME = ElasticRuntime(host, base_port, uid)
    return _RUNTIME


def get_runtime() -> "ElasticRuntime | None":
    return _RUNTIME


def reset_for_tests() -> None:
    global _RUNTIME
    if _RUNTIME is not None and _RUNTIME.beacon is not None:
        _RUNTIME.beacon.close()
    _RUNTIME = None
