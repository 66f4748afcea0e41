"""Fault injection for stream sources AND the transport below them
(SURVEY.md §5.3: the reference has no fault injection anywhere; receiver
recovery was whatever Spark defaulted to).

``FaultInjectingSource`` wraps any Source and raises a simulated receiver
crash every ``crash_every`` tweets (deterministic) or with probability
``crash_prob`` per tweet (seeded) — exercising the supervision/restart/backoff
harness end-to-end in tests and chaos runs. Emitted tweets are passed through
unchanged; a crash loses the in-flight iterator exactly like a dropped
socket, so delivery gaps behave like the real failure mode.

``ChaosInjector`` (``--chaos SPEC``) extends the same idea BELOW the source
layer, to the external dependencies that are the real failure domain (a
host fetch that stalls or is lost, a dispatch that raises, a dashboard that
hangs): seeded latency spikes / multi-second stalls / exceptions at
three injection points —

- ``fetch``  — the pooled ``device_get``s (FetchPipeline),
- ``step``   — the device dispatch (``model.step``),
- ``web``    — every dashboard HTTP request (``WebClient._request``),

so the runtime guards those points carry (fetch deadline/retry/abort, the
publish circuit breaker, the lockstep watchdogs) are testable end-to-end.

r7 adds SOURCE/PARSE chaos — the untrusted-data failure domain the ingest
guards exist for (bounded backpressure, the divergence sentinel, verified
checkpoints):

- ``source.garbage`` — corrupt (truncate + garble) a block source's raw
  byte buffer before the parser sees it: the parser must skip, count, and
  never crash (one corrupted chunk can also bleed into the next via the
  carry, exactly like real wire damage),
- ``source.burst``  — re-emit the current item N extra times (a rate
  spike), exercising the bounded intake queue's block/shed policies,
- ``source.nan``    — poison every valid label of the current featurized
  batch with NaN: the model diverges in one step, exercising the
  divergence sentinel's rollback-to-verified-checkpoint path.

Spec grammar (comma-separated clauses):

    TARGET[:ACTION][@TRIGGER]   or   seed=N

    ACTION   delay=SECONDS (sleep before the call — a spike or a stall,
             depending on magnitude; ``stall=`` is an alias) | error
             (raise InjectedFault instead of the call) — fetch/step/web
             targets only. ``source.*`` targets take no action (the
             injection IS the action), except ``source.burst:rows=N``
             (extra re-emits per firing; default 4).
    TRIGGER  N       every Nth call of that target (deterministic)
             pP      probability P per call (seeded RNG)
             fromN   every call from the Nth on (a permanent outage)
             default: every call

Example: ``--chaos "fetch:delay=2@3,source.nan@5,source.burst:rows=8@p0.1,seed=7"``
"""

from __future__ import annotations

import random
import threading
import time
from typing import Iterator

from ..utils import get_logger
from .sources import Source

log = get_logger("streaming.faults")

TRANSPORT_TARGETS = ("fetch", "step", "web")
SOURCE_TARGETS = ("source.garbage", "source.burst", "source.nan")
# membership churn (r16, ISSUE 13): peer death/stall injectable from the
# CLI like every other fault — previously only reachable via
# tests/distributed_worker.py's peer_kill mode
PEER_TARGETS = ("peer.kill", "peer.pause")
CHAOS_TARGETS = TRANSPORT_TARGETS + SOURCE_TARGETS + PEER_TARGETS

# extra re-emits per source.burst firing when the rule gives no rows=N
BURST_DEFAULT_EXTRA = 4
# default peer.pause stall length (lockstep ticks' worth of wall time)
PAUSE_DEFAULT_TICKS = 4
# exit code of a peer.kill hard death (test-assertable, distinct from the
# jax coordination-service SIGABRT and from clean failures)
PEER_KILL_EXIT_CODE = 77


class InjectedFault(ConnectionError):
    pass


class _ChaosRule:
    """One parsed ``TARGET:ACTION[@TRIGGER]`` clause."""

    __slots__ = ("target", "kind", "value", "mode", "param", "uid")

    def __init__(self, target: str, kind: str, value: float, mode: str,
                 param: float, uid: int = -1):
        self.target = target
        self.kind = kind  # "delay" | "error"
        self.value = value  # sleep seconds (delay only)
        self.mode = mode  # "every" | "prob" | "from"
        self.param = param
        # peer.* host selector: fire only on the host whose ORIGINAL
        # process uid matches (-1 = every host). This is what makes
        # kill-the-lead expressible from one fleet-wide --chaos spec:
        # peer.kill:uid=0:tick=4 kills exactly the launch lead.
        self.uid = int(uid)

    def fires(self, call_index: int, rng: random.Random) -> bool:
        if self.mode == "every":
            return call_index % int(self.param) == 0
        if self.mode == "from":
            return call_index >= int(self.param)
        return rng.random() < self.param

    def on_host(self, uid: int) -> bool:
        return self.uid < 0 or self.uid == int(uid)

    def __repr__(self) -> str:  # shows up in the install log line
        sel = f" uid={self.uid}" if self.uid >= 0 else ""
        if self.kind == "kill":
            return f"{self.target}{sel} (at lockstep tick {int(self.value)})"
        act = (
            "error" if self.kind == "error"
            else "inject" if self.kind == "inject"
            else f"pause={int(self.value)} ticks" if self.kind == "pause"
            else f"delay={self.value:g}s"
        )
        trig = {"every": "every %d", "from": "from call %d on",
                "prob": "p=%g"}[self.mode] % self.param
        return f"{self.target}{sel}:{act} ({trig})"


def _parse_trigger(text: str) -> "tuple[str, float]":
    if text.startswith("p"):
        p = float(text[1:])
        if not 0.0 < p <= 1.0:
            raise ValueError(f"probability trigger out of (0, 1]: {text!r}")
        return "prob", p
    if text.startswith("from"):
        n = int(text[len("from"):])
        if n < 1:
            raise ValueError(f"'from' trigger must be >= 1: {text!r}")
        return "from", n
    n = int(text)
    if n < 1:
        raise ValueError(f"every-Nth trigger must be >= 1: {text!r}")
    return "every", n


class ChaosInjector:
    """Seeded transport-fault injector. ``perturb(target)`` is called at
    each injection point: it may sleep (latency spike / stall) and/or raise
    ``InjectedFault`` according to the parsed rules. Thread-safe — the
    pooled fetch calls it from worker threads; sleeps happen outside the
    lock so concurrent fetches stall independently, like real fetch
    stalls. Deterministic for a given seed and per-target call sequence."""

    def __init__(self, spec: str):
        self.spec = spec
        seed = 0
        rules: list[_ChaosRule] = []
        for raw in spec.split(","):
            clause = raw.strip()
            if not clause:
                continue
            if clause.startswith("seed="):
                seed = int(clause[len("seed="):])
                continue
            body, _, trigger = clause.partition("@")
            target, sep, action = body.partition(":")
            if target not in CHAOS_TARGETS:
                raise ValueError(
                    f"bad chaos clause {clause!r}: want TARGET[:ACTION] with "
                    f"TARGET in {CHAOS_TARGETS}"
                )
            mode, param = _parse_trigger(trigger) if trigger else ("every", 1)
            if target in PEER_TARGETS:
                # membership churn: peer.kill[:uid=U][:tick=N] hard-exits
                # host U (every host when no uid) at lockstep tick N
                # (default 1); peer.pause[:uid=U][:ticks=K] stalls it for
                # ~K ticks' wall time at the trigger's ticks. Parts are
                # colon-separated and order-free.
                count_key = "tick" if target == "peer.kill" else "ticks"
                uid, value = -1, None
                for part in filter(None, action.split(":")):
                    key, eq, num = part.partition("=")
                    if not eq or key not in ("uid", count_key):
                        raise ValueError(
                            f"bad chaos action {part!r} in {clause!r}: "
                            f"{target} takes {count_key}=N and uid=U"
                        )
                    if key == "uid":
                        uid = int(num)
                        if uid < 0:
                            raise ValueError(
                                f"negative uid in {clause!r}"
                            )
                    else:
                        value = int(num)
                        if value < 1:
                            raise ValueError(
                                f"non-positive {count_key} in {clause!r}"
                            )
                if target == "peer.kill":
                    value = 1 if value is None else value
                    rules.append(
                        _ChaosRule(target, "kill", value, "every", value,
                                   uid=uid)
                    )
                else:
                    value = PAUSE_DEFAULT_TICKS if value is None else value
                    rules.append(
                        _ChaosRule(target, "pause", value, mode, param,
                                   uid=uid)
                    )
                continue
            if target in SOURCE_TARGETS:
                # the injection IS the action; only source.burst takes a
                # magnitude (rows=N extra re-emits per firing)
                if action.startswith("rows="):
                    if target != "source.burst":
                        raise ValueError(
                            f"rows= only applies to source.burst, not {clause!r}"
                        )
                    value = int(action.partition("=")[2])
                    if value < 1:
                        raise ValueError(f"non-positive rows in {clause!r}")
                elif action:
                    raise ValueError(
                        f"bad chaos action {action!r} in {clause!r}: "
                        "source targets take no action (source.burst "
                        "accepts rows=N)"
                    )
                else:
                    value = BURST_DEFAULT_EXTRA
                rules.append(_ChaosRule(target, "inject", value, mode, param))
            elif action == "error":
                rules.append(_ChaosRule(target, "error", 0.0, mode, param))
            elif action.startswith(("delay=", "stall=")):
                value = float(action.partition("=")[2])
                if value <= 0:
                    raise ValueError(f"non-positive delay in {clause!r}")
                rules.append(_ChaosRule(target, "delay", value, mode, param))
            else:
                raise ValueError(
                    f"bad chaos action {action!r} in {clause!r}: want "
                    "delay=SECONDS, stall=SECONDS, or error"
                )
        if not rules:
            raise ValueError(f"chaos spec {spec!r} names no injection rules")
        self._rules: dict[str, list[_ChaosRule]] = {}
        for r in rules:
            self._rules.setdefault(r.target, []).append(r)
        self._rng = random.Random(seed)
        self._calls = {t: 0 for t in CHAOS_TARGETS}
        self._lock = threading.Lock()

    def perturb(self, target: str) -> None:
        """Apply this call's injections for ``target``: sleep for every
        firing delay rule, then raise if any error rule fired."""
        rules = self._rules.get(target)
        if not rules:
            return
        with self._lock:
            self._calls[target] += 1
            n = self._calls[target]
            fired = [r for r in rules if r.fires(n, self._rng)]
        if not fired:
            return
        from ..telemetry import blackbox as _blackbox
        from ..telemetry import metrics as _metrics

        reg = _metrics.get_registry()
        raise_after = False
        for r in fired:
            reg.counter("chaos.injected").inc()
            # flight-recorder ring: a post-mortem over a chaos run must
            # show which rules fired on the way down (no-op when no
            # recorder is installed)
            _blackbox.record(
                "chaos", target=target, action=r.kind, call=n,
            )
            if r.kind == "delay":
                reg.counter(f"chaos.{target}.delays").inc()
                log.warning(
                    "chaos: injecting %.2fs %s into %s call #%d",
                    r.value, "stall" if r.value >= 1 else "delay", target, n,
                )
                time.sleep(r.value)
            else:
                reg.counter(f"chaos.{target}.errors").inc()
                raise_after = True
        if raise_after:
            raise InjectedFault(f"injected {target} fault (call #{n})")

    def should(self, target: str) -> "float | None":
        """Source-injection query: count one call of ``target`` and return
        the firing rule's magnitude (``source.burst`` rows; 1 otherwise), or
        None when nothing fires. Never sleeps or raises — the CALLER owns
        the injection (corrupting bytes, duplicating emits, poisoning
        labels), this just decides whether and how much."""
        rules = self._rules.get(target)
        if not rules:
            return None
        with self._lock:
            self._calls[target] += 1
            n = self._calls[target]
            fired = [r for r in rules if r.fires(n, self._rng)]
        if not fired:
            return None
        from ..telemetry import blackbox as _blackbox
        from ..telemetry import metrics as _metrics

        reg = _metrics.get_registry()
        value = 0.0
        for r in fired:
            reg.counter("chaos.injected").inc()
            reg.counter(f"chaos.{target}.injected").inc()
            _blackbox.record("chaos", target=target, action="inject", call=n)
            value = max(value, r.value)
        return value

    def calls(self, target: str) -> int:
        return self._calls.get(target, 0)

    def peer_chaos(self, tick: int, interval: float, uid: int = -1) -> None:
        """``peer.kill``/``peer.pause`` injection, driven by the lockstep
        scheduler once per tick (the TICK NUMBER is the call index —
        deterministic on every host, so a rule fires at the same point of
        each host's own loop). ``uid`` is this host's original process id;
        rules with a uid selector fire only on the matching host. A kill
        is a HARD exit (``os._exit`` with ``PEER_KILL_EXIT_CODE``): no
        abort broadcast, no goodbye — exactly the failure the peer
        watchdog + elastic rescue path exist for. A pause sleeps ~K ticks'
        worth of wall time (``K x max(interval, 0.5s)``), long enough to
        trip the peer watchdog when K x interval exceeds
        ``TWTML_LOCKSTEP_TIMEOUT_S``."""
        from ..telemetry import blackbox as _blackbox
        from ..telemetry import metrics as _metrics

        for r in self._rules.get("peer.kill", ()):
            if tick == int(r.value) and r.on_host(uid):
                log.critical(
                    "chaos: peer.kill firing at lockstep tick %d — hard "
                    "exit %d (no abort broadcast)", tick,
                    PEER_KILL_EXIT_CODE,
                )
                _metrics.get_registry().counter("chaos.injected").inc()
                _blackbox.record(
                    "chaos", target="peer.kill", tick=tick, uid=uid,
                )
                import os as _os
                import sys as _sys

                _sys.stdout.flush()
                _sys.stderr.flush()
                _os._exit(PEER_KILL_EXIT_CODE)
        rules = self._rules.get("peer.pause", ())
        if not rules:
            return
        with self._lock:
            # every host draws the SAME rng sequence (rules evaluate before
            # the uid filter) so uid-selected rules never desynchronize the
            # prob-mode draws of unselected rules across the fleet
            fired = [r for r in rules if r.fires(tick, self._rng)]
        fired = [r for r in fired if r.on_host(uid)]
        for r in fired:
            dur = int(r.value) * max(float(interval), 0.5)
            _metrics.get_registry().counter("chaos.injected").inc()
            _metrics.get_registry().counter("chaos.peer.pauses").inc()
            _blackbox.record(
                "chaos", target="peer.pause", tick=tick, secs=round(dur, 2),
            )
            log.warning(
                "chaos: peer.pause stalling this host %.1fs (~%d ticks) "
                "at lockstep tick %d", dur, int(r.value), tick,
            )
            time.sleep(dur)


# process-wide injector: injection points are scattered across layers
# (apps/common fetch+dispatch, telemetry/web_client) and all belong to the
# one run-level chaos configuration the --chaos flag names
_CHAOS: "ChaosInjector | None" = None


def install_chaos(spec: str) -> ChaosInjector:
    """Parse + activate a chaos spec process-wide (``--chaos`` wiring;
    raises ValueError on a malformed spec)."""
    global _CHAOS
    _CHAOS = ChaosInjector(spec)
    log.warning(
        "transport chaos ACTIVE: %s",
        "; ".join(repr(r) for rs in _CHAOS._rules.values() for r in rs),
    )
    return _CHAOS


def uninstall_chaos() -> None:
    global _CHAOS
    _CHAOS = None


def get_chaos() -> "ChaosInjector | None":
    return _CHAOS


def perturb(target: str) -> None:
    """Module-level injection point: no-op unless a chaos spec is
    installed (one global read on the hot path)."""
    if _CHAOS is not None:
        _CHAOS.perturb(target)


def lockstep_chaos(tick: int, interval: float, uid: int = -1) -> None:
    """``peer.*`` injection point, called by the lockstep scheduler at the
    top of every tick (streaming/context._lockstep_loop) with this host's
    original process uid. No-op unless a chaos spec with peer rules is
    installed."""
    if _CHAOS is not None:
        _CHAOS.peer_chaos(tick, interval, uid=uid)


# -- source/parse injection points (r7 — the ingest-guard failure domain) ----


def maybe_corrupt_block(data: bytes) -> bytes:
    """``source.garbage`` injection point (block sources' bytes → parser
    stage): truncate the buffer mid-line and garble a window, simulating a
    torn/damaged wire chunk. The parser contract (skip malformed lines,
    never crash, count the skips) absorbs it; the truncated tail rides the
    carry into the next chunk like real damage would.

    Buffers under 256 bytes pass untouched (and don't count a call): the
    parser's capacity/tail loops re-parse their own shrinking carry, and
    re-corrupting every remnant would chase it to zero forever instead of
    modeling one damaged chunk."""
    if _CHAOS is None or len(data) < 256:
        return data
    if _CHAOS.should("source.garbage") is None:
        return data
    cut = max(1, len(data) * 2 // 3)
    corrupted = bytearray(data[:cut])
    lo = max(0, cut // 2 - 16)
    for i in range(lo, min(len(corrupted), lo + 32)):
        corrupted[i] ^= 0xFF
    log.warning(
        "chaos: corrupted a %d-byte block buffer (truncated to %d, "
        "garbled 32 bytes)", len(data), cut,
    )
    return bytes(corrupted)


def burst_extra() -> int:
    """``source.burst`` injection point (source emit loop): number of EXTRA
    re-emits of the current item this call (0 = no burst). A burst of
    duplicated items is a rate spike the bounded intake queue must absorb
    (block) or shed (shed-oldest) — rows, not wall-clock, is what the
    backpressure bound meters."""
    if _CHAOS is None:
        return 0
    v = _CHAOS.should("source.burst")
    return int(v) if v else 0


def maybe_poison_labels(batch):
    """``source.nan`` injection point (featurize stage): return ``batch``
    with every VALID row's label poisoned to NaN (padding rows keep their
    zeros — the learner multiplies by mask, and poisoned padding would
    taint even batches the rule never fired on). One poisoned batch drives
    the fused predict-then-train step's weights non-finite in a single
    update — the exact event the divergence sentinel exists to catch."""
    if _CHAOS is None:
        return batch
    if _CHAOS.should("source.nan") is None:
        return batch
    import numpy as np

    label = np.array(batch.label, copy=True)
    valid = np.asarray(batch.mask) > 0
    if not valid.any():
        return batch
    label[valid] = np.nan
    log.warning(
        "chaos: poisoned %d label(s) with NaN in a %d-row batch",
        int(valid.sum()), label.shape[0],
    )
    if hasattr(batch, "_replace"):  # FeatureBatch / UnitBatch NamedTuples
        return batch._replace(label=label)
    from ..features.batch import RaggedUnitBatch

    if isinstance(batch, RaggedUnitBatch):
        return RaggedUnitBatch(
            batch.units, batch.offsets, batch.numeric, label, batch.mask,
            row_len=batch.row_len, num_shards=batch.num_shards,
        )
    raise TypeError(f"source.nan cannot poison a {type(batch).__name__}")


class FaultInjectingSource(Source):
    name = "fault-injecting"

    def __init__(
        self,
        inner: Source,
        crash_every: int = 0,
        crash_prob: float = 0.0,
        max_crashes: int = 3,
        seed: int = 0,
        **kw,
    ):
        kw.setdefault("max_restarts", 1_000_000)  # chaos runs should survive
        kw.setdefault("restart_backoff", 0.01)
        super().__init__(**kw)
        self.inner = inner
        self.crash_every = crash_every
        self.crash_prob = crash_prob
        # crashes are capped so finite sources (replay files) still complete:
        # each restart re-runs inner.produce() from scratch, so unbounded
        # deterministic crashing would livelock any file shorter than
        # crash_every × restarts. max_crashes<=0 means unbounded (only
        # sensible for unbounded sources).
        self.max_crashes = max_crashes
        self._rng = random.Random(seed)
        self._emitted = 0  # TWEETS emitted (a columnar block counts its rows)
        self._next_crash = crash_every
        self.crashes = 0

    def _may_crash(self) -> bool:
        return self.max_crashes <= 0 or self.crashes < self.max_crashes

    def produce(self) -> Iterator:
        from ..features.blocks import ParsedBlock

        for item in self.inner.produce():
            # crash_every counts TWEETS on every source kind: block sources
            # emit ParsedBlocks of ~thousands of rows each, so item-counting
            # would make --faultEvery thousands of times rarer than asked
            size = item.rows if isinstance(item, ParsedBlock) else 1
            if self.crash_prob and self._may_crash():
                # per-tweet probability, scaled to the item's row count
                p = 1.0 - (1.0 - self.crash_prob) ** size
                if self._rng.random() < p:
                    self.crashes += 1
                    raise InjectedFault(
                        f"injected probabilistic crash #{self.crashes}"
                    )
            # count first, then crash BEFORE the yield: the item that
            # crosses the threshold is lost in flight (like a dropped
            # socket), and a threshold crossed inside a stream's final
            # block still fires
            self._emitted += size
            if (
                self.crash_every
                and self._emitted >= self._next_crash
                and self._may_crash()
            ):
                self.crashes += 1
                self._next_crash = self._emitted + self.crash_every
                raise InjectedFault(
                    f"injected receiver crash #{self.crashes} "
                    f"after {self._emitted} tweets"
                )
            yield item

    def stop(self) -> None:
        # unblock the inner source first: our producer thread may be parked
        # in the inner's paced _stop.wait(), which only inner.stop() releases
        self.inner.stop()
        super().stop()
