"""Wire schema for the telemetry API — byte-compatible with the reference.

The reference serializes two case classes with json4s ``ShortTypeHints``,
which adds a ``jsonClass`` discriminator field (spark/.../web/ApiTypes.scala:5-17,
WebClient.scala:11; consumed by the browser at js/index.js:9-16 and the cache
at ApiCache.scala:19-20,41-48). The exact same JSON shape is kept so the
reference's dashboards and ours are interchangeable:

  {"jsonClass":"Config","id":"...","host":"...","viz":["..."]}
  {"jsonClass":"Stats","count":0,"batch":0,"mse":0,"realStddev":0,"predStddev":0}
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass, field


@dataclass
class Config:
    id: str = ""
    host: str = ""
    viz: list[str] = field(default_factory=list)

    json_class = "Config"


@dataclass
class Stats:
    count: int = 0
    batch: int = 0
    mse: int = 0
    realStddev: int = 0
    predStddev: int = 0

    json_class = "Stats"


@dataclass
class Series:
    """Per-batch real/predicted value series — an ADDITIVE message type (no
    reference equivalent; the reference ships these points to the external
    Lightning server only, SessionStats.scala:31-33). Carried on the same
    jsonClass-discriminated wire so legacy dashboards simply ignore it; the
    built-in dashboard renders it as the live chart."""

    real: list[float] = field(default_factory=list)
    pred: list[float] = field(default_factory=list)
    realStddev: float = 0.0
    predStddev: float = 0.0

    json_class = "Series"


@dataclass
class Metrics:
    """Pipeline metrics snapshot — an ADDITIVE message type (no reference
    equivalent) carrying the process-local registry (telemetry/metrics.py)
    and the fetch-health summary to the dashboard's observability panel.
    Rides the jsonClass-discriminated wire like Series, so legacy dashboards
    ignore it. ``counters``/``gauges`` are flat name→value maps; ``health``
    is FetchHealthMonitor.summary() (phase, rtt_ms, transitions,
    observations); ``histograms`` (r8) maps name → derived
    count/mean/p50/p95/p99 (the latency tile — raw buckets stay
    registry-side)."""

    counters: dict = field(default_factory=dict)
    gauges: dict = field(default_factory=dict)
    health: dict = field(default_factory=dict)
    histograms: dict = field(default_factory=dict)

    json_class = "Metrics"


@dataclass
class Hosts:
    """Per-host lockstep telemetry view — an ADDITIVE message type (no
    reference equivalent; the reference is single-process). One row per
    host from the sideband matrix that rides the cadence allgather
    (telemetry/sideband.py), plus the straggler attributor's verdict:
    which host gated this tick, which bottleneck-ladder stage, and the
    tick skew. Legacy dashboards ignore it like Series/Metrics."""

    hosts: list = field(default_factory=list)
    straggler: int = -1
    stage: str = ""
    skewMs: float = 0.0
    # elastic membership (r16): current epoch (-1 = not elastic), live
    # member count, and cumulative departed/rejoined hosts — decode
    # defaults keep legacy frames valid
    epoch: int = -1
    liveHosts: int = 0
    departed: int = 0
    rejoined: int = 0
    # r20: the CURRENT lead's uid — uid 0 at launch, moves only at a won
    # election (streaming/membership.py); -1 when the run is not elastic
    leadUid: int = -1

    json_class = "Hosts"


@dataclass
class Tenants:
    """Per-tenant model-plane view — an ADDITIVE message type (no reference
    equivalent; the reference trains ONE model). One row per tenant from
    the stacked StepOutput the pipeline already fetched (telemetry/
    tenants.py), plus the gating tenant (most rows this tick — where the
    shared row bucket binds first) and the active-tenant count. Legacy
    dashboards ignore it like Series/Metrics/Hosts."""

    tenants: list = field(default_factory=list)
    gating: int = -1
    active: int = 0

    json_class = "Tenants"


@dataclass
class ModelHealth:
    """Model & data quality view — an ADDITIVE message type (no reference
    equivalent; the reference has no model-health signal at all). Derived
    by telemetry/modelwatch.py from the in-step quality vector the
    pipeline already fetched (zero added fetches, the PR 1/5 law):
    graduated health level (ok/warn/alert), the max drift z-score and
    loss-trend slope, the weight/update/gradient norms, a rolling mse
    window (the dashboard's loss sparkline), and per-tenant rows on the
    multi-tenant plane. Legacy dashboards ignore it like
    Series/Metrics/Hosts/Tenants."""

    level: str = "ok"
    driftScore: float = 0.0
    lossTrend: float = 0.0
    weightNorm: float = 0.0
    updateNorm: float = 0.0
    gradNorm: float = 0.0
    mse: list = field(default_factory=list)
    tenants: list = field(default_factory=list)
    episodes: int = 0

    json_class = "ModelHealth"


@dataclass
class Serving:
    """Serving-plane view — an ADDITIVE message type (no reference
    equivalent; the reference never served its model). QPS/latency over the
    rolling serve window, the active snapshot (step + its checkpoint
    quality level), cumulative request/row/error totals, and per-tenant
    served-row counts on the multi-tenant plane (serving/plane.py
    ``stats()``). Legacy dashboards ignore it like the other additive
    types."""

    qps: float = 0.0
    rowsPerSec: float = 0.0
    p50Ms: float = 0.0
    p95Ms: float = 0.0
    p99Ms: float = 0.0
    # serving staleness (ISSUE 16): seconds since the active snapshot was
    # installed (-1 before the first install); decode default keeps legacy
    # frames valid
    snapshotAgeS: float = -1.0
    snapshotStep: int = -1
    level: str = ""
    requests: int = 0
    rows: int = 0
    errors: int = 0
    tenants: list = field(default_factory=list)
    # champion/challenger slice (ISSUE 11, serving/abtest.py): -1 /[] on a
    # plain single-model plane; a fleet router reads the champion from this
    # view through the health check it already makes
    champion: int = -1
    shadows: list = field(default_factory=list)
    promotions: int = 0
    refusedPromotions: int = 0

    json_class = "Serving"


@dataclass
class Freshness:
    """End-to-end freshness view — an ADDITIVE message type (no reference
    equivalent). Derived by telemetry/freshness.py from per-batch lineage
    records stamped at the existing pipeline seams (zero added fetches,
    zero added collectives — the PR 1/5/8 law): event-time lag percentiles
    from tweet ``created_at_ms`` to fetch delivery and to stats publish,
    the rolling low-watermark sparkline, the dominant critical-path edge
    with its per-edge tick counts, and the ``--freshnessSloMs`` breach
    state. Legacy dashboards ignore it like the other additive types."""

    batches: int = 0
    rows: int = 0
    eventLagMs: float = -1.0
    eventLagP50Ms: float = -1.0
    eventLagP95Ms: float = -1.0
    eventLagP99Ms: float = -1.0
    publishLagP95Ms: float = -1.0
    watermarkLagMs: float = -1.0
    watermark: list = field(default_factory=list)
    critical: str = ""
    criticalTicks: dict = field(default_factory=dict)
    sloMs: float = 0.0
    breachRun: int = 0
    breaches: int = 0

    json_class = "Freshness"


@dataclass
class Fleet:
    """Read-fleet view — an ADDITIVE message type (no reference equivalent;
    the reference is one process end to end). Published by the fleet
    router (serving/fleet.py ``stats()``): per-replica health/latency/
    traffic tiles, the routing policy, the router's retry/ejection story,
    and the fleet-wide champion tenant on the champion/challenger plane.
    Legacy dashboards ignore it like the other additive types."""

    policy: str = ""
    replicas: list = field(default_factory=list)
    requests: int = 0
    retries: int = 0
    ejections: int = 0
    champion: int = -1

    json_class = "Fleet"


@dataclass
class History:
    """Telemetry-historian view — an ADDITIVE message type (no reference
    equivalent). Published by telemetry/historian.py from its in-memory
    tail ring (the durable segments never get read on the hot path): the
    long-horizon RSS / fetch-RTT / per-tick stage-cost sparklines, the
    least-squares RSS slope (the soak estimator, live), the current
    health phase, historian disk usage, and the perfGuard regression
    count. Legacy dashboards ignore it like the other additive types."""

    samples: int = 0
    runId: int = 0
    phase: str = ""
    rssMb: float = 0.0
    rssSlopeMbPerMin: float = 0.0
    rttMs: float = 0.0
    diskMb: float = 0.0
    regressions: int = 0
    rss: list = field(default_factory=list)
    rtt: list = field(default_factory=list)
    stageMs: list = field(default_factory=list)

    json_class = "History"


TYPES = {"Config": Config, "Stats": Stats, "Series": Series,
         "Metrics": Metrics, "Hosts": Hosts, "Tenants": Tenants,
         "ModelHealth": ModelHealth, "Serving": Serving, "Fleet": Fleet,
         "Freshness": Freshness, "History": History}


def encode(obj: Config | Stats) -> str:
    payload = {"jsonClass": obj.json_class}
    payload.update(asdict(obj))
    return json.dumps(payload)


def decode(text: str) -> Config | Stats:
    """Dispatch on the jsonClass hint (ApiCache.scala:41-48); raises on
    unknown types like the reference logs-and-drops."""
    payload = json.loads(text)
    kind = payload.pop("jsonClass", None)
    cls = TYPES.get(kind)
    if cls is None:
        raise ValueError(f"json not recognized: {text!r}")
    fields = {k: payload[k] for k in cls.__dataclass_fields__ if k in payload}
    return cls(**fields)
