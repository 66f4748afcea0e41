"""Layered configuration + CLI argument parsing.

Re-design of the reference's config layer (ConfArguments.scala:1-164 +
reference.conf:1-13): Typesafe-config layering becomes a small HOCON-subset
parser over packaged defaults plus an optional ``application.conf`` override,
and the hand-rolled recursive pattern-match CLI parser (ConfArguments.scala:91-158)
becomes an equivalent recursive parser with the same long/short flag surface.

Twitter OAuth credentials are routed into a process-wide property table under
``twitter4j.oauth.*`` keys, mirroring the JVM system properties the reference
sets (ConfArguments.scala:58-76,103-118) so downstream sources read creds from
one place.

Extensions over the reference (flagged in usage): ``--backend``, ``--source``,
``--replayFile``, ``--l2Reg``, ``--dtype``, ``--checkpointDir``, etc.
"""

from __future__ import annotations

import os
import sys
from importlib import resources as _importlib_resources

# --tenantKey: two keys PARTITION a batch over the tenants, ``all`` hands
# every tenant every row (features/batch.tenant_route_keys has the rules)
TENANT_KEYS = ("hash", "lang", "all")

# Process-wide property table, the moral equivalent of JVM system properties
# (reference routes OAuth creds there, ConfArguments.scala:58-76).
_SYSTEM_PROPERTIES: dict[str, str] = {}


def set_property(key: str, value: str) -> None:
    _SYSTEM_PROPERTIES[key] = value


def get_property(key: str, default: str | None = None) -> str | None:
    return _SYSTEM_PROPERTIES.get(key, default)


def parse_conf_text(text: str) -> dict[str, str]:
    """Parse the HOCON subset used by the reference's .conf files
    (``key="value"`` / ``key=value`` lines, ``#``/``//`` comments)."""
    out: dict[str, str] = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#") or line.startswith("//"):
            continue
        if "=" not in line:
            continue
        key, _, value = line.partition("=")
        value = value.strip()
        if len(value) >= 2 and value[0] == '"':
            # Quoted value: take up to the closing quote (rest is comment/junk).
            end = value.find('"', 1)
            value = value[1:end] if end > 0 else value[1:]
        else:
            # Unquoted: strip trailing inline comments.
            for marker in ("#", "//"):
                pos = value.find(marker)
                if pos >= 0:
                    value = value[:pos].rstrip()
        out[key.strip()] = value
    return out


def _load_defaults() -> dict[str, str]:
    ref = _importlib_resources.files("twtml_tpu.resources").joinpath("reference.conf")
    return parse_conf_text(ref.read_text())


def _load_application_conf() -> dict[str, str]:
    """Optional override file, mirroring Typesafe-config's application.conf
    layering (README.md:85-105 of the reference documents this flow).

    Search order: $TWTML_CONFIG, then ./application.conf.
    """
    candidates = []
    env_path = os.environ.get("TWTML_CONFIG", "")
    if env_path:
        candidates.append(env_path)
    candidates.append(os.path.join(os.getcwd(), "application.conf"))
    for path in candidates:
        if os.path.isfile(path):
            with open(path, "r", encoding="utf-8") as fh:
                return parse_conf_text(fh.read())
    return {}


_OAUTH_KEYS = ("consumerKey", "consumerSecret", "accessToken", "accessTokenSecret")


class ConfArguments:
    """Config object with the same knob surface as the reference's
    ConfArguments (ConfArguments.scala:20-28 getters, :91-158 flags).

    Attribute names intentionally keep the reference's camelCase so the CLI
    flags, conf keys, and attributes line up one-to-one.
    """

    def __init__(self) -> None:
        conf = dict(_load_defaults())
        conf.update(_load_application_conf())
        self._conf = conf

        self.lightning: str = conf["lightning"]
        self.twtweb: str = conf["twtweb"]
        self.seconds: int = int(conf["seconds"])
        self.stepSize: float = float(conf["stepSize"])
        self.numIterations: int = int(conf["numIterations"])
        self.miniBatchFraction: float = float(conf["miniBatchFraction"])
        self.numRetweetBegin: int = int(conf["numRetweetBegin"])
        self.numRetweetEnd: int = int(conf["numRetweetEnd"])
        self.numTextFeatures: int = int(conf["numTextFeatures"])

        # Extensions (no reference equivalent).
        self.backend: str = conf.get("backend", "auto")
        self.source: str = conf.get("source", "replay")
        self.replayFile: str = conf.get("replayFile", "")
        self.replaySpeed: float = float(conf.get("replaySpeed", "0.0"))
        self.batchBucket: int = int(conf.get("batchBucket", "0"))
        self.modelShards: int = int(conf.get("modelShards", "1"))
        self.tokenBucket: int = int(conf.get("tokenBucket", "0"))
        self.hashOn: str = conf.get("hashOn", "device")
        if self.hashOn not in ("device", "host"):
            raise ValueError(
                f"hashOn must be 'device' or 'host', got {self.hashOn!r}"
            )
        self.ingest: str = conf.get("ingest", "object")
        if self.ingest not in ("object", "block"):
            raise ValueError(
                f"ingest must be 'object' or 'block', got {self.ingest!r}"
            )
        self.wire: str = conf.get("wire", "auto")
        if self.wire not in ("auto", "padded", "ragged"):
            raise ValueError(
                f"wire must be 'auto', 'padded' or 'ragged', got {self.wire!r}"
            )
        self.blockWire: str = conf.get("blockWire", "auto")
        if self.blockWire not in ("auto", "on", "off"):
            raise ValueError(
                f"blockWire must be 'auto', 'on' or 'off', got "
                f"{self.blockWire!r}"
            )
        self.l2Reg: float = float(conf.get("l2Reg", "0.0"))
        self.l1Reg: float = float(conf.get("l1Reg", "0.0"))
        self.convergenceTol: float = float(conf.get("convergenceTol", "0.001"))
        self.dtype: str = conf.get("dtype", "float32")
        self.checkpointDir: str = conf.get("checkpointDir", "")
        self.checkpointEvery: int = int(conf.get("checkpointEvery", "0"))
        self.journal: str = conf.get("journal", "auto")
        if self.journal not in ("auto", "on", "off"):
            raise ValueError(
                f"journal must be 'auto', 'on' or 'off', got {self.journal!r}"
            )
        self.journalMaxMb: int = int(conf.get("journalMaxMb", "512"))
        if self.journalMaxMb <= 0:
            raise ValueError(
                f"journalMaxMb must be positive, got {self.journalMaxMb}"
            )
        # telemetry historian (r22): durable long-horizon time series at
        # the stats-publish cadence + cross-run perf regression sentinel
        # (telemetry/historian.py)
        self.history: str = conf.get("history", "auto")
        if self.history not in ("auto", "on", "off"):
            raise ValueError(
                f"history must be 'auto', 'on' or 'off', got {self.history!r}"
            )
        self.historyMaxMb: int = int(conf.get("historyMaxMb", "256"))
        if self.historyMaxMb <= 0:
            raise ValueError(
                f"historyMaxMb must be positive, got {self.historyMaxMb}"
            )
        self.perfGuard: str = conf.get("perfGuard", "warn")
        if self.perfGuard not in ("warn", "off"):
            raise ValueError(
                f"perfGuard must be 'warn' or 'off', got {self.perfGuard!r}"
            )
        self.perfGuardRatio: float = float(conf.get("perfGuardRatio", "1.5"))
        if self.perfGuardRatio <= 1.0:
            raise ValueError(
                f"perfGuardRatio must be > 1.0, got {self.perfGuardRatio}"
            )
        self.profileDir: str = conf.get("profileDir", "")
        self.trace: str = conf.get("trace", "")
        self.traceMaxMb: int = int(conf.get("traceMaxMb", "256"))
        self.blackbox: str = conf.get("blackbox", "on")
        if self.blackbox not in ("on", "off"):
            raise ValueError(
                f"blackbox must be 'on' or 'off', got {self.blackbox!r}"
            )
        self.faultEvery: int = int(conf.get("faultEvery", "0"))
        self.chaos: str = conf.get("chaos", "")
        self.webTimeout: float = float(conf.get("webTimeout", "2.0"))
        self.wirePack: str = conf.get("wirePack", "auto")
        if self.wirePack not in ("auto", "stacked", "group"):
            raise ValueError(
                "wirePack must be 'auto', 'stacked' or 'group', got "
                f"{self.wirePack!r}"
            )
        # compressed ragged units wire (r15): C-side digram encode,
        # in-jit gather-expand decode (features/wirecodec.py)
        self.wireCodec: str = conf.get("wireCodec", "auto")
        if self.wireCodec not in ("auto", "off", "dict"):
            raise ValueError(
                "wireCodec must be 'auto', 'off' or 'dict', got "
                f"{self.wireCodec!r}"
            )
        # fused one-pass wire assembly on a pooled buffer arena (r17):
        # the native emitter builds the final packed wire in one C sweep
        self.wireAssemble: str = conf.get("wireAssemble", "auto")
        if self.wireAssemble not in ("auto", "on", "off"):
            raise ValueError(
                "wireAssemble must be 'auto', 'on' or 'off', got "
                f"{self.wireAssemble!r}"
            )
        # one-pass native featurize (r18): the fused C emitter fills the
        # ragged-wire arrays straight from the batch's columns
        self.featurizeNative: str = conf.get("featurizeNative", "auto")
        if self.featurizeNative not in ("auto", "on", "off"):
            raise ValueError(
                "featurizeNative must be 'auto', 'on' or 'off', got "
                f"{self.featurizeNative!r}"
            )
        self.recycleAfterMb: int = int(conf.get("recycleAfterMb", "0"))
        # elastic lockstep membership (r16): host loss shrinks the fleet
        # instead of aborting it; recovered hosts rejoin at epoch
        # boundaries (parallel/elastic.py + streaming/membership.py)
        self.elastic: str = conf.get("elastic", "off")
        if self.elastic not in ("off", "on"):
            raise ValueError(
                f"elastic must be 'off' or 'on', got {self.elastic!r}"
            )
        self.elasticEvictTicks: int = int(conf.get("elasticEvictTicks", "0"))
        self.elasticEvictSkewMs: float = float(
            conf.get("elasticEvictSkewMs", "250")
        )
        self.elasticRejoin: str = conf.get("elasticRejoin", "on")
        if self.elasticRejoin not in ("off", "on"):
            raise ValueError(
                "elasticRejoin must be 'off' or 'on', got "
                f"{self.elasticRejoin!r}"
            )
        # multi-tenant model plane (r10): M models, one jit program, one fetch
        self.tenants: int = int(conf.get("tenants", "1"))
        if self.tenants < 1:
            raise ValueError(f"tenants must be >= 1, got {self.tenants}")
        self.tenantKey: str = conf.get("tenantKey", "hash")
        if self.tenantKey not in TENANT_KEYS:
            raise ValueError(
                f"tenantKey must be one of {TENANT_KEYS}, got "
                f"{self.tenantKey!r}"
            )
        # per-tenant recipes (comma lists of length --tenants; "" = every
        # tenant takes --stepSize / --l2Reg)
        self.tenantStepSize: str = conf.get("tenantStepSize", "")
        self.tenantL2Reg: str = conf.get("tenantL2Reg", "")
        # ingest/state robustness layer (r7)
        self.maxQueueRows: int = int(conf.get("maxQueueRows", "0"))
        self.shedPolicy: str = conf.get("shedPolicy", "block")
        if self.shedPolicy not in ("block", "shed-oldest"):
            raise ValueError(
                "shedPolicy must be 'block' or 'shed-oldest', got "
                f"{self.shedPolicy!r}"
            )
        self.sentinel: str = conf.get("sentinel", "on")
        if self.sentinel not in ("on", "off"):
            raise ValueError(
                f"sentinel must be 'on' or 'off', got {self.sentinel!r}"
            )
        self.sentinelRollbacks: int = int(conf.get("sentinelRollbacks", "3"))
        self.sentinelWindow: int = int(conf.get("sentinelWindow", "512"))
        # serving plane (r12): batched, pipelined low-latency inference
        # from verified snapshots (twtml_tpu/serving/, apps/serve.py)
        self.servePort: int = int(conf.get("servePort", "8888"))
        self.serveBatchRows: int = int(conf.get("serveBatchRows", "256"))
        if self.serveBatchRows < 1:
            raise ValueError(
                f"serveBatchRows must be >= 1, got {self.serveBatchRows}"
            )
        self.serveMaxWaitMs: float = float(conf.get("serveMaxWaitMs", "5.0"))
        self.serveDepth: int = int(conf.get("serveDepth", "8"))
        if self.serveDepth < 1:
            raise ValueError(f"serveDepth must be >= 1, got {self.serveDepth}")
        self.servePromoteEvery: float = float(
            conf.get("servePromoteEvery", "5.0")
        )
        # read fleet + champion/challenger (r14): N serve replicas behind a
        # router (twtml_tpu/serving/fleet.py, apps/router.py) and shadow-
        # scored A/B serving on the tenant stack (serving/abtest.py)
        self.routerPort: int = int(conf.get("routerPort", "8899"))
        self.replicas: str = conf.get("replicas", "")
        self.routePolicy: str = conf.get("routePolicy", "p99")
        if self.routePolicy not in ("p99", "hash"):
            raise ValueError(
                f"routePolicy must be 'p99' or 'hash', got "
                f"{self.routePolicy!r}"
            )
        self.abtest: str = conf.get("abtest", "off")
        if self.abtest not in ("on", "off"):
            raise ValueError(
                f"abtest must be 'on' or 'off', got {self.abtest!r}"
            )
        # model & data observability plane (r11): in-step quality telemetry
        self.modelWatch: str = conf.get("modelWatch", "on")
        if self.modelWatch not in ("on", "off"):
            raise ValueError(
                f"modelWatch must be 'on' or 'off', got {self.modelWatch!r}"
            )
        self.modelWatchWindow: int = int(conf.get("modelWatchWindow", "8"))
        # freshness plane (r16): event-time watermarks, per-batch critical
        # path, and staleness SLOs from lineage records on existing seams
        self.freshness: str = conf.get("freshness", "on")
        if self.freshness not in ("on", "off"):
            raise ValueError(
                f"freshness must be 'on' or 'off', got {self.freshness!r}"
            )
        self.freshnessSloMs: float = float(conf.get("freshnessSloMs", "0"))
        if self.freshnessSloMs < 0:
            raise ValueError(
                f"freshnessSloMs must be >= 0, got {self.freshnessSloMs}"
            )
        self.servingStaleSloS: float = float(
            conf.get("servingStaleSloS", "0")
        )
        if self.servingStaleSloS < 0:
            raise ValueError(
                f"servingStaleSloS must be >= 0, got {self.servingStaleSloS}"
            )

        # Multi-host process group (the reference's one-flag cluster story,
        # ConfArguments.scala:95-98 --master spark://host:port): here a
        # jax.distributed coordinator + process coordinates, settable either
        # via these flags or a twtml://host:port master URL.
        self.coordinator: str = conf.get("coordinator", "")
        self.numProcesses: int = int(conf.get("numProcesses", "0"))
        self.processId: int = int(conf.get("processId", "-1"))

        # Spark-compat knobs: --master/--name are accepted for CLI parity
        # (ConfArguments.scala:95-102); master is interpreted as a backend
        # hint ("local[N]" caps data-parallel shards on CPU) or a
        # twtml://host:port coordinator address. Unrecognized cluster
        # schemes (spark://, mesos://, yarn) are REJECTED at validation
        # (validate_master) — silently running single-host would be worse.
        self._appName: str = "twtml-tpu"
        self.master: str = "local[*]"

        # OAuth creds from conf files land in the property table exactly like
        # the reference's sysprops (ConfArguments.scala:58-76).
        for key in _OAUTH_KEYS:
            value = conf.get(key, "")
            if value != "":
                set_property("twitter4j.oauth." + key, value)

    # -- appName accessors (ConfArguments.scala:78-86) ----------------------
    def appName(self) -> str:
        return self._appName

    def setAppName(self, app_name: str) -> "ConfArguments":
        self._appName = app_name
        return self

    @property
    def usage(self) -> str:
        return f"""
Usage: twtml-train [options]
Usage: python -m twtml_tpu.apps.linear_regression [options]

  Options:
  -h, --help
  -m, --master <master_url>                    local[N] caps CPU shards; twtml://host:port joins
                                               a multi-host run (same as --coordinator). Other
                                               cluster schemes are rejected.
  -n, --name <name>                            A name of your application.
  -C, --consumerKey <consumerKey>              Twitter's consumer key
  -S, --consumerSecret <consumerSecret>        Twitter's consumer secret
  -A, --accessToken <accessToken>              Twitter's access token
  -T, --accessTokenSecret <accessTokenSecret>  Twitter's access token secret
  -l, --lightning <lightning_url>              Default: {self.lightning}
  -w, --twtweb <twtweb_url>                    Default: {self.twtweb}
  -s, --seconds <integer number>               Default: {self.seconds}
  -p, --stepSize <float number>                Default: {self.stepSize}
  -i, --numIterations <integer number>         Default: {self.numIterations}
  -b, --miniBatchFraction <float number>       Default: {self.miniBatchFraction}
  -B, --numRetweetBegin <integer number>       Default: {self.numRetweetBegin}
  -E, --numRetweetEnd <integer number>         Default: {self.numRetweetEnd}
  -f, --numTextFeatures <integer number>       Default: {self.numTextFeatures}

  TPU-framework extensions:
  --coordinator <host:port>                    Join a multi-host jax.distributed process group
                                               (with --numProcesses/--processId; the cluster
                                               analog of the reference's --master spark://...)
  --numProcesses <int>                         Total processes in the multi-host group
  --processId <int>                            This process's rank in the multi-host group
  --backend <auto|tpu|cpu>                     Default: {self.backend}
  --source <replay|twitter|synthetic>          Default: {self.source}
  --replayFile <path.jsonl>                    Tweet replay file (source=replay)
  --replaySpeed <float>                        0 = as-fast-as-possible, else x realtime
  --batchBucket <int>                          Pad batches up to this bucket size (0 = auto)
  --modelShards <int M>                        Shard the hashed text weights (and the Gram
                                               state built over them) by feature over M of the
                                               run's devices: the mesh is (devices / M) data x
                                               M model (SCALING.md). For a hashed width whose
                                               count matrix one chip cannot hold (2^20 dims at
                                               2048 rows). M must divide the device count and
                                               --numTextFeatures. 1 = the data-only mesh.
                                               Default: {self.modelShards}
  --tokenBucket <int>                          Pad per-tweet tokens/units to this bucket
                                               (0 = auto per batch); pinning BOTH buckets
                                               fixes the XLA program shape, enabling the
                                               pre-stream compile warmup
  --hashOn <device|host>                       Bigram-hash featurization inside the XLA step
                                               (device, default) or on the host CPU (host);
                                               bit-identical features either way. Default: {self.hashOn}
  --ingest <object|block>                      Replay ingestion: per-tweet Status objects, or
                                               columnar blocks via the native C parser
                                               (replay source only). Default: {self.ingest}
  --wire <auto|padded|ragged>                  Units wire format: ragged ships concatenated
                                               units + offsets (no pad bytes, on every layout —
                                               packed, sharded, tenant-stacked), padded ships
                                               a [B, L] buffer.
                                               auto = ragged for hashOn=device back-to-back
                                               runs (--seconds 0); padded for wall-clock
                                               streaming (pre-compilable before the stream
                                               starts) and host hashing. Default: {self.wire}
  --l2Reg <float>                              L2 regularization. Default: {self.l2Reg}
  --l1Reg <float>                              L1 regularization: MLlib's L1Updater (LassoWithSGD's)
                                               in the updater's place — every weight soft-
                                               thresholded by stepSize/sqrt(i) x l1Reg after each
                                               gradient step, so the weights the stream does not
                                               inform are exactly zero. The iterations then run
                                               in the feature space itself (one pass over the
                                               batch's count matrix an iteration; models/sgd.py
                                               primal_basis), on one device. One updater a run:
                                               refused with --l2Reg > 0; refused with
                                               --tenantKey all and on any mesh.
                                               Default: {self.l1Reg}
  --convergenceTol <float>                     SGD convergence tolerance. Default: {self.convergenceTol}
  --dtype <float32|bfloat16|float64>           Device dtype. Default: {self.dtype}
  --checkpointDir <path>                       Enable model checkpoint/resume
  --checkpointEvery <int batches>              Checkpoint cadence. Default: {self.checkpointEvery}
  --journal <auto|on|off>                      Durable intake journal (streaming/journal.py):
                                               CRC-framed raw-row records at the intake seam
                                               make sentinel rollback, elastic resync and
                                               restart REPLAY rows instead of counting them
                                               lost; auto = on iff --checkpointDir is set
                                               (verified checkpoints carry the replay
                                               cursor). Default: {self.journal}
  --journalMaxMb <int MB>                      Journal disk ceiling; segments retire once a
                                               verified checkpoint covers them, and the
                                               oldest are dropped (loudly, counted) past
                                               this cap. Default: {self.journalMaxMb}
  --history <auto|on|off>                      Telemetry historian (telemetry/historian.py):
                                               durable CRC-framed time-series segments
                                               sampled at the EXISTING stats-publish cadence
                                               (zero added fetches/collectives) with
                                               health-phase intervals — long-horizon RSS
                                               slope, per-phase RTT/throughput trends, and
                                               the --perfGuard baseline survive the process
                                               (tools/history_report.py reads the leftovers).
                                               auto = on iff --checkpointDir is set; 'off'
                                               is bit-exact pre-historian behavior.
                                               Default: {self.history}
  --historyMaxMb <int MB>                      Historian disk ceiling; the oldest segments
                                               are dropped (loudly, counted) past this cap.
                                               Default: {self.historyMaxMb}
  --perfGuard <warn|off>                       Cross-run perf regression sentinel: healthy-
                                               phase per-stage publish-tick medians stamp a
                                               baseline.json at clean shutdown; the next run
                                               raises ONE warn-only blackbox event +
                                               perf.regressions counter per stage episode
                                               when a stage sustains above
                                               --perfGuardRatio x baseline for a full
                                               window. Never aborts. Default: {self.perfGuard}
  --perfGuardRatio <float>                     Sustained-regression threshold for
                                               --perfGuard. Default: {self.perfGuardRatio}
  --profileDir <path>                          Enable jax.profiler traces
  --trace <path.trace>                         Write a Chrome-trace-event pipeline trace
                                               (Perfetto-loadable): per-batch stage spans
                                               (source read/parse/featurize/dispatch/fetch/
                                               stats) with wire bytes + health-phase stamps;
                                               summarize with tools/trace_report.py
  --traceMaxMb <int MB>                        Size-rotate the --trace file: the active
                                               segment becomes PATH.1 when it crosses this
                                               size (events falling off the old PATH.1 are
                                               counted in trace.dropped_events);
                                               trace_report stitches both segments. 0 =
                                               unbounded. Default: {self.traceMaxMb}
  --blackbox <on|off>                          Crash flight recorder: a bounded in-memory
                                               ring of recent spans/guard events/chaos
                                               firings/sideband rows, dumped as ONE
                                               post-mortem JSON bundle next to the
                                               checkpoint dir on any abort or SIGTERM;
                                               render with tools/postmortem_report.py.
                                               Default: {self.blackbox}
  --faultEvery <int tweets>                    Inject a receiver crash every N tweets (chaos testing)
  --chaos <spec>                               Transport chaos injection BELOW the source layer
                                               (testing the runtime guards): comma-separated
                                               TARGET:ACTION[@TRIGGER] clauses over targets
                                               fetch|step|web. ACTION: delay=SECONDS (stall= is
                                               an alias) or error. TRIGGER: N (every Nth call),
                                               pP (probability P), fromN (every call from the
                                               Nth on); plus seed=N. Example:
                                               "fetch:delay=2@3,web:error@p0.5,seed=7"
  --webTimeout <float seconds>                 Dashboard/web-API request timeout (per publish;
                                               the publish circuit breaker stops a dead
                                               dashboard from costing this per batch).
                                               Default: {self.webTimeout}
  --recycleAfterMb <int MB>                    Bounded process lifetime: checkpoint at the next
                                               batch boundary and re-exec in place once process
                                               RSS crosses this ceiling (needs --checkpointDir;
                                               single-host; resume is exact). 0 = off. Made for
                                               host memory that grows with uploaded bytes
                                               (tools/soak.py measures the slope)
  --elastic <off|on>                           Elastic lockstep membership: a dead or evicted
                                               host SHRINKS the multi-host group (survivors
                                               re-form at an epoch boundary, restore the lead's
                                               verified checkpoint, and adopt the departed
                                               intake shards) instead of aborting the run; a
                                               recovered host REJOINS at the next boundary.
                                               SGD entry points, explicit --processId/
                                               --numProcesses. Default: {self.elastic}
  --elasticEvictTicks <int>                    Elastic straggler eviction: propose shrinking
                                               out a host the sideband attributor names gating
                                               for this many CONSECUTIVE ticks (with skew over
                                               --elasticEvictSkewMs). 0 = never auto-evict
                                               (watchdog-detected death still shrinks).
                                               Default: {self.elasticEvictTicks}
  --elasticEvictSkewMs <float>                 Minimum tick skew (ms) before a gating host
                                               counts toward --elasticEvictTicks.
                                               Default: {self.elasticEvictSkewMs}
  --elasticRejoin <off|on>                     Whether the lead admits parked/restarted hosts
                                               back at epoch boundaries (rejoiners restore the
                                               broadcast checkpoint before their first tick).
                                               Default: {self.elasticRejoin}
  --tenants <int M>                            Multi-tenant model plane: train M models
                                               (per-topic/per-language/per-A/B-arm) in ONE
                                               jit program — rows route to tenants on the
                                               host, the M per-tenant batches ship as one
                                               shared wire (stacked or coalesced, --wirePack;
                                               dry tenants ride all-padding batches), and all
                                               M tenants' stats come back in ONE stacked fetch.
                                               A tenant costs the step at the rows its part
                                               is padded to: a row rung read off each batch
                                               (1.25*B/M up to 128 rows, doubling, B), so a
                                               uniform key pays for M*rung rows, a lopsided
                                               one up to M*B (PERF.md section 6, PR 36).
                                               Per-tenant semantics stay byte-identical to
                                               the single-model path. Default: {self.tenants}
  --tenantKey <hash|lang|all>                  Tenant routing key: 'hash' = deterministic
                                               content hash (A/B-arm style uniform split);
                                               'lang' = script-class heuristic from the
                                               text's code units (per-language scenarios;
                                               needs --hashOn device); 'all' = no routing:
                                               EVERY tenant trains on EVERY row (a champion,
                                               tenant 0, and its challengers: the same learner
                                               under --tenantStepSize / --tenantL2Reg). The
                                               batch ships ONCE as the single-model wire, the
                                               count matrix and the Gram matrix are built ONCE
                                               a batch and only u = C.w, the dual loop and
                                               C^T.alpha run per tenant; the batch's printed
                                               line is tenant 0's. One device, --wirePack
                                               stacked. Default: {self.tenantKey}
  --tenantStepSize <float,float,...>           Per-tenant step sizes, --tenants values in
                                               tenant order (tenant 0 first). Default: every
                                               tenant takes --stepSize
  --tenantL2Reg <float,float,...>              Per-tenant L2 strengths, --tenants values in
                                               tenant order. Default: every tenant takes --l2Reg
  --maxQueueRows <int rows>                    Bounded intake backpressure: cap the source→
                                               batcher queue at this many ROWS. 0 = auto
                                               (8 x --batchBucket when pinned, else unbounded);
                                               -1 = explicitly unbounded. Default: {self.maxQueueRows}
  --shedPolicy <block|shed-oldest>             Policy when the intake queue is full: 'block'
                                               makes the producer wait (replay/backfill — no
                                               rows lost); 'shed-oldest' drops the OLDEST
                                               queued rows, counted in ingest.rows_shed (live
                                               streams — freshest rows win). Default: {self.shedPolicy}
  --sentinel <on|off>                          Divergence sentinel: checks the already-fetched
                                               per-batch stats for NaN/Inf (zero extra host
                                               fetches); on non-finite state rolls the model
                                               back to the last verified-finite checkpoint
                                               (or initial zeros without --checkpointDir),
                                               skips the poisoning batch, and counts
                                               model.rollbacks. Default: {self.sentinel}
  --sentinelRollbacks <int>                    Abort the run (clean checkpointed non-zero
                                               exit) after this many rollbacks within
                                               --sentinelWindow batches; 0 = never abort.
                                               Default: {self.sentinelRollbacks}
  --sentinelWindow <int batches>               The rollback-rate window above.
                                               Default: {self.sentinelWindow}
  --modelWatch <on|off>                        Model & data observability plane: a small
                                               quality vector (weight/update/gradient norms,
                                               prediction/label/residual and dense-feature
                                               moments, hash-bucket occupancy) computed INSIDE
                                               the fused step and fetched with the stats it
                                               already ships (zero extra fetches); the host
                                               derives drift z-scores, a loss-trend slope, and
                                               ok/warn/alert health levels (/api/model +
                                               dashboard "model · drift" tiles; verified
                                               checkpoints are stamped with the quality
                                               snapshot — tools/model_report.py). 'off' makes
                                               the step program bit-identical to the
                                               pre-observability program. Default: {self.modelWatch}
  --modelWatchWindow <int batches>             Sentinel early warning: after the model watch
                                               holds 'alert' this many delivered batches, emit
                                               a blackbox event + counter and force ONE
                                               verified-checkpoint save per episode (warn-only;
                                               no rollback behavior change).
                                               Default: {self.modelWatchWindow}
  --freshness <on|off>                         End-to-end freshness plane: per-batch lineage
                                               records stamped at the existing pipeline seams
                                               (source read → featurize → wire pack → dispatch
                                               → fetch delivery → publish) derive event-time
                                               watermarks (freshness.event_lag_ms p50/p95/p99
                                               from tweet created_at_ms to delivery), a
                                               per-batch critical-path edge, and a low
                                               watermark that rides the lockstep sideband —
                                               zero added host fetches, zero added
                                               collectives (/api/freshness + dashboard
                                               "freshness · e2e lag" tiles). 'off' is the
                                               pre-plane program bit-exactly.
                                               Default: {self.freshness}
  --freshnessSloMs <float ms>                  Freshness SLO: when > 0 and the event→delivery
                                               lag stays above this for a sustained run of
                                               batches, emit a blackbox event + counter and
                                               force ONE verified-checkpoint save per episode
                                               (warn-only, sentinel untouched; the
                                               --modelWatchWindow early-warning shape).
                                               0 = no gate. Default: {self.freshnessSloMs}
  --servingStaleSloS <float s>                 Serving staleness SLO: when > 0 and the served
                                               snapshot's age (serving.snapshot_age_s)
                                               exceeds this, emit a blackbox event + counter
                                               once per breach episode (warn-only). 0 = no
                                               gate. Default: {self.servingStaleSloS}
  --blockWire <auto|on|off>                    Zero-copy native ingest for --ingest block:
                                               'on' parses raw block bytes straight into the
                                               ragged wire's unit representation (one C pass,
                                               uint8 units when every row is ASCII — no
                                               intermediate repack); byte-identical batches
                                               (tests/test_blockwire.py). auto = on whenever
                                               the effective wire is ragged; off = the legacy
                                               ParsedBlock parser. Default: {self.blockWire}
  --servePort <int>                            Serving entry point (apps/serve.py): port the
                                               in-process web server (dashboard + POST
                                               /api/predict front door) listens on.
                                               Default: {self.servePort}
  --serveBatchRows <int rows>                  Serving coalescer: dispatch a predict batch
                                               once this many rows are admitted (the padded
                                               row bucket of the predict program; requests
                                               larger than this are rejected).
                                               Default: {self.serveBatchRows}
  --serveMaxWaitMs <float ms>                  Serving coalescer: bounded admission latency —
                                               dispatch a partial batch once the OLDEST
                                               admitted request has waited this long.
                                               Default: {self.serveMaxWaitMs}
  --serveDepth <int>                           Concurrent in-flight predict-result fetches
                                               (overlapping device_gets, as the trainer's
                                               FetchPipeline does).
                                               Default: {self.serveDepth}
  --servePromoteEvery <float seconds>          Snapshot promoter poll cadence over
                                               --checkpointDir (new verified checkpoints
                                               hot-swap in if their quality stamp is
                                               ok/warn; alert refuses — the
                                               tools/model_report.py --gate predicate).
                                               Default: {self.servePromoteEvery}
  --abtest <on|off>                            Champion/challenger serving
                                               (apps/serve.py over a --tenants M >= 2
                                               tenant-stack checkpoint): live predict
                                               traffic is answered by the CHAMPION tenant
                                               and mirrored shadow-mode to every
                                               challenger inside the same one-dispatch
                                               predict program (zero added fetches);
                                               challengers are scored by the per-tenant
                                               quality stamps the trainer writes, and a
                                               strictly better challenger auto-promotes
                                               the champion pointer through the same
                                               is_promotable gate snapshots use (an
                                               alert-stamped challenger is refused and
                                               counted). Default: {self.abtest}
  --routerPort <int>                           Fleet router entry point (apps/router.py):
                                               port the front-door web server (POST
                                               /api/predict proxy + GET /api/fleet)
                                               listens on. Default: {self.routerPort}
  --replicas <url,url,...>                     Fleet router: comma-separated base URLs of
                                               the serve replicas to route over (e.g.
                                               http://host:8888,http://host:8889). Each
                                               replica is health-checked via its GET
                                               /api/serving; a failing replica is ejected
                                               behind a jittered backoff and its traffic
                                               retried on the others.
  --routePolicy <p99|hash>                     Fleet routing policy: 'p99' sends each
                                               request to the healthy replica with the
                                               lowest rolling forward p99 (ties: fewest
                                               in-flight); 'hash' consistent-hashes the
                                               request body onto a vnode ring so a given
                                               key sticks to one replica and only ~1/N of
                                               keys move on membership change.
                                               Default: {self.routePolicy}
  --wirePack <auto|stacked|group>              Tenant wire layout (--tenants) on the ragged wire:
                                               'group' coalesces the M tenant batches into ONE
                                               contiguous buffer (one put; uint16-delta offsets)
                                               unpacked inside the tenant program; 'stacked'
                                               ships M per-field arrays. auto = stacked until
                                               an on-chip paired verdict (ROADMAP S3;
                                               bit-identical
                                               features either way).
                                               Default: {self.wirePack}
  --wireCodec <auto|off|dict>                  Compressed ragged units wire: 'dict' digram-
                                               compresses the uint8 (all-ASCII) units buffer
                                               in the one C ingest pass (static dictionary,
                                               ~1.3-2x on tweet text) and decodes it INSIDE
                                               the jit program ahead of the ragged re-pad —
                                               byte-identical units (tests/test_wirecodec.py).
                                               Applies to the packed wire forms; non-ASCII
                                               (uint16) units and incompressible batches ship
                                               raw, counted in wire.codec_fallbacks. With
                                               --tenants, 'dict' + --wirePack auto resolves
                                               the group (coalesced) wire. auto = off until an
                                               on-chip paired verdict (ROADMAP S3).
                                               Default: {self.wireCodec}
  --wireAssemble <auto|on|off>                 Fused one-pass wire assembly (r17): 'on' builds
                                               every packed wire (flat / per-shard / coalesced
                                               group) in ONE native C sweep — units digram-
                                               encoded during the copy, uint16-delta offsets,
                                               sideband laid behind — into a pooled buffer
                                               arena (features/arena.py; leases retire when the
                                               batch's stats fetch delivers). Byte-identical
                                               wires and bitwise-equal trajectories vs the
                                               numpy pack pipeline (tests/test_wireassemble.py).
                                               auto = on whenever the native assembler is
                                               loadable (host-only work, no transport-regime
                                               gate); off = the numpy ground truth.
                                               Default: {self.wireAssemble}
  --featurizeNative <auto|on|off>              One-pass native featurize (r18): 'on' fills the
                                               ragged wire's arrays — flat units, padded
                                               offsets, scaled f32 numeric/label/mask — in ONE
                                               C sweep (native/featurize.cpp) into a pooled
                                               arena lease, on both ingest paths (object
                                               Status batches and parsed blocks). Bit-identical
                                               batches and trajectories vs the Python ground
                                               truth (tests/test_featurize_native.py). auto =
                                               on whenever the native emitter is loadable
                                               (host-only work, no transport-regime gate);
                                               off = the Python/numpy ground truth.
                                               Default: {self.featurizeNative}
"""

    def parse(self, args: list[str]) -> "ConfArguments":
        """Recursive flag parser, same shape as ConfArguments.scala:91-158."""
        if not args:
            try:
                self.tenant_recipes()  # the lists against --tenants, at once
                self.updater()  # --l1Reg against --l2Reg
            except ValueError as exc:
                raise SystemExit(str(exc))
            return self
        flag, rest = args[0], args[1:]

        def take() -> str:
            if not rest:
                self.printUsage(1)
            return rest[0]

        if flag in ("--master", "-m"):
            self.master = take()
        elif flag in ("--name", "-n"):
            self.setAppName(take())
        elif flag in ("--consumerKey", "-C"):
            set_property("twitter4j.oauth.consumerKey", take())
        elif flag in ("--consumerSecret", "-S"):
            set_property("twitter4j.oauth.consumerSecret", take())
        elif flag in ("--accessToken", "-A"):
            set_property("twitter4j.oauth.accessToken", take())
        elif flag in ("--accessTokenSecret", "-T"):
            set_property("twitter4j.oauth.accessTokenSecret", take())
        elif flag in ("--lightning", "-l"):
            self.lightning = take()
        elif flag in ("--twtweb", "-w"):
            self.twtweb = take()
        elif flag in ("--seconds", "-s"):
            self.seconds = int(take())
        elif flag in ("--stepSize", "-p"):
            self.stepSize = float(take())
        elif flag in ("--numIterations", "-i"):
            self.numIterations = int(take())
        elif flag in ("--miniBatchFraction", "-b"):
            self.miniBatchFraction = float(take())
        elif flag in ("--numRetweetBegin", "-B"):
            self.numRetweetBegin = int(take())
        elif flag in ("--numRetweetEnd", "-E"):
            self.numRetweetEnd = int(take())
        elif flag in ("--numTextFeatures", "-f"):
            self.numTextFeatures = int(take())
        elif flag == "--coordinator":
            self.coordinator = take()
        elif flag == "--numProcesses":
            self.numProcesses = int(take())
        elif flag == "--processId":
            self.processId = int(take())
        elif flag == "--backend":
            self.backend = take()
        elif flag == "--source":
            self.source = take()
        elif flag == "--replayFile":
            self.replayFile = take()
        elif flag == "--replaySpeed":
            self.replaySpeed = float(take())
        elif flag == "--batchBucket":
            self.batchBucket = int(take())
        elif flag == "--modelShards":
            self.modelShards = int(take())
            if self.modelShards < 1:
                self.printUsage(1)
        elif flag == "--tokenBucket":
            self.tokenBucket = int(take())
        elif flag == "--hashOn":
            self.hashOn = take()
            if self.hashOn not in ("device", "host"):
                self.printUsage(1)
        elif flag == "--ingest":
            self.ingest = take()
            if self.ingest not in ("object", "block"):
                self.printUsage(1)
        elif flag == "--wire":
            self.wire = take()
            if self.wire not in ("auto", "padded", "ragged"):
                self.printUsage(1)
        elif flag == "--blockWire":
            self.blockWire = take()
            if self.blockWire not in ("auto", "on", "off"):
                self.printUsage(1)
        elif flag == "--l2Reg":
            self.l2Reg = float(take())
        elif flag == "--l1Reg":
            self.l1Reg = float(take())
            if self.l1Reg < 0:
                self.printUsage(1)
        elif flag == "--convergenceTol":
            self.convergenceTol = float(take())
        elif flag == "--dtype":
            self.dtype = take()
        elif flag == "--checkpointDir":
            self.checkpointDir = take()
        elif flag == "--checkpointEvery":
            self.checkpointEvery = int(take())
        elif flag == "--journal":
            self.journal = take()
            if self.journal not in ("auto", "on", "off"):
                self.printUsage(1)
        elif flag == "--journalMaxMb":
            self.journalMaxMb = int(take())
            if self.journalMaxMb <= 0:
                self.printUsage(1)
        elif flag == "--history":
            self.history = take()
            if self.history not in ("auto", "on", "off"):
                self.printUsage(1)
        elif flag == "--historyMaxMb":
            self.historyMaxMb = int(take())
            if self.historyMaxMb <= 0:
                self.printUsage(1)
        elif flag == "--perfGuard":
            self.perfGuard = take()
            if self.perfGuard not in ("warn", "off"):
                self.printUsage(1)
        elif flag == "--perfGuardRatio":
            self.perfGuardRatio = float(take())
            if self.perfGuardRatio <= 1.0:
                self.printUsage(1)
        elif flag == "--profileDir":
            self.profileDir = take()
        elif flag == "--trace":
            self.trace = take()
        elif flag == "--traceMaxMb":
            self.traceMaxMb = int(take())
        elif flag == "--blackbox":
            self.blackbox = take()
            if self.blackbox not in ("on", "off"):
                self.printUsage(1)
        elif flag == "--wirePack":
            self.wirePack = take()
            if self.wirePack not in ("auto", "stacked", "group"):
                self.printUsage(1)
        elif flag == "--wireCodec":
            self.wireCodec = take()
            if self.wireCodec not in ("auto", "off", "dict"):
                self.printUsage(1)
        elif flag == "--wireAssemble":
            self.wireAssemble = take()
            if self.wireAssemble not in ("auto", "on", "off"):
                self.printUsage(1)
        elif flag == "--featurizeNative":
            self.featurizeNative = take()
            if self.featurizeNative not in ("auto", "on", "off"):
                self.printUsage(1)
        elif flag == "--recycleAfterMb":
            self.recycleAfterMb = int(take())
        elif flag == "--elastic":
            self.elastic = take()
            if self.elastic not in ("off", "on"):
                self.printUsage(1)
        elif flag == "--elasticEvictTicks":
            self.elasticEvictTicks = int(take())
        elif flag == "--elasticEvictSkewMs":
            self.elasticEvictSkewMs = float(take())
        elif flag == "--elasticRejoin":
            self.elasticRejoin = take()
            if self.elasticRejoin not in ("off", "on"):
                self.printUsage(1)
        elif flag == "--tenants":
            self.tenants = int(take())
            if self.tenants < 1:
                self.printUsage(1)
        elif flag == "--tenantKey":
            self.tenantKey = take()
            if self.tenantKey not in TENANT_KEYS:
                self.printUsage(1)
        elif flag == "--tenantStepSize":
            self.tenantStepSize = take()
        elif flag == "--tenantL2Reg":
            self.tenantL2Reg = take()
        elif flag == "--maxQueueRows":
            self.maxQueueRows = int(take())
        elif flag == "--shedPolicy":
            self.shedPolicy = take()
            if self.shedPolicy not in ("block", "shed-oldest"):
                self.printUsage(1)
        elif flag == "--sentinel":
            self.sentinel = take()
            if self.sentinel not in ("on", "off"):
                self.printUsage(1)
        elif flag == "--sentinelRollbacks":
            self.sentinelRollbacks = int(take())
        elif flag == "--sentinelWindow":
            self.sentinelWindow = int(take())
        elif flag == "--servePort":
            self.servePort = int(take())
        elif flag == "--serveBatchRows":
            self.serveBatchRows = int(take())
            if self.serveBatchRows < 1:
                self.printUsage(1)
        elif flag == "--serveMaxWaitMs":
            self.serveMaxWaitMs = float(take())
        elif flag == "--serveDepth":
            self.serveDepth = int(take())
            if self.serveDepth < 1:
                self.printUsage(1)
        elif flag == "--servePromoteEvery":
            self.servePromoteEvery = float(take())
        elif flag == "--abtest":
            self.abtest = take()
            if self.abtest not in ("on", "off"):
                self.printUsage(1)
        elif flag == "--routerPort":
            self.routerPort = int(take())
        elif flag == "--replicas":
            self.replicas = take()
        elif flag == "--routePolicy":
            self.routePolicy = take()
            if self.routePolicy not in ("p99", "hash"):
                self.printUsage(1)
        elif flag == "--modelWatch":
            self.modelWatch = take()
            if self.modelWatch not in ("on", "off"):
                self.printUsage(1)
        elif flag == "--modelWatchWindow":
            self.modelWatchWindow = int(take())
        elif flag == "--freshness":
            self.freshness = take()
            if self.freshness not in ("on", "off"):
                self.printUsage(1)
        elif flag == "--freshnessSloMs":
            self.freshnessSloMs = float(take())
            if self.freshnessSloMs < 0:
                self.printUsage(1)
        elif flag == "--servingStaleSloS":
            self.servingStaleSloS = float(take())
            if self.servingStaleSloS < 0:
                self.printUsage(1)
        elif flag == "--faultEvery":
            self.faultEvery = int(take())
        elif flag == "--chaos":
            self.chaos = take()
        elif flag == "--webTimeout":
            self.webTimeout = float(take())
        elif flag in ("--help", "-h"):
            self.printUsage(0)
        else:
            self.printUsage(1)
        return self.parse(rest[1:])

    def printUsage(self, exit_code: int) -> None:
        print(self.usage)
        raise SystemExit(exit_code)

    # -- derived ------------------------------------------------------------
    def effective_wire(self) -> str:
        """Resolve ``--wire auto`` (the default) for this configuration:
        RAGGED whenever the device hashes in a back-to-back regime (the
        bench path; no pad bytes uploaded, on every layout — what it buys
        on this machine is not measured, ROADMAP S3); PADDED for host
        hashing (the
        ragged wire ships raw code units by definition) and for WALL-CLOCK
        streaming (--seconds > 0): the ragged units bucket is
        data-dependent, so it cannot pre-compile before the stream starts
        (apps/common.warmup_compile) — a live run would stall for an
        in-stream compile on its first batch — while wall-clock intervals
        are latency-dominated and wire bytes don't bind there. Explicit ``--wire ragged``/``padded``
        always wins; explicit ragged with --hashOn host is rejected at
        source construction (apps/common.build_source)."""
        if self.wire != "auto":
            return self.wire
        if self.hashOn != "device" or self.seconds > 0:
            return "padded"
        return "ragged"

    def effective_block_wire(self) -> bool:
        """Resolve ``--blockWire``: whether block sources should parse
        through the zero-copy wire emitter (raw bytes → ragged-wire units
        in one C pass, features/native.parse_tweet_block_wire). ``auto``
        (the default) follows the effective wire: the emitter produces the
        RAGGED wire's unit representation (narrow uint8 units), so it is
        on exactly when the stream ships ragged; the padded wire keeps the
        legacy ParsedBlock parser (its C pad copy reads uint16). The
        batches are byte-identical either way — this flag moves work, not
        semantics (tests/test_blockwire.py) — and a library without the
        emitter degrades to the legacy parser on its own
        (features/native.py seam)."""
        if self.blockWire != "auto":
            return self.blockWire == "on"
        return self.effective_wire() == "ragged"

    def updater(self) -> str:
        """MLlib's ``GradientDescent`` has ONE updater a run:
        ``SimpleUpdater`` (no regularization), ``SquaredL2Updater``
        (``--l2Reg``) or ``L1Updater`` (``--l1Reg``). Both strengths at
        once are an elastic net, which is none of the three: refused."""
        if self.l1Reg > 0 and (
            self.l2Reg > 0 or any(
                float(v) > 0 for v in self.tenantL2Reg.split(",") if v.strip()
            )
        ):
            raise ValueError(
                f"--l1Reg {self.l1Reg} with --l2Reg {self.l2Reg}"
                + (f" / --tenantL2Reg {self.tenantL2Reg}"
                   if self.tenantL2Reg.strip() else "")
                + ": MLlib's GradientDescent runs ONE updater (L1Updater or "
                "SquaredL2Updater); an elastic net is neither — give one of "
                "the two strengths"
            )
        if self.l1Reg > 0:
            return "l1"
        return "l2" if self.l2Reg > 0 else "simple"

    def tenant_recipes(self) -> "tuple[list[float], list[float]]":
        """(step sizes, L2 strengths), one of each per tenant in tenant
        order: ``--tenantStepSize`` / ``--tenantL2Reg`` where given (exactly
        ``--tenants`` numbers, or refused), else ``--stepSize`` /
        ``--l2Reg`` for every tenant."""
        def one(flag: str, text: str, default: float) -> "list[float]":
            if not text.strip():
                return [float(default)] * self.tenants
            try:
                values = [float(v) for v in text.split(",")]
            except ValueError:
                raise ValueError(
                    f"{flag} takes comma-separated numbers, got {text!r}"
                ) from None
            if len(values) != self.tenants:
                raise ValueError(
                    f"{flag} names {len(values)} tenant(s), --tenants is "
                    f"{self.tenants}: give one value a tenant, tenant 0 first"
                )
            return values

        return (
            one("--tenantStepSize", self.tenantStepSize, self.stepSize),
            one("--tenantL2Reg", self.tenantL2Reg, self.l2Reg),
        )

    def effective_wire_pack(self) -> str:
        """Resolve ``--wirePack auto`` to the default tenant-stack
        wire layout. The coalesced group wire (one contiguous buffer per M
        tenant batches, uint16-delta offsets) is bit-identical to the
        stacked wire and ships one large transfer where the stacked wire
        ships M sets of per-field arrays, but the house rule — measure in
        the target regime before shipping a wire/dispatch change — holds
        the default at STACKED until an on-chip paired run clears it
        (ROADMAP D11).
        Explicit ``--wirePack group``/``stacked`` always wins — except the
        contradictory ``--wirePack stacked --wireCodec dict``, which is
        rejected below: the codec lives on the PACKED wire forms
        (compression compounds the per-array-overhead trap that made
        packing the lean-wire default), so a stacked tenant wire would
        silently ship the tenants' batches uncompressed."""
        if self.effective_wire_codec() == "dict":
            if self.wirePack == "stacked":
                raise ValueError(
                    "--wirePack stacked contradicts --wireCodec dict: the "
                    "codec rides the packed one-buffer wire (use "
                    "--wirePack group, or drop the codec)"
                )
            return "group"
        if self.wirePack != "auto":
            return self.wirePack
        return "stacked"

    def effective_wire_codec(self) -> str:
        """Resolve ``--wireCodec auto`` to the default units
        codec. ``dict`` (the digram codec, features/wirecodec.py) is only
        meaningful on the ragged raw-units wire — explicit ``dict`` with a
        padded/host-hash wire is rejected, like explicit ragged with
        ``--hashOn host``. ``auto`` follows the wirePack precedent: OFF
        until an on-chip paired verdict clears it (measure in the target
        regime before shipping a wire change: no cell runs it, ROADMAP
        D10)."""
        if self.wireCodec in ("off", "auto"):
            return "off"
        if self.effective_wire() != "ragged":
            raise ValueError(
                "--wireCodec dict needs the ragged raw-units wire "
                "(--wire ragged, or auto with --hashOn device and "
                "--seconds 0)"
            )
        return "dict"

    def effective_journal(self) -> bool:
        """Resolve ``--journal auto`` (the default): the durable intake
        journal is ON exactly when ``--checkpointDir`` is set — the replay
        cursor lives in verified checkpoint meta, so without checkpoints
        there is nothing exact to resume from (and the flag's whole point
        is the crash-equals-clean differential, tests/test_journal.py).
        Explicit ``on``/``off`` wins; explicit ``on`` without a checkpoint
        directory is rejected at install (apps/common.install_journal) —
        the journal needs a directory and a cursor authority. ``off`` is
        bit-exact pre-journal behavior: every hook no-ops."""
        if self.journal != "auto":
            return self.journal == "on"
        return bool(self.checkpointDir)

    def effective_history(self) -> bool:
        """Resolve ``--history auto`` (the default): the telemetry
        historian is ON exactly when ``--checkpointDir`` is set — its
        segments and the perfGuard baseline live under the checkpoint
        directory, so without one there is nowhere durable to append.
        Explicit ``on``/``off`` wins; explicit ``on`` without a checkpoint
        directory is rejected at install (apps/common.install_historian).
        ``off`` is bit-exact pre-historian behavior: the sample hook
        no-ops (tests/test_history.py byte-compares weights)."""
        if self.history != "auto":
            return self.history == "on"
        return bool(self.checkpointDir)

    def effective_max_queue_rows(self) -> int:
        """Resolve ``--maxQueueRows``: explicit > 0 wins; 0 (the default)
        sizes the bound from the batch size — 8 pinned row buckets is deep
        enough that the fill gate never starves, shallow enough that a
        stalled consumer bounds host RSS at ~8 batches of parsed rows.
        Without a pinned bucket there is no batch size to derive from, so
        0 stays unbounded (as does an explicit -1)."""
        if self.maxQueueRows > 0:
            return self.maxQueueRows
        if self.maxQueueRows < 0:
            return 0
        return 8 * self.batchBucket if self.batchBucket > 0 else 0

    def local_shards(self) -> int | None:
        """Parse Spark-style local[N] master hints; None means use all devices."""
        m = self.master
        if m.startswith("local[") and m.endswith("]"):
            inner = m[len("local[") : -1]
            if inner != "*":
                try:
                    return max(1, int(inner))
                except ValueError:
                    return None
        return None

    def validate_master(self) -> None:
        """Resolve --master into the runtime it names. ``local``/``local[N]``
        stay single-host; ``twtml://host:port`` is the cluster form (fills
        --coordinator); anything else — notably the reference's
        ``spark://host:port`` — is REJECTED: this runtime cannot honor it,
        and silently running single-host would be worse (VERDICT r2)."""
        m = self.master
        if m == "local" or (m.startswith("local[") and m.endswith("]")):
            return
        if m.startswith("twtml://"):
            addr = m[len("twtml://"):].rstrip("/")
            if not addr:
                raise SystemExit("--master twtml:// needs host:port")
            if self.coordinator and self.coordinator != addr:
                raise SystemExit(
                    f"--master {m} conflicts with --coordinator "
                    f"{self.coordinator}"
                )
            self.coordinator = addr
            return
        raise SystemExit(
            f"unsupported --master {m!r}: this is the TPU-native runtime — "
            "use local[N] for single-host, or twtml://host:port (equivalently "
            "--coordinator host:port --numProcesses N --processId I) for a "
            "multi-host jax.distributed group"
        )

    def multihost(self) -> "tuple[str, int, int] | None":
        """(coordinator, num_processes, process_id) when a multi-host group
        is requested; None for single-host runs. Called after
        ``validate_master`` so twtml:// masters are folded in."""
        if not self.coordinator:
            if self.numProcesses > 0 or self.processId >= 0:
                # half-specified cluster coordinates silently running
                # single-host would double-train the stream and race
                # checkpoint writers — reject, like bad --master schemes
                raise SystemExit(
                    "--numProcesses/--processId need --coordinator "
                    "host:port (or --master twtml://host:port)"
                )
            return None
        if self.numProcesses < 2 or self.processId < 0:
            raise SystemExit(
                "--coordinator requires --numProcesses >= 2 and "
                "--processId >= 0 (one unique id per process)"
            )
        if self.processId >= self.numProcesses:
            raise SystemExit(
                f"--processId {self.processId} out of range for "
                f"--numProcesses {self.numProcesses}"
            )
        return self.coordinator, self.numProcesses, self.processId
