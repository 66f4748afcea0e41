"""Shared app runtime: backend selection, source construction, and mesh-aware
model construction for every entry point.

The reference's one-flag cluster story (``--master local[N]`` / cluster
masters, ConfArguments.scala:95-98) applies to ALL its entry points because
Spark owns the runtime. Here the equivalent lives in ``build_model``: any
SGD-family app scales from one chip to a data-parallel device mesh by
constructing its learner through it (apps/linear_regression.py,
apps/logistic_regression.py; k-means has its own mesh-aware model,
models/kmeans.py), with the CLI face unchanged.
"""

from __future__ import annotations

from ..features.batch import wire_signature
from ..models.linear import StreamingLinearRegressionWithSGD
from ..streaming import faults as _faults
from ..streaming import journal as _journal
from ..streaming.sources import ReplayFileSource, Source, SyntheticSource
from ..telemetry import blackbox as _blackbox
from ..telemetry import freshness as _freshness
from ..telemetry import lineage as _lineage
from ..telemetry import metrics as _metrics
from ..telemetry import modelwatch as _modelwatch
from ..telemetry import sideband as _sideband
from ..telemetry import trace as _trace
from ..utils import get_logger

log = get_logger("apps.common")

# fetch-watchdog policy (see FetchWatchdog): the deadline derives from the
# health monitor's rolling fetch latency — generous multiples, because a
# re-issue only helps a LOST request, not a stalled one
FETCH_DEADLINE_MULT = 25.0
FETCH_DEADLINE_MIN_S = 30.0
FETCH_DEADLINE_MAX_S = 180.0
FETCH_RETRIES = 3


class FetchAbort(RuntimeError):
    """The fetch watchdog exhausted its retries: the run is aborting."""


def init_distributed(conf) -> bool:
    """The cluster face of every entry point (the reference's one-flag story:
    ``--master spark://host:port`` runs the same main on a cluster,
    ConfArguments.scala:95-98, README.md:44-55). Validates --master (bad
    schemes are rejected, not ignored), and when ``--coordinator``/
    ``twtml://`` asks for a multi-host group, joins it via
    ``parallel.distributed.initialize`` — which MUST happen before anything
    initializes the XLA backend, so apps call this first.

    ``--elastic on`` routes group formation through the elastic runtime
    instead (parallel/elastic.py): epoch-addressed custom clients whose
    dead-peer reaction is OURS (the lockstep watchdog + membership plane),
    not the coordination service's process-kill. A RESTARTED host finds a
    live run via the lead's beacon and parks for admission at the next
    epoch boundary — rejoining a mid-flight fleet with the same CLI that
    launched it.

    Returns True when this process should own telemetry/prints (the lead —
    process 0, or any single-host run)."""
    conf.validate_master()
    mh = conf.multihost()
    if mh is None:
        return True
    if conf.backend == "cpu":
        # cross-process CPU collectives need gloo selected BEFORE the
        # backend initializes (it is wired to the distributed client at
        # backend creation) — and the jax.process_index() probe
        # at the end of THIS function is the first backend init. Without
        # this, the documented multi-host CLI dies at its first
        # collective with "Multiprocess computations aren't implemented
        # on the CPU backend" (the test harness had set the flag by hand
        # since PR 1, which is why only raw CLI runs ever hit it).
        import jax

        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    coordinator, num_processes, process_id = mh
    if getattr(conf, "elastic", "off") == "on":
        return _init_elastic(conf, coordinator, num_processes, process_id)
    from ..parallel.distributed import initialize

    initialize(coordinator, num_processes, process_id)
    import jax

    return jax.process_index() == 0


def _init_elastic(conf, coordinator: str, num_processes: int,
                  process_id: int) -> bool:
    """Elastic group formation. Cold start: everyone forms epoch 0 with
    the full launch membership. A restarted host (the run is already live
    and this uid is not — or no longer — a member) parks at the beacon
    and joins at the epoch boundary the lead commits for it."""
    import os as _os
    import time as _time

    from ..parallel import elastic as _elastic

    if num_processes is None or process_id is None:
        raise SystemExit(
            "--elastic on needs explicit --numProcesses/--processId (or a "
            "twtml:// master with both): elastic membership has no "
            "cluster-env auto-detection"
        )
    host, _, port = coordinator.rpartition(":")
    runtime = _elastic.install_runtime(
        host or "127.0.0.1", int(port), process_id
    )
    launch_members = list(range(num_processes))
    if not getattr(conf, "checkpointDir", ""):
        log.warning(
            "--elastic on without --checkpointDir: membership changes "
            "re-synchronize from the lead's LIVE state instead of a "
            "verified on-disk checkpoint (reduced rollback guarantee)"
        )
    if process_id == 0 and runtime.beacon is not None:
        # cold start: uid 0 owns the beacon and leads the launch
        runtime.beacon.publish("forming", 0, launch_members)
        runtime.form(0, launch_members)
        import jax

        return jax.process_index() == 0
    # uid 0 with beacon=None is a RESTARTED ex-lead: an elected successor
    # owns the beacon port now, so it rejoins through the same follower
    # hello/park path as everyone else — demotion is losing the bind
    client = runtime.beacon_client()
    deadline = _time.monotonic() + _elastic._init_timeout_s()
    hello = None
    while _time.monotonic() < deadline:
        hello = client.request("hello", process_id)
        if hello is not None:
            break
        _time.sleep(0.5)
    if hello is None:
        raise SystemExit(
            f"--elastic on: the lead's membership beacon at "
            f"{host}:{runtime.beacon_port} never answered — is the lead up?"
        )
    # the answering beacon names the CURRENT lead (post-election it is the
    # winner's uid, not 0); a restarted ex-lead adopts its successor here
    runtime.set_lead(int(hello.get("lead_uid", 0)))
    if hello["state"] == "forming":
        runtime.form(0, launch_members)
    else:
        # live run: this is a RESTARTED host — park for admission
        log.warning(
            "elastic: run already live at epoch %d (members %s); parking "
            "this host (uid %d) for admission at the next epoch boundary",
            hello["epoch"], hello["members"], process_id,
        )
        joined = False
        park_deadline = _time.monotonic() + float(
            _os.environ.get("TWTML_ELASTIC_PARK_TIMEOUT_S", "") or 120.0
        )
        while _time.monotonic() < park_deadline:
            client.request("join", process_id)
            state = client.request("hello", process_id) or {}
            plan = (client.request("plan", process_id) or {}).get("plan")
            if plan and process_id in plan.get("members", []) and (
                plan["epoch"] > state.get("epoch", -1)
            ):
                # the admission plan names the lead that committed it (it
                # may have changed during the park window)
                runtime.set_lead(int(plan.get("lead_uid", runtime.lead_uid)))
                runtime.joined_late = True
                runtime.form(plan["epoch"], plan["members"])
                joined = True
                break
            _time.sleep(0.5)
        if not joined:
            raise SystemExit(
                "elastic: admission never committed within the park "
                "window (is --elasticRejoin off on the lead, or the "
                "group idle?)"
            )
    import jax

    return jax.process_index() == 0


def select_backend(conf) -> dict:
    """--backend {auto,tpu,cpu}: ``tpu`` fails unless jax's first device is
    a TPU; ``cpu`` forces the host backend (the reference's local[*] analog,
    ConfArguments.scala:54-56); ``auto`` keeps jax's platform choice and
    says so at WARNING when that choice is the CPU. Returns the device
    identity (platform / kind / count) every run record carries, so a run
    that landed on the CPU can never be read as a device result. Also the
    one place every entry point picks up the persistent compile cache."""
    import jax

    from ..utils.backend import (
        backends_initialized,
        configure_compile_cache,
        device_identity,
        set_cpu_device_count_hint,
    )

    configure_compile_cache()
    if getattr(conf, "dtype", "float32") == "float64":
        # without this, jnp silently downcasts f64 → f32 and the flag lies.
        # f64 is the CPU verification dtype (the reference's Java doubles,
        # LinearRegression.scala:32); TPU hardware has no f64 path.
        if conf.backend != "cpu":
            raise SystemExit(
                "--dtype float64 runs on the CPU backend only (TPU has no "
                "f64 hardware path); add --backend cpu"
            )
        jax.config.update("jax_enable_x64", True)
    shards = conf.local_shards()
    # honor the local[N] hint before any backend initialization; it only
    # affects the CPU platform, so it's harmless when TPU wins auto
    hint_dropped = bool(shards) and not set_cpu_device_count_hint(shards)
    if conf.backend == "cpu" and not backends_initialized():
        # jax_platforms no-ops once a backend is live; the outcome is
        # verified below either way
        jax.config.update("jax_platforms", "cpu")
    device = device_identity()  # initializes the backend
    if conf.backend in ("cpu", "tpu") and device["platform"] != conf.backend:
        raise RuntimeError(
            f"--backend {conf.backend} requested but this process computes "
            f"on {device['platform']!r} ({device['kind']} x{device['count']})"
            + (
                ": no TPU is visible to it" if conf.backend == "tpu" else
                ": a non-cpu backend is already initialized (and "
                "jax_default_device does not pin the CPU)"
            )
        )
    if hint_dropped and device["platform"] == "cpu" and device["count"] < shards:
        log.warning(
            "backend already initialized with %d CPU device(s): local[%d] "
            "is capped there", device["count"], shards,
        )
    if conf.backend == "auto" and device["platform"] == "cpu":
        log.warning(
            "--backend auto found no accelerator: this run computes on the "
            "CPU backend (%d device(s)); pass --backend tpu to make that an "
            "error", device["count"],
        )
    log.info(
        "backend: %s (%s) x%d", device["platform"], device["kind"],
        device["count"],
    )
    return device


def install_trace(conf) -> None:
    """``--trace PATH`` wiring shared by every entry point: activate the
    pipeline tracer (telemetry/trace.py). Multi-host runs suffix the path
    with the process index — every host traces its own pipeline; a shared
    path would clobber. Call after ``select_backend`` (reading the process
    count may initialize the backend)."""
    path = getattr(conf, "trace", "")
    if not path:
        return
    import jax

    if jax.process_count() > 1:
        path = f"{path}.p{jax.process_index()}"
    # size rotation (--traceMaxMb, default 256): a 600 s bench / multi-hour
    # soak must not grow the JSONL without bound — PATH.1 keeps the
    # previous segment, trace_report stitches them
    _trace.install(
        path,
        max_bytes=int(getattr(conf, "traceMaxMb", 256) or 0) * 1024 * 1024,
    )


def install_chaos(conf) -> None:
    """``--chaos SPEC`` wiring shared by every entry point: activate the
    seeded transport-fault injector (streaming/faults.py) over the
    fetch/step/web injection points. Multi-host note: injections are
    PER-HOST (each process parses the same spec with its own call
    counters); a step error on one host exercises the lockstep abort
    machinery exactly like a real host-local failure."""
    spec = getattr(conf, "chaos", "")
    if not spec:
        return
    try:
        _faults.install_chaos(spec)
    except ValueError as exc:
        raise SystemExit(f"bad --chaos spec: {exc}")


def install_blackbox(conf) -> None:
    """``--blackbox`` (default on) wiring shared by every entry point:
    activate the crash flight recorder (telemetry/blackbox.py). The bundle
    lands NEXT TO the checkpoint directory — the one place a post-crash
    operator already looks — or the tempdir when checkpoints are off. A
    SIGTERM dumps too (kill -TERM mid-soak leaves evidence). Call after
    ``select_backend`` (the process index may initialize the backend)."""
    if getattr(conf, "blackbox", "on") != "on":
        return
    import os as _os
    import tempfile as _tempfile

    import jax

    ckpt_dir = getattr(conf, "checkpointDir", "")
    out_dir = (
        _os.path.dirname(_os.path.abspath(ckpt_dir))
        if ckpt_dir else _tempfile.gettempdir()
    )
    cfg = {
        k: v for k, v in vars(conf).items()
        if not k.startswith("_conf") and isinstance(v, (str, int, float, bool))
    }
    cfg["_appName"] = conf.appName()
    _blackbox.install(
        config=cfg, out_dir=out_dir, process_index=jax.process_index()
    )
    _blackbox.install_signal_handler()


def install_journal(conf) -> None:
    """``--journal`` wiring shared by the FeatureStream entry points
    (linear/logistic; the k-means raw path has no featurize seam to
    journal at): open this host's durable intake journal
    (streaming/journal.py) so the seam in streaming/context.py appends.
    Per-host directories under ``--checkpointDir`` — the journal records
    THIS host's post-shard intake, keyed by the elastic uid (stable across
    epochs and restarts) or the launch process id, so a restarted host
    finds its own records. Call after ``init_distributed`` (needs the
    process identity) and before the StreamingContext is built."""
    if not conf.effective_journal():
        # a journal left installed by an earlier run() in the same process
        # (tests, embedded uses) would journal THIS run's seam too and
        # leak its committed-cursor pairing — --journal off must be
        # bit-exact pre-journal behavior
        _journal.uninstall()
        return
    if not getattr(conf, "checkpointDir", ""):
        raise SystemExit(
            "--journal on requires --checkpointDir: the replay cursor "
            "lives in verified checkpoint meta (use --journal auto to "
            "follow the checkpoint flag)"
        )
    import os as _os

    from ..parallel.elastic import get_runtime as _get_elastic_runtime

    runtime = _get_elastic_runtime()
    if runtime is not None:
        suffix = f"-u{runtime.uid}"
    else:
        import jax

        suffix = (
            f"-p{jax.process_index()}" if jax.process_count() > 1 else ""
        )
    _journal.install(
        _os.path.join(conf.checkpointDir, f"journal{suffix}"),
        max_mb=int(getattr(conf, "journalMaxMb", 512) or 512),
    )


def install_historian(conf) -> None:
    """``--history`` wiring shared by every entry point: open this host's
    telemetry historian (telemetry/historian.py) so the SessionStats
    publish seam samples into it. Per-host directories under
    ``--checkpointDir`` (the journal's keying: elastic uid, or the launch
    process id) — a restarted host appends after its own recovered tail,
    so one directory accumulates a multi-run timeline and the perfGuard
    baseline round-trips between runs. Call after ``init_distributed``."""
    if not conf.effective_history():
        # a historian left installed by an earlier run() in the same
        # process (tests, embedded uses) would sample THIS run's publish
        # ticks too — --history off must be bit-exact pre-historian
        from ..telemetry import historian as _historian

        _historian.uninstall()
        return
    if not getattr(conf, "checkpointDir", ""):
        raise SystemExit(
            "--history on requires --checkpointDir: the historian "
            "segments and the --perfGuard baseline live under it (use "
            "--history auto to follow the checkpoint flag)"
        )
    import os as _os

    from ..parallel.elastic import get_runtime as _get_elastic_runtime
    from ..telemetry import historian as _historian
    from ..utils.runid import config_fingerprint, next_run_id

    runtime = _get_elastic_runtime()
    if runtime is not None:
        suffix = f"-u{runtime.uid}"
    else:
        import jax

        suffix = (
            f"-p{jax.process_index()}" if jax.process_count() > 1 else ""
        )
    _historian.configure(
        _os.path.join(conf.checkpointDir, f"history{suffix}"),
        max_mb=int(getattr(conf, "historyMaxMb", 256) or 256),
        perf_guard=getattr(conf, "perfGuard", "warn") == "warn",
        guard_ratio=float(getattr(conf, "perfGuardRatio", 1.5) or 1.5),
        run_id=next_run_id(),
        fingerprint=config_fingerprint(conf),
    )


def build_source(
    conf,
    allow_block: bool = False,
    block_interval: "tuple[int, int] | None" = None,
) -> Source:
    """``allow_block``: set by entry points whose pipelines consume
    ParsedBlocks (linear: default labels; logistic: unit_label_fn; k-means:
    numeric columns, which passes ``block_interval`` to override the
    parser's retweet-count filter — it keeps ALL retweets)."""
    import jax

    multihost = jax.process_count() > 1
    if multihost and conf.source == "twitter" and conf.ingest == "block":
        # the block parser keeps no per-tweet ids, and ids are the only
        # shard key a live stream has (IdShardedSource) — refuse the
        # combination rather than silently double-train
        raise SystemExit(
            "multi-host live Twitter intake shards by tweet id, which "
            "--ingest block does not carry; use --ingest object"
        )
    if conf.effective_wire() == "ragged":
        if conf.hashOn != "device":
            raise SystemExit(
                "--wire ragged is a device-hash wire format; "
                "it requires --hashOn device"
            )
    if conf.ingest == "block" and not allow_block:
        raise SystemExit(
            "--ingest block is not wired for this entry point; "
            "use --ingest object"
        )
    if conf.ingest == "block" and conf.source not in ("replay", "twitter"):
        raise SystemExit("--ingest block requires --source replay or twitter")
    if conf.ingest == "block" and conf.hashOn != "device":
        raise SystemExit(
            "--ingest block ships raw code units (device hashing); "
            "--hashOn host requires --ingest object"
        )
    if conf.source == "replay":
        if not conf.replayFile:
            raise SystemExit("--source replay requires --replayFile <path.jsonl>")
        if conf.ingest == "block":
            from ..streaming.sources import BlockReplayFileSource

            if conf.replaySpeed:
                raise SystemExit(
                    "--ingest block replays as fast as possible; "
                    "drop --replaySpeed or use --ingest object"
                )
            begin, end = (
                block_interval
                if block_interval is not None
                else (conf.numRetweetBegin, conf.numRetweetEnd)
            )
            # multi-host: byte-range shard of the file per host — each host
            # parses ONLY its shard (SURVEY §2.4 L0: deserialization ships
            # to every executor), so config #1's native loader feeds
            # cluster runs too (r5; was a SystemExit)
            source: Source = BlockReplayFileSource(
                conf.replayFile, num_retweet_begin=begin, num_retweet_end=end,
                # zero-copy wire emitter (--blockWire): raw bytes → ragged
                # wire units in one C pass, byte-identical batches
                wire=conf.effective_block_wire(),
                shard_index=jax.process_index() if multihost else 0,
                shard_count=jax.process_count() if multihost else 1,
            )
            return _wrap_faults(source, conf)
        source = ReplayFileSource(conf.replayFile, speed=conf.replaySpeed)
    elif conf.source == "synthetic":
        source = SyntheticSource(rate=conf.replaySpeed or 0.0)
    elif conf.source == "twitter":
        from ..streaming.twitter import BlockTwitterSource, TwitterSource

        if conf.ingest == "block":
            # live block ingest (r5): raw stream lines batch into byte
            # blocks for the native C parser — no per-tweet Python objects
            # between the socket and the featurizer
            begin, end = (
                block_interval
                if block_interval is not None
                else (conf.numRetweetBegin, conf.numRetweetEnd)
            )
            source = BlockTwitterSource.from_properties(
                num_retweet_begin=begin, num_retweet_end=end,
                wire=conf.effective_block_wire(),
            )
            return _wrap_faults(source, conf)
        source = TwitterSource.from_properties()
        if multihost:
            from ..streaming.sources import IdShardedSource

            # live streams shard by tweet id (id ≡ processId mod N): every
            # host opens its own connection (duplicated ingress — tens of
            # KB/s at real stream rates) and keeps a disjoint residue
            # slice, so no tweet trains twice (r5; was a SystemExit)
            return _wrap_faults(
                IdShardedSource(
                    source, jax.process_index(), jax.process_count()
                ),
                conf,
            )
    else:
        raise SystemExit(f"unknown --source {conf.source!r}")
    if multihost:
        from ..streaming.sources import ShardedSource

        source = ShardedSource(
            source, jax.process_index(), jax.process_count()
        )
    return _wrap_faults(source, conf)


def _wrap_faults(source: Source, conf) -> Source:
    if conf.faultEvery > 0:
        from ..streaming.faults import FaultInjectingSource

        # finite replay files need the crash cap to avoid livelock (each
        # restart re-reads from the start); unbounded sources keep crashing
        source = FaultInjectingSource(
            source,
            crash_every=conf.faultEvery,
            max_crashes=3 if conf.source == "replay" else 0,
        )
    return source


def mesh_shape(conf) -> int:
    """Data-axis size the conf + attached devices call for: the number of
    visible devices, capped by the ``--master local[N]`` hint."""
    from ..utils.backend import run_devices

    shards = conf.local_shards()
    n_devices = len(run_devices())
    return min(shards, n_devices) if shards else n_devices


def build_mesh(conf, what: str = "training", model_axis: bool = False):
    """The one-flag cluster story: the mesh the conf calls for, or None
    when a single device (or local[1]) keeps execution unsharded. Every
    entry point routes through here so device selection / local[N] capping
    can never diverge between apps.

    The mesh is ``('data',)`` over the run's devices. A caller whose model
    can shard its WEIGHTS (``build_model``'s single-model SGD learners and,
    under ``--tenantKey all``, the arms of a champion/challenger run, which
    ARE that learner under M recipes) passes ``model_axis``: then
    ``--modelShards M`` (> 1) makes it ``('data', 'model')`` =
    (devices / M) x M, and M must divide the device count and
    ``--numTextFeatures``. Any other caller — k-means, the tenant plane
    under a partitioning key — refuses M > 1: a flag that is silently
    ignored would train another deployment than the one asked for.

    Multi-host runs span the WHOLE process group's devices; jax.devices()
    is process-major, so the 1D data axis is automatically process-aligned
    (the topology per-host intake sharding requires,
    parallel/distributed.py)."""
    import jax

    n_model = int(getattr(conf, "modelShards", 1) or 1)
    if n_model > 1 and (not model_axis or jax.process_count() > 1):
        raise SystemExit(
            f"--modelShards {n_model}: the model axis is wired at the entry "
            f"point for the single-model SGD learners (and their arms under "
            f"--tenantKey all) on one host, not for "
            f"{what}" + (" in a multi-host run" if model_axis else "")
        )
    if jax.process_count() > 1:
        from ..parallel import make_mesh

        if conf.local_shards():
            log.warning("--master local[N] hint ignored in a multi-host run")
        log.info(
            "multi-host %s: %d processes, %d global devices",
            what, jax.process_count(), jax.device_count(),
        )
        return make_mesh(num_data=jax.device_count(), devices=jax.devices())
    n_devices = mesh_shape(conf)
    if n_model > 1:
        f_text = conf.numTextFeatures
        if n_devices % n_model or f_text % n_model:
            raise SystemExit(
                f"--modelShards {n_model} must divide the run's "
                f"{n_devices} device(s) and --numTextFeatures {f_text}"
            )
    elif n_devices <= 1:
        return None

    from ..parallel import make_mesh
    from ..utils.backend import run_devices

    # the platform select_backend reported, never another one that happens
    # to be jax's first (a default device pinned to the CPU in a process
    # that also holds a chip)
    devices = run_devices()[:n_devices]
    if n_model == 1:
        log.info("mesh-sharded %s: %d-way data parallel", what, n_devices)
        return make_mesh(num_data=n_devices, devices=devices)
    log.info(
        "mesh-sharded %s: %d-way data x %d-way model (feature) parallel, "
        "%d hashed features a shard",
        what, n_devices // n_model, n_model, f_text // n_model,
    )
    return make_mesh(
        num_data=n_devices // n_model, num_model=n_model, devices=devices
    )


# --l1Reg (MLlib's L1Updater): where the per-iteration pass over the count
# matrix has no form yet, in a sentence each (build_model refuses with them)
L1_REFUSALS = {
    "arms": (
        "--l1Reg with --tenantKey all: the arms share C and G and map only "
        "the DUAL half of the basis (models/sgd.arms_dual_half), and the "
        "L1Updater's soft threshold has no dual form; run one --l1Reg model"
    ),
    "tenants": (
        "--l1Reg with --tenants > 1: the partitioned tenant plane maps the "
        "whole step over the tenants (lax.map), and the primal pass over "
        "each part's count matrix is not wired or tested there; run one "
        "--l1Reg model"
    ),
    "multihost": (
        "--l1Reg in a multi-host run: every one of the numIterations "
        "rounds would all-reduce a [numTextFeatures] gradient across the "
        "hosts; the primal pass runs on one device"
    ),
    "model_axis": (
        "--l1Reg with --modelShards > 1: the feature-sharded step "
        "(parallel/sharding.py) iterates in the Gram basis only; the primal "
        "pass has no form under a model axis"
    ),
    "data_axis": (
        "--l1Reg on a data mesh: each of the numIterations rounds would "
        "psum a [numTextFeatures] gradient (50 a batch); the primal pass "
        "runs on one device — pass --master local[1]"
    ),
}


def build_model(conf, model_cls=StreamingLinearRegressionWithSGD):
    """Single-device fused learner on one chip; mesh-sharded learner when the
    backend exposes several devices (or local[N] caps a virtual CPU mesh) —
    the CLI face of BASELINE config #5's data-parallel scale-up, for ANY
    SGD-family learner (the class's residual/prediction knobs carry over to
    the sharded step). Returns (model, required row multiple for batches).

    ``--tenants M`` (> 1) swaps in the multi-tenant model plane
    (parallel/tenants.TenantStackModel): M stacked models in ONE jit
    program sharing one wire and ONE stacked stats fetch per tick — the
    marginal tenant costs device FLOPs, not extra host fetches.
    Composes with the data mesh (rows P(data), tenant axis
    replicated); the cross-process tenants-on-model-axis layout is driven
    at the library level (tests/test_distributed_multiprocess.py) — the
    app-level multi-host wiring keeps its single-model plane for now.
    ``--tenantKey all`` (M recipes of the ONE learner on every row) runs
    on one device or, with ``--modelShards``, as the arms of the
    feature-sharded mesh model (``ParallelSGDModel(arms=...)``: C and G
    sharded and built once a batch for all arms); the data-only mesh, the
    group wire and a multi-host run keep their refusals."""
    import jax as _jax

    # --wireAssemble: the fused one-pass native pack (r17) is a process-
    # wide seam — every packer (plain / mesh / multi-host / tenant) rides
    # it through features/batch.py, so one configure covers them all
    from ..features import assemble as _assemble

    _assemble.configure(getattr(conf, "wireAssemble", "auto") or "auto")
    # --featurizeNative: the one-pass fused featurize (r18) is the same
    # kind of process-wide seam — both ingest paths ride it through the
    # featurizer, so one configure covers object and block streams
    from ..features import featurize_native as _ffz

    _ffz.configure(getattr(conf, "featurizeNative", "auto") or "auto")

    tenants = int(getattr(conf, "tenants", 1) or 1)
    # TWTML_FORCE_TENANT_PLANE=1 routes even --tenants 1 through the
    # stacked program — the app-level M=1 differential-parity hook (the
    # default path stays the plain single-model plane: a 1-tenant stream
    # must not pay the routing split)
    import os as _os

    force_plane = _os.environ.get("TWTML_FORCE_TENANT_PLANE") == "1"
    l1 = float(getattr(conf, "l1Reg", 0.0) or 0.0)
    if l1 > 0:
        # MLlib's L1Updater (models/sgd.py primal_basis): one device, one
        # model. Each refusal says what the per-iteration pass lacks there;
        # nothing falls to L2 or to the scatter loop in silence.
        if tenants > 1 or force_plane:
            if getattr(conf, "tenantKey", "hash") == "all":
                raise SystemExit(L1_REFUSALS["arms"])
            raise SystemExit(L1_REFUSALS["tenants"])
        if _jax.process_count() > 1:
            raise SystemExit(L1_REFUSALS["multihost"])
        if int(getattr(conf, "modelShards", 1) or 1) > 1:
            raise SystemExit(L1_REFUSALS["model_axis"])
        if mesh_shape(conf) > 1:
            raise SystemExit(L1_REFUSALS["data_axis"])
    if tenants > 1 or (force_plane and tenants == 1):
        if getattr(conf, "tenantKey", "hash") == "lang" and conf.hashOn != "device":
            raise SystemExit(
                "--tenantKey lang routes on raw code units; it requires "
                "--hashOn device"
            )
        from ..parallel.tenants import TenantStackModel
        from ..telemetry import tenants as _tenant_view

        # the Tenants frame and /api/tenants name each tenant's recipe
        _tenant_view.configure(*conf.tenant_recipes())
        # champion and challengers on the SAME rows (--tenantKey all): one
        # host; one device, or a mesh WITH a model axis (below;
        # parallel/tenants.py has the reason for each refusal)
        shared_rows = getattr(conf, "tenantKey", "hash") == "all"
        if shared_rows and _jax.process_count() > 1:
            raise SystemExit(
                "--tenantKey all is single-host: the tenant fleet assembles "
                "a stacked [M, ...] tenant wire across hosts, and under "
                "'all' there is no tenant wire"
            )
        if _jax.process_count() > 1:
            # app-level tenant fleet (r16, PR 7 REMAINING b; ragged wire
            # lifted in r20): the tenant stack behind per-host sharded
            # intake on the 1D process-aligned data mesh — the global
            # tenant wire assembles on the row axis of the stacked
            # [M, ...] leaves, ONE pooled fetch per tick, and the elastic
            # membership plane rebuilds it across epochs like the
            # single-model plane. Ragged tenant parts agree one shared
            # per-shard bucket fleet-wide (a single allgather-max per
            # batch — MultiHostTenantModel._stack_ragged_parts).
            from ..parallel.tenants import MultiHostTenantModel

            mesh = build_mesh(
                conf, what=f"tenant fleet ({model_cls.__name__})"
            )

            def tenant_rebuilder(new_mesh):
                return TenantStackModel.from_conf(
                    conf, new_mesh,
                    residual_fn=model_cls.residual_fn,
                    prediction_fn=model_cls.prediction_fn,
                    round_predictions=model_cls.round_predictions,
                )

            inner = tenant_rebuilder(mesh)
            model = MultiHostTenantModel(
                inner, mesh, rebuilder=tenant_rebuilder
            )
            log.info(
                "multi-tenant model FLEET: %d tenants across %d hosts, "
                "key=%s, stacked wire", tenants, _jax.process_count(),
                model.tenant_key,
            )
            return model, max(1, inner.num_data // _jax.process_count())
        # the arms ARE the single-model learner, so they may take its
        # model axis; a partitioning key keeps build_mesh's refusal
        mesh = build_mesh(
            conf, what=f"tenant plane ({model_cls.__name__})",
            model_axis=shared_rows,
        )
        if shared_rows and mesh is not None and len(mesh.axis_names) < 2:
            raise SystemExit(
                "--tenantKey all runs on one device (--master local[1]) or, "
                "with --modelShards, on a mesh with a model axis: the "
                "data-only mesh has no form of the per-arm half"
            )
        knobs = dict(
            residual_fn=model_cls.residual_fn,
            prediction_fn=model_cls.prediction_fn,
            round_predictions=model_cls.round_predictions,
        )
        try:
            if shared_rows and mesh is not None:
                # the arms of the feature-sharded mesh model: C and G
                # sharded, built once a batch for all M recipes
                from ..parallel import ParallelSGDModel
                from ..parallel.tenants import SHARED_ROWS_GROUP_WIRE

                if conf.wirePack == "group":
                    raise ValueError(
                        f"--tenantKey all: {SHARED_ROWS_GROUP_WIRE}"
                    )
                model = ParallelSGDModel.from_conf(
                    conf, mesh, arms=conf.tenant_recipes(), **knobs
                )
                codec = getattr(conf, "effective_wire_codec", lambda: "off")()
                model.wire_codec = codec if codec == "dict" else ""
            else:
                model = TenantStackModel.from_conf(conf, mesh, **knobs)
        except ValueError as exc:  # what the plane refuses, in its words
            raise SystemExit(str(exc)) from None
        log.info(
            "multi-tenant model plane: %d tenants, key=%s, wire=%s",
            tenants, model.tenant_key, model.wire_pack,
        )
        log.info("tenant recipes: stepSize %s, l2Reg %s",
                 *conf.tenant_recipes())
        return model, (mesh.shape[mesh.axis_names[0]] if mesh else 1)
    mesh = build_mesh(
        conf, what=f"training ({model_cls.__name__})", model_axis=True
    )
    codec = getattr(conf, "effective_wire_codec", lambda: "off")()
    if mesh is not None:
        from ..parallel import ParallelSGDModel

        def sgd_rebuilder(new_mesh):
            if new_mesh is None:
                # an elastic fleet shrunk to one host with one device:
                # build_mesh legitimately says "unsharded", but the
                # MultiHost wrapper's step/pack surface needs A mesh — a
                # 1-device data mesh is the same math (shard_map over one
                # shard) and keeps every holder of the wrapper working
                import jax as _jax_inner

                from ..parallel import make_mesh

                new_mesh = make_mesh(
                    num_data=1, devices=_jax_inner.devices()[:1]
                )
            return ParallelSGDModel.from_conf(
                conf, new_mesh,
                residual_fn=model_cls.residual_fn,
                prediction_fn=model_cls.prediction_fn,
                round_predictions=model_cls.round_predictions,
            )

        model = sgd_rebuilder(mesh)
        import jax

        if jax.process_count() > 1:
            from ..parallel.distributed import MultiHostSGDModel

            # the app featurizes only THIS host's rows: its local batch
            # must divide this host's share of the data axis. The codec
            # bucket (r16) is agreed on the SAME alignment allgather the
            # raw bucket already pays — zero new collectives.
            mh = MultiHostSGDModel(model, mesh, rebuilder=sgd_rebuilder)
            mh.wire_codec = codec if codec == "dict" else ""
            return mh, max(1, model.num_data // jax.process_count())
        # single-process mesh: the mesh packs compress per shard segment
        # (parallel/sharding.py pack_for_wire)
        model.wire_codec = codec if codec == "dict" else ""
        return model, model.num_data
    return model_cls.from_conf(conf), 1


def state_checksum(state) -> str:
    """CRC of a checkpointable state (flat dict or one array) — logged at
    recycle-save and at restore so a recycled run's logs PROVE the
    post-restart weights are bit-identical to the pre-exec save
    (tests/test_recycler.py asserts the two lines match)."""
    import zlib

    import numpy as np

    arrs = state if isinstance(state, dict) else {"state": state}
    crc = 0
    for key in sorted(arrs):
        a = np.ascontiguousarray(np.asarray(arrs[key]))
        crc = zlib.crc32(
            a.tobytes(),
            zlib.crc32(f"{key}:{a.dtype}:{a.shape}".encode(), crc),
        )
    return f"{crc:08x}"


def tenant_stamp(conf) -> "dict | None":
    """What a tenant-stack checkpoint's rows ARE: the routing key and each
    tenant's recipe, in tenant order (``meta["tenants"]``). A ``[M, F+4]``
    array alone does not say whether row 2 is a hash bucket's model or the
    challenger under half the step size; ``AppCheckpoint`` refuses to
    resume a stack under another stamp and ``apps/serve --abtest on``
    reports it. None on the single-model plane."""
    m = int(getattr(conf, "tenants", 1) or 1)
    recipes = getattr(conf, "tenant_recipes", None)
    if m < 2 or recipes is None:
        return None
    steps, l2s = recipes()
    return {
        "count": m, "key": getattr(conf, "tenantKey", "hash"),
        "stepSize": steps, "l2Reg": l2s,
    }


class AppCheckpoint:
    """``--checkpointDir``/``--checkpointEvery`` wiring shared by every entry
    point (model checkpoint/resume is this framework's upgrade over the
    reference, SURVEY.md §5.4 — a restarted reference job begins from
    zeros). Restores state + counters at startup, saves on a cadence-
    crossing test at weight-current boundaries (a pipeline drain snaps to
    the first boundary at/after each cadence point), and saves final state
    at shutdown.

    ``get_state()`` returns the checkpointable arrays (flat dict or one
    array); ``set_state(state)`` restores them into the model.

    Multi-host: only the lead (``lead=True``) WRITES the fleet directory
    (concurrent writers against one directory would race), and restore is
    LEAD-AUTHORITATIVE — after the local restore attempt, the lead's
    state/counters are broadcast to every process, so a follower without
    the lead's filesystem (no shared storage) still resumes consistently
    instead of silently training from zeros against resumed peers.

    Elastic fleets (r20): every NON-lead host shadow-saves the same
    verified archives into its own ``standby-u<uid>/`` subdirectory on the
    same cadence — training is psum-identical, so the archives are
    bit-identical to the lead's. That is the any-host-can-restore
    discipline lead election relies on: ``promote()`` flips authority
    after a won election and the new lead resyncs the fleet from its OWN
    verified archives (no shared storage assumed). Broadcast sourcing
    follows ``_lead`` (not hardcoded process 0), so authority tracks the
    elected lead whatever its epoch pid is."""

    def __init__(self, conf, get_state, set_state, totals: dict,
                 lead: bool = True):
        self._ckpt = None
        self._get_state = get_state
        self._set_state = set_state
        from ..parallel.elastic import get_runtime as _get_elastic_runtime

        runtime = _get_elastic_runtime()
        self._elastic = runtime is not None
        self._lead = runtime.is_lead if self._elastic else lead
        self._shadow = self._elastic and not self._lead
        self.every = int(getattr(conf, "checkpointEvery", 0) or 0)
        self.restored_meta = None
        self._tenants = tenant_stamp(conf)
        if not conf.checkpointDir:
            self._last = 0
            return
        from ..checkpoint import Checkpointer

        ckpt_dir = conf.checkpointDir
        if self._shadow:
            import os as _os

            ckpt_dir = _os.path.join(
                conf.checkpointDir, f"standby-u{runtime.uid}"
            )
            log.info(
                "elastic standby checkpoints: this host shadow-saves "
                "verified archives into %s (any-host-can-restore)",
                ckpt_dir,
            )
        self._ckpt = Checkpointer(ckpt_dir)
        restored = self._ckpt.restore()
        # this host's OWN restored meta (followers restore their shadow
        # archives): the intake journal's boot replay reads its cursor
        # stamp from here (journal_boot_replay) — per-host, never the
        # broadcast (each host replays its own journal)
        self.restored_meta = restored[1] if restored is not None else None
        if restored is not None:
            state, meta = restored
            was = meta.get("tenants")
            if self._tenants and was and was != self._tenants:
                raise SystemExit(
                    f"checkpoint step {meta.get('step')} in {ckpt_dir!r} "
                    f"holds the tenant stack of {was}; this run asks for "
                    f"{self._tenants}. A row of the stack is ONE tenant's "
                    "model under ONE recipe: resume with the key and the "
                    "per-tenant lists it was trained under, or start a new "
                    "--checkpointDir"
                )
            set_state(state)
            totals["count"] = int(meta.get("count", 0))
            totals["batches"] = int(meta.get("batches", 0))
            log.info(
                "resumed from checkpoint step %s (count=%s, state crc %s)",
                meta.get("step"), totals["count"], state_checksum(state),
            )
        import jax

        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils

            # every process contributes its own (structurally identical)
            # state; all receive the LEAD's — the lead is the fleet-dir
            # writer, so its view of the checkpoint is the truth. Source
            # by _lead, not process 0: after an election the lead's epoch
            # pid is whatever the member sort gives it.
            meta_arr, state = multihost_utils.broadcast_one_to_all((
                np.array(
                    [int(restored is not None),
                     totals["count"], totals["batches"]], np.int64,
                ),
                get_state(),
            ), is_source=bool(self._lead))
            # unconditional: a follower restoring a STALE local checkpoint
            # while the lead starts fresh must also converge on the lead
            set_state(jax.tree_util.tree_map(np.asarray, state))
            totals["count"] = int(meta_arr[1])
            totals["batches"] = int(meta_arr[2])
            if int(meta_arr[0]) and restored is None:
                log.info(
                    "resumed from the lead's broadcast checkpoint "
                    "(count=%s)", totals["count"],
                )
            # every host logs the post-broadcast crc: an elastic rejoiner's
            # first-tick weights must BIT-match the lead's, and matching
            # crc lines across hosts are the assertable proof
            log.info(
                "multi-host state synchronized from the lead (count=%s, "
                "state crc %s)", totals["count"],
                state_checksum(self._get_state()),
            )
        self._last = totals["batches"]

    def _save(self, totals: dict) -> None:
        if not self._lead and not self._shadow:
            self._last = totals["batches"]  # keep cadence bookkeeping aligned
            return
        j = _journal.get()
        if j is not None and not j.save_allowed:
            # mid-replay: the weights already re-trained past the rollback
            # cursor, but the committed cursor cannot advance until the
            # final replayed batch delivers — a save now would stamp a
            # cursor whose replay double-trains on crash-restore. Defer;
            # _last stays put so the cadence retries next boundary.
            log.info(
                "checkpoint save deferred at batch %s: journal replay "
                "still draining (retries next boundary)",
                totals["batches"],
            )
            return
        meta = {"count": totals["count"], "batches": totals["batches"]}
        # quality stamp (ISSUE 8): every verified checkpoint records the
        # model-health picture at save time — the promotion-gate substrate
        # the serving plane reads (tools/model_report.py renders history)
        quality = _modelwatch.snapshot_for_checkpoint()
        if quality is not None:
            meta["quality"] = quality
        # freshness stamp (ISSUE 16): the event-lag/watermark picture at
        # save time, so checkpoint history carries the staleness story
        fresh = _freshness.snapshot_for_checkpoint()
        if fresh is not None:
            meta["freshness"] = fresh
        # journal cursor stamp (ISSUE 19): saves run at weight-current
        # boundaries on the thread that featurizes, so every record with
        # id < cursor is inside the state being saved — the replay-exact
        # resume point for rollback/resync/restart (streaming/journal.py)
        jstamp = _journal.snapshot_for_checkpoint()
        if jstamp is not None:
            meta["journal"] = jstamp
        if self._tenants is not None:
            meta["tenants"] = self._tenants
        self._ckpt.save(totals["batches"], self._get_state(), meta)
        self._last = totals["batches"]
        if jstamp is not None:
            # bounded disk: segments retire once covered by EVERY retained
            # verified archive (a fallback restore can land on the oldest)
            oldest = self._ckpt.oldest_meta()
            covered = ((oldest or {}).get("journal") or {}).get("cursor")
            if covered is not None:
                _journal.get().retire_covered(int(covered))
        # sticky flight-recorder context: a post-mortem bundle names the
        # checkpoint a restart will resume from (telemetry/blackbox.py)
        _blackbox.note(
            "last_checkpoint",
            {"step": totals["batches"], "count": totals["count"]},
        )

    def maybe_save(self, totals: dict, at_boundary: bool = True) -> None:
        """Cadence save — call per batch from the app's handler."""
        if self._ckpt is not None and at_boundary and self.every > 0 and (
            totals["batches"] - self._last >= self.every
        ):
            self._save(totals)

    def final_save(self, totals: dict) -> None:
        """Shutdown save when anything advanced past the last save."""
        if self._ckpt is not None and totals["batches"] != self._last:
            self._save(totals)

    def save_now(self, totals: dict) -> bool:
        """Unconditional save (the recycler's pre-exec snapshot). Returns
        False when no checkpoint dir is configured."""
        if self._ckpt is None:
            return False
        self._save(totals)
        return True

    def own_journal_stamp(self, batches: int) -> "dict | None":
        """This host's journal cursor for the agreed rollback point: the
        newest LOCAL archive's stamp, valid only when its ``batches``
        matches the lead-agreed value (cadence saves are psum-aligned, so
        lead and shadow archives land on the same batch indices; a stale
        or missing local archive — fresh joiner, pre-journal history —
        returns None and the caller falls back to counted loss). Local
        disk read only: zero added fetches, zero added collectives."""
        if self._ckpt is None:
            return None
        meta = self._ckpt.latest_meta()
        if meta is None or int(meta.get("batches", -1)) != int(batches):
            return None
        return meta.get("journal")

    def adopt_replay_totals(self, totals: dict, count, batches) -> None:
        """Reset the run counters to a rollback point whose rows a journal
        replay is about to re-ingest: the replayed rows re-count through
        the unchanged handler path, so the final ledger matches an
        unfailed run (the crash-equals-clean differential). Keeps the
        cadence bookkeeping aligned so post-replay saves fire on the same
        boundaries as a clean run."""
        totals["count"] = int(count)
        totals["batches"] = int(batches)
        self._last = totals["batches"]

    def promote(self) -> None:
        """Elastic lead handoff: this host won an election. Its standby
        archives become the fleet's checkpoint lineage — future saves
        continue into the same (formerly standby) directory, and the next
        ``resync_from_verified`` restores from them and broadcasts with
        this host as the source. Idempotent."""
        if self._lead:
            return
        self._lead = True
        self._shadow = False
        if self._ckpt is not None:
            log.warning(
                "checkpoint authority PROMOTED after lead election: this "
                "host's verified archives in %s are the fleet lineage now",
                self._ckpt.directory,
            )
        from ..telemetry import blackbox as _blackbox

        _blackbox.record(
            "checkpoint_promoted",
            directory=getattr(self._ckpt, "directory", ""),
        )

    def resync_from_verified(self, totals: dict) -> bool:
        """Elastic epoch re-synchronization (r16): every member of a
        just-formed epoch converges on the LEAD's state + counters — its
        newest verified on-disk checkpoint when one exists (the documented
        rollback guarantee: a clean commit saves at the boundary first, so
        it loses nothing; a rescue rolls back at most --checkpointEvery
        batches), else its live weights (checkpoints off — survivors are
        psum-identical anyway, and a joiner still inherits the truth).
        Rolled-back rows are counted (``elastic.rows_rolled_back``), never
        silent. Single-process epochs (a fleet shrunk to one host) restore
        locally with no collective. Returns False only when there is
        neither a checkpoint nor a multi-host broadcast to sync from (the
        degenerate 1-host/no-disk case — state simply continues)."""
        import jax

        restored = (
            self._ckpt.restore()
            if self._ckpt is not None and self._lead else None
        )
        old_count = int(totals.get("count", 0))

        def adopt(state, count, batches) -> None:
            self._set_state(state)
            totals["count"] = int(count)
            totals["batches"] = int(batches)
            self._last = totals["batches"]
            rolled = max(0, old_count - totals["count"])
            if rolled:
                _metrics.get_registry().counter(
                    "elastic.rows_rolled_back"
                ).inc(rolled)
            log.warning(
                "elastic resync: state from the lead's %s (count=%d, "
                "batches=%d, state crc %s)%s",
                "verified checkpoint" if restored is not None or not (
                    self._lead
                ) else "live weights",
                totals["count"], totals["batches"], state_checksum(state),
                f" — {rolled} row(s) of post-checkpoint progress rolled "
                f"back (counted)" if rolled else "",
            )

        if jax.process_count() <= 1:
            if restored is None:
                return False
            state, meta = restored
            adopt(state, meta.get("count", 0), meta.get("batches", 0))
            return True
        import numpy as np
        from jax.experimental import multihost_utils

        state = self._get_state()
        count, batches = totals.get("count", 0), totals.get("batches", 0)
        if restored is not None:
            state = restored[0]
            count = restored[1].get("count", 0)
            batches = restored[1].get("batches", 0)
        meta_arr, state = multihost_utils.broadcast_one_to_all((
            np.array([1, count, batches], np.int64), state,
        ), is_source=bool(self._lead))
        adopt(
            jax.tree_util.tree_map(np.asarray, state),
            int(meta_arr[1]), int(meta_arr[2]),
        )
        return True

    def rollback_to_verified(self) -> "dict | None":
        """Restore the newest VERIFIED (checksummed, finite) checkpoint
        into the model — the divergence sentinel's recovery hook. Returns
        the checkpoint meta, or None when no verified checkpoint exists
        (checkpoints off, empty dir, or every archive corrupt/non-finite).

        Multi-host: lead-authoritative like the startup restore — the lead
        restores from disk and its state broadcasts to every process (a
        follower has no checkpoint files). All hosts MUST call this on the
        same tick (the sentinel guarantees it: stats are psum-global and
        deliveries deterministic, verified by the rollback count riding
        the cadence allgather), because the broadcast is a collective."""
        restored = (
            self._ckpt.restore() if self._ckpt is not None else None
        )
        if restored is not None:
            _blackbox.note(
                "last_verified_rollback",
                {"step": restored[1].get("step")},
            )
        import jax

        if jax.process_count() <= 1:
            if restored is None:
                return None
            state, meta = restored
            self._set_state(state)
            return meta
        import numpy as np
        from jax.experimental import multihost_utils

        ok = int(restored is not None) if self._lead else 0
        # EVERY host fetches its current state first: the broadcast needs a
        # structurally identical pytree per process, and get_state itself
        # may be a collective (MultiHostSGDModel.latest_weights allgathers)
        # — the lead must participate too, then its disk state wins
        state = self._get_state()
        count = batches = 0
        if self._lead and restored is not None:
            state = restored[0]
            count = int(restored[1].get("count", 0))
            batches = int(restored[1].get("batches", 0))
        # the flags carry the agreed (count, batches) rollback point on the
        # SAME broadcast — a follower needs it to locate its OWN journal
        # cursor for replay (own_journal_stamp); zero added collectives
        flag, state = multihost_utils.broadcast_one_to_all((
            np.array([ok, count, batches], np.int64), state,
        ), is_source=bool(self._lead))
        if not int(flag[0]):
            return None
        self._set_state(jax.tree_util.tree_map(np.asarray, state))
        if self._lead and restored is not None:
            return restored[1]
        return {
            "broadcast": True,
            "count": int(flag[1]),
            "batches": int(flag[2]),
        }


def journal_replay_rollback(ssc, ckpt: AppCheckpoint, totals: dict, meta,
                            where: str) -> "int | None":
    """Re-ingest every journaled row after the rollback point ``meta``
    names — the conversion of a counted-loss site into a replay-exact one
    (ISSUE 19). Returns rows replayed (0 when the cursor was already at
    the tail), or None when replay was impossible (journal off, or no
    local cursor for the agreed point) — the caller keeps its counted-loss
    accounting then.

    ``meta`` is the rollback target's checkpoint meta: a full local meta
    (single-host / lead), a broadcast stub carrying (count, batches) (a
    follower locates its OWN cursor via ``own_journal_stamp``), or None —
    no verified checkpoint existed, the model was reset to initial zeros,
    and the WHOLE journal replays from cursor 0 (crash-equals-clean holds
    even before the first save).

    Host-side only: disk reads + queue putbacks at the FRONT (row order
    preserved; the replayed rows re-cross the unchanged featurize path
    under append suppression). Multi-host replay rides the existing
    lockstep cadence — a host with fewer replayed rows dispatches
    all-padding ticks per the lockstep invariant; ZERO new collectives,
    zero added fetches."""
    j = _journal.get()
    if j is None:
        return None
    if meta is None:
        count = batches = 0
        stamp = {"cursor": 0, "rows": 0}
    else:
        count = int(meta.get("count", 0))
        batches = int(meta.get("batches", 0))
        stamp = meta.get("journal")
        if stamp is None:
            stamp = ckpt.own_journal_stamp(batches)
    if stamp is None:
        log.warning(
            "journal: no local cursor for the agreed rollback point "
            "(batches=%d) after %s — rows stay counted as lost, not "
            "replayed (stale/missing local archive or pre-journal "
            "history)", batches if meta is not None else -1, where,
        )
        return None
    cursor = int(stamp["cursor"])
    # an EARLIER replay still draining (a storm re-poisons a replayed row,
    # or a reform lands mid-drain) is superseded by this one — its cursor
    # is at or below the old one, so its items re-cover the stale rows
    # still parked at the queue front. Remove them before the new putback
    # or the overlap trains twice.
    stale = j.cancel_pending_replay()
    if stale:
        queued = ssc._drain(0)
        qrows = sum(getattr(s, "rows", 1) for s in queued)
        keep = (
            _journal.IntakeJournal._split_items(queued, stale)
            if qrows > stale else []
        )
        ssc._putback(keep)
        log.warning(
            "journal: superseded an in-progress replay — dropped %d stale "
            "queued row(s) the new replay from cursor %d re-covers",
            min(stale, qrows), cursor,
        )
    items, rows = j.replay_from(cursor)
    ssc._putback(items)
    ckpt.adopt_replay_totals(totals, count, batches)
    _blackbox.record(
        "journal_replay", where=where, rows=rows, cursor=cursor,
        count=count, batches=batches,
    )
    log.warning(
        "journal: replayed %d row(s) from cursor %d after %s — counters "
        "reset to (count=%d, batches=%d); recovery is replay-exact, zero "
        "rows lost", rows, cursor, where, count, batches,
    )
    return rows


def journal_boot_replay(conf, ssc, ckpt: AppCheckpoint, totals: dict) -> int:
    """Boot half of journal recovery (watchdog-abort restart, kill -9,
    recycle): every row this host ever journaled is either inside the
    restored checkpoint (id < cursor) or re-enqueued here from the journal
    (id >= cursor), and the deterministic source fast-forwards past ALL of
    them (``SkipRowsSource``) instead of re-producing from the top. Call
    after ``AppCheckpoint`` restores and before the stream starts."""
    j = _journal.get()
    if j is None:
        return 0
    from ..parallel import elastic as _elastic

    rt = _elastic.get_runtime()
    if rt is not None and rt.joined_late:
        # this host's pre-departure coverage moved to its adopters when
        # the fleet reformed without it — replaying (or fast-forwarding
        # past) its old journal would double-train adopted rows
        j.reset()
        log.warning(
            "journal: reset on late join — this host's pre-departure "
            "rows belong to their adopters now; boot replay skipped"
        )
        return 0
    meta = getattr(ckpt, "restored_meta", None)
    stamp = (meta or {}).get("journal")
    if meta is not None and stamp is None:
        log.warning(
            "journal: the restored checkpoint carries no journal cursor "
            "(pre-journal archive) — boot replay skipped; the source "
            "re-produces from its top as a bare checkpoint-restart would"
        )
        return 0
    if meta is not None and int(meta.get("batches", -1)) != int(
        totals.get("batches", 0)
    ):
        # multi-host: the lead's broadcast moved the counters away from
        # this host's own archive — its cursor no longer names the
        # adopted state, so an exact replay is off the table
        log.warning(
            "journal: local archive (batches=%s) disagrees with the "
            "adopted counters (batches=%s) — boot replay skipped",
            meta.get("batches"), totals.get("batches"),
        )
        return 0
    cursor = int(stamp["cursor"]) if stamp is not None else 0
    skip_rows = j.rows_total
    items, rows = j.replay_from(cursor)
    ssc._putback(items)
    # fast-forward only sources that RE-PRODUCE the same rows on restart
    # (replay file, seeded synthetic) — a live stream never re-produces,
    # so skipping would drop fresh rows, not duplicates
    fast_forward = skip_rows if conf.source != "twitter" else 0
    if fast_forward:
        from ..streaming.sources import SkipRowsSource

        ssc._source = SkipRowsSource(ssc._source, fast_forward)
    _blackbox.record(
        "journal_replay", where="boot", rows=rows, cursor=cursor,
        count=int(totals.get("count", 0)),
        batches=int(totals.get("batches", 0)),
    )
    if skip_rows or rows:
        log.warning(
            "journal: boot resume — %d journaled row(s), %d fast-forwarded "
            "at the source (%d inside the restored checkpoint, %d replayed "
            "from cursor %d); zero rows lost, zero rows double-trained",
            skip_rows, fast_forward, skip_rows - rows, rows, cursor,
        )
    return rows


class DivergenceSentinel:
    """Non-finite-state guard at the model boundary (``--sentinel``, default
    on): one poisoned batch (NaN/Inf labels, adversarial features) drives
    the fused predict-then-train step's weights non-finite in a single
    update, and — before this guard — silently destroyed the model AND,
    within ``keep_last`` cadence saves, every checkpoint the resume path
    relies on.

    **Zero added host fetches** (the r2/r3 measurement law — asserted by
    tests the way the ``--trace`` tests are): the finiteness check reads
    ONLY the StepOutput scalars the pipeline already fetched per batch
    (mse/stdevs — NaN labels or NaN weights propagate into all of them
    through the on-device stats reduction). Healthy-path cost is three
    ``math.isfinite`` calls per batch.

    On a non-finite delivery: the batch is SKIPPED (never handed to the
    app handler — its stats are garbage; the dispatch slot is refunded so
    max-batches caps don't under-train), the model rolls back to the last
    VERIFIED-finite checkpoint (``AppCheckpoint.rollback_to_verified``;
    without ``--checkpointDir`` it resets to the reference's initial
    zeros), and ``model.rollbacks`` counts it. Consecutive non-finite
    deliveries are ONE episode — batches already dispatched against the
    poisoned weights drain through as tainted skips without re-rolling
    back — and the first finite delivery closes it. After
    ``--sentinelRollbacks`` rollbacks within ``--sentinelWindow`` batches
    the run aborts CLEANLY via the existing ``ssc.request_abort`` path
    (checkpointed shutdown, non-zero exit): a stream that keeps poisoning
    the model is an operator problem, not a retry problem.

    PARITY: on the healthy path the sentinel observes and never touches
    reference semantics; it only ever SKIPS batches whose state is
    non-finite — a regime where the reference would train garbage forever
    (PARITY.md).

    Multi-host: stats are psum-global and deliveries deterministic, so
    every host reaches the same verdict at the same delivered batch and
    performs the same rollback (the checkpoint broadcast inside
    ``rollback_to_verified`` is collective). The cumulative rollback count
    rides the per-tick cadence allgather (``ssc.rollback_count_fn``) so
    the group VERIFIES it rolled back the same steps instead of assuming
    it."""

    def __init__(self, conf, model, ckpt: AppCheckpoint, ssc,
                 lead: bool = True, totals: "dict | None" = None):
        self.enabled = getattr(conf, "sentinel", "on") == "on"
        self.max_rollbacks = int(getattr(conf, "sentinelRollbacks", 3) or 0)
        self.window = max(1, int(getattr(conf, "sentinelWindow", 512) or 1))
        self._model = model
        self._ckpt = ckpt
        self._ssc = ssc
        self._lead = lead
        # run counters, for the journal-replay conversion (ISSUE 19): a
        # replayed rollback resets them to the checkpoint so the re-counted
        # rows end at the clean-run ledger; None (legacy callers) keeps
        # the counted-loss path
        self._totals = totals
        # rows of the current episode replayed (vs counted lost): set per
        # rollback by _rollback, read by admit's loss accounting
        self._replaying = False
        self._num_features = int(getattr(conf, "numTextFeatures", 1000))
        self._tainted = False
        self._delivered = 0
        self._rollback_points: list[int] = []
        self._pipeline = None
        reg = _metrics.get_registry()
        self._nonfinite_count = reg.counter("model.nonfinite_batches")
        self._rollback_count = reg.counter("model.rollbacks")
        self._rows_lost = reg.counter("model.rows_lost")
        if self.enabled:
            # the rollback count rides the lockstep cadence allgather so
            # multi-host groups verify they rolled back the same steps
            ssc.rollback_count_fn = lambda: len(self._rollback_points)

    def bind(self, pipeline) -> None:
        """Attach the fetch pipeline/batcher whose ``refund_dispatch``
        keeps max-batches caps exact when a batch is skipped."""
        self._pipeline = pipeline

    @property
    def rollbacks(self) -> int:
        return len(self._rollback_points)

    @staticmethod
    def _finite(out) -> bool:
        import math

        # the already-fetched per-batch scalars: NaN labels hit mse and
        # real_stdev immediately; NaN WEIGHTS (a poisoned prior batch) hit
        # pred_stdev/mse through the predictions — between them every
        # non-finite state the fused step can reach is visible without
        # touching the device
        return (
            math.isfinite(float(out.mse))
            and math.isfinite(float(out.real_stdev))
            and math.isfinite(float(out.pred_stdev))
        )

    def admit(self, out, batch) -> bool:
        """Per-delivery gate (wired by ``attach_pipeline``): True →
        hand the batch to the app handler; False → skipped (non-finite
        state; rollback/abort already handled here)."""
        self._delivered += 1
        if self._finite(out):
            if self._tainted:
                log.warning(
                    "divergence sentinel: finite stats resumed at "
                    "delivered batch %d — rollback recovered the model",
                    self._delivered,
                )
                self._tainted = False
            return True
        self._nonfinite_count.inc()
        rows = int(out.count) if hasattr(out, "count") else 0
        if self._pipeline is not None:
            self._pipeline.refund_dispatch()
        if self._tainted:
            # same episode: a batch dispatched against the poisoned
            # weights before the rollback took effect drains through
            if not self._replaying:
                self._rows_lost.inc(rows)
            log.warning(
                "divergence sentinel: skipping tainted in-flight batch "
                "(delivered %d, %d rows)%s", self._delivered, rows,
                " — rows re-ingest via journal replay"
                if self._replaying else "",
            )
            return False
        self._tainted = True
        self._rollback()
        if not self._replaying:
            # no journal (or no usable cursor): the skipped rows are lost,
            # counted — the pre-journal ledger
            self._rows_lost.inc(rows)
        return False

    def _rollback(self) -> None:
        self._rollback_count.inc()
        self._rollback_points.append(self._delivered)
        _trace.get().instant(
            "sentinel_rollback", delivered=self._delivered,
            episode=len(self._rollback_points),
        )
        _blackbox.record(
            "sentinel_rollback", delivered=self._delivered,
            episode=len(self._rollback_points),
        )
        meta = self._ckpt.rollback_to_verified()
        # journal-replay conversion (ISSUE 19): re-ingest every row after
        # the rollback point instead of skipping it — the sentinel site's
        # half of the crash-equals-clean differential. Legacy callers
        # (no totals) and --journal off keep the counted-loss behavior.
        replayed = None
        if self._totals is not None:
            replayed = journal_replay_rollback(
                self._ssc, self._ckpt, self._totals, meta,
                where="sentinel rollback",
            )
        self._replaying = replayed is not None
        if meta is not None:
            log.error(
                "divergence sentinel: NON-FINITE model state at delivered "
                "batch %d — rolled back to verified checkpoint step %s and "
                "skipping the poisoning batch (rollback #%d)",
                self._delivered, meta.get("step", "?"),
                len(self._rollback_points),
            )
        else:
            # nothing to roll back to: reset to the reference's initial
            # state (zeros, LinearRegression.scala:32) — progress is lost
            # but the stream keeps training, which beats NaN forever
            import numpy as np

            from ..features.batch import NUM_NUMBER_FEATURES

            self._model.set_initial_weights(np.zeros(
                (self._num_features + NUM_NUMBER_FEATURES,), np.float32,
            ))
            log.error(
                "divergence sentinel: NON-FINITE model state at delivered "
                "batch %d and no verified checkpoint — model RESET to "
                "initial zeros (rollback #%d); add --checkpointDir to "
                "preserve progress across rollbacks",
                self._delivered, len(self._rollback_points),
            )
        in_window = [
            p for p in self._rollback_points
            if self._delivered - p < self.window
        ]
        if self.max_rollbacks and len(in_window) >= self.max_rollbacks:
            _metrics.get_registry().counter("model.sentinel_aborts").inc()
            _blackbox.record(
                "sentinel_abort", rollbacks=len(in_window),
                window=self.window,
            )
            log.critical(
                "divergence sentinel: %d rollbacks within %d batches — the "
                "stream keeps poisoning the model; aborting the run "
                "cleanly (the shutdown path flushes a final checkpoint "
                "and the process exits non-zero)",
                len(in_window), self.window,
            )
            self._ssc.request_abort()


class ModelWatchGuard:
    """``--modelWatch`` delivery adapter (ISSUE 8): feeds the host-side
    model watcher (telemetry/modelwatch.py) from the quality leaf the
    pipeline ALREADY fetched inside the StepOutput — zero added host
    fetches, zero added collectives, exactly like the sentinel's
    finiteness check — and implements the sentinel EARLY-WARNING hook:
    when the watcher holds ``alert`` for ``--modelWatchWindow`` delivered
    batches, it emits a blackbox event + counter and forces ONE immediate
    verified-checkpoint save per episode (warn-only: the sentinel's
    non-finite rollback machine is untouched — an alerting-but-finite
    model keeps training, it just leaves a restorable snapshot + evidence
    behind before things possibly get worse).

    Multi-host: the quality vector is psum-global, so every host derives
    the same verdict on the same delivered batch; the forced save is
    lead-only inside ``AppCheckpoint`` like every other save."""

    def __init__(self, conf, ckpt: "AppCheckpoint | None", totals: dict,
                 lead: bool = True):
        self.enabled = getattr(conf, "modelWatch", "on") == "on"
        self.window = max(1, int(getattr(conf, "modelWatchWindow", 8) or 1))
        self._ckpt = ckpt
        self._totals = totals
        self._lead = lead
        self._saved_episode = False
        self._alert_saves = _metrics.get_registry().counter(
            "model.alert_checkpoints"
        )

    def observe(self, out, at_boundary: bool = True) -> None:
        """Per-delivery hook (wired OUTSIDE the tenant adapter in
        ``attach_pipeline``, so the tenant plane's raw [M, Q] quality
        leaf is visible here — per-tenant drift for free)."""
        if not self.enabled or getattr(out, "quality", None) is None:
            return
        import numpy as np

        counts = np.atleast_1d(np.asarray(out.count, np.float64))
        if float(counts.sum()) <= 0:
            return  # an all-padding / globally-empty tick carries no data
        verdict = _modelwatch.record_tick(
            np.asarray(out.quality, np.float64), counts,
            np.asarray(out.mse, np.float64),
        )
        if verdict["level"] != "alert":
            self._saved_episode = False
            return
        if (
            verdict["alert_run"] >= self.window
            and not self._saved_episode
            and at_boundary  # save_now reads weights — they must be current
        ):
            self._saved_episode = True
            self._alert_saves.inc()
            _blackbox.record(
                "modelwatch_alert_checkpoint",
                batches=self._totals.get("batches", 0),
                drift=round(verdict["drift_score"], 3),
                trend=round(verdict["loss_trend"], 4),
            )
            saved = self._ckpt.save_now(self._totals) if (
                self._ckpt is not None
            ) else False
            log.warning(
                "model watch: ALERT held for %d batches (drift z=%.2f, "
                "loss trend %+.1f%%) — %s (early warning only; training "
                "continues, the sentinel still owns rollback)",
                verdict["alert_run"], verdict["drift_score"],
                verdict["loss_trend"] * 100.0,
                "forced a verified-checkpoint save"
                if saved else "no checkpoint dir configured, evidence "
                "recorded to the flight recorder only",
            )


class FreshnessGuard:
    """``--freshness`` delivery adapter (ISSUE 16): pops the batch's lineage
    record at fetch delivery (telemetry/freshness.py — pure host arithmetic
    over stamps the seams already took; zero added host fetches, zero added
    collectives like the sentinel/model-watch checks) and implements the
    ``--freshnessSloMs`` early-warning hook in the ModelWatchGuard shape:
    when the event→delivery lag stays over the SLO for a sustained run, the
    plane emits the blackbox event + counter and this guard forces ONE
    verified-checkpoint save per breach episode (warn-only — a stale-but-
    healthy model keeps training; it just leaves a restorable snapshot
    behind from BEFORE the backlog grew).

    Wired OUTERMOST in ``attach_pipeline`` so every delivery — even
    ticks the sentinel skips or the multihost filter drops as globally
    empty — advances the lineage FIFO; the FIFOs stay aligned with the
    dispatch order exactly because nothing upstream can swallow a
    delivery before this hook sees it."""

    def __init__(self, conf, ckpt: "AppCheckpoint | None" = None,
                 totals: "dict | None" = None, lead: bool = True):
        self.enabled = getattr(conf, "freshness", "on") == "on"
        self._ckpt = ckpt
        self._totals = totals if totals is not None else {}
        self._lead = lead
        self._saved_episode = False
        self._slo_saves = _metrics.get_registry().counter(
            "freshness.slo_checkpoints"
        )

    def observe(self, out, at_boundary: bool = True) -> None:
        if not self.enabled:
            return
        verdict = _freshness.record_delivery()
        if verdict is None:
            return
        if not verdict["breach"]:
            self._saved_episode = False
            return
        if (
            verdict["in_episode"]
            and not self._saved_episode
            and at_boundary  # save_now reads weights — they must be current
        ):
            self._saved_episode = True
            self._slo_saves.inc()
            saved = self._ckpt.save_now(self._totals) if (
                self._ckpt is not None
            ) else False
            log.warning(
                "freshness guard: event lag %.0f ms over SLO for %d "
                "batches (critical edge: %s) — %s (warn-only; training "
                "continues, the sentinel still owns rollback)",
                verdict["event_lag_ms"], verdict["breach_run"],
                verdict["critical"] or "?",
                "forced a verified-checkpoint save"
                if saved else "no checkpoint dir configured, evidence "
                "recorded to the flight recorder only",
            )


class ProcessRecycler:
    """``--recycleAfterMb``: bounded process lifetime as a MECHANISM, not
    just a diagnosis (the RSS watchdog warns about host memory that grows
    with uploaded bytes but could not act). When process
    RSS crosses the configured ABSOLUTE ceiling, the next weights-current
    batch boundary checkpoints and re-execs the process in place
    (``os.execv`` — same interpreter, same argv, same environment).
    Restore is exact (``AppCheckpoint``: weights + counters resume
    bit-identically), so the recycle is invisible to the learning
    trajectory; a live source simply reconnects and continues, while a
    replay source restarts its file exactly as a manual
    checkpoint-restart would (the flag targets long-lived LIVE
    deployments — the regime a slow host-memory growth affects).

    Refused multi-host (one host exec'ing would desert the lockstep group;
    recycle the whole group externally) and without ``--checkpointDir``
    (nothing to resume from). ``TWTML_RECYCLE_MAX`` caps recycles per
    process lineage (the count rides the ``TWTML_RECYCLES`` env var across
    execs); unbounded by default."""

    def __init__(self, conf, ckpt: AppCheckpoint, totals: dict,
                 sample_every: int = 1):
        import os as _os

        self.threshold = float(getattr(conf, "recycleAfterMb", 0) or 0)
        self._ticks = 0
        # sample on every boundary by default: rss_mb is a ~µs statm read
        # and boundaries are already sparse in back-to-back mode (the
        # attach_pipeline cadence); TWTML_RECYCLE_SAMPLE_EVERY remains
        # the test hook pinning WHICH boundary recycles
        self._sample_every = max(
            1,
            int(_os.environ.get("TWTML_RECYCLE_SAMPLE_EVERY", sample_every)),
        )
        if self.threshold <= 0:
            return
        import jax

        if jax.process_count() > 1:
            raise SystemExit(
                "--recycleAfterMb is single-host: a multi-host lockstep "
                "group cannot lose a member mid-collective — recycle the "
                "whole group externally on the RSS watchdog's warning"
            )
        if not getattr(conf, "checkpointDir", ""):
            raise SystemExit(
                "--recycleAfterMb needs --checkpointDir (a recycle is "
                "checkpoint + re-exec; without a checkpoint the restart "
                "would train from zeros)"
            )
        self._ckpt = ckpt
        self._totals = totals
        self._lineage = int(_os.environ.get("TWTML_RECYCLES", "0") or 0)
        self._max = int(_os.environ.get("TWTML_RECYCLE_MAX", "0") or 0)
        self._capped_warned = False

    def check(self, at_boundary: bool = True) -> None:
        """Call per batch from the app handler, AFTER the cadence
        checkpoint logic. Samples RSS every ``sample_every`` ticks; only a
        weights-current boundary may recycle (the snapshot must include
        this batch)."""
        if self.threshold <= 0 or not at_boundary:
            return
        self._ticks += 1
        if self._ticks % self._sample_every:
            return
        from ..utils.rss import rss_mb

        cur = rss_mb()
        if cur < self.threshold:
            return
        if self._max and self._lineage >= self._max:
            if not self._capped_warned:
                self._capped_warned = True
                log.warning(
                    "RSS %.0f MB over the --recycleAfterMb ceiling but "
                    "TWTML_RECYCLE_MAX=%d reached; running on", cur, self._max,
                )
            return
        self._recycle(cur)

    def _recycle(self, cur_mb: float) -> None:
        import os as _os
        import sys as _sys

        self._ckpt.save_now(self._totals)
        main = _sys.modules.get("__main__")
        spec = getattr(main, "__spec__", None)
        if spec is not None and spec.name:
            argv = [_sys.executable, "-m", spec.name] + _sys.argv[1:]
        else:
            argv = [_sys.executable] + _sys.argv
        log.warning(
            "process RSS %.0f MB crossed --recycleAfterMb %.0f: "
            "checkpointed at batch %d (count=%d, state crc %s) and "
            "re-exec'ing (recycle #%d of this lineage). Resume is exact.",
            cur_mb, self.threshold, self._totals["batches"],
            self._totals["count"],
            state_checksum(self._ckpt._get_state()),
            self._lineage + 1,
        )
        _os.environ["TWTML_RECYCLES"] = str(self._lineage + 1)
        for h in list(log.handlers) or []:
            try:
                h.flush()
            except Exception:  # lawcheck: disable=TW005 -- best-effort log flush immediately before execv; a sick handler must not stop the recycle
                pass
        _sys.stdout.flush()
        _sys.stderr.flush()
        _os.execv(_sys.executable, argv)


class FetchWatchdog:
    """Deadline + bounded-retry + clean-abort guard over the pooled host
    fetches (FetchPipeline).

    Why it is safe to retry: a ``device_get`` reads arrays that stay
    resident on the device, so a fetch that missed its deadline or raised
    can simply be RE-ISSUED; a duplicate concurrent get reads the same
    bytes. The deadline derives from the health monitor's rolling fetch
    latency (``FETCH_DEADLINE_MULT`` × median, clamped to
    [``FETCH_DEADLINE_MIN_S``, ``FETCH_DEADLINE_MAX_S``]) — deliberately
    generous, because a retry only helps a LOST request, not a stalled
    device. Whether a fetch is ever lost on this machine is not known
    (ROADMAP D5).

    After ``retries`` re-issues the run aborts CLEANLY instead of the
    pre-guard behavior (an untimed ``future.result()`` = a silent permanent
    hang): the abort hook marks the run failed and stops the stream, the
    app's shutdown path flushes a final checkpoint, and the process exits
    non-zero with a critical log line.

    Env overrides (ops/test hooks): ``TWTML_FETCH_DEADLINE_S`` pins a fixed
    deadline; ``TWTML_FETCH_RETRIES`` overrides the retry budget.
    Constructor args win over both."""

    def __init__(self, health, abort=None, deadline_s: float = 0.0,
                 retries: "int | None" = None):
        import os as _os

        self._health = health
        self._abort = abort
        self.deadline_s = deadline_s or float(
            _os.environ.get("TWTML_FETCH_DEADLINE_S", "0") or 0
        )
        self.retries = (
            retries if retries is not None
            else int(_os.environ.get("TWTML_FETCH_RETRIES", FETCH_RETRIES))
        )
        reg = _metrics.get_registry()
        self._retry_count = reg.counter("fetch.retries")
        self._abort_count = reg.counter("fetch.aborts")
        self.aborted = False

    def deadline(self) -> float:
        if self.deadline_s > 0:
            return self.deadline_s
        med_s = self._health.median_ms() / 1e3
        if med_s <= 0:
            # no samples yet (first fetch of the run): be maximally patient
            return FETCH_DEADLINE_MAX_S
        return min(
            max(FETCH_DEADLINE_MULT * med_s, FETCH_DEADLINE_MIN_S),
            FETCH_DEADLINE_MAX_S,
        )

    def await_result(self, future, reissue):
        """Blocking wait for a pooled fetch future under the deadline;
        ``reissue()`` must submit a fresh fetch of the same device output
        and return its future."""
        from concurrent.futures import TimeoutError as _FutTimeout

        attempts = 0
        while True:
            deadline = self.deadline()
            try:
                return future.result(timeout=deadline)
            except _FutTimeout:
                why = f"made no progress within its {deadline:.1f}s deadline"
            except Exception as exc:  # lawcheck: disable=TW005 -- not a swallow: the failure is captured into `why` and drives the watchdog's retry/abort machine below
                why = f"failed ({exc!r})"
            attempts += 1
            if attempts > self.retries:
                self.aborted = True
                self._abort_count.inc()
                _trace.get().instant("fetch_abort", attempts=attempts)
                _blackbox.record("fetch_abort", attempts=attempts, why=why)
                log.critical(
                    "pooled stats fetch %s after %d attempt(s); aborting "
                    "the run — the stream stops and the shutdown path "
                    "flushes a final checkpoint (FetchWatchdog)",
                    why, attempts,
                )
                if self._abort is not None:
                    self._abort()
                raise FetchAbort(
                    f"pooled fetch {why} after {attempts} attempts"
                )
            self._retry_count.inc()
            _blackbox.record("fetch_retry", attempt=attempts, why=why)
            log.warning(
                "pooled stats fetch %s; re-issuing (retry %d/%d — the "
                "arrays stay resident, a duplicate device_get is safe)",
                why, attempts, self.retries,
            )
            future = reissue()


_codec_fallback_warned = False


def _dispatch_lease(wire, *batches):
    """The arena lease(s) a dispatch must hold until its fetch delivers:
    the packed wire's own lease plus the featurize-stage lease riding
    each unpacked batch (the one-pass native featurizer, r18, leases its
    output arrays from the same arena). Identity-deduplicating — an
    unpacked dispatch sees the same object through both views."""
    from ..features.arena import chain_leases

    return chain_leases(
        getattr(wire, "_lease", None),
        *(getattr(b, "_lease", None) for b in batches),
    )


def _record_wire_codec(wire, requested: str) -> None:
    """Per-pack codec telemetry (r15 satellite): the compressed-units
    split from ``features/batch.wire_composition`` → the
    ``wire.units_compressed_bytes`` + ``wire.codec_ratio`` gauges on
    /api/metrics (dashboard "wire ratio" tile). A pack that REQUESTED the
    codec but shipped raw (non-ASCII-widened units, or an incompressible
    batch) is the loud per-batch fallback: counted in
    ``wire.codec_fallbacks`` and warned once per process. Pure layout
    math — no array reads, no fetches."""
    global _codec_fallback_warned
    if not requested or requested == "off":
        return
    from ..features.batch import wire_composition

    comp = wire_composition(wire)
    reg = _metrics.get_registry()
    phys = comp.get("units_compressed")
    if phys is None:
        reg.counter("wire.codec_fallbacks").inc()
        reg.gauge("wire.codec_ratio").set(1.0)
        reg.gauge("wire.units_compressed_bytes").set(comp.get("units", 0))
        if not _codec_fallback_warned:
            _codec_fallback_warned = True
            log.warning(
                "wire codec requested but this batch shipped RAW "
                "(non-ASCII-widened units or incompressible) — counted "
                "in wire.codec_fallbacks; further fallbacks are silent"
            )
        return
    reg.gauge("wire.units_compressed_bytes").set(phys)
    if phys:
        reg.gauge("wire.codec_ratio").set(
            round(comp["units"] / phys, 3)
        )


class FetchPipeline:
    """Depth-D concurrent stats fetch for back-to-back regimes: the main
    thread dispatches ``model.step(batch)`` and hands each StepOutput's
    host fetch to a small thread pool; completed outputs are consumed IN
    ORDER on the main thread.

    Why: a per-batch stats fetch is a latency-bound REQUEST — starting the
    copy early (a one-batch-lagged fetch) does not shorten it, but
    CONCURRENT ``device_get``s overlap their latencies, so ``depth`` of
    them in flight hide up to ``depth`` fetch round trips behind dispatch.
    Dispatch and ``device_put``
    stay on the main thread (lawcheck TW003); gets from worker threads are
    what this pipeline exists to issue.

    Semantics vs the synchronous path: per-batch stats identical and in
    order; ``at_boundary`` is True only when nothing newer has been
    dispatched (pipeline drained — end of stream, or a ``boundary_every``
    cadence drain so checkpoint saves still see current weights);
    ``max_dispatch`` caps how
    many batches may train, so max-batches stops stay EXACT (the cap is
    enforced before dispatch, not discovered after). ``flush()`` after
    stream termination drains the tail.

    The round (``on_batch``) is *backpressure → deliver → pack → dispatch*:
    while ``depth`` results are in flight it blocks on the oldest (the
    device-paced path), then delivers, oldest first, the k leading results
    whose fetch was done WHEN THAT DELIVERY BEGAN — k is counted once, so a
    result that becomes done during a delivery waits for the next round (at
    most one round late), and a host that fell behind finds k ≥ 2 and
    catches up in one round — and only then packs and dispatches. Why the
    count (PERF.md §7 row 17): re-testing ``done()`` after every delivery
    made rounds alternate TWO deliveries and none wherever a device step
    ended inside one delivery's handlers and POSTs (a 12 ms step under a
    19 ms round: stats arrived ~6 then ~33 ms apart). Why not the dispatch
    first (PERF.md §6 PR 39, measured): it published each result one
    featurize sooner and cost the tenant plane 1.5–4% of its rate.
    ``poll()``, ``drain()`` and ``flush()`` deliver everything that is done
    or in flight: they dispatch nothing.

    ``deterministic`` (multi-host mode) disables the opportunistic
    already-done early emit: handler side effects (request_stop,
    empty-global refunds) then fire only at DETERMINISTIC points — the
    depth backpressure, cadence drains, cap drains, and flush — all driven
    by the dispatch counter, which advances identically on every lockstep
    host. With the opportunistic emit, one host could see a stop/refund a
    tick earlier than a peer (wall-clock-dependent ``done()``), exit the
    lockstep loop early, and leave the peer blocked in its next
    collective (r3 advisor finding)."""

    def __init__(self, model, handle, depth: int = 8, stop_requested=None,
                 boundary_every: int = 0, max_dispatch: int = 0,
                 pack: bool = False, deterministic: bool = False,
                 abort=None, fetch_deadline_s: float = 0.0,
                 fetch_retries: "int | None" = None,
                 wire_codec: str = ""):
        from concurrent.futures import ThreadPoolExecutor

        self.model = model
        self.handle = handle
        self.depth = max(1, depth)
        # compressed units wire (--wireCodec, r15): forwarded to the plain
        # pack_batch below; model-aware packers carry their own attribute
        self.wire_codec = wire_codec
        # one-buffer wire: one device_put per batch instead of one per
        # array (its gain on this machine: not measured, ROADMAP S3);
        # handlers still receive the UNPACKED batch. The pack itself is model-aware (r5): mesh models lay the
        # buffer out PER SHARD so the data axis can shard it
        # (ParallelSGDModel.pack_for_wire), multi-host models additionally
        # assemble the global buffer from every host's local shard segments
        # (MultiHostSGDModel.pack_for_wire); plain models use the
        # field-major features/batch.pack_batch
        self.pack = pack
        self._packer = getattr(model, "pack_for_wire", None)
        self.deterministic = deterministic
        self._stop_requested = stop_requested
        self.boundary_every = boundary_every
        self.max_dispatch = max_dispatch
        # model-aware host transfer (MultiHostSGDModel.fetch_output defers
        # the lead's prediction localization into the pooled fetch); plain
        # models use jax.device_get
        self._fetch = getattr(model, "fetch_output", None)
        self._pool = ThreadPoolExecutor(
            max_workers=self.depth, thread_name_prefix="twtml-stats-fetch"
        )
        # observability (side-channel only): every pooled fetch is timed and
        # fed to the fetch-health monitor + fetch-latency histogram; no
        # extra host fetch is ever issued — the timing wraps the ONE fetch
        # this pipeline already makes per batch
        self._registry = _metrics.get_registry()
        self._health = _metrics.get_health_monitor()
        self._fetch_count = self._registry.counter("fetch.count")
        self._fetch_hist = self._registry.histogram("fetch.latency_s")
        self._depth_gauge = self._registry.gauge("fetch.queue_depth")
        self._refund_count = self._registry.counter("fetch.refunds")
        # deadline/retry/abort guard over every pooled fetch — the
        # pre-guard future.result() in _emit_one was a silent permanent
        # hang on a wedged transport (FetchWatchdog)
        self._watchdog = FetchWatchdog(
            self._health, abort=abort,
            deadline_s=fetch_deadline_s, retries=fetch_retries,
        )
        # [(future, out, batch, t, lease, batch id)] oldest first
        self._pending: list = []
        self._head_since = None  # poll()'s head-fetch deadline bookkeeping
        self._dispatched = 0
        # checkpoint cadence runs on its own MONOTONIC counter: a
        # refund_dispatch must not make the cap accounting pass a cadence
        # point twice or skip it (r3 advisor finding)
        self._cadence = 0
        self._last_boundary = 0

    def _timed_fetch(self, out, seq=None):
        """The pooled host fetch, timed for the fetch-health monitor and
        the ``fetch`` trace stage (``seq``: the batch id it carries). This
        wraps the ONE fetch the pipeline already makes per batch —
        instrumentation never adds a ``device_get``."""
        import time as _time

        import jax

        fetch = self._fetch or jax.device_get
        tr = _trace.get()
        t0 = _time.perf_counter()
        with tr.batch_scope(seq), tr.span("fetch", depth=self.depth):
            _faults.perturb("fetch")  # --chaos: inside the timed window,
            # so injected stalls feed the health monitor like real ones
            host = fetch(out)
        dt = _time.perf_counter() - t0
        self._fetch_count.inc()
        self._fetch_hist.observe(dt)
        self._health.observe(dt)
        _sideband.record_stage("fetch", dt)
        return host

    def _emit_one(self) -> None:
        future, out, batch, t, lease, seq = self._pending.pop(0)
        tr = _trace.get()
        # the delivered batch's id, for deliver_wait and the handler's
        # stats_publish (a newer batch's scope may be open around this)
        with tr.batch_scope(seq):
            try:
                # the scheduler's wait for the oldest in-flight result: its
                # slack (PERF.md §3); a span only when it has to wait
                with (_trace.NULL_SPAN if future.done()
                      else tr.span("deliver_wait")):
                    host = self._watchdog.await_result(
                        future,
                        lambda: self._pool.submit(
                            self._timed_fetch, out, seq
                        ),
                    )
            except FetchAbort:
                # the dispatch may still execute on the wedged backend:
                # never donate its wire buffer back for reuse
                # (features/arena.py); the batch trained but was never
                # delivered, so its cap slot comes back (every dispatched
                # batch is either delivered or refunded — flush and
                # drain_discard hold the same rule)
                if lease is not None:
                    lease.discard()
                self.refund_dispatch()
                raise
            self.handle(host, batch, t, at_boundary=not self._pending)
        if lease is not None:
            # fetch delivered ⇒ the dispatch consumed its wire bytes: the
            # arena lease retires to the pool. AFTER the handler — the
            # lease may chain the batch's featurize-stage arrays (r18),
            # which delivery handlers still read (tenant re-routing,
            # per-batch stats), and a prefetching featurize thread must
            # not be handed the buffer while they do
            lease.retire()

    def _drain(self) -> None:
        while self._pending:
            self._emit_one()

    def on_batch(self, batch, t) -> None:
        import jax

        if self._watchdog.aborted:
            return  # fetch abort in flight: nothing more may train
        stop = self._stop_requested
        if stop is not None and stop():
            return  # stop requested: nothing more may train
        if self.max_dispatch and self._dispatched >= self.max_dispatch:
            # cap reached: later batches must not train — but whatever DID
            # train must still be delivered NOW, or the handler-side stop
            # (max-batches → request_stop) never fires and an unbounded
            # live source keeps batching forever
            self._drain()
            return
        # backpressure: block down to depth-1 in flight (the device-paced
        # path); then ONE DELIVERY A ROUND (class docstring; PERF.md §7 row
        # 17): the leading results that are done AT THIS MOMENT, oldest
        # first, and no more — the count is taken once, never re-tested
        # after a delivery. Skipped in deterministic/multi-host mode
        in_flight = len(self._pending)
        while len(self._pending) >= self.depth:
            self._emit_one()
            if stop is not None and stop():
                return  # the cap landed on an emitted batch: do not dispatch
        ready = 0
        if not self.deterministic:
            for entry in self._pending:
                if not entry[0].done():
                    break
                ready += 1
        for _ in range(ready):
            self._emit_one()
            if stop is not None and stop():
                return  # as above
        tr = _trace.get()
        import time as _time

        if self.pack:
            from ..features.batch import pack_batch

            packer = self._packer or (
                lambda b: pack_batch(b, codec=self.wire_codec or None)
            )
            t0 = _time.perf_counter()
            if tr.enabled:
                from ..features.batch import wire_nbytes

                with tr.span("wire_pack", mode="single") as sp:
                    wire = packer(batch)
                    sp.add(wire_bytes=wire_nbytes(wire))
            else:
                wire = packer(batch)
            _sideband.record_stage("wire_pack", _time.perf_counter() - t0)
            _record_wire_codec(
                wire,
                (getattr(self.model, "wire_codec", "") or "")
                if self._packer else self.wire_codec,
            )
        else:
            wire = batch
        # argument uploads ride the dispatch (no
        # separate device_put on the single-host hot path); timed
        # unconditionally for the sideband's upload attribution, with the
        # --chaos injection INSIDE the window so injected dispatch stalls
        # attribute like real ones
        t0 = _time.perf_counter()
        with tr.span("dispatch", depth=len(self._pending),
                     signature=lambda: wire_signature(wire, batch)):
            _faults.perturb("step")  # --chaos dispatch injection
            out = self.model.step(wire)  # dispatch on the MAIN thread
        dt = _time.perf_counter() - t0
        _sideband.record_stage("dispatch", dt)
        _lineage.mark_dispatch()
        seq = _trace.current_batch()
        self._pending.append(
            (self._pool.submit(self._timed_fetch, out, seq), out, batch, t,
             _dispatch_lease(wire, batch), seq)
        )
        self._depth_gauge.set(len(self._pending))
        self._dispatched += 1
        self._cadence += 1
        if self.boundary_every and (
            self._cadence - self._last_boundary >= self.boundary_every
        ):
            self._drain()  # cadence point: weights current for checkpoints
            self._last_boundary = self._cadence
        if tr.enabled:
            # the round's engagement counter (paired_delivery_share)
            left = len(self._pending)
            tr.instant("deliver_round", batch=seq, ready=ready,
                       delivered=in_flight + 1 - left, pending=left)

    def refund_dispatch(self) -> None:
        """Give back one ``max_dispatch`` slot — called by handlers that
        SKIP a delivered batch (multi-host globally-empty batches: they
        dispatch for collective alignment but must not count toward a
        max-batches cap, or capped runs under-train)."""
        self._dispatched -= 1
        self._refund_count.inc()

    def drain(self) -> None:
        """Deliver every pending output NOW without dispatching more —
        the elastic membership plane calls this before a group re-forms
        (nothing may stay in flight across a backend rebuild)."""
        self._drain()

    def drain_discard(self, why: str) -> int:
        """Rescue-path drain (elastic detach, ``clean=False``): a peer
        died mid-step, so any in-flight output's collectives are POISONED
        — their buffer definition events fail permanently
        (FAILED_PRECONDITION "Gloo all-reduce failed"), and awaiting them
        just burns the fetch watchdog's re-issues before it aborts the
        whole run (measured on the 2-host lead-kill storm,
        tools/chaos_fleet.py). The reform restores the lead's verified
        checkpoint anyway, so the rescue DISCARDS in-flight outputs
        instead of awaiting them: cap slots refunded (every dispatched
        batch is either delivered or refunded), arena leases discarded
        (the dead-peer dispatch may still touch its wire buffer — never
        reuse), and the rolled-back rows counted loudly in
        ``elastic.rows_discarded_inflight``. Clean commits keep the
        lossless ``drain()``. Returns the discarded row count."""
        if not self._pending:
            return 0
        n, rows = len(self._pending), 0
        for future, _out, batch, _t, lease, _seq in self._pending:
            future.cancel()  # not-yet-started fetches never run
            rows += int(getattr(batch, "num_valid", 0) or 0)
            self.refund_dispatch()
            if lease is not None:
                lease.discard()
        self._pending.clear()
        self._depth_gauge.set(0)
        self._registry.counter("elastic.rows_discarded_inflight").inc(rows)
        log.warning(
            "elastic rescue: discarded %d in-flight batch output(s) "
            "(~%d row(s)) — %s; the resync restores the verified "
            "checkpoint, so these rolled-back rows are counted in "
            "elastic.rows_discarded_inflight, never awaited", n, rows, why,
        )
        return rows

    @property
    def pending_fetches(self) -> int:
        """In-flight pooled fetches (the serving plane's idle loop reads
        this to pick its poll cadence)."""
        return len(self._pending)

    def poll(self) -> None:
        """Emit any already-completed in-order results WITHOUT dispatching —
        the serving plane's idle tick, so predictions deliver promptly when
        no new request arrives to trigger the on_batch emit path. Skipped
        in deterministic (multi-host lockstep) mode for the same reason the
        opportunistic early emit is: wall-clock-dependent ``done()`` must
        not drive side effects there.

        The watchdog deadline holds here too: a head fetch that outlives
        it with NO follow-up traffic (the idle-server wedged-fetch case)
        is emitted through the BLOCKING path, whose watchdog re-issues and
        eventually aborts — without this, a stalled fetch on a quiet
        serving plane would hang its clients until the next request."""
        if self.deterministic:
            return
        while self._pending and self._pending[0][0].done():
            self._emit_one()
        if not self._pending:
            self._head_since = None
            return
        import time as _time

        head = self._pending[0][0]
        now = _time.monotonic()
        since = getattr(self, "_head_since", None)
        if since is None or since[0] is not head:
            self._head_since = (head, now)
            return
        if now - since[1] > self._watchdog.deadline():
            self._head_since = None
            self._emit_one()  # blocking: the watchdog owns it from here

    def flush(self) -> None:
        try:
            self._drain()
        except FetchAbort:
            # already logged + the abort hook fired; the app's shutdown
            # path owns the final checkpoint flush — never raise into it
            if self._pending:
                log.warning(
                    "dropping %d undelivered batch output(s) after the "
                    "fetch abort", len(self._pending),
                )
                for _f, _o, _b, _t, lease, _seq in self._pending:
                    if lease is not None:
                        lease.discard()  # wedged dispatches: no reuse
                    self.refund_dispatch()  # trained, never delivered
                self._pending.clear()
        finally:
            # shutdown in a finally: an exception re-raised from
            # future.result() during the drain must not leak the executor
            self._pool.shutdown(wait=False)


def _rebalance_intake(source, old_members, new_members, my_uid: int,
                      reason: str) -> None:
    """Intake rebalance across an elastic membership change. Departed
    hosts' residue classes are adopted round-robin by survivors (exact
    going-forward coverage — streaming/sources.py); a REJOINED host's
    handling is source-kind-aware: live id-sharded streams hand its
    residues back (ids are position-free), replay index shards keep them
    with the adopters (the rejoiner becomes a hot standby — re-reading its
    file shard from zero would double-train). Sources with no residue
    surface (block byte-range shards) lose the departed range, counted."""
    sharded = source
    while sharded is not None and not hasattr(sharded, "adopt_residues"):
        sharded = getattr(sharded, "inner", None)
    departed = sorted(u for u in old_members if u not in new_members)
    rejoined = sorted(u for u in new_members if u not in old_members)
    reg = _metrics.get_registry()
    if sharded is None:
        if departed:
            reg.counter("elastic.shards_lost").inc(len(departed))
            log.warning(
                "elastic: this source kind cannot adopt departed shard(s) "
                "%s — their remaining rows are lost (counted in "
                "elastic.shards_lost)", departed,
            )
        return
    survivors = sorted(u for u in new_members if u in old_members)
    for i, uid in enumerate(departed):
        owner = survivors[i % len(survivors)] if survivors else -1
        if owner == my_uid:
            sharded.adopt_residues([uid])
    from ..streaming.sources import IdShardedSource

    if rejoined and isinstance(sharded, IdShardedSource):
        # live stream: the rejoiner's fresh connection resumes its id
        # residues from now — adopters release them (position-free keys)
        sharded.release_residues(rejoined)
    if my_uid in rejoined and not isinstance(sharded, IdShardedSource):
        # replay standby: contribute all-padding batches; the adopters own
        # the residues and the weights stay bit-synchronized regardless
        sharded.residues.clear()
        log.warning(
            "elastic: rejoined a replay-sharded run as a hot standby "
            "(index shards are position-bound; residues stay with their "
            "adopters)"
        )


def attach_elastic(conf, ssc, model, stream, ckpt, totals):
    """``--elastic on`` wiring: build the membership plane over the
    elastic runtime formed in ``init_distributed`` and install it on the
    streaming context. The two transition callbacks close over the whole
    app stack so a membership change is a full re-provisioning:

    detach — drain the fetch pipeline (nothing in flight across a backend
    rebuild; a RESCUE discards in-flight outputs instead — a dead peer
    poisons their collectives, ``drain_discard``), on a CLEAN commit
    checkpoint at the boundary (loss-free), then abandon the epoch's
    process group;

    attach — form the new epoch, rebuild the mesh + model in place,
    re-synchronize state/counters from the lead (broadcast of its verified
    checkpoint — the PR 4 path), rebalance intake shards across the new
    membership, and pre-compile the step for the new world so the first
    post-reform tick doesn't stall.

    Returns the plane (or None when the run is not elastic); pass it to
    ``attach_pipeline`` so the pipeline drain hook binds."""
    import jax

    from ..parallel import elastic as _elastic
    from ..streaming.membership import MembershipPlane

    runtime = _elastic.get_runtime()
    if runtime is None or jax.process_count() <= 1:
        return None
    source = ssc._source
    if runtime.joined_late:
        # a restarted host admitted into a LIVE run: its replay-index
        # residues were adopted by the incumbents when it departed —
        # re-reading its file shard from zero would double-train, so it
        # contributes as a hot standby (live id-sharded sources keep their
        # residues: the incumbents release them, _rebalance_intake)
        from ..streaming.sources import IdShardedSource

        sharded = source
        while sharded is not None and not hasattr(sharded, "adopt_residues"):
            sharded = getattr(sharded, "inner", None)
        if sharded is not None and not isinstance(sharded, IdShardedSource):
            sharded.residues.clear()
            log.warning(
                "elastic: joined a live replay-sharded run as a hot "
                "standby (residues stay with their adopters)"
            )
    st: dict = {
        "pipeline": None,
        "old_members": list(runtime.members),
    }

    def detach(clean: bool) -> None:
        st["old_members"] = list(runtime.members)
        pipe = st.get("pipeline")
        if pipe is not None:
            if clean:
                pipe.drain()
            else:
                # a rescue: the dead peer poisoned any in-flight step's
                # collectives — discard them (rows counted, resync rolls
                # them back) instead of awaiting permanently-failed
                # buffers into a watchdog abort
                pipe.drain_discard("a peer died mid-step")
        if clean:
            # every member is alive and synchronized at a clean commit
            # tick: the lead snapshots HERE so the resync after formation
            # restores exactly the pre-transition state — zero loss
            ckpt.save_now(totals)
        runtime.abandon()

    def attach(plan: dict, reason: str) -> None:
        runtime.form(plan["epoch"], plan["members"])
        if runtime.is_lead:
            # a won election lands here: checkpoint authority moves to
            # this host BEFORE the resync broadcast below, so the fleet
            # restores from the WINNER's verified archives (idempotent —
            # an incumbent lead is already promoted)
            ckpt.promote()
        mesh = build_mesh(conf, what=f"elastic epoch {plan['epoch']}")
        model.rebuild(mesh)
        if reason == "rejoin":
            # a rejoiner's queued rows predate its absence; the adopters
            # own that coverage now — training them would double-train
            dropped = sum(
                getattr(s, "rows", 1) for s in ssc._drain(0)
            )
            if dropped:
                _metrics.get_registry().counter(
                    "elastic.rows_dropped_rejoin"
                ).inc(dropped)
                log.warning(
                    "elastic: dropped %d stale queued row(s) on rejoin "
                    "(counted in elastic.rows_dropped_rejoin)", dropped,
                )
        pre_resync = (int(totals["count"]), int(totals["batches"]))
        ckpt.resync_from_verified(totals)
        # journal-replay conversion (ISSUE 19): after the fleet converges
        # on the lead-agreed rollback point, every host re-ingests ITS OWN
        # journaled rows past its cursor — the in-flight rows a rescue
        # discarded (drain_discard) and the post-checkpoint rows the
        # resync rolled back. Replay rides the lockstep cadence (dry hosts
        # dispatch all-padding); ZERO new collectives. A REJOINER instead
        # resets its journal: its pre-departure coverage moved to the
        # adopters (_rebalance_intake), so replaying it would double-train.
        if _journal.get() is not None:
            # the reform discarded the fetch pipeline's in-flight
            # deliveries wholesale (drain_discard above): their dispatch
            # tokens would strand and desync every later pairing — drop
            # them; the replay below re-covers their rows
            _journal.get().clear_inflight()
            rejoined = set(plan["members"]) - set(st["old_members"])
            if runtime.uid in rejoined:
                _journal.get().reset()
                log.warning(
                    "journal: reset on rejoin — this host's pre-departure "
                    "rows belong to their adopters now"
                )
            else:
                stub = {
                    "count": totals["count"], "batches": totals["batches"],
                }
                if (totals["count"], totals["batches"]) == pre_resync:
                    # nothing rolled back: the resync adopted weights that
                    # cover exactly the delivered batches (the lead's live
                    # weights when no verified checkpoint exists yet, or a
                    # clean-commit save at the current boundary). This
                    # host's COMMITTED delivery cursor is that same point
                    # — no archive lookup needed, so the first reform can
                    # precede the first save and still replay the
                    # discarded in-flight rows instead of counting them.
                    stub["journal"] = (
                        _journal.get().snapshot_for_checkpoint()
                    )
                journal_replay_rollback(
                    ssc, ckpt, totals, stub, where=f"elastic {reason}",
                )
        _rebalance_intake(
            source, st["old_members"], plan["members"], runtime.uid, reason,
        )
        warmup_compile(stream, model)

    plane = MembershipPlane(
        runtime, detach, attach,
        evict_ticks=int(getattr(conf, "elasticEvictTicks", 0) or 0),
        evict_skew_ms=float(getattr(conf, "elasticEvictSkewMs", 250.0)),
        rejoin=getattr(conf, "elasticRejoin", "on") == "on",
    )
    plane._bind_box = st  # attach_pipeline fills st["pipeline"]
    ssc.membership = plane
    log.info(
        "elastic membership plane ACTIVE: epoch %d, members %s, "
        "evict after %s gating tick(s), rejoin %s",
        runtime.epoch, runtime.members,
        plane.evict_ticks or "∞", "on" if plane.rejoin else "off",
    )
    return plane


def elastic_exit(failed: bool = False) -> None:
    """Elastic runs must leave via a hard exit (abandoned-epoch teardown
    during interpreter finalization LOG(FATAL)s — parallel/elastic.py);
    no-op without an elastic runtime. Call as the LAST line of an app's
    run path, after checkpoints and telemetry have flushed."""
    from ..parallel import elastic as _elastic

    runtime = _elastic.get_runtime()
    if runtime is None:
        return
    log.info(
        "elastic run complete (epoch %d, %d reform(s)); hard exit %d",
        runtime.epoch, len(runtime._graveyard), 1 if failed else 0,
    )
    runtime.finalize_exit(1 if failed else 0)


def attach_pipeline(conf, stream, model, handle, stop_requested=None,
                    max_dispatch: int = 0, abort=None, sentinel=None,
                    modelwatch=None, elastic=None, freshness=None):
    """Wire the app's per-batch ``handle(out, batch, t, at_boundary)`` to the
    stream: pack → dispatch → fetch → the delivery-wrapper chain → ``handle``.
    Back to back (``--seconds 0``) the fetches ride a ``FetchPipeline``;
    under a wall clock each batch is fetched synchronously. Returns
    ``flush`` — the app must invoke it after termination (delivers what is
    still in flight).

    ``at_boundary`` is True whenever the model's weights are current as of
    this batch (nothing newer dispatched) — the guard for side effects that
    read ``model.latest_weights``, e.g. checkpoints.

    ``stop_requested``: optional predicate (the app's
    ``ssc.stop_requested``) that lets the fetch pipeline honor a
    max-batches stop; ``max_dispatch`` additionally caps how many batches
    may ever train (exact max-batches under the concurrent fetch pipeline
    — see FetchPipeline)."""
    import jax

    from ..utils.rss import RssWatchdog

    mesh_dm = None
    if hasattr(model, "mesh_layout"):
        # start-up mark of a mesh model (a no-op without --trace)
        layout = model.mesh_layout()
        _trace.get().instant("mesh_layout", **layout)
        mesh_dm = [layout["data"], layout["model"]]
        arms = model.mesh_arms(int(getattr(stream, "row_bucket", 0) or 0))
        if arms:
            # --tenantKey all on the mesh: what a chip ships a batch for
            # the arms (parallel/sharding.ParallelSGDModel.mesh_arms)
            _trace.get().instant("mesh_arms", **arms)

    # RSS watchdog on the batch cadence: the long-running loops are where
    # slow host-memory growth accumulates (utils/rss.py)
    watchdog = RssWatchdog()
    guarded_handle = handle

    def handle(out, batch, t, at_boundary=True):  # noqa: F811
        watchdog.tick()
        # journal committed-cursor advance (ISSUE 19): the INNERMOST
        # wrapper — only batches every admission filter accepted (no
        # sentinel skip, no globally-empty no-op) reach here, so the
        # popped dispatch token is safe to commit. BEFORE the app handler:
        # a checkpoint save inside this very delivery must stamp a cursor
        # that covers this batch.
        _j = _journal.get()
        if _j is not None:
            _j.note_delivered()
        guarded_handle(out, batch, t, at_boundary=at_boundary)

    if sentinel is not None and sentinel.enabled:
        # divergence gate between the fetch and the app handler: a
        # non-finite delivery is skipped (rollback handled inside admit);
        # wrapped INSIDE the multi-host empty-batch filter below, so the
        # gate only ever sees batches with rows
        sentinel_inner = handle

        def handle(out, batch, t, at_boundary=True):  # noqa: F811
            if not sentinel.admit(out, batch):
                return
            sentinel_inner(out, batch, t, at_boundary=at_boundary)

    # a tenant-plane model (any M, the forced M=1 differential included)
    # carries num_tenants; plain models don't
    num_tenants = int(getattr(model, "num_tenants", 0) or 0)
    if num_tenants >= 1:
        # multi-tenant model plane: the OUTERMOST delivery wrapper — the
        # fetched [M, ...] StepOutput records the per-tenant view
        # (telemetry/tenants.py, from arrays already on the host — zero
        # added fetches) and collapses to ONE batch-level StepOutput in
        # original row order for the pre-existing chain (sentinel,
        # session stats, checkpoints). M=1 passes through bit-exact.
        import numpy as np

        from ..ops.quality import QUALITY_INDEX
        from ..parallel.tenants import aggregate_tenant_output
        from ..telemetry import tenants as _tenants

        tenant_inner = handle

        tenant_key = getattr(model, "tenant_key", "hash")
        shared_batches = _metrics.get_registry().counter(
            "tenants.shared_batches"
        )

        def handle(out, batch, t, at_boundary=True):  # noqa: F811
            counts = np.asarray(out.count, np.int64)
            _tenants.record_tick(counts, np.asarray(out.mse, np.float64))
            if tenant_key == "all":
                shared_batches.inc()  # ONE batch, one C and one G, M arms
            tr = _trace.get()
            if tr.enabled:
                # once per delivered batch, from what the ONE fetch brought:
                # every tenant's part was padded to the row rung the split
                # took (the [M, rung] predictions leaf has it; ``batch`` is
                # the ORIGINAL host batch), so the step computed on M·rung
                # rows for Σ valid
                preds = out.predictions
                bucket = int(
                    batch.mask.shape[0] if preds is None else preds.shape[1]
                )
                # the M rungs in tenant order (PR 49): a lopsided split
                # pads the fullest tenant's part to ``bucket`` and the
                # others to a lower rung, which only the split knows
                take = getattr(model, "take_buckets", None)
                buckets = (
                    take(_trace.current_batch()) if take else None
                ) or [bucket] * counts.size
                extra = {}
                if getattr(out, "quality", None) is not None:
                    # each part's OWN Gram plane (the ``gram_plane`` instant
                    # keeps the slowest over the tenants with rows): a
                    # near-dry part of short rows takes s8 beside bf16 ones
                    extra["planes"] = np.asarray(out.quality)[
                        :, QUALITY_INDEX["gram_plane"]
                    ].astype(int).tolist()
                if mesh_dm is not None and tenant_key == "all":
                    extra["mesh"] = mesh_dm  # the arms' [d, m] mesh
                # under ``all`` every arm saw the whole batch: rows is
                # [B]·M, bucket B, no padding
                tr.instant(
                    "tenant_rows", batch=_trace.current_batch(),
                    key=tenant_key, rows=counts.tolist(), bucket=bucket,
                    buckets=buckets,
                    pad_rows=int(sum(buckets) - counts.sum()),
                    **extra,
                )
            tenant_inner(
                aggregate_tenant_output(out, batch, model), batch, t,
                at_boundary=at_boundary,
            )

    if float(getattr(conf, "l1Reg", 0.0) or 0.0) > 0:
        # the primal learner's own view (--l1Reg; models/sgd.py
        # primal_basis), from the [2] int32 leaf the ONE fetch brought:
        # the rounds that ran before the freeze, and the share of text
        # weights that are exactly zero — what an operator runs Lasso for
        from ..ops.quality import QUALITY_INDEX as _QUALITY_INDEX

        primal_inner = handle
        n_text = int(conf.numTextFeatures)
        reg = _metrics.get_registry()

        def handle(out, batch, t, at_boundary=True):  # noqa: F811
            primal = getattr(out, "primal", None)
            if primal is not None:
                ran, zeros = (int(v) for v in primal)
                reg.gauge("model.primal_iterations").set(ran)
                reg.gauge("model.weights_zero_share").set(
                    round(zeros / n_text, 6)
                )
                # the plane the gate took rides the quality vector; -1
                # without it (--modelWatch off) or outside fits_gram
                plane = -1
                if getattr(out, "quality", None) is not None:
                    plane = int(out.quality[_QUALITY_INDEX["gram_plane"]])
                _trace.get().instant(
                    "primal", batch=_trace.current_batch(), iterations=ran,
                    zero_weights=zeros, plane=plane,
                )
            primal_inner(out, batch, t, at_boundary=at_boundary)

    if modelwatch is not None and modelwatch.enabled:
        # model-watch adapter (ISSUE 8), wrapped OUTSIDE the tenant
        # aggregation so it reads the RAW StepOutput — the tenant plane's
        # stacked [M, Q] quality leaf gives per-tenant drift for free;
        # pure host bookkeeping on arrays the fetch already delivered
        mw_inner = handle

        def handle(out, batch, t, at_boundary=True):  # noqa: F811
            modelwatch.observe(out, at_boundary=at_boundary)
            mw_inner(out, batch, t, at_boundary=at_boundary)

    multihost = jax.process_count() > 1
    if multihost and (stream.row_bucket <= 0 or stream.token_bucket <= 0):
        raise SystemExit(
            "multi-host runs need pinned shapes: set --batchBucket and "
            "--tokenBucket (every host must dispatch the same collective "
            "program every tick, including all-padding batches)"
        )

    def skip_empty(fn):
        if multihost:
            # a host whose interval/shard came up empty must STILL dispatch
            # its all-padding batch — the other hosts' collectives wait on
            # its program (streaming/context._lockstep_loop)
            return fn

        def cb(batch, t):
            if batch.num_valid == 0:
                log.debug("batch: 0")
                _lineage.drop_newest()  # the shed batch never dispatches
                _js = _journal.get()
                if _js is not None:
                    _js.drop_newest()  # un-push its dispatch token too
                return
            fn(batch, t)

        return cb

    if multihost:
        # the LOCAL batch can't gate the step (collectives above), but a
        # GLOBALLY empty batch (every row filtered out on every host) must
        # not surface to the app — single-host runs skip those pre-step.
        # It must not consume a max-batches slot either (refund below, set
        # once the pipeline exists).
        import numpy as _np

        inner_handle = handle
        pipeline_ref: list = []

        def handle(out, batch, t, at_boundary=True):  # noqa: F811
            # the tenant fleet delivers an [M]-stacked count; a batch is
            # globally empty only when EVERY tenant's share is
            if int(_np.asarray(out.count).sum()) == 0:
                log.debug("batch: 0 (global)")
                if pipeline_ref:
                    pipeline_ref[0].refund_dispatch()
                return
            inner_handle(out, batch, t, at_boundary=at_boundary)

    if freshness is not None and freshness.enabled:
        # freshness adapter (ISSUE 16), the OUTERMOST delivery wrapper:
        # every delivered batch — including ones the sentinel skips or the
        # multihost filter drops as globally empty — must pop its lineage
        # record, or the dispatch-ordered FIFO desynchronizes
        fresh_inner = handle

        def handle(out, batch, t, at_boundary=True):  # noqa: F811
            freshness.observe(out, at_boundary=at_boundary)
            fresh_inner(out, batch, t, at_boundary=at_boundary)

    if _journal.get() is not None:
        # journal dispatch-token pop (ISSUE 19): the OUTERMOST delivery
        # wrapper — every delivered batch, including ones the sentinel
        # skips or the multihost filter drops as globally empty, must pop
        # its token in dispatch order or the committed-cursor pairing
        # desynchronizes (the commit itself happens in the innermost
        # wrapper above, so filtered batches pop without committing)
        journal_pop_inner = handle

        def handle(out, batch, t, at_boundary=True):  # noqa: F811
            _jp = _journal.get()
            if _jp is not None:
                _jp.pop_dispatch()
            journal_pop_inner(out, batch, t, at_boundary=at_boundary)

    # cadence drains exist for checkpoint saves only: without a
    # checkpointDir each drain would stall the fetch pipelining for a
    # no-op save
    boundary_every = (
        int(getattr(conf, "checkpointEvery", 0) or 0)
        if getattr(conf, "checkpointDir", "")
        else 0
    )
    if int(getattr(conf, "recycleAfterMb", 0) or 0) > 0 and not boundary_every:
        # --recycleAfterMb can only act at weights-current boundaries; in
        # back-to-back mode with no --checkpointEvery the pipeline would
        # otherwise never drain mid-stream and the flag would be silently
        # inert (r5 review) — impose a default recycle-check cadence
        boundary_every = 64

    # the ragged wire additionally ships as ONE packed buffer (one put
    # per batch; bit-identical unpack inside the jit step). Since r5
    # every layout packs: mesh models lay the buffer out per shard and
    # multi-host models assemble it globally (pack_for_wire), so the fast
    # path survives every deployment shape.
    pack = bool(getattr(stream, "ragged", False)) and getattr(
        model, "accepts_packed", False
    )
    # compressed units wire (--wireCodec dict, r15): rides exactly the
    # packed wire forms (pack_batch / the coalesced tenant wire / the mesh
    # per-shard packs — compression compounds the per-array-overhead trap
    # that made packing the lean-wire default). Model-aware packers carry
    # their own wire_codec attribute (set in build_model / from_conf);
    # this value drives the pipeline-level plain packers.
    wire_codec = ""
    if pack:
        _codec = getattr(conf, "effective_wire_codec", lambda: "off")()
        wire_codec = _codec if _codec == "dict" else ""

    if conf.seconds <= 0:
        # back-to-back: concurrent in-order stats fetches overlap their
        # latencies (FetchPipeline; what depth 8 buys on this machine is
        # not measured, ROADMAP S3); checkpoint cadence points drain the
        # pipeline so saves see current weights. Multi-host runs emit only
        # at deterministic points so stop/refund side effects land on the
        # same tick on every lockstep host.
        pipe = FetchPipeline(
            model, handle, stop_requested=stop_requested,
            boundary_every=boundary_every,
            max_dispatch=max_dispatch,
            pack=pack,
            deterministic=multihost,
            abort=abort,
            wire_codec=wire_codec,
        )
        if multihost:
            pipeline_ref.append(pipe)  # empty-batch refunds (above)
        if sentinel is not None:
            sentinel.bind(pipe)  # skipped batches refund their cap slot
        if elastic is not None:
            elastic._bind_box["pipeline"] = pipe  # reform drain hook
        stream.foreach_batch(skip_empty(pipe.on_batch))
        return pipe.flush

    def per_batch(batch, t):
        # wall-clock streaming: ONE synchronous host transfer for the
        # whole StepOutput (sequential scalar fetches each pay a full
        # round trip). The fetch is ~2% of a 5 s interval; a lagged
        # fetch here would delay live dashboard stats a full interval
        # for nothing.
        import time as _time

        tr = _trace.get()
        if pack:
            from ..features.batch import pack_batch

            packer = getattr(model, "pack_for_wire", None) or (
                lambda b: pack_batch(b, codec=wire_codec or None)
            )
            tp = _time.perf_counter()
            if tr.enabled:
                with tr.span("wire_pack", mode="single"):
                    wire = packer(batch)
            else:
                wire = packer(batch)
            _sideband.record_stage(
                "wire_pack", _time.perf_counter() - tp
            )
            _record_wire_codec(
                wire,
                (getattr(model, "wire_codec", "") or "")
                if getattr(model, "pack_for_wire", None)
                else wire_codec,
            )
        else:
            wire = batch
        lease = _dispatch_lease(wire, batch)
        td = _time.perf_counter()
        with tr.span("dispatch",
                     signature=lambda: wire_signature(wire, batch)):
            _faults.perturb("step")  # --chaos dispatch injection
            out = model.step(wire)
        d_dt = _time.perf_counter() - td
        _sideband.record_stage("dispatch", d_dt)
        _lineage.mark_dispatch()
        fetch = getattr(model, "fetch_output", None) or jax.device_get
        t0 = _time.perf_counter()
        with tr.span("fetch", depth=1):
            _faults.perturb("fetch")
            out = fetch(out)
        dt = _time.perf_counter() - t0
        reg = _metrics.get_registry()
        reg.counter("fetch.count").inc()
        reg.histogram("fetch.latency_s").observe(dt)
        _metrics.get_health_monitor().observe(dt)
        _sideband.record_stage("fetch", dt)
        handle(out, batch, t, at_boundary=True)
        if lease is not None:
            lease.retire()  # synchronous fetch: dispatch consumed it
            # (after the handler — the lease may chain the batch's
            # featurize-stage arrays, r18)

    stream.foreach_batch(skip_empty(per_batch))
    return lambda: None


def warmup_compile(stream, model) -> None:
    """Pre-compile the step for the known batch shape BEFORE the stream
    starts, so the first wall-clock micro-batch doesn't swallow the whole
    compile-time backlog (~30 s on a cold TPU chip, during which a live
    source keeps producing). Only possible when --batchBucket AND
    --tokenBucket pin the full XLA program shape (read from the stream's
    own configuration — the single source of truth). The warm batch comes
    from the stream's OWN featurize dispatch (``featurize_empty``) so it
    compiles exactly the program the stream will run; an all-padding batch
    is semantically a no-op for the learner (zero-sample iterations leave
    weights untouched)."""
    if stream.row_bucket <= 0 or stream.token_bucket <= 0:
        return
    # what compiled in here is in the trace as ``compile`` spans with
    # ``during: warmup_compile`` (telemetry/trace.py)
    with _trace.get().span("warmup_compile", rows=stream.row_bucket):
        _warmup_compile(stream, model)


def _warmup_compile(stream, model) -> None:
    import time as _time

    import numpy as np

    from ..features.batch import UnitBatch

    if getattr(stream, "ragged", False):
        # the ragged wire's units-buffer bucket is DATA-dependent (Σ row
        # lengths, rounded to RAGGED_UNIT_MULTIPLE) — an all-padding batch
        # compiles the minimum bucket, not the one real batches will hit,
        # so full pre-compilation is impossible here. Say so instead of
        # logging a readiness that isn't real; the first real batch
        # compiles in-flight (totals concentrate tightly, so steady state
        # is one or two buckets). Live wall-clock streams that cannot
        # afford that stall should use --wire padded.
        log.info(
            "--wire ragged: units bucket is data-dependent; the first real "
            "batch compiles its program in-flight (pre-compile n/a)"
        )
        return
    t0 = _time.perf_counter()
    empty = stream.featurize_empty()
    variants = [empty]
    if isinstance(empty, UnitBatch) and empty.units.dtype == np.uint8:
        # the units wire dtype is per-batch metadata (uint8 iff every row
        # is ASCII — featurizer._pad_ragged_units): warm BOTH programs so
        # a stream's first non-ASCII tweet doesn't stall mid-flight
        variants.append(empty._replace(units=empty.units.astype(np.uint16)))
    for v in variants:
        model.step(v)
    log.info(
        "pre-compiled the train step for buckets (%d, %d) in %.1fs",
        stream.row_bucket, stream.token_bucket, _time.perf_counter() - t0,
    )
