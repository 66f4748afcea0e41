"""Serving entry point — the read path as a product (ISSUE 9 / ROADMAP 1).

Boots the existing dashboard web server IN-PROCESS with a ServingPlane
attached (``POST /api/predict`` + ``GET /api/serving``), promotes the newest
servable snapshot from ``--checkpointDir`` (verified + quality stamp
ok/warn — the ``tools/model_report.py --gate`` predicate), keeps promoting
as the trainer writes new checkpoints (hot-swap between dispatches, never
tearing an in-flight batch), and publishes the ``Serving`` telemetry view on
a fixed cadence.

Deployment shape: the TRAIN process writes verified checkpoints; THIS
process reads them off disk and owns the query traffic — the handoff is the
filesystem, so serving adds zero host fetches and zero collectives to the
train path (the PR 1/5 law, asserted by counting in tests/test_serving.py).
Run both against the same ``--checkpointDir``:

    python -m twtml_tpu.apps.linear_regression --checkpointDir ck \
        --checkpointEvery 64 ...
    python -m twtml_tpu.apps.serve --checkpointDir ck --servePort 8888

    curl -s localhost:8888/api/predict -d '{"rows": [{"text": "hello"}]}'
"""

from __future__ import annotations

import sys
import threading
import time

from ..config import ConfArguments
from ..utils import get_logger
from .common import install_blackbox, install_chaos, install_trace, select_backend

log = get_logger("apps.serve")

PUBLISH_EVERY_S = 2.0


def run(conf: ConfArguments, started=None, stop_event=None,
        max_seconds: float = 0.0) -> dict:
    """Boot snapshot → plane → promoter → web server; serve until
    ``stop_event``/SIGINT/``max_seconds``. ``started(server, plane,
    promoter)`` fires once the front door is live (the test hook). Returns
    the final serving stats view."""
    if conf.multihost() is not None:
        raise SystemExit(
            "the serve entry point is single-host: scale reads by running "
            "N serve processes against replicas of the checkpoint directory"
        )
    if not conf.checkpointDir:
        raise SystemExit(
            "--checkpointDir is required: serving promotes verified "
            "checkpoint snapshots (train with --checkpointDir/"
            "--checkpointEvery to produce them)"
        )
    device = select_backend(conf)
    install_trace(conf)
    install_chaos(conf)
    install_blackbox(conf)

    from ..serving import ServingPlane, SnapshotPromoter, load_servable
    from ..telemetry.web_client import WebClient
    from ..web.server import Server

    snapshot, reason = load_servable(conf.checkpointDir)
    if snapshot is None:
        raise SystemExit(f"no servable snapshot: {reason}")
    log.info(
        "initial snapshot: step %d, %d tenant(s) — %s",
        snapshot.step, snapshot.num_tenants, reason,
    )
    engine = None
    stamp = snapshot.meta.get("tenants") or {}
    if stamp.get("key") == "all" and getattr(conf, "abtest", "off") != "on":
        raise SystemExit(
            "this checkpoint is a --tenantKey all stack: "
            f"{snapshot.num_tenants} recipes of one learner trained on the "
            "SAME rows, so no row belongs to one of them. Serve it with "
            "--abtest on (the champion answers, the challengers score the "
            "same rows)"
        )
    if getattr(conf, "abtest", "off") == "on":
        # champion/challenger (ISSUE 11): the tenant-stack snapshot's
        # variants ride ONE mirrored predict program — the champion
        # answers, challengers shadow-score, and per-tenant quality stamps
        # auto-promote the champion pointer through the is_promotable gate
        if snapshot.num_tenants < 2:
            raise SystemExit(
                "--abtest on needs a tenant-stack checkpoint "
                f"({snapshot.num_tenants} tenant(s) found): train with "
                "--tenants M >= 2 so the snapshot carries M variants"
            )
        from ..serving.abtest import ChampionEngine

        import jax.numpy as jnp

        engine = ChampionEngine(
            num_text_features=conf.numTextFeatures,
            num_tenants=snapshot.num_tenants,
            # the mirrored wire never routes: 'all' (a training key, which
            # ships no tenant wire) has nothing to say here
            tenant_key="hash" if conf.tenantKey == "all" else conf.tenantKey,
            dtype=jnp.dtype(getattr(conf, "dtype", "float32")),
        )
        for m, (step, l2) in enumerate(zip(
            stamp.get("stepSize") or (), stamp.get("l2Reg") or ()
        )):
            log.info(
                "tenant %d: stepSize %s, l2Reg %s (key %s)%s", m, step, l2,
                stamp.get("key"), " — the champion" if m == 0 else "",
            )
    plane = ServingPlane.from_conf(conf, snapshot, engine=engine)
    log.info("pre-compiling the predict program...")
    plane.warmup()
    plane.start()
    promoter = SnapshotPromoter(
        conf.checkpointDir, plane,
        poll_s=float(getattr(conf, "servePromoteEvery", 5.0) or 5.0),
    ).start()
    server = Server(port=conf.servePort).attach_serving(plane)
    server.start_background()
    port = server._runner.addresses[0][1]
    web = WebClient(f"http://127.0.0.1:{port}",
                    timeout=float(getattr(conf, "webTimeout", 2.0)))
    log.info("serving front door live: POST /api/predict on port %d", port)
    if started is not None:
        started(server, plane, promoter)

    t0 = time.monotonic()
    stop_event = stop_event or threading.Event()
    try:
        while not stop_event.is_set():
            if max_seconds and time.monotonic() - t0 >= max_seconds:
                break
            if plane.failed:
                break
            stop_event.wait(PUBLISH_EVERY_S)
            try:
                # the Serving view rides the same additive jsonClass wire
                # as every dashboard payload (cache + websocket broadcast)
                web.serving(plane.stats())
            except Exception:
                log.debug("serving publish failed", exc_info=True)
    except KeyboardInterrupt:
        pass
    finally:
        promoter.stop()
        plane.stop()
        # the run record names the device it served from (not part of
        # the published Serving view)
        stats = dict(plane.stats(), device=device)
        server.stop()
        from ..telemetry import trace as pipeline_trace

        pipeline_trace.uninstall()
    if plane.failed:
        raise RuntimeError(
            "serving plane aborted by the fetch watchdog (wedged transport); "
            "in-flight requests were rejected, not hung — see critical log"
        )
    log.info(
        "serve session done: %s requests, %s rows, qps %s",
        stats["requests"], stats["rows"], stats["qps"],
    )
    return stats


def main(argv=None) -> None:
    conf = (
        ConfArguments()
        .setAppName("twitter-stream-ml-serve")
        .parse(list(sys.argv[1:] if argv is None else argv))
    )
    run(conf)


if __name__ == "__main__":
    main()
