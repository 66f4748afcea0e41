"""Worker process for the multi-host (jax.distributed) integration test.

Not a test module — launched by tests/test_distributed_multiprocess.py, two
processes forming a process group over localhost (gloo CPU collectives, 2
virtual devices each = 4 global). Each worker featurizes its shard of the
stream (the per-host sharded intake of SURVEY.md §7 stage 5), contributes
its rows to the global batch via host_local_batch_to_global, and runs one
mesh-sharded training step. Prints one JSON line with the step stats and
final weights.

Usage: python tests/distributed_worker.py <process_id> <num_processes> \
           <coordinator_port> <wire_format: unit|host> [mesh: 1d|2d]

``2d`` builds a (data=2, model=2) mesh over the 4 global devices — the
feature-sharded weight layout spanning PROCESS boundaries (each process
holds half of each weight shard pair).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from twtml_tpu.utils.backend import set_cpu_device_count_hint  # noqa: E402

set_cpu_device_count_hint(2)


def main() -> None:
    pid, nprocs, port, wire = (
        int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    )
    mesh_kind = sys.argv[5] if len(sys.argv) > 5 else "1d"
    if mesh_kind == "elastic_count":
        # ISSUE 13 acceptance (the PR 1/5 law re-asserted for the
        # membership plane): a REAL two-process lockstep run with the
        # elastic membership plane ACTIVE and membership columns riding
        # every tick. The cadence allgather count must equal the tick
        # count (the columns widened the payload, never the call count)
        # and jax.device_get must fire once per dispatched batch (zero
        # added host fetches). Formation goes through the ElasticRuntime
        # itself, so the counted run exercises the real detection-disabled
        # clients — not a stand-in.
        import jax.experimental.multihost_utils as mh

        from twtml_tpu.apps.common import FetchPipeline
        from twtml_tpu.features.featurizer import Featurizer
        from twtml_tpu.models import StreamingLinearRegressionWithSGD
        from twtml_tpu.parallel import elastic as _elastic
        from twtml_tpu.streaming.context import StreamingContext
        from twtml_tpu.streaming.membership import MembershipPlane
        from twtml_tpu.streaming.sources import ShardedSource, SyntheticSource
        from twtml_tpu.telemetry import metrics as _metrics

        runtime = _elastic.install_runtime("127.0.0.1", port, pid)
        runtime.form(0, list(range(nprocs)))

        counts = {"allgather": 0, "get": 0}
        real_ag = mh.process_allgather

        def counting_ag(arr, **kw):
            counts["allgather"] += 1
            return real_ag(arr, **kw)

        mh.process_allgather = counting_ag
        real_get = jax.device_get

        def counting_get(x):
            counts["get"] += 1
            return real_get(x)

        jax.device_get = counting_get

        model = StreamingLinearRegressionWithSGD(
            num_iterations=5, step_size=0.005
        )
        ssc = StreamingContext(batch_interval=0)
        stream = ssc.source_stream(
            ShardedSource(
                SyntheticSource(total=192, seed=7, base_ms=1785320000000),
                pid, nprocs,
            ),
            Featurizer(now_ms=1785320000000),
            row_bucket=16, token_bucket=64, row_multiple=2,
            device_hash=True,
        )
        transitions: list = []
        ssc.membership = MembershipPlane(
            runtime,
            lambda clean: transitions.append(("detach", clean)),
            lambda plan, reason: transitions.append(("attach", reason)),
        )
        pipe = FetchPipeline(
            model, lambda out, b, t, at_boundary: None, deterministic=True,
        )
        stream.foreach_batch(pipe.on_batch)
        ssc.start(lockstep=True)
        terminated = ssc.await_termination(timeout=120)
        ssc.stop()
        pipe.flush()
        reg = _metrics.get_registry().snapshot()
        print(json.dumps({
            "process": pid,
            "terminated": bool(terminated),
            "failed": bool(ssc.failed),
            "batches": int(ssc.batches_processed),
            "ticks": int(reg["counters"].get("lockstep.ticks", 0)),
            "allgathers": counts["allgather"],
            "device_gets": counts["get"],
            "fetch_count": int(reg["counters"].get("fetch.count", 0)),
            "epoch": runtime.epoch,
            "members": runtime.members,
            "transitions": transitions,
        }), flush=True)
        sys.stdout.flush()
        # elastic processes always leave hard (parallel/elastic.py): the
        # custom clients never run the shutdown barrier, so interpreter
        # teardown could trip the leaked-service poll FATAL
        runtime.finalize_exit(0)
        return
    jax.distributed.initialize(
        f"127.0.0.1:{port}", num_processes=nprocs, process_id=pid
    )

    if mesh_kind == "sideband":
        # fleet observability (ISSUE 5): a REAL two-process lockstep run
        # with host 1 artificially delayed via --chaos step:delay (the
        # injection sits INSIDE the dispatch timing window, so the stall
        # attributes to the upload stage). Both hosts gather the same
        # sideband matrix on the one cadence allgather; both must name
        # host 1 as the straggler. The allgather itself is counted so the
        # test proves the sideband added NO collective, and jax.device_get
        # is counted so it proves no added host fetch.
        #
        # The per-host model is deliberately HOST-LOCAL (no collectives in
        # the step): on this test's CPU backend collective execution is
        # synchronous, so a stall on one host would spread into every
        # peer's dispatch wall time through the in-step rendezvous and no
        # skew could be observed (on the real async-dispatch transport the
        # wait happens on device instead). A collective-free step keeps
        # each host's stage clocks its own, and makes the cadence
        # allgather the ONLY collective in the loop — exactly what the
        # zero-added-collectives count asserts against.
        import jax.experimental.multihost_utils as mh

        from twtml_tpu.apps.common import FetchPipeline
        from twtml_tpu.features.featurizer import Featurizer
        from twtml_tpu.models import StreamingLinearRegressionWithSGD
        from twtml_tpu.streaming import faults as _faults
        from twtml_tpu.streaming.context import StreamingContext
        from twtml_tpu.streaming.sources import ShardedSource, SyntheticSource
        from twtml_tpu.telemetry import metrics as _metrics
        from twtml_tpu.telemetry import sideband as _sideband

        if pid == 1:
            _faults.install_chaos("step:delay=0.12")

        counts = {"allgather": 0, "get": 0}
        real_ag = mh.process_allgather

        def counting_ag(arr):
            counts["allgather"] += 1
            return real_ag(arr)

        mh.process_allgather = counting_ag
        real_get = jax.device_get

        def counting_get(x):
            counts["get"] += 1
            return real_get(x)

        jax.device_get = counting_get

        model = StreamingLinearRegressionWithSGD(
            num_iterations=5, step_size=0.005
        )

        ssc = StreamingContext(batch_interval=0)
        stream = ssc.source_stream(
            ShardedSource(
                SyntheticSource(total=192, seed=7, base_ms=1785320000000),
                pid, nprocs,
            ),
            Featurizer(now_ms=1785320000000),
            row_bucket=16, token_bucket=64, row_multiple=2,
            device_hash=True,
        )
        pipe = FetchPipeline(
            model, lambda out, b, t, at_boundary: None,
            deterministic=True,
        )
        stream.foreach_batch(pipe.on_batch)
        ssc.start(lockstep=True)
        terminated = ssc.await_termination(timeout=120)
        ssc.stop()
        pipe.flush()

        reg = _metrics.get_registry().snapshot()
        view = _sideband.last_hosts()
        print(json.dumps({
            "process": pid,
            "terminated": bool(terminated),
            "failed": bool(ssc.failed),
            "batches": int(ssc.batches_processed),
            "ticks": int(reg["counters"].get("lockstep.ticks", 0)),
            "allgathers": counts["allgather"],
            "device_gets": counts["get"],
            "fetch_count": int(reg["counters"].get("fetch.count", 0)),
            "straggler_host": int(
                reg["gauges"].get("lockstep.straggler_host", -2)
            ),
            "tick_skew_ms": float(
                reg["gauges"].get("lockstep.tick_skew_ms", 0.0)
            ),
            "view_straggler": view["straggler"] if view else None,
            "view_stage": view["stage"] if view else None,
            "num_hosts_seen": len(view["hosts"]) if view else 0,
        }), flush=True)
        return

    if mesh_kind in ("lockstep_abort", "peer_kill"):
        # the anti-hang machinery. lockstep_abort: host 1's batch handler
        # raises mid-run; its loop must broadcast abort so host 0 STOPS
        # (instead of stalling in its next collective), and BOTH mark the
        # run failed. peer_kill: host 1 dies HARD (os._exit — no abort
        # broadcast, no goodbye); host 0's next cadence allgather can then
        # never complete, and the lockstep peer watchdog
        # (TWTML_LOCKSTEP_TIMEOUT_S) must turn that into a loud failed
        # abort rather than an infinite collective hang.
        from twtml_tpu.features.featurizer import Featurizer
        from twtml_tpu.parallel import ParallelSGDModel, make_mesh
        from twtml_tpu.parallel.distributed import host_local_batch_to_global
        from twtml_tpu.streaming.context import StreamingContext
        from twtml_tpu.streaming.sources import ShardedSource, SyntheticSource

        mesh = make_mesh(num_data=len(jax.devices()), devices=jax.devices())
        model = ParallelSGDModel(mesh, num_iterations=5, step_size=0.005)
        ssc = StreamingContext(batch_interval=0)
        stream = ssc.source_stream(
            ShardedSource(
                SyntheticSource(total=256, seed=7, base_ms=1785320000000),
                pid, nprocs,
            ),
            Featurizer(now_ms=1785320000000),
            row_bucket=16, token_bucket=64, row_multiple=2,
            device_hash=True,
        )
        seen = {"n": 0}

        def on_batch(batch, t):
            seen["n"] += 1
            model.step(host_local_batch_to_global(batch, mesh))
            if pid == 1 and seen["n"] == 3:
                if mesh_kind == "peer_kill":
                    # hard kill AFTER this tick's dispatch: the peer's
                    # tick-3 collectives complete, so the hang host 0 must
                    # survive is the NEXT cadence allgather
                    os._exit(42)
                # post-dispatch handler failure: the recoverable class —
                # this host's collective program DID run, so the peer's
                # collectives complete and the abort flag can reach it on
                # the next tick. (A failure BEFORE dispatch deadlocks the
                # peer's in-order collective queue until runtime timeouts —
                # the documented unrecoverable class.)
                raise RuntimeError("injected handler failure on host 1")

        stream.foreach_batch(on_batch)
        ssc.start(lockstep=True)
        terminated = ssc.await_termination(timeout=60)
        ssc.stop()
        print(json.dumps({
            "process": pid,
            "terminated": bool(terminated),
            "failed": bool(ssc.failed),
            "batches_seen": seen["n"],
        }), flush=True)
        if mesh_kind == "peer_kill":
            # with a hard-dead peer, jax.distributed's atexit shutdown
            # barrier can never complete — its client FATALs the process
            # (SIGABRT) after the coordination-service timeout. The
            # watchdog behavior under test is fully reported above, so
            # skip the doomed barrier. (A real app exits non-zero via its
            # RuntimeError in exactly this state.)
            sys.stdout.flush()
            os._exit(0)
        return

    import numpy as np

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.parallel import ParallelSGDModel, make_mesh, shard_batch
    from twtml_tpu.parallel.distributed import host_local_batch_to_global
    from twtml_tpu.streaming.sources import SyntheticSource

    # base_ms pinned: the 2d topology device_puts the SAME global batch from
    # every process, which demands bit-identical featurization
    statuses = list(
        SyntheticSource(total=64, seed=7, base_ms=1785320000000).produce()
    )
    feat = Featurizer(now_ms=1785320000000)

    def featurize(sts):
        if wire == "unit":
            return feat.featurize_batch_units(
                sts, row_bucket=len(sts), unit_bucket=64, pre_filtered=True
            )
        return feat.featurize_batch(
            sts, row_bucket=len(sts), token_bucket=64, pre_filtered=True
        )

    if mesh_kind == "tenants":
        # ISSUE 7: the multi-tenant plane with the TENANT axis mapped onto
        # the cross-process MODEL axis — device order [p0d0,p1d0,p0d1,p1d1]
        # pairs processes on the model axis (as in '2d' below), so each
        # process addresses only HALF the tenants' weight shards and the
        # latest_weights/stats reads exercise the process_allgather path.
        # Tenants are independent (no collective crosses the model axis);
        # rows shard over 'data'. Both hosts featurize the SAME stream
        # (base_ms pinned) and device_put the same routed stacked wire.
        from twtml_tpu.parallel import TenantStackModel, make_mesh

        d = jax.devices()
        mesh = make_mesh(
            num_data=2, num_model=2, devices=[d[0], d[2], d[1], d[3]]
        )
        model = TenantStackModel(
            4, num_iterations=5, step_size=0.005, mesh=mesh
        )
        chunks = [statuses[:32], statuses[32:]]
        for sts in chunks:
            out = model.step(feat.featurize_batch_units(
                sts, row_bucket=32, unit_bucket=64, pre_filtered=True
            ))
        gather = TenantStackModel._to_host
        print(json.dumps({
            "process": pid,
            "tenant_counts": gather(out.count).tolist(),
            "tenant_mses": gather(out.mse).tolist(),
            "weights_addressable": bool(out.count.is_fully_addressable),
            "weights": np.asarray(model.latest_weights).tolist(),
        }), flush=True)
        return

    if mesh_kind == "2d_ckpt":
        # checkpoint round-trip on the cross-process feature-sharded layout:
        # step → gather (process_allgather: shards are NOT fully addressable
        # here) → pid 0 writes the .npz → barrier → BOTH processes restore
        # into a FRESH model (set_initial_weights materializes only local
        # shards via make_array_from_callback) → second step. Must equal an
        # uninterrupted 2-step run.
        from jax.experimental import multihost_utils

        from twtml_tpu.checkpoint import Checkpointer

        d = jax.devices()
        mesh = make_mesh(
            num_data=2, num_model=2, devices=[d[0], d[2], d[1], d[3]]
        )
        model = ParallelSGDModel(
            mesh, num_text_features=1000, num_iterations=5, step_size=0.005
        )
        global_batch = shard_batch(featurize(statuses), mesh)
        model.step(global_batch)
        ckpt = Checkpointer(os.environ["TWTML_CKPT_DIR"])
        gathered = model.latest_weights  # collective: every process calls it
        if pid == 0:
            ckpt.save(1, gathered, {"batches": 1})
        multihost_utils.sync_global_devices("ckpt-written")
        weights, meta = ckpt.restore()
        assert meta["batches"] == 1
        resumed = ParallelSGDModel(
            mesh, num_text_features=1000, num_iterations=5, step_size=0.005
        ).set_initial_weights(weights)
        assert not resumed._weights["text"].is_fully_addressable
        out = resumed.step(global_batch)
        print(json.dumps({
            "process": pid,
            "count": float(out.count),
            "mse": float(out.mse),
            "weights": np.asarray(resumed.latest_weights).tolist(),
        }), flush=True)
        return
    if mesh_kind == "2d_gram":
        # the Gram (dual) inner loop with BOTH of its collectives crossing
        # process boundaries: the batch all-gather over 'data' and the G
        # panel psum over 'model' (device order pairs processes on the model
        # axis, as in '2d' below). Must match the dense single-process math.
        d = jax.devices()
        mesh = make_mesh(
            num_data=2, num_model=2, devices=[d[0], d[2], d[1], d[3]]
        )
        model = ParallelSGDModel(
            mesh, num_text_features=1000, num_iterations=5, step_size=0.005,
            use_sparse=True, use_gram=True,
        )
        global_batch = shard_batch(featurize(statuses), mesh)
    elif mesh_kind == "2d":
        # arrange devices so the MODEL axis pairs devices from DIFFERENT
        # processes: jax.devices() is process-major [p0d0,p0d1,p1d0,p1d1];
        # ordering [p0d0,p1d0,p0d1,p1d1] makes each mesh row mix processes —
        # the model-axis psum rides the cross-process (DCN-analog) path and
        # each weight shard is NOT fully addressable from one process
        # (exercising the latest_weights allgather). With this topology the
        # DATA shards span both processes too, so per-host intake sharding
        # doesn't apply: every host supplies the full batch (device_put
        # places each device's local shard from it).
        d = jax.devices()
        mesh = make_mesh(
            num_data=2, num_model=2, devices=[d[0], d[2], d[1], d[3]]
        )
        model = ParallelSGDModel(
            mesh, num_text_features=1000, num_iterations=5, step_size=0.005
        )
        global_batch = shard_batch(featurize(statuses), mesh)
    else:
        mesh = make_mesh(num_data=len(jax.devices()), devices=jax.devices())
        model = ParallelSGDModel(mesh, num_iterations=5, step_size=0.005)
        local = statuses[pid::nprocs]  # this host's stream shard
        batch = featurize(local)
        global_batch = host_local_batch_to_global(batch, mesh)
    out = model.step(global_batch)
    print(json.dumps({
        "process": pid,
        "count": float(out.count),
        "mse": float(out.mse),
        "weights": np.asarray(model.latest_weights).tolist(),
    }), flush=True)


if __name__ == "__main__":
    main()
