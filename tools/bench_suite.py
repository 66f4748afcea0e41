"""Benchmark suite — one measurement per BASELINE.md target config.

BASELINE.md lists five configs to measure (the reference publishes no
numbers, so every baseline is measured, not copied):

  1. replay_linear     — streaming linear regression on a replayed
                         (deterministic synthetic) tweet stream
  2. twitter_live      — same on the live Twitter stream (needs OAuth creds
                         + network; reported as skipped when absent)
  3. logistic_sentiment— streaming logistic regression, lexicon sentiment
                         labels (BASELINE config #3)
  4. hashing_2e18_l2   — 2^18-dim HashingTF featurizer + L2-regularized SGD,
                         the sparse gather/scatter path (config #4)
  5. sharded_dp4       — 4-way data-parallel mesh, per-shard stream +
                         in-program psum gradient reduce (config #5; skipped
                         below 4 chips — a virtual CPU mesh only under
                         TWTML_BENCH_CPU=1, labelled platform cpu)
  6. sharded_dp4_logistic — the logistic learner on the same 4-way mesh
                         (sentiment labels; non-least-squares residual
                         through the sharded step)
  7. sharded_2e18_2d   — config #4's 2^18 feature space on the 2D
                         (data × model) mesh: feature-sharded weights, the
                         Gram dual loop's per-batch collective schedule
                         (SURVEY §5.7's long-context analog, distributed)

Each config runs in its own subprocess (clean jax backend state; one after
another, and the parent never touches jax — a chip belongs to one process)
and prints one JSON line: {"config", "tweets_per_sec", "seconds", "batches",
"final_metric", "backend", "device", "skipped"?}. Every line names the device
it ran on. Without an accelerator the suite refuses to run unless
TWTML_BENCH_CPU=1 asks for the host on purpose (records then say platform
"cpu" and are not device results); a config that errors makes the suite exit
non-zero. The headline single-number benchmark stays bench.py.

Usage: python tools/bench_suite.py [--tweets N] [--batch B] [--json out.jsonl]
       [--configs name,name,...]   (default: all)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CONFIGS = [
    "replay_linear",
    "twitter_live",
    "logistic_sentiment",
    "hashing_2e18_l2",
    "sharded_dp4",
    "sharded_dp4_logistic",
    "sharded_2e18_2d",
    "multi_tenant_m8",
    "serving_qps",
    "wire_codec",
    "featurize",
]


def _status_json(s) -> dict:
    """Status → the wire-format tweet JSON object (recursive on retweets)."""
    d = {
        "text": s.text,
        "retweet_count": s.retweet_count,
        "user": {
            "followers_count": s.followers_count,
            "favourites_count": s.favourites_count,
            "friends_count": s.friends_count,
        },
        "timestamp_ms": str(s.created_at_ms),
        "lang": s.lang or "en",
    }
    if s.retweeted_status is not None:
        d["retweeted_status"] = _status_json(s.retweeted_status)
    return d


def _pipeline_rate(model, feat, statuses, batch_size, row_multiple=1, shard=None,
                   ragged=False, pack=True):
    """The shared double-buffered pipeline (utils/benchloop.py), with the
    suite's per-config featurizer/shard hooks. ``pack=False`` hands the
    model the UNPACKED ragged batch — models that build their own wire at
    the step boundary (the tenant plane's routed stack) need it raw."""
    from twtml_tpu.utils.benchloop import measure_pipeline

    chunks = [statuses[i : i + batch_size] for i in range(0, len(statuses), batch_size)]

    def featurize(chunk):
        # units wire format → bigram hashing on device (ops/text_hash.py);
        # ragged = concatenated units, no pad bytes, shipped as ONE packed
        # buffer (features/batch.py)
        b = (
            feat.featurize_batch_ragged(
                chunk, row_bucket=batch_size, pre_filtered=True,
                row_multiple=row_multiple, pack=pack,
            )
            if ragged
            else feat.featurize_batch_units(
                chunk, row_bucket=batch_size, pre_filtered=True,
                row_multiple=row_multiple,
            )
        )
        return shard(b) if shard else b

    # best-of-3: one pass is never trusted (see bench.py)
    out = measure_pipeline(model, featurize, chunks, repeats=3)
    return {
        "tweets_per_sec": round(out["tweets_per_sec"], 1),
        "seconds": round(out["seconds"], 3),
        "batches": out["batches"],
        "final_metric": round(out["final_mse"], 3),
    }


def run_config(name: str, n_tweets: int, batch_size: int = 0) -> dict:
    """``batch_size`` 0 = the per-config r4 operating point (the dict
    below; 2048 where no sweep moved it); an explicit value is honored
    everywhere."""
    explicit_batch = batch_size > 0
    # per-config operating points, inherited from sweeps taken on another
    # machine (git history at f46e967); not re-measured here — ROADMAP S3
    # re-derives them on the chip. Mesh configs keep 2048.
    # Explicit --batch always wins; default batches cap at n_tweets/4 so
    # a small-corpus run still measures a multi-chunk pipeline instead of
    # one half-padding batch.
    # (config #4 stays at 2048, which divides 65536 exactly;
    # tools/bench_2e18.py sweeps its batch size)
    if not explicit_batch:
        batch_size = {
            "replay_linear": 8192,
            "logistic_sentiment": 16384,
        }.get(name, 2048)
        batch_size = max(256, min(batch_size, n_tweets // 4 or batch_size))
    import jax

    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.sources import SyntheticSource

    out: dict = {"config": name}

    if name == "twitter_live":
        from twtml_tpu.config import ConfArguments, get_property, set_property

        conf = ConfArguments().parse(["--source", "twitter"])
        creds = [
            get_property("twitter4j.oauth." + k)
            for k in ("consumerKey", "consumerSecret", "accessToken", "accessTokenSecret")
        ]
        from twtml_tpu.apps import linear_regression as app

        if all(creds):
            # Live measurement: run the real app for ~6 batches and report
            # its observed ingest rate (bounded by the stream, not compute).
            t0 = time.perf_counter()
            totals = app.run(conf, max_batches=6)
            dt = time.perf_counter() - t0
            return {
                **out,
                "tweets_per_sec": round(totals["count"] / dt, 1),
                "seconds": round(dt, 3),
                "batches": totals["batches"],
                "backend": jax.default_backend(),
            }
        # No creds/egress on this rig: measure the SAME TwitterSource →
        # train path against an in-process v1.1-protocol server (the full
        # native stack — OAuth1 signing, chunked HTTP decode, line
        # reassembly, Status parse — is exercised for real; only the remote
        # endpoint is local). Tagged mode=local-protocol so it is never
        # read as a real-Twitter number. (VERDICT r2 #6)
        from tools.localstream import LocalV11StreamServer
        from twtml_tpu import config as _twtml_config
        from twtml_tpu.streaming.twitter import TwitterSource

        lines = [
            json.dumps(_status_json(s))
            for s in SyntheticSource(total=n_tweets, seed=3).produce()
        ]
        # 3 corpus replays per window (the server replays on reconnect):
        # a one-corpus window is RAMP-dominated (the fetch pipeline's
        # fill/drain tails and first-batch costs), and the steady state
        # is what the config claims
        n_batches = max(1, 3 * (n_tweets // batch_size))
        # snapshot the process-global property table: the fake bench creds
        # + local streamBaseURL must not leak past this measurement (a
        # later twitter_live call would mistake them for REAL creds)
        saved_props = dict(_twtml_config._SYSTEM_PROPERTIES)
        try:
            with LocalV11StreamServer(lines) as server:
                for k in ("consumerKey", "consumerSecret",
                          "accessToken", "accessTokenSecret"):
                    set_property("twitter4j.oauth." + k, "bench-" + k)
                set_property("twitter4j.streamBaseURL", server.url)
                live_args = [
                    "--source", "twitter", "--seconds", "0",
                    "--batchBucket", str(batch_size), "--tokenBucket", "128",
                    "--lightning", "http://127.0.0.1:9",
                    "--twtweb", "http://127.0.0.1:9",
                ]
                conf = ConfArguments().parse(live_args)

                # stage rate: the protocol path alone (connect → chunked
                # decode → reassemble → parse), no training attached
                src = TwitterSource.from_properties()
                got: list = []
                t0 = time.perf_counter()
                for s in src.produce():
                    got.append(s)
                    if len(got) >= n_tweets:
                        break
                protocol_s = time.perf_counter() - t0

                # the REAL app main (LinearRegression.scala:44 analog) over
                # the same stream. The rate is computed over the app's OWN
                # post-warmup streaming window (totals["stream_seconds"]):
                # the compile warmup runs before ssc.start (warmup_compile),
                # and per-batch stats ride the app's default FetchPipeline —
                # counting startup in the denominator made r3's full-app
                # number ~6k while the stages ran 34-79k (VERDICT r3 #4).
                # Best-of-3 app runs (each reconnects and replays the
                # server's stream): this is a single-pass measurement
                # otherwise, and one multi-second stall landing INSIDE the
                # window is enough to fake a 100× regression
                def best_of_3(run_conf):
                    best = None
                    for _ in range(3):
                        t0 = time.perf_counter()
                        totals = app.run(run_conf, max_batches=n_batches)
                        dt = time.perf_counter() - t0
                        stream_s = totals.get("stream_seconds") or dt
                        rec = (stream_s, dt, totals)
                        if best is None or stream_s < best[0]:
                            best = rec
                    return best

                stream_s, dt, totals = best_of_3(conf)

                # r5 (VERDICT r4 #9): the same app over the same stream
                # with LIVE BLOCK INGEST — raw lines batch into the native
                # C parser (BlockTwitterSource), deleting the per-line
                # json.loads + Status assembly that was the full-app vs
                # protocol-stage gap
                # same flags as the object arm + the one under test — the
                # two arms must stay comparable
                conf_block = ConfArguments().parse(
                    live_args + ["--ingest", "block"]
                )
                blk_stream_s, _blk_dt, blk_totals = best_of_3(conf_block)
        finally:
            _twtml_config._SYSTEM_PROPERTIES.clear()
            _twtml_config._SYSTEM_PROPERTIES.update(saved_props)
        return {
            **out,
            "mode": "local-protocol",
            "tweets_per_sec": round(totals["count"] / stream_s, 1),
            "protocol_tweets_per_sec": round(len(got) / protocol_s, 1),
            "block_tweets_per_sec": round(
                blk_totals["count"] / blk_stream_s, 1
            ),
            "seconds": round(stream_s, 3),
            "startup_seconds": round(dt - stream_s, 3),
            "batches": totals["batches"],
            "block_batches": blk_totals["batches"],
            "backend": jax.default_backend(),
        }

    if (
        name == "sharded_2e18_2d"
        and n_tweets > 2048
        and jax.default_backend() == "cpu"
    ):
        # program validation, not a speed number: the 2^18 Gram build on a
        # virtual CPU mesh runs ~150 tweets/s — cap the sample so a full
        # suite invocation doesn't stall ~20 min on this one config
        n_tweets = 2048
        out["note"] = "cpu program validation; sample capped at 2048 tweets"

    statuses = list(SyntheticSource(total=n_tweets, seed=3).produce())

    if name == "replay_linear":
        # the BASELINE config is a replayed-tweet FILE source: materialize
        # the synthetic stream to .jsonl once, then measure the real ingest
        # path end-to-end — native block parse → featurize → fused step.
        # The three stages run PIPELINED per pass (VERDICT r1 #4): a worker
        # thread owns the C parser (ctypes releases the GIL), a prefetch
        # thread featurizes the next chunk, and the main thread keeps every
        # device interaction (device_put off-main collapses the transport).
        import queue
        import tempfile
        import threading

        from twtml_tpu.features.blocks import iter_row_chunks, merge_blocks
        from twtml_tpu.models import StreamingLinearRegressionWithSGD
        from twtml_tpu.streaming.sources import BlockReplayFileSource
        from twtml_tpu.utils.benchloop import measure_passes

        feat = Featurizer(now_ms=1785320000000)
        model = StreamingLinearRegressionWithSGD()
        with tempfile.NamedTemporaryFile(
            "w", suffix=".jsonl", delete=False
        ) as fh:
            for s in statuses:
                fh.write(json.dumps(_status_json(s)) + "\n")
            path = fh.name
        try:
            block = merge_blocks(list(BlockReplayFileSource(path).produce()))
            rows = block.rows
            if rows == 0:
                return {
                    **out, "tweets_per_sec": 0.0, "seconds": 0.0,
                    "batches": 0, "final_metric": 0.0,
                    "backend": jax.default_backend(),
                    "note": "replay file produced zero kept rows",
                }
            n_chunks = -(-rows // batch_size)

            def featurize(sub):
                # ragged wire from blocks (r3): the block already holds
                # concatenated units + offsets, so no pad copy at all
                # (tools/bench_ragged.py --ingest block is the paired
                # harness)
                return feat.featurize_parsed_block(
                    sub, row_bucket=batch_size, ragged=True, pack=True
                )

            # warm the compile caches for both the full and the tail chunk
            for sub in iter_row_chunks([block], batch_size):
                model.step(featurize(sub)).mse.block_until_ready()

            def pipeline_source():
                # copy=False: blocks are views, featurized promptly; 4MB
                # blocks amortize per-call overhead. wire=True: the
                # zero-copy emitter — the shipped config-#1 path
                # (--blockWire auto resolves on for the ragged wire)
                return BlockReplayFileSource(
                    path, copy=False, block_bytes=4 << 20, wire=True
                ).produce()

            def one_pass():
                """File bytes → trained weights, stages overlapped: the
                worker owns parse→chunk→featurize (its GIL-held numpy work
                hides under the GIL-free C parse and the main thread's
                device waits); main owns every device interaction. Worker
                failures propagate — a truncated pass must never be scored
                as a fast successful one."""
                model.reset()
                q: "queue.Queue" = queue.Queue(maxsize=8)

                def producer():
                    try:
                        for sub in iter_row_chunks(pipeline_source(), batch_size):
                            q.put(featurize(sub))
                        q.put(None)
                    except BaseException as exc:  # noqa: BLE001
                        q.put(exc)

                t0 = time.perf_counter()
                threading.Thread(target=producer, daemon=True).start()
                last = None
                while True:
                    item = q.get()
                    if item is None:
                        break
                    if isinstance(item, BaseException):
                        raise item
                    last = model.step(item)
                # real host fetch: the weights chain through every step, so
                # one data-dependent scalar fetch closes the timed window
                # over actual work
                float(last.mse)
                return time.perf_counter() - t0, last

            # the shared stall-riding measurement core (benchloop): best-of
            # with a time budget + settle check, never trusting one pass
            best_dt, final, _passes = measure_passes(
                one_pass, repeats=3, time_budget_s=30.0, settled_after=2
            )

            # stage rates for the notes column, measured with the SAME
            # source settings the pipeline uses: parse alone, train alone
            def parse_pass():
                t0 = time.perf_counter()
                for _ in pipeline_source():
                    pass
                return time.perf_counter() - t0, None

            parse_s, _, _ = measure_passes(parse_pass, repeats=3)
            subs = list(iter_row_chunks([block], batch_size))

            def train_pass():
                model.reset()
                t0 = time.perf_counter()
                last = None
                for sub in subs:
                    last = model.step(featurize(sub))
                float(last.mse)  # one real fetch closes the pass
                return time.perf_counter() - t0, None

            train_s, _, _ = measure_passes(train_pass, repeats=3)

            out.update(
                {
                    "tweets_per_sec": round(rows / best_dt, 1),
                    "seconds": round(best_dt, 3),
                    "batches": n_chunks,
                    "final_metric": round(float(final.mse), 3),
                    "parse_tweets_per_sec": round(rows / parse_s, 1),
                    "train_tweets_per_sec": round(rows / train_s, 1),
                }
            )
        finally:
            os.unlink(path)
    elif name == "logistic_sentiment":
        from twtml_tpu.features.sentiment import (
            sentiment_label,
            sentiment_labels,
        )
        from twtml_tpu.models import StreamingLogisticRegressionWithSGD

        feat = Featurizer(now_ms=1785320000000)
        feat.label_fn = sentiment_label
        feat.batch_label_fn = sentiment_labels
        model = StreamingLogisticRegressionWithSGD()
        # ragged wire: +9.7% paired over 193 interleaved rounds
        # (tools/bench_ragged.py --config logistic)
        out.update(_pipeline_rate(model, feat, statuses, batch_size,
                                  ragged=True))
    elif name == "hashing_2e18_l2":
        from twtml_tpu.models import StreamingLinearRegressionWithSGD

        feat = Featurizer(num_text_features=2**18, now_ms=1785320000000)
        model = StreamingLinearRegressionWithSGD(
            num_text_features=2**18, l2_reg=0.1
        )
        # batch: 2048 at the suite's pass shape (see the per-config
        # defaults comment above; tools/bench_2e18.py re-checks the
        # batch curve — b3072 wins long passes, b2048 wins here).
        out.update(_pipeline_rate(model, feat, statuses, batch_size,
                                  ragged=True))
    elif name == "multi_tenant_m8":
        # the multi-tenant model plane (ISSUE 7): 8 models, one jit
        # program, one stacked fetch per tick — the per-config rate here;
        # the PAIRED verdict vs 8 sequential single-tenant pipelines is
        # tools/bench_tenants.py (interleaved arms, per-round ratios)
        from twtml_tpu.parallel import TenantStackModel

        feat = Featurizer(now_ms=1785320000000)
        model = TenantStackModel(8)
        out.update(_pipeline_rate(model, feat, statuses, batch_size,
                                  ragged=True, pack=False))
        out["tenants"] = 8
    elif name == "serving_qps":
        # the serving plane (ISSUE 9): coalesced + depth-8 pipelined
        # inference vs naive per-request, paired on tools/pairedbench.py
        # with the 70 ms modeled-RTT control (the acceptance regime —
        # tools/bench_serving.py is the full harness; this is its compact
        # per-config form for the suite's one-line-per-config record)
        from tools.bench_serving import measure as serving_measure

        rec = serving_measure(
            requests=64, rows_per_request=16, batch_rows=256, depth=8,
            budget=30.0, model_rtt_ms=70.0,
        )
        out.update({
            "qps_pipelined": rec["pipelined_rtt"]["qps_median"],
            "qps_naive": rec["naive_rtt"]["qps_median"],
            "p99_ms": rec["pipelined_rtt"]["p99_ms"],
            "paired_speedup_rtt70": (
                rec["pipelined_rtt"]["paired_speedup_vs_naive"]
            ),
            "paired_speedup_cpu_control": (
                rec["pipelined"]["paired_speedup_vs_naive"]
            ),
        })
    elif name == "wire_codec":
        # the compressed ragged units wire (ISSUE 12): digram codec off vs
        # on, paired on tools/pairedbench.py, in the object-ingest regime
        # with the modeled upload-bound transport control —
        # tools/bench_wirecodec.py is the full harness (both ingest
        # regimes, group-wire arms); this is its compact per-config form
        from tools.bench_wirecodec import measure as codec_measure

        small = n_tweets < 16384  # plumbing-test sizes stay fast
        rec = codec_measure(
            regime="object", n_tweets=min(n_tweets, 32768),
            batch=batch_size if explicit_batch else 4096,
            k=2 if small else 4, budget_s=3.0 if small else 25.0,
        )
        modeled = rec["modeled_upload"]
        out.update({
            "wire_ratio": modeled["wire_ratio_single"],
            "units_ratio": modeled["units_ratio"],
            "paired_codec_cpu_control": (
                rec["control"]["paired_single_codec_vs_raw"]
            ),
            "paired_codec_upload55": (
                modeled["paired_upload_bound"]["55"]["single_codec_vs_raw"]
            ),
            "paired_group_codec_upload55": (
                modeled["paired_upload_bound"]["55"]["group_codec_vs_raw"]
            ),
            "final_metric": rec["control"]["final_mse"],
        })
    elif name == "featurize":
        # one-pass host featurize (ISSUE 15): the featurize stage split
        # into sub-stages and paired r17/truth/fused on the object path
        # plus the block host chain — tools/bench_featurize.py is the
        # full harness; this is its compact per-config form
        from tools.bench_featurize import measure as featurize_measure

        small = n_tweets < 16384  # plumbing-test sizes stay fast
        obj = featurize_measure(
            regime="object", n_tweets=min(n_tweets, 65536),
            batch=batch_size if explicit_batch else 8192,
            budget_s=3.0 if small else 25.0,
        )["object"]
        blk = featurize_measure(
            regime="block", n_tweets=min(n_tweets, 65536),
            batch=batch_size if explicit_batch else 8192,
            budget_s=3.0 if small else 25.0,
        )["block"]
        out.update({
            "paired_fused_vs_r17": obj["paired_fused_vs_r17"],
            "paired_truth_vs_r17": obj["paired_truth_vs_r17"],
            "tweets_per_sec_fused": obj["tweets_per_sec_fused"],
            "paired_block_chain": blk["paired_chain_fused_vs_truth"],
            "block_chain_tweets_per_sec": blk[
                "chain_tweets_per_sec_fused"
            ],
        })
    elif name in ("sharded_dp4", "sharded_dp4_logistic", "sharded_2e18_2d"):
        from twtml_tpu.parallel import ParallelSGDModel, make_mesh
        from twtml_tpu.parallel.sharding import shard_batch

        if len(jax.devices()) < 4:
            return {**out, "skipped": (
                f"needs 4 devices, found {len(jax.devices())} "
                f"({jax.default_backend()})"
            )}
        # per-config mesh shape / feature width; data-axis size sets the
        # row_multiple every padded batch must divide by
        num_data, num_model = (2, 2) if name == "sharded_2e18_2d" else (4, 1)
        mesh = make_mesh(
            num_data=num_data, num_model=num_model, devices=jax.devices()[:4]
        )
        if name == "sharded_2e18_2d":
            feat = Featurizer(num_text_features=2**18, now_ms=1785320000000)
            model = ParallelSGDModel(mesh, num_text_features=2**18, l2_reg=0.1)
        elif name == "sharded_dp4_logistic":
            from twtml_tpu.features.sentiment import (
                sentiment_label,
                sentiment_labels,
            )
            from twtml_tpu.models import StreamingLogisticRegressionWithSGD as LR

            feat = Featurizer(now_ms=1785320000000)
            feat.label_fn = sentiment_label
            feat.batch_label_fn = sentiment_labels
            model = ParallelSGDModel(
                mesh, step_size=0.1,
                residual_fn=LR.residual_fn, prediction_fn=LR.prediction_fn,
                round_predictions=LR.round_predictions,
            )
        else:
            feat = Featurizer(now_ms=1785320000000)
            model = ParallelSGDModel(mesh)
        out.update(
            _pipeline_rate(
                model, feat, statuses, batch_size,
                row_multiple=num_data, shard=lambda b: shard_batch(b, mesh),
            )
        )
    else:
        raise SystemExit(f"unknown config {name!r}")

    out["backend"] = jax.default_backend()
    return out


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    # 65536 default tweets: the per-config default batches (up to 16384)
    # need several chunks per pass to measure a pipeline, not one batch
    n_tweets, batch_size, out_path, child = 65536, 0, "", ""  # batch 0 = default
    selected = list(CONFIGS)
    i = 0
    while i < len(args):
        if args[i] == "--tweets":
            n_tweets = int(args[i + 1]); i += 2
        elif args[i] == "--batch":
            batch_size = int(args[i + 1]); i += 2
        elif args[i] == "--json":
            out_path = args[i + 1]; i += 2
        elif args[i] == "--config":
            child = args[i + 1]; i += 2
        elif args[i] == "--configs":
            selected = [c for c in args[i + 1].split(",") if c]
            unknown = set(selected) - set(CONFIGS)
            if unknown:
                raise SystemExit(f"unknown configs: {sorted(unknown)}")
            i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    force_cpu = bool(os.environ.get("TWTML_BENCH_CPU"))

    if child:
        if force_cpu:
            # TWTML_BENCH_CPU=1 is the ONLY way onto virtual CPU devices
            # (program validation, host-side rates): must happen before
            # this process initializes any backend. Without it real devices
            # win, and the sharded configs skip below 4 chips (run_config)
            # instead of substituting a virtual mesh.
            from twtml_tpu.utils import force_virtual_cpu_devices

            force_virtual_cpu_devices(4 if child.startswith("sharded_") else 1)
        rec = run_config(child, n_tweets, batch_size)
        from twtml_tpu.utils.backend import device_identity

        # every record names the device it ran on, as jax reports it
        rec["device"] = device_identity()
        print(json.dumps(rec))
        return

    if not force_cpu:
        # refuse a chip-less run instead of quietly measuring the CPU:
        # probe the platform in a throwaway subprocess (a chip belongs to
        # one process, so the parent must never initialize one while
        # children need it — the children below run one after another)
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
            capture_output=True, text=True, timeout=300,
        )
        found = probe.stdout.strip().splitlines()[-1:] or ["probe failed"]
        if probe.returncode != 0 or found[0].split()[0] == "cpu":
            raise SystemExit(
                f"bench_suite: no accelerator (device probe: {found[0]!r}, "
                f"exit {probe.returncode}). Set TWTML_BENCH_CPU=1 to run the "
                "suite host-side on purpose — its records then say "
                "platform 'cpu' and are not device results."
            )
    env = dict(os.environ)

    # run provenance (ISSUE 20): ONE monotonic run id for the whole suite
    # invocation (each config line carries its own fingerprint) so suite
    # rows join the telemetry historian's segments run-over-run
    from twtml_tpu.utils.runid import config_fingerprint, next_run_id

    suite_run_id = next_run_id()
    lines = []
    for name in selected:
        proc = None
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--config", name,
                 "--tweets", str(n_tweets), "--batch", str(batch_size)],
                env=env, capture_output=True, text=True, timeout=1800,
            )
            rec = json.loads(proc.stdout.strip().splitlines()[-1])
        except subprocess.TimeoutExpired:
            rec = {"config": name, "error": "timeout (1800s)"}
        except (ValueError, IndexError) as exc:
            detail = (
                (proc.stderr or proc.stdout).strip()[-400:]
                if proc is not None
                else ""
            )
            rec = {"config": name, "error": detail or repr(exc)}
        rec["run_id"] = suite_run_id
        rec["config_fingerprint"] = config_fingerprint(
            {"config": name, "tweets": n_tweets, "batch": batch_size})
        lines.append(rec)
        print(json.dumps(rec), flush=True)
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            fh.write("\n".join(json.dumps(r) for r in lines) + "\n")
    failed = [r["config"] for r in lines if "error" in r]
    if failed:
        # a config that errored is a failed suite, not a JSON line and exit 0
        raise SystemExit(f"bench_suite: config(s) failed: {failed}")


if __name__ == "__main__":
    main()
