"""End-to-end learning-quality tests on an analytically-known stream
(SURVEY.md §7 stage 3: "RMSE-curve parity tests against an
analytically-known synthetic stream").

The text-dependent stream below has labels the hashed-bigram featurization
CAN express (label ≈ a + b·len(text) is representable since the per-tweet
token-count total equals the bigram count ≈ len−1), so streaming SGD with
progressive validation must drive per-batch RMSE from the label scale down
toward the noise floor. A second test documents the featurization ceiling:
label components driven by followers are invisible through the reference's
hand-scaled ×1e-12 numeric features (SURVEY.md §2.5 "poor-man's
normalization"), so RMSE plateaus at that component's variance — faithful
to the reference's behavior, and the reason BASELINE config #4 introduces
bigger featurization."""

import numpy as np

from twtml_tpu.features.featurizer import Featurizer, Status
from twtml_tpu.models import StreamingLinearRegressionWithSGD
from twtml_tpu.streaming.sources import MultiSource, SyntheticSource

WORDS = "tpu stream learn fast jax mesh shard grad psum tweet".split()


def text_only_batches(n_batches=24, batch=512, seed=5, noise=5.0):
    rng = np.random.default_rng(seed)
    feat = Featurizer(now_ms=1785320000000)
    for _ in range(n_batches):
        statuses = []
        for _ in range(batch):
            text = " ".join(rng.choice(WORDS, size=int(rng.integers(3, 12))))
            label = 100 + 2 * len(text) + rng.normal(0, noise)
            statuses.append(
                Status(
                    text="RT " + text,
                    retweeted_status=Status(
                        text=text, retweet_count=int(max(label, 0))
                    ),
                )
            )
        yield feat.featurize_batch(statuses, row_bucket=batch, pre_filtered=True)


def test_rmse_converges_toward_noise_floor():
    model = StreamingLinearRegressionWithSGD(step_size=0.1, num_iterations=50)
    rmses = [float(model.step(b).mse) ** 0.5 for b in text_only_batches()]
    # progressive validation: first batch is scored with zero weights (RMSE
    # at the label scale), late batches approach the noise floor (σ=5)
    assert rmses[0] > 150
    assert np.mean(rmses[-4:]) < 30
    assert np.mean(rmses[-4:]) < rmses[0] / 5


def test_featurization_ceiling_is_faithful():
    """Follower-driven label variance can't be learned through ×1e-12-scaled
    numeric features — the RMSE plateau sits at that component's scale, far
    above the noise floor (reference quirk preserved, SURVEY.md §2.5)."""
    statuses = list(SyntheticSource(total=8 * 512, seed=5).produce())
    feat = Featurizer(now_ms=1785320000000)
    model = StreamingLinearRegressionWithSGD(step_size=0.1, num_iterations=50)
    rmse = None
    for k in range(8):
        batch = feat.featurize_batch(
            statuses[k * 512 : (k + 1) * 512], row_bucket=512, pre_filtered=True
        )
        rmse = float(model.step(batch).mse) ** 0.5
    assert 150 < rmse < 400  # plateaued at the unlearnable component's stdev


def test_sharded_receivers_feed_one_stream():
    import time

    shards = [SyntheticSource(total=25, seed=s) for s in range(4)]
    multi = MultiSource(shards)
    got = []
    multi.start(got.append)
    deadline = time.time() + 10
    while not multi.exhausted and time.time() < deadline:
        time.sleep(0.01)
    multi.stop()
    assert multi.exhausted
    assert len(got) == 100  # 4 shards × 25 tweets, all delivered


def test_rmse_curve_identical_across_ingest_modes(tmp_path):
    """Streaming 8 micro-batches from a FILE with weights carried across
    batches: the object path and the native block path must produce the
    SAME per-batch MSE curve — the 'identical RMSE curves' acceptance bar
    (BASELINE.md north star) applied to the ingest modes."""
    import json

    from twtml_tpu.features.blocks import merge_blocks
    from twtml_tpu.streaming.sources import BlockReplayFileSource

    statuses = list(SyntheticSource(total=2048, seed=11).produce())
    path = tmp_path / "stream.jsonl"
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(s.to_json()) + "\n")

    feat = Featurizer(now_ms=1785320000000)
    B = 256

    model_o = StreamingLinearRegressionWithSGD(num_iterations=10)
    curve_o = []
    for i in range(0, 2048, B):
        out = model_o.step(feat.featurize_batch_units(
            statuses[i : i + B], row_bucket=B, unit_bucket=64,
            pre_filtered=True,
        ))
        curve_o.append(float(out.mse))

    block = merge_blocks(list(BlockReplayFileSource(str(path)).produce()))
    assert block.rows == 2048
    model_b = StreamingLinearRegressionWithSGD(num_iterations=10)
    curve_b = []
    for i in range(0, 2048, B):
        sub = type(block)(
            block.numeric[i : i + B],
            block.units[block.offsets[i] : block.offsets[i + B]],
            block.offsets[i : i + B + 1] - block.offsets[i],
            block.ascii[i : i + B],
        )
        out = model_b.step(feat.featurize_parsed_block(
            sub, row_bucket=B, unit_bucket=64
        ))
        curve_b.append(float(out.mse))

    assert len(curve_o) == 8
    np.testing.assert_allclose(curve_o, curve_b, rtol=1e-6)
    assert curve_o[-1] < curve_o[0]  # it actually learns along the curve
