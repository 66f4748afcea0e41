"""Share of the model's hashed text weights that are EXACTLY zero after a
batch's step — the number an operator runs Lasso for: the mean, over the
span file's ``primal`` instants, of ``zero_weights`` over the
configuration's ``numTextFeatures`` (the program counts the zeros inside
the step, after the last soft threshold, and fetches the count with the
batch's statistics). MLlib's ``L1Updater`` leaves a column the stream does
not inform at zero for good; under ``SquaredL2Updater`` it only decays.
Over every batch of the window run, as ``gram_fast_plane_share`` is; the
run replays its pool, so the share falls while the first pass still meets
new columns and then settles. A property of the MODEL the step leaves,
not of a layer's speed: it moves no end-to-end metric while every round
reads a dense C (``moves`` names the rate an active-column form would
move), and ``better: higher`` is the manifest's word, not a goal — an
all-zero model reads 100 and is what ``correct`` (``weights_dev``) is
there to refuse (PERF.md section 3). None without the instants or the
live cell's sizes."""

from benchmark.layer_metrics import primal_loop_ms_per_batch as primal
from benchmark.layer_metrics.collective_ici_share import live_config


def read(art):
    seen, config = primal.instants(), live_config()
    if not seen or config is None:
        return None
    f = float(config["model"]["numTextFeatures"])
    return 100.0 * sum(a["zero_weights"] for a in seen) / len(seen) / f
