"""Share of the chip's HBM bandwidth at which the primal learner's
per-iteration pass over the count matrix runs: the bytes the iterations
NEED over the time the loop took — the new kernel's share of its roofline.

NEEDED (``needed_bytes``, from the configuration's own sizes and what the
program counted): the iterations that ran a batch (the mean ``iterations``
of the span file's ``primal`` instants: the rounds before the
converged-freeze) times ONE streamed read of the ``[B, F]`` count matrix at
the width of the plane the batch took (the instants' ``plane``: 4 bytes an
element on the exact plane, 2 on bf16, 1 on s8). The ``[F]`` weights and
gradient, resident on chip across a pass, and the ``[B]`` vectors are left
out: a lower bound. TOOK: the device time under the ``primal_loop`` scope a
batch (``primal_loop_ms_per_batch`` has the reduction), which also holds
the updater's ``[F]``-sized arithmetic and the convergence norms of every
round: more time, never less. So the share cannot pass 100% while C is
dense: no round can form ``u = C·w`` and ``∇ = Cᵀr`` without reading every
element of C once. Two reads a round (``CountPlane.dot`` then ``.tdot``)
cap it near 50%; the one-pass kernel (``ops/primal_pass.py``) is what can
pass that. A program that keeps iterating after the freeze (the
``fori_loop`` runs all ``numIterations`` rounds and discards the frozen
ones) reads MORE than is counted here and its share falls: the count is of
what MLlib's loop needs (its loop breaks at convergence). On the cell's
mix a batch freezes after ~9 of its 50 rounds, so the first chip reading
was 16.5% with the kernel itself at 92% of HBM over all 50 (PERF.md
section 6, PR 55; the log line prints both): the gap is what a truly early
exit would save. A later PR that iterates on the batch's ACTIVE
columns only (absent columns have a closed form under the threshold) reads
less than ``B·F`` a round and makes this count stale — it then needs a
``benchmark`` issue to restate it first. This is the cell's ONE roofline
share: ``step_roofline`` does not list the cell, because
``work_counts/lasso2e18.py`` is given the configuration alone, cannot see
the rounds a batch needed and so counts all ``numIterations`` — the most,
not the need (an early exit would read over 100% against it). None
without the scope, the instants or the live cell's sizes.
"""

from benchmark.layer_metrics import primal_loop_ms_per_batch as primal
from benchmark.layer_metrics.collective_ici_share import live_config

PLANE_BYTES = {0: 4, 1: 2, 2: 1}   # ops/gram.text_gram: exact, bf16, s8


def needed_bytes(config: dict, iterations: float, element_bytes: float) -> float:
    """``iterations`` reads of the ``[batch_rows, numTextFeatures]`` count
    matrix."""
    return (iterations * float(config["batch_rows"])
            * float(config["model"]["numTextFeatures"]) * element_bytes)


def read(art):
    profile, peaks = art.get("profile"), art.get("peaks")
    if not profile or not profile.get("batches") or not peaks:
        return None
    red, config = primal.of_live_run(), live_config()
    seen = [a for a in primal.instants() if a.get("plane") in PLANE_BYTES]
    if red is None or config is None or not seen:
        return None
    iterations = sum(a["iterations"] for a in seen) / len(seen)
    width = sum(PLANE_BYTES[a["plane"]] for a in seen) / len(seen)
    took = red["loop_s"] / profile["batches"]
    floor = needed_bytes(config, iterations, width) / peaks["hbm_bytes_per_s"]
    # for the log alone: the share the kernel itself runs at, were every
    # round the device executes (numIterations: a fori_loop) a needed one
    executed = needed_bytes(
        config, float(config["model"]["numIterations"]), width
    ) / peaks["hbm_bytes_per_s"]
    print(f"[bench] primal_pass_hbm_share: {iterations:.2f} rounds a batch "
          f"before the freeze, {width:.2f} B an element, {floor * 1e3:.3f} ms "
          f"of HBM, the loop took {took * 1e3:.3f} ms; all "
          f"{config['model']['numIterations']} rounds the device ran: "
          f"{100.0 * executed / took:.1f}% (not the metric)")
    return 100.0 * floor / took
