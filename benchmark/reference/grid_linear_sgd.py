"""Plain reference of a CHAMPION AND ITS CHALLENGERS on one stream: NumPy,
float64 (configuration ``hash2e18-grid4``: ``--tenants M --tenantKey all``
with ``--tenantStepSize`` / ``--tenantL2Reg``).

M learners, ONE recipe each, ALL rows each. Nothing is routed: every kept
line of a batch goes to every arm, and arm m makes
``linear_sgd.LinearSGD``'s step on the whole batch (predict with its
pre-update weights, then train: MLlib's ``GradientDescent`` with
``LeastSquaresGradient`` and ``SquaredL2Updater``, everything of it in
``linear_sgd.py``) under its OWN step size and L2 strength. That is M
independent runs of the plain update, which is all the law says: arm m's
model is the single model's under arm m's recipe on that stream. How a
program shares work between the arms (one count matrix, one Gram matrix) is
not this file's business, and nothing of it is here. The upstream reference
(QilinGu/twitter-stream-ml) trains one model; the grid is Spark 1.6.1
``ml-guide``'s ``ParamGridBuilder().addGrid(lr.regParam, ...)`` made
prequential: every arm predicts each batch before it trains on it.

THE RECIPES are read from the configuration's ``model``, imported from
nowhere: ``model["tenantStepSize"]`` and ``model["tenantL2Reg"]``, one number
an arm, arm 0 (the champion) first; the number of arms is
``model["tenants"]`` and both lists must have that length.

Per batch it reports what the app prints for the batch under this key
(``parallel/tenants.aggregate_tenant_output``): ``count``, the batch's rows
(every arm saw them all; NOT M times them), and the CHAMPION's ``mse``: the
mean over the rows of the squared error of arm 0's HALF_UP-rounded
pre-update prediction, HALF_UP-rounded once.

``.w`` is ``[M, F+4]``, the stacked checkpoint's layout. ``precision="bf16"``
is the CONTROL (``linear_sgd``'s rounding of every product's floating
operands, inside each arm's update), not a reference.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import linear_sgd


def recipes(model: dict) -> list:
    """``[(stepSize, l2Reg)]``, one pair an arm, from the configuration's
    ``model``."""
    m = int(model["tenants"])
    steps, l2s = model["tenantStepSize"], model["tenantL2Reg"]
    if not len(steps) == len(l2s) == m:
        raise ValueError(
            f"{m} arms need {m} step sizes and {m} L2 strengths, got "
            f"{len(steps)} and {len(l2s)}")
    return [(float(s), float(r)) for s, r in zip(steps, l2s)]


class GridLinearSGD:
    """One ``LinearSGD`` an arm, each under its own recipe, all on the same
    rows; the batch's stats are arm 0's."""

    def __init__(self, num_text_features, recipes, **learner):
        self.f = int(num_text_features)
        self.arms = [
            linear_sgd.LinearSGD(self.f, step_size=step, l2_reg=l2, **learner)
            for step, l2 in recipes
        ]

    @property
    def w(self) -> np.ndarray:
        return np.stack([a.w for a in self.arms])

    def step_batch(self, texts, followers, favourites, friends, created_ms,
                   retweets, now_ms) -> dict:
        rows, cols, numeric = linear_sgd.featurize(
            texts, followers, favourites, friends, created_ms, now_ms, self.f)
        y = np.asarray(retweets, np.float64)
        stats = [a.step_batch(rows, cols, numeric, y) for a in self.arms]
        return {"count": stats[0]["count"], "mse": stats[0]["mse"],
                "arm_mse": [s["mse"] for s in stats]}


def train_on_chunks(chunks, *, batch_rows, n_batches, model, generator,
                    precision="float64"):
    """The ONE signature every reference has."""
    learner = GridLinearSGD(
        model["numTextFeatures"], recipes(model),
        num_iterations=model["numIterations"], precision=precision,
    )
    out = [
        learner.step_batch(*batch, now_ms=generator["now_ms"])
        for batch in linear_sgd.kept_batches(chunks, batch_rows, n_batches)
    ]
    return learner, out
