"""Plain reference of the streaming logistic learner: NumPy, float64.

MLlib 1.6.1 ``mllib.classification.StreamingLogisticRegressionWithSGD`` at
its no-argument constructor (stepSize 0.1, numIterations 50,
miniBatchFraction 1.0, regParam 0.0), driven per micro-batch as
``StreamingLinearAlgorithm.trainOn`` / ``predictOn`` drive it (predict with
the pre-update weights, then train from them): ``LogisticGradient`` for two
classes (multiplier σ(w·x) − y, gradient summed over the batch and divided by
its size), ``SimpleUpdater`` (no regularisation: w − stepSize/√t · g),
``GradientDescent.runMiniBatchSGD``'s convergence test
(‖w_t − w_{t−1}‖ < 0.001 · max(‖w_t‖, 1)), no intercept, no feature scaling
(``LogisticRegressionWithSGD`` sets neither), ``predictPoint`` with threshold
0.5 (σ(w·x) > 0.5 → 1.0). The features are the linear learner's
(``linear_sgd.featurize``: char-bigram ``HashingTF`` + four scaled numerics).
It imports the benchmark's own ``linear_sgd`` for them and for the bf16
rounding, nothing of the program, and takes nothing the program made.

Departures from MLlib, each shared with the program and noted here:

- THE LABEL. BASELINE.json configs[2] says "binary sentiment" and gives no
  rule, MLlib has none: it is the repo's lexicon rule, this file's OWN copy
  of ``twtml_tpu/features/sentiment.py``: 1.0 unless negative lexicon words
  outnumber positive ones among the lower-cased ``[a-z']+`` words of the
  original's text. The two word lists are DATA of the mix
  (``generator.lexicon``).
- The convergence test also runs after the FIRST iteration, against the
  batch's starting weights (MLlib has no previous weights then and tests from
  the second on): as ``linear_sgd.py`` and ``models/sgd.py`` do.
- The statistic. MLlib prints none; the app prints the misclassification
  share of the batch under the PRE-update weights (``errRate``, three
  decimals), which is ``rate`` here, unrounded.

Per batch it reports what the ``rate`` rule of ``benchmark/compare.py``
reads: ``rate`` and ``near_rows``, the rows whose margin lies in
0 < |w·x| < ``MARGIN_EPS``: float32 rounding may flip their hard prediction
(σ(1e-8) is 0.5 in float32). A margin of exactly 0 is w = 0, the first
batch: σ(0) = 0.5 is not over the threshold on either side.

``precision="bf16"`` is the CONTROL, not a reference (``linear_sgd``'s
rounding of every product's floating operands to bfloat16).
"""

from __future__ import annotations

import re

import numpy as np

from benchmark.reference import linear_sgd

MARGIN_EPS = 1e-4   # float32 margins of size ~1 are good to ~1e-6
_WORD = re.compile(r"[a-z']+")


def labels_of(texts, lexicon) -> np.ndarray:
    pos, neg = set(lexicon["positive"]), set(lexicon["negative"])
    out = np.ones(len(texts))
    for i, t in enumerate(texts):
        words = _WORD.findall(t.lower())
        if sum(w in neg for w in words) > sum(w in pos for w in words):
            out[i] = 0.0
    return out


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


class LogisticSGD(linear_sgd.LinearSGD):
    def step_batch(self, rows, cols, numeric, labels):
        y, n = labels, labels.size
        margin = self._xw(self.w, rows, cols, numeric, n)
        preds = (sigmoid(margin) > 0.5).astype(np.float64)
        stats = {
            "count": int(n),
            "rate": float(np.mean(preds != y)),
            "near_rows": int(np.sum((margin != 0)
                                    & (np.abs(margin) < MARGIN_EPS))),
            "label0_share": float(np.mean(y == 0.0)),
        }
        w = self.w
        for it in range(1, self.iters + 1):
            grad = self._xtr(
                sigmoid(self._xw(w, rows, cols, numeric, n)) - y,
                rows, cols, numeric,
            ) / n
            w_new = w - self.step / np.sqrt(it) * grad
            done = np.linalg.norm(w_new - w) < linear_sgd.CONVERGENCE_TOL * max(
                np.linalg.norm(w_new), 1.0)
            w = w_new
            if done:
                break
        self.w = w
        return stats


def train_on_chunks(chunks, *, batch_rows, n_batches, model, generator,
                    precision="float64"):
    f = int(model["numTextFeatures"])
    learner = LogisticSGD(f, num_iterations=model["numIterations"],
                          step_size=model["stepSize"], precision=precision)
    out = []
    for text, followers, favourites, friends, created, _rt in (
            linear_sgd.kept_batches(chunks, batch_rows, n_batches)):
        rows, cols, numeric = linear_sgd.featurize(
            text, followers, favourites, friends, created,
            generator["now_ms"], f)
        out.append(learner.step_batch(
            rows, cols, numeric, labels_of(text, generator["lexicon"])))
    return learner, out
