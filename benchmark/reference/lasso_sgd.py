"""Plain reference of the streaming Lasso learner: NumPy, float64.

Apache Spark 1.6.1 ``mllib.optimization.GradientDescent.runMiniBatchSGD``
with ``LeastSquaresGradient`` and **``L1Updater``** — ``LassoWithSGD``'s
optimizer — driven per micro-batch as ``StreamingLinearAlgorithm.trainOn`` /
``predictOn`` drive any ``GeneralizedLinearAlgorithm`` (predict with the
pre-update weights, then train from them; MLlib ships no streaming Lasso
class, and this is that optimizer in ``StreamingLinearRegressionWithSGD``'s
place). ``L1Updater.compute`` as the Scala source states it:

    thisIterStepSize = stepSize / sqrt(iter)
    w' = w - thisIterStepSize * gradient          (gradient = sum / n)
    shrinkageVal = regParam * thisIterStepSize
    w_i = signum(w'_i) * max(0.0, abs(w'_i) - shrinkageVal)   for EVERY i

— the four numeric weights too: MLlib thresholds the whole vector. The
features are QilinGu/twitter-stream-ml ``MllibHelper.scala``'s (char-bigram
``HashingTF`` with Java ``String.hashCode``, four hand-scaled numerics,
label = the original's retweet count), the stats ``LinearRegression.scala``'s
(mse over HALF_UP-rounded predictions, population stdev).

It imports nothing of the program and nothing of the other references: the
hashing, the design matrix and the loop below are its own, from the
generator's truth columns. ``X`` is the bigram COUNTS as a coordinate
list (row, column, count: 2^18 columns of which a row touches a few
hundred) plus a dense ``[n, 4]`` block; its two products are sums by
``numpy.bincount``.

Departures from MLlib: none in the updater. As ``linear_sgd.py`` and the
program do, the convergence test ``‖w_t − w_{t−1}‖ < tol·max(‖w_t‖, 1)``
also runs after the FIRST iteration, against the batch's starting weights
(MLlib has no previous weights then and tests from the second on), and the
loop stops after the iteration that met it.

``precision="bf16"`` is the CONTROL, not a reference: every product's
floating operands (weights, residuals, numeric features; the counts are
small integers, exact either way) are rounded to bfloat16 first, the
nearest precision below the float32 the configuration states.
``benchmark/compare.py`` must call its output not correct.
"""

from __future__ import annotations

import numpy as np

COUNT_SCALE = 1e-12   # MllibHelper.scala:64-66
AGE_SCALE = 1e-14     # MllibHelper.scala:67


def half_up(x):
    """BigDecimal HALF_UP to an integer: ties away from zero."""
    x = np.asarray(x, dtype=np.float64)
    return np.sign(x) * np.floor(np.abs(x) + 0.5)


def to_bf16(x):
    """float64 → the nearest bfloat16 (ties to even), as float64."""
    bits = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) >> 16 << 16
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def bigram_columns(text: str, num_features: int) -> np.ndarray:
    """``text.toLowerCase.sliding(2)`` → ``HashingTF.indexOf`` of each
    window: Java's ``String.hashCode`` of two UTF-16 units is ``31·a + b``
    (below 2^31, so never negative), then ``nonNegativeMod``. A one-unit
    text is its own single window."""
    units = np.frombuffer(
        text.lower().encode("utf-16-le", "surrogatepass"), dtype="<u2"
    ).astype(np.int64)
    if units.size == 1:
        return units % num_features
    return (31 * units[:-1] + units[1:]) % num_features


class Design:
    """``X = [bigram counts | four numerics]`` of one batch, and the two
    products the loop needs. ``q`` rounds a product's floating operands
    (the control) or is the identity."""

    def __init__(self, texts, numeric, num_features, q):
        n, f = len(texts), int(num_features)
        cols = [bigram_columns(t, f) for t in texts]
        rows = np.repeat(np.arange(n), [c.size for c in cols])
        # duplicate (row, column) pairs are SUMMED: term frequencies
        cells, counts = np.unique(
            rows * f + np.concatenate(cols), return_counts=True)
        self.rows, self.cols = np.divmod(cells, f)
        self.counts = counts.astype(np.float64)
        self.numeric = q(np.asarray(numeric, dtype=np.float64))
        self.n, self.f, self.q = n, f, q

    def dot(self, w):
        w = self.q(w)
        text = np.bincount(
            self.rows, self.counts * w[self.cols], minlength=self.n)
        return text + self.numeric @ w[self.f:]

    def tdot(self, r):
        r = self.q(r)
        text = np.bincount(
            self.cols, self.counts * r[self.rows], minlength=self.f)
        return np.concatenate([text, self.numeric.T @ r])


def soft_threshold(w, shrinkage):
    return np.sign(w) * np.maximum(0.0, np.abs(w) - shrinkage)


class LassoSGD:
    """Weights + the per-batch predict-then-train step."""

    def __init__(self, num_features, *, iterations, step_size, reg_param,
                 convergence_tol, precision="float64"):
        if precision not in ("float64", "bf16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.f = int(num_features)
        self.iterations = int(iterations)
        self.step_size = float(step_size)
        self.reg_param = float(reg_param)
        self.tol = float(convergence_tol)
        self.q = to_bf16 if precision == "bf16" else (lambda a: a)
        self.w = np.zeros(self.f + 4)
        self.ran: list = []   # iterations each batch ran

    def step_batch(self, texts, numeric, labels):
        x = Design(texts, numeric, self.f, self.q)
        y = np.asarray(labels, dtype=np.float64)
        n = y.size
        preds = half_up(x.dot(self.w))
        stats = {
            "count": int(n),
            "mse": float(half_up(np.mean((y - preds) ** 2))),
            "real_stdev": float(half_up(np.std(y))),
            "pred_stdev": float(half_up(np.std(preds))),
        }
        w = self.w
        for it in range(1, self.iterations + 1):
            eta = self.step_size / np.sqrt(it)
            gradient = x.tdot(x.dot(w) - y) / n
            w_new = soft_threshold(w - eta * gradient, self.reg_param * eta)
            converged = np.linalg.norm(w_new - w) < self.tol * max(
                np.linalg.norm(w_new), 1.0)
            w = w_new
            if converged:
                break
        self.ran.append(it)
        self.w = w
        return stats


def kept_lines(chunks):
    """The generator's truth for the lines the trainer's filter keeps, in
    stream order: ``(texts, [followers, favourites, friends, created_ms,
    retweets])``, each column one float64 array."""
    texts, cols = [], [[] for _ in range(5)]
    for ch in chunks:
        keep = np.flatnonzero(ch.kept)
        texts.extend(ch.text[i] for i in keep)
        for dst, src in zip(cols, (ch.followers, ch.favourites, ch.friends,
                                   ch.created_ms, ch.retweets)):
            dst.append(np.asarray(src, dtype=np.float64)[keep])
    return texts, [np.concatenate(c) for c in cols]


def train_on_chunks(chunks, *, batch_rows, n_batches, model, generator,
                    precision="float64"):
    """The ONE signature every reference has (``drivers/train.reference``):
    ``model`` is the configuration file's ``model`` object, ``generator``
    the mix's. Returns ``(learner with .w, [stats per batch])``; the printed
    statistic is each batch's ``mse``."""
    learner = LassoSGD(
        model["numTextFeatures"], iterations=model["numIterations"],
        step_size=model["stepSize"], reg_param=model["l1Reg"],
        convergence_tol=model["convergenceTol"], precision=precision,
    )
    texts, (followers, favourites, friends, created, retweets) = kept_lines(
        chunks)
    if len(texts) < batch_rows * n_batches:
        raise ValueError(f"{len(texts)} kept lines cannot fill {n_batches} "
                         f"batches of {batch_rows}")
    numeric = np.stack([
        followers * COUNT_SCALE, favourites * COUNT_SCALE,
        friends * COUNT_SCALE,
        (float(generator["now_ms"]) - created) * AGE_SCALE,
    ], axis=1)
    out = []
    for b in range(n_batches):
        s = slice(b * batch_rows, (b + 1) * batch_rows)
        out.append(learner.step_batch(texts[s], numeric[s], retweets[s]))
    return learner, out
