"""Plain reference of the streaming linear learner: NumPy, float64.

Follows the published description — QilinGu/twitter-stream-ml
``MllibHelper.scala`` (filter, char-bigram ``HashingTF`` with Java
``String.hashCode``, four hand-scaled numerics, label = the original's
retweet count) and MLlib 1.6 ``GradientDescent.runMiniBatchSGD`` with
``LeastSquaresGradient`` and ``SimpleUpdater`` / ``SquaredL2Updater``, driven
per micro-batch by ``StreamingLinearRegressionWithSGD`` (predict with the
pre-update weights, then train), stats as ``LinearRegression.scala`` prints
them (mse over HALF_UP-rounded predictions, population stdev). It imports
nothing of the program and takes nothing the program made: its inputs are
the generator's truth columns.

The design matrix is never built dense (2^18 columns): products run over the
per-occurrence (row, column) pairs with ``np.bincount``.

``precision="bf16"`` is the CONTROL, not a reference: every product's
floating operands (weights, residuals, numeric features) are rounded to
bfloat16 first, the nearest precision below the float32 the configurations
state. ``benchmark/compare.py`` must call its output not correct.
"""

from __future__ import annotations

import numpy as np

COUNT_SCALE = 1e-12   # MllibHelper.scala:64-66
AGE_SCALE = 1e-14     # MllibHelper.scala:67
CONVERGENCE_TOL = 0.001  # MLlib GradientDescent default


def half_up(x):
    """BigDecimal HALF_UP to an integer: ties away from zero."""
    x = np.asarray(x, dtype=np.float64)
    return np.where(x >= 0, np.floor(x + 0.5), np.ceil(x - 0.5))


def to_bf16(x):
    """Round float64 values to the nearest bfloat16 (ties to even)."""
    f = np.asarray(x, dtype=np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def featurize(texts, followers, favourites, friends, created_ms, now_ms,
              num_text_features):
    """→ (rows, cols, numeric): one (row, col) pair per bigram OCCURRENCE
    (HashingTF's term frequency is their count), numeric ``[n, 4]``."""
    units, lengths = [], np.empty(len(texts), dtype=np.int64)
    for i, t in enumerate(texts):
        b = np.frombuffer(
            t.lower().encode("utf-16-le", "surrogatepass"), dtype="<u2"
        )
        units.append(b)
        lengths[i] = b.size
    flat = (np.concatenate(units) if units else np.zeros(0, "<u2")).astype(
        np.int64
    )
    row_of = np.repeat(np.arange(len(texts)), lengths)
    # Scala text.sliding(2): windows (j, j+1) inside one row; a one-unit
    # text is its own single window and hashes to its unit
    same = row_of[:-1] == row_of[1:]
    rows = row_of[:-1][same]
    h = (31 * flat[:-1] + flat[1:])[same]   # < 2^31: no wrap, never negative
    single = np.flatnonzero(lengths == 1)
    if single.size:
        starts = np.cumsum(lengths) - lengths
        rows = np.concatenate([rows, single])
        h = np.concatenate([h, flat[starts[single]]])
    cols = h % int(num_text_features)
    numeric = np.stack([
        np.asarray(followers, np.float64) * COUNT_SCALE,
        np.asarray(favourites, np.float64) * COUNT_SCALE,
        np.asarray(friends, np.float64) * COUNT_SCALE,
        (float(now_ms) - np.asarray(created_ms, np.float64)) * AGE_SCALE,
    ], axis=1)
    return rows, cols, numeric


class LinearSGD:
    """Weights + the per-batch predict-then-train step."""

    def __init__(self, num_text_features, num_iterations=50, step_size=0.005,
                 l2_reg=0.0, precision="float64"):
        if precision not in ("float64", "bf16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.f = int(num_text_features)
        self.iters = int(num_iterations)
        self.step = float(step_size)
        self.l2 = float(l2_reg)
        self.q = to_bf16 if precision == "bf16" else (lambda a: a)
        self.w = np.zeros(self.f + 4, dtype=np.float64)

    def _xw(self, w, rows, cols, numeric, n):
        w = self.q(w)
        return (
            np.bincount(rows, weights=w[cols], minlength=n)
            + self.q(numeric) @ w[self.f:]
        )

    def _xtr(self, r, rows, cols, numeric):
        r = self.q(r)
        return np.concatenate([
            np.bincount(cols, weights=r[rows], minlength=self.f),
            self.q(numeric).T @ r,
        ])

    def predict(self, rows, cols, numeric):
        return half_up(self._xw(self.w, rows, cols, numeric, numeric.shape[0]))

    def step_batch(self, rows, cols, numeric, labels):
        """One micro-batch → the stats the program publishes for it."""
        y = np.asarray(labels, dtype=np.float64)
        n = y.size
        preds = self.predict(rows, cols, numeric)
        stats = {
            "count": int(n),
            "mse": float(half_up(np.mean((y - preds) ** 2))),
            "real_stdev": float(half_up(np.std(y))),
            "pred_stdev": float(half_up(np.std(preds))),
        }
        w = self.w
        for it in range(1, self.iters + 1):
            grad = self._xtr(
                self._xw(w, rows, cols, numeric, n) - y, rows, cols, numeric
            ) / n
            eta = self.step / np.sqrt(it)
            w_new = w * (1.0 - eta * self.l2) - eta * grad
            done = np.linalg.norm(w_new - w) < CONVERGENCE_TOL * max(
                np.linalg.norm(w_new), 1.0
            )
            w = w_new
            if done:
                break
        self.w = w
        return stats


def kept_batches(chunks, batch_rows, n_batches):
    """The generator's truth for the lines the filter keeps, in stream
    order, cut into ``n_batches`` batches of ``batch_rows``: yields
    ``(texts, followers, favourites, friends, created_ms, retweets)``."""
    text, cols5 = [], [[] for _ in range(5)]
    for ch in chunks:
        keep = np.flatnonzero(ch.kept)
        text.extend(ch.text[i] for i in keep)
        for dst, src in zip(cols5, (ch.followers, ch.favourites, ch.friends,
                                    ch.created_ms, ch.retweets)):
            dst.append(np.asarray(src)[keep])
    cols5 = [np.concatenate(c) for c in cols5]
    if len(text) < batch_rows * n_batches:
        raise ValueError(
            f"{len(text)} kept lines cannot fill {n_batches} batches of "
            f"{batch_rows}"
        )
    for b in range(n_batches):
        s = slice(b * batch_rows, (b + 1) * batch_rows)
        yield (text[s], *(c[s] for c in cols5))


def train_on_chunks(chunks, *, batch_rows, n_batches, model, generator,
                    precision="float64"):
    """The ONE signature every reference has (``drivers/train.reference``
    calls the module the configuration's ``reference`` names): ``model`` is
    the configuration file's ``model`` object, ``generator`` the mix's; a
    reference picks what it needs out of them itself. Filters the generated
    lines, cuts them into ``n_batches`` batches of ``batch_rows`` kept lines
    in stream order and trains. Returns ``(model with .w, [stats per
    batch])``; the printed statistic is each batch's ``mse``."""
    f = int(model["numTextFeatures"])
    learner = LinearSGD(
        f, num_iterations=model["numIterations"], step_size=model["stepSize"],
        l2_reg=model["l2Reg"], precision=precision,
    )
    out = []
    for text, followers, favourites, friends, created, retweets in kept_batches(
            chunks, batch_rows, n_batches):
        rows, cols, numeric = featurize(
            text, followers, favourites, friends, created,
            generator["now_ms"], f,
        )
        out.append(learner.step_batch(rows, cols, numeric, retweets))
    return learner, out
