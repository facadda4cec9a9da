"""A tree ensemble as the reference reads it, for the tree kinds' model files.

``bench/models/random_forest.py`` and ``bench/models/gradient_boosting.py``
differ only in whether the trees' leaves are averaged or added, and in
their class boundary; both read the served ensemble here.
"""
from __future__ import annotations

import numpy as np

from bench.precision import F64, Precision

#: a tree comparison whose estimate lies within this share of the feature's
#: own scale (|value| + scaler scale) of the threshold may go either way: the
#: program's float32 estimates carry relative errors near 1e-6 (compensated
#: prefix sums), so 1e-4 leaves them a hundredfold room, while bfloat16
#: (2^-8 relative) lies forty times beyond it.
AMBIGUITY = 1e-4


class Trees:
    """A tree ensemble as arrays: nodes split ``x[feature] <= threshold``,
    leaves loop to themselves; ``mean`` averages the trees (random forest),
    otherwise they add up (boosting, learning rate folded into the leaves)."""

    def __init__(self, feature, threshold, left, right, value, depth: int,
                 base: float, mean: bool, scaler_mean, scaler_scale):
        self.feature = np.asarray(feature, np.int64)
        self.threshold = np.asarray(threshold, np.float64)
        self.left = np.asarray(left, np.int64)
        self.right = np.asarray(right, np.int64)
        self.value = np.asarray(value, np.float64)
        self.depth = int(depth)
        self.base = float(base)
        self.mean = bool(mean)
        self.mu = np.asarray(scaler_mean, np.float64)
        self.scale = np.asarray(scaler_scale, np.float64)

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]

    def scaled(self, full, r: Precision = F64):
        return r(r(full - self.mu) / self.scale)

    def predict(self, xs, r: Precision = F64) -> np.ndarray:
        """Outputs for scaled rows ``xs`` (rows, F)."""
        thr, leaf = r(self.threshold), r(self.value)
        t_idx = np.arange(self.n_trees)[:, None]
        rows = np.arange(xs.shape[0])[None, :]
        idx = np.zeros((self.n_trees, xs.shape[0]), np.int64)
        for _ in range(self.depth):
            f = self.feature[t_idx, idx]
            go_left = xs[rows, f] <= thr[t_idx, idx]
            idx = np.where(go_left, self.left[t_idx, idx], self.right[t_idx, idx])
        total = r(np.sum(leaf[t_idx, idx], axis=0))
        return r(self.base + (r(total / self.n_trees) if self.mean else total))

    def interval(self, full) -> tuple[float, float]:
        """(lowest, highest) output of one unscaled row when every comparison
        within :data:`AMBIGUITY` of its threshold may go either way."""
        xs = self.scaled(full)
        eps = AMBIGUITY * (np.abs(full) + self.scale) / self.scale
        lo = hi = 0.0
        for t in range(self.n_trees):
            nodes = {0}
            for _ in range(self.depth):
                nxt = set()
                for i in nodes:
                    f, th = self.feature[t, i], self.threshold[t, i]
                    if abs(xs[f] - th) <= eps[f]:
                        nxt.update((self.left[t, i], self.right[t, i]))
                    else:
                        nxt.add(self.left[t, i] if xs[f] <= th else self.right[t, i])
                nodes = nxt
            leaves = [self.value[t, i] for i in nodes]
            lo, hi = lo + min(leaves), hi + max(leaves)
        if self.mean:
            lo, hi = lo / self.n_trees, hi / self.n_trees
        return self.base + lo, self.base + hi

    def intervals(self, full) -> tuple[np.ndarray, np.ndarray]:
        """:meth:`interval` of each unscaled row of ``full`` (rows, F), to the
        bit: a row with no comparison within the ambiguity on its path sums
        its one leaf a tree in the same order; any other row goes through
        :meth:`interval` itself."""
        full = np.asarray(full, np.float64)
        xs = self.scaled(full)
        eps = AMBIGUITY * (np.abs(full) + self.scale) / self.scale
        t_idx = np.arange(self.n_trees)[:, None]
        rows = np.arange(full.shape[0])[None, :]
        idx = np.zeros((self.n_trees, full.shape[0]), np.int64)
        either = np.zeros(full.shape[0], bool)
        for _ in range(self.depth):
            f = self.feature[t_idx, idx]
            x, th = xs[rows, f], self.threshold[t_idx, idx]
            either |= np.any(np.abs(x - th) <= eps[rows, f], axis=0)
            idx = np.where(x <= th, self.left[t_idx, idx], self.right[t_idx, idx])
        leaf = self.value[t_idx, idx]
        total = np.zeros(full.shape[0])
        for t in range(self.n_trees):
            total = total + leaf[t]
        if self.mean:
            total = total / self.n_trees
        lo = self.base + total
        hi = lo.copy()
        for i in np.flatnonzero(either):
            lo[i], hi[i] = self.interval(full[i])
        return lo, hi


def read(pipeline, mean: bool) -> Trees:
    """The served ensemble's arrays and the pipeline's scaler."""
    model = pipeline.model
    ens = model.ensemble
    return Trees(
        np.asarray(ens.feature), np.asarray(ens.threshold), np.asarray(ens.left),
        np.asarray(ens.right), np.asarray(ens.value), ens.depth, model.base,
        mean, pipeline.scaler_mean, pipeline.scaler_scale,
    )


def raw(trees: Trees, full, r: Precision = F64) -> np.ndarray:
    """The score of unscaled rows ``full`` (rows, F)."""
    return trees.predict(trees.scaled(full, r), r)


def interval(trees: Trees, full) -> tuple[np.ndarray, np.ndarray]:
    """The score's range of each unscaled row of ``full`` (rows, F)."""
    return trees.intervals(full)


def ops_per_row(trees: Trees) -> int:
    """One comparison per node visited: trees × depth."""
    return trees.n_trees * trees.depth
