"""Plain reference of a served answer, and the comparison that decides ``correct``.

For a request that the program served with plan ``z`` after ``it`` planner
iterations, the reference recomputes, from the host store and the served
model as the file of its kind reads it (``bench/models/<kind>.py``: the
score of a row, its range where comparisons may go either way, the class
boundary), in float64 NumPy:

* the aggregate estimates over the first ``z_j`` rows of each group's
  stored sample order (paper §3.2: CLT with finite-population correction
  for AVG/SUM/COUNT/VAR/STD; the nearest-rank order statistic and Beta
  order-statistic bootstrap replicates for MEDIAN/QUANTILE, appendix D);
* the model on them: the point answer ŷ, and the m QMC rows of the AMI
  stage (paper §3.3, unscrambled Sobol points through Φ⁻¹);
* the Eq. 1 guarantee probability: for regression Pr(|Y − ŷ| ≤ δ) of the
  Normal fitted to the QMC outputs; for classification (δ = 0) Pr(Y = ŷ),
  the share of the QMC rows whose class is ŷ.

It imports nothing of the program.  The bootstrap replicate ranks come from
counter-based draws (key ``fold_in(PRNGKey(0), it)``, Marsaglia-Tsang gammas
in four fixed rounds), which the reference draws again with JAX's RNG, so
both sides use the same replicates.

``Precision("bfloat16")`` is the control: the same arithmetic with every
array rounded to bfloat16 after each step (sums accumulate wide, as on the
chip); it must come out as not correct.
"""
from __future__ import annotations

import math
import warnings

import numpy as np
from scipy.special import ndtr, ndtri
from scipy.stats import qmc

from bench.precision import F64, Precision
# the tree kinds' reading of an ensemble, kept under its old name here
from bench.trees import AMBIGUITY, Trees  # noqa: F401

#: the clip the AMI transform applies to its uniforms before Φ⁻¹
U_CLIP = 1e-7


# ---------------------------------------------------------------- estimates
def parametric(op: str, x: np.ndarray, z: int, n: int, r: Precision = F64):
    """(value, sigma) of a parametric aggregate from a z-row sample."""
    x = r(x[:z])
    zf = float(max(z, 1))
    mean = r(np.sum(x) / zf) if z > 0 else r(0.0)
    d = r(x - mean)
    m2 = r(np.sum(r(d * d)) / zf) if z > 1 else r(0.0)
    m4 = r(np.sum(r(r(d * d) ** 2)) / zf) if z > 1 else r(0.0)
    s2 = r(m2 * zf / max(zf - 1.0, 1.0))
    fpc = r(math.sqrt(min(max((n - z) / max(n - 1.0, 1.0), 0.0), 1.0)))
    se = r(np.sqrt(max(float(s2), 0.0) / zf) * fpc)
    var_s2 = r(max((m4 - m2 * m2 * (zf - 3.0) / max(zf - 1.0, 1.0)) / zf, 0.0))
    if op == "avg":
        value, sigma = mean, se
    elif op in ("sum", "count"):
        value, sigma = r(n * mean), r(n * se)
    elif op == "var":
        value, sigma = s2, r(np.sqrt(var_s2) * fpc)
    elif op == "std":
        value = r(np.sqrt(max(float(s2), 0.0)))
        sigma = r(np.sqrt(var_s2 / max(4.0 * float(s2), 1e-12)) * fpc)
    else:
        raise ValueError(f"not a parametric aggregate: {op!r}")
    if z >= n:
        sigma = r(0.0)
    return float(value), float(sigma)


def nearest_rank(q: float, z: int) -> int:
    return int(min(max(math.floor(q * (z - 1.0) + 0.5), 0), max(z - 1, 0)))


_BOOT = {}


def boot_ranks(it: int, z, qs, n_boot: int) -> np.ndarray:
    """(h, n_boot) bootstrap ranks into each holistic sample's sorted order.

    The replicate of a q-quantile over a z-row sample is the order
    statistic at rank floor(z·V), V ~ Beta(r+1, z−r), r the nearest rank;
    V = Ga/(Ga+Gb) from two Marsaglia-Tsang gammas of four fixed proposal
    rounds, keys split from ``fold_in(PRNGKey(0), it)``.
    """
    import jax
    import jax.numpy as jnp

    if n_boot not in _BOOT:

        def draw(it, z, qs):
            f32 = jnp.float32
            key = jax.random.fold_in(jax.random.PRNGKey(0), it)
            zf = z.astype(f32)
            zm1 = jnp.maximum(z - 1, 0)
            rank = jnp.clip(jnp.floor(qs * (zf - 1.0) + 0.5).astype(jnp.int32),
                            0, zm1)
            shape = (z.shape[0], n_boot)

            def gamma(k, a):
                d = jnp.broadcast_to(a[:, None], shape) - 1.0 / 3.0
                c = 1.0 / jnp.sqrt(9.0 * d)
                out = d + 1.0 / 3.0
                done = jnp.zeros(shape, bool)
                for kk in jax.random.split(k, 4):
                    kn, ku = jax.random.split(kk)
                    x = jax.random.normal(kn, shape)
                    v = (1.0 + c * x) ** 3
                    u = jax.random.uniform(ku, shape, minval=1e-38)
                    sv = jnp.where(v > 0.0, v, 1.0)
                    ok = (v > 0.0) & (
                        jnp.log(u) < 0.5 * x * x + d - d * sv + d * jnp.log(sv)
                    )
                    out = jnp.where(ok & ~done, d * sv, out)
                    done = done | ok
                return out

            ka, kb = jax.random.split(key)
            ga = gamma(ka, (rank + 1).astype(f32))
            gb = gamma(kb, jnp.maximum(z - rank, 1).astype(f32))
            v = ga / (ga + gb)
            return jnp.clip(jnp.floor(zf[:, None] * v).astype(jnp.int32),
                            0, zm1[:, None])

        _BOOT[n_boot] = jax.jit(draw)
    out = _BOOT[n_boot](np.int32(it), np.asarray(z, np.int32),
                        np.asarray(qs, np.float32))
    return np.asarray(out)


def estimates(features, groups, z, n, it: int, n_boot: int, r: Precision = F64):
    """(value (k,), sigma (k,), replicates {j: (B,) sorted}) at plan z.

    ``features``: [(op, q)]; ``groups``: each feature's group values in the
    store's sample order (a prefix is a simple random sample).
    """
    k = len(features)
    value, sigma, reps = np.zeros(k), np.zeros(k), {}
    hol = [j for j, (op, _) in enumerate(features) if op in ("median", "quantile")]
    for j, (op, _q) in enumerate(features):
        if j not in hol:
            value[j], sigma[j] = parametric(op, groups[j], int(z[j]), int(n[j]), r)
    if hol:
        ranks = boot_ranks(it, [z[j] for j in hol],
                           [features[j][1] for j in hol], n_boot)
        for row, j in enumerate(hol):
            zj, nj = int(z[j]), int(n[j])
            if zj <= 0:
                value[j], reps[j] = 0.0, np.zeros(n_boot)
                continue
            s = np.sort(r(groups[j][:zj]))
            value[j] = s[nearest_rank(features[j][1], zj)]
            reps[j] = (np.full(n_boot, value[j]) if zj >= nj
                       else np.sort(s[ranks[row]]))
    return value, sigma, reps


# ------------------------------------------------------------ the guarantee
_SOBOL: dict = {}


def ami_uniforms(m: int, k: int) -> np.ndarray:
    """The first m unscrambled Sobol points in k dimensions, mid-cell."""
    if (m, k) not in _SOBOL:
        with warnings.catch_warnings():
            # the first m points, as the program draws them, whatever m is
            warnings.simplefilter("ignore", UserWarning)
            pts = qmc.Sobol(k, scramble=False, bits=32).random(m)
        _SOBOL[(m, k)] = (np.round(pts * 2.0**32) + 0.5) / 2.0**32
    return _SOBOL[(m, k)]


def ami_rows(value, sigma, reps, m: int, r: Precision = F64) -> np.ndarray:
    """(m, k) feature rows of the AMI stage: x̂ + σ·Φ⁻¹(u) for parametric
    features, the replicates' inverse CDF at u for holistic ones."""
    u = ami_uniforms(m, len(value))
    rows = r(value[None, :] + r(sigma[None, :] * r(ndtri(np.clip(u, U_CLIP, 1 - U_CLIP)))))
    for j, rep in reps.items():
        b = rep.shape[0]
        rows[:, j] = rep[np.clip((u[:, j] * b).astype(np.int64), 0, b - 1)]
    return rows


def guarantee_prob(y_hat: float, y, delta: float, r: Precision = F64) -> float:
    """Pr(|Y − ŷ| ≤ δ) for Y ~ Normal(mean(y), sd(y))."""
    y = r(y)
    mean = float(r(np.mean(y)))
    sd = float(r(np.sqrt(np.mean(r(r(y - mean) ** 2)))))
    bias = mean - y_hat
    if sd <= 1e-12:
        return float(abs(bias) <= delta)
    return float(r(ndtr((delta - bias) / sd) - ndtr((-delta - bias) / sd)))


class Model:
    """The served model, as the file of its kind reads it.

    ``kind`` is the module of ``bench/models/<kind>.py``: ``of(pipeline)``
    reads the model's arrays, ``raw(arrays, full, r)`` scores unscaled rows,
    ``interval(arrays, full)`` gives each row's (lo, hi) score where
    comparisons may go either way, ``threshold`` is the class boundary
    (class 1 where the score is above it) and ``ops_per_row(arrays)`` the
    operations one row needs.  ``task``: ``regression`` or
    ``classification``.
    """

    def __init__(self, kind, pipeline, task: str):
        self.kind, self.task = kind, task
        self.arrays = kind.of(pipeline)

    @property
    def classifies(self) -> bool:
        return self.task == "classification"

    @property
    def threshold(self) -> float:
        return float(self.kind.threshold)

    def label(self, score) -> float:
        """What the program serves for a score: the score, or its class."""
        return float(score > self.threshold) if self.classifies else float(score)

    def raw(self, full, r: Precision = F64) -> np.ndarray:
        return np.asarray(self.kind.raw(self.arrays, full, r), np.float64)

    def interval(self, full) -> tuple[np.ndarray, np.ndarray]:
        lo, hi = self.kind.interval(self.arrays, full)
        return np.asarray(lo, np.float64), np.asarray(hi, np.float64)

    def ops_per_row(self) -> int:
        return int(self.kind.ops_per_row(self.arrays))

    def classes(self, lo, hi) -> tuple[np.ndarray, np.ndarray]:
        """(lowest, highest) class that scores in [lo, hi] allow.

        The class decision ``score > threshold`` is one more comparison,
        so a score within :data:`AMBIGUITY` of the boundary's own scale
        (|threshold| + 1) may go either way, as a tree comparison may.
        """
        t = self.threshold
        eps = AMBIGUITY * (abs(t) + 1.0)
        return ((np.asarray(lo) > t + eps).astype(np.float64),
                (np.asarray(hi) > t - eps).astype(np.float64))


class Answer:
    """What the reference (or a control in its precision) says of one request:
    the point's score and its range, the QMC rows' scores and, for
    classification, their ranges."""

    def __init__(self, y_hat, y_lo, y_hi, y_ami, ami_lo=None, ami_hi=None):
        self.y_hat, self.y_lo, self.y_hi, self.y_ami = y_hat, y_lo, y_hi, y_ami
        self.ami_lo, self.ami_hi = ami_lo, ami_hi


def answer(problem: dict, model: Model, z, it: int, r: Precision = F64) -> Answer:
    """The model's scores at plan z.

    ``problem``: ``features`` [(op, q)], ``groups``, ``n``, ``exact`` (e,),
    ``m``, ``n_boot``.
    """
    value, sigma, reps = estimates(problem["features"], problem["groups"], z,
                                   problem["n"], it, problem["n_boot"], r)
    exact = np.asarray(problem["exact"], np.float64)
    point = np.concatenate([value, exact])
    rows = ami_rows(value, sigma, reps, problem["m"], r)
    full = np.concatenate([rows, np.broadcast_to(exact, (rows.shape[0], exact.size))], 1)
    y_ami = model.raw(full, r)
    y_hat = float(model.raw(point[None, :], r)[0])
    if r.dtype is not None:
        return Answer(y_hat, y_hat, y_hat, y_ami, y_ami, y_ami)
    lo, hi = model.interval(point[None, :])
    ami = model.interval(full) if model.classifies else (None, None)
    return Answer(y_hat, lo[0], hi[0], y_ami, *ami)


def served(ans: Answer, model: Model, delta: float, r: Precision = F64) -> dict:
    """What a program that computed ``ans`` would serve: ``y_hat`` and
    ``prob``.  Classification: the point's class and the share of the QMC
    rows in it, a count over m that no precision rounds."""
    y = model.label(ans.y_hat)
    if not model.classifies:
        return {"y_hat": y, "prob": guarantee_prob(y, ans.y_ami, delta, r)}
    return {"y_hat": y, "prob": float(np.mean((ans.y_ami > model.threshold) == y))}


# ------------------------------------------------------------- comparison
def plan_ok(z, n, it: int, alpha: float, gamma: float) -> bool:
    """Is z reachable from z⁰ = ⌈αN⌉ in ``it`` steps of ⌈γΣN⌉ rows, each on
    one feature and clipped at N?  A product rounded to float32, as the
    configuration states, may fall on either side of an integer."""
    z, n = [int(x) for x in z], [int(x) for x in n]

    def ceils(a, b):
        return {math.ceil(a * b),
                math.ceil(float(np.float32(np.float32(a) * np.float32(b))))}

    for step in ceils(gamma, sum(n)):
        step = max(step, 1)
        steps = []
        for zj, nj in zip(z, n):
            fits = []
            for z0 in {min(max(c, min(2, nj)), nj) for c in ceils(alpha, nj)}:
                if not z0 <= zj <= nj:
                    continue
                if (zj - z0) % step == 0:
                    fits.append((zj - z0) // step)
                elif zj == nj:
                    fits.append(-(-(zj - z0) // step))
            if not fits:
                break
            steps.append(min(fits))
        else:
            exhausted = all(zj == nj for zj, nj in zip(z, n))
            if sum(steps) == it or (exhausted and sum(steps) <= it):
                return True
    return False


def request_gaps(got: dict, ref: Answer, delta: float, m_prob) -> dict:
    """The compared numbers of one request.

    ``got``: the program's (or control's) ``y_hat``, ``prob``.  ``m_prob``
    is the probability for a given ŷ: ``guarantee_prob`` over the
    reference's QMC outputs.  ŷ is judged against the interval the
    reference allows; the probability at the program's ŷ clipped into it.
    """
    y = float(got["y_hat"])
    gap = max(ref.y_lo - y, y - ref.y_hi, 0.0)
    p_ref = m_prob(min(max(y, ref.y_lo), ref.y_hi))
    return {"yhat_gap": gap / max(delta, 1e-12),
            "prob_gap": abs(float(got["prob"]) - p_ref), "prob_ref": p_ref}


def class_gaps(got: dict, ref: Answer, model: Model) -> dict:
    """The compared numbers of one request of a classification (δ = 0).

    ``yhat_gap`` is 0 where the program's class is one the reference allows
    at the point, else 1.  [p_lo, p_hi] is the share of the QMC rows whose
    class is the program's ŷ, a row whose score range allows both classes
    counting as either; ``prob_gap`` is the distance of the program's
    ``prob`` from it, and ``prob_ref`` is p_hi.
    """
    y = float(got["y_hat"])
    low, high = model.classes(ref.y_lo, ref.y_hi)
    rows_low, rows_high = model.classes(ref.ami_lo, ref.ami_hi)
    p_lo = float(np.mean((rows_low == y) & (rows_high == y)))
    p_hi = float(np.mean((rows_low <= y) & (y <= rows_high)))
    prob = float(got["prob"])
    return {"yhat_gap": 0.0 if y in (float(low), float(high)) else 1.0,
            "prob_gap": max(p_lo - prob, prob - p_hi, 0.0), "prob_ref": p_hi}


def gaps(got: dict, ref: Answer, model: Model, delta: float) -> dict:
    """The compared numbers of one request, as the configuration's task says."""
    if model.classifies:
        return class_gaps(got, ref, model)
    return request_gaps(got, ref, delta,
                        lambda y: guarantee_prob(y, ref.y_ami, delta))
