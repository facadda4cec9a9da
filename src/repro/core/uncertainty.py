"""Feature-uncertainty representation (paper §3.2, ``U_x``).

Biathlon represents the error distribution of every approximated aggregate
feature explicitly (not just a scalar stddev) so that the AMI stage can draw
feature samples from it.  Two families are supported, exactly as in the paper:

* **parametric** — Normal(0, sigma) errors for SUM / COUNT / AVG / VAR / STD
  (CLT, following Mozafari & Niu [53]); sampling uses the inverse normal CDF;
* **empirical** — bootstrap replicate tables for holistic aggregates
  (MEDIAN / QUANTILE, paper appendix D); sampling uses the replicate
  empirical inverse CDF.

Both are packed into one fixed-shape struct so a *batch of heterogeneous
features* is a single PyTree of arrays — jittable, vmappable, and usable
inside ``lax.while_loop`` (the fused executor) and in the multi-pod dry-run.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qmc import uniform_to_normal

__all__ = [
    "FeatureUncertainty",
    "sample_features",
    "sample_features_fused",
    "exact_uncertainty",
]


class FeatureUncertainty(NamedTuple):
    """Uncertainty of ``k`` features, fixed shapes (k,), (k, B).

    value:        point estimate x̂ per feature.
    sigma:        Normal error stddev (0 when exact or empirical).
    replicates:   sorted bootstrap replicates per feature (value-padded when
                  parametric, so gathering from them is always safe).
    is_empirical: which features use the replicate table.
    """

    value: jnp.ndarray        # (k,) float32
    sigma: jnp.ndarray        # (k,) float32
    replicates: jnp.ndarray   # (k, B) float32, sorted along B
    is_empirical: jnp.ndarray  # (k,) bool

    @property
    def k(self) -> int:
        return self.value.shape[-1]

    @property
    def n_replicates(self) -> int:
        return self.replicates.shape[-1]

    def effective_std(self) -> jnp.ndarray:
        """Stddev of the error distribution regardless of representation."""
        emp_std = jnp.std(self.replicates, axis=-1)
        return jnp.where(self.is_empirical, emp_std, self.sigma)


def exact_uncertainty(values: jnp.ndarray, n_replicates: int = 1) -> FeatureUncertainty:
    """Zero-uncertainty wrapper for exactly-computed features."""
    values = jnp.asarray(values, jnp.float32)
    k = values.shape[-1]
    return FeatureUncertainty(
        value=values,
        sigma=jnp.zeros((k,), jnp.float32),
        replicates=jnp.broadcast_to(values[:, None], (k, n_replicates)).astype(
            jnp.float32
        ),
        is_empirical=jnp.zeros((k,), bool),
    )


def sample_features(unc: FeatureUncertainty, u: jnp.ndarray) -> jnp.ndarray:
    """Draw feature vectors from ``x̂ + U_x`` via inverse-CDF on uniforms.

    u: ``(m, k)`` low-discrepancy uniforms in (0, 1).
    returns ``(m, k)`` feature samples; exact features (sigma==0, parametric)
    come out constant, so a fully-exact plan degenerates to m identical rows —
    which is precisely what makes the guarantee check trivially pass then.
    """
    m, k = u.shape
    # Parametric path: x̂ + sigma * Phi^{-1}(u).
    parametric = unc.value[None, :] + unc.sigma[None, :] * uniform_to_normal(u)
    # Empirical path: inverse CDF of the sorted replicate table.
    b = unc.n_replicates
    idx = jnp.clip((u * b).astype(jnp.int32), 0, b - 1)  # (m, k)
    empirical = jax.vmap(
        lambda col, i: col[i], in_axes=(0, 1), out_axes=1
    )(unc.replicates, idx)  # gather per-feature replicate columns -> (m, k)
    return jnp.where(unc.is_empirical[None, :], empirical, parametric)


def sample_features_fused(
    value: jnp.ndarray,        # (k,) point estimates
    sigma: jnp.ndarray,        # (k,) Normal error stddevs (0 for holistic)
    replicates: jnp.ndarray,   # (h, B) sorted replicate table, holistic rows
    hol_idx: jnp.ndarray | None,  # (h,) static holistic feature indices
    u: jnp.ndarray,            # (m, k) low-discrepancy uniforms
) -> jnp.ndarray:
    """:func:`sample_features`, fused-loop-state edition.

    The fused executor carries (value, sigma) for all k features plus a
    compact (h, B) replicate table for just the holistic ones (``hol_idx``
    names them, statically), instead of a full ``FeatureUncertainty``
    pytree with value-padded (k, B) replicates.  Sampling semantics are
    identical: parametric features draw ``x̂ + σ·Φ⁻¹(u)``, holistic
    features the empirical inverse CDF of their replicate row at the SAME
    uniform column.  Shared by the megabatch sampler in
    ``core/executor_fused.py`` (AMI rows and Saltelli A/B blocks alike).
    """
    rows = value[None, :] + sigma[None, :] * uniform_to_normal(u)
    if hol_idx is not None and replicates.shape[0]:
        b = replicates.shape[1]
        idx = jnp.clip((u[:, hol_idx] * b).astype(jnp.int32), 0, b - 1)
        emp = jax.vmap(
            lambda col, i: col[i], in_axes=(0, 1), out_axes=1
        )(replicates, idx)                            # (m, h)
        rows = set_columns(rows, hol_idx, emp)
    return rows


def set_columns(x: jnp.ndarray, idx, cols: jnp.ndarray) -> jnp.ndarray:
    """``x.at[..., idx].set(cols)`` for a concrete index list ``idx``.

    Built from static column slices and one stack, so no scatter reaches
    XLA: the TPU compiler aborts (a sublane-gather CHECK in its sorted
    scatter emitter) when it fuses the replicate gather above into the
    scatter that ``.at[].set`` lowers to.
    """
    pos = {int(j): p for p, j in enumerate(np.asarray(idx))}
    return jnp.stack(
        [
            cols[..., pos[j]] if j in pos else x[..., j]
            for j in range(x.shape[-1])
        ],
        axis=-1,
    )
