"""Pallas TPU kernel: masked-quantile + bootstrap-resample selection.

The holistic AFC stage (MEDIAN / QUANTILE, paper appendix D) needs, per
feature, the order statistics of the live z-prefix at a handful of ranks:
the point-estimate rank plus ``B`` bootstrap-replicate ranks (the empirical
inverse-CDF table the AMI sampler draws from).  A general sort is awkward on
the VPU; selecting *given* ranks is not.  This kernel selects by **stable
rank counting**:

* grid ``(k_tiles, ci_tiles, cj_tiles)`` with ``cj`` innermost: tile ``ci``
  holds the candidate elements, tile ``cj`` streams the comparison elements;
* out-of-prefix columns compare as +inf (iota-vs-z mask, branch-free), ties
  break on column index, so every element has a unique rank and prefix
  elements occupy ranks ``0..z-1`` exactly;
* a VMEM scratch accumulates each candidate's rank across ``cj`` tiles; on
  the last ``cj`` tile the candidates matching the requested target ranks
  are selected into the ``(block_k, R)`` output accumulator.

Cost is O(cap²/VPU-width) masked compares per feature — quadratic, but one
fused VMEM-resident pass with no data-dependent shapes, which is what the
``lax.while_loop`` executor needs.  Beyond ~4k-row caps the XLA-sort oracle
(`ref.masked_select_ranks_ref`) wins; ``ops.masked_quantile_estimates``
routes between them per ``afc_backend`` exactly like ``sampled_moments``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["masked_select_ranks"]


def _kernel(
    z_ref, vals_i_ref, vals_j_ref, targets_ref, out_ref, rank_ref,
    *, block_ci: int, block_cj: int, n_cj: int
):
    ci = pl.program_id(1)
    cj = pl.program_id(2)
    z = z_ref[...]                                   # (block_k, 1)
    vi = vals_i_ref[...].astype(jnp.float32)         # (block_k, block_ci)
    vj = vals_j_ref[...].astype(jnp.float32)         # (block_k, block_cj)
    coli = ci * block_ci + jax.lax.broadcasted_iota(jnp.int32, vi.shape, 1)
    colj = cj * block_cj + jax.lax.broadcasted_iota(jnp.int32, vj.shape, 1)
    vi = jnp.where(coli < z, vi, jnp.inf)
    vj = jnp.where(colj < z, vj, jnp.inf)

    @pl.when(cj == 0)
    def _init_ranks():
        rank_ref[...] = jnp.zeros_like(rank_ref)

    # stable rank of candidate i = #{j : v_j < v_i  or  (v_j == v_i, j < i)};
    # +inf padding ties resolve on index too, so ranks are a permutation.
    less = vj[:, None, :] < vi[:, :, None]
    tie = (vj[:, None, :] == vi[:, :, None]) & (
        colj[:, None, :] < coli[:, :, None]
    )
    rank_ref[...] += jnp.sum(less | tie, axis=2).astype(jnp.int32)

    @pl.when(cj == n_cj - 1)
    def _select():
        @pl.when(ci == 0)
        def _init_out():
            out_ref[...] = jnp.zeros_like(out_ref)

        t = targets_ref[...]                          # (block_k, R)
        hit = rank_ref[...][:, :, None] == t[:, None, :]
        # where() keeps +inf out of unselected lanes (inf * 0 would be NaN)
        out_ref[...] += jnp.sum(
            jnp.where(hit, vi[:, :, None], 0.0), axis=1
        )


@functools.partial(
    jax.jit, static_argnames=("block_k", "block_ci", "block_cj", "interpret")
)
def masked_select_ranks(
    vals: jnp.ndarray,        # (k, cap) f32
    z: jnp.ndarray,           # (k,) int32 live prefix lengths
    targets: jnp.ndarray,     # (k, R) int32 ranks into the sorted prefix
    *,
    block_k: int = 8,
    block_ci: int = 128,
    block_cj: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """(k, R) order statistics of each z-prefix at ``targets`` ranks.

    Semantics match :func:`ref.masked_select_ranks_ref`: out-of-prefix
    positions sort as +inf, so target ranks must lie in [0, z-1] for finite
    results (callers clip; ``z == 0`` rows return +inf and are overridden by
    the empty-prefix convention upstream).  Shapes need not divide the block
    sizes — inputs are padded (padded rows carry z = 0, padded targets point
    past the buffer and select nothing, contributing 0 to unsliced rows).
    On the TPU a row block is a multiple of 8 or the whole array, and ``z``
    enters as a ``(k, 1)`` column for the same tiling rule.
    """
    k, cap = vals.shape
    r = targets.shape[1]
    block_k = min(block_k, k)
    block_ci = min(block_ci, cap)
    block_cj = min(block_cj, cap)
    kp = -(-k // block_k) * block_k
    # pad columns to a common multiple so BOTH tile grids cover every column
    # (padding max(block_ci, block_cj) alone would drop trailing candidates
    # whenever the smaller block does not divide it)
    tile = math.lcm(block_ci, block_cj)
    capp = -(-cap // tile) * tile
    rp = -(-r // 128) * 128 if not interpret else r
    if (kp, capp) != (k, cap):
        vals = jnp.pad(vals, ((0, kp - k), (0, capp - cap)))
        z = jnp.pad(z, (0, kp - k))
        targets = jnp.pad(targets, ((0, kp - k), (0, 0)))
    if rp != r:
        # pad with an impossible rank: selects nothing, contributes 0.0
        targets = jnp.pad(
            targets, ((0, 0), (0, rp - r)), constant_values=capp + 1
        )
    n_cj = capp // block_cj
    grid = (kp // block_k, capp // block_ci, n_cj)
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_ci=block_ci, block_cj=block_cj, n_cj=n_cj
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, 1), lambda i, ci, cj: (i, 0)),
            pl.BlockSpec((block_k, block_ci), lambda i, ci, cj: (i, ci)),
            pl.BlockSpec((block_k, block_cj), lambda i, ci, cj: (i, cj)),
            pl.BlockSpec((block_k, rp), lambda i, ci, cj: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_k, rp), lambda i, ci, cj: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, rp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_k, block_ci), jnp.int32)],
        interpret=interpret,
    )(z[:, None], vals, vals, targets)
    return out[:k, :r]
