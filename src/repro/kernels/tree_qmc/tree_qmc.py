"""Pallas TPU kernel: tree-ensemble inference over the QMC sample batch.

AMI (§3.3) evaluates the pipeline model on m~1000 QMC rows, and the Sobol-
Saltelli estimator on m(k+2) more — for the paper's tree pipelines (LGBM /
XGB / RF) this batched ensemble inference IS the serving hot spot once AFC
is approximated away.

TPU adaptation (DESIGN.md §3): trees are tensorized Hummingbird-style into
complete node arrays, and traversal is a branch-free level-wise gather chain

    idx <- (x[row, feat[tree, idx]] <= thr[tree, idx]) ? L[idx] : R[idx]

Grid: (row tiles, tree tiles).  A (block_t, max_nodes) slab of node tables
and a (block_m, F) row tile live in VMEM; `depth` gather rounds happen
entirely on-chip; per-tree leaf values are summed and accumulated into the
output row tile across tree tiles (innermost grid dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ensemble_sum"]


def _kernel(feat_ref, thr_ref, left_ref, right_ref, val_ref, x_ref, out_ref, *, depth):
    ti = pl.program_id(1)
    feat = feat_ref[...]          # (bt, M) int32
    thr = thr_ref[...]            # (bt, M) f32
    left = left_ref[...]
    right = right_ref[...]
    val = val_ref[...]
    x = x_ref[...]                # (bm, F) f32
    bt, _ = feat.shape
    bm = x.shape[0]

    idx = jnp.zeros((bt, bm), jnp.int32)
    for _ in range(depth):
        f = jnp.take_along_axis(feat, idx, axis=1)            # (bt, bm)
        t = jnp.take_along_axis(thr, idx, axis=1)
        xv = jnp.take_along_axis(x, f.T, axis=1).T            # x[row, f]
        go_left = xv <= t
        nl = jnp.take_along_axis(left, idx, axis=1)
        nr = jnp.take_along_axis(right, idx, axis=1)
        idx = jnp.where(go_left, nl, nr)
    leaves = jnp.take_along_axis(val, idx, axis=1)            # (bt, bm)
    tile_sum = jnp.sum(leaves, axis=0)                        # (bm,)

    @pl.when(ti == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += tile_sum


@functools.partial(
    jax.jit, static_argnames=("depth", "block_m", "block_t", "interpret")
)
def ensemble_sum(
    feature: jnp.ndarray,         # (T, M) int32
    threshold: jnp.ndarray,       # (T, M) f32
    left: jnp.ndarray,            # (T, M) int32
    right: jnp.ndarray,           # (T, M) int32
    value: jnp.ndarray,           # (T, M) f32
    x: jnp.ndarray,               # (m, F) f32
    *,
    depth: int,
    block_m: int = 256,
    block_t: int = 8,
    interpret: bool,
) -> jnp.ndarray:
    """(m,) sum of per-tree leaf values (caller adds base / divides)."""
    t, m_nodes = feature.shape
    m, f = x.shape
    block_m = min(block_m, m)
    block_t = min(block_t, t)
    assert m % block_m == 0 and t % block_t == 0
    grid = (m // block_m, t // block_t)
    tree_spec = pl.BlockSpec((block_t, m_nodes), lambda i, j: (j, 0))
    return pl.pallas_call(
        functools.partial(_kernel, depth=depth),
        grid=grid,
        in_specs=[
            tree_spec,
            tree_spec,
            tree_spec,
            tree_spec,
            tree_spec,
            pl.BlockSpec((block_m, f), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((m,), jnp.float32),
        interpret=interpret,
    )(feature, threshold, left, right, value, x)
