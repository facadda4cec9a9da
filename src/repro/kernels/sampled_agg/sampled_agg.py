"""Pallas TPU kernel: fused prefix-masked streaming moments (AFC hot loop).

The paper's AFC stage re-scans the sampled rows once per aggregate operator
(ClickHouse computes SUM, then AVG, then STD...).  On TPU we fuse all
parametric aggregates into ONE pass: each grid step loads a (block_k,
block_c) VMEM tile of the sample buffers, applies the prefix mask with an
iota compare (branch-free — the mask IS the sample size), and accumulates
five power sums per feature into a VMEM accumulator ([count, Σv, Σv², Σv³,
Σv⁴] — the 4th power is what the VAR/STD error estimators need).

Grid: (k_tiles, c_tiles) with c innermost so each feature row's accumulator
stays resident in VMEM across its column tiles.

TPU adaptation notes (DESIGN.md §3): the paper's row-at-a-time online
aggregation becomes a tiled masked reduction — incremental sampling is a
*wider mask*, not more I/O, so planner iterations never re-touch HBM rows
they already consumed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sampled_agg.compensated import kahan_step

__all__ = ["sampled_moments"]


def _kernel(z_ref, shift_ref, vals_ref, out_ref, comp_ref, *, block_c: int, n_c: int):
    ci = pl.program_id(1)
    # (block_k, block_c) tile of sample values
    v = vals_ref[...].astype(jnp.float32)
    z = z_ref[...]  # (block_k, 1) int32 live sample sizes
    shift = shift_ref[...]  # (block_k, 1) f32 per-feature accumulation origin
    col0 = ci * block_c
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    mask = (cols < z).astype(jnp.float32)
    v = (v - shift) * mask
    v2 = v * v
    tile = jnp.stack(
        [
            jnp.sum(mask, axis=1),
            jnp.sum(v, axis=1),
            jnp.sum(v2, axis=1),
            jnp.sum(v2 * v, axis=1),
            jnp.sum(v2 * v2, axis=1),
        ],
        axis=1,
    )  # (block_k, 5)

    @pl.when(ci == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    # Kahan-compensated cross-tile carry: a plain `out += tile` loses the
    # small tiles' contribution once the running Σv⁴ dominates them (60k-row
    # heavy-tailed columns); the VMEM (hi, lo) pair keeps the accumulated
    # rounding and folds it back once on the last column tile.
    hi, lo = kahan_step(out_ref[...], comp_ref[...], tile)
    out_ref[...] = hi
    comp_ref[...] = lo

    @pl.when(ci == n_c - 1)
    def _collapse():
        out_ref[...] += comp_ref[...]


@functools.partial(jax.jit, static_argnames=("block_k", "block_c", "interpret"))
def sampled_moments(
    vals: jnp.ndarray,            # (k, cap) f32
    z: jnp.ndarray,               # (k,) int32
    shift: jnp.ndarray | None = None,  # (k,) f32 accumulation origin
    *,
    block_k: int = 8,
    block_c: int = 1024,
    interpret: bool,
) -> jnp.ndarray:
    """(k, 5) power sums [count, s1, s2, s3, s4] of ``vals - shift`` over
    each valid prefix (see ref.py for the shift rationale; None = no shift).

    Shapes need not divide the block sizes: inputs are zero-padded up to the
    tile grid (padded rows carry z=0, so they contribute nothing) and the
    output is sliced back to k rows.  ``z`` and ``shift`` enter as ``(k, 1)``
    columns: Mosaic refuses 1-D blocks that are neither the whole array nor
    a multiple of 128.
    """
    k, cap = vals.shape
    if shift is None:
        shift = jnp.zeros((k,), jnp.float32)
    block_k = min(block_k, k)
    block_c = min(block_c, cap)
    kp = -(-k // block_k) * block_k
    capp = -(-cap // block_c) * block_c
    if (kp, capp) != (k, cap):
        vals = jnp.pad(vals, ((0, kp - k), (0, capp - cap)))
        z = jnp.pad(z, (0, kp - k))
        shift = jnp.pad(shift, (0, kp - k))
    grid = (kp // block_k, capp // block_c)
    out = pl.pallas_call(
        functools.partial(_kernel, block_c=block_c, n_c=capp // block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, block_c), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_k, 5), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, 5), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_k, 5), jnp.float32)],
        interpret=interpret,
    )(z[:, None], shift.astype(jnp.float32)[:, None], vals)
    return out[:k]
