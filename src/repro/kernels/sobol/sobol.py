"""Pallas TPU kernel: Sobol low-discrepancy point generation (AMI, §3.3).

The QMC uniforms are the first thing every AMI / Sobol-index call needs:
an (m, d) grid of uint32 Sobol points.  The direct gray-code construction is
32 masked XOR steps over a (block_m, d) tile — pure VPU integer work with no
cross-tile dependence, so the grid parallelizes over m tiles and the
direction-number table (d, 32) stays VMEM-resident.

This is the TPU adaptation of "draw m low-discrepancy samples": no host
round-trip, generated where the model inference (tree_qmc / MLP matmul)
consumes it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["sobol_points"]


def _kernel(sv_ref, out_ref, *, block_m: int, skip: int):
    mi = pl.program_id(0)
    base = skip + mi * block_m
    idx = base + jax.lax.broadcasted_iota(jnp.uint32, (block_m, 1), 0)
    gray = idx ^ (idx >> 1)                      # (block_m, 1)
    sv = sv_ref[...]                             # (d, 32) uint32
    acc = jnp.zeros((block_m, sv.shape[0]), jnp.uint32)
    for b in range(32):
        bit = ((gray >> b) & 1).astype(bool)     # (block_m, 1)
        acc = jnp.where(bit, acc ^ sv[None, :, b], acc)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("m", "dim", "skip", "block_m", "interpret"))
def sobol_points(
    m: int,
    dim: int,
    skip: int = 0,
    *,
    block_m: int = 256,
    interpret: bool,
) -> jnp.ndarray:
    """(m, dim) uint32 Sobol points, bit-exact with the jnp/scipy oracle."""
    from repro.core.sobol_tables import DIRECTION_NUMBERS

    sv = jnp.asarray(DIRECTION_NUMBERS[:dim], jnp.uint32)
    block_m = min(block_m, m)
    assert m % block_m == 0
    return pl.pallas_call(
        functools.partial(_kernel, block_m=block_m, skip=skip),
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((dim, 32), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_m, dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, dim), jnp.uint32),
        interpret=interpret,
    )(sv)
