"""Pallas TPU kernel: blockwise online-softmax (flash) attention.

The substrate hot spot for the LM zoo's train/prefill cells.  Grid is
(batch*heads, q_tiles, kv_tiles) with kv innermost; the running max /
denominator / output accumulator live in f32 VMEM scratch across kv tiles
(never touching HBM), and causal masking skips fully-masked kv tiles via
``pl.when`` — the work saved is exactly the upper triangle, which the pure
XLA fallback (models.lm.layers.attention_blockwise) cannot skip.

Tile defaults (128 x 128 on the MXU's native 128-lane layout) keep the live
set at q(128, d) + k/v(128, d) + scores(128, 128) f32 ~ 0.4 MB for d=128 —
far under the ~16 MB VMEM budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
    block_q: int, block_k: int, scale: float, causal: bool, nk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # causal: tiles entirely above the diagonal contribute nothing
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        v = v_ref[0].astype(jnp.float32)                    # (bk, dv)
        s = q @ k.T                                         # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + p @ v
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,   # (B, H, Sq, D)
    k: jnp.ndarray,   # (B, H, Sk, D)
    v: jnp.ndarray,   # (B, H, Sk, Dv)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """(B, H, Sq, Dv) attention output; KV heads must be pre-expanded."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    dv = v.shape[-1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k
    scale = d ** -0.5

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, dv)

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            block_q=block_q,
            block_k=block_k,
            scale=scale,
            causal=causal,
            nk=nk,
        ),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, dv)
