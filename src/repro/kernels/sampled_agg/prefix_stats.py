"""Per-request prefix statistics: the incremental-AFC precompute (DESIGN.md
§ Incremental AFC).

The fused executor's while_loop used to pay O(n) per planner iteration:
``masked_estimates`` re-scanned the full (k, cap) values matrix and the
holistic path re-rank-counted the whole padded column, even when the live
prefix z was a few percent of the group.  This module hoists ALL
data-proportional work into a **once-per-request precompute** so the loop
body touches O(1)-ish state per feature:

* :func:`prefix_power_sums` — a tiled Pallas kernel (jnp oracle:
  :func:`prefix_power_sums_ref`) producing the inclusive running power sums
  ``P_p[j, c] = Σ_{i ≤ c} (v_{j,i} − shift_j)^p`` for p = 1..4.  The AFC
  (value, sigma) at ANY plan z is then one gather of the (k, 4) table row at
  ``z − 1`` fed through the unchanged ``estimates_from_power_sums`` tail —
  the per-iteration cost no longer depends on the group size.  Accumulation
  is compensated (``compensated.py``): the cross-tile carry is a Kahan
  (hi, lo) pair, the oracle an error-free-transform ``associative_scan`` —
  f32 storage with double-precision-class accumulation, since a naive f32
  running Σv⁴ visibly drifts by 60k-row heavy-tailed groups.
  Memory: (k, cap, 4) f32 = 4× the values buffer, freed with it per request
  (the values buffer itself is donated — serving/batched.py).

* :func:`build_rank_index` / :func:`select_ranks_indexed` — the holistic
  (MEDIAN/QUANTILE) equivalent.  The column is argsorted ONCE with its
  original positions attached (stable, so ties break on position exactly
  like the ``quantile_select`` rank-counting kernel).  Because the planner
  only ever visits ``z ∈ {min(z⁰ + i·γ, n)}`` (z⁰, γ and max_iters are loop
  constants), prefix membership counts are precomputed per candidate z at
  block granularity; an order statistic of the live prefix is then a
  **prefix-membership rank query**: an unrolled binary search over the
  block counts (O(log(cap/S)) gathers) plus one S-element block scan —
  O(h·B·log n)-class work per bootstrap-replicate update instead of the
  O(h·B·n) full-column rank count.  Index memory: 2·(h, cap) value/index
  rows + an (h, max_iters+1, cap/S + 1) int32 count table.

The argsort itself stays an XLA sort (not Pallas): TPU's native sort is
already one fused HBM pass, and it runs once per request outside the loop.
Backend routing (kernel vs oracle for the power-sum tables) goes through
``ops.prefix_power_sums`` exactly like ``sampled_moments``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sampled_agg.compensated import comp_cumsum, kahan_step, two_sum

__all__ = [
    "N_POWERS",
    "prefix_power_sums",
    "prefix_power_sums_ref",
    "prefix_moments_at",
    "append_power_sums",
    "HolisticRankIndex",
    "build_rank_index",
    "merge_sorted_prefix",
    "rank_counts_from_sorted",
    "rank_index_from_sorted",
    "select_ranks_indexed",
]

N_POWERS = 4  # [Σu, Σu², Σu³, Σu⁴] — count at z is just z


# --------------------------------------------------------------------------
# Parametric: running power-sum tables
# --------------------------------------------------------------------------
def _powers(v: jnp.ndarray) -> jnp.ndarray:
    """(…, c) f32 -> (…, c, 4) stacked u, u², u³, u⁴."""
    v2 = v * v
    return jnp.stack([v, v2, v2 * v, v2 * v2], axis=-1)


def prefix_power_sums_ref(
    vals: jnp.ndarray, shift: jnp.ndarray | None = None
) -> jnp.ndarray:
    """(k, cap) f32 -> (k, cap, 4) inclusive prefix sums of (v−shift)^p.

    Compensated scan (O(ε·log n) error); the prefix row at index ``z − 1``
    is exactly the ``[s1..s4]`` tail of ``sampled_moments``'s output at plan
    z (count = z), so the two paths share ``estimates_from_power_sums``.
    """
    v = vals.astype(jnp.float32)
    if shift is not None:
        v = v - shift.astype(jnp.float32)[:, None]
    return comp_cumsum(_powers(v), axis=1)


def _prefix_kernel(shift_ref, vals_ref, out_ref, hi_ref, lo_ref, *, block_c: int):
    ci = pl.program_id(1)
    v = vals_ref[...].astype(jnp.float32) - shift_ref[...]
    p = _powers(v)                               # (block_k, block_c, 4)

    # within-tile inclusive scan: log-step doubling (Mosaic-safe static
    # slices + concatenate; error O(ε·log block_c))
    s = 1
    while s < block_c:
        p = p + jnp.concatenate(
            [jnp.zeros_like(p[:, :s]), p[:, :-s]], axis=1
        )
        s *= 2

    @pl.when(ci == 0)
    def _init():
        hi_ref[...] = jnp.zeros_like(hi_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)

    carry_hi = hi_ref[...]                        # (block_k, 4)
    carry_lo = lo_ref[...]
    # add the smaller correction first so it is not absorbed by the carry
    out_ref[...] = carry_hi[:, None, :] + (p + carry_lo[:, None, :])
    last = jax.lax.slice_in_dim(p, block_c - 1, block_c, axis=1)[:, 0, :]
    hi, lo = kahan_step(carry_hi, carry_lo, last)
    hi_ref[...] = hi
    lo_ref[...] = lo


@functools.partial(jax.jit, static_argnames=("block_k", "block_c", "interpret"))
def prefix_power_sums(
    vals: jnp.ndarray,                 # (k, cap) f32
    shift: jnp.ndarray | None = None,  # (k,) f32 accumulation origin
    *,
    block_k: int = 8,
    block_c: int = 256,
    interpret: bool,
) -> jnp.ndarray:
    """Pallas twin of :func:`prefix_power_sums_ref`: (k, cap, 4) tables.

    Grid (k_tiles, c_tiles) with c innermost; each feature row's running
    totals live in a VMEM (hi, lo) Kahan pair across its column tiles, so
    tile boundaries add no uncompensated rounding.  Shapes need not divide
    the blocks — inputs are zero-padded and the output sliced back to
    (k, cap).  The sliced-off padded region is NOT a valid prefix
    continuation (zero-padded columns accumulate ``(0 - shift)^p``, not 0);
    only the returned [:k, :cap] entries are meaningful.

    ``block_c`` is 256 because the TPU pads the output block's minor dim of
    4 to 128 lanes in VMEM: at 1,024 the kernel needs ~21 MB of the 16 MiB
    scoped VMEM.  ``shift`` enters as a ``(k, 1)`` column so its block obeys
    the (8, 128) tiling rule.
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
        shift = jnp.pad(shift, (0, kp - k))
    grid = (kp // block_k, capp // block_c)
    out = pl.pallas_call(
        functools.partial(_prefix_kernel, block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, block_c), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec(
            (block_k, block_c, N_POWERS), lambda i, j: (i, j, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((kp, capp, N_POWERS), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_k, N_POWERS), jnp.float32),
            pltpu.VMEM((block_k, N_POWERS), jnp.float32),
        ],
        interpret=interpret,
    )(shift.astype(jnp.float32)[:, None], vals)
    return out[:k, :cap]


def prefix_moments_at(ptab: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Gather the (k, 5) ``[count, s1..s4]`` moments row at plan z.

    ``ptab``: (k, cap, 4) prefix tables; ``z``: (k,) int32 in [0, cap].
    This is the whole per-iteration parametric AFC read — one gather,
    independent of cap.  ``z == 0`` rows are all-zero (empty prefix).
    """
    cap = ptab.shape[1]
    idx = jnp.clip(z - 1, 0, cap - 1).astype(jnp.int32)
    row = jnp.take_along_axis(ptab, idx[:, None, None], axis=1)[:, 0]
    row = jnp.where(z[:, None] > 0, row, 0.0)
    return jnp.concatenate([z.astype(jnp.float32)[:, None], row], axis=1)


# --------------------------------------------------------------------------
# Holistic: presorted column + per-candidate-z prefix-membership counts
# --------------------------------------------------------------------------
class HolisticRankIndex(NamedTuple):
    """Argsort-with-original-index structure for holistic columns.

    sorted_vals: (h, capp) f32 ascending; positions ≥ n (and pad) are +inf.
    sorted_idx:  (h, capp) i32 original buffer position of each element
                 (stable ties — matches the rank-counting kernel's
                 tie-break); pad entries point past the buffer.
    blk_cnt:     (h, n_z, n_blk+1) i32 — blk_cnt[f, i, b] counts sorted
                 positions p < b·S whose original index < zcand[f, i]
                 (exclusive block-start prefix-membership counts; entry
                 n_blk is the total, = zcand clipped to n).
    zcand:       (h, n_z) i32 — the feature's reachable plan ladder
                 ``min(z⁰ + i·γ, n)``; every runtime z is one of these.
    """

    sorted_vals: jnp.ndarray
    sorted_idx: jnp.ndarray
    blk_cnt: jnp.ndarray
    zcand: jnp.ndarray


BLOCK_S = 128  # block-scan granularity S of the membership counts


def build_rank_index(
    vals: jnp.ndarray,      # (h, cap) holistic-feature prefix buffers
    n: jnp.ndarray,         # (h,) int32 group sizes
    zcand: jnp.ndarray,     # (h, n_z) int32 reachable plans, nondecreasing
    *,
    block: int = BLOCK_S,
) -> HolisticRankIndex:
    """One-time (per request) index build — the only O(n·n_z) holistic work.

    Runs outside the while_loop; the loop then answers every order-statistic
    query through :func:`select_ranks_indexed` without touching the raw
    column again.
    """
    h, cap = vals.shape
    block = min(block, cap)
    capp = -(-cap // block) * block
    pos = jnp.arange(cap, dtype=jnp.int32)
    padded = jnp.where(pos[None, :] < n[:, None], vals.astype(jnp.float32), jnp.inf)
    if capp != cap:
        padded = jnp.pad(padded, ((0, 0), (0, capp - cap)), constant_values=jnp.inf)
    order = jnp.argsort(padded, axis=1, stable=True).astype(jnp.int32)
    svals = jnp.take_along_axis(padded, order, axis=1)
    return rank_index_from_sorted(svals, order, zcand, block=block)


def rank_counts_from_sorted(
    sidx: jnp.ndarray,      # (h, capp) original positions, sorted-value order
    zcand: jnp.ndarray,     # (h, n_z) candidate plans
    *,
    block: int = BLOCK_S,
) -> jnp.ndarray:
    """Exclusive block-start prefix-membership counts from a sorted order.

    The count half of :func:`build_rank_index`, factored out so a column
    whose sorted order is *incrementally maintained* (merge-on-query append
    path, DESIGN.md § Online feature store) can refresh its ``blk_cnt``
    table — the only part that depends on the candidate ladder — without
    re-running the argsort.
    """
    h, capp = sidx.shape
    member = sidx[:, None, :] < zcand[:, :, None]           # (h, n_z, capp)
    per_blk = member.reshape(h, zcand.shape[1], capp // block, block).sum(
        axis=-1, dtype=jnp.int32
    )
    return jnp.concatenate(
        [
            jnp.zeros((h, zcand.shape[1], 1), jnp.int32),
            jnp.cumsum(per_blk, axis=-1, dtype=jnp.int32),
        ],
        axis=-1,
    )


def rank_index_from_sorted(
    svals: jnp.ndarray,     # (h, capp) ascending, +inf past the live prefix
    sidx: jnp.ndarray,      # (h, capp) original positions (stable tie order)
    zcand: jnp.ndarray,     # (h, n_z)
    *,
    block: int = BLOCK_S,
) -> HolisticRankIndex:
    """Assemble a :class:`HolisticRankIndex` from presorted value/index rows.

    ``build_rank_index == rank_index_from_sorted ∘ stable-argsort``; callers
    that maintain the sorted order themselves (:func:`merge_sorted_prefix`)
    use this to skip the sort.
    """
    return HolisticRankIndex(
        sorted_vals=svals,
        sorted_idx=sidx.astype(jnp.int32),
        blk_cnt=rank_counts_from_sorted(sidx, zcand, block=block),
        zcand=zcand,
    )


def select_ranks_indexed(
    index: HolisticRankIndex,
    z: jnp.ndarray,         # (h,) int32 live prefix lengths (∈ zcand rows)
    targets: jnp.ndarray,   # (h, R) int32 ranks into the sorted z-prefix
) -> jnp.ndarray:
    """(h, R) order statistics of each z-prefix — the incremental twin of
    ``masked_select_ranks_ref``.

    Per query: an unrolled binary search over the candidate-z block counts
    finds the S-block holding prefix-rank r, then one S-element scan of
    (sorted_idx, sorted_vals) selects the element whose running membership
    count hits r + 1.  Out-of-prefix ranks (r ≥ z, incl. z == 0) return
    +inf, matching the oracle's convention (callers clip/override).
    """
    svals, sidx, blk_cnt, zcand = index
    h, capp = svals.shape
    n_blk = blk_cnt.shape[-1] - 1
    block = capp // n_blk
    r = targets.astype(jnp.int32)

    # candidate row of this z (z is always a ladder member; ties → first)
    iz = jnp.sum(zcand < z[:, None], axis=1).astype(jnp.int32)
    cnt = jnp.take_along_axis(blk_cnt, iz[:, None, None], axis=1)[:, 0]

    # largest b with cnt[b] <= r — unrolled bisect_right, log2(n_blk+1)
    # static steps of one gather each (no data-dependent while)
    lo = jnp.zeros(r.shape, jnp.int32)
    hi = jnp.full(r.shape, n_blk, jnp.int32)
    steps = max(1, (n_blk + 1).bit_length())
    for _ in range(steps):
        mid = (lo + hi + 1) // 2
        cm = jnp.take_along_axis(cnt, mid, axis=1)
        go = cm <= r
        lo = jnp.where(go, mid, lo)
        hi = jnp.where(go, hi, mid - 1)
    b = jnp.minimum(lo, n_blk - 1)                          # (h, R)

    base = jnp.take_along_axis(cnt, b, axis=1)              # count before block
    posn = b[:, :, None] * block + jnp.arange(block, dtype=jnp.int32)
    gi = jax.vmap(lambda row, p: row[p])(sidx, posn)        # (h, R, S)
    gv = jax.vmap(lambda row, p: row[p])(svals, posn)
    member = gi < z[:, None, None]
    running = base[:, :, None] + jnp.cumsum(member, axis=-1)
    hit = member & (running == (r + 1)[:, :, None])
    val = jnp.sum(jnp.where(hit, gv, 0.0), axis=-1)
    return jnp.where(jnp.any(hit, axis=-1), val, jnp.inf)


# --------------------------------------------------------------------------
# Streaming-append delta updates (DESIGN.md § Online feature store)
# --------------------------------------------------------------------------
def append_power_sums(
    ptab: jnp.ndarray,       # (k, cap, 4) prefix power-sum tables
    shift: jnp.ndarray,      # (k,) the tables' accumulation origin
    j: jnp.ndarray,          # () int32 insertion position, 0 < j
    x: jnp.ndarray,          # (k,) inserted value per feature row
    aff: jnp.ndarray | None = None,  # (k,) bool — rows the event touches
) -> jnp.ndarray:
    """Delta-update prefix tables for one insertion at position ``j``.

    Inserting ``x`` at prefix position j maps the old row onto the new one
    exactly: ``P'[c] = P[c]`` for c < j and ``P'[c] = P[c−1] + (x−shift)^p``
    for c ≥ j — a shift-right plus one broadcast addition, performed as a
    Knuth :func:`two_sum` error-free transform so each delta adds at most
    one f32 rounding (vs the O(ε·log n) compensated rebuild).  On data where
    f32 arithmetic is exact (integer-valued columns within 2²⁴) the result
    is **bitwise identical** to a from-scratch :func:`prefix_power_sums_ref`
    rebuild — the append→rebuild parity tests pin exactly that; on general
    floats the two differ only in final-rounding placement (O(ε)).

    Callers must hold two preconditions the math assumes: ``j ≥ 1`` (j = 0
    replaces the shift basis ``vals[:, 0]`` — rebuild instead) and ``j``
    within the buffer (``j ≥ cap`` is a no-op: the masked update never
    fires).  ``aff`` masks the update to the feature rows whose
    (table, group) the event belongs to.
    """
    k, cap, _ = ptab.shape
    pw = _powers(x.astype(jnp.float32) - shift.astype(jnp.float32))  # (k, 4)
    shifted = jnp.concatenate(
        [jnp.zeros((k, 1, N_POWERS), jnp.float32), ptab[:, :-1]], axis=1
    )
    s, e = two_sum(shifted, pw[:, None, :])
    upd = s + e
    c = jnp.arange(cap, dtype=jnp.int32)
    mask = (c[None, :] >= j) & (j < cap)
    if aff is not None:
        mask = mask & aff[:, None]
    return jnp.where(mask[:, :, None], upd, ptab)


def merge_sorted_prefix(
    svals: jnp.ndarray,      # (h, capp) sorted values, +inf past the prefix
    sidx: jnp.ndarray,       # (h, capp) original positions
    n: jnp.ndarray,          # (h,) int32 live prefix lengths (<= cap)
    cap: int,                # buffer width the positions index into
    j: jnp.ndarray,          # () int32 insertion position
    x: jnp.ndarray,          # (h,) inserted value per feature row
    aff: jnp.ndarray | None = None,  # (h,) bool — rows the event touches
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merge one appended element into maintained sorted-prefix runs.

    The merge-on-query half of the holistic append path: the compacted base
    run is the cached index's own (sorted_vals, sorted_idx) pair, the
    pending run is the store's append log, and this routine merges one
    pending event in O(capp) data movement — no argsort.  Ordering is
    (value, original position) lexicographic, exactly the stable-argsort
    order :func:`build_rank_index` produces, so the merged arrays are
    **bitwise identical** to a full re-sort (finite column values assumed).

    Steps per affected row: renumber live positions ≥ j (the buffer shifted
    right), drop the element pushed past ``cap`` (at most one, only when the
    buffer was full), insert (x, j) at its lexicographic rank, and normalize
    the +inf tail to the argsort convention (positions in order).  ``j ≥
    cap`` is a no-op (the row landed beyond the prefix buffer).  Returns the
    merged ``(svals, sidx, n)``.
    """
    h, capp = svals.shape
    pos = jnp.arange(capp, dtype=jnp.int32)

    def merge_one(sv, si, nf, xf):
        live = si < nf
        si_r = jnp.where(live & (si >= j), si + 1, si)
        drop = live & (si_r >= cap)
        order = jnp.argsort(drop.astype(jnp.int32), stable=True)
        sv2, si2 = sv[order], si_r[order]
        nlive = nf - jnp.sum(drop).astype(jnp.int32)
        before = (pos < nlive) & ((sv2 < xf) | ((sv2 == xf) & (si2 < j)))
        ins = jnp.sum(before).astype(jnp.int32)
        sv_prev = jnp.concatenate([sv2[:1], sv2[:-1]])
        si_prev = jnp.concatenate([si2[:1], si2[:-1]])
        sv3 = jnp.where(pos < ins, sv2, jnp.where(pos == ins, xf, sv_prev))
        si3 = jnp.where(pos < ins, si2, jnp.where(pos == ins, j, si_prev))
        n2 = jnp.minimum(nlive + 1, cap)
        sv4 = jnp.where(pos < n2, sv3, jnp.inf)
        si4 = jnp.where(pos < n2, si3, pos)
        return sv4, si4.astype(jnp.int32), n2

    msv, msi, mn = jax.vmap(merge_one)(
        svals, sidx, n.astype(jnp.int32), x.astype(jnp.float32)
    )
    apply = jnp.asarray(j, jnp.int32) < cap
    if aff is not None:
        apply = apply & aff
    apply = jnp.broadcast_to(apply, (h,))
    return (
        jnp.where(apply[:, None], msv, svals),
        jnp.where(apply[:, None], msi, sidx),
        jnp.where(apply, mn, n.astype(jnp.int32)),
    )
