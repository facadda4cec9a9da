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
  ``P[j, p, c] = Σ_{i ≤ c} (v_{j,i} − shift_j)^(p+1)`` for the powers 1..4.
  The AFC (value, sigma) at ANY plan z is then one gather of the (k, 4)
  column at ``z − 1`` fed through the unchanged ``estimates_from_power_sums``
  tail — the per-iteration cost no longer depends on the group size.
  Accumulation is compensated (``compensated.py``): the cross-column carry
  is a Kahan (hi, lo) pair, the oracle an error-free-transform
  ``associative_scan`` — f32 storage with double-precision-class
  accumulation, since a naive f32 running Σv⁴ visibly drifts by 60k-row
  heavy-tailed groups.  Layout: (k, 4, cap), cap on the lanes, so the 4
  powers of a feature fill the sublanes of one (4, 128) tile densely.
  Memory: 4× the values buffer, freed with it per request (the values
  buffer itself is donated — serving/batched.py).

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
    "block_c",
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
LANES = 128                 # the TPU vector's lane width: one scan column
_SCOPED_VMEM = 16 * 2**20   # the kernel's scoped-VMEM limit (TPU default)
# columns scanned per loop step: their scans are independent, so unrolled
# they overlap the Kahan carry that chains them (on a TPU v5e at k = 9 and
# cap 131,072: 0.74 ms a call at 1, 0.39 at 2, 0.22 at 4, 0.41 at 8)
COLUMNS_PER_STEP = 4


def _power_list(v: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
    """u, u², u³, u⁴ of ``v``, each shaped like it."""
    v2 = v * v
    return v, v2, v2 * v, v2 * v2


def _powers(v: jnp.ndarray, axis: int) -> jnp.ndarray:
    """The four powers of ``v`` stacked on a new ``axis``."""
    return jnp.stack(_power_list(v), axis=axis)


@jax.jit
def prefix_power_sums_ref(
    vals: jnp.ndarray, shift: jnp.ndarray | None = None
) -> jnp.ndarray:
    """(k, cap) f32 -> (k, 4, cap) inclusive prefix sums of (v−shift)^p.

    Compensated scan (O(ε·log n) error); the column ``[:, :, z − 1]`` is
    exactly the ``[s1..s4]`` tail of ``sampled_moments``'s output at plan
    z (count = z), so the two paths share ``estimates_from_power_sums``.
    """
    v = vals.astype(jnp.float32)
    if shift is not None:
        v = v - shift.astype(jnp.float32)[:, None]
    return comp_cumsum(_powers(v, axis=-2), axis=-1)


def block_c(k: int, cap: int) -> int:
    """Lanes of the table one grid step writes, from ``(k, cap)`` alone.

    The largest power-of-two multiple of 128 whose double-buffered blocks
    fit half the scoped VMEM: the (k, cap) input block, sublanes rounded up
    to 8, and the (k, 4, cap) output block, its 4 powers counted as a full
    8-sublane tile.  Never wider than ``cap`` rounded up to 128.
    """
    per_lane = 2 * 4 * (-(-k // 8) * 8 + 8 * k)
    bc = LANES
    while 2 * bc * per_lane <= _SCOPED_VMEM // 2 and bc < cap:
        bc *= 2
    return bc


def _prefix_kernel(shift_ref, vals_ref, out_ref, hi_ref, lo_ref):
    k, bc = vals_ref.shape
    step = min(COLUMNS_PER_STEP, bc // LANES)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hi_ref[...] = jnp.zeros_like(hi_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (k, LANES), 1)
    shift = shift_ref[...]                        # (k, 1)

    def columns(g, carry):
        his, los = carry                          # 4 × (k, 128) each
        for q in range(step):
            off = pl.multiple_of((g * step + q) * LANES, LANES)
            v = vals_ref[:, pl.ds(off, LANES)].astype(jnp.float32) - shift
            new_his, new_los = [], []
            for p, u in enumerate(_power_list(v)):
                # inclusive scan of the column: log-step doubling along lanes
                s = 1
                while s < LANES:
                    u = u + jnp.where(lane >= s, pltpu.roll(u, s, 1), 0.0)
                    s *= 2
                # the smaller correction first, so the carry does not absorb it
                out_ref[:, p, pl.ds(off, LANES)] = his[p] + (u + los[p])
                last = jnp.broadcast_to(u[:, LANES - 1:], u.shape)
                hi, lo = kahan_step(his[p], los[p], last)
                new_his.append(hi)
                new_los.append(lo)
            his, los = tuple(new_his), tuple(new_los)
        return his, los

    carry = (
        tuple(hi_ref[p] for p in range(N_POWERS)),
        tuple(lo_ref[p] for p in range(N_POWERS)),
    )
    his, los = jax.lax.fori_loop(0, bc // (step * LANES), columns, carry)
    for p in range(N_POWERS):
        hi_ref[p] = his[p]
        lo_ref[p] = los[p]


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefix_power_sums(
    vals: jnp.ndarray,                 # (k, cap) f32
    shift: jnp.ndarray | None = None,  # (k,) f32 accumulation origin
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Pallas twin of :func:`prefix_power_sums_ref`: (k, 4, cap) tables.

    Layout: cap is the lane (minor) axis and the 4 powers sit on sublanes,
    the order the served lane table keeps, so the refill writes the
    kernel's output into its lane slot with no slice and no relayout.

    Blocks: all k rows in one block, cap tiled by :func:`block_c` (8,192
    lanes at the served widths).  A cap off the block grid is zero-padded
    and the output sliced back; padded columns are not a valid prefix
    continuation (they accumulate ``(0 − shift)^p``), but a prefix scan
    never carries them backwards.

    Scan: each 128-lane column is scanned by log-step doubling
    (``pltpu.roll`` and a lane mask; plain f32 adds, error O(ε·log 128)),
    and the columns are chained in order through a Kahan (hi, lo) carry
    per feature row and power that lives in VMEM across the grid, so the
    column and block boundaries add no uncompensated rounding.  Each loop
    step scans ``COLUMNS_PER_STEP`` columns.  ``shift``
    enters as a ``(k, 1)`` column so its block obeys the tiling rule.
    """
    k, cap = vals.shape
    if shift is None:
        shift = jnp.zeros((k,), jnp.float32)
    bc = block_c(k, cap)
    capp = -(-cap // bc) * bc
    if capp != cap:
        vals = jnp.pad(vals, ((0, 0), (0, capp - cap)))
    out = pl.pallas_call(
        _prefix_kernel,
        grid=(capp // bc,),
        in_specs=[
            pl.BlockSpec((k, 1), lambda j: (0, 0)),
            pl.BlockSpec((k, bc), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((k, N_POWERS, bc), lambda j: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((k, N_POWERS, capp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((N_POWERS, k, LANES), jnp.float32),
            pltpu.VMEM((N_POWERS, k, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(shift.astype(jnp.float32)[:, None], vals)
    return out if capp == cap else out[..., :cap]


def prefix_moments_at(ptab: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Gather the (k, 5) ``[count, s1..s4]`` moments row at plan z.

    ``ptab``: (k, 4, cap) prefix tables; ``z``: (k,) int32 in [0, cap].
    This is the whole per-iteration parametric AFC read — one gather along
    the lanes, independent of cap.  ``z == 0`` rows are all-zero (empty
    prefix).
    """
    cap = ptab.shape[-1]
    idx = jnp.clip(z - 1, 0, cap - 1).astype(jnp.int32)
    row = jnp.take_along_axis(ptab, idx[:, None, None], axis=-1)[..., 0]
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
    ptab: jnp.ndarray,       # (k, 4, cap) prefix power-sum tables
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
    k, _, cap = ptab.shape
    pw = _powers(x.astype(jnp.float32) - shift.astype(jnp.float32), axis=-1)
    shifted = jnp.concatenate(
        [jnp.zeros((k, N_POWERS, 1), jnp.float32), ptab[..., :-1]], axis=-1
    )
    s, e = two_sum(shifted, pw[..., None])
    upd = s + e
    c = jnp.arange(cap, dtype=jnp.int32)
    mask = (c[None, :] >= j) & (j < cap)
    if aff is not None:
        mask = mask & aff[:, None]
    return jnp.where(mask[:, None, :], upd, ptab)


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
