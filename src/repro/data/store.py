"""In-memory columnar datastore with group-indexed incremental sampling.

Plays the role ClickHouse plays in the paper (§4 System Setup): each *table*
holds row-aligned columns plus a **group index** (e.g. rows per user / per
trip region).  At build time rows are permuted once *within each group* with a
fixed seed, so that

    prefix of length z  ==  simple random sample of size z without replacement

and growing a plan from z to z' touches only rows [z, z') — the paper's
incremental online-aggregation property.  On a real TPU cluster the column
buffers live sharded in HBM and the gather below is the ``sampled_agg``
Pallas kernel's DMA; here they live in host memory / device 0.

**Streaming append** (DESIGN.md § Online feature store): the paper's setting
is *online* aggregation over continuously arriving rows, so the store is not
a frozen snapshot.  :meth:`Table.append` extends a group's permuted prefix by
drawing the new row's position ``j ~ Uniform{0..m}`` from the table's own
seeded RNG stream (the sequential construction of a uniform random
permutation), which preserves the prefix-is-SRS invariant for every prefix
length after every append.  Each insertion bumps the group's **version** —
the cache-invalidation signal for device-resident precompute
(serving/feature_cache.py) — and is recorded in a bounded per-group append
log so cached prefix tables can be *delta-updated* instead of rebuilt.

**Crash recovery** (DESIGN.md § Fault tolerance): the bounded per-group log
is a cache-refresh convenience, not a durability story, so every append is
ALSO written to an unbounded **journal** stamped with a table-wide monotone
sequence number.  The raw column arrays play the durable-storage role; the
derived index state (``perm`` / ``group_ptr`` / ``versions`` / the bounded
log) is exactly what a crash or a partial write can corrupt, and
:meth:`Table.recover` rebuilds all of it by replaying the journal over the
build-time base state — byte-identical to the never-crashed table, because
each journal entry carries the ORIGINAL drawn prefix position ``j`` (no
re-draws on replay).  ``recover`` can also revalidate attached feature
caches so device-resident entries whose version/checksum no longer match
the rebuilt store are dropped instead of served.

**Input sanitization**: a NaN/Inf smuggled into a column poisons every
prefix power sum built over it, so :meth:`Table.append` polices values at
the edge — ``sanitize="reject"`` (default) raises naming the table, column
and offending row; ``sanitize="clamp"`` maps NaN to 0.0 (the store's
neutral pad value) and ±Inf to the column's observed finite range.

The store is deliberately framework-agnostic (plain numpy in, jnp out) so the
serving runtime, the fused executor, and the benchmarks all share it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Mapping

import jax.numpy as jnp
import numpy as np

from repro.tracing import span

__all__ = ["Table", "ColumnStore", "bucket_size", "build_table", "MAX_APPEND_LOG"]

#: Append-log depth per group.  A cached entry older than this many
#: insertions can no longer be delta-refreshed and falls back to a full
#: rebuild — bounding both log memory and worst-case delta-chain length.
MAX_APPEND_LOG = 64


def bucket_size(z: int, minimum: int = 64) -> int:
    """Round a sample size up to the next power of two (bounds recompiles)."""
    cap = minimum
    while cap < z:
        cap *= 2
    return cap


@dataclass
class Table:
    """Row-aligned columns + CSR-style group index over a permutation.

    ``versions[g]`` counts insertions into dense group ``g`` since build
    (0 = pristine); any append bumps it, so ``(table, group, version)`` is a
    sound cache key.  ``rng`` continues the build-time seeded stream, making
    the whole append trajectory deterministic given (seed, append sequence).
    """

    columns: dict[str, np.ndarray]
    group_ptr: np.ndarray          # (G+1,) offsets into perm
    perm: np.ndarray               # (R,) row ids, permuted within each group
    group_ids: dict[int, int]      # external group key -> dense group index
    name: str = ""
    rng: np.random.Generator = field(
        default_factory=lambda: np.random.default_rng(0), repr=False
    )
    versions: list[int] = field(default_factory=list, repr=False)
    # dense group -> [(version, j, row_id)] for the last MAX_APPEND_LOG
    # insertions, oldest first (version = the group version the insertion
    # produced; j = the drawn prefix position; row_id indexes ``columns``).
    _log: dict[int, list[tuple[int, int, int]]] = field(
        default_factory=dict, repr=False
    )
    #: Table-wide monotone sequence number; stamped on every journal entry.
    seq: int = field(default=0, repr=False)
    # Complete append journal, oldest first: (seq, external group key, j,
    # row_id), with j = -1 marking a group registration (add_group) event.
    # Unlike the bounded ``_log`` this is never truncated — it is the
    # replay source for :meth:`recover`.
    _journal: list[tuple[int, int, int, int]] = field(
        default_factory=list, repr=False
    )

    def __post_init__(self) -> None:
        # Build-time base state recover() replays the journal over.  These
        # are index-only copies (permutation + CSR offsets), never column
        # data — the raw columns are the durable record.
        self._base_perm = self.perm.copy()
        self._base_ptr = self.group_ptr.copy()
        self._base_gids = dict(self.group_ids)
        self._base_versions = list(self.versions)

    @property
    def n_rows(self) -> int:
        return int(self.perm.shape[0])

    @property
    def n_groups(self) -> int:
        return int(self.group_ptr.shape[0] - 1)

    def _group_index(self, gid: int) -> int:
        """Dense index of an external group key, or a loud ValueError.

        Streaming ingest makes unknown keys an expected runtime condition
        (a request for a user the store has never seen), so the error names
        the table and the key instead of leaking a bare KeyError.
        """
        try:
            return self.group_ids[int(gid)]
        except KeyError:
            raise ValueError(
                f"table {self.name or '<unnamed>'!r}: unknown group key "
                f"{int(gid)} (known groups: {len(self.group_ids)})"
            ) from None

    def version(self, gid: int) -> int:
        """Insertions into the group since build — the cache-key component."""
        g = self._group_index(gid)
        return self.versions[g] if g < len(self.versions) else 0

    def group_size(self, gid: int) -> int:
        g = self._group_index(gid)
        return int(self.group_ptr[g + 1] - self.group_ptr[g])

    def sample_prefix(self, column: str, gid: int, cap: int) -> np.ndarray:
        """First ``min(cap, N)`` permuted rows of the group, padded to cap.

        The prefix is the group's canonical SRS order; callers mask with the
        live ``z``.  Padding repeats 0.0 (masked out by estimators); an
        empty group is therefore the all-zero buffer with n = 0.
        """
        g = self._group_index(gid)
        start, stop = int(self.group_ptr[g]), int(self.group_ptr[g + 1])
        take = min(cap, stop - start)
        rows = self.perm[start : start + take]
        out = np.zeros((cap,), np.float32)
        out[:take] = self.columns[column][rows]
        return out

    def full_values(self, column: str, gid: int) -> np.ndarray:
        g = self._group_index(gid)
        start, stop = int(self.group_ptr[g]), int(self.group_ptr[g + 1])
        return self.columns[column][self.perm[start:stop]].astype(np.float32)

    def lookup(self, column: str, gid: int) -> float:
        """Point lookup (lightweight datastore op — computed exactly).

        An empty group (a just-registered user with no history) reads as
        0.0 — the same neutral value the padded sample buffers use — rather
        than silently reading the next group's first row.
        """
        g = self._group_index(gid)
        start, stop = int(self.group_ptr[g]), int(self.group_ptr[g + 1])
        if start == stop:
            return 0.0
        return float(self.columns[column][self.perm[start]])

    # --- streaming append --------------------------------------------------
    def add_group(self, gid: int) -> int:
        """Register an empty group (a new user); returns its dense index.

        Idempotent for known keys.  The group starts at version 0 with zero
        rows: lookups read 0.0 and sample buffers come back all-pad until
        the first append.
        """
        key = int(gid)
        if key in self.group_ids:
            return self.group_ids[key]
        g = self._register_group(key)
        self.seq += 1
        self._journal.append((self.seq, key, -1, -1))
        return g

    def _register_group(self, key: int) -> int:
        """Grow the index for a new group WITHOUT journaling (replay path)."""
        g = self.n_groups
        self.group_ptr = np.append(self.group_ptr, self.group_ptr[-1])
        self.group_ids[key] = g
        self._ensure_versions(g)
        return g

    def _ensure_versions(self, g: int) -> None:
        while len(self.versions) <= g:
            self.versions.append(0)

    def _sanitize_columns(
        self, new_cols: dict[str, np.ndarray], policy: str
    ) -> dict[str, np.ndarray]:
        """Police NaN/Inf at the ingest edge (they poison prefix power sums).

        ``reject`` raises naming the table, column and offending row within
        the append batch; ``clamp`` maps NaN to 0.0 (the store's neutral pad
        value) and ±Inf to the column's observed finite range.
        """
        if policy not in ("reject", "clamp"):
            raise ValueError(
                f"table {self.name or '<unnamed>'!r}: unknown sanitize "
                f"policy {policy!r} (expected 'reject' or 'clamp')"
            )
        for k, v in new_cols.items():
            if not np.issubdtype(v.dtype, np.floating):
                continue
            bad = ~np.isfinite(v)
            if not bad.any():
                continue
            if policy == "reject":
                i = int(np.flatnonzero(bad)[0])
                raise ValueError(
                    f"table {self.name or '<unnamed>'!r}: non-finite value "
                    f"{float(v[i])!r} in append column {k!r} at batch row "
                    f"{i} (sanitize='reject'; pass sanitize='clamp' to "
                    f"coerce)"
                )
            old = self.columns[k]
            pool = np.concatenate([old[np.isfinite(old)], v[~bad]])
            hi = float(pool.max()) if pool.size else 0.0
            lo = float(pool.min()) if pool.size else 0.0
            w = v.copy()
            w[np.isnan(v)] = 0.0
            w[v == np.inf] = hi
            w[v == -np.inf] = lo
            new_cols[k] = w
        return new_cols

    def append(
        self,
        rows: Mapping[str, np.ndarray],
        group_key,
        *,
        sanitize: str = "reject",
    ) -> None:
        """Append rows, drawing each one's SRS position from the seeded RNG.

        ``rows`` maps every existing column name to a (r,) array;
        ``group_key`` gives each row's group (unknown keys register new
        groups).  Row i lands at position ``j ~ Uniform{0..m}`` inside its
        group's permuted prefix (m = the group's size before the insertion)
        — the sequential construction of a uniform random permutation, so
        every prefix stays a simple random sample after every append.

        Each insertion bumps the group's version and is logged (bounded at
        ``MAX_APPEND_LOG`` per group) so device-resident caches can
        delta-update instead of rebuilding — and journaled (unbounded,
        sequence-stamped) so :meth:`recover` can rebuild the index state.
        """
        group_key = np.atleast_1d(np.asarray(group_key))
        r = group_key.shape[0]
        missing = sorted(set(self.columns) - set(rows))
        extra = sorted(set(rows) - set(self.columns))
        if missing or extra:
            raise ValueError(
                f"table {self.name or '<unnamed>'!r}: append columns must "
                f"match the table (missing {missing}, unexpected {extra})"
            )
        new_cols = {
            k: np.atleast_1d(np.asarray(v)).astype(self.columns[k].dtype)
            for k, v in rows.items()
        }
        for k, v in new_cols.items():
            if v.shape[0] != r:
                raise ValueError(
                    f"table {self.name or '<unnamed>'!r}: column {k!r} has "
                    f"{v.shape[0]} rows, group_key has {r}"
                )
        new_cols = self._sanitize_columns(new_cols, sanitize)
        base = self.n_rows
        for k in self.columns:
            self.columns[k] = np.concatenate([self.columns[k], new_cols[k]])
        for i in range(r):
            key = int(group_key[i])
            g = self.add_group(key)
            row_id = base + i
            start = int(self.group_ptr[g])
            m = int(self.group_ptr[g + 1]) - start
            j = int(self.rng.integers(0, m + 1))
            self.perm = np.insert(self.perm, start + j, row_id)
            self.group_ptr[g + 1 :] += 1
            self._ensure_versions(g)
            self.versions[g] += 1
            log = self._log.setdefault(g, [])
            log.append((self.versions[g], j, row_id))
            del log[:-MAX_APPEND_LOG]
            self.seq += 1
            self._journal.append((self.seq, key, j, row_id))

    def events_since(
        self, gid: int, version: int
    ) -> list[tuple[int, int]] | None:
        """The ``(j, row_id)`` insertions after ``version``, oldest first.

        Returns ``None`` when the bounded log no longer reaches back to
        ``version`` (or the group predates version tracking) — the caller
        must fall back to a full rebuild.
        """
        g = self._group_index(gid)
        current = self.versions[g] if g < len(self.versions) else 0
        if version == current:
            return []
        log = self._log.get(g, [])
        if not log or log[0][0] > version + 1:
            return None
        return [(j, row_id) for (v, j, row_id) in log if v > version]

    # --- crash recovery ----------------------------------------------------
    def recover(self, caches: tuple = ()) -> dict[str, int]:
        """Rebuild the derived index state by replaying the append journal.

        The raw column arrays are the durable record; ``perm`` /
        ``group_ptr`` / ``group_ids`` / ``versions`` / the bounded log are
        all derived, and a crash mid-append (or a corrupted buffer) can
        leave any of them torn.  Replaying the journal over the build-time
        base state rebuilds them byte-identical to the never-crashed table:
        each entry carries the ORIGINAL drawn prefix position ``j``, so no
        randomness is re-drawn and the SRS trajectory is reproduced exactly.

        ``caches`` are :class:`~repro.serving.feature_cache.FeatureCache`
        instances to revalidate afterwards — entries whose stored version or
        checksum no longer match the rebuilt store are dropped rather than
        served.  Returns counters: events replayed, groups rebuilt, cache
        entries dropped.
        """
        seqs = [e[0] for e in self._journal]
        if seqs and seqs != list(range(seqs[0], seqs[0] + len(seqs))):
            raise ValueError(
                f"table {self.name or '<unnamed>'!r}: append journal is not "
                f"a gap-free monotone sequence — cannot recover"
            )
        perm = self._base_perm.copy()
        ptr = self._base_ptr.copy()
        gids = dict(self._base_gids)
        versions = list(self._base_versions)
        log: dict[int, list[tuple[int, int, int]]] = {}
        for (_seq, key, j, row_id) in self._journal:
            if j < 0:
                if key not in gids:
                    gids[key] = len(ptr) - 1
                    ptr = np.append(ptr, ptr[-1])
                    while len(versions) < len(ptr) - 1:
                        versions.append(0)
                continue
            g = gids[key]
            start = int(ptr[g])
            perm = np.insert(perm, start + j, row_id)
            ptr[g + 1 :] += 1
            while len(versions) <= g:
                versions.append(0)
            versions[g] += 1
            glog = log.setdefault(g, [])
            glog.append((versions[g], j, row_id))
            del glog[:-MAX_APPEND_LOG]
        self.perm = perm
        self.group_ptr = ptr
        self.group_ids = gids
        self.versions = versions
        self._log = log
        dropped = sum(int(c.revalidate()) for c in caches)
        return {
            "replayed": len(self._journal),
            "groups": len(gids),
            "cache_entries_dropped": dropped,
        }


def build_table(
    columns: Mapping[str, np.ndarray],
    group_key: np.ndarray,
    seed: int = 0,
) -> Table:
    """Index ``columns`` by ``group_key`` and fix the per-group sample order."""
    group_key = np.asarray(group_key)
    uniq, inverse = np.unique(group_key, return_inverse=True)
    order = np.argsort(inverse, kind="stable")
    counts = np.bincount(inverse, minlength=len(uniq))
    ptr = np.zeros(len(uniq) + 1, np.int64)
    np.cumsum(counts, out=ptr[1:])
    rng = np.random.default_rng(seed)
    perm = order.copy()
    for g in range(len(uniq)):
        s, e = ptr[g], ptr[g + 1]
        perm[s:e] = rng.permutation(perm[s:e])
    cols = {k: np.asarray(v) for k, v in columns.items()}
    gids = {int(k): i for i, k in enumerate(uniq)}
    return Table(
        columns=cols, group_ptr=ptr, perm=perm, group_ids=gids,
        rng=rng, versions=[0] * len(uniq),
    )


@dataclass
class ColumnStore:
    """A named collection of tables — the serving datastore."""

    tables: dict[str, Table] = field(default_factory=dict)

    def add(self, name: str, table: Table) -> "ColumnStore":
        table.name = table.name or name
        self.tables[name] = table
        return self

    def __getitem__(self, name: str) -> Table:
        return self.tables[name]

    # --- fused-executor support -------------------------------------------
    def request_buffers(
        self,
        specs: list[tuple[str, str, int]],
        cap: int,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Gather (k, cap) padded prefix buffers + (k,) group sizes.

        One host->device transfer per request; afterwards the whole
        iterate-until-guaranteed loop runs on device (FusedExecutor).
        ``specs`` is [(table, column, gid), ...] per aggregate feature.
        """
        bufs = np.stack(
            [self.tables[t].sample_prefix(c, g, cap) for (t, c, g) in specs]
        )
        sizes = np.array(
            [min(self.tables[t].group_size(g), cap) for (t, c, g) in specs],
            np.int32,
        )
        with span("put", h2d_bytes=bufs.nbytes + sizes.nbytes):
            return jnp.asarray(bufs), jnp.asarray(sizes)

    def spec_versions(self, specs: list[tuple[str, str, int]]) -> tuple[int, ...]:
        """Per-spec group versions — the freshness half of a cache key."""
        return tuple(self.tables[t].version(g) for (t, _c, g) in specs)
