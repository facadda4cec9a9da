"""Continuous batching: a lane table served by the chunked fused executor.

The fixed-lane server (serving/batched.py) holds every lane of an admission
batch hostage until the SLOWEST request's while-loop exits — the straggler
waste ``straggler_report`` measures.  Continuous batching applies the
LLM-serving idea to planner loops: the executor runs ``chunk_iters``
planner iterations per dispatch over a persistent **lane table** (a
:class:`~repro.core.executor_fused.LaneState` pytree batched over lanes),
and a lane whose request converges is refilled from the admission queue at
the next chunk boundary — capacity approaches the per-device block-sum
bound instead of lanes·max(iters).

Two executables per power-of-two cap bucket, REGARDLESS of fill, chunk
count, or refill pattern (the compile contract ``compile_count`` /
``compiled_buckets`` make testable):

* **refill** — a SINGLE-LANE chunked-executor ``init`` scattered into the
  donated table at a traced lane index (``dynamic_update_slice`` per
  leaf): admitting a request costs exactly one lane's init — the AFC
  precompute, z⁰ evaluation and (k, cap) transfer for THAT request only —
  and admitting any lane reuses the one executable, because the index is
  data.  (A full-width masked-init refill was measured 8-20x more
  expensive per admission: every event re-ran the precompute for all
  lanes and shipped the whole (lanes, k, cap) buffer.)  Shapes depend
  only on (k, cap).
* **chunk** — the vmapped ``chunk`` advancing every lane at most
  ``chunk_iters`` iterations; done/inactive lanes cost zero loop trips.
  Shapes depend only on (cap, lanes, chunk_iters).

A ``mesh`` (1-D ``("lanes",)``, ``launch.mesh.make_serving_mesh``) shards
the table data-parallel via ``shard_map`` exactly like the fixed-lane
path: every LaneState leaf partitions on its leading lanes dimension and
the compiled programs stay **collective-free**.  The refill scatter
receives the fresh lane replicated and the global lane index as data;
each device translates it to a local row and only the owner writes its
shard — per-device lane recycling with no cross-device traffic.

The scheduler that drives this (arrival queue -> free-lane admission at
chunk boundaries -> chunk-granularity accounting) is
``serving/runtime.ContinuousServingRuntime``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.analysis.contracts import assert_compile_contract
from repro.core.executor_fused import (
    CHUNK_CARRY_LEAVES,
    build_afc_precompute,
    build_chunked_executor,
    pipeline_executor_kwargs,
    shard_lanes_state_executor,
)
from repro.core.pipeline import make_fused_model_fn
from repro.data.store import bucket_size
from repro.launch.mesh import LANES_AXIS
from repro.serving.batched import (
    lane_request_inputs,
    sanitize_lane_inputs,
    validate_serving_mesh,
)
from repro.serving.feature_cache import FeatureCache

__all__ = ["ContinuousBatchedServer"]


class ContinuousBatchedServer:
    """Lane-table server over the chunked fused executor.

    ``batch_size`` is the lane count of the persistent table,
    ``chunk_iters`` the planner iterations per chunk dispatch — the
    continuous-batching knob trading scheduling granularity (how quickly a
    freed lane is refilled) against per-dispatch overhead.  ``max_cap``,
    ``mesh``, ``afc_backend`` and ``cache_size`` mean exactly what they
    mean on :class:`~repro.serving.batched.BatchedFusedServer`: with a
    cache, every admission feeds a version-keyed LRU entry's
    ``(vals, n, PrebuiltTables)`` into a ``prebuilt=True`` refill — the
    single-lane init skips its AFC precompute — at the price of one extra
    executable per bucket (the cold precompute; ``cache_size`` and
    ``mesh`` are mutually exclusive).

    The server is deliberately schedule-free: it owns the compiled
    executables and the buffer assembly, while the caller owns the table
    and the lane bookkeeping — ``new_table`` -> (``admit`` | ``run_chunk``)*
    -> ``readback``.  One table serves one cap bucket (the trace-wide max);
    per-request degradation knobs are traced refill inputs, so tier changes
    never compile (the PR-6 contract survives recycling).
    """

    def __init__(self, bundle, config, batch_size: int = 8,
                 chunk_iters: int = 4, max_cap: int | None = None,
                 mesh=None, afc_backend: str = "auto",
                 cache_size: int | None = None, sanitize: str = "reject"):
        self.bundle = bundle
        self.config = config
        self.batch_size = batch_size
        self.chunk_iters = int(chunk_iters)
        self.mesh = mesh
        if sanitize not in ("reject", "clamp"):
            raise ValueError(
                f"sanitize must be 'reject' or 'clamp', got {sanitize!r}"
            )
        self.sanitize = sanitize
        self.n_devices = validate_serving_mesh(mesh, batch_size)
        if cache_size is not None and mesh is not None:
            raise ValueError(
                "cache_size and mesh are mutually exclusive: cached "
                "admissions feed host-tracked cache entries into the refill "
                "scatter, sharded tables partition device-resident buffers"
            )
        self._cache_size = cache_size
        self.cache: FeatureCache | None = None
        cached = cache_size is not None
        #: registered contracts governing this server's compiled executables
        #: (repro.analysis.contracts; declared in core/executor_fused.py) —
        #: the refill + chunk pair sums to the 2-per-bucket compile budget;
        #: the cache-fed table adds the cold precompute for 3 per bucket
        self.contract = (
            ("refill", "chunk", "afc_precompute")
            if cached
            else ("refill", "chunk")
        )
        p = bundle.pipeline
        feat_kwargs = pipeline_executor_kwargs(p.agg_features)
        self._agg_ids = feat_kwargs.pop("agg_ids")
        self._init_fn, chunk_fn = build_chunked_executor(
            make_fused_model_fn(p), chunk_iters=self.chunk_iters,
            k=p.k, task=p.task, n_classes=max(p.n_classes, 2),
            m=config.m, m_sobol=config.m_sobol, alpha=config.alpha,
            gamma=config.gamma, tau=config.tau, max_iters=config.max_iters,
            n_boot=config.n_bootstrap, afc_backend=afc_backend,
            prebuilt=cached, **feat_kwargs,
        )

        # trace hooks: fire once per jit cache miss (= per compiled
        # executable), exactly like BatchedFusedServer._counted — they sit
        # INSIDE the vmap/shard_map wrappers so the sharded path counts too
        self._refill_compiles = 0
        self._chunk_compiles = 0
        self._cold_compiles = 0

        if cached:
            pre = build_afc_precompute(
                k=p.k, alpha=config.alpha, gamma=config.gamma,
                max_iters=config.max_iters,
                holistic=feat_kwargs["holistic"],
                quantiles=feat_kwargs["quantiles"],
                approximate=feat_kwargs["approximate"],
            )
            self._pre_cold = pre.cold
            inner_cold = pre.cold

            def _counted_cold(vals, n):
                self._cold_compiles += 1
                return inner_cold(vals, n)

            self.cache = FeatureCache(
                bundle.store, jax.jit(_counted_cold), pre.refresh,
                maxsize=cache_size,
            )

            def _counted_init(vals, n, agg_ids, delta, exact, active, tau,
                              cap, tables):
                self._refill_compiles += 1
                return self._init_fn(vals, n, agg_ids, delta, exact, active,
                                     tau, cap, tables)
        else:

            def _counted_init(vals, n, agg_ids, delta, exact, active, tau,
                              cap):
                self._refill_compiles += 1
                return self._init_fn(vals, n, agg_ids, delta, exact, active,
                                     tau, cap)

        def _counted_chunk(state):
            self._chunk_compiles += 1
            return chunk_fn(state)

        def _write_lane(table, fresh, row):
            # one lane's slice of the donated table rewritten in place;
            # every other row aliases through untouched
            return jax.tree_util.tree_map(
                lambda old, new: jax.lax.dynamic_update_index_in_dim(
                    old, new.astype(old.dtype), row, 0
                ),
                table, fresh,
            )

        spec = PartitionSpec(LANES_AXIS)
        if mesh is not None:
            rows_per_dev = batch_size // self.n_devices

            def _refill_shard(table, vals, n, agg_ids, delta, exact, tau,
                              cap, lane):
                # inside shard_map: `table` is this device's row block, the
                # fresh-lane inputs are replicated.  Every device runs the
                # (cheap, single-lane) init; only the owner of the global
                # lane index writes its shard — no collectives.
                fresh = _counted_init(vals, n, agg_ids, delta, exact,
                                      jnp.asarray(True), tau, cap)
                local = lane - jax.lax.axis_index("lanes") * rows_per_dev
                mine = (local >= 0) & (local < rows_per_dev)
                row = jnp.clip(local, 0, rows_per_dev - 1)
                keep = jax.tree_util.tree_map(
                    lambda old: jax.lax.dynamic_index_in_dim(
                        old, row, 0, keepdims=False
                    ),
                    table,
                )
                safe = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(mine, new.astype(old.dtype),
                                               old),
                    fresh, keep,
                )
                return _write_lane(table, safe, row)

            refill_fn = jax.shard_map(
                _refill_shard, mesh=mesh,
                in_specs=(spec,) + (PartitionSpec(),) * 8,
                out_specs=spec, check_vma=False,
            )
            self._chunk = shard_lanes_state_executor(_counted_chunk, mesh)
        elif cached:

            def refill_fn(table, vals, n, agg_ids, delta, exact, tau, cap,
                          lane, tables):
                fresh = _counted_init(vals, n, agg_ids, delta, exact,
                                      jnp.asarray(True), tau, cap, tables)
                return _write_lane(table, fresh, lane)

            self._chunk = jax.jit(jax.vmap(_counted_chunk),
                                  donate_argnums=(0,))
        else:

            def refill_fn(table, vals, n, agg_ids, delta, exact, tau, cap,
                          lane):
                fresh = _counted_init(vals, n, agg_ids, delta, exact,
                                      jnp.asarray(True), tau, cap)
                return _write_lane(table, fresh, lane)

            self._chunk = jax.jit(jax.vmap(_counted_chunk),
                                  donate_argnums=(0,))

        # a sharded table leaves every program as it entered: pinning the
        # output sharding keeps XLA from reporting the zero-size leaves as
        # replicated, which would make the next dispatch a new cache key
        pinned = {} if mesh is None else {
            "out_shardings": NamedSharding(mesh, spec)
        }
        self._refill = jax.jit(refill_fn, donate_argnums=(0,), **pinned)
        self._caps_seen: set[int] = set()
        max_n = max(
            bundle.store[f.table].group_size(g)
            for f in p.agg_features
            for g in bundle.store[f.table].group_ids
        )
        self._max_cap = bucket_size(max_n)
        if max_cap is not None:
            self._max_cap = min(self._max_cap, bucket_size(max_cap))

    # ------------------------------------------------------------------
    @property
    def compiled_buckets(self) -> list[int]:
        """Cap buckets served so far (≤ log2(max_cap) entries ever)."""
        return sorted(self._caps_seen)

    @property
    def compile_count(self) -> int:
        """Executables built so far, per cap bucket.

        Must equal ``2 * len(compiled_buckets)`` (refill + chunk) — or 3
        with the feature cache enabled (+ the cold AFC precompute) — the
        continuous compile contract (``refill_compiles`` /
        ``chunk_compiles`` / ``cold_compiles`` split it).
        """
        return self._refill_compiles + self._chunk_compiles + self._cold_compiles

    @property
    def cold_compiles(self) -> int:
        return self._cold_compiles

    @property
    def refill_compiles(self) -> int:
        return self._refill_compiles

    @property
    def chunk_compiles(self) -> int:
        return self._chunk_compiles

    def check_compile_contract(self, *, buckets=None) -> None:
        """Assert observed compiles match the registered ``refill`` +
        ``chunk`` contracts (two executables per cap bucket, total)."""
        assert_compile_contract(self, self.contract, buckets=buckets)

    def request_cap(self, req: dict) -> int:
        """Power-of-two bucket over THIS request's largest group."""
        p = self.bundle.pipeline
        max_n = int(p.group_sizes(self.bundle.store, req).max())
        return min(bucket_size(max_n), self._max_cap)

    def trace_cap(self, requests) -> int:
        """The shared table cap for a trace: max over its requests."""
        return max(self.request_cap(r) for r in requests)

    # ------------------------------------------------------------------
    def new_table(self, cap: int):
        """An all-pad lane table at a cap bucket (device-resident zeros).

        Leaf shapes come from ``jax.eval_shape`` on the init function — no
        compile, no transfer of real data.  Zero leaves are a valid empty
        table: ``active=False`` forces every lane's loop predicate false,
        so a chunk over pad lanes runs zero trips (``done`` is only read
        for occupied lanes; the scheduler owns occupancy).
        """
        p = self.bundle.pipeline
        k, e = p.k, len(p.exact_features)
        dummy = (
            jax.ShapeDtypeStruct((k, cap), np.float32),   # vals
            jax.ShapeDtypeStruct((k,), np.int32),          # n
            jax.ShapeDtypeStruct((k,), np.int32),          # agg_ids
            jax.ShapeDtypeStruct((), np.float32),          # delta
            jax.ShapeDtypeStruct((e,), np.float32),        # exact
            jax.ShapeDtypeStruct((), bool),                # active
            jax.ShapeDtypeStruct((), np.float32),          # tau
            jax.ShapeDtypeStruct((), np.int32),            # iter_cap
        )
        if self.cache is not None:
            # the prebuilt init also takes a PrebuiltTables — its shapes come
            # from eval_shape on the cold precompute (no compile either)
            tables = jax.eval_shape(self._pre_cold, dummy[0], dummy[1])
            lane = jax.eval_shape(self._init_fn, *dummy, tables)
        else:
            lane = jax.eval_shape(self._init_fn, *dummy)
        lanes = self.batch_size
        table = jax.tree_util.tree_map(
            lambda s: jnp.zeros((lanes,) + s.shape, s.dtype), lane
        )
        if self.mesh is not None:
            # start sharded exactly as the refill/chunk programs return it:
            # a table committed elsewhere is a different jit cache key, and
            # the first dispatch after it would mint a second refill
            table = jax.device_put(
                table, NamedSharding(self.mesh, PartitionSpec(LANES_AXIS))
            )
        return table

    # ------------------------------------------------------------------
    def admit(self, table, cap: int, assignments):
        """Refill lanes with fresh requests: one single-lane dispatch each.

        ``assignments`` is a list of ``(lane, request, knobs_or_None)``;
        each named lane's ENTIRE LaneState slice is overwritten with the
        freshly initialized request (buffers, prefix tables, z⁰ carry,
        knobs), other lanes pass through untouched (the donated table
        aliases them in place).  An admission costs exactly the admitted
        request's own init — never a full-table re-init — and the lane
        index is traced data, so every dispatch reuses the bucket's one
        refill executable.  Returns ``(table, true_rows)`` where
        ``true_rows`` maps lane -> the request's TRUE total group rows (the
        honest ``sample_frac`` denominator the paper's §4 uses — cap
        clipping only shrinks the numerator).
        """
        p = self.bundle.pipeline
        store = self.bundle.store
        cfg = self.config
        delta_default = (
            cfg.delta if cfg.delta is not None else p.delta_default
        )
        lanes = self.batch_size
        seen: set[int] = set()
        true_rows: dict[int, int] = {}
        for lane, req, kn in assignments:
            if not 0 <= lane < lanes:
                raise ValueError(f"lane {lane} outside 0..{lanes - 1}")
            if lane in seen:
                raise ValueError(f"lane {lane} assigned twice in one admit")
            if self.request_cap(req) > cap:
                raise ValueError(
                    f"request needs cap {self.request_cap(req)} > table "
                    f"cap {cap}; size the table with trace_cap"
                )
            seen.add(lane)
        self._caps_seen.add(cap)
        for lane, req, kn in assignments:
            if self.cache is not None:
                # cached admission: vals/n/tables come device-resident from
                # the LRU; the refill scatter copies them into the lane row,
                # so the entry itself is never aliased by the donated table
                entry = self.cache.get(p.agg_specs(req), cap)
                vals, n = entry.vals, entry.n
                true_n = np.asarray(p.group_sizes(store, req), np.int64)
                exact = np.asarray(
                    p.exact_feature_values(store, req), np.float32
                )
                # cached vals are device-resident — checking them here would
                # cost a D2H sync per admission and defeat the zero-H2D hit
                # path; they are protected by append-time sanitization plus
                # the cache's power-sum integrity check instead.
                exact = sanitize_lane_inputs(
                    None, exact, policy=self.sanitize,
                    where=f"admit lane {lane}",
                )[1]
            else:
                vals, n, true_n, exact = lane_request_inputs(
                    p, store, req, cap
                )
                vals, exact = sanitize_lane_inputs(
                    vals, exact, policy=self.sanitize,
                    where=f"admit lane {lane}",
                )
            true_rows[lane] = int(true_n.sum())
            delta = delta_default if kn is None else kn.delta
            tau = cfg.tau if kn is None else kn.tau
            iter_cap = (
                cfg.max_iters if kn is None
                else min(int(kn.iter_cap), cfg.max_iters)
            )
            refill_args = (
                table,
                jnp.asarray(vals),
                jnp.asarray(n),
                self._agg_ids,
                jnp.asarray(delta, jnp.float32),
                jnp.asarray(exact),
                jnp.asarray(tau, jnp.float32),
                jnp.asarray(iter_cap, jnp.int32),
                jnp.asarray(lane, jnp.int32),
            )
            if self.cache is not None:
                table = self._refill(*refill_args, entry.tables)
            else:
                table = self._refill(*refill_args)
        return table, true_rows

    def run_chunk(self, table):
        """Advance every lane at most ``chunk_iters`` planner iterations."""
        return self._chunk(table)

    # ------------------------------------------------------------------
    @staticmethod
    def readback(table) -> dict:
        """Host copies of the small per-lane leaves the scheduler reads.

        Never touches ``vals``/``ptab``/``rindex`` — the big buffers stay
        device-resident across the whole table lifetime.
        """
        return dict(
            done=np.asarray(table.done),
            active=np.asarray(table.active),
            it=np.asarray(table.it, np.int64),
            z=np.asarray(table.z),
            n=np.asarray(table.n),
            y_hat=np.asarray(table.y_hat),
            prob=np.asarray(table.prob),
        )

    # --- chunk-boundary checkpoint / rollback --------------------------
    @staticmethod
    def snapshot(table) -> dict[str, np.ndarray]:
        """Checkpoint of the chunk-mutable carry: host copies of exactly
        the :data:`~repro.core.executor_fused.CHUNK_CARRY_LEAVES`.

        Every other LaneState leaf is content-invariant across a chunk
        dispatch (the big buffers are donated/aliased through with values
        unchanged), so this is the WHOLE state a rollback needs — a few KB
        per lane, no executables, no device work beyond the D2H copy.
        """
        return {
            name: np.asarray(getattr(table, name))
            for name in CHUNK_CARRY_LEAVES
        }

    @staticmethod
    def restore(table, ckpt: dict[str, np.ndarray]):
        """Roll the carry back to a :meth:`snapshot` — zero executables.

        Each checkpointed leaf is re-uploaded with ``device_put`` onto its
        current sharding (so sharded tables restore shard-local) and swapped
        into the pytree with ``_replace``; the untouched big buffers keep
        their device residency.  Replaying the chunk after a restore is
        bitwise-identical to a fault-free run because the bootstrap RNG is
        counter-based on the restored ``it``.
        """
        return table._replace(**{
            name: jax.device_put(val, getattr(table, name).sharding)
            for name, val in ckpt.items()
        })

    @staticmethod
    def clear_lanes(table, lanes):
        """Host-side eviction of specific lanes (quarantine / failure).

        Flips ``active=False`` / ``done=True`` for the named lanes so the
        chunk predicate never runs them again — the same all-pad posture
        ``new_table`` starts from.  The lane's other leaves keep their
        (possibly poisoned) values; they are unreadable until the next
        ``admit`` overwrites the whole slice.  Pure host swap + device_put:
        no executables.
        """
        active = np.asarray(table.active).copy()
        done = np.asarray(table.done).copy()
        for lane in lanes:
            active[lane] = False
            done[lane] = True
        return table._replace(
            active=jax.device_put(active, table.active.sharding),
            done=jax.device_put(done, table.done.sharding),
        )
