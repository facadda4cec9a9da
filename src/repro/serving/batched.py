"""Batched Biathlon serving: many concurrent requests in ONE XLA program.

The fused executor's state is fixed-shape, so a batch of requests vmaps
cleanly: each request carries its own sample buffers, group sizes, exact
features and delta; per-request early exit happens by predication inside
the shared while_loop (the loop runs until EVERY request in the admission
batch satisfies Eq. 1 or exhausts — the standard continuous-batching trade:
stragglers in a batch pay for each other, so admission batches should be
sized to the arrival rate).

Two mechanisms bound the jit cache:

* **Fixed lanes** — every admission batch is padded to exactly
  ``batch_size`` rows; pad lanes carry zero buffers and an ``active=False``
  flag that forces their while_loop predicate false inside the executor
  (executor_fused.py).  The compiled shape is therefore
  ``(batch_size, k, cap)`` for ANY batch fill 1..batch_size — one executable
  per cap bucket, not one per distinct fill.
* **Per-batch cap bucketing** — the (lanes, k, cap) gather pads to the next
  power of two above the BATCH's largest group, not the store-wide worst
  case, so a batch of small-group requests does proportionally small AFC
  work (the same power-of-two trick ``HostLoopExecutor`` uses).

``straggler_report`` makes the batching trade measurable (per-request
iterations vs the batch's shared iteration count, over ACTIVE lanes only).

This is the throughput-serving mode a TPU deployment would run: one
(lanes, k, cap) gather, one program, R guarantees.  The arrival-driven
admission loop that feeds it lives in serving/runtime.py.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import assert_compile_contract
from repro.core.executor_fused import (
    build_afc_precompute,
    build_fused_executor,
    pipeline_executor_kwargs,
    shard_lanes_executor,
)
from repro.core.pipeline import make_fused_model_fn
from repro.data.store import bucket_size
from repro.serving.feature_cache import FeatureCache
from repro.tracing import span

__all__ = [
    "BatchedFusedServer",
    "BatchResult",
    "chunked_straggler_report",
    "device_fill",
    "lane_request_inputs",
    "sanitize_lane_inputs",
    "straggler_report",
    "validate_serving_mesh",
]


def sanitize_lane_inputs(vals, exact, *, policy: str, where: str):
    """Police NaN/Inf in a lane's host-side inputs at the serving edge.

    A non-finite feature value entering the executor propagates through
    every prefix power sum and megabatch evaluation of its lane — and with
    continuous batching the poisoned carry then LIVES in the lane table.
    ``policy='reject'`` raises naming the offending buffer, feature row and
    position; ``policy='clamp'`` zeroes non-finite entries (0.0 is the
    store's neutral pad value, masked out by estimators at true prefix
    lengths).  ``vals`` may be ``None`` (cached admissions keep their
    values device-resident and are protected by the cache's integrity
    check instead).  Returns the (possibly rewritten) ``(vals, exact)``.
    """
    if policy not in ("reject", "clamp"):
        raise ValueError(
            f"{where}: unknown sanitize policy {policy!r} "
            f"(expected 'reject' or 'clamp')"
        )
    out = []
    for name, buf in (("vals", vals), ("exact", exact)):
        if buf is None:
            out.append(None)
            continue
        buf = np.asarray(buf)
        bad = ~np.isfinite(buf)
        if not bad.any():
            out.append(buf)
            continue
        if policy == "reject":
            pos = tuple(int(x) for x in np.argwhere(bad)[0])
            raise ValueError(
                f"{where}: non-finite value {float(buf[pos])!r} in request "
                f"{name} buffer at {pos} (sanitize='reject'; use "
                f"sanitize='clamp' to coerce, or fix the store column)"
            )
        buf = buf.copy()
        buf[bad] = 0.0
        out.append(buf)
    return tuple(out)


def validate_serving_mesh(mesh, lanes: int) -> int:
    """Validate a serving mesh against a fixed lane count; returns its size.

    Shared by the fixed-lane and continuous servers: the mesh must be 1-D,
    named ``lanes`` (shard_map partitions on the literal axis name — a
    differently-named mesh would only fail deep inside tracing at the first
    dispatch), and must divide the lane count evenly.  ``None`` means
    unsharded (returns 1).
    """
    if mesh is None:
        return 1
    if mesh.devices.ndim != 1:
        raise ValueError(
            f"serving mesh must be 1-D over 'lanes', got shape "
            f"{mesh.devices.shape}"
        )
    names = tuple(getattr(mesh, "axis_names", ()))
    if names and names != ("lanes",):
        raise ValueError(
            f"serving mesh axis must be named 'lanes', got {names}; "
            "build it with launch.mesh.make_serving_mesh"
        )
    n_devices = int(mesh.devices.size)
    if lanes % n_devices != 0:
        raise ValueError(
            f"batch_size {lanes} must be divisible by the mesh's "
            f"{n_devices} devices"
        )
    return n_devices


def lane_request_inputs(pipeline, store, req: dict, cap: int):
    """One request's lane inputs at a cap bucket.

    Returns ``(vals (k, cap) f32, n (k,) i32 clamped, true_n (k,) i64,
    exact (e,) f32)`` — the per-lane buffer assembly shared by the
    fixed-lane batch path and the continuous refill path, so both feed the
    executor identical data (a precondition of the recycling-parity
    contract).
    """
    v, _ = store.request_buffers(pipeline.agg_specs(req), cap)
    true_n = np.asarray(pipeline.group_sizes(store, req), np.int64)
    with span("fetch", d2h_bytes=v.nbytes):
        vals = np.asarray(v, np.float32)
    return (
        vals,
        np.minimum(true_n, cap).astype(np.int32),
        true_n,
        np.asarray(pipeline.exact_feature_values(store, req), np.float32),
    )


class BatchResult(NamedTuple):
    y_hat: np.ndarray
    prob: np.ndarray
    iters: np.ndarray       # (R,) per-request planner iterations (active lanes)
    sample_frac: np.ndarray  # samples touched / TRUE group rows (paper §4)
    batch_iters: int        # shared while_loop trip count = max(iters)
    cap: int                # bucketed buffer cap used for this batch
    lanes: int              # padded lane count the executable was compiled for
    z: np.ndarray | None = None  # (R, k) final per-request plans (active lanes)
    n_devices: int = 1      # mesh size the lanes were sharded over


def device_fill(fill: int, lanes: int, n_devices: int) -> np.ndarray:
    """Active lanes per device for a front-packed fill of a sharded batch.

    Lanes partition contiguously over the 1-D serving mesh (lane block
    ``d*lanes/D .. (d+1)*lanes/D - 1`` lives on device ``d``) and admission
    fills lanes front-to-back, so a batch with ``fill`` active lanes puts
    ``clip(fill - d·L/D, 0, L/D)`` of them on device ``d``.  Returns the
    (n_devices,) int array of active-lane counts.
    """
    if lanes % max(n_devices, 1) != 0:
        raise ValueError(f"lanes {lanes} not divisible by n_devices {n_devices}")
    per_dev = lanes // max(n_devices, 1)
    d = np.arange(max(n_devices, 1))
    return np.clip(fill - d * per_dev, 0, per_dev).astype(np.int64)


def straggler_report(res: BatchResult) -> dict:
    """How much the admission batch paid for its slowest request.

    ``wasted_iters[i]`` counts loop trips request i sat through after its own
    guarantee was met (predicated no-ops that still burn compute in the
    shared program); ``wasted_frac`` is their share of the batch's total
    *active*-lane-iterations — the admission-sizing signal.  Pad lanes never
    iterate (their predicate is forced false), so they are excluded from the
    waste accounting; ``fill`` reports how full the fixed-lane batch was.

    On a sharded batch (``res.n_devices > 1``) a lane only waits for the
    stragglers sharing its OWN device — each device's while-loop exits
    independently — so the waste accounting is per-device (lane i waits for
    its device-block max, not the global max) instead of silently charging
    every lane the global straggler.  ``per_device_fill`` gives each
    device's active-lane fraction (lanes partition contiguously, fills are
    front-packed) and ``lane_imbalance`` the max−min spread of those
    fractions — 0 means perfectly balanced, 1 means some device is full
    while another is all padding.

    An empty batch (zero active lanes) yields zeros and ``straggler == -1``.
    """
    iters = np.asarray(res.iters)
    n_dev = max(int(getattr(res, "n_devices", 1)), 1)
    lanes = max(int(res.lanes), 1)
    dev_active = device_fill(iters.size, lanes, n_dev)
    per_dev_fill = dev_active / (lanes // n_dev)
    if iters.size == 0:
        return {
            "batch_iters": 0,
            "per_request_iters": iters,
            "wasted_iters": iters,
            "wasted_frac": 0.0,
            "straggler": -1,
            "cap": int(res.cap),
            "lanes": int(res.lanes),
            "fill": 0.0,
            "n_devices": n_dev,
            "per_device_fill": per_dev_fill,
            "lane_imbalance": 0.0,
        }
    # lane i's device is i // (lanes/D): waste is measured against the max of
    # its own device block (== batch_iters when n_devices == 1)
    dev_of = np.arange(iters.size) // (lanes // n_dev)
    dev_max = np.zeros(n_dev, iters.dtype)
    np.maximum.at(dev_max, dev_of, iters)
    wasted = dev_max[dev_of] - iters
    total = max(int(dev_max[dev_of].sum()), 1)
    return {
        "batch_iters": int(res.batch_iters),
        "per_request_iters": iters,
        "wasted_iters": wasted,
        "wasted_frac": float(wasted.sum()) / total,
        "straggler": int(np.argmax(iters)),
        "cap": int(res.cap),
        "lanes": int(res.lanes),
        "fill": float(len(iters)) / lanes,
        "n_devices": n_dev,
        "per_device_fill": per_dev_fill,
        "lane_imbalance": float(per_dev_fill.max() - per_dev_fill.min()),
    }


def chunked_straggler_report(
    chunk_iters, occupied, *, lanes: int, n_devices: int = 1
) -> dict:
    """Chunk-granularity waste accounting for recycled lanes.

    With continuous batching a lane serves many requests per batch window
    and fills are NOT front-packed (a freed lane is refilled in place), so
    :func:`straggler_report`'s batch-global and :func:`device_fill`'s
    front-packed assumptions both break.  This report charges waste per
    **chunk** against each device block's chunk-boundary maximum: inputs
    are the (n_chunks, lanes) matrices of per-chunk planner-iteration
    counts and lane occupancy the scheduler records at every chunk
    boundary.

    ``wasted_iters[l]`` counts the loop trips lane ``l`` sat through beyond
    its own work while some co-resident lane on its device was still
    iterating — summed over chunks, so a lane recycled mid-window is only
    ever charged against the stragglers it ACTUALLY shared a dispatch with
    (the fixed-lane report would charge the whole batch window).
    ``per_device_fill`` / ``lane_imbalance`` are occupancy-true: mean
    occupied-lane fraction per device block over chunks, well-defined for
    any refill pattern and empty-safe (zero chunks -> zeros).
    """
    lanes = int(lanes)
    n_dev = max(int(n_devices), 1)
    if lanes % n_dev != 0:
        raise ValueError(f"lanes {lanes} not divisible by n_devices {n_dev}")
    per_dev = lanes // n_dev
    it = np.asarray(chunk_iters, np.int64).reshape(-1, lanes)
    occ = np.asarray(occupied, bool).reshape(-1, lanes)
    if it.shape != occ.shape:
        raise ValueError(
            f"chunk_iters {it.shape} and occupied {occ.shape} must align"
        )
    n_chunks = it.shape[0]
    if n_chunks == 0:
        return {
            "n_chunks": 0,
            "lanes": lanes,
            "n_devices": n_dev,
            "lane_occupancy": 0.0,
            "per_device_fill": [0.0] * n_dev,
            "lane_imbalance": 0.0,
            "wasted_iters": np.zeros(lanes, np.int64),
            "wasted_frac": 0.0,
            "total_iters": 0,
        }
    it = np.where(occ, it, 0)
    blk = it.reshape(n_chunks, n_dev, per_dev)
    occ_blk = occ.reshape(n_chunks, n_dev, per_dev)
    # each dispatch, a lane waits for its OWN device block's straggler —
    # the chunk-boundary device-block max, not the batch-window global max
    blk_max = blk.max(axis=2)                                   # (C, D)
    wasted = np.where(occ_blk, blk_max[:, :, None] - blk, 0)    # (C, D, L/D)
    charged = np.where(occ_blk, blk_max[:, :, None], 0)
    occ_frac = occ_blk.mean(axis=2)                             # (C, D)
    return {
        "n_chunks": int(n_chunks),
        "lanes": lanes,
        "n_devices": n_dev,
        "lane_occupancy": float(occ.mean()),
        "per_device_fill": [float(x) for x in occ_frac.mean(axis=0)],
        "lane_imbalance": float((occ_frac.max(1) - occ_frac.min(1)).mean()),
        "wasted_iters": wasted.reshape(n_chunks, lanes).sum(axis=0),
        "wasted_frac": float(wasted.sum()) / max(int(charged.sum()), 1),
        "total_iters": int(it.sum()),
    }


class BatchedFusedServer:
    """vmapped FusedExecutor over fixed-lane admission batches of requests.

    One compiled program per power-of-two cap bucket: batches are padded to
    exactly ``batch_size`` lanes (inactive lanes predicated out on device),
    so the jit cache is keyed by ``(batch_size, k, cap)`` only — varying
    batch fill never recompiles.  ``compile_count`` / ``compiled_buckets``
    make that observable (and testable).

    ``max_cap`` optionally lowers the store-wide buffer ceiling (bounded
    device memory); groups larger than the cap degrade gracefully — the
    executor exhausts at ``cap`` rows and ``sample_frac`` stays honest
    because its denominator is the TRUE group size.

    ``mesh`` (a 1-D ``("lanes",)`` mesh from ``launch.mesh.make_serving_mesh``)
    shards the fixed lanes data-parallel across its devices via
    ``shard_map``: lane ``i`` lives on device ``i // (batch_size/D)``, model
    params stay replicated, and the hot path runs no collectives.  The
    fixed-lane contract is mesh-invariant — still ONE executable per
    power-of-two cap bucket, for every fill and any device count — and
    per-lane results are identical to the unsharded server (bitwise for the
    integer plans; fp-tolerance for predictions, since XLA recompiles at a
    different per-device lane count).  ``batch_size`` must divide evenly
    over the mesh.

    The (lanes, k, cap) values buffer is **donated** on both paths and
    threaded back out as ``FusedResult.lane_vals``, so XLA aliases it in
    place instead of copying it per batch; ``afc_backend`` is forwarded to
    :func:`build_fused_executor` ("auto" = incremental prefix-stats AFC,
    "ref" = the pre-refactor rescan oracle).

    ``cache_size`` enables the hot-group feature cache: every lane's
    ``(vals, n, PrebuiltTables)`` comes from a version-keyed LRU
    (serving/feature_cache.py), the executor runs ``prebuilt=True``, and the
    per-lane stacks are fresh ``jnp.stack`` copies — so donating the stacked
    buffer never aliases a cache entry.  Incompatible with ``mesh`` (the
    sharded path owns its lane buffers device-side).
    """

    def __init__(self, bundle, config, batch_size: int = 8,
                 max_cap: int | None = None, mesh=None,
                 afc_backend: str = "auto", cache_size: int | None = None,
                 sanitize: str = "reject"):
        self.bundle = bundle
        self.config = config
        self.batch_size = batch_size
        self.mesh = mesh
        if sanitize not in ("reject", "clamp"):
            raise ValueError(
                f"sanitize must be 'reject' or 'clamp', got {sanitize!r}"
            )
        self.sanitize = sanitize
        self.n_devices = validate_serving_mesh(mesh, batch_size)
        if cache_size is not None and mesh is not None:
            raise ValueError(
                "cache_size and mesh are mutually exclusive: cached lanes "
                "stack host-tracked cache entries, sharded lanes partition "
                "device-resident buffers"
            )
        self._cache_size = cache_size
        self.cache: FeatureCache | None = None
        cached = cache_size is not None
        #: registered contract governing this server's compiled executables
        #: (repro.analysis.contracts; declared in core/executor_fused.py)
        if cached:
            self.contract = ("fused_prebuilt", "afc_precompute")
        elif mesh is not None:
            self.contract = ("sharded_lanes",)
        else:
            self.contract = ("fused",)
        p = bundle.pipeline
        feat_kwargs = pipeline_executor_kwargs(p.agg_features)
        self._agg_ids = feat_kwargs.pop("agg_ids")
        self._run = build_fused_executor(
            make_fused_model_fn(p), k=p.k, task=p.task,
            n_classes=max(p.n_classes, 2),
            m=config.m, m_sobol=config.m_sobol, alpha=config.alpha,
            gamma=config.gamma, tau=config.tau, max_iters=config.max_iters,
            n_boot=config.n_bootstrap, afc_backend=afc_backend,
            prebuilt=cached, **feat_kwargs,
        )

        # jit caches one executable per distinct (lanes, k, cap) input shape;
        # fixed lanes + power-of-two caps bound that to one per cap bucket.
        # The trace hook fires exactly once per cache miss (= per compile),
        # making the compile count observable without backend internals.
        self._compile_count = 0

        if cached:
            pre = build_afc_precompute(
                k=p.k, alpha=config.alpha, gamma=config.gamma,
                max_iters=config.max_iters,
                holistic=feat_kwargs["holistic"],
                quantiles=feat_kwargs["quantiles"],
                approximate=feat_kwargs["approximate"],
            )
            inner_cold = pre.cold

            def _counted_pre(vals, ns, agg_ids, delta, exacts, tables,
                             active, tau, iter_cap):
                self._compile_count += 1
                res = self._run(vals, ns, agg_ids, delta, exacts, tables,
                                active, tau, iter_cap)
                return res._replace(lane_vals=vals)

            def _counted_cold(vals, n):
                self._compile_count += 1
                return inner_cold(vals, n)

            self._batched = jax.jit(jax.vmap(_counted_pre),
                                    donate_argnums=(0,))
            self.cache = FeatureCache(
                bundle.store, jax.jit(_counted_cold), pre.refresh,
                maxsize=cache_size,
            )
        else:
            def _counted(vals, ns, agg_ids, delta, exacts, active, tau,
                         iter_cap):
                self._compile_count += 1
                res = self._run(vals, ns, agg_ids, delta, exacts, active, tau,
                                iter_cap)
                # thread the donated values buffer back out as lane state:
                # the identity passthrough becomes an XLA input-output alias,
                # so the (lanes, k, cap) buffer is neither copied per batch
                # nor kept alive twice (no-copy contract; see
                # shard_lanes_executor).
                return res._replace(lane_vals=vals)

            # the trace hook sits INSIDE the vmap/shard_map wrappers, so it
            # still fires exactly once per jit cache miss on the sharded path
            if mesh is not None:
                self._batched = shard_lanes_executor(
                    _counted, mesh, donate_vals=True
                )
            else:
                self._batched = jax.jit(jax.vmap(_counted), donate_argnums=(0,))
        self._caps_seen: set[int] = set()
        max_n = max(
            bundle.store[f.table].group_size(g)
            for f in p.agg_features
            for g in bundle.store[f.table].group_ids
        )
        self._max_cap = bucket_size(max_n)  # store-wide ceiling, not the default
        if max_cap is not None:
            self._max_cap = min(self._max_cap, bucket_size(max_cap))

    # ------------------------------------------------------------------
    @property
    def compiled_buckets(self) -> list[int]:
        """Cap buckets served so far (≤ log2(max_cap) entries ever)."""
        return sorted(self._caps_seen)

    @property
    def compile_count(self) -> int:
        """Executables built so far — must equal ``len(compiled_buckets)``."""
        return self._compile_count

    def check_compile_contract(self, *, buckets=None) -> None:
        """Assert observed compiles match the registered ``fused`` /
        ``sharded_lanes`` contract (one executable per cap bucket)."""
        assert_compile_contract(self, self.contract, buckets=buckets)

    def batch_cap(self, requests: list[dict]) -> int:
        """Power-of-two bucket over THIS batch's largest group."""
        p = self.bundle.pipeline
        max_n = max(
            int(p.group_sizes(self.bundle.store, req).max()) for req in requests
        )
        return min(bucket_size(max_n), self._max_cap)

    # ------------------------------------------------------------------
    def serve_batch(self, requests: list[dict], knobs=None) -> BatchResult:
        """Serve an admission batch of 0..batch_size requests.

        The batch is padded to exactly ``batch_size`` lanes; results are
        sliced back to the real requests before returning.  Oversize lists
        are rejected — admitting them would compile one executable per
        distinct oversize fill, breaking the fixed-lane no-recompile
        contract (callers chunk at admission time; serving/runtime.py does).

        ``knobs`` (optional, aligned with ``requests``) carries per-lane
        degradation settings — :class:`~repro.serving.degrade.LaneKnobs`
        entries (or ``None`` for the config defaults).  delta, tau, and the
        planner iteration cap are all *traced* ``(lanes,)`` inputs of the
        fused executor, so an SLO controller can vary them every batch
        without minting a new executable per cap bucket (the fixed-lane
        compile contract is knob-invariant; pad lanes carry the defaults).
        """
        p = self.bundle.pipeline
        store = self.bundle.store
        delta = (
            self.config.delta if self.config.delta is not None else p.delta_default
        )
        r = len(requests)
        if r > self.batch_size:
            raise ValueError(
                f"admission batch of {r} exceeds the fixed lane count "
                f"{self.batch_size}; chunk before dispatch"
            )
        if knobs is not None and len(knobs) != r:
            raise ValueError(
                f"knobs ({len(knobs)}) must align with requests ({r})"
            )
        if r == 0:
            empty = np.zeros((0,), np.float32)
            return BatchResult(
                y_hat=empty, prob=empty, iters=np.zeros((0,), np.int32),
                sample_frac=empty, batch_iters=0, cap=0, lanes=self.batch_size,
                z=np.zeros((0, p.k), np.int32), n_devices=self.n_devices,
            )
        lanes = self.batch_size
        cap = self.batch_cap(requests)
        true_ns = np.zeros((r, p.k), np.int64)
        exacts = np.zeros((lanes, len(p.exact_features)), np.float32)
        entries = None
        if self.cache is not None:
            # cached lanes: vals/n/tables come device-resident from the LRU;
            # only the cheap scalars (true sizes, exact features) touch host
            entries = []
            for i, req in enumerate(requests):
                entries.append(self.cache.get(p.agg_specs(req), cap))
                true_ns[i] = np.asarray(p.group_sizes(store, req), np.int64)
                exacts[i] = np.asarray(
                    p.exact_feature_values(store, req), np.float32
                )
                exacts[i] = sanitize_lane_inputs(
                    None, exacts[i], policy=self.sanitize,
                    where=f"serve_batch lane {i}",
                )[1]
        else:
            vals = np.zeros((lanes, p.k, cap), np.float32)
            ns = np.zeros((lanes, p.k), np.int32)
            for i, req in enumerate(requests):
                vals[i], ns[i], true_ns[i], exacts[i] = lane_request_inputs(
                    p, store, req, cap
                )
                vals[i], exacts[i] = sanitize_lane_inputs(
                    vals[i], exacts[i], policy=self.sanitize,
                    where=f"serve_batch lane {i}",
                )
        active = np.arange(lanes) < r
        # per-lane degradation knobs: traced data, never part of the cache
        # key (pad lanes + unknobbed requests get the config defaults)
        deltas = np.full((lanes,), delta, np.float32)
        taus = np.full((lanes,), self.config.tau, np.float32)
        caps = np.full((lanes,), self.config.max_iters, np.int32)
        if knobs is not None:
            for i, kn in enumerate(knobs):
                if kn is None:
                    continue
                deltas[i] = kn.delta
                taus[i] = kn.tau
                caps[i] = min(int(kn.iter_cap), self.config.max_iters)
        self._caps_seen.add(cap)
        if entries is not None:
            # pad lanes reuse the first entry (active=False predicates them
            # out); jnp.stack COPIES, so the donated stacked buffer can never
            # alias — and never corrupt — a live cache entry
            lane_entries = entries + [entries[0]] * (lanes - r)
            res = self._batched(
                jnp.stack([e.vals for e in lane_entries]),
                jnp.stack([e.n for e in lane_entries]),
                jnp.broadcast_to(self._agg_ids, (lanes, p.k)),
                jnp.asarray(deltas),
                jnp.asarray(exacts),
                jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *[e.tables for e in lane_entries],
                ),
                jnp.asarray(active),
                jnp.asarray(taus),
                jnp.asarray(caps),
            )
        else:
            res = self._batched(
                jnp.asarray(vals),
                jnp.asarray(ns),
                jnp.broadcast_to(self._agg_ids, (lanes, p.k)),
                jnp.asarray(deltas),
                jnp.asarray(exacts),
                jnp.asarray(active),
                jnp.asarray(taus),
                jnp.asarray(caps),
            )
        iters = np.asarray(res.iters)[:r]
        return BatchResult(
            y_hat=np.asarray(res.y_hat)[:r],
            prob=np.asarray(res.prob)[:r],
            iters=iters,
            # paper §4 sample fraction: touched rows over TRUE group rows
            # (matches BiathlonServer.serve across modes; cap clipping only
            # shrinks the numerator)
            sample_frac=np.asarray(res.samples_used)[:r]
            / np.maximum(true_ns.sum(1), 1),
            batch_iters=int(iters.max(initial=0)),
            cap=cap,
            lanes=lanes,
            z=np.asarray(res.z)[:r],
            n_devices=self.n_devices,
        )
