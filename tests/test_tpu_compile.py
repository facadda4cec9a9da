"""Ahead-of-time compiles of the served path for a described TPU v5e.

Nothing here runs on a chip.  The TPU compiler is installed, and it
compiles for a ``v5e:2x2`` topology that is described, not attached: what
Mosaic or XLA would refuse on the chip (block shapes off the (8, 128)
tiling, scoped-VMEM overruns, unsupported primitives, compiler CHECK
failures) is refused here.  Covered:

* the three Pallas kernels on the path at the widths the pipelines use,
  bare and under ``vmap`` (how the fixed-lane and continuous executors call
  them): ``sampled_moments`` and ``prefix_power_sums`` at turbofan's k=9 up
  to cap 65,536, ``masked_select_ranks`` at sensor_health's h=3 (and a
  two-tile h=9) with B=256 bootstrap targets at cap 1,024;
* the continuous server's refill and chunk executables: turbofan at cap
  65,536 (incremental AFC, prefix-table kernel in the refill) and
  sensor_health at cap 1,024 (rescan, both loop kernels in the chunk);
* the same pair sharded over the four chips of the ``("lanes",)`` mesh,
  which must stay collective-free.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library (see the on-chip guide).
The persistent compile cache is off around these compiles, since an entry
written for a described chip cannot be read back without one.
"""
import inspect
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.executor import BiathlonConfig
from repro.data.synthetic import make_pipeline, make_pipeline_median
from repro.kernels.sampled_agg import ops
from repro.kernels.sampled_agg.prefix_stats import prefix_power_sums
from repro.kernels.sampled_agg.quantile_select import masked_select_ranks
from repro.kernels.sampled_agg.sampled_agg import sampled_moments
from repro.launch.mesh import make_serving_mesh
from repro.serving import ContinuousBatchedServer

f32, i32 = jnp.float32, jnp.int32
LANES = 8
N_BOOT = 256
# the served config's megabatch widths (benchmarks/common.py DEFAULT_CFG)
CFG = BiathlonConfig(m=500, m_sobol=128, n_bootstrap=N_BOOT)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernel_routing(monkeypatch):
    """Steer ops' backend query to the TPU branch: Pallas, no interpreter.

    The REPRO_AFC_BACKEND override (set by the CI legs) is removed so the
    executors take the default per-cap-bucket routing a chip sees.
    """
    monkeypatch.delenv("REPRO_AFC_BACKEND", raising=False)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _shape(sharding, shape, dtype=f32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; return the executable's HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _batched(fn, lanes):
    return jax.vmap(fn) if lanes else fn


def _lead(lanes):
    return (lanes,) if lanes else ()


# ------------------------------------------------------------- kernels
@pytest.mark.parametrize("k,cap,lanes", [
    (9, 65536, 0),       # turbofan width, largest bucket
    (9, 1024, LANES),    # rescan route inside the vmapped chunk
    (5, 128, LANES),     # sensor_health width, smallest bucket
])
def test_sampled_moments_compiles(one_chip, k, cap, lanes):
    fn = _batched(
        lambda v, z, s: sampled_moments(v, z, s, interpret=False), lanes
    )
    b = _lead(lanes)
    text = _compile(
        fn,
        _shape(one_chip, b + (k, cap)),
        _shape(one_chip, b + (k,), i32),
        _shape(one_chip, b + (k,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,cap,lanes", [
    (9, 65536, 0),       # the per-request refill precompute
    (9, 65536, LANES),   # the fixed-lane batch program
    (9, 128, 0),         # cached path: incremental at every cap
    (5, 4096, LANES),    # sensor_health width
])
def test_prefix_power_sums_compiles(one_chip, k, cap, lanes):
    fn = _batched(
        lambda v, s: prefix_power_sums(v, s, interpret=False), lanes
    )
    b = _lead(lanes)
    text = _compile(
        fn, _shape(one_chip, b + (k, cap)), _shape(one_chip, b + (k,))
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("h,lanes", [
    (3, 0),          # sensor_health: two medians + a tail quantile
    (3, LANES),      # inside the vmapped rescan chunk
    (9, LANES),      # more holistic columns than one 8-row tile
])
def test_masked_select_ranks_compiles(one_chip, h, lanes):
    cap = 1024
    fn = _batched(
        lambda v, z, t: masked_select_ranks(v, z, t, interpret=False), lanes
    )
    b = _lead(lanes)
    text = _compile(
        fn,
        _shape(one_chip, b + (h, cap)),
        _shape(one_chip, b + (h,), i32),
        _shape(one_chip, b + (h, 1 + N_BOOT), i32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "fn", [sampled_moments, prefix_power_sums, masked_select_ranks],
    ids=lambda f: f.__name__,
)
def test_kernels_never_default_to_the_interpreter(fn):
    """A caller that leaves ``interpret`` out must not get the Pallas
    interpreter on a chip without a word: the argument is required."""
    param = inspect.signature(fn).parameters["interpret"]
    assert param.default is inspect.Parameter.empty


# ------------------------------------------- continuous server programs
def _server(name, mesh=None):
    size = dict(rows_per_group=200, n_train_groups=30, n_serve_groups=2,
                n_requests=2)
    if name == "battery_median":
        b = make_pipeline_median("battery", **size)
    else:
        b = make_pipeline(name, **size)
    return ContinuousBatchedServer(b, CFG, batch_size=LANES, chunk_iters=4,
                                   mesh=mesh)


def _table_shapes(srv, cap, sharding):
    """The lane table's leaves as shapes, from the single-lane init."""
    p = srv.bundle.pipeline
    k, e = p.k, len(p.exact_features)
    lane = jax.eval_shape(
        srv._init_fn,
        *(jax.ShapeDtypeStruct(s, d) for s, d in [
            ((k, cap), f32), ((k,), i32), ((k,), i32), ((), f32),
            ((e,), f32), ((), bool), ((), f32), ((), i32),
        ]),
    )
    return jax.tree_util.tree_map(
        lambda s: _shape(sharding, (LANES,) + s.shape, s.dtype), lane
    )


def _refill_shapes(srv, cap, table_sharding, arg_sharding):
    p = srv.bundle.pipeline
    k, e = p.k, len(p.exact_features)
    return (
        _table_shapes(srv, cap, table_sharding),
        _shape(arg_sharding, (k, cap)),
        _shape(arg_sharding, (k,), i32),
        _shape(arg_sharding, (k,), i32),
        _shape(arg_sharding, ()),
        _shape(arg_sharding, (e,)),
        _shape(arg_sharding, ()),
        _shape(arg_sharding, (), i32),
        _shape(arg_sharding, (), i32),
    )


@pytest.mark.parametrize("name,cap,kernel_in", [
    # incremental AFC: the prefix-table kernel runs in the refill only
    ("turbofan", 65536, "refill"),
    # rescan: sampled_moments + masked_select_ranks in every chunk (and
    # the replicate-column scatter the TPU compiler once aborted on)
    ("sensor_health", 1024, "chunk"),
])
def test_continuous_executables_compile(one_chip, kernel_routing, name, cap,
                                        kernel_in):
    srv = _server(name)
    args = _refill_shapes(srv, cap, one_chip, one_chip)
    text = {
        "refill": srv._refill.lower(*args).compile().as_text(),
        "chunk": srv._chunk.lower(args[0]).compile().as_text(),
    }
    assert "tpu_custom_call" in text[kernel_in]


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) \(")


def _computations(text):
    """{computation: {instruction: (type, opcode, operands, called)}} of a
    compiled module; the entry computation is keyed ``ENTRY``.  Operands
    are instruction names, or the index of a ``parameter``."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault("ENTRY" if m.group(1) else m.group(2), {})
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            name, typ, op, rest = m.groups()
            args = rest.split(")", 1)[0]
            operands = (
                [int(args)] if op == "parameter"
                else re.findall(r"%([\w.\-]+)", args)
            )
            called = re.search(r"calls=%([\w.\-]+)", rest)
            cur[name] = (typ, op, operands, called and called.group(1))
    return comps


def _laid_out_bytes(typ):
    """Bytes of an f32 array as laid out: its two minor dims rounded up to
    the layout's tile."""
    dims = [int(d) for d in re.search(r"\[([\d,]+)\]", typ).group(1).split(",")]
    m = re.search(r"\{([\d,]+):T\((\d+),(\d+)\)", typ)
    minor_to_major = [int(d) for d in m.group(1).split(",")]
    tile = (int(m.group(3)), int(m.group(2)))     # minor dim first
    n = 4
    for i, d in enumerate(minor_to_major):
        n *= -(-dims[d] // tile[i]) * tile[i] if i < 2 else dims[d]
    return n


def _in_any_memory_space(typ):
    return re.sub(r"S\(\d+\)", "", typ)


@pytest.mark.parametrize("name,k", [("turbofan", 9), ("battery_median", 10)])
def test_refill_writes_the_prefix_table_once_lane_dense(one_chip,
                                                         kernel_routing,
                                                         name, k):
    """The refill's prefix tables go from the kernel into the lane table
    as they are: one ``prefix_power_sums`` kernel, its output within 2x of
    the logical 4·k·cap f32 words, and nothing between it and the table's
    ``dynamic-update-slice`` but bitcasts and the compiler's asynchronous
    moves between memory spaces, which keep the shape and the tiling (no
    slice, no pad, no relayout copy)."""
    cap = 131072
    srv = _server(name)
    args = _refill_shapes(srv, cap, one_chip, one_chip)
    comps = _computations(srv._refill.lower(*args).compile().as_text())
    entry = comps["ENTRY"]

    kernels = [n for n, (_, op, _, _) in entry.items()
               if op == "custom-call" and n.startswith("prefix_power_sums")]
    assert len(kernels) == 1, kernels
    kernel_type = entry[kernels[0]][0]
    assert _laid_out_bytes(kernel_type) <= 2 * 4 * k * cap * 4, kernel_type

    table = f"f32[{LANES},{k},4,{cap}]"
    writes = [n for n, (typ, op, _, _) in entry.items()
              if typ.startswith(table)
              and op in ("dynamic-update-slice", "fusion")]
    assert len(writes) == 1, writes
    _, op, operands, called = entry[writes[0]]
    if op == "fusion":
        # a fused write holds bitcasts around the update-slice, nothing else
        fused = comps[called]
        assert {v[1] for v in fused.values()} <= {
            "parameter", "bitcast", "constant", "dynamic-update-slice"
        }, fused
        (dus,) = [v for v in fused.values() if v[1] == "dynamic-update-slice"]
        src = dus[2][1]
        while fused[src][1] == "bitcast":
            src = fused[src][2][0]
        assert fused[src][1] == "parameter", fused[src]
        src = operands[fused[src][2][0]]
    else:
        src = operands[1]
    while src != kernels[0]:
        typ, op, operands, _ = entry[src]
        assert op in ("bitcast", "copy-done", "copy-start"), (src, op)
        if op == "copy-start":
            # (destination, source, context): only the memory space moves
            dest = typ.strip("()").split(", ")[0]
            assert (_in_any_memory_space(dest)
                    == _in_any_memory_space(entry[operands[0]][0])), typ
        src = operands[0]


def test_lanes_mesh_executables_compile(topo, kernel_routing):
    """Refill + chunk over the four chips of the lanes mesh: kernels in,
    no collective anywhere (the sharded-lanes contract)."""
    mesh = make_serving_mesh(4, devices=topo.devices)
    srv = _server("turbofan", mesh=mesh)
    cap = 4096
    args = _refill_shapes(srv, cap, NamedSharding(mesh, PartitionSpec("lanes")),
                          NamedSharding(mesh, PartitionSpec()))
    refill = srv._refill.lower(*args).compile().as_text()
    chunk = srv._chunk.lower(args[0]).compile().as_text()
    assert "tpu_custom_call" in refill
    for text in (refill, chunk):
        for op in ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute", "reduce-scatter"):
            assert op not in text, op
