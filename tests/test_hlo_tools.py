"""Edge cases of the HLO text tooling (launch/hlo_cost.py, hlo_stats.py).

The static contract checker (repro.analysis) stands on these parsers, so
the degenerate inputs it can hit — empty modules, modules with no while
loop, multiple whiles (the continuous refill + chunk pair), gather-heavy
incremental-AFC bodies — must behave, not explode.  Synthetic HLO text
pins the parser semantics independent of the installed XLA's exact output;
a few real lowerings cover the integration.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_lint
from repro.launch.hlo_cost import HloCost, analyze_hlo, while_costs
from repro.launch.hlo_stats import collect_collective_stats


# ----------------------------------------------------------- empty module
def test_empty_module_costs_nothing():
    cost = analyze_hlo("", 1)
    assert (cost.flops, cost.bytes, cost.link_bytes) == (0.0, 0.0, 0.0)
    assert while_costs("") == []
    stats = collect_collective_stats("", 1)
    assert stats.per_op_count == {} and stats.link_bytes == 0.0


def test_garbage_module_costs_nothing():
    text = "HloModule nonsense\n\nthis is not hlo at all\n"
    assert analyze_hlo(text, 1) == HloCost()
    assert while_costs(text) == []


# ------------------------------------------------------- module, no while
def test_module_without_while_loop():
    """A straight-line program: while_costs is empty (not an error), and the
    checker's planner probe correctly reports 'no while' as None."""
    w = jnp.ones((8, 4), jnp.float32)
    text = (
        jax.jit(lambda x: x @ w)
        .lower(jax.ShapeDtypeStruct((3, 8), jnp.float32))
        .compile()
        .as_text()
    )
    assert while_costs(text) == []
    assert hlo_lint.planner_body_cost(text) is None
    cost = analyze_hlo(text, 1)
    assert cost.flops > 0 or cost.bytes > 0  # still priced as a program


# ----------------------------------------------- multiple while loops
_TWO_WHILES = """\
HloModule two_whiles

%big_body (pb: (s32[], f32[4096])) -> (s32[], f32[4096]) {
  %pb = (s32[], f32[4096]) parameter(0)
  %ib = s32[] get-tuple-element(%pb), index=0
  %oneb = s32[] constant(1)
  %nib = s32[] add(%ib, %oneb)
  %vb = f32[4096] get-tuple-element(%pb), index=1
  %nvb = f32[4096] copy(%vb)
  ROOT %tb = (s32[], f32[4096]) tuple(%nib, %nvb)
}

%big_cond (pc: (s32[], f32[4096])) -> pred[] {
  %pc = (s32[], f32[4096]) parameter(0)
  %ic = s32[] get-tuple-element(%pc), index=0
  %limc = s32[] constant(7)
  ROOT %cmpc = pred[] compare(%ic, %limc), direction=LT
}

%small_body (ps: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ps = (s32[], f32[8]) parameter(0)
  %is = s32[] get-tuple-element(%ps), index=0
  %ones = s32[] constant(1)
  %nis = s32[] add(%is, %ones)
  %vs = f32[8] get-tuple-element(%ps), index=1
  %nvs = f32[8] copy(%vs)
  ROOT %ts = (s32[], f32[8]) tuple(%nis, %nvs)
}

%small_cond (pd: (s32[], f32[8])) -> pred[] {
  %pd = (s32[], f32[8]) parameter(0)
  %id = s32[] get-tuple-element(%pd), index=0
  %limd = s32[] constant(3)
  ROOT %cmpd = pred[] compare(%id, %limd), direction=LT
}

ENTRY %main (x: f32[4096], y: f32[8]) -> f32[8] {
  %x = f32[4096] parameter(0)
  %y = f32[8] parameter(1)
  %zero = s32[] constant(0)
  %init1 = (s32[], f32[4096]) tuple(%zero, %x)
  %w1 = (s32[], f32[4096]) while(%init1), condition=%big_cond, body=%big_body
  %init2 = (s32[], f32[8]) tuple(%zero, %y)
  %w2 = (s32[], f32[8]) while(%init2), condition=%small_cond, body=%small_body
  ROOT %o = f32[8] get-tuple-element(%w2), index=1
}
"""


def test_multiple_while_loops_each_reported():
    """Refill + chunk shape: two independent whiles, each with its own body
    cost and trip count — and the planner probe picks the expensive one."""
    costs = while_costs(_TWO_WHILES)
    assert len(costs) == 2
    by_body = {c["body"]: c for c in costs}
    assert set(by_body) == {"big_body", "small_body"}
    assert by_body["big_body"]["trips"] == 7
    assert by_body["small_body"]["trips"] == 3
    # per-trip body cost reflects the carried buffer width
    assert by_body["big_body"]["cost"].bytes > 100 * by_body["small_body"]["cost"].bytes
    probe = hlo_lint.planner_body_cost(_TWO_WHILES)
    assert probe is not None
    assert probe.bytes == by_body["big_body"]["cost"].bytes


def test_real_two_while_program_parses():
    """A lowered program with two genuinely separate while loops."""
    def f(x, y):
        x = jax.lax.fori_loop(0, 7, lambda i, v: v * 1.5, x)
        y = jax.lax.fori_loop(0, 3, lambda i, v: v + 1.0, y)
        return x.sum() + y.sum()

    text = (
        jax.jit(f)
        .lower(
            jax.ShapeDtypeStruct((4096,), jnp.float32),
            jax.ShapeDtypeStruct((8,), jnp.float32),
        )
        .compile()
        .as_text()
    )
    costs = while_costs(text)
    # XLA may unroll/fuse the tiny loop away, but the big one must survive
    assert len(costs) >= 1
    assert max(c["trips"] for c in costs) >= 1


# -------------------------------------------- gather-bytes (incremental)
_GATHER = """\
HloModule gather_probe

ENTRY %main (tab: f32[3,4096,4], idx: s32[3,1]) -> f32[3,4] {
  %tab = f32[3,4096,4] parameter(0)
  %idx = s32[3,1] parameter(1)
  ROOT %g = f32[3,4] gather(%tab, %idx), offset_dims={1}
}
"""


def test_gather_charges_addressed_rows_not_the_table():
    """The incremental-AFC promise lives here: an O(1) prefix lookup must
    bill the gathered rows + indices, NOT the (k, 4, cap) table it indexes
    — otherwise every while body would look O(cap) and the flatness
    contract could never hold."""
    cost = analyze_hlo(_GATHER, 1)
    idx_bytes = 3 * 1 * 4
    result_bytes = 3 * 4 * 4
    table_bytes = 3 * 4096 * 4 * 4
    assert cost.bytes == pytest.approx(idx_bytes + 2 * result_bytes)
    assert cost.bytes < table_bytes / 100


def test_incremental_body_gathers_stay_flat_across_cap():
    """Integration: the real incremental executor's while body is priced
    cap-independent (the contract checker's flatness probe in miniature)."""
    from repro.core.executor_fused import build_fused_executor

    def body_bytes(cap):
        w = jnp.asarray([1.0, -2.0, 0.5])
        fused = build_fused_executor(
            lambda rows, exact: rows @ w,
            k=3, task="regression", m=16, m_sobol=8, max_iters=8, n_boot=16,
            afc_backend="incremental",
        )
        args = (
            jax.ShapeDtypeStruct((3, cap), jnp.float32),
            jax.ShapeDtypeStruct((3,), jnp.int32),
            jax.ShapeDtypeStruct((3,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((0,), jnp.float32),
        )
        text = jax.jit(fused).lower(*args).compile().as_text()
        probe = hlo_lint.planner_body_cost(text)
        assert probe is not None
        return probe.bytes

    small, big = body_bytes(1024), body_bytes(8192)
    assert big <= 1.3 * small


# --------------------------------------------------- collective stats
_COLLECTIVE = """\
HloModule coll

ENTRY %main (x: f32[1024]) -> f32[1024] {
  %x = f32[1024] parameter(0)
  ROOT %ar = f32[1024] all-reduce(%x), replica_groups=[2,4], to_apply=%sum
}
"""


def test_collective_stats_ring_weighting():
    stats = collect_collective_stats(_COLLECTIVE, 8)
    assert stats.per_op_count == {"all-reduce": 1}
    buf = 1024 * 4
    assert stats.per_op_bytes["all-reduce"] == pytest.approx(buf)
    # iota groups [2,4]: group size 4 -> ring all-reduce 2*(g-1)/g
    assert stats.link_bytes == pytest.approx(2.0 * 3 / 4 * buf)


def test_collective_stats_ignore_non_collective_lines():
    text = (
        "HloModule none\n\nENTRY %m (x: f32[64]) -> f32[64] {\n"
        "  %x = f32[64] parameter(0)\n"
        "  ROOT %y = f32[64] add(%x, %x)\n}\n"
    )
    stats = collect_collective_stats(text, 4)
    assert stats.per_op_count == {}
    assert stats.link_bytes == 0.0


def test_empty_group_defaults_to_n_devices():
    text = (
        "HloModule d\n\nENTRY %m (x: f32[256]) -> f32[256] {\n"
        "  %x = f32[256] parameter(0)\n"
        "  ROOT %ag = f32[256] all-gather(%x), dimensions={0}\n}\n"
    )
    stats = collect_collective_stats(text, 8)
    buf = 256 * 4
    # no replica_groups annotation: group size falls back to n_devices
    assert stats.link_bytes == pytest.approx((8 - 1) / 8 * buf)
