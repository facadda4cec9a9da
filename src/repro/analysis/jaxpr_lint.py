"""Jaxpr linting: RNG discipline, host-sync hazards, dtype drift.

Works on traced (un-lowered) programs, so violations are caught with jax
names still attached — ``random_split`` in a while body is reported as
exactly that, not as an opaque HLO fusion.  The walker recurses through
every nested jaxpr a primitive carries in its params (``while`` cond/body,
``scan``, ``cond`` branches, ``pjit``, custom-derivative wrappers), tracking
whether the current jaxpr executes inside a device loop body.

Checks (each returns a list of :class:`LintFinding`):

* :func:`check_rng` — counter-based RNG discipline.  Inside loop bodies,
  ``random_split`` is forbidden (bootstrap replicate draws must
  ``fold_in`` the per-request iteration counter on a closure key —
  ``executor_fused._executor_core.afc`` — or lane-recycling loses bitwise
  parity with serial replay), and the loop carry must not thread a PRNG
  key (neither a typed ``key<...>`` aval nor a raw u32 key that the body
  re-wraps and re-emits): a threaded key makes a lane's draw depend on how
  many iterations *previous occupants* of the carry ran.
* :func:`check_host_sync` — callback primitives (``pure_callback``,
  ``io_callback``, ``debug_callback``) anywhere in the program: each one is
  a device->host round trip serializing the hot path the fused executor
  exists to avoid.  (The other host-sync hazard — coercing a traced value
  to a Python bool — cannot appear in a jaxpr at all: it raises at trace
  time, and :func:`trace_for_lint` converts that raise into a finding.)
* :func:`check_dtypes` — weak-typed input avals (each one is a retrace
  waiting for a caller that promotes differently — the
  executables-per-bucket killer) and f64 leaks anywhere in the program.

Findings carry the violated contract *field* so the checker can report
"executable X violates contract Y: <message>" without string-matching.
"""
from __future__ import annotations

from dataclasses import dataclass
from collections.abc import Callable, Iterator
from typing import Any

import jax
from jax.extend import core as jax_core

__all__ = [
    "LintFinding",
    "check_dtypes",
    "check_host_sync",
    "check_rng",
    "iter_jaxprs",
    "lint_jaxpr",
    "trace_for_lint",
]

#: Primitives that are a device->host synchronization on every execution.
HOST_CALLBACK_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "debug_print",
     "callback"}
)

#: Loop primitives whose nested jaxprs execute once per iteration.
_LOOP_PRIMITIVES = frozenset({"while", "scan"})


@dataclass(frozen=True)
class LintFinding:
    """One contract violation found by a lint pass.

    ``contract`` names the violated :class:`ExecutableContract` field
    (``"rng"``, ``"collectives"``, ``"donated"``, ``"weak_type_inputs"``,
    ``"allow_f64"``, ``"while_body_flat"``, ``"host_sync"``), ``where`` the
    jaxpr path (e.g. ``"while.body"``) or HLO location, ``message`` the
    actionable description.
    """

    contract: str
    executable: str
    where: str
    message: str

    def __str__(self) -> str:
        return (
            f"[{self.executable}] contract {self.contract!r} violated at "
            f"{self.where}: {self.message}"
        )


def _as_jaxpr(obj: Any) -> Any:
    """Unwrap ClosedJaxpr -> Jaxpr; pass Jaxpr through; else None."""
    if isinstance(obj, jax_core.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, jax_core.Jaxpr):
        return obj
    return None


def iter_jaxprs(
    jaxpr: Any, path: str = "", in_loop: bool = False
) -> Iterator[tuple[str, Any, bool]]:
    """Yield ``(path, jaxpr, in_loop)`` for a jaxpr and every nested jaxpr.

    ``in_loop`` is True when the yielded jaxpr executes inside a device
    loop body (a ``while`` body or ``scan`` body, at any nesting depth).
    ``while`` *cond* jaxprs are visited but not marked as loop bodies —
    they run per trip too, but never mutate carried state, and the RNG
    rules only concern state evolution.
    """
    root = _as_jaxpr(jaxpr)
    if root is None:
        return
    yield path or "<root>", root, in_loop
    for i, eqn in enumerate(root.eqns):
        prim = eqn.primitive.name
        for key, val in eqn.params.items():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for j, sub in enumerate(vals):
                sub_j = _as_jaxpr(sub)
                if sub_j is None:
                    continue
                tag = f"{key}[{j}]" if isinstance(val, (tuple, list)) else key
                sub_path = f"{path}.{prim}:{i}.{tag}" if path else f"{prim}:{i}.{tag}"
                body = in_loop or (
                    prim in _LOOP_PRIMITIVES and "cond" not in key
                )
                yield from iter_jaxprs(sub, sub_path, body)


def _is_key_aval(aval: Any) -> bool:
    """Typed PRNG-key aval (``key<fry>[...]``)?"""
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    try:
        return jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key)
    except TypeError:  # non-jax dtype objects
        return False


def _threaded_raw_key_slots(body_jaxpr: Any, nconsts: int) -> list[int]:
    """Carry slots that smell like a raw (u32) PRNG key threaded per trip.

    A raw key threaded through a while carry shows up as: the carry invar
    feeds ``random_wrap`` (the body consumes it as a key) AND the matching
    outvar is produced by ``random_unwrap`` (the body emits an *evolved*
    key back into the carry).  ``fold_in`` on a closure key never matches:
    its key is a constvar, not a carry slot.
    """
    jx = _as_jaxpr(body_jaxpr)
    if jx is None:
        return []
    wrapped_invars: set[Any] = set()
    unwrap_outvars: set[Any] = set()
    for eqn in jx.eqns:
        if eqn.primitive.name == "random_wrap":
            for v in eqn.invars:
                if isinstance(v, jax_core.Var):
                    wrapped_invars.add(v)
        if eqn.primitive.name == "random_unwrap":
            for v in eqn.outvars:
                unwrap_outvars.add(v)
    slots: list[int] = []
    carry_in = jx.invars[nconsts:]
    for idx, (iv, ov) in enumerate(zip(carry_in, jx.outvars)):
        emitted = isinstance(ov, jax_core.Var) and ov in unwrap_outvars
        if iv in wrapped_invars and emitted:
            slots.append(idx)
    return slots


def _subtree_has_fold_in(jaxpr: Any) -> bool:
    """Does the jaxpr tree contain a ``random_fold_in`` anywhere?"""
    for _, jx, _ in iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name == "random_fold_in":
                return True
    return False


def check_rng(jaxpr: Any, executable: str) -> list[LintFinding]:
    """Counter-based RNG discipline: no split, no key threaded in a carry.

    A loop body may fan a key out with ``random_split`` *provided* the
    body's RNG is rooted in a ``random_fold_in`` (the counter-based
    pattern: ``key = fold_in(base, it)`` then split into a fixed number of
    per-draw subkeys — draws depend only on the iteration index, so a
    recycled lane replays bitwise).  A body that splits with NO fold_in
    anywhere is evolving a key per trip — the parity breaker.  Carried
    keys (typed or raw) are flagged unconditionally.
    """
    findings: list[LintFinding] = []
    loop_bodies_without_fold_in: list[str] = []
    for path, jx, in_loop in iter_jaxprs(jaxpr):
        for i, eqn in enumerate(jx.eqns):
            prim = eqn.primitive.name
            if prim == "random_split" and in_loop and any(
                path == p or path.startswith(f"{p}.")
                for p in loop_bodies_without_fold_in
            ):
                findings.append(LintFinding(
                    contract="rng",
                    executable=executable,
                    where=f"{path}.eqn[{i}]",
                    message=(
                        "jax.random.split inside a loop body whose RNG is "
                        "not rooted in fold_in — per-iteration keys must "
                        "derive from fold_in on the iteration counter "
                        "(counter-based RNG keeps recycled-lane trajectories "
                        "bitwise-reproducible; see executor_fused._executor_core)"
                    ),
                ))
            if prim in _LOOP_PRIMITIVES:
                body = eqn.params.get("body_jaxpr") or eqn.params.get("jaxpr")
                nconsts = int(
                    eqn.params.get("body_nconsts", eqn.params.get("num_consts", 0))
                )
                body_jx = _as_jaxpr(body)
                if body_jx is None:
                    continue
                # parent jaxprs are always visited before their children, so
                # recording the body path here covers the splits inside it
                # (path format must mirror iter_jaxprs)
                base = "" if path == "<root>" else path
                tag = "body_jaxpr" if "body_jaxpr" in eqn.params else "jaxpr"
                body_path = f"{base}.{prim}:{i}.{tag}" if base else f"{prim}:{i}.{tag}"
                if not _subtree_has_fold_in(body):
                    loop_bodies_without_fold_in.append(body_path)
                for slot, iv in enumerate(body_jx.invars[nconsts:]):
                    if _is_key_aval(iv.aval):
                        findings.append(LintFinding(
                            contract="rng",
                            executable=executable,
                            where=f"{path}.eqn[{i}].carry[{slot}]",
                            message=(
                                f"PRNG key {iv.aval} threaded through the "
                                "loop carry — a carried key evolves with the "
                                "trip count, so a recycled lane's draws "
                                "depend on its predecessors; fold_in a "
                                "counter on a closure key instead"
                            ),
                        ))
                for slot in _threaded_raw_key_slots(body, nconsts):
                    findings.append(LintFinding(
                        contract="rng",
                        executable=executable,
                        where=f"{path}.eqn[{i}].carry[{slot}]",
                        message=(
                            "raw u32 PRNG key threaded through the loop "
                            "carry (random_wrap on the carry-in, "
                            "random_unwrap back into the carry-out) — "
                            "fold_in a counter on a closure key instead"
                        ),
                    ))
    return findings


def check_host_sync(jaxpr: Any, executable: str) -> list[LintFinding]:
    """Callback primitives = device->host round trips on the hot path."""
    findings: list[LintFinding] = []
    for path, jx, in_loop in iter_jaxprs(jaxpr):
        for i, eqn in enumerate(jx.eqns):
            if eqn.primitive.name in HOST_CALLBACK_PRIMITIVES:
                where_note = (
                    "inside a loop body — per-iteration"
                    if in_loop else "a per-dispatch"
                )
                findings.append(LintFinding(
                    contract="host_sync",
                    executable=executable,
                    where=f"{path}.eqn[{i}]",
                    message=(
                        f"{eqn.primitive.name} is {where_note} device->host "
                        "round trip; the fused hot path must stay on device "
                        "(move the callback outside the compiled program)"
                    ),
                ))
    return findings


def check_dtypes(
    jaxpr: Any,
    executable: str,
    *,
    allow_weak_inputs: bool = False,
    allow_f64: bool = False,
) -> list[LintFinding]:
    """Weak-typed inputs (retrace hazards) and f64 leaks."""
    findings: list[LintFinding] = []
    root = _as_jaxpr(jaxpr)
    if root is None:
        return findings
    if not allow_weak_inputs:
        for i, v in enumerate(root.invars):
            if getattr(v.aval, "weak_type", False):
                findings.append(LintFinding(
                    contract="weak_type_inputs",
                    executable=executable,
                    where=f"<root>.invars[{i}]",
                    message=(
                        f"input {i} has weak-typed aval {v.aval} — a raw "
                        "Python scalar reached the traced call; pin the "
                        "dtype at the call site (np.float32 / "
                        "jnp.asarray(x, jnp.float32)) or every promotion-"
                        "context change mints a new executable"
                    ),
                ))
    if not allow_f64:
        for path, jx, _ in iter_jaxprs(jaxpr):
            for i, eqn in enumerate(jx.eqns):
                for v in eqn.outvars:
                    dtype = getattr(v.aval, "dtype", None)
                    if dtype is not None and str(dtype) == "float64":
                        findings.append(LintFinding(
                            contract="allow_f64",
                            executable=executable,
                            where=f"{path}.eqn[{i}]",
                            message=(
                                f"{eqn.primitive.name} produces f64 {v.aval} "
                                "— the stack is pinned to f32 with "
                                "compensated accumulation; f64 doubles HBM "
                                "traffic and halves TPU throughput"
                            ),
                        ))
                        break  # one finding per eqn is enough
    return findings


def lint_jaxpr(
    jaxpr: Any,
    executable: str,
    *,
    rng: str = "counter_based",
    allow_weak_inputs: bool = False,
    allow_f64: bool = False,
) -> list[LintFinding]:
    """All jaxpr checks an :class:`ExecutableContract` implies, in one pass."""
    findings: list[LintFinding] = []
    if rng == "counter_based":
        findings += check_rng(jaxpr, executable)
    findings += check_host_sync(jaxpr, executable)
    findings += check_dtypes(
        jaxpr, executable,
        allow_weak_inputs=allow_weak_inputs, allow_f64=allow_f64,
    )
    return findings


def trace_for_lint(
    fn: Callable[..., Any], *args: Any, executable: str = "<fn>"
) -> tuple[Any, list[LintFinding]]:
    """Trace ``fn(*args)`` to a jaxpr, converting trace-time host-sync
    errors (coercing a traced value to a Python bool / implicit
    concretization) into findings instead of raising.

    Returns ``(closed_jaxpr_or_None, findings)`` — a None jaxpr means the
    trace itself failed, and the findings say why.
    """
    try:
        return jax.make_jaxpr(fn)(*args), []
    except jax.errors.TracerBoolConversionError as e:
        return None, [LintFinding(
            contract="host_sync",
            executable=executable,
            where="<trace>",
            message=(
                "traced value coerced to a Python bool — this is a "
                "device->host sync that would abort compilation of the hot "
                f"path (use lax.cond / jnp.where): {e}"
            ),
        )]
    except jax.errors.ConcretizationTypeError as e:
        return None, [LintFinding(
            contract="host_sync",
            executable=executable,
            where="<trace>",
            message=(
                "traced value concretized on the host (implicit "
                f"device-to-host transfer): {e}"
            ),
        )]
