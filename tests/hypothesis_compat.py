"""Optional-hypothesis shim (requirements-dev.txt).

``from hypothesis_compat import given, settings, st`` gives test modules the
real hypothesis API when installed; otherwise property-based tests collect
as clean skips (pytest.importorskip semantics scoped to the decorated test,
not the whole module) and every plain test keeps running.

CI sets ``REQUIRE_HYPOTHESIS=1``: there the skip path is a hard error, so
the property tests can never silently rot back into permanent skips (they
did exactly that between the dep landing in requirements-dev.txt and CI
actually asserting on it).
"""
from __future__ import annotations

import os

import pytest

try:
    from hypothesis import (  # noqa: F401
        example,
        given,
        settings,
        strategies as st,
    )

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised only without the dev dep
    HAVE_HYPOTHESIS = False
    if os.environ.get("REQUIRE_HYPOTHESIS", "0") == "1":
        raise ImportError(
            "REQUIRE_HYPOTHESIS=1 but hypothesis is not importable — "
            "install requirements-dev.txt (CI must run the property tests, "
            "not skip them)"
        )

    def given(*_a, **_kw):
        def deco(fn):
            def stub():
                pytest.importorskip("hypothesis")

            stub.__name__ = fn.__name__
            stub.__doc__ = fn.__doc__
            return stub

        return deco

    def settings(*_a, **_kw):
        return lambda fn: fn

    def example(*_a, **_kw):
        return lambda fn: fn

    class _AnyStrategy:
        def __getattr__(self, _name):
            return lambda *a, **kw: None

    st = _AnyStrategy()
