"""Fallback shim for ``hypothesis`` so property tests skip cleanly.

The container does not ship hypothesis and nothing may be pip-installed.
Test modules import via::

    try:
        from hypothesis import HealthCheck, example, given, settings
        from hypothesis import strategies as st
    except ImportError:
        from hypo_stub import HealthCheck, example, given, settings, st

When the real library is absent, ``@given`` replaces the test with a
zero-argument function that calls ``pytest.skip`` — the deterministic tests
in the same module keep running, and the property tests show up as skipped
instead of breaking collection.
"""
from __future__ import annotations

import pytest


class HealthCheck:
    too_slow = "too_slow"
    filter_too_much = "filter_too_much"
    data_too_large = "data_too_large"


def settings(*_a, **_k):
    def deco(fn):
        return fn
    return deco


def example(*_a, **_k):
    def deco(fn):
        return fn
    return deco


def given(*_a, **_k):
    def deco(fn):
        def skipper():
            pytest.skip("hypothesis not installed")
        skipper.__name__ = fn.__name__
        skipper.__doc__ = fn.__doc__
        return skipper
    return deco


class _Strategies:
    """Any strategy constructor returns an inert placeholder.

    Strategy expressions are evaluated at decoration time (e.g.
    ``@given(st.integers(0, 3))``), so they only need to not raise.
    ``st.composite`` bodies are never executed because ``@given`` skips.
    """

    @staticmethod
    def composite(fn):
        def strategy(*_a, **_k):
            return None
        strategy.__name__ = fn.__name__
        return strategy

    def __getattr__(self, _name):
        return lambda *a, **k: None


st = _Strategies()
