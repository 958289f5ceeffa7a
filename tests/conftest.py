"""Shared test plumbing: degrade hypothesis property tests to skips when
hypothesis is not installed, instead of failing collection of the whole file
(the non-property tests in the same modules still run)."""
import pytest


def hypothesis_or_skip():
    """Return (given, settings, strategies). Without hypothesis, `given`
    replaces the test with a skip and the strategy stubs accept any args."""
    try:
        from hypothesis import given, settings, strategies
        return given, settings, strategies
    except ImportError:
        class _AnyStrategy:
            def __getattr__(self, name):
                return lambda *a, **k: None

        def given(*a, **k):
            def deco(fn):
                @pytest.mark.skip(reason="hypothesis not installed")
                def skipped():
                    pass
                skipped.__name__ = fn.__name__
                return skipped
            return deco

        def settings(*a, **k):
            return lambda fn: fn

        return given, settings, _AnyStrategy()
