"""Deterministic stand-in for the slice of the hypothesis API this suite
uses, for environments where hypothesis is not installed (the container
policy forbids adding deps). Each ``@given`` test runs ``max_examples``
times with examples drawn from a per-example seeded numpy Generator, so
failures are reproducible. Shrinking and the full strategy algebra are
out of scope — only what the tests import: integers, floats, lists,
permutations, composite, given, settings, example.
"""
from __future__ import annotations

import inspect

import numpy as np


class _Strategy:
    def __init__(self, fn):
        self._fn = fn

    def example(self, rng: np.random.Generator):
        return self._fn(rng)


class _Strategies:
    @staticmethod
    def integers(min_value: int, max_value: int) -> _Strategy:
        return _Strategy(
            lambda rng: int(rng.integers(min_value, max_value + 1)))

    @staticmethod
    def floats(min_value: float, max_value: float) -> _Strategy:
        return _Strategy(lambda rng: float(rng.uniform(min_value, max_value)))

    @staticmethod
    def lists(elements: _Strategy, min_size: int = 0,
              max_size: int = 10) -> _Strategy:
        def gen(rng):
            size = int(rng.integers(min_size, max_size + 1))
            return [elements.example(rng) for _ in range(size)]
        return _Strategy(gen)

    @staticmethod
    def sampled_from(values) -> _Strategy:
        vals = list(values)
        return _Strategy(lambda rng: vals[int(rng.integers(len(vals)))])

    @staticmethod
    def permutations(values) -> _Strategy:
        vals = list(values)
        return _Strategy(
            lambda rng: [vals[i] for i in rng.permutation(len(vals))])

    @staticmethod
    def composite(fn):
        def build(*args, **kwargs):
            return _Strategy(
                lambda rng: fn(lambda s: s.example(rng), *args, **kwargs))
        return build


strategies = _Strategies()


def settings(max_examples: int = 20, deadline=None, **_ignored):
    def deco(fn):
        fn._fallback_max_examples = max_examples
        return fn
    return deco


def example(**kwargs):
    """An explicit case, run before the drawn ones (as hypothesis does)."""
    def deco(fn):
        fn._fallback_examples = [kwargs] + getattr(fn, "_fallback_examples",
                                                   [])
        return fn
    return deco


def given(*strats):
    """As hypothesis does, the strategies fill the test's last arguments;
    any before them (``pytest.mark.parametrize``'s) stay the wrapper's own
    and are passed through."""
    def deco(fn):
        params = list(inspect.signature(fn).parameters.values())
        lead, drawn = params[:len(params) - len(strats)], [
            p.name for p in params[len(params) - len(strats):]]

        def run(**lead_kwargs):
            for kwargs in getattr(fn, "_fallback_examples", []):
                fn(**lead_kwargs, **kwargs)
            # read at call time: ``@settings`` is conventionally stacked
            # ABOVE ``@given``, so it decorates (and tags) the wrapper
            # after this closure is built
            n = getattr(run, "_fallback_max_examples",
                        getattr(fn, "_fallback_max_examples", 20))
            for i in range(n):
                rng = np.random.default_rng(i)
                fn(**lead_kwargs,
                   **dict(zip(drawn, [s.example(rng) for s in strats])))

        run.__name__ = fn.__name__
        run.__doc__ = fn.__doc__
        run.__signature__ = inspect.Signature(lead)
        return run
    return deco
