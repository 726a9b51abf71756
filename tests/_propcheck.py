"""Minimal stand-in for `hypothesis` on containers where it isn't installed.

The real library is used when available (import these names via::

    try:
        import hypothesis
        import hypothesis.strategies as st
    except ImportError:
        from _propcheck import hypothesis, st

); otherwise this module provides a deterministic mini property-runner with
the same decorator surface (``given`` / ``settings``) and the few strategies
the test-suite uses (``integers``, ``floats``, ``booleans``,
``sampled_from``).  Each test runs ``max_examples`` samples drawn from a
seeded RNG, always including the strategy endpoints first so boundary cases
are exercised on every run.
"""
from __future__ import annotations

import functools
import inspect
import zlib

import numpy as np


class _Strategy:
    def __init__(self, draw, endpoints=()):
        self.draw = draw
        self.endpoints = tuple(endpoints)


def _integers(lo: int, hi: int) -> _Strategy:
    return _Strategy(lambda rng: int(rng.integers(lo, hi + 1)),
                     endpoints=(lo, hi))


def _floats(lo: float, hi: float, **_kw) -> _Strategy:
    return _Strategy(lambda rng: float(rng.uniform(lo, hi)),
                     endpoints=(lo, hi))


def _booleans() -> _Strategy:
    return _Strategy(lambda rng: bool(rng.integers(0, 2)),
                     endpoints=(False, True))


def _sampled_from(seq) -> _Strategy:
    seq = list(seq)
    return _Strategy(lambda rng: seq[int(rng.integers(len(seq)))],
                     endpoints=seq[:2])


class _StrategiesModule:
    integers = staticmethod(_integers)
    floats = staticmethod(_floats)
    booleans = staticmethod(_booleans)
    sampled_from = staticmethod(_sampled_from)


st = _StrategiesModule()


class _HypothesisModule:
    @staticmethod
    def settings(max_examples: int = 10, **_kw):
        def deco(fn):
            fn._propcheck_max_examples = max_examples
            return fn
        return deco

    @staticmethod
    def given(*strategies: _Strategy):
        def deco(fn):
            # as in hypothesis, the strategies fill the rightmost
            # parameters; the rest stay visible to pytest (parametrize)
            sig = inspect.signature(fn)
            names = list(sig.parameters)
            kept, drawn = (names[:len(names) - len(strategies)],
                           names[len(names) - len(strategies):])

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                # settings() may sit above given() (decorating wrapper)
                # or below it (decorating fn) — honor both orders
                n = getattr(wrapper, "_propcheck_max_examples",
                            getattr(fn, "_propcheck_max_examples", 10))
                # crc32, not hash(): PYTHONHASHSEED randomizes the latter
                # per process, which would make failures irreproducible
                rng = np.random.default_rng(
                    zlib.crc32(fn.__name__.encode()))
                # endpoint combinations first (diagonal), then random draws
                n_ep = max((len(s.endpoints) for s in strategies), default=0)
                cases = []
                for i in range(n_ep):
                    cases.append(tuple(
                        s.endpoints[min(i, len(s.endpoints) - 1)]
                        for s in strategies))
                while len(cases) < n:
                    cases.append(tuple(s.draw(rng) for s in strategies))
                for case in cases[:max(n, n_ep)]:
                    fn(*args, **kwargs, **dict(zip(drawn, case)))
            # pytest follows __wrapped__ when introspecting the signature
            # and would treat the drawn parameters as fixtures
            del wrapper.__wrapped__
            wrapper.__signature__ = sig.replace(
                parameters=[sig.parameters[k] for k in kept])
            return wrapper
        return deco


hypothesis = _HypothesisModule()
