"""Fallback for ``hypothesis`` so the tier-1 suite collects everywhere.

The container image does not ship hypothesis; the property tests only use
``@given`` over ``st.integers`` / ``st.sampled_from`` with
``@settings(max_examples=N, deadline=None)`` and ``@example``.  This shim reproduces that
subset with a seeded PRNG so the tests stay deterministic per run order
and still sweep a spread of examples.  When the real hypothesis is
installed it is used verbatim.

Usage in test modules (tests/ is on sys.path under pytest)::

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        from _hypothesis_compat import given, settings, st
"""

from __future__ import annotations

import functools
import inspect
import itertools
import random
from typing import Any, Callable, Sequence

try:  # pragma: no cover - exercised only when hypothesis is installed
    from hypothesis import example, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    class _Strategy:
        """A draw() closure over a Random instance."""

        def __init__(self, draw: Callable[[random.Random], Any]):
            self._draw = draw

        def draw(self, rng: random.Random) -> Any:
            return self._draw(rng)

    class _Strategies:
        @staticmethod
        def integers(min_value: int, max_value: int) -> _Strategy:
            return _Strategy(lambda rng: rng.randint(min_value, max_value))

        @staticmethod
        def sampled_from(options: Sequence[Any]) -> _Strategy:
            opts = list(options)
            return _Strategy(lambda rng: rng.choice(opts))

        @staticmethod
        def booleans() -> _Strategy:
            return _Strategy(lambda rng: bool(rng.getrandbits(1)))

        @staticmethod
        def floats(min_value: float = 0.0, max_value: float = 1.0,
                   **_ignored) -> _Strategy:
            return _Strategy(lambda rng: rng.uniform(min_value, max_value))

    st = _Strategies()

    def settings(max_examples: int = 20, deadline=None, **_ignored):
        def deco(fn):
            fn._compat_max_examples = max_examples
            return fn
        return deco

    def example(**drawn: Any):
        def deco(fn):
            fn._compat_examples = [drawn] + getattr(fn, "_compat_examples",
                                                    [])
            return fn
        return deco

    def given(**strategies: _Strategy):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                for drawn in getattr(fn, "_compat_examples", []):
                    fn(*args, **drawn, **kwargs)
                n = getattr(fn, "_compat_max_examples", 20)
                # Seed on the test name so each test gets a stable but
                # distinct example stream across runs.
                rng = random.Random(fn.__qualname__)
                for i in itertools.count():
                    if i >= n:
                        break
                    drawn = {name: s.draw(rng)
                             for name, s in strategies.items()}
                    try:
                        fn(*args, **drawn, **kwargs)
                    except Exception as e:
                        raise AssertionError(
                            f"{fn.__name__} failed on example {i}: "
                            f"{drawn!r}") from e
            wrapper._compat_max_examples = getattr(
                fn, "_compat_max_examples", 20)
            # Strip the strategy-supplied parameters from the visible
            # signature (and drop __wrapped__) so pytest doesn't try to
            # inject them as fixtures.
            sig = inspect.signature(fn)
            params = [p for name, p in sig.parameters.items()
                      if name not in strategies]
            wrapper.__signature__ = sig.replace(parameters=params)
            del wrapper.__wrapped__
            return wrapper
        return deco


strategies = st

__all__ = ["example", "given", "settings", "st", "strategies",
           "HAVE_HYPOTHESIS"]
