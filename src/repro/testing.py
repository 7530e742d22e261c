"""Property-testing helpers with a graceful ``hypothesis`` fallback.

Test modules import ``given``/``settings``/``strategies`` from here. When the
real ``hypothesis`` package is installed it is re-exported unchanged; when it
is missing (minimal containers) a deterministic random-sampling stand-in runs
each property ``max_examples`` times with values drawn from a seeded
``numpy.random.Generator``. The fallback covers only the strategy surface the
repo uses (``integers``, ``floats``, ``booleans``, ``sampled_from``) — it does
*not* shrink failures, so keep real hypothesis installed where possible.
"""
from __future__ import annotations

try:  # pragma: no cover - exercised only when hypothesis is installed
    from hypothesis import given, settings, strategies  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    import functools
    import inspect
    import zlib

    import numpy as np

    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, draw_fn):
            self._draw_fn = draw_fn

        def draw(self, rng):
            return self._draw_fn(rng)

    class strategies:  # type: ignore[no-redef]
        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(
                lambda rng: int(rng.integers(min_value, max_value + 1))
            )

        @staticmethod
        def floats(min_value, max_value):
            return _Strategy(lambda rng: float(rng.uniform(min_value, max_value)))

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: bool(rng.integers(0, 2)))

        @staticmethod
        def sampled_from(elements):
            elements = list(elements)
            return _Strategy(
                lambda rng: elements[int(rng.integers(0, len(elements)))]
            )

    def settings(max_examples: int = 20, deadline=None, **_ignored):
        """Record max_examples on the (already-@given-wrapped) function."""

        def deco(fn):
            fn._max_examples = max_examples
            return fn

        return deco

    def given(**strats):
        """Run the test once per drawn example (seeded by the test name)."""

        def deco(fn):
            @functools.wraps(fn)
            def runner(*args, **kwargs):
                n = getattr(runner, "_max_examples", 20)
                seed = zlib.crc32(fn.__qualname__.encode())
                rng = np.random.default_rng(seed)
                for _ in range(n):
                    drawn = {k: s.draw(rng) for k, s in strats.items()}
                    fn(*args, **drawn, **kwargs)

            # pytest must not see the property parameters as fixtures; it
            # keeps seeing the rest (fixtures, ``parametrize`` arguments)
            sig = inspect.signature(fn)
            runner.__signature__ = sig.replace(parameters=[
                p for name, p in sig.parameters.items() if name not in strats
            ])
            runner.__wrapped__ = None
            del runner.__wrapped__
            return runner

        return deco
