"""Host spans of the batched driver and planner, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``. With the
profiler off it is a no-op ``TraceMe``; under ``jax.profiler.trace(dir)``
each ``with span(...)`` block is one host event on the clock the device
operations use, with ``args`` as its stats. The device phases of the fused
step are ``jax.named_scope`` scopes (``core/summa3d.py``,
``core/local_spgemm.py``). The names of both start with ``spgemm.``.
"""
from __future__ import annotations

import jax


def span(name: str, **args):
    """A host span named ``name``; ``args`` are ints or strings."""
    for key, value in args.items():
        if not isinstance(value, (int, str)):
            raise TypeError(f"span {name!r}: {key}={value!r} is neither an "
                            f"int nor a str")
    return jax.profiler.TraceAnnotation(name, **args)
