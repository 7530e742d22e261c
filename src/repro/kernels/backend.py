"""Where the Pallas kernels run: compiled by Mosaic on a TPU, in interpret
mode on any other backend.

Every kernel entry point resolves ``interpret=None`` here when it is called,
so the decision is made once, in one place, at call time. Nothing here runs
at import: asking for the backend initializes it.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret=None) -> bool:
    """An explicit ``interpret`` wins; ``None`` means interpret unless the
    default backend is a TPU (Mosaic lowers only there)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
