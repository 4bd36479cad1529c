"""Pallas TPU kernels of the served path (attention, recurrences, the
corpus top-k scan).  Each kernel has ``kernel.py`` (the Pallas body),
``ops.py`` (the jitted wrapper) and ``ref.py`` (the pure-jnp oracle)."""

from __future__ import annotations

import jax


def resolve_interpret(interpret=None) -> bool:
    """``interpret=None`` resolves per backend: the Pallas interpreter
    only where the backend is the CPU (no Mosaic lowering exists there),
    the compiled kernel everywhere else.  Explicit values win."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
