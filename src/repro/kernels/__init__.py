"""Pallas TPU kernels for the compute hot spots.

Each kernel package ships ``kernel.py`` (pl.pallas_call + explicit
BlockSpec VMEM tiling), ``ops.py`` (jit'd public wrapper) and ``ref.py``
(pure-jnp oracle).  Every entry point takes ``interpret=None``, which
resolves by backend: the Pallas interpreter on CPU (tests), the Mosaic
compiler everywhere else — on a TPU a kernel is always compiled.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → interpret only when the default backend is the CPU."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
