"""Jit wrapper for fused RMSNorm (flattens leading dims)."""
from __future__ import annotations

import functools

import jax

from .kernel import rmsnorm as _kernel


@functools.partial(jax.jit, static_argnames=("eps", "row_block",
                                             "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, row_block: int = 256,
            interpret: bool | None = None):
    shape = x.shape
    y = _kernel(x.reshape(-1, shape[-1]), scale, eps=eps,
                row_block=min(row_block, max(x.size // shape[-1], 1)),
                interpret=interpret)
    return y.reshape(shape)
