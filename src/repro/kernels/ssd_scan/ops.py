"""Jit wrapper for the SSD scan kernel."""
from __future__ import annotations

import functools

import jax

from .kernel import ssd_scan as _kernel


@functools.partial(jax.jit,
                   static_argnames=("chunk", "d_block", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, d_block: int = 128,
             interpret: bool | None = None):
    return _kernel(x, dt, A, Bm, Cm, chunk=chunk, d_block=d_block,
                   interpret=interpret)
