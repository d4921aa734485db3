"""Jit wrapper for the grouped expert matmul."""
from __future__ import annotations

import functools

import jax

from .kernel import moe_gmm as _kernel


@functools.partial(jax.jit, static_argnames=("c_block", "f_block",
                                             "d_block", "interpret"))
def moe_gmm(x, w, group_sizes, *, c_block: int = 128, f_block: int = 512,
            d_block: int = 512, interpret: bool | None = None):
    return _kernel(x, w, group_sizes, c_block=c_block, f_block=f_block,
                   d_block=d_block, interpret=interpret)
