"""Fused RMSNorm Pallas TPU kernel: one HBM read + one write per element
(the unfused graph reads x three times: square-mean, normalise, scale).

Grid: (row_blocks,); each step loads a (row_block, D) tile into VMEM,
reduces within registers, normalises and scales in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret


def _rms_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            row_block: int = 256,
            interpret: bool | None = None) -> jax.Array:
    """x (R, D), scale (D,) → (R, D)."""
    R, D = x.shape
    row_block = min(row_block, R)
    assert R % row_block == 0
    kern = functools.partial(_rms_kernel, eps=eps)
    return pl.pallas_call(
        kern,
        grid=(R // row_block,),
        in_specs=[pl.BlockSpec((row_block, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((row_block, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
    )(x, scale)
