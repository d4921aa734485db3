"""Grouped expert matmul Pallas TPU kernel.

Computes y[e] = x[e] @ w[e] for the (E, C, D)·(E, D, F) dispatched-expert
batch, with per-expert *valid row counts* (``group_sizes``) so padded
capacity slots cost no MXU work beyond their tile.

Grid: (E, C_blocks, F_blocks, D_blocks) — the contraction (last) dim is
sequential, accumulating into a VMEM f32 scratch tile; (E, C, F) tiles
are parallel.  Block shapes default to the MXU-native 128×128×512 so the
working set (x_tile + w_tile + acc) stays ≪ VMEM and every matmul dim is
lane-aligned.  Rows beyond ``group_sizes[e]`` are masked at the epilogue;
``group_sizes`` is scalar-prefetched into SMEM so the per-expert count is
a scalar read, not a vector load at an unaligned offset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret


def _gmm_kernel(gs_ref, x_ref, w_ref, y_ref, acc_ref, *, c_block: int):
    d_i = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(d_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)        # (c_block, d_block)
    w = w_ref[0].astype(jnp.float32)        # (d_block, f_block)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    e = pl.program_id(0)
    ci = pl.program_id(1)

    @pl.when(d_i == nd - 1)
    def _epilogue():
        n_valid = gs_ref[e]
        row = ci * c_block + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        y_ref[0, ...] = jnp.where(row < n_valid, acc_ref[...],
                                  0).astype(y_ref.dtype)


def moe_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
            c_block: int = 128, f_block: int = 512, d_block: int = 512,
            interpret: bool | None = None) -> jax.Array:
    """x (E, C, D) · w (E, D, F) with valid-row masking → (E, C, F)."""
    E, C, D = x.shape
    F = w.shape[-1]
    c_block = min(c_block, C)
    f_block = min(f_block, F)
    d_block = min(d_block, D)
    assert C % c_block == 0 and F % f_block == 0 and D % d_block == 0

    kern = functools.partial(_gmm_kernel, c_block=c_block)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E, C // c_block, F // f_block, D // d_block),
            in_specs=[
                pl.BlockSpec((1, c_block, d_block),
                             lambda e, c, f, d, gs: (e, c, d)),
                pl.BlockSpec((1, d_block, f_block),
                             lambda e, c, f, d, gs: (e, d, f)),
            ],
            out_specs=pl.BlockSpec((1, c_block, f_block),
                                   lambda e, c, f, d, gs: (e, c, f)),
            scratch_shapes=[pltpu.VMEM((c_block, f_block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(group_sizes.astype(jnp.int32), x, w)
