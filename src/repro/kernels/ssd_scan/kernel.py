"""Mamba selective-scan Pallas TPU kernel (chunked SSD form).

Grid: (batch, d_inner_blocks, chunks) — chunks iterate sequentially
("arbitrary"), carrying the (d_block, N) SSM state in VMEM scratch across
chunk steps; batch and channel blocks are parallel.  Within a chunk the
recurrence runs as a fori_loop entirely in VMEM/VREGs: the HBM traffic is
exactly one read of (x, dt, B, C) and one write of y per token — the
memory-optimal dataflow for the recurrence (it is memory-bound: ~6·N
flops per element against ~8 bytes moved).

Channel blocking keeps the VMEM working set at
chunk·d_block·(2+N/…) ≪ 16 MiB and d_block a lane multiple (128).

Layout for the TPU compiler: the state is carried transposed, hᵀ
(N, d_block), so a time step's x/dt rows broadcast over sublanes and
its B/C entries are (N, 1) columns.  A, B and C arrive transposed from
the wrapper (Aᵀ (N, Din); Bᵀ, Cᵀ (B, N, S)); step ``t`` reads its x/dt
rows from the refs with ``pl.ds`` and picks its B/C columns with a lane
mask, and writes its y row straight into the output block — no value
is sliced at a traced index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, at_ref, bt_ref, ct_ref, y_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    At = at_ref[...].astype(jnp.float32)    # (N, d_block)
    Bt = bt_ref[0].astype(jnp.float32)      # (N, chunk)
    Ct = ct_ref[0].astype(jnp.float32)      # (N, chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, Bt.shape, 1)

    def step(t, h):
        x_t = x_ref[0, pl.ds(t, 1), :].astype(jnp.float32)    # (1, d_blk)
        dt_t = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        sel = lane == t
        b_t = jnp.sum(jnp.where(sel, Bt, 0.0), axis=1, keepdims=True)
        c_t = jnp.sum(jnp.where(sel, Ct, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dt_t * At) * h + b_t * (dt_t * x_t)       # (N, d_blk)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            h * c_t, axis=0, keepdims=True).astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 128, d_block: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """x, dt (B,S,Din); A (Din,N); Bm,Cm (B,S,N) → y (B,S,Din) f32."""
    B, S, Din = x.shape
    N = A.shape[-1]
    chunk = min(chunk, S)
    d_block = min(d_block, Din)
    assert S % chunk == 0 and Din % d_block == 0
    nc, nd = S // chunk, Din // d_block

    kern = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, d_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((N, d_block), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, N, chunk), lambda b, d, c: (b, 0, c)),
            pl.BlockSpec((1, N, chunk), lambda b, d, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block),
                               lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, Din), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, d_block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, dt, A.T, Bm.swapaxes(1, 2), Cm.swapaxes(1, 2))
