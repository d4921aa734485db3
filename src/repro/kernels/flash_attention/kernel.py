"""Flash attention Pallas TPU kernel: blockwise online softmax with GQA,
causal and sliding-window masking.

Grid: (batch·kv_heads, q_blocks, kv_blocks) — the last dimension is
sequential ("arbitrary") on TPU, carrying the running (m, l, acc)
statistics in VMEM scratch across kv blocks; batch·heads and q blocks are
parallel across cores.  Block shapes keep the working set
(q_tile + k_tile + v_tile + acc) in VMEM and the matmul dims
MXU-aligned: q/kv tiles default 128·512 with Dh up to 256.

HBM→VMEM movement per (bh, i) pass: q once, full K/V stream once — the
FlashAttention dataflow; nothing quadratic ever leaves VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

NEG = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int | None,
                 q_block: int, kv_block: int):
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (G, qb, Dh)
    k = k_ref[0].astype(jnp.float32)               # (kb, Dh)
    v = v_ref[0].astype(jnp.float32)               # (kb, Dv)

    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # s: (G, qb, kb); mask from global positions
    qpos = i * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0)
    kpos = j * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)
    mask = jnp.ones((q_block, kv_block), jnp.bool_)
    if causal:
        mask = kpos <= qpos
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    s = jnp.where(mask[None], s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    q_block: int = 128, kv_block: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """q (BH, G, Sq, Dh); k (BH, Skv, Dh); v (BH, Skv, Dv) →
    (BH, G, Sq, Dv).  BH = batch × kv_heads, G = query group size."""
    BH, G, Sq, Dh = q.shape
    Skv = k.shape[1]
    Dv = v.shape[2]
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    assert Sq % q_block == 0 and Skv % kv_block == 0
    nq, nk = Sq // q_block, Skv // kv_block
    scale = 1.0 / math.sqrt(Dh)

    kern = functools.partial(_attn_kernel, scale=scale, causal=causal,
                             window=window, q_block=q_block,
                             kv_block=kv_block)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, G, q_block, Dh), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, kv_block, Dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_block, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, q_block, Dv),
                               lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, G, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, q_block), jnp.float32),
            pltpu.VMEM((G, q_block), jnp.float32),
            pltpu.VMEM((G, q_block, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
