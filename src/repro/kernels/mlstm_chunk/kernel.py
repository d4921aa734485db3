"""xLSTM mLSTM chunkwise Pallas TPU kernel.

Grid: (batch·heads, chunks) with the chunk dimension sequential,
carrying the (Dh, Dh) matrix memory C, the normaliser n (Dh,), and the
stabiliser m (scalar) in VMEM scratch.  Per chunk:

* intra-chunk: the (L, L) decay-masked qkᵀ quadratic — two MXU matmuls,
* inter-chunk: q reads the carried matrix memory with cumulative decay,
* state update: rank-L update of C with per-step forget products.

The stabilised exponential gating (max-subtraction) follows the xLSTM
paper's log-space formulation so f32 accumulation never overflows.

Layout for the TPU compiler: the gates arrive as (BH, 1, S) so a chunk's
gate block (1, 1, L) is a legal tile, and read as (1, L) rows.  The
inclusive cumulative log-forget sum is a lower-triangular matmul, taken
in both orientations ((1, L) row and (L, 1) column) so no value needs a
transpose; the input gate's column form is a product with the identity.
Every product runs at ``Precision.HIGHEST``: Mosaic's default takes one
bf16 pass, which the f32 decay-weighted scores do not survive (measured
on a v5e: max error 1.56 against the oracle at xlstm-125m widths), and
it makes the cumulative sums exact sums of the f32 gate values.  The
normaliser is carried as a (1, Dh) row and the stabiliser as a (1, 1)
tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

NEG = -1e30
_NT = (((1,), (1,)), ((), ()))     # contract the last dims: a @ bᵀ
_NN = (((1,), (0,)), ((), ()))     # a @ b
_HI = jax.lax.Precision.HIGHEST


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, y_ref,
                  c_ref, n_ref, m_ref, *, dh: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    q = q_ref[0].astype(jnp.float32)                 # (L, Dh)
    k = k_ref[0].astype(jnp.float32) / (dh ** 0.5)   # xLSTM: scale k only
    v = v_ref[0].astype(jnp.float32)
    i_row = i_ref[0].astype(jnp.float32)             # (1, L)
    logf_row = jax.nn.log_sigmoid(f_ref[0].astype(jnp.float32))

    L = q.shape[0]
    tpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    spos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = spos <= tpos
    tril = causal.astype(jnp.float32)
    eye = (spos == tpos).astype(jnp.float32)
    # F_t = sum_{s<=t} logf_s (inclusive), as a column and as a row
    F_col = jax.lax.dot_general(tril, logf_row, _NT, precision=_HI)
    F_row = jax.lax.dot_general(logf_row, tril, _NT, precision=_HI)
    i_col = jax.lax.dot_general(eye, i_row, _NT, precision=_HI)

    m_prev = m_ref[...]                              # (1, 1)
    # Stabiliser candidates: inter-chunk (m_prev + F_t) vs intra (D row max)
    # D[t,s] = F_t - F_s + i_s for s<=t
    dmat = jnp.where(causal, F_col - F_row + i_row, NEG)
    m_intra = jnp.max(dmat, axis=1, keepdims=True)   # (L, 1)
    m_t = jnp.maximum(m_prev + F_col, m_intra)

    inter_decay = jnp.exp(m_prev + F_col - m_t)      # (L, 1)
    dexp = jnp.exp(dmat - m_t)                       # (L, L)

    scores = jax.lax.dot_general(q, k, _NT, precision=_HI,
                                 preferred_element_type=jnp.float32)
    w = scores * dexp
    y_intra = jax.lax.dot_general(w, v, _NN, precision=_HI,
                                  preferred_element_type=jnp.float32)
    y_inter = jax.lax.dot_general(q, c_ref[...], _NN, precision=_HI,
                                  preferred_element_type=jnp.float32)
    num = y_intra + y_inter * inter_decay
    n_inter = jnp.sum(q * n_ref[...], axis=1, keepdims=True) * inter_decay
    denom = jnp.sum(w, axis=1, keepdims=True) + n_inter
    denom = jnp.maximum(jnp.abs(denom), jnp.exp(-m_t)) + 1e-6
    y_ref[0, ...] = (num / denom).astype(y_ref.dtype)

    # ---- state update to end of chunk --------------------------------------
    m_new = m_t[L - 1:, :]                           # (1, 1)
    F_last = F_col[L - 1:, :]
    # contribution of each step s: exp(F_last - F_s + i_s - m_new)
    ku = k * jnp.exp(F_last - F_col + i_col - m_new)  # (L, Dh)
    decay_all = jnp.exp(m_prev + F_last - m_new)
    c_ref[...] = decay_all * c_ref[...] + jax.lax.dot_general(
        ku, v, (((0,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    n_ref[...] = decay_all * n_ref[...] + jnp.sum(ku, axis=0, keepdims=True)
    m_ref[...] = m_new


def mlstm_chunk(q, k, v, i_pre, f_pre, *, chunk: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """q,k,v (BH, S, Dh); i_pre,f_pre (BH, S) → y (BH, S, Dh) f32."""
    BH, S, Dh = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    kern = functools.partial(_mlstm_kernel, dh=Dh)
    return pl.pallas_call(
        kern,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, Dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, Dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, Dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, chunk, Dh), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((Dh, Dh), jnp.float32),
            pltpu.VMEM((1, Dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v, i_pre[:, None, :], f_pre[:, None, :])
