"""Jit wrapper: model layout (B,S,H,Dh) ↔ kernel layout (B·H,S,Dh)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import mlstm_chunk as _kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk(q, k, v, i_pre, f_pre, *, chunk: int = 128,
                interpret: bool | None = None):
    """q,k,v (B,S,H,Dh); i/f (B,S,H) → (B,S,H·Dh) f32."""
    B, S, H, Dh = q.shape
    def tok(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    y = _kernel(tok(q), tok(k), tok(v),
                i_pre.transpose(0, 2, 1).reshape(B * H, S),
                f_pre.transpose(0, 2, 1).reshape(B * H, S),
                chunk=chunk, interpret=interpret)
    return y.reshape(B, H, S, Dh).transpose(0, 2, 1, 3).reshape(
        B, S, H * Dh)
