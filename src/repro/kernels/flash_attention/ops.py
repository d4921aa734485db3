"""Public jit'd wrapper: model-layout (B,S,H,Dh) ↔ kernel layout."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "q_block", "kv_block",
                                             "interpret"))
def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
        window: int | None = None, q_block: int = 128,
        kv_block: int = 512, interpret: bool | None = None) -> jax.Array:
    """q (B,Sq,H,Dh); k/v (B,Skv,KVH,Dh) with GQA → (B,Sq,H,Dv)."""
    B, Sq, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    Dv = v.shape[-1]
    qk = q.reshape(B, Sq, KVH, G, Dh).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KVH, G, Sq, Dh)
    kk = k.transpose(0, 2, 1, 3).reshape(B * KVH, -1, Dh)
    vk = v.transpose(0, 2, 1, 3).reshape(B * KVH, -1, Dv)
    o = flash_attention(qk, kk, vk, causal=causal, window=window,
                        q_block=q_block, kv_block=kv_block,
                        interpret=interpret)
    return o.reshape(B, KVH, G, Sq, Dv).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sq, H, Dv)
