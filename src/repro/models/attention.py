"""Attention family: GQA (with causal / sliding-window masks), cross
attention (VLM image layers), and DeepSeek-style MLA with the absorbed
decode form over the latent KV cache.

All functions take a ``constrain`` callable — the ShardingPlan's buffer
sites — so the HIDA plan, not the model, owns layout decisions.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import BF16, F32, ParamBuilder, apply_rope, rope_angles

Constrain = Callable[..., jax.Array]
_NEG = -1e30


class KVCache(NamedTuple):
    k: jax.Array          # (B, S_max, KVH, Dh)  or MLA: (B, S_max, kv_lora+rope)
    v: Optional[jax.Array]
    #: tokens already cached.  Scalar int32 for lock-step decode (all
    #: batch rows at one position — the training/smoke path), or a
    #: per-slot ``(B,)`` int32 vector for the continuous-batching server,
    #: where every slot advances independently.  The rank is static under
    #: jit, so the two layouts trace to different (cached) programs.
    pos: jax.Array


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def init_gqa(pb: ParamBuilder, path: str, cfg: ArchConfig,
             stack: int | None = None) -> None:
    D, H, KV, Dh = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim)
    pb.weight(f"{path}/w_q", (D, H, Dh), ("d_model", "heads", "d_head"),
              stack=stack)
    pb.weight(f"{path}/w_kv", (D, 2, KV, Dh),
              ("d_model", "two", "kv_heads", "d_head"), stack=stack)
    pb.weight(f"{path}/w_o", (H, Dh, D), ("heads", "d_head", "d_model"),
              stack=stack)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array | None,
          ) -> jax.Array:
    """q (B,Sq,H,Dh), k/v (B,Skv,KVH,Dh) with GQA head grouping."""
    B, Sq, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, Dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(F32)
    scores = scores / math.sqrt(Dh)
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(q.dtype), v)
    return ctx.reshape(B, Sq, H, v.shape[-1])


#: switch to the memory-linear chunked path above this many score elements
#: (the materialised (B,H,Sq,Skv) f32 tensor is what blows HBM otherwise)
_FLASH_THRESHOLD = 1 << 21
_Q_BLOCK = 256
_KV_BLOCK = 1024


def flash_attention_jnp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int | None = None,
                        q_block: int = _Q_BLOCK,
                        kv_block: int = _KV_BLOCK,
                        scale: float | None = None) -> jax.Array:
    """Online-softmax chunked attention (FlashAttention dataflow in pure
    jnp): O(Sq·Dh) memory instead of O(Sq·Skv).  Doubles as the oracle for
    the Pallas TPU kernel.  GQA grouping handled natively.

    Both scan bodies are rematerialised so the backward pass never holds
    more than one (q_block × kv_block) probability tile per head group.
    """
    B, Sq, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    Skv = k.shape[1]
    Dv = v.shape[-1]          # MLA: value dim ≠ qk dim
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    nq, nk = Sq // q_block, Skv // kv_block
    if Sq % q_block or Skv % kv_block:
        return _sdpa(q, k, v,
                     causal_mask(Sq, Skv, window) if causal else None)
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)

    qb = q.reshape(B, nq, q_block, KVH, G, Dh).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(B, nk, kv_block, KVH, Dh).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nk, kv_block, KVH, Dv).transpose(1, 0, 3, 2, 4)

    def q_body(_, inputs):
        qblk, qi = inputs

        # qblk is closed over, NOT carried: carrying it through the kv
        # scan makes the backward save a copy per kv iteration (measured:
        # tens of GiB of stacked q tiles on the 128-head MLA configs).
        def kv_body(carry, kv_inputs):
            m, l, acc = carry
            kblk, vblk, ki = kv_inputs
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk.astype(F32),
                           kblk.astype(F32)) * scale
            qpos = qi * q_block + jnp.arange(q_block)
            kpos = ki * kv_block + jnp.arange(kv_block)
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None, None], s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vblk.astype(F32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, KVH, G, q_block), -jnp.inf, F32)
        l0 = jnp.zeros((B, KVH, G, q_block), F32)
        a0 = jnp.zeros((B, KVH, G, q_block, Dv), F32)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_body), (m0, l0, a0),
            (kb, vb, jnp.arange(nk)))
        y = acc / jnp.maximum(l, 1e-30)[..., None]
        # Stack per-block outputs in the storage dtype: the f32 stacked
        # ys of a 128-head MLA layer is 3 GiB/device otherwise.
        return None, y.astype(q.dtype)

    _, ys = jax.lax.scan(jax.checkpoint(q_body), None,
                         (qb, jnp.arange(nq)))
    # ys: (nq, B, KVH, G, q_block, Dv)
    out = ys.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def causal_mask(Sq: int, Skv: int, window: int | None = None,
                q_offset: int = 0) -> jax.Array:
    """(1,1,1,Sq,Skv) boolean mask; ``window`` adds the SWA band."""
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Skv)[None, :]
    m = kpos <= qpos
    if window is not None:
        m = m & (kpos > qpos - window)
    return m[None, None, None]


def decode_mask(Skv: int, pos: jax.Array, window: int | None = None
                ) -> jax.Array:
    """Single-token decode mask at position ``pos``: ``(1,1,1,1,Skv)``
    for scalar ``pos``, ``(B,1,1,1,Skv)`` for per-slot ``(B,)`` ``pos``
    (each slot attends only to its own prefix, so stale cache rows from
    a previous slot occupant are masked to exact-zero probability)."""
    kpos = jnp.arange(Skv)
    if pos.ndim:
        m = kpos[None, :] <= pos[:, None]
        if window is not None:
            m = m & (kpos[None, :] > pos[:, None] - window)
        return m[:, None, None, None, :]
    m = kpos <= pos
    if window is not None:
        m = m & (kpos > pos - window)
    return m[None, None, None, None, :]


def gqa_attention(x: jax.Array, p: dict, cfg: ArchConfig,
                  positions: jax.Array, constrain: Constrain,
                  cache: KVCache | None = None,
                  kv_x: jax.Array | None = None,
                  causal: bool = True,
                  use_kernels: bool = False,
                  return_kv: bool = False,
                  ) -> tuple[jax.Array, KVCache | None]:
    """Self- or cross-attention.  ``cache`` implies single-step decode;
    ``kv_x`` switches to cross-attention over a context stream.
    ``return_kv`` (no ``cache``) also returns the post-rotary ``k``/``v``
    it attended over, ``KVCache(k, v, None)``: the one-pass prefill's
    cache rows."""
    Dh = cfg.resolved_head_dim
    rot_dim = int(Dh * cfg.rope_pct) & ~1

    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    src = kv_x if kv_x is not None else x
    kv = jnp.einsum("bsd,dghk->bsghk", src, p["w_kv"])
    k, v = kv[:, :, 0], kv[:, :, 1]
    q = constrain(q, ("batch", "seq", "heads", "d_head"), "q")
    k = constrain(k, ("batch", "kv_seq", "kv_heads", "d_head"), "k")
    v = constrain(v, ("batch", "kv_seq", "kv_heads", "d_head"), "v")

    if kv_x is None and rot_dim > 0:
        cos, sin = rope_angles(positions, rot_dim)
        q = apply_rope(q, cos, sin, rot_dim)
        kv_pos = positions if cache is None else positions
        kcos, ksin = (cos, sin)
        k = apply_rope(k, kcos, ksin, rot_dim)

    new_cache = None
    if cache is not None:
        if cache.pos.ndim:
            # Per-slot decode (continuous batching): each row scatters
            # its single new token at its own position.  S must be 1.
            rows = jnp.arange(x.shape[0])
            k_all = cache.k.at[rows, cache.pos].set(k[:, 0])
            v_all = cache.v.at[rows, cache.pos].set(v[:, 0])
        else:
            k_all = jax.lax.dynamic_update_slice(
                cache.k, k, (0, cache.pos, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                cache.v, v, (0, cache.pos, 0, 0))
        new_cache = KVCache(k_all, v_all, cache.pos + x.shape[1])
        mask = decode_mask(k_all.shape[1], cache.pos, cfg.attn_window)
        ctx = _sdpa(q, k_all, v_all, mask)
    else:
        is_causal = causal and kv_x is None
        if use_kernels:
            from ..kernels.flash_attention import ops as fa_ops
            ctx = fa_ops.mha(q, k, v, causal=is_causal,
                             window=cfg.attn_window,
                             q_block=min(128, q.shape[1]),
                             kv_block=min(128, k.shape[1]))
        elif q.shape[1] * k.shape[1] > _FLASH_THRESHOLD:
            ctx = flash_attention_jnp(q, k, v, causal=is_causal,
                                      window=cfg.attn_window)
        else:
            mask = (causal_mask(x.shape[1], k.shape[1], cfg.attn_window)
                    if is_causal else None)
            ctx = _sdpa(q, k, v, mask)
        if return_kv:
            new_cache = KVCache(k, v, None)

    ctx = constrain(ctx, ("batch", "seq", "heads", "d_head"), "attn_ctx")
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["w_o"])
    return out, new_cache


# --------------------------------------------------------------------------
# MLA (DeepSeek V2/V3)
# --------------------------------------------------------------------------

def init_mla(pb: ParamBuilder, path: str, cfg: ArchConfig,
             stack: int | None = None) -> None:
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    pb.weight(f"{path}/w_q_a", (D, m.q_lora), ("d_model", "q_lora"),
              stack=stack)
    pb.weight(f"{path}/w_q_b", (m.q_lora, H, m.nope_dim + m.rope_dim),
              ("q_lora", "heads", "d_head"), stack=stack)
    pb.weight(f"{path}/w_kv_a", (D, m.kv_lora + m.rope_dim),
              ("d_model", "kv_lora"), stack=stack)
    pb.weight(f"{path}/w_uk", (H, m.kv_lora, m.nope_dim),
              ("heads", "kv_lora", "d_head"), stack=stack)
    pb.weight(f"{path}/w_uv", (H, m.kv_lora, m.v_dim),
              ("heads", "kv_lora", "d_head"), stack=stack)
    pb.weight(f"{path}/w_o", (H, m.v_dim, D),
              ("heads", "d_head", "d_model"), stack=stack)


def mla_attention(x: jax.Array, p: dict, cfg: ArchConfig,
                  positions: jax.Array, constrain: Constrain,
                  cache: KVCache | None = None,
                  ) -> tuple[jax.Array, KVCache | None]:
    """MLA with the latent cache: prefill/train uses the materialised
    per-head K/V; decode uses the *absorbed* form (queries projected into
    latent space so the cache stays (kv_lora+rope) per token)."""
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads

    qa = jnp.einsum("bsd,dr->bsr", x, p["w_q_a"])
    q = jnp.einsum("bsr,rhk->bshk", qa, p["w_q_b"])
    q_nope, q_pe = q[..., :m.nope_dim], q[..., m.nope_dim:]
    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_kv_a"])
    q_nope = constrain(q_nope, ("batch", "seq", "heads", "d_head"), "q")
    ckv_full = constrain(ckv_full, ("batch", "kv_seq", "kv_lora"), "c_kv")

    cos, sin = rope_angles(positions, m.rope_dim)
    q_pe = apply_rope(q_pe, cos, sin, m.rope_dim)
    k_pe = apply_rope(ckv_full[:, :, None, m.kv_lora:], cos, sin,
                      m.rope_dim)[:, :, 0]
    ckv = jnp.concatenate([ckv_full[..., :m.kv_lora], k_pe], axis=-1)

    new_cache = None
    if cache is not None:
        if cache.pos.ndim:
            lat = cache.k.at[jnp.arange(B), cache.pos].set(ckv[:, 0])
        else:
            lat = jax.lax.dynamic_update_slice(cache.k, ckv,
                                               (0, cache.pos, 0))
        new_cache = KVCache(lat, None, cache.pos + S)
        c_nope, c_pe = lat[..., :m.kv_lora], lat[..., m.kv_lora:]
        # Absorbed: q_lat[h] = q_nope[h] @ W_uk[h]  (B,S,H,kv_lora).
        # f32 accumulation throughout so the absorbed and materialised
        # forms agree (MXU accumulates f32 natively).
        q_lat = jnp.einsum("bshk,hrk->bshr", q_nope, p["w_uk"],
                           preferred_element_type=F32)
        scores = (jnp.einsum("bshr,btr->bhst", q_lat,
                             c_nope.astype(F32))
                  + jnp.einsum("bshk,btk->bhst", q_pe, c_pe,
                               preferred_element_type=F32))
        scores = scores / math.sqrt(m.nope_dim + m.rope_dim)
        kpos = jnp.arange(lat.shape[1])[None, None, None, :]
        cpos = (cache.pos[:, None, None, None] if cache.pos.ndim
                else cache.pos)
        scores = jnp.where(kpos <= cpos, scores, _NEG)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx_lat = jnp.einsum("bhst,btr->bshr", probs,
                             c_nope.astype(F32))
        ctx = jnp.einsum("bshr,hrv->bshv", ctx_lat,
                         p["w_uv"].astype(F32)).astype(x.dtype)
    else:
        # NOTE (§Perf P3.5, refuted & reverted): running the *absorbed*
        # form here (flash over the shared latent cache, KVH=1, Dqk=640)
        # measured WORSE — 106→184 GiB/dev, coll 190→412 GiB — because
        # per-head latent queries (H·640) + latent contexts (H·512)
        # outweigh the per-head k/v (H·320) they replace.  The
        # materialised per-head flash below is the better training form.
        k_nope = jnp.einsum("bsr,hrk->bshk", ckv[..., :m.kv_lora],
                            p["w_uk"], preferred_element_type=F32)
        v = jnp.einsum("bsr,hrv->bshv", ckv[..., :m.kv_lora],
                       p["w_uv"], preferred_element_type=F32)
        if S * S > _FLASH_THRESHOLD:
            # Concat the nope/rope halves into one effective q/k — MLA
            # reduces to standard attention with Dv ≠ Dqk, which the
            # chunked path supports.
            BF = x.dtype
            q_eff = jnp.concatenate([q_nope.astype(BF),
                                     q_pe.astype(BF)], axis=-1)
            k_pe_h = jnp.broadcast_to(
                ckv[:, :, None, m.kv_lora:],
                (B, S, H, m.rope_dim)).astype(BF)
            k_eff = jnp.concatenate([k_nope.astype(BF), k_pe_h], axis=-1)
            ctx = flash_attention_jnp(q_eff, k_eff, v.astype(BF),
                                      causal=True)
        else:
            scores = (jnp.einsum("bshk,bthk->bhst", q_nope.astype(F32),
                                 k_nope)
                      + jnp.einsum("bshk,btk->bhst", q_pe,
                                   ckv[..., m.kv_lora:],
                                   preferred_element_type=F32))
            scores = scores / math.sqrt(m.nope_dim + m.rope_dim)
            qpos = jnp.arange(S)[:, None]
            tpos = jnp.arange(S)[None, :]
            scores = jnp.where((tpos <= qpos)[None, None], scores, _NEG)
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bhst,bthv->bshv", probs, v).astype(x.dtype)

    ctx = constrain(ctx, ("batch", "seq", "heads", "d_head"), "attn_ctx")
    out = jnp.einsum("bshv,hvd->bsd", ctx, p["w_o"])
    return out, new_cache
