"""Plain reference of a dense decoder LM: ``jax.numpy`` in float32 at
the highest matmul precision, no cache, no batching tricks, no kernels.

It follows the published architecture (pre-norm blocks, RMSNorm or
LayerNorm, rotary on the first ``rot_dim`` features of each head,
grouped-query attention, SwiGLU, tied or separate head), with these
departures, each matched to how the weights are laid out:

* rotary pairs are interleaved features ``(2i, 2i+1)``; the published
  rotate-half form is the same model under a fixed permutation of the
  query and key columns, and the weights are random;
* the training objective is the program's: mean cross-entropy plus
  ``1e-4`` times the mean squared log-partition (z-loss).

``quant="fp8"`` is the control: every matmul operand rounded to
``float8_e4m3fn`` with a per-tensor scale, the rest as above.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from model import Spec

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Z_LOSS = 1e-4
_NEG = -1e30
_FP8_MAX = 448.0


def _q(x, quant):
    """``x`` in f32, or rounded to fp8 with a per-tensor scale; the
    rounding passes gradients straight through, so the backward pass
    multiplies its f32 cotangents by the rounded operands."""
    x = x.astype(F32)
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(quant)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    r = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(r - x)


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HIGHEST)


def _norm(spec: Spec, x, p):
    if spec.norm == "rms":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + spec.eps) * p["scale"].astype(F32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + spec.eps) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def _rope(spec: Spec, x, pos):
    """x (B,S,H,Dh); rotate interleaved pairs of the first rot_dim."""
    r = spec.rot_dim
    if r == 0:
        return x
    inv = 1.0 / (spec.rope_theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = pos[:, :, None].astype(F32) * inv           # (B,S,r/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    a, b = x[..., 0:r:2], x[..., 1:r:2]
    rot = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([rot.reshape(x[..., :r].shape), x[..., r:]], -1)


def _layer(spec: Spec, x, lp, pos, quant):
    B, S, _ = x.shape
    H, KV, Dh = spec.heads, spec.kv_heads, spec.head_dim
    h = _norm(spec, x, lp["norm1"])
    q = _mm("bsd,dhk->bshk", h, lp["mix"]["w_q"], quant)
    kv = _mm("bsd,dghk->bsghk", h, lp["mix"]["w_kv"], quant)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q, k = _rope(spec, q, pos), _rope(spec, k, pos)
    q = q.reshape(B, S, KV, H // KV, Dh)
    s = _mm("bqhgd,bkhd->bhgqk", q, k, quant) / math.sqrt(Dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, _NEG)
    ctx = _mm("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + _mm("bshk,hkd->bsd", ctx.reshape(B, S, H, Dh),
                lp["mix"]["w_o"], quant)
    h = _norm(spec, x, lp["norm2"])
    gu = _mm("bsd,dgf->bsgf", h, lp["ffn"]["w_in"], quant)
    act = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
    return x + _mm("bsf,fd->bsd", act, lp["ffn"]["w_out"], quant)


def hidden(spec: Spec, w: dict, tokens, quant=None):
    """Final normed hidden states (B,S,D) for token ids (B,S)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = _q(w["embed"], quant)[tokens] if quant else \
        w["embed"][tokens].astype(F32)

    def body(x, lp):
        return jax.checkpoint(
            lambda x, lp: _layer(spec, x, lp, pos, quant))(x, lp), None

    x, _ = jax.lax.scan(body, x, w["group0"]["b0"])
    return _norm(spec, x, w["final_norm"])


def head(spec: Spec, w: dict):
    return w["embed"].T if spec.tied else w["head"]


def logits(spec: Spec, w: dict, tokens, quant=None):
    return _mm("bsd,dv->bsv", hidden(spec, w, tokens, quant),
               head(spec, w), quant)


def loss(spec: Spec, w: dict, tokens, labels, quant=None):
    lg = logits(spec, w, tokens, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + Z_LOSS * jnp.mean(lse * lse)


def loss_and_grad(spec: Spec, w: dict, tokens, labels, quant=None,
                  rows: int = 1):
    """Mean loss and its gradient in f32, ``rows`` batch rows at a time
    (equal blocks, so the mean of the block means is the mean)."""
    w32 = jax.tree.map(lambda a: a.astype(F32), w)
    fn = jax.jit(jax.value_and_grad(
        lambda w, t, l: loss(spec, w, t, l, quant)))
    B = tokens.shape[0]
    n = B // rows
    total, grads = 0.0, None
    for i in range(n):
        sl = slice(i * rows, (i + 1) * rows)
        l, g = fn(w32, tokens[sl], labels[sl])
        total = total + l / n
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, jax.tree.map(lambda g: g / n, grads)


def adamw(w, grads, mu, nu, step: int, lr: float, *, b1: float, b2: float,
          eps: float, weight_decay: float, clip: float, store=None):
    """One AdamW step in f32 with global-norm clipping and decoupled
    weight decay on every leaf; ``step`` counts from 1.  ``store`` (a
    tree of dtypes) rounds each new weight to the type the configuration
    keeps it in, as a bf16 model's weights are kept."""
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, clip / (gn + 1e-9))
    g = jax.tree.map(lambda a: a * scale, grads)
    mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
    nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    w = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + weight_decay * p), w, mu, nu)
    if store is not None:
        w = jax.tree.map(lambda p, d: p.astype(d).astype(F32), w, store)
    return w, mu, nu, g
