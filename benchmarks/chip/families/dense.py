"""The dense decoder family: pre-norm blocks of grouped-query attention
and a SwiGLU feed-forward, RMSNorm or LayerNorm, rotary on the first
``rot_dim`` features of each head, a tied or separate head, the layers
one scanned stack.  What the yardstick knows of the family: the sizes
it reads from a configuration file (:class:`Spec`), which field of the
program's ``ArchConfig`` holds each (:data:`FIELDS`), the weights'
layout, and the plain reference.

The reference is ``jax.numpy`` in float32 at the highest matmul
precision, no cache, no batching tricks, no kernels, with these
departures, each matched to how the weights are laid out:

* rotary pairs are interleaved features ``(2i, 2i+1)``; the published
  rotate-half form is the same model under a fixed permutation of the
  query and key columns, and the weights are random;
* the training objective is the program's: mean cross-entropy plus
  ``1e-4`` times the mean squared log-partition (z-loss).

``quant="fp8"`` is the control (:func:`reference._q`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import reference
from reference import F32, _mm, _q
from weights import LOGIT_STD

BF16 = jnp.bfloat16
_NEG = -1e30

#: A published key the family reads, and the ``ArchConfig`` field that
#: holds it.  A key a configuration's ``reduced`` names must be here.
FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads", "head_dim": "head_dim",
          "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
          "num_hidden_layers": "n_layers"}


@dataclass(frozen=True)
class Spec:
    name: str
    arch: str
    family: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "rms" | "layernorm"
    eps: float
    rope_theta: float
    rot_dim: int         # rotary features per head (even)
    tied: bool

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over all layers, in bf16."""
        return 2 * self.layers * self.kv_heads * self.head_dim * 2


def spec_of(conf: dict) -> Spec:
    """The sizes of a configuration file, as it is run."""
    head_dim = conf.get("head_dim") or (conf["hidden_size"]
                                        // conf["num_attention_heads"])
    norm = conf["norm"]
    eps = conf["rms_norm_eps"] if norm == "rms" else conf["norm_eps"]
    rot = int(head_dim * conf.get("partial_rotary_factor", 1.0)) & ~1
    return Spec(name=conf["name"], arch=conf["arch"], family=conf["family"],
                layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
                heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"], head_dim=head_dim,
                d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                norm=norm, eps=eps, rope_theta=conf["rope_theta"],
                rot_dim=rot, tied=bool(conf["tie_word_embeddings"]))


def program_mismatches(spec: Spec, arch_cfg) -> list[str]:
    """Where the program's ``ArchConfig`` departs from the sizes run.  A
    run on such a config is no run of the configuration."""
    want = {"n_layers": spec.layers, "d_model": spec.d_model,
            "n_heads": spec.heads, "n_kv_heads": spec.kv_heads,
            "resolved_head_dim": spec.head_dim, "d_ff": spec.d_ff,
            "vocab": spec.vocab, "tie_embeddings": spec.tied,
            "norm": "rms" if spec.norm == "rms" else "ln"}
    out = [f"{k}: program {getattr(arch_cfg, k)!r}, configuration {v!r}"
           for k, v in want.items() if getattr(arch_cfg, k) != v]
    rot = int(arch_cfg.resolved_head_dim * arch_cfg.rope_pct) & ~1
    if rot != spec.rot_dim:
        out.append(f"rotary features: program {rot}, configuration "
                   f"{spec.rot_dim}")
    return out


def layout(spec: Spec) -> dict[str, tuple[tuple[int, ...], object, float]]:
    """``path -> (shape, dtype, std)``.  ``std`` 0 marks a norm scale
    (1 + noise) or bias (noise), drawn apart."""
    L, D, H, KV, Dh, F, V = (spec.layers, spec.d_model, spec.heads,
                             spec.kv_heads, spec.head_dim, spec.d_ff,
                             spec.vocab)
    emb_std = LOGIT_STD / np.sqrt(D)
    out = {"embed": ((V, D), BF16, emb_std)}
    blk = "group0/b0"
    norms = ["scale"] + (["bias"] if spec.norm != "rms" else [])
    for n in ("norm1", "norm2"):
        for leaf in norms:
            out[f"{blk}/{n}/{leaf}"] = ((L, D), F32, 0.0)
    out[f"{blk}/mix/w_q"] = ((L, D, H, Dh), BF16, 1 / np.sqrt(D))
    out[f"{blk}/mix/w_kv"] = ((L, D, 2, KV, Dh), BF16, 1 / np.sqrt(D))
    out[f"{blk}/mix/w_o"] = ((L, H, Dh, D), BF16, 1 / np.sqrt(H * Dh))
    out[f"{blk}/ffn/w_in"] = ((L, D, 2, F), BF16, 1 / np.sqrt(D))
    out[f"{blk}/ffn/w_out"] = ((L, F, D), BF16, 1 / np.sqrt(F))
    for leaf in norms:
        out[f"final_norm/{leaf}"] = ((D,), F32, 0.0)
    if not spec.tied:
        out["head"] = ((D, V), BF16, emb_std)
    return out


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


def _positions(x):
    B, S = x.shape[:2]
    return jnp.broadcast_to(jnp.arange(S), (B, S))


def _embed(w: dict, tokens, quant):
    return (_q(w["embed"], quant)[tokens] if quant else
            w["embed"][tokens].astype(F32))


def hidden(spec: Spec, w: dict, tokens, quant=None):
    """Final normed hidden states (B,S,D) for token ids (B,S)."""
    pos = _positions(tokens)
    x = _embed(w, tokens, quant)

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
    return reference.xent_z(logits(spec, w, tokens, quant), labels)


def _split(w: dict):
    """The stacked layers' weights, and the rest."""
    return w["group0"]["b0"], {k: v for k, v in w.items() if k != "group0"}


def _join(stack, rest: dict) -> dict:
    return dict(rest, group0={"b0": stack})


def loss_and_grad(spec: Spec, w: dict, tokens, labels, quant=None,
                  rows: int = 1, batch_sharding=None):
    """Mean of :func:`loss` and its gradient in f32, layer by layer
    (:func:`reference.grad_layers`)."""
    def post(rest, x, labels):
        h = _norm(spec, x, rest["final_norm"])
        lg = _mm("bsd,dv->bsv", h, head(spec, rest), quant)
        return reference.xent_z(lg, labels)

    return reference.grad_layers(
        _split, _join, lambda rest, t: _embed(rest, t, quant),
        lambda lp, x: _layer(spec, x, lp, _positions(x), quant), post,
        w, tokens, labels, rows, batch_sharding)
