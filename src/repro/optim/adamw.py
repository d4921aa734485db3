"""AdamW, shape-polymorphic (works on concrete arrays *and*
ShapeDtypeStruct trees so the dry-run can derive optimizer-state shapes
without allocating).

Moments default to f32; the deepseek-v3 config selects bf16 moments (the
V3 paper's low-precision recipe), halving optimizer HBM.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


@dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    moment_dtype: str = "f32"
    grad_clip: float = 1.0

    @property
    def _mdt(self):
        return jnp.bfloat16 if self.moment_dtype == "bf16" else jnp.float32

    def init(self, params) -> AdamWState:
        def zeros(p):
            if isinstance(p, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(p.shape, self._mdt,
                                            sharding=p.sharding)
            return jnp.zeros(p.shape, self._mdt)
        step = (jax.ShapeDtypeStruct((), jnp.int32)
                if any(isinstance(l, jax.ShapeDtypeStruct)
                       for l in jax.tree.leaves(params))
                else jnp.zeros((), jnp.int32))
        return AdamWState(step, jax.tree.map(zeros, params),
                          jax.tree.map(zeros, params))

    @jax.named_scope("optimizer")
    def update(self, grads, state: AdamWState, params,
               lr_scale: jax.Array | float = 1.0):
        """Returns (new_params, new_state).  Update math in f32; params
        keep their storage dtype."""
        step = state.step + 1
        # Global-norm clip.
        if self.grad_clip:
            gn = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)))
            scale = jnp.minimum(1.0, self.grad_clip / (gn + 1e-9))
        else:
            scale = 1.0

        b1, b2 = self.b1, self.b2
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32) * scale
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
            mh = m32 / c1
            vh = v32 / c2
            delta = mh / (jnp.sqrt(vh) + self.eps)
            if self.weight_decay:
                delta = delta + self.weight_decay * p.astype(jnp.float32)
            new_p = p.astype(jnp.float32) - self.lr * lr_scale * delta
            return (new_p.astype(p.dtype), m32.astype(self._mdt),
                    v32.astype(self._mdt))

        out = jax.tree.map(upd, grads, state.mu, state.nu, params)
        new_params = jax.tree.map(lambda o: o[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda o: o[1], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        new_nu = jax.tree.map(lambda o: o[2], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        return new_params, AdamWState(step, new_mu, new_nu)


def cosine_schedule(base_lr: float, warmup: int, total: int):
    def f(step):
        step = jnp.asarray(step, jnp.float32)
        warm = step / jnp.maximum(warmup, 1)
        prog = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1),
                        0.0, 1.0)
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, cos)
    return f
