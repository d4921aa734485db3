"""What the plain references of every family share: ``jax.numpy`` in
float32 at the highest matmul precision, the training objective, the
gradient over blocks of rows, and AdamW.  Each family's forward pass is
``families/<family>.py``.

``quant="fp8"`` is the control: every matmul operand rounded to
``float8_e4m3fn`` with a per-tensor scale, the rest as above.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Z_LOSS = 1e-4
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


def xent_z(lg, labels):
    """The program's objective: mean cross-entropy plus ``Z_LOSS`` times
    the mean squared log-partition."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + Z_LOSS * jnp.mean(lse * lse)


def grad_layers(split, join, pre, layer, post, w: dict, tokens, labels,
                rows: int = 1, batch_sharding=None):
    """Mean loss and its gradient in f32, ``rows`` batch rows at a time
    (equal blocks, so the mean of the block means is the mean) and layer
    by layer.  ``split(w)`` gives the stacked layers' weights and the
    rest, and ``join`` undoes it; ``pre(rest, tokens)`` gives the first
    layer's input, ``layer(lp, x)`` a layer's output from its slice
    ``lp`` of the stack, ``post(rest, x, labels)`` the loss.  The forward
    keeps each layer's input; the backward takes each layer's
    vector-Jacobian product in turn, its forward recomputed as under
    ``jax.checkpoint``.  So one layer's weights and temporaries are live
    at a time, the gradient is laid out as ``w`` is and summed in place,
    and each block of rows is placed by ``batch_sharding``."""
    w32 = jax.tree.map(lambda a: a if a.dtype == F32 else a.astype(F32), w)
    lay = jax.tree.map(lambda a: a.sharding, w32)
    stack, rest = split(w32)
    stack_lay, rest_lay = split(lay)
    n_layers = jax.tree.leaves(stack)[0].shape[0]
    take = jax.jit(lambda s, i: jax.tree.map(lambda a: a[i], s))
    fwd = jax.jit(layer)
    bwd = jax.jit(lambda lp, x, dy: jax.vjp(layer, lp, x)[1](dy))
    head = jax.jit(jax.value_and_grad(post, argnums=(0, 1)))
    first = jax.jit(pre)
    first_bwd = jax.jit(lambda r, t, dx: jax.vjp(lambda r: pre(r, t), r)[1](
        dx)[0])
    put = jax.jit(lambda acc, g, i: jax.tree.map(
        lambda a, b: a.at[i].add(b), acc, g),
        out_shardings=stack_lay, donate_argnums=0)
    add = jax.jit(lambda a, b, c: jax.tree.map(
        lambda a, b, c: a + b + c, a, b, c),
        out_shardings=rest_lay, donate_argnums=0)
    g_stack, g_rest = split(jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype, device=a.sharding), w32))
    B = tokens.shape[0]
    n = B // rows
    total = 0.0
    for b in range(n):
        sl = slice(b * rows, (b + 1) * rows)
        t, l = (jax.device_put(x[sl], batch_sharding)
                for x in (tokens, labels))
        xs = [first(rest, t)]
        for i in range(n_layers):
            xs.append(fwd(take(stack, i), xs[-1]))
        loss, (d_rest, dx) = head(rest, xs.pop(), l)
        total = total + loss / n
        for i in reversed(range(n_layers)):
            d_lp, dx = bwd(take(stack, i), xs.pop(), dx)
            g_stack = put(g_stack, d_lp, i)
        g_rest = add(g_rest, d_rest, first_bwd(rest, t, dx))
    scale = jax.jit(lambda g: jax.tree.map(lambda a: a / n, g),
                    out_shardings=lay, donate_argnums=0)
    return total, scale(join(g_stack, g_rest))


def adamw(w, grads, mu, nu, step: int, lr: float, *, b1: float, b2: float,
          eps: float, weight_decay: float, clip: float, store=None):
    """One AdamW step in f32 with global-norm clipping and decoupled
    weight decay on every leaf; ``step`` counts from 1.  ``store`` (a
    tree of dtypes) rounds each new weight to the precision of the type
    the configuration keeps it in, as a bf16 model's weights are kept."""
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
        w = jax.tree.map(_round_to, w, store)
    return w, mu, nu, g


def _round_to(x, dtype):
    """``x`` rounded to ``dtype``'s precision, kept in f32.  Unlike a
    round trip through ``astype``, which a compiler that allows excess
    precision may drop inside a jitted program, the rounding stays."""
    if jnp.dtype(dtype) == F32:
        return x
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)
