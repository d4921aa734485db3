"""Random weights from ``--seed``, made on the device in one jitted call,
in the layout and types the program serves and trains them in.

The yardstick states that layout itself (``families/<family>.py``
``layout``, which callers pass in) and refuses a program whose parameter
tree differs from it, so the reference reads the very arrays the program
is given and nothing the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = jnp.bfloat16, jnp.float32

#: Spread of the output logits: a served token's margin over its
#: runner-up is then of the order of one, as in a trained model, and
#: not the near-ties of a unit-scale random head.
LOGIT_STD = 4.0


def key_of(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer (``PRNGKey`` keeps
    only the low 32 bits of a large seed)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        keys = path.split("/")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = leaf
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


def make(lay: dict, seed: int, sharding=None) -> dict:
    """The whole parameter tree of the layout ``lay`` (``path -> (shape,
    dtype, std)``), on the device, in one call; each leaf made where
    ``sharding`` (a tree like the parameters') places it."""

    def gen(key):
        flat = {}
        for i, (path, (shape, dtype, std)) in enumerate(sorted(lay.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, F32 if dtype == F32 else BF16)
            if std:
                flat[path] = (z * jnp.asarray(std, z.dtype)).astype(dtype)
            elif path.endswith("scale"):
                flat[path] = (1.0 + 0.1 * z).astype(dtype)
            else:
                flat[path] = (0.1 * z).astype(dtype)
        return _nest(flat)

    return jax.jit(gen, out_shardings=sharding)(key_of(seed))


def zeros(like, sharding=None):
    """Zeros in the shape and type of each leaf of ``like``, placed by
    ``sharding`` (a tree like it) or as the leaf is."""
    if sharding is None:
        sharding = jax.tree.map(lambda a: a.sharding, like)
    return jax.tree.map(
        lambda a, s: jnp.zeros(a.shape, a.dtype, device=s), like, sharding)


def check_tree(want: dict, abstract: dict) -> list[str]:
    """Differences between the program's (abstract) parameter tree and
    the layout ``want``."""
    have = {p: (tuple(a.shape), a.dtype) for p, a in flatten(abstract).items()}
    out = [f"{p}: missing in program" for p in want if p not in have]
    out += [f"{p}: not in the yardstick's layout" for p in have
            if p not in want]
    out += [f"{p}: program {have[p]}, yardstick {(s, jnp.dtype(d))}"
            for p, (s, d, _) in want.items()
            if p in have and have[p] != (s, jnp.dtype(d))]
    return out
