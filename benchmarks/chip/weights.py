"""Random weights from ``--seed``, made on the device in one jitted call,
in the layout and types the program serves and trains them in.

The yardstick states that layout itself (:func:`layout`) and refuses a
program whose parameter tree differs from it, so the reference reads
the very arrays the program is given and nothing the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from model import Spec

BF16, F32 = jnp.bfloat16, jnp.float32

#: Spread of the output logits: a served token's margin over its
#: runner-up is then of the order of one, as in a trained model, and
#: not the near-ties of a unit-scale random head.
LOGIT_STD = 4.0


def layout(spec: Spec) -> dict[str, tuple[tuple[int, ...], object, float]]:
    """``path -> (shape, dtype, std)`` of a dense decoder whose layers are
    one scanned stack.  ``std`` 0 marks a norm scale (1 + noise) or bias
    (noise), drawn apart."""
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


def make(spec: Spec, seed: int, sharding=None) -> dict:
    """The whole parameter tree, on the device, in one call."""
    lay = layout(spec)

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


def check_tree(spec: Spec, abstract: dict) -> list[str]:
    """Differences between the program's (abstract) parameter tree and
    :func:`layout`."""
    want = layout(spec)
    have = {p: (tuple(a.shape), a.dtype) for p, a in flatten(abstract).items()}
    out = [f"{p}: missing in program" for p in want if p not in have]
    out += [f"{p}: not in the yardstick's layout" for p in have
            if p not in want]
    out += [f"{p}: program {have[p]}, yardstick {(s, jnp.dtype(d))}"
            for p, (s, d, _) in want.items()
            if p in have and have[p] != (s, jnp.dtype(d))]
    return out
