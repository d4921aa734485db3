"""Step builders shared by the dry-run, trainer, and server.

``build_train_step`` / ``build_serve_step`` assemble the jitted step with
in/out shardings derived entirely from the HIDA ShardingPlan (params via
``param_spec`` + FSDP, batch via logical dims, caches via ``cache_dims``).
``input_specs`` returns ShapeDtypeStruct stand-ins for every input of a
cell — weak-type-correct, shardable, zero allocation.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig, ShapeSpec
from ..core.plan import ShardingPlan
from ..models.lm import LM
from ..optim import AdamW

BF16 = jnp.bfloat16


def _is_dims_leaf(x) -> bool:
    return (isinstance(x, tuple)
            and all(isinstance(i, str) for i in x)) or x == ()


def sharding_tree(dims_tree, mesh: Mesh, plan: ShardingPlan,
                  weight: bool = False, shapes_tree=None):
    """Map a logical-dims pytree to NamedShardings."""
    def one(dims, leaf=None):
        shape = leaf.shape if (leaf is not None and weight) else None
        return plan.named_sharding(mesh, dims, weight=weight, shape=shape)
    if shapes_tree is not None:
        return jax.tree.map(one, dims_tree, shapes_tree,
                            is_leaf=_is_dims_leaf)
    return jax.tree.map(one, dims_tree, is_leaf=_is_dims_leaf)


# --------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, deliverable e step 2)
# --------------------------------------------------------------------------

def batch_specs(cfg: ArchConfig, shape: ShapeSpec) -> tuple[dict, dict]:
    """(specs, dims) for the data batch of one cell."""
    B = shape.global_batch
    S = 1 if shape.mode == "decode" else shape.seq_len
    specs: dict = {}
    dims: dict = {}
    if cfg.frontend == "audio_frames":
        specs["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), BF16)
        dims["frames"] = ("batch", "seq", "d_model")
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        dims["tokens"] = ("batch", "seq")
    if cfg.frontend == "vision":
        specs["img_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_img_tokens, cfg.d_model), BF16)
        dims["img_embeds"] = ("batch", "kv_seq", "d_model")
    if shape.mode == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        dims["labels"] = ("batch", "seq")
    if shape.mode == "decode":
        specs["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
        dims["pos"] = ()
    return specs, dims


def input_specs(cfg: ArchConfig, shape: ShapeSpec, lm: LM | None = None
                ) -> dict:
    """All abstract inputs of the cell: batch (+ params/caches trees)."""
    lm = lm or LM(cfg)
    specs, _ = batch_specs(cfg, shape)
    out = {"batch": specs}
    out["params"], _ = lm.init(None, abstract=True)
    if shape.mode == "decode":
        out["caches"] = lm.init_caches(shape.global_batch, shape.seq_len,
                                       abstract=True)
    return out


# --------------------------------------------------------------------------
# Steps
# --------------------------------------------------------------------------

@dataclass
class TrainStep:
    fn: Callable            # (params, opt_state, batch) -> (params, opt_state, metrics)
    in_shardings: tuple
    out_shardings: Any
    abstract_inputs: tuple  # matching ShapeDtypeStruct trees


def build_train_step(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                     plan: ShardingPlan, opt: AdamW | None = None,
                     remat: str = "full", use_kernels: bool = False,
                     accum_steps: int = 1) -> TrainStep:
    """``accum_steps > 1`` microbatches the global batch inside the step
    (lax.scan over B/K slices accumulating gradients, one optimizer
    update): live activation set shrinks ~K× at the cost of a
    params-shaped f32 accumulator — the standard memory lever for cells
    whose activations exceed HBM at the full per-step token count."""
    lm = LM(cfg, plan=plan, mesh=mesh, remat=remat,
            use_kernels=use_kernels)
    opt = opt or AdamW(moment_dtype=cfg.opt_moment_dtype)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lm.loss_fn, has_aux=True)(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((accum_steps, -1) + x.shape[1:])
                if x.ndim else jnp.broadcast_to(x, (accum_steps,)),
                batch)

            def body(carry, mb):
                gsum, _ = carry
                (l, m), g = jax.value_and_grad(
                    lm.loss_fn, has_aux=True)(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, m), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"loss": jnp.zeros(()), "xent": jnp.zeros(()),
                  "aux_lb": jnp.zeros(()), "aux_z": jnp.zeros(())}
            if cfg.mtp:
                m0["mtp"] = jnp.zeros(())
            (gsum, metrics), _ = jax.lax.scan(body, (g0, m0), micro)
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, metrics

    params_abs, dims = lm.init(None, abstract=True)
    opt_abs = opt.init(params_abs)
    bspecs, bdims = batch_specs(cfg, shape)

    p_sh = sharding_tree(dims, mesh, plan, weight=True,
                         shapes_tree=params_abs)
    o_sh = (NamedSharding(mesh, P()),
            jax.tree.map(lambda s: s, p_sh), jax.tree.map(lambda s: s, p_sh))
    o_sh = type(opt_abs)(*o_sh)
    b_sh = sharding_tree(bdims, mesh, plan)
    m_sh = NamedSharding(mesh, P())

    fn = jax.jit(train_step,
                 in_shardings=(p_sh, o_sh, b_sh),
                 out_shardings=(p_sh, o_sh, None),
                 donate_argnums=(0, 1))
    return TrainStep(fn, (p_sh, o_sh, b_sh), (p_sh, o_sh, None),
                     (params_abs, opt_abs, bspecs))


@dataclass
class ServeStep:
    prefill: Callable | None
    decode: Callable
    abstract_inputs: tuple   # (params, batch, caches)


def build_serve_step(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                     plan: ShardingPlan, use_kernels: bool = False
                     ) -> ServeStep:
    lm = LM(cfg, plan=plan, mesh=mesh, remat="none",
            use_kernels=use_kernels)

    params_abs, dims = lm.init(None, abstract=True)
    p_sh = sharding_tree(dims, mesh, plan, weight=True,
                         shapes_tree=params_abs)
    bspecs, bdims = batch_specs(cfg, shape)
    b_sh = sharding_tree(bdims, mesh, plan)

    caches_abs = lm.init_caches(shape.global_batch, shape.seq_len,
                                abstract=True)
    cdims = lm.cache_dims()
    c_sh = sharding_tree(cdims, mesh, plan)

    decode = jax.jit(lm.decode_step,
                     in_shardings=(p_sh, b_sh, c_sh),
                     out_shardings=(None, c_sh),
                     donate_argnums=(2,))
    prefill = None
    if shape.mode == "prefill":
        prefill = jax.jit(lm.prefill, in_shardings=(p_sh, b_sh))
    return ServeStep(prefill, decode, (params_abs, bspecs, caches_abs))


def build_prefill_step(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                       plan: ShardingPlan, use_kernels: bool = False):
    """The served prefill, ``LM.prefill`` over full-length rows: the last
    logits, and for self-attention GQA configs the caches it fills."""
    lm = LM(cfg, plan=plan, mesh=mesh, remat="none",
            use_kernels=use_kernels)
    params_abs, dims = lm.init(None, abstract=True)
    p_sh = sharding_tree(dims, mesh, plan, weight=True,
                         shapes_tree=params_abs)
    bspecs, bdims = batch_specs(cfg, shape)
    b_sh = sharding_tree(bdims, mesh, plan)
    fn = jax.jit(lm.prefill, in_shardings=(p_sh, b_sh))
    return fn, (params_abs, bspecs)
