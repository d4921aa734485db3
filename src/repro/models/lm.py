"""Decoder LM assembly for all assigned architectures.

A model is assembled from the config's layer pattern: homogeneous
repeated super-blocks are executed with ``lax.scan`` over stacked
parameters (keeps HLO size O(pattern), not O(n_layers) — essential for the
60-layer dry-runs), prefix layers run unrolled.  Sharding is applied only
through the ShardingPlan's buffer sites; the model never names a mesh
axis.

Entry points:

* ``loss_fn(params, batch)``    — training loss (+ MoE aux, MTP).
* ``prefill(params, batch)``    — full-sequence forward; returns each
  row's last logits and, for self-attention GQA configs, the filled
  caches.
* ``decode_step(params, batch, caches)`` — one-token step with KV / SSM /
  xLSTM state caches.
* ``init_caches(B, S_max)``     — abstract-friendly cache pytree.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .attention import (KVCache, gqa_attention, init_gqa, init_mla,
                        mla_attention)
from .layers import (BF16, F32, ParamBuilder, apply_norm, cross_entropy,
                     init_mlp, init_norm, mlp)
from .moe import MoEAux, init_moe, moe_ffn
from .ssm import SSMState, init_mamba, mamba_block
from .xlstm import (MLSTMState, SLSTMState, init_mlstm, init_slstm,
                    mlstm_block, slstm_block)

AUX_LB_WEIGHT = 0.01
AUX_Z_WEIGHT = 1e-3
MTP_WEIGHT = 0.3

#: The ``jax.named_scope`` names of the compiled programs, by layer: the
#: mixer sublayer by kind (``attn`` also for cross-attention and MLA),
#: the FFN, the layer scan's own work (each layer's slice of the stacked
#: weights and caches, the stacked write of its new cache), the cache
#: gate, the head, the loss, the serving prefill's slot install and the
#: optimizer update.  They reach each optimised HLO instruction's
#: ``metadata={op_name=...}``, where trace readers find the innermost one
#: to attribute device time to a layer.
SCOPES = ("embed", "attn", "mamba", "mlstm", "slstm", "ffn", "layer_scan",
          "cache_gate", "head", "loss", "prefill_install", "optimizer")


def _noop_constrain(x, dims, site=None):
    return x


@dataclass
class LM:
    cfg: ArchConfig
    plan: Any = None              # ShardingPlan | None
    mesh: Any = None              # concrete jax Mesh (shard_map EP path)
    use_kernels: bool = False
    remat: str = "full"           # none | full | dots

    # -- helpers ---------------------------------------------------------------
    @property
    def constrain(self) -> Callable:
        if self.plan is None:
            return _noop_constrain
        return self.plan.constrain

    def _groups(self):
        return self.cfg.layer_groups()

    def _ep(self):
        """Expert-parallel routing hint: (batch_axes, expert_axes,
        seq_axes, mesh) — the explicit all_to_all dispatch path.  The
        concrete mesh must be captured here: inside scan/checkpoint
        tracing the ambient-mesh context is abstract."""
        if self.plan is None or self.mesh is None:
            return None
        eaxes = tuple(self.plan.rules.get("experts", ()))
        if not eaxes:
            return None
        baxes = tuple(self.plan.rules.get("batch", ()))
        saxes = tuple(a for a in self.plan.rules.get("seq", ())
                      if a not in baxes)
        tp = self.plan.meta.get("moe_tp")
        return (baxes, eaxes, saxes, self.mesh, tp)

    # -- init --------------------------------------------------------------------
    def init(self, rng: jax.Array | None,
             abstract: bool = False) -> tuple[dict, dict]:
        """Returns (params, dims) — dims mirrors params with logical axis
        names for plan-driven sharding.  ``abstract=True`` returns
        ShapeDtypeStructs (dry-run: zero allocation)."""
        cfg = self.cfg
        pb = ParamBuilder(rng, abstract=abstract)
        if cfg.frontend != "audio_frames":
            pb.weight("embed", (cfg.vocab, cfg.d_model),
                      ("vocab", "d_model"), scale=0.02)
        for gi, (pattern, repeats) in enumerate(self._groups()):
            stack = repeats if repeats > 1 else None
            base = f"group{gi}"
            for j, (mix, ffn) in enumerate(pattern):
                pfx = f"{base}/b{j}"
                init_norm(pb, f"{pfx}/norm1", cfg.norm, cfg.d_model,
                          stack=stack)
                if mix in ("attn", "xattn"):
                    if cfg.mla is not None:
                        init_mla(pb, f"{pfx}/mix", cfg, stack=stack)
                    else:
                        init_gqa(pb, f"{pfx}/mix", cfg, stack=stack)
                elif mix == "mamba":
                    init_mamba(pb, f"{pfx}/mix", cfg, stack=stack)
                elif mix == "mlstm":
                    init_mlstm(pb, f"{pfx}/mix", cfg, stack=stack)
                elif mix == "slstm":
                    init_slstm(pb, f"{pfx}/mix", cfg, stack=stack)
                if ffn != "none":
                    init_norm(pb, f"{pfx}/norm2", cfg.norm, cfg.d_model,
                              stack=stack)
                if ffn == "dense":
                    d_ff = cfg.dense_d_ff or cfg.d_ff
                    init_mlp(pb, f"{pfx}/ffn", cfg.d_model, d_ff,
                             stack=stack)
                elif ffn == "moe":
                    init_moe(pb, f"{pfx}/ffn", cfg, stack=stack)
        init_norm(pb, "final_norm", cfg.norm, cfg.d_model)
        if not cfg.tie_embeddings:
            pb.weight("head", (cfg.d_model, cfg.vocab),
                      ("d_model", "vocab"), scale=0.02)
        if cfg.mtp:
            pb.weight("mtp/proj", (2 * cfg.d_model, cfg.d_model),
                      ("d_model2", "d_model"))
            init_norm(pb, "mtp/norm1", cfg.norm, cfg.d_model)
            init_gqa(pb, "mtp/mix", cfg)
            init_norm(pb, "mtp/norm2", cfg.norm, cfg.d_model)
            init_mlp(pb, "mtp/ffn", cfg.d_model,
                     cfg.dense_d_ff or cfg.d_ff)
        return pb.params, pb.dims

    # -- one block ----------------------------------------------------------------
    def _block(self, resid, bp, mix, ffn, positions, img, cache=None,
               emit_kv=False):
        cfg = self.cfg
        c = self.constrain
        aux = MoEAux(jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))
        new_cache = cache
        with jax.named_scope("attn" if mix == "xattn" else mix):
            x = apply_norm(cfg.norm, resid, bp["norm1"])
            if mix in ("attn", "xattn"):
                kv_x = img if mix == "xattn" else None
                if cfg.mla is not None:
                    out, kvc = mla_attention(x, bp["mix"], cfg, positions,
                                             c, cache=cache)
                else:
                    out, kvc = gqa_attention(
                        x, bp["mix"], cfg, positions, c, cache=cache,
                        kv_x=kv_x,
                        use_kernels=self.use_kernels and cache is None,
                        return_kv=emit_kv)
                new_cache = kvc if cache is not None or emit_kv else None
            elif mix == "mamba":
                if cache is not None:
                    state, carry = cache
                    out, state, carry = mamba_block(
                        x, bp["mix"], cfg, c, state=state, conv_carry=carry)
                    new_cache = (state, carry)
                else:
                    out = mamba_block(x, bp["mix"], cfg, c,
                                      use_kernels=self.use_kernels)
            elif mix == "mlstm":
                if cache is not None:
                    out, new_cache = mlstm_block(x, bp["mix"], cfg, c,
                                                 state=cache)
                else:
                    out = mlstm_block(x, bp["mix"], cfg, c,
                                      use_kernels=self.use_kernels)
            elif mix == "slstm":
                if cache is not None:
                    out, new_cache = slstm_block(x, bp["mix"], cfg, c,
                                                 state=cache)
                else:
                    out = slstm_block(x, bp["mix"], cfg, c)
            resid = resid + out
        resid = c(resid, ("batch", "seq", "d_model"), "residual")

        if ffn in ("dense", "moe"):
            with jax.named_scope("ffn"):
                x2 = apply_norm(cfg.norm, resid, bp["norm2"])
                if ffn == "dense":
                    resid = resid + mlp(x2, bp["ffn"], c)
                else:
                    moe_out, aux = moe_ffn(x2, bp["ffn"], cfg, c,
                                           ep=self._ep())
                    resid = resid + moe_out
        resid = c(resid, ("batch", "seq", "d_model"), "residual2")
        return resid, aux, new_cache

    def _super_block(self, resid, gparams, pattern, positions, img,
                     caches=None, emit_kv=False):
        auxes = []
        new_caches = {} if caches is not None or emit_kv else None
        for j, (mix, ffn) in enumerate(pattern):
            cache = caches.get(f"b{j}") if caches is not None else None
            resid, aux, nc = self._block(resid, gparams[f"b{j}"], mix, ffn,
                                         positions, img, cache, emit_kv)
            auxes.append(aux)
            if new_caches is not None:
                new_caches[f"b{j}"] = nc
        total_aux = MoEAux(
            sum(a.load_balance_loss for a in auxes),
            sum(a.router_z_loss for a in auxes),
            sum(a.dropped_fraction for a in auxes) / max(len(auxes), 1))
        return resid, total_aux, new_caches

    # -- forward -------------------------------------------------------------------
    def _backbone(self, params, resid, positions, img, caches=None,
                  emit_kv=False):
        """Runs all layer groups; returns (resid, aux, new_caches).
        ``emit_kv`` (no ``caches``): new_caches holds each attention
        layer's full-sequence ``KVCache(k, v, None)``, stacked over a
        scanned group's layers."""
        cfg = self.cfg
        lb = jnp.zeros(())
        zl = jnp.zeros(())
        new_caches = {} if caches is not None or emit_kv else None
        for gi, (pattern, repeats) in enumerate(self._groups()):
            gparams = params[f"group{gi}"]
            gcaches = caches.get(f"group{gi}") if caches is not None else None
            if repeats == 1:
                resid, aux, nc = self._super_block(
                    resid, gparams, pattern, positions, img, gcaches,
                    emit_kv)
                lb, zl = lb + aux.load_balance_loss, zl + aux.router_z_loss
                if new_caches is not None:
                    new_caches[f"group{gi}"] = nc
                continue

            def body(carry, xs, pattern=pattern):
                r, lb_c, zl_c = carry
                if caches is not None:
                    lp, lc = xs
                else:
                    lp, lc = xs, None
                r, aux, nc = self._super_block(r, lp, pattern, positions,
                                               img, lc, emit_kv)
                return ((r, lb_c + aux.load_balance_loss,
                         zl_c + aux.router_z_loss), nc)

            if self.remat == "full":
                body = jax.checkpoint(body)
            elif self.remat == "dots":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            xs = (gparams, gcaches) if caches is not None else gparams
            with jax.named_scope("layer_scan"):
                (resid, lb, zl), scanned_caches = jax.lax.scan(
                    body, (resid, lb, zl), xs)
            if new_caches is not None:
                new_caches[f"group{gi}"] = scanned_caches
        return resid, (lb, zl), new_caches

    @jax.named_scope("embed")
    def _embed(self, params, batch):
        cfg = self.cfg
        c = self.constrain
        if cfg.frontend == "audio_frames":
            resid = batch["frames"].astype(BF16)
        else:
            resid = params["embed"][batch["tokens"]].astype(BF16)
        resid = c(resid, ("batch", "seq", "d_model"), "embed_out")
        img = None
        if cfg.frontend == "vision":
            img = batch["img_embeds"].astype(BF16)
        return resid, img

    @jax.named_scope("head")
    def _head(self, params, resid):
        cfg = self.cfg
        x = apply_norm(cfg.norm, resid, params["final_norm"])
        table = (params["embed"].T if cfg.tie_embeddings
                 else params["head"])
        logits = jnp.einsum("bsd,dv->bsv", x, table.astype(BF16))
        return self.constrain(logits, ("batch", "seq", "vocab"), "logits")

    def logits_fn(self, params, batch) -> jax.Array:
        """Full-sequence logits (teacher forcing) — used by tests to check
        decode-vs-parallel consistency and by the serving scorer."""
        cfg = self.cfg
        if cfg.frontend == "audio_frames":
            B, S = batch["frames"].shape[:2]
        else:
            B, S = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        resid, img = self._embed(params, batch)
        resid, _, _ = self._backbone(params, resid, positions, img)
        return self._head(params, resid)

    def loss_fn(self, params, batch) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        B, S = batch["labels"].shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        resid, img = self._embed(params, batch)
        resid, (lb, zl), _ = self._backbone(params, resid, positions, img)
        logits = self._head(params, resid)
        with jax.named_scope("loss"):
            loss = cross_entropy(logits, batch["labels"])
        metrics = {"xent": loss, "aux_lb": lb, "aux_z": zl}
        if cfg.mtp:
            mtp_loss = self._mtp_loss(params, resid, batch, positions)
            metrics["mtp"] = mtp_loss
            loss = loss + MTP_WEIGHT * mtp_loss
        loss = loss + AUX_LB_WEIGHT * lb + AUX_Z_WEIGHT * zl
        metrics["loss"] = loss
        return loss, metrics

    def _mtp_loss(self, params, resid, batch, positions):
        """DeepSeek-V3 depth-1 multi-token prediction: combine the final
        hidden state with the embedding of the *next* token, run one extra
        block, predict token t+2 with the shared head."""
        cfg = self.cfg
        nxt = jnp.pad(batch["labels"][:, 1:], ((0, 0), (0, 1)))
        emb = params["embed"][nxt].astype(BF16)
        h = jnp.concatenate(
            [apply_norm(cfg.norm, resid, params["mtp"]["norm1"]), emb],
            axis=-1)
        h = jnp.einsum("bse,ed->bsd", h, params["mtp"]["proj"])
        out, _ = gqa_attention(h, params["mtp"]["mix"], cfg, positions,
                               self.constrain)
        h = h + out
        x2 = apply_norm(cfg.norm, h, params["mtp"]["norm2"])
        h = h + mlp(x2, params["mtp"]["ffn"], self.constrain)
        logits = self._head(params, h)
        mtp_labels = jnp.pad(batch["labels"][:, 2:], ((0, 0), (0, 2)))
        return cross_entropy(logits, mtp_labels, z_loss=0.0)

    # -- serving -------------------------------------------------------------------
    def init_caches(self, B: int, S_max: int, abstract: bool = False,
                    vector_pos: bool = False) -> dict:
        """Cache pytree (zeros) — shape source for dry-run input_specs.

        ``vector_pos=True`` makes every attention cache position a
        per-slot ``(B,)`` vector instead of a shared scalar — required by
        the continuous-batching server, where slots sit at independent
        positions (see :class:`repro.launch.scheduler.ContinuousBatcher`).
        """
        cfg = self.cfg
        caches: dict = {}
        for gi, (pattern, repeats) in enumerate(self._groups()):
            g: dict = {}
            for j, (mix, ffn) in enumerate(pattern):
                g[f"b{j}"] = self._block_cache(mix, B, S_max, repeats,
                                               abstract, vector_pos)
            caches[f"group{gi}"] = g
        return caches

    def cache_dims(self) -> dict:
        """Pytree mirroring ``init_caches`` whose leaves are logical-dim
        tuples (for plan-driven cache sharding)."""
        dims_map = {
            "kv": ("batch", "kv_seq", "kv_heads", "d_head"),
            "lat": ("batch", "kv_seq", "kv_lora"),
            "pos": (),
            "ssm_h": ("batch", "d_inner", "d_state"),
            "conv": ("batch", "d_conv", "d_inner"),
            "mC": ("batch", "heads", "d_head", "d_head2"),
            "mn": ("batch", "heads", "d_head"),
            "mm": ("batch", "heads"),
            "sl": ("batch", "d_model"),
        }
        cfg = self.cfg
        out: dict = {}
        for gi, (pattern, repeats) in enumerate(self._groups()):
            g: dict = {}
            for j, (mix, _) in enumerate(pattern):
                pre = ("layers",) if repeats > 1 else ()
                if mix in ("attn", "xattn"):
                    if cfg.mla is not None:
                        leaf = KVCache(pre + dims_map["lat"], None,
                                       pre + dims_map["pos"])
                    else:
                        leaf = KVCache(pre + dims_map["kv"],
                                       pre + dims_map["kv"],
                                       pre + dims_map["pos"])
                elif mix == "mamba":
                    leaf = (SSMState(pre + dims_map["ssm_h"]),
                            pre + dims_map["conv"])
                elif mix == "mlstm":
                    leaf = MLSTMState(pre + dims_map["mC"],
                                      pre + dims_map["mn"],
                                      pre + dims_map["mm"])
                elif mix == "slstm":
                    leaf = SLSTMState(*([pre + dims_map["sl"]] * 4))
                else:
                    leaf = None
                g[f"b{j}"] = leaf
            out[f"group{gi}"] = g
        return out

    def _block_cache(self, mix, B, S_max, repeats, abstract=False,
                     vector_pos=False):
        cfg = self.cfg
        pos_shape = (B,) if vector_pos else ()

        def z(shape, dtype=BF16):
            full = (repeats,) + shape if repeats > 1 else shape
            if abstract:
                return jax.ShapeDtypeStruct(full, dtype)
            return jnp.zeros(full, dtype)

        if mix in ("attn", "xattn"):
            if cfg.mla is not None:
                m = cfg.mla
                return KVCache(z((B, S_max, m.kv_lora + m.rope_dim)), None,
                               z(pos_shape, jnp.int32))
            KVH, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
            S_eff = min(S_max, cfg.attn_window or S_max)
            # SWA caches could be ring buffers of the window; we keep the
            # full length for mask simplicity except in long_500k where
            # the window bound is what makes the cell feasible.
            S_c = S_eff if (cfg.attn_window and S_max > 65536) else S_max
            return KVCache(z((B, S_c, KVH, Dh)), z((B, S_c, KVH, Dh)),
                           z(pos_shape, jnp.int32))
        if mix == "mamba":
            mb = cfg.mamba
            Din = mb.expand * cfg.d_model
            return (SSMState(z((B, Din, mb.d_state), F32)),
                    z((B, mb.d_conv - 1, Din)))
        if mix == "mlstm":
            Din = cfg.xlstm.proj_factor_mlstm * cfg.d_model
            H = cfg.n_heads
            Dh = Din // H
            return MLSTMState(z((B, H, Dh, Dh), F32), z((B, H, Dh), F32),
                              z((B, H), F32))
        if mix == "slstm":
            D = cfg.d_model
            return SLSTMState(z((B, D), F32), z((B, D), F32),
                              z((B, D), F32), z((B, D), F32))
        return None

    @property
    def prefill_fills_caches(self) -> bool:
        """Whether :meth:`prefill` fills the caches: every block is
        self-attention with a GQA cache.  A recurrent mixer's state, MLA's
        latent cache and cross-attention are filled by decode steps."""
        return self.cfg.mla is None and all(
            mix == "attn" for mix, _ in self.cfg.layer_kinds())

    def prefill(self, params, batch) -> tuple[jax.Array, dict | None]:
        """Full-sequence forward over ``(B, S)`` prompts at positions
        ``0..S-1``.  ``batch["lengths"]`` (optional ``(B,)`` int32) holds
        each row's prompt length; without it every row is ``S`` long.

        Returns ``(logits, caches)``: each row's logits at its last prompt
        position, ``(B, 1, vocab)``, and where :attr:`prefill_fills_caches`
        the caches, laid out as ``init_caches(B, S, vector_pos=True)``,
        with every layer's post-rotary K/V at positions ``0..S-1`` and
        ``pos = lengths`` (``None`` for other configs).  Rows past a
        prompt's length hold the K/V of its padding, which causal
        attention keeps from every real position and decode overwrites
        before it reads."""
        cfg = self.cfg
        if cfg.frontend == "audio_frames":
            B, S = batch["frames"].shape[:2]
        else:
            B, S = batch["tokens"].shape
        lengths = batch.get("lengths")
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        fill = self.prefill_fills_caches
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        resid, img = self._embed(params, batch)
        resid, _, kv = self._backbone(params, resid, positions, img,
                                      emit_kv=fill)
        last = jnp.take_along_axis(resid, (lengths - 1)[:, None, None],
                                   axis=1)
        logits = self._head(params, last)
        if not fill:
            return logits, None
        caches = {}
        for gi, (_pattern, repeats) in enumerate(self._groups()):
            pos = (jnp.broadcast_to(lengths, (repeats, B)) if repeats > 1
                   else lengths)
            caches[f"group{gi}"] = {
                b: KVCache(c.k, c.v, pos) for b, c in kv[f"group{gi}"].items()}
        return logits, caches

    def decode_step(self, params, batch, caches) -> tuple[jax.Array, dict]:
        """One-token step: batch holds the current token (B,1) (or frame)
        and the position — a scalar (lock-step batch) or a per-slot
        ``(B,)`` vector (continuous batching; caches must then come from
        ``init_caches(vector_pos=True)``).

        ``batch["active"]`` (optional, ``(B,)`` bool) gates the cache
        write-back per slot: an inactive slot's caches pass through
        bit-identical to never stepping, so empty decode slots neither
        advance their position nor pollute the cache a future occupant
        will overwrite-and-mask.  Requires vector positions."""
        cfg = self.cfg
        if cfg.frontend == "audio_frames":
            B = batch["frames"].shape[0]
        else:
            B = batch["tokens"].shape[0]
        pos = batch["pos"]
        positions = (pos[:, None] if pos.ndim
                     else jnp.broadcast_to(pos, (B, 1)))
        resid, img = self._embed(params, batch)
        resid, _, new_caches = self._backbone(params, resid, positions,
                                              img, caches=caches)
        if "active" in batch:
            new_caches = self._gate_caches(batch["active"], caches,
                                           new_caches)
        logits = self._head(params, resid)
        return logits, new_caches

    @jax.named_scope("cache_gate")
    def _gate_caches(self, active, old, new):
        """Per-slot select between the stepped and the previous cache
        leaves.  The batch axis of every leaf is 0, except inside a
        stacked (scanned) layer group where the leading axis is the
        layers axis — selection is applied per group so the broadcast
        shape is always right."""
        out: dict = {}
        for gi, (_pattern, repeats) in enumerate(self._groups()):
            ax = 1 if repeats > 1 else 0
            B = active.shape[0]

            def sel(o, n, ax=ax):
                shape = [1] * n.ndim
                shape[ax] = B
                return jnp.where(active.reshape(shape), n, o)

            g = f"group{gi}"
            out[g] = jax.tree.map(sel, old[g], new[g])
        return out
