"""Model-zoo tests: per-arch smoke (deliverable f), decode-vs-parallel
consistency for every sequence-mixer family, and sub-block oracles."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_archs
from repro.models import LM
from repro.models.ssm import selective_scan_assoc, selective_scan_seq
from repro.models.xlstm import _mlstm_parallel, MLSTMState

RNG = jax.random.PRNGKey(0)


def _batch_for(cfg, B, S, rng=RNG):
    batch = {"labels": jax.random.randint(rng, (B, S), 0, cfg.vocab)}
    if cfg.frontend == "audio_frames":
        batch["frames"] = jax.random.normal(rng, (B, S, cfg.d_model),
                                            jnp.bfloat16)
    else:
        batch["tokens"] = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    if cfg.frontend == "vision":
        batch["img_embeds"] = jax.random.normal(
            rng, (B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
    return batch


# --------------------------------------------------------------------------
# Per-arch smoke: one train step on a reduced config (deliverable f)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", list_archs())
def test_arch_smoke_train_step(arch):
    cfg = get_config(arch, smoke=True)
    lm = LM(cfg, remat="none")
    params, dims = lm.init(RNG)
    batch = _batch_for(cfg, B=2, S=16)

    def step(p, b):
        loss, metrics = lm.loss_fn(p, b)
        return loss

    loss, grads = jax.jit(jax.value_and_grad(step))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss))
    # grads finite and same structure
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in flat)
    assert jax.tree.structure(grads) == jax.tree.structure(params)


@pytest.mark.slow
@pytest.mark.parametrize("arch", list_archs())
def test_arch_smoke_decode_shapes(arch):
    cfg = get_config(arch, smoke=True)
    lm = LM(cfg, remat="none")
    params, _ = lm.init(RNG)
    B, S_max = 2, 8
    caches = lm.init_caches(B, S_max)
    batch = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.frontend == "audio_frames":
        batch["frames"] = jax.random.normal(RNG, (B, 1, cfg.d_model),
                                            jnp.bfloat16)
    else:
        batch["tokens"] = jnp.zeros((B, 1), jnp.int32)
    if cfg.frontend == "vision":
        batch["img_embeds"] = jax.random.normal(
            RNG, (B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
    logits, new_caches = jax.jit(lm.decode_step)(params, batch, caches)
    assert logits.shape == (B, 1, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    assert jax.tree.structure(new_caches) == jax.tree.structure(caches)


# --------------------------------------------------------------------------
# Named scopes reach the optimised HLO (what trace readers join on)
# --------------------------------------------------------------------------

def _hlo_scopes(compiled) -> set[str]:
    """The :data:`SCOPES` among the ``op_name`` path components of an
    optimised HLO, ``jvp(``/``transpose(`` wrappers removed."""
    from repro.models.lm import SCOPES
    parts = set()
    for op in re.findall(r'op_name="([^"]*)"', compiled.as_text()):
        parts |= {re.sub(r"^(?:\w+\()+|\)+$", "", p) for p in op.split("/")}
    return parts & set(SCOPES)


@pytest.mark.parametrize("arch", list_archs())
def test_decode_hlo_names_each_layer(arch):
    """The gated decode step's optimised HLO carries a scope for each
    mixer kind of the config (``attn`` for cross-attention and MLA too),
    the FFN, the layer scan, the cache gate and the head."""
    cfg = get_config(arch, smoke=True)
    lm = LM(cfg, remat="none")
    params, _ = lm.init(RNG)
    B = 2
    batch = {"pos": jnp.zeros((B,), jnp.int32),
             "active": jnp.ones((B,), bool)}
    if cfg.frontend == "audio_frames":
        batch["frames"] = jnp.zeros((B, 1, cfg.d_model), jnp.bfloat16)
    else:
        batch["tokens"] = jnp.zeros((B, 1), jnp.int32)
    if cfg.frontend == "vision":
        batch["img_embeds"] = jnp.zeros((B, cfg.n_img_tokens, cfg.d_model),
                                        jnp.bfloat16)
    caches = lm.init_caches(B, 8, vector_pos=True)
    found = _hlo_scopes(jax.jit(lm.decode_step).lower(
        params, batch, caches).compile())
    kinds = cfg.layer_kinds()
    want = {"attn" if mix == "xattn" else mix for mix, _ in kinds}
    want |= {"cache_gate", "head"}
    if any(repeats > 1 for _, repeats in cfg.layer_groups()):
        want.add("layer_scan")
    if any(ffn != "none" for _, ffn in kinds):
        want.add("ffn")
    if cfg.frontend != "audio_frames":
        want.add("embed")
    assert want <= found, want - found


def test_train_hlo_names_each_layer():
    """The full-remat train step's optimised HLO carries the layer scopes
    through the layer scan, the recompute and the backward pass, and the
    loss's and the optimizer's."""
    from repro.optim import AdamW
    cfg = get_config("smollm-135m", smoke=True)
    lm = LM(cfg, remat="full")
    opt = AdamW()
    params, _ = lm.init(RNG)

    def step(p, s, b):
        (loss, _), g = jax.value_and_grad(lm.loss_fn, has_aux=True)(p, b)
        return opt.update(g, s, p), loss

    text = jax.jit(step).lower(params, opt.init(params),
                               _batch_for(cfg, 2, 16)).compile()
    assert {"embed", "attn", "ffn", "layer_scan", "head", "loss",
            "optimizer"} <= _hlo_scopes(text)
    ops = re.findall(r'op_name="([^"]*)"', text.as_text())
    assert any("transpose(" in op and "/attn/" in op for op in ops)


# --------------------------------------------------------------------------
# Decode ≡ teacher-forced forward, per mixer family
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", [
    "smollm-135m",        # GQA + RoPE
    "h2o-danube-3-4b",    # sliding window
    "stablelm-3b",        # MHA + partial rotary + LN
    "deepseek-v2-236b",   # MLA absorbed decode
    "jamba-v0.1-52b",     # Mamba state + attention interleave + MoE
    "xlstm-125m",         # mLSTM/sLSTM states
    "musicgen-large",     # audio frontend
])
@pytest.mark.slow
def test_decode_matches_parallel(arch):
    cfg = get_config(arch, smoke=True)
    lm = LM(cfg, remat="none")
    params, _ = lm.init(RNG)
    B, S = 2, 12
    batch = _batch_for(cfg, B, S)
    full = np.asarray(jax.jit(lm.logits_fn)(params, batch), np.float32)

    caches = lm.init_caches(B, S)
    step = jax.jit(lm.decode_step)
    outs = []
    for t in range(S):
        sb = {"pos": jnp.asarray(t, jnp.int32)}
        if cfg.frontend == "audio_frames":
            sb["frames"] = batch["frames"][:, t:t + 1]
        else:
            sb["tokens"] = batch["tokens"][:, t:t + 1]
        if cfg.frontend == "vision":
            sb["img_embeds"] = batch["img_embeds"]
        logits, caches = step(params, sb, caches)
        outs.append(np.asarray(logits[:, 0], np.float32))
    stepped = np.stack(outs, axis=1)
    # bf16 params + different reduction orders → loose numeric tolerance,
    # but structural bugs (position off-by-one) blow way past this.
    np.testing.assert_allclose(stepped, full, atol=0.25, rtol=0.1)
    agree = np.mean(stepped.argmax(-1) == full.argmax(-1))
    assert agree > 0.9


# --------------------------------------------------------------------------
# Sequence-mixer oracles
# --------------------------------------------------------------------------

def test_selective_scan_chunked_matches_seq():
    from repro.models.ssm import selective_scan_chunked
    rng = np.random.default_rng(5)
    B, S, D, N = 2, 96, 8, 4
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, S, D)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(D, N)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    y1 = selective_scan_chunked(x, dt, A, Bm, Cm, chunk=16)
    y2, _ = selective_scan_seq(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


def test_selective_scan_assoc_matches_seq():
    rng = np.random.default_rng(0)
    B, S, D, N = 2, 33, 8, 4
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, S, D)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(D, N)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    y1 = selective_scan_assoc(x, dt, A, Bm, Cm)
    y2, _ = selective_scan_seq(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


def test_mlstm_parallel_matches_steps():
    rng = np.random.default_rng(1)
    B, S, H, Dh = 2, 9, 2, 4
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    i_pre = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    f_pre = jnp.asarray(rng.normal(size=(B, S, H)) + 2.0, jnp.float32)
    y_par = np.asarray(_mlstm_parallel(q, k, v, i_pre, f_pre))

    # Step-by-step matrix-memory recurrence (the decode form).
    C = np.zeros((B, H, Dh, Dh), np.float32)
    n = np.zeros((B, H, Dh), np.float32)
    m = np.full((B, H), -np.inf, np.float32)
    ys = []
    qn, kn, vn = map(np.asarray, (q, k, v))
    for t in range(S):
        logf = np.asarray(jax.nn.log_sigmoid(f_pre[:, t]))
        it = np.asarray(i_pre[:, t])
        m_new = np.maximum(logf + m, it)
        fg = np.exp(logf + m - m_new)
        ig = np.exp(it - m_new)
        kt = kn[:, t] / np.sqrt(Dh)
        C = fg[..., None, None] * C + ig[..., None, None] * (
            kt[..., :, None] * vn[:, t][..., None, :])
        n = fg[..., None] * n + ig[..., None] * kt
        num = np.einsum("bhd,bhde->bhe", qn[:, t], C)
        den = np.maximum(np.abs(np.einsum("bhd,bhd->bh", qn[:, t], n)),
                         np.exp(-m_new))[..., None]
        ys.append(num / (den + 1e-6))
        m = m_new
    y_step = np.stack(ys, axis=1)
    np.testing.assert_allclose(y_par, y_step, rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# MoE
# --------------------------------------------------------------------------

def test_moe_dispatch_indices_invariants():
    from repro.models.moe import dispatch_indices
    rng = np.random.default_rng(2)
    T, K, E, cap = 64, 2, 8, 24
    idx = jnp.asarray(rng.integers(0, E, size=(T, K)))
    eid, slot, keep = dispatch_indices(idx, E, cap)
    eid, slot, keep = map(np.asarray, (eid, slot, keep))
    assert (slot[keep] < cap).all()
    # No two kept assignments share (expert, slot).
    pairs = set()
    for e, s, k in zip(eid, slot, keep):
        if k:
            assert (e, s) not in pairs
            pairs.add((e, s))
    # Per-expert kept counts == min(assigned, capacity).
    for e in range(E):
        assigned = int((eid == e).sum())
        kept = int(((eid == e) & keep).sum())
        assert kept == min(assigned, cap)


@pytest.mark.slow
def test_moe_matches_dense_reference_when_no_drop():
    """With capacity ≥ T·K the sort-based dispatch must equal the
    brute-force dense (every-expert) weighted combination."""
    from repro.models.moe import moe_ffn, router_topk
    from repro.models.layers import ParamBuilder
    cfg = get_config("deepseek-v2-236b", smoke=True)
    cfg = type(cfg)(**{**cfg.__dict__,
                       "moe": type(cfg.moe)(
                           n_experts=4, top_k=2, n_shared=0, d_expert=16,
                           capacity_factor=8.0)})
    pb = ParamBuilder(RNG)
    from repro.models.moe import init_moe
    init_moe(pb, "m", cfg)
    p = pb.params["m"]
    B, S = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    out, aux = moe_ffn(x, p, cfg, lambda t, d, s=None: t)
    assert float(aux.dropped_fraction) == 0.0

    xt = x.reshape(-1, cfg.d_model)
    gate, idx, _ = router_topk(xt, p["w_router"], cfg.moe)
    ref = np.zeros((xt.shape[0], cfg.d_model), np.float32)
    for e in range(cfg.moe.n_experts):
        h = np.einsum("td,dgf->tgf", np.asarray(xt, np.float32),
                      np.asarray(p["w_in"][e], np.float32))
        act = np.asarray(jax.nn.silu(h[..., 0, :])) * h[..., 1, :]
        oe = act @ np.asarray(p["w_out"][e], np.float32)
        w = np.zeros(xt.shape[0], np.float32)
        for kk in range(cfg.moe.top_k):
            w += np.where(np.asarray(idx[:, kk]) == e,
                          np.asarray(gate[:, kk], np.float32), 0)
        ref += w[:, None] * oe
    got = np.asarray(out.reshape(-1, cfg.d_model), np.float32)
    # bf16 expert compute vs f32 reference: tolerance scaled to the O(30)
    # output magnitude.
    np.testing.assert_allclose(got, ref, rtol=0.1, atol=0.25)


def test_cross_entropy_matches_naive():
    from repro.models.layers import cross_entropy
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.normal(size=(4, 8, 32)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 32, size=(4, 8)))
    got = float(cross_entropy(logits, labels, z_loss=0.0))
    p = jax.nn.log_softmax(logits, axis=-1)
    ref = -float(jnp.mean(jnp.take_along_axis(
        p, labels[..., None], axis=-1)))
    assert abs(got - ref) < 1e-5
